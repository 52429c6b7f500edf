"""Profiling / numeric sanity (ref: J10 —
`linalg/profiler/{OpProfiler,ProfilerConfig}.java`, ProfilingMode enum at
`executioner/OpExecutioner.java:53-63` {DISABLED, NAN_PANIC, INF_PANIC,
ANY_PANIC, OPERATIONS, METHODS, ALL, SCOPE_PANIC, BANDWIDTH}, native
profiling structs `include/graph/profiling/`).

TPU-native shape: per-op timing dissolves under XLA fusion (there are no
per-op kernels to time), so the profiler times named SECTIONS (step,
epoch, forward…) and wraps `jax.profiler` for the real device trace
(xplane). The NaN/Inf panic modes survive intact as pytree checks —
the jax.debug/checkify-era equivalent of the reference's per-op panics.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict, deque
from enum import Enum
from typing import Any, Dict, Optional

import jax
import numpy as np


class ProfilingMode(Enum):
    """Ref: OpExecutioner.ProfilingMode :53-63."""
    DISABLED = "disabled"
    NAN_PANIC = "nan_panic"
    INF_PANIC = "inf_panic"
    ANY_PANIC = "any_panic"
    OPERATIONS = "operations"
    SCOPE_PANIC = "scope_panic"
    ALL = "all"


class ND4JOpProfilerException(RuntimeError):
    """Ref: the exception OpProfiler's panic modes raise."""


def check_for_nan(tree, label: str = "array"):
    """Ref: OpProfiler NAN_PANIC hook."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        if np.issubdtype(a.dtype, np.floating) and np.isnan(a).any():
            raise ND4JOpProfilerException(
                f"NaN detected in {label}{jax.tree_util.keystr(path)}")


def check_for_inf(tree, label: str = "array"):
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        if np.issubdtype(a.dtype, np.floating) and np.isinf(a).any():
            raise ND4JOpProfilerException(
                f"Inf detected in {label}{jax.tree_util.keystr(path)}")


class OpProfiler:
    """Section timing + panic checks (ref: OpProfiler singleton —
    getInstance, timing aggregation per op name, reset, printOutDashboard
    -> print_report)."""

    _instance: Optional["OpProfiler"] = None

    def __init__(self):
        self.mode = ProfilingMode.DISABLED
        self._totals: Dict[str, float] = defaultdict(float)
        self._counts: Dict[str, int] = defaultdict(int)
        # serving records sections from many threads; unlocked '+=' on
        # the shared dicts would lose updates under preemption
        self._rec_lock = threading.Lock()

    @classmethod
    def get_instance(cls) -> "OpProfiler":
        if cls._instance is None:
            cls._instance = OpProfiler()
        return cls._instance

    def set_mode(self, mode: ProfilingMode):
        self.mode = mode

    @contextlib.contextmanager
    def record(self, name: str):
        """Time a named section on the host's clock (ref: processOpCall
        timing path). It waits for nothing: a section that only
        enqueues device work is timed as the enqueue, so a site that
        means the device's time has to fetch a result inside the
        section."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if self.mode in (ProfilingMode.OPERATIONS, ProfilingMode.ALL):
                dt = time.perf_counter() - t0
                with self._rec_lock:
                    self._totals[name] += dt
                    self._counts[name] += 1

    def note(self, name: str, dt_s: float):
        """Record an externally-measured duration into a section. The
        pipelined decode loop measures dispatch->sync spans that START
        in one loop iteration and END in the next — no lexical scope a
        ``with record()`` block could wrap — so the scheduler times the
        span itself and deposits it here. Same mode gate and lock as
        :meth:`record`."""
        if self.mode in (ProfilingMode.OPERATIONS, ProfilingMode.ALL):
            with self._rec_lock:
                self._totals[name] += dt_s
                self._counts[name] += 1

    def check(self, tree, label: str = "array"):
        """Apply the active panic mode to a pytree of arrays."""
        if self.mode in (ProfilingMode.NAN_PANIC, ProfilingMode.ANY_PANIC,
                         ProfilingMode.ALL):
            check_for_nan(tree, label)
        if self.mode in (ProfilingMode.INF_PANIC, ProfilingMode.ANY_PANIC,
                         ProfilingMode.ALL):
            check_for_inf(tree, label)

    def timings(self) -> Dict[str, Dict[str, float]]:
        with self._rec_lock:  # record() inserts from serving threads
            items = [(n, self._totals[n], self._counts[n])
                     for n in self._totals]
        return {name: {"total_s": total,
                       "count": count,
                       "mean_s": total / max(1, count)}
                for name, total, count in items}

    def reset(self):
        self._totals.clear()
        self._counts.clear()

    def print_report(self):
        for name, t in sorted(self.timings().items(),
                              key=lambda kv: -kv[1]["total_s"]):
            print(f"{name:<32} {t['count']:>8} calls "
                  f"{t['total_s'] * 1e3:>10.2f} ms total "
                  f"{t['mean_s'] * 1e6:>10.1f} us/call")


class Counter:
    """Thread-safe monotonically-increasing event counter. The
    resilient-training supervisor bumps these from the step loop while
    tests/listeners read them concurrently; an unlocked ``+=`` would
    lose increments under preemption (same rationale as OpProfiler's
    record lock)."""

    def __init__(self):
        self._v = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> int:
        with self._lock:
            self._v += int(n)
            return self._v

    def value(self) -> int:
        with self._lock:
            return self._v


#: the exact key set of :meth:`Reservoir.snapshot` — consumers that
#: re-render snapshots (the Prometheus exposition in serving/metrics.py,
#: tools/trace_report.py dumps) detect reservoir-shaped summary dicts by
#: this signature, so it is defined once here rather than re-guessed
RESERVOIR_SNAPSHOT_KEYS = ("count", "mean", "p50", "p90", "p99", "max")


class Reservoir:
    """Bounded sample reservoir with percentile queries (ref role: the
    reference's PerformanceListener latency aggregation). Keeps the most
    recent ``size`` samples (ring buffer) — serving traffic wants the
    recent distribution, not the all-time one — and answers p50/p99 via
    a sorted copy on read. Thread-safe; record() is O(1)."""

    def __init__(self, size: int = 8192):
        self._size = int(size)
        self._buf = [0.0] * self._size
        self._n = 0          # total samples ever
        self._lock = threading.Lock()

    def record(self, value: float):
        with self._lock:
            self._buf[self._n % self._size] = float(value)
            self._n += 1

    def record_many(self, values):
        """Record a batch under ONE lock acquisition — the generation
        scheduler emits one sample per active slot per decode step, and
        per-sample locking would be measurable at step cadence."""
        with self._lock:
            for v in values:
                self._buf[self._n % self._size] = float(v)
                self._n += 1

    def count(self) -> int:
        return self._n

    def _samples(self):
        with self._lock:
            k = min(self._n, self._size)
            return sorted(self._buf[:k])

    @staticmethod
    def _nearest_rank(s, p: float) -> float:
        return s[min(len(s) - 1,
                     max(0, int(round(p / 100.0 * (len(s) - 1)))))]

    def percentile(self, p: float) -> float:
        """Nearest-rank percentile over the retained window (0 if empty)."""
        s = self._samples()
        return self._nearest_rank(s, p) if s else 0.0

    def snapshot(self) -> Dict[str, float]:
        s = self._samples()
        if not s:
            return dict.fromkeys(RESERVOIR_SNAPSHOT_KEYS, 0.0) | {
                "count": self._n}
        return {"count": self._n,
                "mean": float(sum(s) / len(s)),
                "p50": self._nearest_rank(s, 50),
                "p90": self._nearest_rank(s, 90),
                "p99": self._nearest_rank(s, 99),
                "max": s[-1]}


class RateMeter:
    """Sliding-window event-rate meter (tokens/sec, requests/sec).
    Keeps (timestamp, count) pairs inside ``window_s`` and reports
    events/sec over the observed span — the serving dashboards want
    the CURRENT rate, not the all-time mean. Thread-safe."""

    def __init__(self, window_s: float = 30.0):
        self._window = float(window_s)
        self._events: "deque[tuple]" = deque()
        self._total = 0
        self._lock = threading.Lock()

    def record(self, n: int = 1):
        now = time.perf_counter()
        with self._lock:
            self._events.append((now, int(n)))
            self._total += int(n)
            self._prune(now)

    def _prune(self, now: float):
        cutoff = now - self._window
        while self._events and self._events[0][0] < cutoff:
            self._events.popleft()

    def total(self) -> int:
        return self._total

    def rate(self) -> float:
        """Events/sec over the retained window (0 with <2 data points —
        a single burst has no measurable span)."""
        now = time.perf_counter()
        with self._lock:
            self._prune(now)
            if len(self._events) < 2:
                return 0.0
            span = now - self._events[0][0]
            if span <= 0:
                return 0.0
            return sum(n for _, n in self._events) / span


class CountHistogram:
    """Exact value->count histogram for small integer domains (batch
    sizes, bucket ids). Thread-safe."""

    def __init__(self):
        self._counts: Dict[int, int] = defaultdict(int)
        self._lock = threading.Lock()

    def record(self, value: int, weight: int = 1):
        with self._lock:
            self._counts[int(value)] += int(weight)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {str(k): v for k, v in sorted(self._counts.items())}

    def total(self) -> int:
        with self._lock:
            return sum(self._counts.values())

    def weighted_sum(self) -> int:
        with self._lock:
            return sum(k * v for k, v in self._counts.items())

    def mean(self) -> float:
        with self._lock:
            n = sum(self._counts.values())
            return (sum(k * v for k, v in self._counts.items()) / n
                    if n else 0.0)


@contextlib.contextmanager
def device_trace(log_dir: str):
    """XLA/TPU trace capture (xplane) — view in TensorBoard/XProf (ref
    role: the native-side profiling structs + SameDiff UI log)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class ProfilerListener:
    """TrainingListener applying panic checks to loss/params every
    iteration (the fit-loop integration point of the panic modes)."""

    def __init__(self, mode: ProfilingMode = ProfilingMode.NAN_PANIC,
                 check_params: bool = False):
        self.profiler = OpProfiler.get_instance()
        self.mode = mode
        self.check_params = check_params

    def iteration_done(self, model, iteration: int, epoch: int):
        prev = self.profiler.mode
        self.profiler.set_mode(self.mode)
        try:
            self.profiler.check(
                {"score": np.asarray(model.score_)}, "loss")
            if self.check_params:
                self.profiler.check(model._params, "params")
        finally:
            self.profiler.set_mode(prev)

    def on_epoch_end(self, model):
        pass


# ---------------------------------------------------------------------------
# SCOPE_PANIC-style workspace lifetime validation (ref: the reference's
# workspace validation — `DebugMode`/SCOPE_PANIC crash when an array
# allocated inside a closed workspace scope is touched afterwards
# (scope-panic message cited at `InferenceSession.java:39`; enums in
# `nd4j-buffer/.../memory/enums/DebugMode.java`). XLA owns buffer
# lifetimes on TPU, so the hazard this guards is the EAGER one: host
# code holding a reference to an array whose workspace scope (or
# donated buffer) is gone. The validator reproduces the crash-early
# contract without native scopes.)
# ---------------------------------------------------------------------------
class ScopePanicException(ND4JOpProfilerException):
    """Raised when a scope-tracked array is touched after its scope
    closed (ref: the SCOPE_PANIC workspace error)."""


class ScopedArray:
    """Proxy handing out the underlying array only while its scope is
    open. Unwraps via `.value`, `np.asarray(...)`, or jnp use (both go
    through __array__). Carries the scope GENERATION it was tracked in,
    so re-entering the same scope object does not resurrect arrays from
    a previous pass."""

    __slots__ = ("_arr", "_scope", "_gen")

    def __init__(self, arr, scope):
        self._arr = arr
        self._scope = scope
        self._gen = scope._gen

    def _check(self):
        if self._scope.closed or self._gen != self._scope._gen:
            mode = OpProfiler.get_instance().mode
            if mode in (ProfilingMode.SCOPE_PANIC, ProfilingMode.ALL):
                raise ScopePanicException(
                    f"array of shape {getattr(self._arr, 'shape', '?')} "
                    f"used after workspace scope "
                    f"'{self._scope.name}' closed (SCOPE_PANIC; ref "
                    "Nd4jWorkspace scope validation)")
        return self._arr

    @property
    def value(self):
        return self._check()

    def __array__(self, dtype=None, copy=None):
        import numpy as _np
        a = _np.asarray(self._check())
        return a.astype(dtype) if dtype is not None else a

    def __jax_array__(self):
        return self._check()

    @property
    def shape(self):
        return getattr(self._arr, "shape", None)

    @property
    def dtype(self):
        return getattr(self._arr, "dtype", None)

    def __repr__(self):
        state = "CLOSED" if self._scope.closed else "open"
        return f"ScopedArray(shape={self.shape}, scope={state})"


class WorkspaceScope:
    """Context manager mirroring `try (MemoryWorkspace ws =
    ws.notifyScopeEntered())` semantics: arrays `track()`ed inside are
    invalid after exit, and touching them raises under SCOPE_PANIC."""

    def __init__(self, name: str = "WS"):
        self.name = name
        self.closed = False
        self._gen = 0

    def track(self, arr) -> ScopedArray:
        if self.closed:
            raise ScopePanicException(
                f"cannot allocate in closed scope '{self.name}'")
        return ScopedArray(arr, self)

    def __enter__(self):
        self.closed = False
        self._gen += 1
        return self

    def __exit__(self, *exc):
        self.closed = True
        return False
