"""Inference engine: bucketed batching + bounded compiled-executable cache.

Ref role: `libnd4j/server/GraphServer.cpp` caches the compiled graph
across requests; TensorFlow Serving's BatchingSession pads requests to
allowed batch sizes so one compiled program serves many request shapes.

TPU-native shape: every novel input shape costs an XLA compile, so the
engine pads each request batch up to the next power-of-two BUCKET and
keeps a bounded LRU of ahead-of-time compiled executables keyed by
(bucket, row signature, outputs). Steady-state traffic therefore runs
entirely out of the cache; `warmup(buckets=...)` pre-compiles the hot
buckets before the server takes traffic.
"""
from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from ..profiler import OpProfiler
from .metrics import ServingMetrics


# -- process-level XLA executable memo ---------------------------------
# ``jax.jit(fn).lower(...).compile()`` bypasses jax's jit cache (every
# engine builds fresh closures), so two engines serving the same
# architecture at the same shapes each pay the full XLA compile — which
# dominates multi-engine processes (replica-per-model servers, test
# suites). The memo is keyed by the lowered program's own text:
# identical HLO is identical compute, so there is no config
# fingerprint to get wrong. Backend and donation spec are in the key
# because they live in compile options, not (reliably) in the text.
# Tracing/lowering still runs per engine (cheap); only the XLA compile
# is shared. Executables are stateless and reentrant, so cross-engine
# sharing — donated buffers included — is safe.
_EXE_MEMO: "OrderedDict[Tuple, Any]" = OrderedDict()
_EXE_MEMO_LOCK = threading.Lock()
_EXE_MEMO_CAP = 64


def compile_memoized(fn, args, donate_argnums=()):
    """``jit(fn, donate).lower(*args).compile()`` with a bounded
    process-level LRU keyed by (backend, donation, sha256(HLO))."""
    donate = tuple(donate_argnums)
    lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
    key = (jax.default_backend(), donate,
           hashlib.sha256(lowered.as_text().encode()).hexdigest())
    with _EXE_MEMO_LOCK:
        exe = _EXE_MEMO.get(key)
        if exe is not None:
            _EXE_MEMO.move_to_end(key)
            return exe
    exe = lowered.compile()
    with _EXE_MEMO_LOCK:
        prior = _EXE_MEMO.get(key)
        if prior is not None:
            return prior          # lost a benign compile race
        _EXE_MEMO[key] = exe
        while len(_EXE_MEMO) > _EXE_MEMO_CAP:
            _EXE_MEMO.popitem(last=False)
    return exe


class ServingError(RuntimeError):
    """Base class for serving-layer failures (maps to HTTP 5xx)."""


class ClientError(ValueError):
    """Malformed request — the caller's fault (maps to HTTP 400)."""


def next_bucket(n: int, min_bucket: int = 1, max_bucket: int = 1 << 30) -> int:
    """Smallest power-of-two >= n, clamped to [min_bucket, max_bucket]."""
    if n <= 0:
        raise ClientError("empty batch")
    b = max(int(min_bucket), 1)
    while b < n:
        b <<= 1
    return min(b, int(max_bucket))


def _pad_rows(a: np.ndarray, bucket: int) -> np.ndarray:
    n = a.shape[0]
    if n == bucket:
        return a
    pad = np.zeros((bucket - n,) + a.shape[1:], a.dtype)
    return np.concatenate([a, pad], axis=0)


class InferenceEngine:
    """Wraps any model exposing ``output(...)`` behind a bucketed,
    compile-cached forward pass.

    Supported natively (params passed as executable arguments, so the
    weights are NOT baked into each compiled program):
    - :class:`~deeplearning4j_tpu.nn.MultiLayerNetwork`
    - :class:`~deeplearning4j_tpu.nn.graph.ComputationGraph`
    - :class:`~deeplearning4j_tpu.autodiff.SameDiff` (named feeds;
      ``default_outputs`` or per-request ``outputs`` select heads)

    Anything else with an ``output(x)`` method falls back to calling it
    per batch (still bucket-padded, so the model's own jit cache keys
    stay bounded), without the AOT executable cache.
    """

    def __init__(self, model, default_outputs: Optional[Sequence[str]] = None,
                 max_batch_size: int = 64, min_bucket: int = 1,
                 cache_size: int = 16,
                 metrics: Optional[ServingMetrics] = None,
                 fault_injector=None):
        self.model = model
        # a faults.FaultInjector (or None — the default; the
        # hot path then pays exactly one attribute load per call)
        self._faults = fault_injector
        self.default_outputs = list(default_outputs or [])
        self.max_batch_size = int(max_batch_size)
        self.min_bucket = int(min_bucket)
        self.metrics = metrics or ServingMetrics()
        self._cache: "OrderedDict[tuple, Any]" = OrderedDict()
        self._cache_size = max(1, int(cache_size))
        self._lock = threading.Lock()
        self._compiling: Dict[tuple, threading.Event] = {}
        self._profiler = OpProfiler.get_instance()
        self._kind, self._fn_for = self._adapt(model)

    # -- model adapters ------------------------------------------------
    def _adapt(self, model):
        """Returns (kind, fn_for(outputs) -> f(state, inputs)). Weights
        flow through ``state`` (see :meth:`_state_for`), never as
        closure constants, so executables serve the model's LIVE
        parameters — a fit() or checkpoint restore after registration
        is picked up on the next request."""
        from ..autodiff.samediff import SameDiff
        if isinstance(model, SameDiff):
            def fn_for(outputs):
                if not outputs:
                    raise ClientError("SameDiff serving needs 'outputs'")
                gfn = model._build(tuple(outputs))
                needed = set(gfn.needed)

                def f(state, feed):
                    vals = {k: v for k, v in {**state[0], **feed}.items()
                            if k in needed}
                    return gfn(vals, state[1])
                f.needed = gfn.needed
                return f
            return "samediff", fn_for
        cls = type(model).__name__
        if hasattr(model, "_forward") and hasattr(model, "conf") and \
                hasattr(model.conf, "graph_inputs"):
            if getattr(model, "_params", None) is None:
                model.init()

            def fn_for(outputs):
                def f(state, inputs):
                    acts, _ = model._forward(state[0], state[1], inputs,
                                             False, None)
                    return [acts[n]
                            for n in (outputs or model.conf.graph_outputs)]
                return f
            return "graph", fn_for
        if hasattr(model, "_forward") and hasattr(model, "_reshape_input"):
            if getattr(model, "_params", None) is None:
                model.init()

            def fn_for(outputs):
                def f(state, x):
                    act, _, _ = model._forward(state[0], state[1],
                                               model._reshape_input(x),
                                               False, None)
                    return act
                return f
            return "mln", fn_for
        if not hasattr(model, "output"):
            raise ServingError(
                f"{cls} has no output(...) method — cannot serve it")
        return "duck", None

    def _state_for(self, fn):
        """Executable arguments holding the weights, read LIVE from the
        model at every call (SameDiff resolves per output-head: only
        the values that head needs)."""
        if self._kind != "samediff":
            return (self.model._params, self.model._net_state)
        from ..autodiff.samediff import VariableType
        model = self.model
        vals = {k: v for k, v in model._values.items()
                if k in set(fn.needed)
                and model._vars[k].vtype != VariableType.PLACEHOLDER}
        return (vals, jax.random.PRNGKey(model.seed))

    # -- request normalization -----------------------------------------
    def normalize(self, inputs, outputs=None):
        """Parse a request payload into (feed, n_rows, signature).

        Arrays for MLN/ComputationGraph-style models; name->array dicts
        for SameDiff / multi-input graphs. Raises :class:`ClientError`
        on malformed payloads."""
        outs = tuple(outputs or self.default_outputs)
        if self._kind == "samediff":
            if not isinstance(inputs, dict):
                raise ClientError(
                    "SameDiff serving takes {'inputs': {name: array}}")
            if not outs:
                raise ClientError("SameDiff serving needs 'outputs'")
            from ..autodiff.samediff import VariableType
            unknown = [o for o in outs if o not in self.model._vars]
            if unknown:
                raise ClientError(f"unknown outputs {unknown}")
            feed = {}
            for k, v in inputs.items():
                var = self.model._vars.get(k)
                if var is None:
                    raise ClientError(f"unknown input {k!r}")
                dtype = getattr(var, "dtype", None) or np.float32
                try:
                    feed[k] = np.asarray(v, dtype)
                except (TypeError, ValueError) as e:
                    raise ClientError(f"input {k!r} is not a tensor: {e}")
            if not feed:
                raise ClientError("empty inputs")
            for k, a in feed.items():
                if a.ndim == 0:
                    raise ClientError(
                        f"input {k!r} must be at least 1-D (a batch)")
            fn = self.model._build(outs)
            missing = [nm for nm in fn.needed if nm not in feed
                       and self.model._vars[nm].vtype
                       == VariableType.PLACEHOLDER]
            if missing:
                raise ClientError(f"missing inputs for placeholders "
                                  f"{missing}")
            ns = {a.shape[0] for a in feed.values()}
            if len(ns) != 1:
                raise ClientError(f"inconsistent batch sizes: {sorted(ns)}")
            n = ns.pop()
            sig = ("sd", outs, tuple(sorted(
                (k, a.shape[1:], str(a.dtype)) for k, a in feed.items())))
            return feed, n, sig
        if self._kind == "graph" and outs:
            unknown = [o for o in outs
                       if o not in self.model.conf.graph_outputs]
            if unknown:
                raise ClientError(
                    f"unknown outputs {unknown} (graph outputs: "
                    f"{self.model.conf.graph_outputs})")
        elif outs and list(outs) != list(self.default_outputs):
            # MLN/duck models have one unnamed output head; silently
            # returning it under the client's requested name would be
            # a lie
            raise ClientError(
                "this model has a single unnamed output — omit 'outputs'")
        if isinstance(inputs, dict):
            if self._kind != "graph":
                raise ClientError("this model takes a plain array input")
            feed = {}
            for k, v in inputs.items():
                if k not in self.model.conf.graph_inputs:
                    raise ClientError(f"unknown input {k!r} (graph inputs: "
                                      f"{self.model.conf.graph_inputs})")
                try:
                    feed[k] = np.asarray(v, np.float32)
                except (TypeError, ValueError) as e:
                    raise ClientError(f"input {k!r} is not a tensor: {e}")
            if set(feed) != set(self.model.conf.graph_inputs):
                raise ClientError(
                    f"graph needs inputs {self.model.conf.graph_inputs}")
            for k, a in feed.items():
                if a.ndim == 0:
                    raise ClientError(
                        f"input {k!r} must be at least 1-D (a batch)")
            ns = {a.shape[0] for a in feed.values()}
            if len(ns) != 1:
                raise ClientError(f"inconsistent batch sizes: {sorted(ns)}")
            n = ns.pop()
            sig = ("graph", outs, tuple(sorted(
                (k, a.shape[1:]) for k, a in feed.items())))
            return feed, n, sig
        try:
            x = np.asarray(inputs, np.float32)
        except (TypeError, ValueError) as e:
            raise ClientError(f"inputs is not a tensor: {e}")
        if x.ndim == 0:
            raise ClientError("inputs must be at least 1-D (a batch)")
        if self._kind == "graph":
            gin = self.model.conf.graph_inputs
            if len(gin) > 1:
                raise ClientError(
                    "multi-input graph needs {'inputs': {name: array}}")
            feed = {gin[0]: x}
            return feed, x.shape[0], ("graph", outs,
                                      ((gin[0], x.shape[1:]),))
        return x, x.shape[0], (self._kind, outs, x.shape[1:])

    # -- compile cache -------------------------------------------------
    def _compiled(self, sig, bucket, feed):
        key = (sig, bucket)
        while True:
            with self._lock:
                hit = self._cache.get(key)
                if hit is not None:
                    self._cache.move_to_end(key)
                    self.metrics.cache_hits += 1
                    return hit
                ev = self._compiling.get(key)
                if ev is None:
                    # claim the compile; do it OUTSIDE the lock so
                    # cache hits for other buckets never wait on a
                    # multi-second XLA compile
                    ev = threading.Event()
                    self._compiling[key] = ev
                    self.metrics.cache_misses += 1
                    break
            ev.wait()  # another thread is compiling this key — reuse it
        try:
            fn = self._fn_for(sig[1])
            state = self._state_for(fn)
            with self._profiler.record("serving.compile"):
                exe = compile_memoized(fn, (state, feed))
            with self._lock:
                self.metrics.compiles += 1
                # cache the executable WITH its fn: weights are re-read
                # live via _state_for at every call, never frozen in
                self._cache[key] = (exe, fn)
                while len(self._cache) > self._cache_size:
                    self._cache.popitem(last=False)
                    self.metrics.cache_evictions += 1
                return self._cache[key]
        finally:
            with self._lock:
                self._compiling.pop(key, None)
            ev.set()

    def warmup(self, buckets: Sequence[int], example=None,
               outputs: Optional[Sequence[str]] = None):
        """Pre-compile executables for the given batch buckets so the
        server never compiles under traffic. ``example`` is one request
        payload (any batch size — row 0 is replicated); SameDiff models
        with fully-known placeholder shapes can omit it."""
        if example is None:
            example = self._infer_example(outputs)
        feed, _, sig = self.normalize(example, outputs)
        warmed = []
        for b in sorted(set(int(x) for x in buckets)):
            if b < 1 or b > self.max_batch_size:
                raise ValueError(f"bucket {b} outside [1, max_batch_size="
                                 f"{self.max_batch_size}]")
            padded = (jax.tree_util.tree_map(lambda a: _pad_rows(a[:1], b),
                                             feed)
                      if isinstance(feed, dict) else _pad_rows(feed[:1], b))
            self._compiled(sig, b, padded)
            warmed.append(b)
        self.metrics.warmed_buckets = sorted(
            set(self.metrics.warmed_buckets) | set(warmed))
        return warmed

    def _infer_example(self, outputs):
        if self._kind == "samediff":
            from ..autodiff.samediff import VariableType
            outs = tuple(outputs or self.default_outputs)
            fn = self._fn_for(outs)
            feed = {}
            for nm in fn.needed:
                var = self.model._vars[nm]
                if var.vtype != VariableType.PLACEHOLDER:
                    continue
                shape = var.shape
                if shape is None or any(d is None for d in shape[1:]):
                    raise ValueError(
                        f"placeholder {nm!r} has unknown non-batch dims — "
                        "pass example= to warmup()")
                feed[nm] = np.zeros((1,) + tuple(shape[1:]),
                                    var.dtype or np.float32)
            return feed
        shape = getattr(self.model, "_input_shape", None)
        kind = getattr(self.model, "_input_kind", None)
        if shape:
            if kind == "cnnflat":
                h, w, c = shape
                return np.zeros((1, h * w * c), np.float32)
            return np.zeros((1,) + tuple(shape), np.float32)
        raise ValueError("cannot infer the input shape for this model — "
                         "pass example= to warmup()")

    # -- execution -----------------------------------------------------
    def predict(self, inputs, outputs: Optional[Sequence[str]] = None,
                trace=None):
        """Run one (possibly multi-request) batch. Batches larger than
        ``max_batch_size`` are chunked. Returns numpy results shaped
        like the model's own ``output(...)``. ``trace`` (a
        :class:`~..tracing.Trace`, or ``None``) records the device call
        as one retroactive span — the unbatched direct path's analog of
        the batcher's per-request device span."""
        feed, n, sig = self.normalize(inputs, outputs)
        if trace is None:
            return self.predict_normalized(feed, n, sig)
        t0 = time.perf_counter()
        res = self.predict_normalized(feed, n, sig)
        trace.span("device", t_start=t0, t_end=time.perf_counter(),
                   rows=n, bucket=next_bucket(
                       min(n, self.max_batch_size), self.min_bucket,
                       self.max_batch_size))
        return res

    def predict_normalized(self, feed, n, sig):
        """Hot-path entry for callers that already hold a normalized
        (feed, n_rows, signature) triple — the batcher's device call
        goes through here so the scheduler thread never re-validates
        rows every submit() already validated."""
        if n > self.max_batch_size:
            parts = []
            for i in range(0, n, self.max_batch_size):
                part = _slice(feed, i, i + self.max_batch_size)
                parts.append(self.predict_normalized(
                    part, min(self.max_batch_size, n - i), sig))
            return _concat_results(parts)
        bucket = next_bucket(n, self.min_bucket, self.max_batch_size)
        self.metrics.bucket_hist.record(bucket)
        padded = (jax.tree_util.tree_map(lambda a: _pad_rows(a, bucket), feed)
                  if isinstance(feed, dict) else _pad_rows(feed, bucket))
        if self._faults is not None:
            # injection seam: fires BEFORE the device call, so a
            # transient fault leaves no partial state and the batcher
            # above can retry the whole call
            self._faults.fire("device_step")
        if self._kind == "duck":
            # fallback: the model's own output() (its internal jit cache
            # still benefits from the bounded bucket shapes)
            with self._profiler.record("serving.device_call"):
                res = self.model.output(padded)
            return _trim(res, n, bucket, sig[1])
        exe, fn = self._compiled(sig, bucket, padded)
        with self._profiler.record("serving.device_call"):
            res = exe(self._state_for(fn), padded)
        return _trim(res, n, bucket, sig[1])


def _slice(tree, lo, hi):
    """Row-slice a feed or result (dict / list-of-heads / array)."""
    if isinstance(tree, dict):
        return {k: v[lo:hi] for k, v in tree.items()}
    if isinstance(tree, list):
        return [v[lo:hi] for v in tree]
    return tree[lo:hi]


def _row_aligned(v, bucket):
    """Padding and coalescing are only sound for outputs with one row
    per input row. A batch-REDUCING head (e.g. a mean over the batch)
    would silently fold the zero padding rows — and other requests'
    rows — into every answer, so fail loudly instead."""
    a = np.asarray(v)
    if a.ndim == 0 or a.shape[0] != bucket:
        raise ServingError(
            f"model output shape {a.shape} is not row-aligned with the "
            f"batch (expected leading dim {bucket}); batch-reducing "
            "outputs cannot be served through the dynamic batcher — "
            "compute them client-side or serve via model.output directly")
    return a


def _trim(res, n, bucket, outs):
    """Strip padding rows and convert to numpy."""
    if isinstance(res, dict):
        return {k: _row_aligned(v, bucket)[:n] for k, v in res.items()}
    if isinstance(res, (list, tuple)):
        trimmed = [_row_aligned(v, bucket)[:n] for v in res]
        if outs and len(outs) == len(trimmed):
            return dict(zip(outs, trimmed))
        return trimmed[0] if len(trimmed) == 1 else trimmed
    return _row_aligned(res, bucket)[:n]


def _concat_results(parts):
    first = parts[0]
    if isinstance(first, dict):
        return {k: np.concatenate([p[k] for p in parts]) for k in first}
    if isinstance(first, list):
        return [np.concatenate([p[i] for p in parts])
                for i in range(len(first))]
    return np.concatenate(parts)
