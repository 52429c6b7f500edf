"""Model serving + compiled-program export.

Ref: the reference's serving surface — `libnd4j/server/GraphServer.cpp`
(gRPC + FlatBuffers inference server that caches the compiled graph
across requests), the KNN REST server
(`deeplearning4j-nearestneighbor-server`), and datavec's
spark-inference REST endpoints (L7 inventory).

TPU-native shape — a real inference runtime, not one call per request:

- :class:`~.engine.InferenceEngine`: pads request batches into
  power-of-two buckets and keeps a bounded LRU of AOT-compiled
  executables per (bucket, signature), with `warmup()` so steady-state
  traffic never recompiles (the GraphServer compiled-graph cache,
  generalized across batch shapes).
- :class:`~.batcher.MicroBatcher`: coalesces concurrent requests into
  one device call under a max_batch_size / max_latency_ms policy, with
  per-request deadlines and a bounded queue that sheds load (503)
  instead of growing without limit (TF Serving BatchingSession /
  Clipper adaptive batching, PAPERS.md).
- :class:`~.registry.ModelRegistry`: named, versioned multi-model
  hosting, routed at ``/v1/models/<name>/predict``.
- :class:`InferenceServer`: the thin stdlib-HTTP front-end over
  registry + batcher. The legacy single-model constructor
  (``InferenceServer(model, port=0)``) still works and routes through
  the full runtime.
- :func:`export_stablehlo`: serialize a SameDiff (or any jittable
  fn+args) to StableHLO text — the portable compiled-graph artifact
  replacing the reference's FlatBuffers graph format (SURVEY.md §2.1).

HTTP surface::

    POST /predict                      default model
    POST /v1/models/<name>/predict     named model (latest version)
    POST /generate                     default generator
    POST /v1/models/<name>/generate    continuous-batching generation
                                       ({"stream": true} -> chunked
                                       newline-delimited JSON tokens;
                                       {"session_id": "..."} pins the
                                       turn's KV blocks for prefix
                                       reuse on the next turn — paged
                                       backend, docs/generation.md)
    GET  /v1/models                    registry listing
    GET  /stats                        serving metrics per model, plus
                                       a compact top-level "summary"
                                       (per-model live occupancy /
                                       queue depth / draining flag)
                                       for routers and load balancers
    GET  /metrics                      the same counters as Prometheus
                                       text exposition (scrapable)
    GET  /debug/traces                 bounded ring of recent / slow /
                                       errored request traces; filter
                                       with ?request_id=<id>
    GET  /health                       legacy summary (always 200)
    GET  /healthz                      liveness: 503 when any engine
                                       loop is wedged (stall watchdog)
    GET  /readyz                       readiness: 503 + Retry-After
                                       while draining

Observability (docs/observability.md): every request carries an
``X-Request-Id`` (accepted from the caller or minted here, echoed on
the response); with ``tracing=True`` — or per-request via ``?trace=1``
/ a ``"trace": 1`` body field, which also embeds the timeline in the
response — the request records admission / queue / prefill / decode
spans retained at ``/debug/traces``. ``log_requests=`` emits one
structured JSON access-log line per HTTP request.

Status codes: 400 malformed request (client), 404 unknown route/model,
500 internal failure (incl. quarantined poison requests), 503 load
shed (queue full) or draining — always with ``Retry-After``, 504
deadline exceeded.

Priority classes (docs/serving.md "Overload and admission control"):
every predict/generate request may carry ``"priority": "interactive"``
(default) or ``"batch"`` — as a JSON field or the ``X-Priority``
request header (the field wins when both are present). Under pressure
batch-class work is shed first (503) so interactive p99 holds, and
deadline-aware admission sheds requests whose budget is already blown
before they burn a device step.

Fault tolerance (:mod:`..faults`, docs/serving.md "Operating the
server"): supervised engine loops retry transient step faults with
bounded backoff and rebuild cache-corrupting failures by
recompute-recovery (no accepted request is ever lost); poison requests
(non-finite logits) are quarantined alone; ``drain()`` — wirable to
SIGTERM via :meth:`InferenceServer.install_signal_handlers` — flips
readiness off, finishes in-flight work, then joins the scheduler
threads. ``faults.{retries,recoveries,quarantined,drains}`` counters
surface per model at ``GET /stats``.

Fleet tier (:mod:`.fleet`, docs/serving.md "Running a fleet"): N
replicas of this server go behind a :class:`~.fleet.FleetRouter` —
occupancy-aware routing on the ``/stats`` summary, health-gated
membership via ``/healthz``/``/readyz``, straggler hedging under a
token-bucket retry budget, and :meth:`~.fleet.ReplicaFleet.
rolling_restart` extending the single-replica zero-loss drain
guarantee fleet-wide.

Generation (see :mod:`.generation`): causal LMs registered via
``register_generator`` decode token-by-token under iteration-level
scheduling against a static-shape KV cache — requests join and leave
the device batch every decode step, so short generations never wait
on long ones and the compiled executables never change shape. Two
cache backends: dense per-slot panels (``cache="slots"``) or the
paged block pool (``cache="paged"``, :mod:`.paging`) with
all-or-nothing block admission and chunked prefill, so memory scales
with ACTUAL sequence lengths and long prompts never stall the decode
loop for more than one chunk.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import threading
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np

from ..faults import (CorruptedStateFault, FaultInjector,
                      PoisonRequestError, TransientFault)
from ..tracing import Tracer
from .aio import AioReplicaFrontend
from .batcher import (DeadlineExceededError, DrainingError, MicroBatcher,
                      QueueFullError)
from .engine import ClientError, InferenceEngine, ServingError, next_bucket
from .fleet import (FleetError, FleetMetrics, FleetRouter,
                    NoReplicasError, Replica, ReplicaFleet)
from .generation import GenerationEngine
from .kvcache import KVCache, SlotTable
from .metrics import (GenerationMetrics, ServingMetrics,
                      profiler_sections, prometheus_text)
from .offload import DiskRing, HostBlockStore, HostRun
from .paging import BlockAllocator, BlockTable, PagedKVCache
from .registry import (ModelNotFound, ModelRegistry, ServedGenerator,
                       ServedModel)

__all__ = [
    "InferenceServer", "InferenceEngine", "MicroBatcher", "ModelRegistry",
    "ModelNotFound", "ServedModel", "ServedGenerator", "GenerationEngine",
    "GenerationMetrics", "KVCache", "SlotTable", "PagedKVCache",
    "BlockAllocator", "BlockTable", "ServingMetrics",
    "HostBlockStore", "HostRun", "DiskRing",
    "ClientError", "ServingError", "QueueFullError",
    "DeadlineExceededError", "DrainingError", "FaultInjector",
    "TransientFault", "CorruptedStateFault", "PoisonRequestError",
    "ReplicaFleet", "FleetRouter", "Replica", "FleetMetrics",
    "FleetError", "NoReplicasError",
    "next_bucket", "export_stablehlo", "Tracer", "prometheus_text",
]


def export_stablehlo(fn_or_samediff, example_args=None,
                     outputs: Optional[Sequence[str]] = None,
                     placeholders: Optional[Dict[str, Any]] = None) -> str:
    """StableHLO text for a jittable fn or a SameDiff graph.

    SameDiff: pass `outputs` (names) and `placeholders` (example arrays
    fixing shapes). Function: pass `example_args`.
    """
    from ..autodiff.samediff import SameDiff
    if isinstance(fn_or_samediff, SameDiff):
        sd = fn_or_samediff
        outs = tuple(outputs or sd._loss_variables)
        if not outs:
            raise ValueError("pass outputs= for SameDiff export")
        gfn = sd._build(outs)
        vals = sd._filter_values(sd._exec_values(placeholders or {}), gfn)
        rng = jax.random.PRNGKey(sd.seed)
        lowered = jax.jit(lambda v, r: gfn(v, r)).lower(vals, rng)
    else:
        lowered = jax.jit(fn_or_samediff).lower(*(example_args or ()))
    return lowered.as_text()


class InferenceServer:
    """HTTP JSON inference front-end over registry + batcher (ref role:
    GraphServer.cpp).

    Single-model (legacy, still supported)::

        server = InferenceServer(model, port=0)

    Multi-model::

        server = InferenceServer(port=0)
        server.register("mnist", model_a)
        server.register("ranker", model_b, default_outputs=["score"])

    ``host`` defaults to loopback; pass ``host="0.0.0.0"`` to bind
    externally for multi-host deployments.

    The listener (:mod:`.aio`, docs/serving.md "Front-end
    architecture") serves every connection off one event loop — open
    connections cost a socket buffer, not a thread, so thousands of
    idle keep-alive or streaming clients don't breed thousands of
    blocked threads — with engine-blocking work on a bounded daemon
    pool; a request head that does not complete within
    ``http_header_timeout_s`` is dropped (the slow-loris cap).
    """

    DEFAULT_MODEL = "default"

    def __init__(self, model=None, port: int = 0,
                 default_outputs: Optional[Sequence[str]] = None,
                 host: str = "127.0.0.1",
                 registry: Optional[ModelRegistry] = None,
                 batching: bool = True,
                 max_batch_size: int = 64,
                 max_latency_ms: float = 5.0,
                 max_queue: int = 256,
                 default_timeout_ms: float = 30_000.0,
                 warmup_buckets: Optional[Sequence[int]] = None,
                 warmup_example=None,
                 max_body_bytes: int = 256 * 1024 * 1024,
                 tracing: bool = False,
                 trace_ring: int = 256,
                 trace_slow_ms: float = 1000.0,
                 log_requests=False,
                 http_header_timeout_s: float = 10.0):
        self.max_body_bytes = int(max_body_bytes)
        self.registry = registry or ModelRegistry()
        self._owns_registry = registry is None
        self._ready = True            # flips off when drain() starts
        self._prev_handlers: Dict[int, Any] = {}
        self._signal_drain: Optional[threading.Thread] = None
        # request tracing (docs/observability.md): disabled by default
        # — Tracer.begin then returns None and every instrumented path
        # skips span work on a single attribute check. ?trace=1 still
        # traces one request through a disabled tracer.
        self.tracer = Tracer(enabled=bool(tracing), ring=trace_ring,
                             slow_ms=trace_slow_ms)
        # structured access log: False = off, True = stderr, else any
        # writable text stream (one JSON object per line)
        self._log_stream = (sys.stderr if log_requests is True
                            else (log_requests or None))
        self._log_lock = threading.Lock()
        # dead-socket writes swallowed by the handler (clients/routers
        # that timed out and hung up): invisible before this counter
        self.client_disconnects = 0
        self._disc_lock = threading.Lock()
        self._opts = dict(batching=batching, max_batch_size=max_batch_size,
                          max_latency_ms=max_latency_ms,
                          max_queue=max_queue,
                          default_timeout_ms=default_timeout_ms)
        self.model = model  # legacy attribute
        if model is not None:
            served = self.register(self.DEFAULT_MODEL, model,
                                   default_outputs=default_outputs)
            if warmup_buckets:
                served.warmup(warmup_buckets, example=warmup_example)
        self._aio = AioReplicaFrontend(
            self, host, port, header_timeout_s=http_header_timeout_s)
        self.host = self._aio.host
        self.port = self._aio.port

    # -- model management ----------------------------------------------
    def register(self, name: str, model, **opts) -> ServedModel:
        """Register a model under ``name`` (engine + batcher built from
        the server's batching policy unless overridden in ``opts``)."""
        merged = dict(self._opts)
        merged.update(opts)
        return self.registry.register(name, model, **merged)

    def unregister(self, name: str, version: Optional[int] = None):
        self.registry.unregister(name, version)

    def served(self, name: str = DEFAULT_MODEL,
               version: Optional[int] = None) -> ServedModel:
        return self.registry.get(name, version)

    def register_generator(self, name: str, model, **opts) -> ServedGenerator:
        """Register a causal LM for continuous-batching generation at
        ``/v1/models/<name>/generate`` (queue bound and default
        timeout inherit the server's batching policy unless
        overridden)."""
        merged = {"max_queue": self._opts["max_queue"],
                  "default_timeout_ms": self._opts["default_timeout_ms"]}
        merged.update(opts)
        return self.registry.register_generator(name, model, **merged)

    # -- request handling ----------------------------------------------
    def _route(self, path: str):
        """Map a POST path to (model name, action); None = 404."""
        if path == "/predict":
            return self.DEFAULT_MODEL, "predict"
        if path == "/generate":
            return self.DEFAULT_MODEL, "generate"
        parts = [p for p in path.split("/") if p]
        if len(parts) == 4 and parts[:2] == ["v1", "models"] \
                and parts[3] in ("predict", "generate"):
            return parts[2], parts[3]
        return None

    def _predict(self, name: str, req, trace=None) -> dict:
        if not isinstance(req, dict):
            raise ClientError("request body must be a JSON object")
        if "inputs" not in req:
            raise ClientError("missing 'inputs'")
        version = req.get("version")
        if version is not None and (not isinstance(version, int)
                                    or isinstance(version, bool)):
            raise ClientError("'version' must be an integer")
        served = self.registry.get(name, version)
        if not hasattr(served, "predict"):
            raise ClientError(
                f"model {name!r} is a generation model — POST to "
                f"/v1/models/{name}/generate instead")
        outputs = req.get("outputs")
        if outputs is not None and not isinstance(outputs, (list, tuple)):
            raise ClientError("'outputs' must be a list of names")
        timeout_ms = req.get("timeout_ms")
        if timeout_ms is not None and (
                not isinstance(timeout_ms, (int, float))
                or isinstance(timeout_ms, bool)):
            raise ClientError("'timeout_ms' must be a number")
        priority = req.get("priority", "interactive")
        if not isinstance(priority, str):
            raise ClientError("'priority' must be a string")
        res = served.predict(req["inputs"], outputs, timeout_ms=timeout_ms,
                             priority=priority, trace=trace)
        if isinstance(res, dict):
            return {"outputs": {k: np.asarray(v).tolist()
                                for k, v in res.items()}}
        if isinstance(res, list):
            return {"outputs": [np.asarray(v).tolist() for v in res]}
        return {"outputs": np.asarray(res).tolist()}

    def _gen_opts(self, name: str, req):
        """Parse + validate a generate payload into (served, prompt,
        engine kwargs). Raises :class:`ClientError` on bad fields."""
        if not isinstance(req, dict):
            raise ClientError("request body must be a JSON object")
        if "prompt" not in req:
            raise ClientError("missing 'prompt' (a list of token ids)")
        version = req.get("version")
        if version is not None and not isinstance(version, int):
            raise ClientError("'version' must be an integer")
        served = self.registry.get(name, version)
        if not hasattr(served, "generate"):
            raise ClientError(
                f"model {name!r} is a predict model — POST to "
                f"/v1/models/{name}/predict instead")
        opts = {}
        for key, types in (("max_tokens", int), ("temperature",
                                                 (int, float)),
                           ("top_k", int), ("seed", int),
                           ("eos_id", int),
                           ("timeout_ms", (int, float))):
            if key in req and req[key] is not None:
                if not isinstance(req[key], types) or isinstance(
                        req[key], bool):
                    raise ClientError(f"{key!r} must be a number")
                opts[key] = req[key]
        priority = req.get("priority")
        if priority is not None:
            if not isinstance(priority, str):
                raise ClientError("'priority' must be a string")
            opts["priority"] = priority
        session_id = req.get("session_id")
        if session_id is not None:
            # length/backend validation stays in the engine — it owns
            # the session store; here only the JSON type is checked
            if not isinstance(session_id, str):
                raise ClientError("'session_id' must be a string")
            opts["session_id"] = session_id
        return served, req["prompt"], opts

    def _generate(self, name: str, req, trace=None) -> dict:
        served, prompt, opts = self._gen_opts(name, req)
        return served.generate(prompt, trace=trace, **opts)

    def _generate_stream(self, name: str, req, trace=None):
        served, prompt, opts = self._gen_opts(name, req)
        return served.stream(prompt, trace=trace, **opts)

    def _count_disconnect(self):
        """Count a swallowed dead-socket write (client hung up while a
        response or stream chunk was in flight). Routine under router
        timeouts/hedging, but a rate spike means clients are giving up
        before replies arrive — surfaced in ``summary()``."""
        with self._disc_lock:
            self.client_disconnects += 1

    def _access_log(self, entry: dict):
        """Emit one structured JSON access-log line (off unless the
        server was built with ``log_requests=``). Logging failures
        never take down a request handler."""
        stream = self._log_stream
        if stream is None:
            return
        try:
            line = json.dumps(entry, separators=(",", ":"))
            with self._log_lock:
                stream.write(line + "\n")
                stream.flush()
        except (OSError, ValueError):
            pass

    def _count_error(self, name: str, code: int, version=None):
        try:
            m = self.registry.get(
                name, version if isinstance(version, int) else None).metrics
        except Exception:  # noqa: BLE001 — unknown model has no metrics
            return
        if code == 400:
            m.inc("client_errors")
        elif code >= 500 and code not in (503, 504) \
                and not isinstance(m, GenerationMetrics):
            # generation 5xx are already counted at the engine
            # (GenerationEngine._fail) so direct-API users see them
            # too; counting here as well would double them
            m.inc("server_errors")

    def _health(self) -> dict:
        d = {"status": "ok", "models": self.registry.names()}
        if self.model is not None:
            d["model"] = type(self.model).__name__  # legacy field
        return d

    # -- lifecycle (docs/serving.md "Operating the server") ------------
    def ready(self) -> bool:
        """Readiness: True until :meth:`drain` starts. ``/readyz``
        mirrors this (200 vs 503 + Retry-After) so load balancers pull
        the replica before its in-flight work finishes."""
        return self._ready

    def _healthz(self):
        """Liveness: (status code, body). 503 only when some engine's
        scheduler loop is WEDGED — thread dead or heartbeat stale past
        its stall watchdog. Draining/stopped engines are alive (that's
        readiness's job), so a restart isn't provoked mid-drain."""
        models = self.registry.health()
        ok = all(models.values())
        return (200 if ok else 503), {
            "status": "ok" if ok else "stalled",
            "models": models}

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful shutdown, phase 1: flip readiness off (``/readyz``
        -> 503, new POSTs -> 503 + Retry-After), drain every engine
        (in-flight requests finish, scheduler threads join). The HTTP
        listener stays up so ``/stats``, ``/healthz`` and in-flight
        streaming responses keep flowing; call :meth:`stop` (phase 2)
        to tear it down. Returns True when everything drained within
        ``timeout_s``."""
        self._ready = False
        return self.registry.drain(timeout_s)

    def install_signal_handlers(self, signals=(signal.SIGTERM,),
                                drain_timeout_s: float = 30.0,
                                reraise: bool = True) -> bool:
        """Wire graceful drain to SIGTERM (the platform's preemption
        notice — same contract as
        :class:`~..parallel.elastic.PreemptionHandler` for training):
        on signal, drain + stop, then chain the previous handler (or
        re-deliver the default action so the process actually exits).
        Signal handlers are a main-thread-only facility; elsewhere
        this degrades to a no-op and returns False.

        The handler itself only flips readiness and hands off: Python
        runs it on the main thread between bytecodes, so the main
        thread may at that instant hold the very registry/batcher
        locks ``drain()`` needs — blocking in the handler would
        deadlock the process on a lock its own thread holds. The
        blocking drain + stop run on a dedicated thread. Chaining
        works by RESTORING the previous disposition in the handler
        (``signal.signal`` is itself main-thread-only) and having the
        worker re-deliver the signal after the drain: CPython then
        runs the previous handler on the main thread, the context it
        is entitled to (e.g. ``PreemptionHandler`` re-arms SIG_DFL,
        legal only there). A side effect is the usual graceful-then-
        forceful contract: a second signal during the drain takes the
        previous/default action immediately."""
        if threading.current_thread() is not threading.main_thread():
            return False

        def _handle(signum, frame):
            self._ready = False       # lock-free; /readyz flips now
            if self._signal_drain is not None \
                    and self._signal_drain.is_alive():
                return                # drain already in flight
            prev = self._prev_handlers.get(signum)
            if reraise and prev is not None:
                signal.signal(signum, prev)

            def _drain_and_exit():
                self.drain(drain_timeout_s)
                self.stop()
                if reraise and prev is not None \
                        and prev != signal.SIG_IGN:
                    os.kill(os.getpid(), signum)
            # non-daemon: interpreter exit waits for the (time-bounded)
            # drain instead of killing it mid-flight
            self._signal_drain = threading.Thread(
                target=_drain_and_exit, name="serving-signal-drain",
                daemon=False)
            self._signal_drain.start()
        for s in signals:
            self._prev_handlers[s] = signal.getsignal(s)
            signal.signal(s, _handle)
        return True

    def stats(self) -> dict:
        return {"summary": self.summary(),
                "models": self.registry.stats(),
                "profiler": profiler_sections()}

    def summary(self) -> dict:
        """Compact machine-readable routing summary, also embedded as
        the ``summary`` key of ``GET /stats``: per-model live
        occupancy / queue depth / draining flag plus a server-level
        ``load`` total — what :class:`~.fleet.FleetRouter` (or any
        external load balancer) reads to pick a replica without
        parsing nested histogram snapshots."""
        models = self.registry.summary()
        return {"ready": self.ready(),
                "draining": not self.ready(),
                "load": sum(m["load"] for m in models.values()),
                # server-level shed total: a fleet poller aggregates
                # these into per-replica overload counters
                "shed": sum(m.get("shed", 0) for m in models.values()),
                "client_disconnects": self.client_disconnects,
                "models": models}

    def stop(self):
        # readiness off FIRST: handler threads still in flight when the
        # listener stops would otherwise race the registry teardown and
        # answer 404 ("unknown model") — a lie that a router would pass
        # through as terminal. Shedding 503 + Retry-After instead keeps
        # even a hard (drain-less) stop retryable upstream.
        self._ready = False
        self._aio.stop()
        if self._owns_registry:
            self.registry.stop()
