"""Observability tests (ISSUE 10): end-to-end request tracing and the
unified /metrics telemetry plane.

Covers the tracing primitives (ring-bounded retention, span trees,
zero-cost-when-disabled), X-Request-Id propagation and trace stitching
across the fleet (the acceptance scenario: ONE trace for a
hedged-AND-retried generate through a 3-replica fleet), Prometheus
text exposition on replicas and the fleet front-end (parity with
/stats), the structured JSON access log, the client_disconnects
counter, and the framework-free tools/trace_report.py stitcher."""
import importlib.util
import inspect
import io
import json
import os
import re
import socket
import threading
import time
import tracemalloc
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.serving import (FaultInjector, FleetRouter,
                                        InferenceServer, ReplicaFleet)
from deeplearning4j_tpu.tracing import Tracer, new_request_id

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(seed=0, n_in=4, n_out=3):
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=n_out, loss="mcxent",
                               activation="softmax"))
            .input_type_feed_forward(n_in).build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture(scope="module")
def mlp():
    return _mlp()


@pytest.fixture(scope="module")
def tiny_lm():
    from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM
    return CausalTransformerLM(vocab_size=64, d_model=16, n_layers=1,
                               n_heads=2, max_seq_len=32, seed=0,
                               implementation="plain").init()


X = np.arange(4, dtype=np.float32).reshape(1, 4).tolist()


def _post(url, payload, headers=None, timeout=60):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), headers=hdrs)
    resp = urllib.request.urlopen(req, timeout=timeout)
    return resp, json.loads(resp.read())


def _get_json(url, timeout=30):
    return json.loads(urllib.request.urlopen(url, timeout=timeout).read())


class _Slow:
    """Duck-typed model: output() sleeps (forces the response to land
    after the client hangs up)."""

    def __init__(self, delay=0.5):
        self.delay = delay

    def output(self, x):
        time.sleep(self.delay)
        return np.zeros((np.asarray(x).shape[0], 1), np.float32)


# ---------------------------------------------------------------------
# tracing primitives
# ---------------------------------------------------------------------
class TestTracer:

    def test_disabled_begin_returns_none_and_finish_tolerates_it(self):
        tr = Tracer(enabled=False)
        assert tr.begin() is None
        tr.finish(None)                       # no-op, no crash
        assert tr.snapshot()["started"] == 0

    def test_force_traces_single_request_while_disabled(self):
        tr = Tracer(enabled=False)
        t = tr.begin("rid-1", force=True)
        assert t is not None and t.trace_id == "rid-1"
        t.span("http").end(status=200)
        tr.finish(t)
        dumped = tr.dump(request_id="rid-1")
        assert len(dumped) == 1
        assert dumped[0]["spans"][0]["kind"] == "http"
        assert dumped[0]["spans"][0]["attrs"]["status"] == 200

    def test_minted_request_ids_are_unique_hex(self):
        ids = {new_request_id() for _ in range(64)}
        assert len(ids) == 64
        assert all(re.fullmatch(r"[0-9a-f]{16}", i) for i in ids)

    def test_recent_ring_is_bounded(self):
        tr = Tracer(enabled=True, ring=8)
        for i in range(25):
            t = tr.begin(f"r{i}")
            t.span("http").end()
            tr.finish(t)
        snap = tr.snapshot()
        assert snap["started"] == snap["finished"] == 25
        assert snap["recent"] == 8
        # newest first, oldest evicted
        dumped = tr.dump(limit=100)
        got = [d["request_id"] for d in dumped]
        assert got[0] == "r24" and "r0" not in got

    def test_slow_and_errored_rings_retain_past_recent_eviction(self):
        tr = Tracer(enabled=True, ring=2, slow_ms=5.0)
        slow = tr.begin("slow-one")
        slow.t_start -= 1.0                    # fake a 1s trace
        tr.finish(slow)
        err = tr.begin("err-one")
        tr.finish(err, error=True)
        for i in range(10):                    # cycle the recent ring
            tr.finish(tr.begin(f"f{i}"))
        snap = tr.snapshot()
        assert snap["slow"] >= 1 and snap["errored"] >= 1
        assert len(tr.dump(request_id="slow-one")) == 1
        errd = tr.dump(request_id="err-one")
        assert len(errd) == 1 and errd[0]["error"] is True

    def test_dump_limit_and_dedup(self):
        tr = Tracer(enabled=True, ring=16, slow_ms=0.0)  # all slow too
        for i in range(6):
            tr.finish(tr.begin(f"r{i}"))
        # each trace sits in recent AND slow; dump must dedupe
        assert len(tr.dump(limit=100)) == 6
        assert len(tr.dump(limit=3)) == 3

    def test_span_tree_defaults_to_component_root(self):
        tr = Tracer(enabled=True)
        t = tr.begin("tree")
        root = t.span("http")
        a = t.span("admission")
        q = t.span("queue")
        explicit = t.span("device", parent=q)
        assert a.parent_id == root.span_id
        assert q.parent_id == root.span_id
        assert explicit.parent_id == q.span_id
        assert len({root.span_id, a.span_id, q.span_id,
                    explicit.span_id}) == 4

    def test_retroactive_span_and_open_span_serialization(self):
        tr = Tracer(enabled=True)
        t = tr.begin("retro")
        t.span("decode", t_start=t.t_start,
               t_end=t.t_start + 0.250, steps=5)
        open_span = t.span("hedge")            # never ended
        tr.finish(t)
        d = t.to_dict()
        decode = next(s for s in d["spans"] if s["kind"] == "decode")
        assert decode["duration_ms"] == pytest.approx(250.0, abs=1.0)
        assert decode["attrs"]["steps"] == 5
        hedge = next(s for s in d["spans"] if s["kind"] == "hedge")
        assert hedge["duration_ms"] is None    # open -> null, visible
        assert open_span.span_id == hedge["span_id"]

    def test_concurrent_span_ids_unique(self):
        # hedge arms record into one trace from two threads
        import threading
        tr = Tracer(enabled=True)
        t = tr.begin("conc")
        spans = []

        def rec():
            for _ in range(50):
                spans.append(t.span("dispatch").end())

        th = [threading.Thread(target=rec) for _ in range(4)]
        for x in th:
            x.start()
        for x in th:
            x.join()
        ids = [s.span_id for s in spans]
        assert len(ids) == len(set(ids)) == 200


# ---------------------------------------------------------------------
# replica HTTP: request ids, per-request timelines, /debug/traces
# ---------------------------------------------------------------------
class TestReplicaTracingHTTP:

    @pytest.fixture(scope="class")
    def server(self, mlp):
        srv = InferenceServer(port=0, tracing=True)
        srv.register("default", mlp)
        yield srv
        srv.stop()

    def test_request_id_minted_and_echoed(self, server):
        base = f"http://{server.host}:{server.port}"
        resp, _ = _post(base + "/predict", {"inputs": X})
        minted = resp.headers.get("X-Request-Id")
        assert minted and re.fullmatch(r"[0-9a-f]{16}", minted)
        resp2, _ = _post(base + "/predict", {"inputs": X},
                         headers={"X-Request-Id": "caller-chose-this"})
        assert resp2.headers.get("X-Request-Id") == "caller-chose-this"

    def test_trace_query_param_embeds_timeline(self, server):
        base = f"http://{server.host}:{server.port}"
        _, body = _post(base + "/predict?trace=1", {"inputs": X})
        tl = body["trace"]
        kinds = [s["kind"] for s in tl["spans"]]
        assert kinds[0] == "http"
        assert {"admission", "queue", "device"} <= set(kinds)
        adm = next(s for s in tl["spans"] if s["kind"] == "admission")
        assert adm["attrs"]["verdict"] == "admitted"
        assert "device_ewma_ms" in adm["attrs"]
        assert "est_wait_ms" in adm["attrs"]
        assert tl["duration_ms"] > 0

    def test_trace_body_flag_equivalent(self, server):
        base = f"http://{server.host}:{server.port}"
        _, body = _post(base + "/predict", {"inputs": X, "trace": 1})
        assert {"admission", "queue", "device"} <= {
            s["kind"] for s in body["trace"]["spans"]}

    def test_debug_traces_filter_by_request_id(self, server):
        base = f"http://{server.host}:{server.port}"
        _post(base + "/predict", {"inputs": X},
              headers={"X-Request-Id": "findme-0001"})
        doc = _get_json(base + "/debug/traces?request_id=findme-0001")
        assert [t["trace_id"] for t in doc["traces"]] == ["findme-0001"]
        assert doc["tracer"]["enabled"] is True
        assert doc["tracer"]["finished"] >= 1
        everything = _get_json(base + "/debug/traces?limit=2")
        assert len(everything["traces"]) <= 2


# ---------------------------------------------------------------------
# /metrics: Prometheus text exposition
# ---------------------------------------------------------------------
# parser + generic snapshot-vs-exposition walker live in _obs_util so
# the training-side tests share them (ISSUE 13)
from _obs_util import assert_exposition_parity  # noqa: E402
from _obs_util import parse_prometheus as _parse_prometheus  # noqa: E402


class TestPrometheusExposition:

    def test_replica_metrics_parse_and_agree_with_stats(self, mlp):
        srv = InferenceServer(port=0)
        srv.register("default", mlp)
        try:
            base = f"http://{srv.host}:{srv.port}"
            for _ in range(3):
                _post(base + "/predict", {"inputs": X})
            # quiesced: no traffic in flight between the two reads
            stats = _get_json(base + "/stats")
            resp = urllib.request.urlopen(base + "/metrics", timeout=30)
            assert resp.headers.get("Content-Type", "").startswith(
                "text/plain; version=0.0.4")
            samples, types = _parse_prometheus(resp.read().decode())
            assert types, "no # TYPE lines"
            # EVERY numeric leaf of the /stats snapshot must appear on
            # /metrics with the documented name/type/value (the generic
            # walker replaces per-family hand asserts — ISSUE 13)
            checked = assert_exposition_parity(stats, samples, types)
            assert checked > 20
            # spot-check the mapping conventions survived
            key = ("dl4j_model_requests_total", '{model="default"}')
            assert samples[key] == stats["models"]["default"]["requests"]
            assert types["dl4j_model_latency_ms"] == "summary"
            assert any(n == "dl4j_model_batch_hist" and "bucket=" in lab
                       for n, lab in samples)
        finally:
            srv.stop()

    def test_fleet_metrics_parse_and_agree_with_stats(self, mlp):
        fleet = ReplicaFleet(poll_interval_s=None)
        srv = InferenceServer(port=0)
        srv.register("default", mlp)
        fleet.add(srv)
        fleet.poll_now()
        router = FleetRouter(fleet)
        try:
            host, port = router.serve()
            base = f"http://{host}:{port}"
            for _ in range(2):
                _post(base + "/predict", {"inputs": X})
            stats = _get_json(base + "/stats")
            resp = urllib.request.urlopen(base + "/metrics", timeout=30)
            samples, types = _parse_prometheus(resp.read().decode())
            assert_exposition_parity(stats, samples, types)
            assert samples[("dl4j_fleet_requests_total", "")] == \
                stats["fleet"]["requests"]
            # per-replica families carry {replica=...}
            assert any(n == "dl4j_replica_in_flight" and "replica=" in lab
                       for n, lab in samples)
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)

    def test_prefix_cache_counters_parse_and_agree_with_stats(
            self, tiny_lm):
        """ISSUE 11 parity: the paged engine's prefix-cache block of
        /stats (hits, COW copies, session gauges) exports 1:1 on
        /metrics — counters as _total, gauges bare."""
        srv = InferenceServer(port=0)
        g = srv.register_generator(
            "lm", tiny_lm, num_slots=2, max_seq_len=32,
            prompt_buckets=[8], cache="paged", block_size=8,
            prefill_chunk_tokens=8)
        g.warmup()
        try:
            prompt = [1, 5, 2, 9, 3, 7, 4, 6, 8, 10, 1, 5, 2, 9, 3, 7]
            g.generate(prompt, max_tokens=3, timeout_ms=60_000)
            g.generate(prompt, max_tokens=3, timeout_ms=60_000,
                       session_id="s1")
            base = f"http://{srv.host}:{srv.port}"
            stats = _get_json(base + "/stats")
            pc = stats["models"]["lm"]["paged"]["prefix_cache"]
            assert pc["prefix_hits"] >= 1 and pc["sessions_live"] == 1
            samples, types = _parse_prometheus(urllib.request.urlopen(
                base + "/metrics", timeout=30).read().decode())
            # the generic walker covers every prefix-cache leaf
            # (counters as _total, gauges bare) plus the rest of the
            # snapshot in one pass
            assert_exposition_parity(stats, samples, types)
            lab = '{model="lm"}'
            stem = "dl4j_model_paged_prefix_cache_"
            assert samples[(f"{stem}prefix_hits_total", lab)] == \
                pc["prefix_hits"]
            assert types[f"{stem}sessions_live"] == "gauge"
        finally:
            srv.stop()

    def test_spec_counters_parse_and_agree_with_stats(self, tiny_lm):
        """ISSUE 12 parity: the speculating engine's `spec` block of
        /stats (proposed/accepted, verify batches, rollbacks,
        fallbacks) exports 1:1 on /metrics — counters as _total, the
        accept_rate / speculation_k / enabled knobs as gauges."""
        srv = InferenceServer(port=0)
        g = srv.register_generator(
            "lm", tiny_lm, num_slots=2, max_seq_len=32,
            prompt_buckets=[8], speculation_k=2)
        g.warmup()
        try:
            for i in range(3):
                g.generate([1 + i, 5, 2, 9], max_tokens=8,
                           temperature=0.0, seed=i, timeout_ms=60_000)
            base = f"http://{srv.host}:{srv.port}"
            stats = _get_json(base + "/stats")
            sp = stats["models"]["lm"]["spec"]
            assert sp["enabled"] is True
            assert sp["verify_batches"] >= 1
            assert sp["draft_tokens_proposed"] == \
                2 * sp["verify_batches"]
            samples, types = _parse_prometheus(urllib.request.urlopen(
                base + "/metrics", timeout=30).read().decode())
            # every spec leaf (and everything else) via the walker
            assert_exposition_parity(stats, samples, types)
            lab = '{model="lm"}'
            assert samples[("dl4j_model_spec_verify_batches_total",
                            lab)] == sp["verify_batches"]
            assert types["dl4j_model_spec_accept_rate"] == "gauge"
        finally:
            srv.stop()


# ---------------------------------------------------------------------
# structured access log + client_disconnects (satellites a, b)
# ---------------------------------------------------------------------
class TestAccessLog:

    def test_off_by_default(self, mlp):
        srv = InferenceServer(port=0)
        srv.register("default", mlp)
        try:
            assert srv._log_stream is None
            _post(f"http://{srv.host}:{srv.port}/predict", {"inputs": X})
        finally:
            srv.stop()

    def test_replica_and_router_log_lines_parse_with_propagated_rid(
            self, mlp):
        rep_log, rtr_log = io.StringIO(), io.StringIO()
        srv = InferenceServer(port=0, log_requests=rep_log)
        srv.register("default", mlp)
        fleet = ReplicaFleet(poll_interval_s=None)
        fleet.add(srv)
        fleet.poll_now()
        router = FleetRouter(fleet)
        try:
            host, port = router.serve(log_requests=rtr_log)
            rid = "acclog-rid-42"
            resp, _ = _post(f"http://{host}:{port}/predict",
                            {"inputs": X},
                            headers={"X-Request-Id": rid,
                                     "X-Priority": "batch"})
            assert resp.status == 200

            def entries(buf):
                return [json.loads(line) for line in
                        buf.getvalue().splitlines() if line]

            for log, path in ((rtr_log, "/predict"),
                              (rep_log, "/predict")):
                es = [e for e in entries(log)
                      if e.get("request_id") == rid]
                assert es, f"no access-log line with rid in {log}"
                e = es[0]
                assert e["method"] == "POST" and e["path"] == path
                assert e["status"] == 200
                assert e["latency_ms"] >= 0
                assert e["priority"] == "batch"
                assert "ts" in e and "shed_reason" not in e
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)

    def test_shed_reason_logged_on_503(self, mlp):
        log = io.StringIO()
        srv = InferenceServer(port=0, log_requests=log)
        srv.register("default", mlp)
        try:
            srv.drain(timeout_s=10)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(f"http://{srv.host}:{srv.port}/predict",
                      {"inputs": X}, headers={"X-Request-Id": "shed-1"})
            assert ei.value.code == 503
            es = [json.loads(l) for l in log.getvalue().splitlines()]
            shed = [e for e in es if e.get("request_id") == "shed-1"]
            assert shed and shed[0]["status"] == 503
            assert shed[0]["shed_reason"] == "draining"
        finally:
            srv.stop()


class TestClientDisconnects:

    def test_dead_socket_write_is_counted(self):
        srv = InferenceServer(port=0, max_batch_size=1,
                              max_latency_ms=1.0)
        srv.register("default", _Slow(0.5))
        try:
            payload = json.dumps(
                {"inputs": [[0.0]]}).encode()
            s = socket.create_connection((srv.host, srv.port),
                                         timeout=10)
            s.sendall(
                b"POST /predict HTTP/1.1\r\n"
                b"Host: x\r\nContent-Type: application/json\r\n"
                b"Content-Length: %d\r\n\r\n%s" % (len(payload),
                                                   payload))
            time.sleep(0.1)                    # request fully read
            # RST on close so the server's write genuinely fails
            s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                         b"\x01\x00\x00\x00\x00\x00\x00\x00")
            s.close()
            deadline = time.time() + 15
            while time.time() < deadline:
                if srv.summary().get("client_disconnects", 0) >= 1:
                    break
                time.sleep(0.1)
            assert srv.summary()["client_disconnects"] >= 1
        finally:
            srv.stop()


# ---------------------------------------------------------------------
# engine-level span content (admission verdicts, decode retro span)
# ---------------------------------------------------------------------
class TestEngineSpans:

    def test_generation_spans_and_shed_verdict(self, tiny_lm):
        srv = InferenceServer(port=0, tracing=True)
        g = srv.register_generator("lm", tiny_lm, num_slots=2,
                                   max_seq_len=32, prompt_buckets=[8],
                                   cache="paged", block_size=4,
                                   num_blocks=16)
        g.warmup()
        try:
            tr = srv.tracer.begin("gen-ok")
            out = g.engine.generate([1, 2, 3], max_tokens=8,
                                    temperature=0.0, trace=tr)
            srv.tracer.finish(tr)
            d = tr.to_dict()
            kinds = {s["kind"] for s in d["spans"]}
            assert {"admission", "queue", "prefill", "decode"} <= kinds
            adm = next(s for s in d["spans"]
                       if s["kind"] == "admission")
            assert adm["attrs"]["verdict"] == "admitted"
            assert "decode_ewma_ms" in adm["attrs"]
            dec = next(s for s in d["spans"] if s["kind"] == "decode")
            assert dec["attrs"]["steps"] == len(out["tokens"])

            # shed path: prompt longer than max_seq_len is a
            # ClientError at admission, recorded with verdict="shed"
            tr2 = srv.tracer.begin("gen-shed")
            from deeplearning4j_tpu.serving.engine import ClientError
            with pytest.raises(ClientError):
                g.engine.generate(list(range(1, 60)), max_tokens=8,
                                  trace=tr2)
            srv.tracer.finish(tr2, error=True)
            adm2 = next(s for s in tr2.to_dict()["spans"]
                        if s["kind"] == "admission")
            assert adm2["attrs"]["verdict"] == "shed"
            assert "error" in adm2["attrs"]
        finally:
            srv.stop()


    def test_tracing_leaves_the_tokens_as_they_were(self, tiny_lm):
        """A traced request samples what the same request samples
        untraced (all temperatures, requests in flight together), and
        each trace holds at least its admission, queue and decode."""
        srv = InferenceServer(port=0, tracing=True)
        g = srv.register_generator("lm", tiny_lm, num_slots=2,
                                   max_seq_len=32, prompt_buckets=[8],
                                   cache="paged", block_size=4,
                                   num_blocks=16)
        g.warmup()
        cases = [([1 + i, 2, 3], 4 + i % 3, (0.0, 0.8)[i % 2], i)
                 for i in range(8)]

        def run(traced):
            outs, traces = [None] * len(cases), [None] * len(cases)

            def go(i):
                prompt, n, temp, seed = cases[i]
                if traced:
                    traces[i] = srv.tracer.begin(f"t{i}")
                outs[i] = g.engine.generate(
                    prompt, max_tokens=n, temperature=temp, seed=seed,
                    trace=traces[i])["tokens"]
                if traced:
                    srv.tracer.finish(traces[i])
            threads = [threading.Thread(target=go, args=(i,))
                       for i in range(len(cases))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            return outs, traces
        try:
            plain, _ = run(False)
            traced, traces = run(True)
            assert traced == plain
            for tr in traces:
                kinds = {s["kind"] for s in tr.to_dict()["spans"]}
                assert {"admission", "queue", "decode"} <= kinds
        finally:
            srv.stop()


# ---------------------------------------------------------------------
# zero-cost guarantees on the decode hot loop (satellite d)
# ---------------------------------------------------------------------
class TestTraceOverhead:

    def test_decode_hot_loop_carries_no_tracing_code(self):
        from deeplearning4j_tpu.serving.generation import GenerationEngine
        for fn in (GenerationEngine._decode_step, GenerationEngine._loop,
                   GenerationEngine._dispatch_decode,
                   GenerationEngine._collect_decode,
                   GenerationEngine._retire):
            assert "trace" not in inspect.getsource(fn).lower(), (
                f"{fn.__name__} must stay free of tracing code; the "
                "decode span is rebuilt retroactively in _trace_terminal")

    def test_disabled_tracing_allocates_nothing(self, tiny_lm):
        srv = InferenceServer(port=0)          # tracing OFF
        g = srv.register_generator("lm", tiny_lm, num_slots=2,
                                   max_seq_len=32, prompt_buckets=[8],
                                   cache="paged", block_size=4,
                                   num_blocks=16)
        g.warmup()
        try:
            g.engine.generate([1, 2, 3], max_tokens=4)   # warm paths
            trace_py = os.path.join("deeplearning4j_tpu", "tracing.py")
            tracemalloc.start()
            try:
                g.engine.generate([4, 5, 6], max_tokens=8)
                snap = tracemalloc.take_snapshot()
            finally:
                tracemalloc.stop()
            hits = [st for st in snap.statistics("filename")
                    if st.traceback[0].filename.endswith(trace_py)]
            assert not hits, (
                "disabled tracing must allocate nothing: "
                f"{[(h.traceback[0].filename, h.size) for h in hits]}")
        finally:
            srv.stop()


# ---------------------------------------------------------------------
# the acceptance scenario: ONE stitched trace for a hedged-and-retried
# generate through a 3-replica fleet, over HTTP
# ---------------------------------------------------------------------
class TestFleetTraceStitching:

    def test_hedged_and_retried_generate_yields_one_stitched_trace(
            self, tiny_lm):
        def mk():
            server = InferenceServer(port=0, tracing=True)
            g = server.register_generator(
                "lm", tiny_lm, num_slots=2, max_seq_len=32,
                prompt_buckets=[8], cache="paged", block_size=4,
                num_blocks=16)
            g.warmup()
            return server, g

        (sa, ga), (sb, gb), (sc, gc) = mk(), mk(), mk()
        # slow generation on B and C so the hedge timer always fires
        for g in (gb, gc):
            g.engine.set_fault_injector(FaultInjector(
                rates={"latency": 1.0}, latency_ms=5.0))
        fleet = ReplicaFleet(poll_interval_s=None)
        for s in (sa, sb, sc):
            fleet.add(s)
        fleet.poll_now()
        sa.drain(timeout_s=10)       # A sheds 503 fast -> retry path
        by_port = {r.port: r for r in fleet.replicas()}
        # bias occupancy so the router picks A, then B, hedges to C
        by_port[sb.port].begin()
        by_port[sc.port].begin()
        by_port[sc.port].begin()
        router = FleetRouter(fleet, hedge_after_ms=30.0,
                             hedge_generate=True, tracing=True)
        try:
            host, port = router.serve()
            rid = "e2e-trace-1"
            resp, body = _post(
                f"http://{host}:{port}/v1/models/lm/generate",
                {"prompt": [1, 2, 3], "max_tokens": 16, "seed": 7},
                headers={"X-Request-Id": rid})
            assert resp.status == 200
            assert resp.headers.get("X-Request-Id") == rid
            assert len(body["tokens"]) == 16
            snap = fleet.snapshot()
            assert snap["retries"] >= 1, "A's 503 must have retried"
            assert snap["hedges"] >= 1, "the hedge timer must have fired"

            def dump(base):
                return _get_json(
                    base + f"/debug/traces?request_id={rid}")["traces"]

            # router fragment: the hedge pair shares the trace, the
            # losing arm is marked discarded
            rt = dump(f"http://{host}:{port}")
            assert len(rt) == 1 and rt[0]["trace_id"] == rid
            rkinds = [s["kind"] for s in rt[0]["spans"]]
            assert rkinds[0] == "frontend"
            assert {"pick", "dispatch", "retry", "hedge"} <= set(rkinds)
            hedge = next(s for s in rt[0]["spans"]
                         if s["kind"] == "hedge")
            dispatches = [s for s in rt[0]["spans"]
                          if s["kind"] in ("dispatch", "hedge")]
            assert sum(1 for s in dispatches
                       if s["attrs"].get("discarded")) == 1
            arms = {s["attrs"].get("replica") for s in dispatches}
            assert len(arms) >= 2, "hedge arms hit distinct replicas"
            assert hedge["attrs"]["replica"] in arms

            # the winning replica's fragment carries the full
            # queue/admission/prefill/decode picture under the SAME id
            winner = next(s["attrs"]["replica"] for s in dispatches
                          if s["attrs"].get("status") == 200
                          and not s["attrs"].get("discarded"))
            win_rep = next(r for r in fleet.replicas()
                           if r.id == winner)
            wt = dump(f"http://{win_rep.host}:{win_rep.port}")
            assert len(wt) == 1 and wt[0]["trace_id"] == rid
            wkinds = {s["kind"] for s in wt[0]["spans"]}
            assert {"http", "admission", "queue", "prefill",
                    "decode"} <= wkinds
            # stitched: every fragment shares the propagated id
            assert {t["trace_id"] for t in rt + wt} == {rid}
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)
            for s in (sa, sb, sc):
                s.stop()

    def test_cooldown_wait_span_recorded_when_fleet_cooling(self, mlp):
        fleet = ReplicaFleet(poll_interval_s=None)
        srv = InferenceServer(port=0)
        srv.register("default", mlp)
        fleet.add(srv)
        fleet.poll_now()
        rep = fleet.replicas()[0]
        rep.cooldown_until = time.monotonic() + 0.15
        router = FleetRouter(fleet, cooldown_wait_s=1.0, tracing=True)
        try:
            status, _hdrs, _body = router.post_raw(
                "/predict", json.dumps({"inputs": X}).encode(),
                {"X-Request-Id": "cool-1"})
            assert status == 200
            t = router.tracer.dump(request_id="cool-1")[0]
            kinds = [s["kind"] for s in t["spans"]]
            assert "cooldown_wait" in kinds
            cw = next(s for s in t["spans"]
                      if s["kind"] == "cooldown_wait")
            assert cw["duration_ms"] > 0
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)


# ---------------------------------------------------------------------
# tools/trace_report.py (satellite f)
# ---------------------------------------------------------------------
def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trp", os.path.join(ROOT, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(sid, pid, kind, off, dur, **attrs):
    return {"span_id": sid, "parent_id": pid, "kind": kind,
            "t_offset_ms": off, "duration_ms": dur, "attrs": attrs}


class TestTraceReportTool:

    @pytest.fixture()
    def dumps(self, tmp_path):
        router = {"traces": [{
            "trace_id": "rid1", "request_id": "rid1",
            "duration_ms": 50.0, "error": False,
            "spans": [
                _span(1, None, "frontend", 0.0, 50.0),
                _span(2, 1, "pick", 0.5, 0.1, replica="r1"),
                _span(3, 1, "dispatch", 1.0, 45.0, replica="r1"),
                _span(4, 1, "hedge", 31.0, None, replica="r2",
                      discarded=True),
            ]}]}
        replica = {"traces": [
            {"trace_id": "rid1", "request_id": "rid1",
             "duration_ms": 44.0, "error": False,
             "spans": [
                 _span(1, None, "http", 0.0, 44.0),
                 _span(2, 1, "queue", 0.2, 4.0),
                 _span(3, 1, "device", 5.0, 38.0),
             ]},
            {"trace_id": "rid2", "request_id": "rid2",
             "duration_ms": 7.0, "error": True,
             "spans": [_span(1, None, "http", 0.0, 7.0)]},
        ]}
        p1 = tmp_path / "router.json"
        p2 = tmp_path / "replica.json"
        p1.write_text(json.dumps(router))
        p2.write_text(json.dumps(replica))
        return str(p1), str(p2)

    def test_merge_by_trace_id_with_namespaced_span_ids(self, dumps):
        trp = _load_trace_report()
        traces = trp.load_traces(list(dumps))
        assert len(traces) == 2
        merged = next(t for t in traces if t["trace_id"] == "rid1")
        assert len(merged["spans"]) == 7       # 4 router + 3 replica
        ids = [s["span_id"] for s in merged["spans"]]
        assert len(set(ids)) == 7, "cross-tier span ids must not collide"
        assert merged["duration_ms"] == 50.0   # max across tiers
        # parent links survive namespacing: replica queue -> replica http
        q = next(s for s in merged["spans"] if s["kind"] == "queue")
        http = next(s for s in merged["spans"] if s["kind"] == "http")
        assert q["parent_id"] == http["span_id"]

    def test_kind_stats_and_critical_path(self, dumps):
        trp = _load_trace_report()
        rep = trp.report(list(dumps))
        assert rep["n_traces"] == 2
        assert rep["kinds"]["http"]["count"] == 2
        assert rep["kinds"]["dispatch"]["p50_ms"] == 45.0
        assert "hedge" not in rep["kinds"]     # open span: no duration
        s = rep["slowest"]
        assert s["trace_id"] == "rid1" and s["n_spans"] == 7
        path_kinds = [h["kind"] for h in s["critical_path"]]
        # frontend (longest root) -> dispatch (longest child); the
        # replica's http tree is a second root, not on this chain
        assert path_kinds[0] == "frontend"
        assert path_kinds[1] == "dispatch"

    def test_main_human_and_json_modes(self, dumps, capsys):
        trp = _load_trace_report()
        assert trp.main(list(dumps)) == 0
        human = capsys.readouterr().out
        assert "slowest trace rid1" in human
        assert "frontend" in human and "dispatch" in human
        assert trp.main(list(dumps) + ["--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["n_traces"] == 2
        assert doc["slowest"]["trace_id"] == "rid1"

    def test_main_bad_input_returns_1(self, tmp_path, capsys):
        trp = _load_trace_report()
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert trp.main([str(bad)]) == 1
        assert "error:" in capsys.readouterr().err
        assert trp.main([str(tmp_path / "missing.json")]) == 1

    def test_tool_is_framework_free(self):
        src = open(os.path.join(ROOT, "tools",
                                "trace_report.py")).read()
        for banned in ("import jax", "import numpy",
                       "from deeplearning4j_tpu"):
            assert banned not in src
