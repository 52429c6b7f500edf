"""Replica-fleet tier tests (ISSUE 6): occupancy-aware routing,
health-gated membership, straggler hedging under a retry budget, the
compact /stats routing summary, streaming + mid-stream disconnect
THROUGH the router, and zero-loss rolling restarts extending PR 4's
single-replica drain guarantee fleet-wide."""
import http.client
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.serving import (FaultInjector, FleetRouter,
                                        InferenceServer, ReplicaFleet)


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


def _predict_factory(model, fault_injector=None):
    """Builds a warmed single-model replica (the shape a rolling
    restart's factory must have: ready before it returns)."""
    def factory():
        server = InferenceServer(port=0, max_batch_size=4,
                                 max_latency_ms=2.0)
        server.register("default", model, fault_injector=fault_injector)
        server.served().warmup([1, 2, 4])
        return server
    return factory


def _gen_factory(lm, **opts):
    def factory():
        server = InferenceServer(port=0)
        merged = dict(num_slots=2, max_seq_len=32, prompt_buckets=[8],
                      cache="paged", block_size=4, num_blocks=16)
        merged.update(opts)
        g = server.register_generator("lm", lm, **merged)
        g.warmup()
        return server
    return factory


def _mkfleet(factories, poll_interval_s=None, **fleet_kw):
    fleet = ReplicaFleet(poll_interval_s=poll_interval_s, **fleet_kw)
    for f in factories:
        fleet.add(f(), factory=f)
    return fleet


class _Slow:
    """Duck-typed model: output() sleeps (slow-replica stand-in)."""

    def __init__(self, delay=0.2):
        self.delay = delay

    def output(self, x):
        time.sleep(self.delay)
        return np.zeros((np.asarray(x).shape[0], 1), np.float32)


X = np.arange(4, dtype=np.float32).reshape(1, 4).tolist()


class TestStatsSummary:
    """Satellite: the compact machine-readable routing summary at
    GET /stats — live occupancy, queue depth, draining flag — so the
    router (and any external LB) needs no histogram parsing."""

    def test_summary_shape_predict_and_generation(self, mlp, tiny_lm):
        server = InferenceServer(port=0)
        server.register("m", mlp)
        server.register_generator("lm", tiny_lm, num_slots=2,
                                  max_seq_len=32, prompt_buckets=[8])
        try:
            stats = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/stats",
                timeout=30).read())
            s = stats["summary"]
            assert s["ready"] is True and s["draining"] is False
            assert s["load"] == 0
            m = s["models"]["m"]
            assert m["mode"] == "predict"
            assert m["capacity"] == 64 and m["occupancy"] == 0.0
            assert m["queue_depth"] == 0 and m["draining"] is False
            g = s["models"]["lm"]
            assert g["mode"] == "generation"
            assert g["capacity"] == 2 and g["active"] == 0
            assert g["draining"] is False and g["load"] == 0
        finally:
            server.stop()

    def test_summary_reflects_live_occupancy_and_drain(self, tiny_lm):
        server = InferenceServer(port=0)
        g = server.register_generator("lm", tiny_lm, num_slots=2,
                                      max_seq_len=32, prompt_buckets=[8])
        g.warmup()
        try:
            stream = g.stream([1, 2, 3], max_tokens=64, seed=0,
                              timeout_ms=60_000)
            next(stream)   # a generation is now live in a slot
            s = server.summary()
            lm = s["models"]["lm"]
            assert lm["active"] == 1 and lm["occupancy"] == 0.5
            assert s["load"] >= 1
            stream.close()
            server.drain(timeout_s=30.0)
            s = server.summary()
            assert s["ready"] is False and s["draining"] is True
            assert s["models"]["lm"]["draining"] is True
        finally:
            server.stop()


class TestRouting:
    def test_occupancy_steers_away_from_loaded_replica(self, mlp):
        """The router must pick by live queue/occupancy pulled from
        /stats, not round-robin: a replica with a backed-up queue
        stops attracting new work even though it is healthy."""
        slow = InferenceServer(port=0, max_batch_size=2,
                               max_latency_ms=1.0)
        slow.register("default", _Slow(delay=0.4))
        fast_factory = _predict_factory(mlp)
        fast = fast_factory()
        fleet = ReplicaFleet(poll_interval_s=None)
        r_slow = fleet.add(slow)
        r_fast = fleet.add(fast)
        router = FleetRouter(fleet)
        try:
            # back the slow replica up with direct traffic (not via
            # the router — models an external/second-router client)
            def direct():
                try:
                    urllib.request.urlopen(urllib.request.Request(
                        f"http://127.0.0.1:{slow.port}/predict",
                        data=json.dumps({"inputs": X}).encode()),
                        timeout=60).read()
                except Exception:
                    pass
            ts = [threading.Thread(target=direct) for _ in range(4)]
            for t in ts:
                t.start()
            deadline = time.time() + 10
            while time.time() < deadline:
                fleet.poll_now()
                if r_slow.summary.get("load", 0) >= 1:
                    break
            assert r_slow.summary["load"] >= 1
            assert r_fast.summary["load"] == 0
            # every routed request now lands on the idle replica
            for _ in range(4):
                assert router._pick(set()) is r_fast
            before = r_slow.routed
            for _ in range(4):
                st, body = router.post("/predict", {"inputs": X})
                assert st == 200
            assert r_slow.routed == before
            for t in ts:
                t.join()
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)

    def test_equal_replicas_share_load(self, mlp):
        f = _predict_factory(mlp)
        fleet = _mkfleet([f, f])
        router = FleetRouter(fleet)
        try:
            for _ in range(6):
                st, _ = router.post("/predict", {"inputs": X})
                assert st == 200
            r0, r1 = fleet.replicas()
            # tie-break rotation: equal-score replicas both serve
            assert r0.routed == 3 and r1.routed == 3
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)

    def test_draining_replica_is_retried_elsewhere(self, mlp):
        """PR 4's 503 + Retry-After contract, finally honored by a
        peer: a draining replica's shed answers are transparently
        retried against a live replica — no client-visible failure."""
        f = _predict_factory(mlp)
        fleet = _mkfleet([f, f])
        router = FleetRouter(fleet)
        draining = fleet.replicas()[0]
        try:
            expect = None
            draining.server.drain(timeout_s=10.0)
            for _ in range(6):
                st, body = router.post("/predict", {"inputs": X})
                assert st == 200
                expect = expect or body["outputs"]
                assert body["outputs"] == expect
            m = fleet.metrics
            assert m.requests_lost == 0 and m.responses == 6
            assert m.retries >= 1      # at least one shed was rerouted
            # after a poll the drained replica leaves the eligible set
            fleet.poll_now()
            assert not draining.eligible() and draining.admitted
            assert [r.id for r in fleet.eligible()] == \
                [fleet.replicas()[1].id]
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)

    def test_dead_replica_ejected_then_readmitted(self, mlp):
        f = _predict_factory(mlp)
        fleet = _mkfleet([f, f], eject_after=2)
        router = FleetRouter(fleet)
        dead = fleet.replicas()[0]
        try:
            dead.server.stop()         # replica process "dies"
            fleet.poll_now()
            fleet.poll_now()
            assert not dead.admitted
            assert fleet.metrics.ejections == 1
            # traffic keeps flowing through the survivor
            st, _ = router.post("/predict", {"inputs": X})
            assert st == 200
            # recovery: replica comes back (new process, new port)
            new = f()
            with dead._lock:
                dead.server, dead.host, dead.port = new, new.host, new.port
            fleet.poll_now()
            assert dead.admitted and dead.eligible()
            assert fleet.metrics.readmissions == 1
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)


class TestHedging:
    def test_straggler_hedged_first_response_wins(self, mlp):
        """A deterministic straggler (seeded injector sleeps every
        device call 300 ms) is hedged after hedge_after_ms; the fast
        replica's answer wins, so no request pays the full stall."""
        inj = FaultInjector(seed=0, rates={"device_step": 1.0},
                            slow_ms={"device_step": 300.0})
        fleet = _mkfleet([_predict_factory(mlp, fault_injector=inj),
                          _predict_factory(mlp)])
        router = FleetRouter(fleet, hedge_after_ms=40.0,
                             hedge_budget_ratio=0.5,
                             hedge_budget_burst=2.0)
        n = 10
        try:
            expect = None
            t0 = time.perf_counter()
            for _ in range(n):
                st, body = router.post("/predict", {"inputs": X})
                assert st == 200
                expect = expect or body["outputs"]
                assert body["outputs"] == expect
            dt = time.perf_counter() - t0
            m = fleet.metrics
            assert m.hedges >= 1 and m.hedges_won >= 1
            assert m.hedges <= 2.0 + 0.5 * n     # budget bound
            assert m.requests_lost == 0 and m.responses == n
            # without hedging, every request on the straggler pays
            # 300ms+; with it the sequential run beats n * stall
            assert dt < n * 0.3
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)

    def test_hedge_budget_is_never_exceeded(self, mlp):
        """burst=1, ratio=0: exactly ONE hedge is ever allowed, no
        matter how slow the fleet is — hedging cannot amplify an
        overload."""
        def slow_factory():
            inj = FaultInjector(seed=0, rates={"device_step": 1.0},
                                slow_ms={"device_step": 150.0})
            return _predict_factory(_mlp(), fault_injector=inj)()
        fleet = ReplicaFleet(poll_interval_s=None)
        fleet.add(slow_factory())
        fleet.add(slow_factory())
        router = FleetRouter(fleet, hedge_after_ms=20.0,
                             hedge_budget_ratio=0.0,
                             hedge_budget_burst=1.0)
        try:
            for _ in range(4):
                st, _ = router.post("/predict", {"inputs": X})
                assert st == 200
            m = fleet.metrics
            assert m.hedges == 1                  # the single token
            assert m.hedge_budget_denied >= 1     # later wants denied
            assert m.responses == 4 and m.requests_lost == 0
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)


class TestStreamingThroughRouter:
    def test_stream_matches_direct_engine(self, tiny_lm):
        from deeplearning4j_tpu.serving import GenerationEngine
        ref_eng = GenerationEngine(tiny_lm, num_slots=1, max_seq_len=32,
                                   prompt_buckets=[8])
        ref = ref_eng.generate([1, 2, 3], max_tokens=6, seed=7,
                               timeout_ms=60_000)["tokens"]
        ref_eng.stop()
        fleet = _mkfleet([_gen_factory(tiny_lm)] * 2)
        router = FleetRouter(fleet)
        try:
            toks = [it["token"] for it in
                    router.stream("/v1/models/lm/generate",
                                  {"prompt": [1, 2, 3], "max_tokens": 6,
                                   "seed": 7, "timeout_ms": 60_000})
                    if "token" in it]
            assert toks == ref
            st, body = router.post("/v1/models/lm/generate",
                                   {"prompt": [1, 2, 3], "max_tokens": 6,
                                    "seed": 7, "timeout_ms": 60_000})
            assert st == 200 and body["tokens"] == ref
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)

    def test_midstream_disconnect_frees_replica_promptly(self, tiny_lm):
        """Satellite: a client that vanishes mid-stream THROUGH the
        router must free the backing replica's slot/blocks and drop
        its live occupancy — one layer above PR 4's engine-level
        disconnect tests."""
        fleet = _mkfleet([_gen_factory(tiny_lm)] * 2)
        router = FleetRouter(fleet)
        host, port = router.serve()
        payload = json.dumps({"prompt": [1, 2, 3], "max_tokens": 200,
                              "seed": 1, "stream": True,
                              "timeout_ms": 120_000}).encode()
        try:
            sk = socket.create_connection((host, port), timeout=30)
            sk.sendall(b"POST /v1/models/lm/generate HTTP/1.1\r\n"
                       b"Host: x\r\nContent-Type: application/json\r\n"
                       + f"Content-Length: {len(payload)}\r\n\r\n".encode()
                       + payload)
            got = b""
            while got.count(b"token") < 3:
                chunk = sk.recv(4096)
                assert chunk, "stream ended before 3 tokens"
                got += chunk
            sk.close()                 # client hangs up mid-stream

            def engines():
                return [rep.server.registry.get("lm").engine
                        for rep in fleet.replicas()]

            deadline = time.time() + 20
            while time.time() < deadline:
                if all(e.metrics.active_slots == 0 for e in engines()) \
                        and all(r.in_flight == 0
                                for r in fleet.replicas()):
                    break
                time.sleep(0.05)
            assert all(e.metrics.active_slots == 0 for e in engines())
            assert all(r.in_flight == 0 for r in fleet.replicas())
            for e in engines():
                pg = e.stats()["paged"]
                assert pg["blocks_free"] == pg["blocks_total"]
            # the freed capacity is immediately reusable
            st, body = router.post("/v1/models/lm/generate",
                                   {"prompt": [1, 2, 3], "max_tokens": 4,
                                    "seed": 2, "timeout_ms": 60_000})
            assert st == 200 and len(body["tokens"]) == 4
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)


    def test_upstream_stall_midstream_yields_inband_error(self, tiny_lm):
        """The other half of the disconnect story: the UPSTREAM
        (replica) failing mid-stream must leave the still-connected
        client a terminal in-band error chunk and a well-formed
        chunked ending — the contract the replica-direct path honors —
        not a raw truncation, and must not masquerade as a client
        disconnect. Driven by a seeded injector stalling every decode
        step past the router's socket timeout."""
        inj = FaultInjector(seed=0, rates={"device_step": 1.0},
                            slow_ms={"device_step": 2500.0})

        def factory():
            server = InferenceServer(port=0)
            g = server.register_generator(
                "lm", tiny_lm, num_slots=2, max_seq_len=32,
                prompt_buckets=[8], cache="paged", block_size=4,
                num_blocks=16, fault_injector=inj)
            g.warmup()
            return server
        fleet = ReplicaFleet(poll_interval_s=None)
        rep = fleet.add(factory())
        router = FleetRouter(fleet, timeout_s=1.0)
        host, port = router.serve()
        payload = json.dumps({"prompt": [1, 2, 3], "max_tokens": 20,
                              "seed": 3, "stream": True,
                              "timeout_ms": 120_000}).encode()
        try:
            sk = socket.create_connection((host, port), timeout=30)
            sk.sendall(b"POST /v1/models/lm/generate HTTP/1.1\r\n"
                       b"Host: x\r\nContent-Type: application/json\r\n"
                       + f"Content-Length: {len(payload)}\r\n\r\n".encode()
                       + payload)
            got = b""
            while not got.endswith(b"0\r\n\r\n"):
                chunk = sk.recv(4096)
                assert chunk, f"truncated stream: {got[-120:]!r}"
                got += chunk
            sk.close()
            # the prefill's first token streamed before the stall...
            assert got.count(b'"token"') >= 1
            # ...and the stall surfaced as the terminal in-band error
            lines = [l for l in got.split(b"\r\n") if l.startswith(b"{")]
            last = json.loads(lines[-1])
            assert last.get("done") is True
            assert "error" in last, last
            # the router released its in-flight count promptly
            deadline = time.time() + 10
            while rep.in_flight and time.time() < deadline:
                time.sleep(0.05)
            assert rep.in_flight == 0
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)


class TestRollingRestart:
    def test_zero_loss_bit_identical_predict(self, mlp):
        """The acceptance bar: with requests in flight against a
        3-replica fleet, draining + restarting EVERY replica in
        sequence loses zero accepted requests and every response is
        bit-identical to the restart-free answer."""
        expected = {}
        for i in range(6):
            x = (np.arange(4, dtype=np.float32) + i).reshape(1, 4)
            expected[i] = json.loads(json.dumps(
                np.asarray(mlp.output(x)).tolist()))
        f = _predict_factory(mlp)
        fleet = _mkfleet([f, f, f], poll_interval_s=0.05)
        router = FleetRouter(fleet, hedge_after_ms=500.0,
                             hedge_budget_ratio=0.1,
                             hedge_budget_burst=2.0)
        stop = threading.Event()
        failures = []
        counts = [0] * 6

        def client(i):
            x = (np.arange(4, dtype=np.float32) + i).reshape(1, 4)
            payload = {"inputs": x.tolist(), "timeout_ms": 60_000}
            while not stop.is_set():
                try:
                    st, body = router.post("/predict", payload)
                except Exception as e:   # noqa: BLE001
                    failures.append(repr(e))
                    continue
                if st != 200:
                    failures.append((i, st, body))
                elif body["outputs"] != expected[i]:
                    failures.append((i, "mismatch", body["outputs"]))
                else:
                    counts[i] += 1
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        try:
            for t in threads:
                t.start()
            time.sleep(0.3)            # traffic is rolling
            ok = fleet.rolling_restart(drain_timeout_s=30.0,
                                       ready_timeout_s=120.0)
            time.sleep(0.3)            # traffic outlives the restarts
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60)
            router.stop()
            fleet.stop(stop_replicas=True)
        assert ok, "a replica failed to drain/return ready"
        assert not failures, failures[:5]
        assert all(c > 0 for c in counts)
        m = fleet.metrics
        assert m.restarts == 3
        assert m.requests_lost == 0
        assert m.requests == m.responses

    def test_zero_loss_predict_over_the_routers_listener(self, mlp):
        """The same bar through the router's HTTP listener and
        keep-alive client sockets: every 503 a draining replica emits
        is absorbed by the router's retry path, so a client sees no
        non-200 and the router loses nothing. Outputs are compared
        within tolerance: coalescing pads requests into varying batch
        buckets, and reductions across shapes are not bit-stable."""
        xs = [(np.arange(4, dtype=np.float32) + i).reshape(1, 4)
              for i in range(6)]
        expected = [np.asarray(mlp.output(x)) for x in xs]
        f = _predict_factory(mlp)
        fleet = _mkfleet([f, f, f], poll_interval_s=0.05)
        router = FleetRouter(fleet)
        host, port = router.serve()
        stop = threading.Event()
        failures = []
        counts = [0] * 6

        def client(i):
            body = json.dumps({"inputs": xs[i].tolist(),
                               "timeout_ms": 60_000}).encode()
            conn = http.client.HTTPConnection(host, port, timeout=60)
            try:
                while not stop.is_set():
                    conn.request("POST", "/predict", body=body)
                    r = conn.getresponse()
                    data = r.read()
                    if r.status != 200:
                        failures.append((i, r.status, data[:200]))
                    elif not np.allclose(json.loads(data)["outputs"],
                                         expected[i], rtol=1e-4,
                                         atol=1e-6):
                        failures.append((i, "mismatch", data[:200]))
                    else:
                        counts[i] += 1
            except Exception as e:   # noqa: BLE001 — record, never a
                failures.append((i, repr(e)))   # silently dead client
            finally:
                conn.close()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        try:
            for t in threads:
                t.start()
            time.sleep(0.3)            # traffic is rolling
            ok = fleet.rolling_restart(drain_timeout_s=30.0,
                                       ready_timeout_s=120.0)
            time.sleep(0.3)            # traffic outlives the restarts
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60)
            router.stop()
            fleet.stop(stop_replicas=True)
        assert ok, "a replica failed to drain/return ready"
        assert not failures, failures[:5]
        assert all(c > 0 for c in counts), counts
        assert fleet.metrics.restarts == 3
        assert fleet.metrics.requests_lost == 0

    def test_zero_loss_token_identical_generation(self, tiny_lm):
        """Fleet-wide extension of recompute-recovery's guarantee for
        GENERATION: rolling-restarting all replicas under live
        generate traffic loses nothing, and per-seed outputs are
        token-identical to a restart-free engine."""
        from deeplearning4j_tpu.serving import GenerationEngine
        ref_eng = GenerationEngine(tiny_lm, num_slots=1, max_seq_len=32,
                                   prompt_buckets=[8])
        ref = {s: ref_eng.generate([1 + s, 2, 3], max_tokens=6, seed=s,
                                   timeout_ms=60_000)["tokens"]
               for s in range(4)}
        ref_eng.stop()
        f = _gen_factory(tiny_lm)
        fleet = _mkfleet([f, f, f], poll_interval_s=0.05)
        router = FleetRouter(fleet)
        stop = threading.Event()
        failures = []
        done = [0] * 4

        def client(s):
            payload = {"prompt": [1 + s, 2, 3], "max_tokens": 6,
                       "seed": s, "timeout_ms": 60_000}
            while not stop.is_set():
                try:
                    st, body = router.post("/v1/models/lm/generate",
                                           payload)
                except Exception as e:   # noqa: BLE001
                    failures.append(repr(e))
                    continue
                if st != 200:
                    failures.append((s, st, body))
                elif body["tokens"] != ref[s]:
                    failures.append((s, "mismatch", body["tokens"]))
                else:
                    done[s] += 1
        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(4)]
        try:
            for t in threads:
                t.start()
            time.sleep(0.3)
            ok = fleet.rolling_restart(drain_timeout_s=30.0,
                                       ready_timeout_s=120.0)
            time.sleep(0.3)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=120)
            router.stop()
            fleet.stop(stop_replicas=True)
        assert ok
        assert not failures, failures[:5]
        assert all(c > 0 for c in done)
        assert fleet.metrics.restarts == 3
        assert fleet.metrics.requests_lost == 0


class TestFleetHTTP:
    def test_probes_and_stats(self, mlp):
        f = _predict_factory(mlp)
        fleet = _mkfleet([f, f])
        router = FleetRouter(fleet)
        host, port = router.serve()
        base = f"http://{host}:{port}"
        try:
            hz = json.loads(urllib.request.urlopen(
                base + "/healthz", timeout=30).read())
            assert hz["status"] == "ok"
            rz = json.loads(urllib.request.urlopen(
                base + "/readyz", timeout=30).read())
            assert rz["ready"] is True
            models = json.loads(urllib.request.urlopen(
                base + "/v1/models", timeout=30).read())
            assert "default" in models
            st, _ = router.post("/predict", {"inputs": X})
            assert st == 200
            stats = json.loads(urllib.request.urlopen(
                base + "/stats", timeout=30).read())["fleet"]
            assert stats["responses"] >= 1
            assert len(stats["replicas"]) == 2
            for rep in stats["replicas"]:
                assert {"id", "address", "eligible", "in_flight",
                        "requests_routed", "score"} <= set(rep)
            # readiness follows the eligible set
            for rep in fleet.replicas():
                fleet.cordon(rep.id)
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(base + "/readyz", timeout=30)
            assert exc.value.code == 503
            assert exc.value.headers.get("Retry-After")
            for rep in fleet.replicas():
                fleet.uncordon(rep.id)
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)

    def test_no_replicas_is_shed_not_crash(self):
        fleet = ReplicaFleet(poll_interval_s=None)
        router = FleetRouter(fleet)
        try:
            st, body = router.post("/predict", {"inputs": X})
            assert st == 503 and "error" in body
            assert fleet.metrics.requests_lost == 1
        finally:
            router.stop()
            fleet.stop()


class _FlipServer:
    """Minimal stdlib HTTP replica that answers POST /predict with 503
    + Retry-After while ``mode == "shed"`` and 200 once flipped —
    the router-side backpressure loop's test double. ``hits`` counts
    requests that actually REACHED the socket, so a cooldown test can
    prove the router never contacted a cooling replica."""

    def __init__(self, retry_after="0"):
        import http.server
        outer = self

        class H(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length",
                                                     0) or 0))
                outer.hits += 1
                if outer.mode == "shed":
                    body = json.dumps({"error": "shedding"}).encode()
                    self.send_response(503)
                    self.send_header("Retry-After", outer.retry_after)
                else:
                    body = json.dumps({"outputs": [[0.0]]}).encode()
                    self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # noqa: N802 — stdlib name
                pass

        self.mode = "shed"
        self.retry_after = retry_after
        self.hits = 0
        self.httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_address[1]
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


class TestBackpressure:
    """Satellite + tentpole (ISSUE 9): Retry-After honored as a
    router-side eligibility cooldown (a shedding replica is NOT routed
    straight back to), and the consecutive-shed circuit breaker —
    distinct from health ejection — with its closed -> open ->
    half_open -> closed lifecycle."""

    def _rep(self, **fleet_kw):
        fleet = ReplicaFleet(poll_interval_s=None, **fleet_kw)
        # never contacted: these tests drive note_shed/note_ok directly
        rep = fleet.add(host="127.0.0.1", port=9)
        return fleet, rep

    def test_breaker_trips_after_consecutive_sheds(self):
        fleet, rep = self._rep(breaker_threshold=3, breaker_open_s=60.0)
        try:
            for i in range(2):
                fleet.note_shed(rep, retry_after_s=0)
                assert rep.breaker_state() == "closed"
                assert fleet.routable(rep)          # strikes, not open
            fleet.note_shed(rep, retry_after_s=0)   # third strike
            assert rep.breaker_state() == "open"
            assert not fleet.routable(rep)
            assert fleet.metrics.breaker_trips == 1
            assert fleet.metrics.sheds == 3
            # open is a BREAKER state, not a health state: the replica
            # is still admitted/eligible, just not routable
            assert rep.eligible()
            assert fleet.metrics.ejections == 0
            snap = rep.snapshot()
            assert snap["breaker"] == "open"
            assert snap["consecutive_sheds"] == 3
        finally:
            fleet.stop()

    def test_half_open_single_probe_then_recovery(self):
        fleet, rep = self._rep(breaker_threshold=2, breaker_open_s=0.15)
        try:
            fleet.note_shed(rep, retry_after_s=0)
            fleet.note_shed(rep, retry_after_s=0)
            assert rep.breaker_state() == "open"
            time.sleep(0.2)
            assert rep.breaker_state() == "half_open"
            assert fleet.routable(rep)              # probe slot open
            assert fleet.claim_probe(rep)           # first probe wins
            assert not fleet.claim_probe(rep)       # one per window
            assert not fleet.routable(rep)          # slot now claimed
            fleet.note_ok(rep)                      # probe succeeded
            assert rep.breaker_state() == "closed"
            assert fleet.routable(rep)
            assert rep.consecutive_sheds == 0
            assert fleet.metrics.breaker_probes == 1
            assert fleet.metrics.breaker_recoveries == 1
        finally:
            fleet.stop()

    def test_failed_probe_reopens_breaker(self):
        fleet, rep = self._rep(breaker_threshold=2, breaker_open_s=0.15)
        try:
            fleet.note_shed(rep, retry_after_s=0)
            fleet.note_shed(rep, retry_after_s=0)
            time.sleep(0.2)
            assert fleet.claim_probe(rep)
            fleet.note_shed(rep, retry_after_s=0)   # probe answered 503
            assert rep.breaker_state() == "open"    # window re-opened
            assert not fleet.routable(rep)
            assert fleet.metrics.breaker_trips == 1  # no double-count
        finally:
            fleet.stop()

    def test_retry_after_cooldown_is_capped(self):
        fleet, rep = self._rep(cooldown_cap_s=0.15,
                               breaker_threshold=100)
        try:
            fleet.note_shed(rep, retry_after_s=9999)
            assert not fleet.routable(rep)
            time.sleep(0.2)                          # past the cap
            assert fleet.routable(rep)
            # malformed Retry-After falls back to a finite default
            fleet.note_shed(rep, retry_after_s="soon")
            assert not fleet.routable(rep)
            assert fleet.metrics.cooldowns == 2
        finally:
            fleet.stop()

    def test_stale_ok_does_not_clear_fresh_cooldown(self):
        """A 200 for a request dispatched BEFORE the shed landed is
        stale evidence: under concurrency an in-flight request
        completing right after a shed must not cancel the fresh
        cooldown (or close the breaker) and route traffic straight
        back at the overloaded replica."""
        fleet, rep = self._rep(breaker_threshold=1)
        try:
            t_before = time.monotonic()
            time.sleep(0.01)
            fleet.note_shed(rep, retry_after_s=30)
            assert not fleet.routable(rep)
            assert rep.breaker_state() == "open"
            fleet.note_ok(rep, dispatched_at=t_before)   # stale answer
            assert not fleet.routable(rep)               # still cooling
            assert rep.breaker_state() == "open"
            assert rep.consecutive_sheds == 1
            assert fleet.metrics.breaker_recoveries == 0
            # an answer to a request dispatched AFTER the shed is
            # real evidence of recovery
            fleet.note_ok(rep, dispatched_at=time.monotonic())
            assert fleet.routable(rep)
            assert rep.breaker_state() == "closed"
            assert fleet.metrics.breaker_recoveries == 1
        finally:
            fleet.stop()

    def test_non_2xx_answers_are_not_recovery(self):
        """Only a 2xx proves the replica is serving again: a 500/404
        passing through the router must leave the cooldown and the
        shed streak untouched."""
        fleet, rep = self._rep(breaker_threshold=100)
        router = FleetRouter(fleet)
        try:
            fleet.note_shed(rep, retry_after_s=30)
            assert not fleet.routable(rep)
            router._note(rep, 500, {}, time.monotonic())
            router._note(rep, 404, {}, time.monotonic())
            assert not fleet.routable(rep)               # cooldown holds
            assert rep.consecutive_sheds == 1
            router._note(rep, 200, {}, time.monotonic())
            assert fleet.routable(rep)
            assert rep.consecutive_sheds == 0
        finally:
            router.stop()
            fleet.stop()

    def test_rebuilt_replica_starts_with_clean_slate(self):
        fleet, rep = self._rep(breaker_threshold=1)
        try:
            fleet.note_shed(rep, retry_after_s=30)
            assert rep.breaker_state() == "open"
            rep.reset_backpressure()                 # rolling restart
            assert rep.breaker_state() == "closed"
            assert fleet.routable(rep)
            assert rep.consecutive_sheds == 0
        finally:
            fleet.stop()

    def test_router_honors_retry_after_cooldown_then_expiry(self):
        """Bugfix (satellite): a 503 + Retry-After must take the
        replica OUT of the routable set for the advertised window —
        the next request is not sent straight back to it (the socket
        sees no contact at all) — and the cooldown EXPIRES: once the
        window passes the replica is routed to again."""
        flip = _FlipServer(retry_after="0.3")
        fleet = ReplicaFleet(poll_interval_s=None, breaker_threshold=100)
        router = FleetRouter(fleet)
        try:
            rep = fleet.add(host="127.0.0.1", port=flip.port)
            st, _ = router.post("/predict", {"inputs": X})
            assert st == 503                        # the shed passes up
            assert flip.hits == 1
            assert not fleet.routable(rep)          # cooling
            st, body = router.post("/predict", {"inputs": X})
            assert st == 503 and "error" in body
            assert flip.hits == 1                   # NEVER re-contacted
            assert fleet.metrics.sheds == 1
            time.sleep(0.4)                         # cooldown expired
            flip.mode = "ok"
            st, body = router.post("/predict", {"inputs": X})
            assert st == 200 and body["outputs"] == [[0.0]]
            assert flip.hits == 2
            assert fleet.routable(rep)              # note_ok cleared it
            assert rep.consecutive_sheds == 0
            snap = fleet.snapshot()
            assert snap["sheds"] == 1
            assert snap["cooldowns"] == 1
            assert 0.0 < snap["goodput"] <= 1.0
        finally:
            router.stop()
            fleet.stop()
            flip.stop()

    def test_breaker_opens_through_router_traffic(self):
        """End-to-end: consecutive 503s observed by the ROUTER trip
        the breaker; after the open window a half-open probe finds
        the replica recovered and traffic resumes."""
        flip = _FlipServer(retry_after="0")
        fleet = ReplicaFleet(poll_interval_s=None, breaker_threshold=3,
                             breaker_open_s=0.2)
        router = FleetRouter(fleet)
        try:
            rep = fleet.add(host="127.0.0.1", port=flip.port)
            for _ in range(3):
                st, _ = router.post("/predict", {"inputs": X})
                assert st == 503
            assert rep.breaker_state() == "open"
            assert flip.hits == 3
            st, _ = router.post("/predict", {"inputs": X})
            assert st == 503 and flip.hits == 3     # open: no contact
            flip.mode = "ok"
            time.sleep(0.25)                        # -> half_open
            st, body = router.post("/predict", {"inputs": X})
            assert st == 200                        # the probe, via _pick
            assert rep.breaker_state() == "closed"
            assert fleet.metrics.breaker_probes >= 1
            assert fleet.metrics.breaker_recoveries == 1
        finally:
            router.stop()
            fleet.stop()
            flip.stop()


class TestPriorityThroughRouter:
    """A fronted fleet drops in wherever a single replica stood, so
    the replica-level priority contract (X-Priority header classifies
    the request, unknown class -> 400) must hold THROUGH the router's
    proxy hop, not just replica-direct."""

    def test_x_priority_header_survives_proxy_hop(self, mlp):
        fleet = _mkfleet([_predict_factory(mlp)])
        router = FleetRouter(fleet)
        host, port = router.serve()

        def post(prio):
            req = urllib.request.Request(
                f"http://{host}:{port}/predict",
                data=json.dumps({"inputs": X}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Priority": prio})
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        try:
            st, out = post("batch")
            assert st == 200 and "outputs" in out
            # a bogus class must 400 at the REPLICA — if the router
            # stripped the header this would be silently admitted as
            # interactive and answer 200
            st, out = post("urgent")
            assert st == 400
            assert "priority" in out.get("error", "").lower()
            assert router.metrics.snapshot()["client_errors"] == 1
        finally:
            router.stop()
            fleet.stop(stop_replicas=True)
