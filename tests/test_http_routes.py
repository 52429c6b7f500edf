"""The HTTP route table, once, as a table.

``FleetRouter.serve`` says it answers "the same route table as a
replica"; this pins what both front ends (``serving/aio.py``:
``AioReplicaFrontend``, ``AioRouterFrontend``) answer to every route:
status and content type, over one tiny predict model and one tiny
generator. Where the two differ the table records both answers (the
router has no ``/health``). Socket edge cases (malformed heads, 431,
slow loris) are tests/test_aio_frontend.py's.
"""
import http.client
import json

import numpy as np
import pytest

from deeplearning4j_tpu.serving import (FleetRouter, InferenceServer,
                                        ReplicaFleet)
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM

JSON = "application/json"
PROM = "text/plain; version=0.0.4; charset=utf-8"
NDJSON = "application/x-ndjson"

PREDICT = {"inputs": [[1.0, 2.0, 3.0, 4.0]]}
GENERATE = {"prompt": [1, 2, 3], "max_tokens": 4, "seed": 0,
            "timeout_ms": 120_000}


class _Echo:
    """Duck-typed predict model: no jit, no compile cost."""

    def output(self, x):
        return np.asarray(x, np.float32) * 2.0


@pytest.fixture(scope="module")
def ends():
    """(host, port) of a replica and of a router in front of it."""
    lm = CausalTransformerLM(vocab_size=64, d_model=16, n_layers=1,
                             n_heads=2, max_seq_len=32, seed=0,
                             implementation="plain").init()
    srv = InferenceServer(port=0, max_batch_size=4, max_latency_ms=1.0)
    srv.register(InferenceServer.DEFAULT_MODEL, _Echo())
    srv.register_generator("lm", lm, num_slots=2, max_queue=16,
                           prompt_buckets=[8]).warmup()
    fleet = ReplicaFleet(poll_interval_s=None)
    fleet.add(srv)
    router = FleetRouter(fleet)
    try:
        yield {"replica": (srv.host, srv.port), "router": router.serve()}
    finally:
        router.stop()
        fleet.stop(stop_replicas=True)


def _body(obj) -> bytes:
    return obj if isinstance(obj, bytes) else json.dumps(obj).encode()


#: (id, method, path, request headers, body,
#:  replica's (status, content type), router's (status, content type))
ROUTES = [
    ("get-health", "GET", "/health", {}, None, (200, JSON), (404, JSON)),
    ("get-healthz", "GET", "/healthz", {}, None, (200, JSON), (200, JSON)),
    ("get-readyz", "GET", "/readyz", {}, None, (200, JSON), (200, JSON)),
    ("get-stats", "GET", "/stats", {}, None, (200, JSON), (200, JSON)),
    ("get-metrics", "GET", "/metrics", {}, None, (200, PROM), (200, PROM)),
    ("get-traces", "GET", "/debug/traces", {}, None,
     (200, JSON), (200, JSON)),
    ("get-models", "GET", "/v1/models", {}, None, (200, JSON), (200, JSON)),
    ("get-unknown", "GET", "/nope", {}, None, (404, JSON), (404, JSON)),
    ("predict", "POST", "/v1/models/default/predict", {}, PREDICT,
     (200, JSON), (200, JSON)),
    ("predict-default-route", "POST", "/predict", {}, PREDICT,
     (200, JSON), (200, JSON)),
    ("generate", "POST", "/v1/models/lm/generate", {}, GENERATE,
     (200, JSON), (200, JSON)),
    ("generate-stream", "POST", "/v1/models/lm/generate", {},
     dict(GENERATE, stream=True), (200, NDJSON), (200, NDJSON)),
    ("unknown-model", "POST", "/v1/models/ghost/predict", {}, PREDICT,
     (404, JSON), (404, JSON)),
    ("unknown-post-path", "POST", "/v1/nope", {}, PREDICT,
     (404, JSON), (404, JSON)),
    ("malformed-json", "POST", "/v1/models/default/predict", {},
     b"{not json", (400, JSON), (400, JSON)),
    ("unknown-priority", "POST", "/v1/models/default/predict",
     {"X-Priority": "urgent"}, PREDICT, (400, JSON), (400, JSON)),
    ("unknown-method", "BREW", "/healthz", {}, None,
     (501, JSON), (501, JSON)),
]


@pytest.mark.parametrize("end", ["replica", "router"])
@pytest.mark.parametrize(
    "method,path,headers,body,replica,router",
    [r[1:] for r in ROUTES], ids=[r[0] for r in ROUTES])
def test_route(ends, end, method, path, headers, body, replica, router):
    status, ctype = replica if end == "replica" else router
    host, port = ends[end]
    conn = http.client.HTTPConnection(host, port, timeout=120)
    try:
        conn.request(method, path,
                     body=None if body is None else _body(body),
                     headers={"Content-Type": JSON, **headers})
        resp = conn.getresponse()
        data = resp.read()
        assert (resp.status, resp.getheader("Content-Type")) == \
            (status, ctype), data[:200]
        if ctype == NDJSON:
            assert resp.getheader("Transfer-Encoding") == "chunked"
            lines = [json.loads(x) for x in data.splitlines()]
            assert [x["index"] for x in lines[:-1]] == [0, 1, 2, 3]
            assert lines[-1]["done"] is True
        elif ctype == JSON:
            parsed = json.loads(data)
            if status != 200:
                assert "error" in parsed
    finally:
        conn.close()
