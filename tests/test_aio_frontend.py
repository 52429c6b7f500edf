"""Event-loop HTTP front-end (ISSUE 14): the socket edge cases
(slow-loris heads, malformed request lines, oversized headers), the
zero-thread cost of idle streaming connections, keep-alive reuse under
many idle conns, and the pipelined-decode token-identity A/B.

The route table is pinned in tests/test_http_routes.py; the rest of the
serving surface (drain/readyz/SIGTERM, mid-stream disconnect through
the router, chunked framing, shed semantics) is covered by the other
serving suites, all of which go through this listener."""
import http.client
import json
import socket
import threading
import time

import pytest

from deeplearning4j_tpu.serving import GenerationEngine, InferenceServer
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM


@pytest.fixture(scope="module")
def lm():
    return CausalTransformerLM(vocab_size=64, d_model=16, n_layers=1,
                               n_heads=2, max_seq_len=32, seed=0,
                               implementation="plain").init()


class _Echo:
    """Duck-typed predict model: no jit, no compile cost."""

    def output(self, x):
        import numpy as np
        return np.asarray(x, np.float32) * 2.0


X = [[1.0, 2.0, 3.0, 4.0]]


def _predict_server(**kw):
    s = InferenceServer(port=0, max_batch_size=4, max_latency_ms=1.0,
                        **kw)
    s.register("m", _Echo())
    return s


def _post_stream_head(host, port, body: bytes):
    """Open a streaming POST, read to the end of the response head,
    and return (socket, leftover-bytes-past-the-head) — body chunks
    can ride the same packet as the head."""
    sk = socket.create_connection((host, port), timeout=30)
    sk.sendall(b"POST /v1/models/lm/generate HTTP/1.1\r\n"
               b"Host: x\r\nContent-Type: application/json\r\n"
               + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    buf = b""
    sk.settimeout(30)
    while b"\r\n\r\n" not in buf:
        d = sk.recv(4096)
        assert d, f"closed before headers: {buf!r}"
        buf += d
    assert buf.startswith(b"HTTP/1.1 200"), buf[:80]
    return sk, buf.split(b"\r\n\r\n", 1)[1]


class TestSocketEdgeCases:
    def test_partial_header_dropped_after_timeout(self):
        """Slow-loris: a head that never completes is dropped after
        header_timeout_s without a thread ever being committed, and
        the server keeps answering other clients throughout."""
        srv = _predict_server(http_header_timeout_s=0.5)
        base = f"http://{srv.host}:{srv.port}"
        try:
            sk = socket.create_connection((srv.host, srv.port),
                                          timeout=10)
            sk.sendall(b"POST /v1/models/m/predict HTTP/1.1\r\n"
                       b"Host: x\r\n")          # head never finishes
            # the server stays responsive while the loris dangles
            import urllib.request
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=10) as r:
                assert r.status == 200
            sk.settimeout(5)
            t0 = time.monotonic()
            assert sk.recv(4096) == b""          # dropped, no response
            assert time.monotonic() - t0 < 4.0
            sk.close()
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=10) as r:
                assert r.status == 200
        finally:
            srv.stop()

    def test_malformed_request_line_rejected_with_400(self):
        srv = _predict_server()
        try:
            sk = socket.create_connection((srv.host, srv.port),
                                          timeout=10)
            sk.sendall(b"GARBAGE\r\n\r\n")     # not method/target/ver
            sk.settimeout(10)
            buf = sk.recv(4096)
            assert buf.startswith(b"HTTP/1.1 400"), buf[:80]
            sk.close()
            # an unknown METHOD on a well-formed line is 501
            sk = socket.create_connection((srv.host, srv.port),
                                          timeout=10)
            sk.sendall(b"BREW /healthz HTTP/1.1\r\nHost: x\r\n\r\n")
            sk.settimeout(10)
            buf = sk.recv(4096)
            assert buf.startswith(b"HTTP/1.1 501"), buf[:80]
            sk.close()
        finally:
            srv.stop()

    def test_oversized_head_rejected_with_431(self):
        srv = _predict_server()
        try:
            sk = socket.create_connection((srv.host, srv.port),
                                          timeout=10)
            sk.sendall(b"GET /healthz HTTP/1.1\r\nHost: x\r\n")
            filler = b"X-Filler: " + b"a" * 8000 + b"\r\n"
            try:
                for _ in range(40):              # > 256 KiB of head
                    sk.sendall(filler)
                sk.sendall(b"\r\n")
            except (BrokenPipeError, ConnectionResetError):
                pass                             # reject already sent
            sk.settimeout(10)
            buf = b""
            try:
                while len(buf) < 16:
                    d = sk.recv(4096)
                    if not d:
                        break
                    buf += d
            except (ConnectionResetError, socket.timeout):
                pass
            assert buf.startswith(b"HTTP/1.1 431"), buf[:80]
            sk.close()
        finally:
            srv.stop()

    def test_keepalive_reuse_under_many_idle_conns(self):
        """Dozens of idle keep-alive conns cost the aio replica no
        threads, and a busy keep-alive client keeps getting answers
        over ONE reused socket the whole time."""
        srv = _predict_server()
        idle = []
        try:
            base_threads = threading.active_count()
            for _ in range(50):
                c = http.client.HTTPConnection(srv.host, srv.port,
                                               timeout=30)
                c.request("GET", "/healthz")
                assert c.getresponse().read()    # drain, keep open
                idle.append(c)
            assert threading.active_count() - base_threads <= 12
            busy = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=30)
            sock_id = None
            for _ in range(5):
                busy.request("POST", "/v1/models/m/predict",
                             body=json.dumps({"inputs": X}).encode())
                r = busy.getresponse()
                body = json.loads(r.read())
                assert r.status == 200
                assert body["outputs"] == [[2.0, 4.0, 6.0, 8.0]]
                # same underlying socket — keep-alive actually reused
                if sock_id is None:
                    sock_id = id(busy.sock)
                assert id(busy.sock) == sock_id
            busy.close()
        finally:
            for c in idle:
                c.close()
            srv.stop()


class TestIdleStreamCost:
    def test_idle_streams_hold_no_pool_workers(self, lm):
        """The connscale claim at test scale: N streaming requests on
        a 1-slot engine leave N-1 streams queued and idle with their
        headers already answered — and the process thread count stays
        flat, because the aio tier consumes token queues through the
        engine's stream_notify hook instead of parking a blocking
        thread per open stream."""
        srv = InferenceServer(port=0)
        g = srv.register_generator("lm", lm, num_slots=1, max_queue=64,
                                   default_timeout_ms=120_000,
                                   prompt_buckets=[8])
        g.warmup()
        body = json.dumps({"prompt": [1, 2, 3], "max_tokens": 28,
                           "stream": True, "seed": 0,
                           "timeout_ms": 120_000}).encode()
        socks = []
        try:
            base_threads = threading.active_count()
            for _ in range(24):
                socks.append(_post_stream_head(srv.host, srv.port, body))
            time.sleep(0.3)
            assert threading.active_count() - base_threads <= 12, \
                "idle open streams must not hold threads"
            # the streams are real: every one of them completes
            for sk, buf in socks:
                sk.settimeout(60)
                while not buf.endswith(b"0\r\n\r\n"):
                    d = sk.recv(65536)
                    assert d, f"truncated stream: {buf[-80:]!r}"
                    buf += d
                assert buf.count(b'"token"') == 28
        finally:
            for sk, _ in socks:
                sk.close()
            srv.stop()


class TestPipelinedDecodeIdentity:
    def test_pipeline_ab_token_identity_and_zero_recompiles(self, lm):
        """Tentpole (b) acceptance at test scale: the pipelined decode
        loop (dispatch step t+1 before syncing step t) is bitwise
        token-identical to the synchronous loop on BOTH cache
        backends, with zero post-warmup compiles either way."""
        cases = [([1, 2, 3], 6, 0.0, 0, 11),
                 ([4, 5], 8, 0.8, 8, 12),
                 ([6], 5, 0.5, 4, 13),
                 ([7, 8, 9, 10], 7, 0.9, 16, 14)]

        def run(cache, pipeline):
            kw = dict(cache="paged", block_size=4, num_blocks=32) \
                if cache == "paged" else {}
            eng = GenerationEngine(lm, num_slots=4, max_queue=16,
                                   prompt_buckets=[8],
                                   decode_pipeline=pipeline, **kw)
            eng.warmup()
            before = eng.metrics.compiles
            outs = []
            try:
                for i, (p, n, temp, topk, seed) in enumerate(cases):
                    outs.append(eng.generate(
                        p, max_tokens=n, temperature=temp, top_k=topk,
                        seed=seed, timeout_ms=60_000)["tokens"])
                assert eng.metrics.compiles == before, \
                    f"post-warmup recompile ({cache}, pipeline={pipeline})"
            finally:
                eng.stop()
            return outs

        for cache in ("slots", "paged"):
            sync = run(cache, False)
            piped = run(cache, True)
            assert piped == sync, f"tokens diverged on {cache}"
