"""Benchmark runner — prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Methodology follows the reference's own benchmark guidance
(`docs/deeplearning4j/templates/benchmark.md:16-100,165-186`): warmup
excluded, fixed realistic minibatch, ETL excluded (data pre-staged on
device), wall-clock over many iterations with sequential dependency
between steps.

HONEST TIMING CONTRACT (VERDICT r3 #1): the timed region ends with a
host fetch of the final loss (`float(np.asarray(loss))`) — because every
step consumes the previous step's params, fetching the last loss forces
the entire dependent chain to have executed on device. The harness then
applies physics gates and HARD-FAILS (exit 2, "error" in the JSON) if:
  - derived MFU > 1.0 for any model (impossible), or
  - ResNet50 batch-128 runs < 2.5x the per-iter time of batch-32
    (a 4x-larger batch that isn't ~4x slower per iter means the timer
    measured dispatch, not device execution).
Every sub-result records its final loss and, where datasets are
involved, whether the data was synthetic (datasets.*.synthetic).

Headline: ResNet50 ImageNet-shaped training throughput, batch 32,
bf16 mixed precision (the TPU-native policy: bf16 compute on the MXU,
f32 master params/loss — `nn/multilayer.py:_cdt`) on one chip —
BASELINE config 2. Extras: ResNet50 b128, f32 reference point, BERT-base
fine-tune via the TF importer (config 3), LeNet-MNIST accuracy
(config 1), Word2Vec tokens/sec (config 4), and the flash-vs-XLA
attention sweep (VERDICT r3 #3).

One process per chip: a chip belongs to the first process that touches
JAX, so THIS process never imports jax (it imports only
`deeplearning4j_tpu.flags`, which does not either) and runs each leg as
its own subprocess, strictly one after another. The training and
attention legs measure the chip and refuse to start without one; the
serving/fleet/generation legs are pinned to `JAX_PLATFORMS=cpu` and say
`platform: cpu` in what they print. A leg that exits non-zero, times
out or prints no JSON ends the run with a non-zero exit naming it —
there is no fallback leg.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

# bf16/fp32-accumulate peak matmul TFLOP/s per chip, by PJRT device_kind
# (public spec sheets; used only to derive an auditable MFU estimate).
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

_COMMON = r"""
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp
from deeplearning4j_tpu.compile_cache import place_compile_cache
place_compile_cache()

def timed_steps(run_step, n_warmup, n_timed):
    '''Run warmup, then time n_timed sequentially-dependent steps, ending
    the timed region with a host fetch of the final loss (the honest
    barrier: the last loss transitively depends on every step).'''
    loss = None
    for i in range(n_warmup):
        loss = run_step(i)
    _ = float(np.asarray(loss))  # drain warmup before starting the clock
    t0 = time.perf_counter()
    for i in range(n_timed):
        loss = run_step(n_warmup + i)
    final_loss = float(np.asarray(loss))  # forces the whole chain
    dt = time.perf_counter() - t0
    return dt, final_loss

def cost_flops(compiled):
    '''FLOPs of one call from XLA's own cost analysis (None when the
    backend reports none).'''
    cost = compiled.cost_analysis()
    c = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float((c or {}).get("flops", 0.0)) or None

def emit(model, batch, n, dt, final_loss, flops=None, **kw):
    d = jax.devices()[0]
    print(json.dumps({
        "samples_per_sec": n * batch / dt,
        "ms_per_iter": 1000 * dt / n,
        "final_loss": final_loss,
        "platform": d.platform,
        "device_kind": d.device_kind,
        "model": model,
        "flops_per_step": flops,
        **kw}))
"""

RESNET_CODE = _COMMON + r"""
from deeplearning4j_tpu.flags import flags as _flags
BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 32
DTYPE = sys.argv[2] if len(sys.argv) > 2 else "bfloat16"
N = _flags.bench_iters or (int(sys.argv[3]) if len(sys.argv) > 3 else 20)
from deeplearning4j_tpu.zoo.resnet import ResNet50
model = ResNet50(num_classes=1000, seed=0).init()
if DTYPE != "float32":
    model.conf.dtype = DTYPE  # mixed precision: bf16 compute, f32 master
rs = np.random.RandomState(0)
x = jnp.asarray(rs.rand(BATCH, 224, 224, 3).astype(np.float32))
y = jnp.asarray(np.eye(1000, dtype=np.float32)[rs.randint(0, 1000, BATCH)])
inputs = model._as_inputs(x)
labels = model._as_labels(y)
masks = model._as_masks(None)
step = model._make_step()
rng = jax.random.PRNGKey(0)
state = [model._params, model._opt_state, model._net_state]
_t0 = time.perf_counter()
step = step.lower(state[0], state[1], state[2], jnp.asarray(0),
                  inputs, labels, masks, rng).compile()
compile_s = round(time.perf_counter() - _t0, 1)
flops = cost_flops(step)

def run_step(i):
    state[0], state[1], state[2], loss = step(
        state[0], state[1], state[2], jnp.asarray(i), inputs, labels,
        masks, rng)
    return loss

dt, final_loss = timed_steps(run_step, 3, N)
emit(f"ResNet50-224 train (batch {BATCH}, {DTYPE})", BATCH, N, dt,
     final_loss, flops, dtype=DTYPE, synthetic_data=True,
     compile_seconds=compile_s)
"""

BERT_CODE = _COMMON + r"""
import os
CACHE = os.path.join(os.getcwd(), ".bench_cache")
os.makedirs(CACHE, exist_ok=True)
PB = os.path.join(CACHE, "bert_base_s128.pb")
SEQ, BATCH, NCLS, VOCAB = 128, 32, 2, 1000
if not os.path.exists(PB):
    from deeplearning4j_tpu.interop.tf_bert import build_frozen_bert
    graph_bytes, meta = build_frozen_bert(
        vocab=VOCAB, seq_len=SEQ, n_classes=NCLS, preset="base", seed=0)
    with open(PB, "wb") as f:
        f.write(graph_bytes)

from deeplearning4j_tpu.modelimport import TFGraphMapper
from deeplearning4j_tpu.autodiff.samediff import TrainingConfig
from deeplearning4j_tpu.learning import Adam

sd = TFGraphMapper.import_graph(PB)
out = [v.name for v in sd.variables()][-1]
for v in list(sd.variables()):
    arr = sd._values.get(v.name)
    if arr is not None and hasattr(arr, "ndim") and \
        np.asarray(arr).dtype == np.float32 and np.asarray(arr).size > 2:
        sd.convert_to_variable(v.name)
labels = sd.placeholder("labels", (None, NCLS))
probs = sd.get_variable(out)
lp = probs.clipbyvalue(1e-7, 1.0).log()
loss = (labels * lp).reduce_sum(axes=(-1,)).reduce_mean().neg()
sd.set_loss_variables(loss.name)
DTYPE = sys.argv[1] if len(sys.argv) > 1 else "bfloat16"
sd.set_training_config(TrainingConfig(
    updater=Adam(2e-5), data_set_feature_mapping=["ids", "mask"],
    data_set_label_mapping=["labels"],
    compute_dtype=None if DTYPE == "float32" else DTYPE))
sd.initialize_training()
step = sd._train_step_fn()
tnames = tuple(sd._trainable())
tvars = {n: sd._values[n] for n in tnames}
needed = sd._loss_fn(tnames).needed
nondiff = {k: v for k, v in sd._values.items()
           if k not in tnames and k in needed}
rs = np.random.RandomState(0)
feed = dict(nondiff)
feed["ids"] = jnp.asarray(rs.randint(0, VOCAB, (BATCH, SEQ)), jnp.int32)
feed["mask"] = jnp.asarray(np.ones((BATCH, SEQ), np.int32))
feed["labels"] = jnp.asarray(
    np.eye(NCLS, dtype=np.float32)[rs.randint(0, NCLS, BATCH)])
rng = jax.random.PRNGKey(0)
state = [tvars, sd._updater_state]
_t0 = time.perf_counter()
compiled = step.lower(state[0], state[1], 0, feed, rng).compile()
compile_s = round(time.perf_counter() - _t0, 1)
flops = cost_flops(compiled)

def run_step(i):
    state[0], state[1], lv = compiled(state[0], state[1], i, feed, rng)
    return lv

from deeplearning4j_tpu.flags import flags as _flags
N = _flags.bench_iters or 15
dt, final_loss = timed_steps(run_step, 3, N)
emit(f"BERT-base-s{SEQ} TF-import fine-tune (batch {BATCH}, {DTYPE})",
     BATCH, N, dt, final_loss, flops, dtype=DTYPE,
     synthetic_data=True, compile_seconds=compile_s)
"""

LENET_CODE = _COMMON + r"""
import os
from deeplearning4j_tpu.datasets import MnistDataSetIterator
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer,
                                          OutputLayer, SubsamplingLayer)

BATCH = 128
conf = (NeuralNetConfiguration.builder().seed(123).updater(Adam(1e-3))
        .weight_init("relu").list()
        .layer(ConvolutionLayer(n_out=20, kernel=(5, 5), activation="relu"))
        .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
        .layer(ConvolutionLayer(n_out=50, kernel=(5, 5), activation="relu"))
        .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
        .layer(DenseLayer(n_out=500, activation="relu"))
        .layer(OutputLayer(n_out=10, loss="mcxent", activation="softmax"))
        .input_type_convolutional(28, 28, 1).build())
model = MultiLayerNetwork(conf).init()
it = MnistDataSetIterator(batch=BATCH, train=True, flatten=False,
                          num_examples=4096, shuffle=False)
synthetic = bool(it.synthetic)
source = getattr(it, "source", "synthetic" if synthetic else "mnist")
batches = [(jnp.asarray(b[0]), jnp.asarray(b[1])) for b in it]
step = model._make_step()
rng = jax.random.PRNGKey(0)
state = [model._params, model._opt_state, model._net_state]

def run_step(i):
    x, y = batches[i % len(batches)]
    state[0], state[1], state[2], loss = step(
        state[0], state[1], state[2], jnp.asarray(i), x, y, None, rng)
    return loss

from deeplearning4j_tpu.flags import flags as _flags
N = _flags.bench_iters or 60
dt, final_loss = timed_steps(run_step, 3, N)
# accuracy check (BASELINE config 1: >=0.98 on the real test set)
model._params, model._opt_state, model._net_state = state
model._jit_step = step
train_it = MnistDataSetIterator(batch=BATCH, train=True, flatten=False)
# enough epochs to hit the >=0.98 bar on the small real-digits split
# (the vendored fixture is 1,437 train / 360 test samples); full MNIST
# and the big synthetic fallback get one epoch as before
model.fit(train_it, epochs=8 if source == "real-digits-8x8" else 1)
test_it = MnistDataSetIterator(batch=512, train=False, flatten=False)
acc = model.evaluate(test_it).accuracy()
emit("LeNet-MNIST train (batch 128)", BATCH, N, dt, final_loss,
     test_accuracy=round(float(acc), 4), synthetic_data=synthetic,
     data_source=source)
"""

ATTENTION_CODE = _COMMON + r"""
# flash (Pallas) vs plain fused-XLA attention, train-step wall-clock
# (fwd+bwd through the kernel), with and without key-padding masks.
from deeplearning4j_tpu.kernels import flash_attention
from deeplearning4j_tpu.parallel.longseq import dot_product_attention

B, H, D = 4, 8, 64
# T list overridable for the CPU harness smoke (tiny sizes): the sweep
# itself must be known-good BEFORE the first real chip window
Ts = tuple(int(t) for t in sys.argv[1:]) or (512, 2048, 8192)
results = {}
for T in Ts:
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.rand(B, T, H, D).astype(np.float32)) * 0.1
    k = jnp.asarray(rs.rand(B, T, H, D).astype(np.float32)) * 0.1
    v = jnp.asarray(rs.rand(B, T, H, D).astype(np.float32)) * 0.1
    lens = np.full(B, T, np.int32); lens[: B // 2] = int(T * 0.75)
    pad_mask = jnp.asarray(np.arange(T)[None, :] < lens[:, None],
                           jnp.float32)
    for name, fn, use_mask in (
            ("flash", lambda q, k, v, m: flash_attention(
                q, k, v, causal=True, key_mask=m), False),
            ("xla", lambda q, k, v, m: dot_product_attention(
                q, k, v, causal=True), False),
            ("flash_masked", lambda q, k, v, m: flash_attention(
                q, k, v, causal=True, key_mask=m), True),
            ("xla_masked", lambda q, k, v, m: dot_product_attention(
                q, k, v, mask=None if m is None else
                m[:, None, None, :] > 0, causal=True), True)):
        m = pad_mask if use_mask else None

        @jax.jit
        def train_step(q, k, v, m=m, fn=fn):
            def loss_fn(q, k, v):
                return jnp.sum(fn(q, k, v, m) ** 2)
            l, g = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(q, k, v)
            return l, g

        try:
            loss = None
            qc = q
            for _ in range(2):
                loss, grads = train_step(qc, k, v)
            _ = float(np.asarray(loss))
            NIT = 10 if T <= 2048 else 5
            t0 = time.perf_counter()
            for _ in range(NIT):
                loss, grads = train_step(qc, k, v)
                # chain: next step's input depends on this step's grads,
                # so the final host fetch forces every timed execution
                # (same honest-timing contract as timed_steps)
                qc = qc + 0.0 * grads[0]
            _ = float(np.asarray(loss))
            dt = time.perf_counter() - t0
            results[f"T{T}_{name}"] = round(1000 * dt / NIT, 3)
        except Exception as e:
            results[f"T{T}_{name}"] = f"fail: {type(e).__name__}"
d = jax.devices()[0]
print(json.dumps({"model": "attention fwd+bwd ms/step (B4 H8 D64)",
                  "platform": d.platform, "device_kind": d.device_kind,
                  "results": results}))
"""

ETL_CODE = _COMMON + r"""
# ETL pipeline throughput, reported SEPARATELY from model benches per the
# reference's own methodology (benchmark.md: 'ETL measured separately via
# PerformanceListener'): CSV -> schema transform -> batched DataSets.
import os, tempfile, time
from deeplearning4j_tpu.etl import CSVRecordReader
from deeplearning4j_tpu.etl.iterators import RecordReaderDataSetIterator

N_ROWS, N_FEAT = 200_000, 20
rs = np.random.RandomState(0)
data = rs.rand(N_ROWS, N_FEAT).astype(np.float32)
labels = rs.randint(0, 5, (N_ROWS, 1))
with tempfile.TemporaryDirectory() as td:
    path = os.path.join(td, "data.csv")
    np.savetxt(path, np.hstack([data, labels]), delimiter=",", fmt="%.6f")
    t0 = time.perf_counter()
    reader = CSVRecordReader(path)
    it = RecordReaderDataSetIterator(reader, batch_size=512,
                                     label_index=N_FEAT, num_classes=5)
    n = 0
    for feats, _labels in it:
        n += np.asarray(feats).shape[0]
    dt = time.perf_counter() - t0
print(json.dumps({"model": "ETL CSV->DataSet pipeline",
                  "rows_per_sec": round(n / dt, 1), "rows": n,
                  "wall_seconds": round(dt, 2)}))
"""

SERVING_CODE = _COMMON + r"""
# Serving-runtime scenario: 32 concurrent HTTP clients against one MLP,
# dynamic micro-batching (serving/ subsystem) vs the SEED per-request
# path (a minimal handler calling model.output(x) per request — the
# pre-subsystem InferenceServer behavior, reproduced inline so the
# baseline stays honest as the real server evolves). CPU-JAX: the model
# is sized so batch-1 inference is weight-streaming-bound (H=4096 f32,
# ~140MB/request), which is exactly the regime dynamic batching exists
# for — a batched GEMM reads the weights once per 32 rows.
import threading, urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.serving import InferenceServer

N_CLIENTS, N_REQ = 32, int(sys.argv[2]) if len(sys.argv) > 2 else 8
HIDDEN = int(sys.argv[1]) if len(sys.argv) > 1 else 6144
conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3)).list()
        .layer(DenseLayer(n_out=HIDDEN, activation="relu"))
        .layer(DenseLayer(n_out=HIDDEN, activation="relu"))
        .layer(DenseLayer(n_out=HIDDEN, activation="relu"))
        .layer(OutputLayer(n_out=10, loss="mcxent", activation="softmax"))
        .input_type_feed_forward(64).build())
model = MultiLayerNetwork(conf).init()
rs = np.random.RandomState(0)
reqs = [json.dumps({"inputs": rs.randn(1, 64).astype(np.float32).tolist()})
        .encode() for _ in range(N_CLIENTS)]

def hammer(port, path, lat_ms):
    '''N_CLIENTS threads x N_REQ requests over persistent (keep-alive)
    connections; returns wall seconds.'''
    import http.client

    def client(i):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        for _ in range(N_REQ):
            t0 = time.perf_counter()
            for attempt in range(3):  # transient conn resets under load
                try:
                    conn.request("POST", path, body=reqs[i])
                    conn.getresponse().read()
                    break
                except (ConnectionError, OSError, http.client.HTTPException):
                    conn.close()
                    conn = http.client.HTTPConnection("127.0.0.1", port,
                                                      timeout=120)
                    if attempt == 2:
                        raise
            lat_ms.append((time.perf_counter() - t0) * 1e3)
        conn.close()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(N_CLIENTS)]
    t0 = time.perf_counter()
    for t in threads: t.start()
    for t in threads: t.join()
    return time.perf_counter() - t0

def pct(xs, p):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(round(p / 100.0 * (len(xs) - 1))))]

# -- seed per-request baseline (one unbatched model.output per request)
class SeedHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"  # same transport as the real server
    def log_message(self, *a): pass
    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        req = json.loads(self.rfile.read(n))
        y = np.asarray(model.output(np.asarray(req["inputs"], np.float32)))
        body = json.dumps({"outputs": y.tolist()}).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

class SeedServer(ThreadingHTTPServer):
    request_queue_size = 128  # match the real server's backlog
    daemon_threads = True

seed_httpd = SeedServer(("127.0.0.1", 0), SeedHandler)
seed_port = seed_httpd.server_address[1]
threading.Thread(target=seed_httpd.serve_forever, daemon=True).start()
_ = hammer(seed_port, "/predict", [])  # warmup (compile + caches)
seed_lat = []
seed_dt = hammer(seed_port, "/predict", seed_lat)
seed_httpd.shutdown(); seed_httpd.server_close()

# -- dynamic batcher
server = InferenceServer(model, port=0, max_batch_size=32,
                         max_latency_ms=60.0, max_queue=512,
                         warmup_buckets=[1, 2, 4, 8, 16, 32])
_ = hammer(server.port, "/predict", [])  # warmup pass
bat_lat = []
bat_dt = hammer(server.port, "/predict", bat_lat)
stats = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{server.port}/stats", timeout=10).read())
m = stats["models"]["default"]
server.stop()

n = N_CLIENTS * N_REQ
emit(f"Serving MLP-{HIDDEN} dynamic batching ({N_CLIENTS} clients)",
     1, n, bat_dt, None,
     requests_per_sec=round(n / bat_dt, 1),
     unbatched_requests_per_sec=round(n / seed_dt, 1),
     speedup_vs_unbatched=round(seed_dt / bat_dt, 2),
     p50_ms=round(pct(bat_lat, 50), 2), p99_ms=round(pct(bat_lat, 99), 2),
     unbatched_p50_ms=round(pct(seed_lat, 50), 2),
     unbatched_p99_ms=round(pct(seed_lat, 99), 2),
     mean_device_batch=m["mean_batch"], batch_hist=m["batch_hist"],
     compiles=m["compile_cache"]["compiles"],
     recompiles_post_warmup=m["compile_cache"]["compiles"]
     - len(m["compile_cache"]["warmed_buckets"]),
     synthetic_data=True)
"""

GENERATION_CODE = _COMMON + r"""
# Continuous-batching generation scenario (ISSUE 2 acceptance): >=16
# concurrent mixed-length generate requests through the slot-based
# decode engine vs SEQUENTIAL PER-REQUEST DECODE — the pre-subsystem
# path: one request at a time, each token re-running the full prefix
# through the model (the only generation the repo supported before the
# KV-cache slots existed), bucket-padded to power-of-two lengths with
# each bucket AOT-compiled once, so the baseline pays zero mid-run
# compiles — the same courtesy PR 1's serving bench gave the seed
# handler. The subsystem's two wins compose against it: the static-
# slot KV cache (O(prefix) -> O(1) work per token) and iteration-level
# scheduling (per-step host/dispatch overhead amortized across slots).
# A second reference — the SAME engine at num_slots=1 — isolates the
# scheduling win alone and keeps the cache win honest.
import threading
from deeplearning4j_tpu.serving import GenerationEngine, next_bucket
from deeplearning4j_tpu.serving.generation import _sample_one
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM

VOCAB, DM, NL, NH, TMAX = 256, 64, 2, 4, 192
N_REQ = int(sys.argv[1]) if len(sys.argv) > 1 else 32
N_SLOTS = int(sys.argv[2]) if len(sys.argv) > 2 else 16
BUCKETS = [8, 16, 32, 64, 128, 192]
lm = CausalTransformerLM(vocab_size=VOCAB, d_model=DM, n_layers=NL,
                         n_heads=NH, max_seq_len=TMAX, seed=0,
                         implementation="plain").init()
rs = np.random.RandomState(0)
reqs = []
for i in range(N_REQ):
    plen = int(rs.choice([4, 8, 16, 32, 64]))
    n_gen = int(rs.choice([16, 32, 64, 96]))
    reqs.append((rs.randint(0, VOCAB, plen).tolist(), n_gen))

# -- baseline: uncached sequential per-request decode (pre-subsystem).
# Same sampler and same per-request PRNG stream (fold_in(seed, i) for
# token i), so its outputs are comparable token-for-token.
def build_uncached(bucket):
    def f(params, tokens, length, seed, temp, topk, step):
        mask = (jnp.arange(bucket)[None] < length).astype(jnp.float32)
        logits, _, _ = lm.forward_prefill(params, tokens, mask)
        last = jax.lax.dynamic_index_in_dim(logits[0], length - 1,
                                            axis=0, keepdims=False)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return _sample_one(last, temp, topk, key)
    return jax.jit(f).lower(
        lm._params, np.zeros((1, bucket), np.int32), np.int32(1),
        np.uint32(0), np.float32(0.0), np.int32(0), np.int32(0)).compile()

uncached = {b: build_uncached(b) for b in BUCKETS}

def uncached_generate(prompt, max_tokens, seed, temp=0.8, topk=32):
    toks = list(prompt)
    out = []
    for i in range(min(max_tokens, TMAX - len(prompt))):
        L = len(toks)
        b = next_bucket(L, BUCKETS[0], TMAX)
        arr = np.zeros((1, b), np.int32)
        arr[0, :L] = toks
        t = int(np.asarray(uncached[b](
            lm._params, arr, np.int32(L), np.uint32(seed),
            np.float32(temp), np.int32(topk), np.int32(i))))
        out.append(t)
        toks.append(t)
    return out

def run_uncached():
    t0 = time.perf_counter()
    outs = [uncached_generate(p, n, seed=i)
            for i, (p, n) in enumerate(reqs)]
    dt = time.perf_counter() - t0
    return dt, sum(len(t) for t in outs), outs

def run_all(eng, concurrent):
    '''Returns (wall_s, total_tokens, [token lists]).'''
    results = [None] * N_REQ

    def go(i):
        p, n = reqs[i]
        results[i] = eng.generate(p, max_tokens=n, temperature=0.8,
                                  top_k=32, seed=i, timeout_ms=600_000)
    t0 = time.perf_counter()
    if concurrent:
        ts = [threading.Thread(target=go, args=(i,))
              for i in range(N_REQ)]
        for t in ts: t.start()
        for t in ts: t.join()
    else:
        for i in range(N_REQ):
            go(i)
    dt = time.perf_counter() - t0
    toks = [r["tokens"] for r in results]
    return dt, sum(len(t) for t in toks), toks

run_uncached()                              # warmup pass
seq_dt, seq_tok, seq_out = run_uncached()

# cached sequential reference: same engine, one slot, one at a time
cseq_eng = GenerationEngine(lm, num_slots=1, max_queue=N_REQ + 8)
cseq_eng.warmup()
run_all(cseq_eng, concurrent=False)         # warmup pass (caches hot)
cseq_dt, cseq_tok, cseq_out = run_all(cseq_eng, concurrent=False)
cseq_eng.stop()

# continuous batching: N_SLOTS slots, all requests in flight
eng = GenerationEngine(lm, num_slots=N_SLOTS, max_queue=N_REQ * 2)
eng.warmup()
run_all(eng, concurrent=True)               # warmup pass
compiles_before = eng.metrics.compiles
cb_dt, cb_tok, cb_out = run_all(eng, concurrent=True)
recompiles = eng.metrics.compiles - compiles_before
stats = eng.stats()
dense_kv_bytes = stats["kv_cache_bytes"]

# -- traced re-run (ISSUE 10): the SAME engine and workload with a
# per-request trace recorded end to end (admission, queue, prefill,
# decode spans). The gated claim is the tokens/sec cost of tracing
# ENABLED (< 5% in acceptance; the disabled path is zero-cost by
# construction — the decode loop carries no tracing code at all).
from deeplearning4j_tpu.tracing import Tracer
tracer = Tracer(enabled=True, ring=N_REQ * 2)

def run_all_traced(eng2):
    results = [None] * N_REQ
    traces = [None] * N_REQ
    def go(i):
        p, n = reqs[i]
        tr = tracer.begin()
        results[i] = eng2.generate(p, max_tokens=n, temperature=0.8,
                                   top_k=32, seed=i, timeout_ms=600_000,
                                   trace=tr)
        tracer.finish(tr)
        traces[i] = tr
    ts = [threading.Thread(target=go, args=(i,)) for i in range(N_REQ)]
    t0 = time.perf_counter()
    for t in ts: t.start()
    for t in ts: t.join()
    dt = time.perf_counter() - t0
    toks = [r["tokens"] for r in results]
    return dt, sum(len(t) for t in toks), toks, traces

tr_dt, tr_tok, tr_out, tr_traces = run_all_traced(eng)
trace_overhead = max(0.0, (cb_tok / cb_dt) / (tr_tok / tr_dt) - 1.0)
trace_spans = sum(len(t.spans) for t in tr_traces)

# -- scheduler-overhead probe (ISSUE 13): a dedicated OpProfiler
# OPERATIONS pass over the SAME saturated continuous-batching
# workload. Device time is the sum of the profiled generation
# sections (prefill + decode_step + spec draft/verify); everything
# else in the wall clock is host-side scheduling — queue hops, slot
# bookkeeping, Python dispatch. The gated number is that host-side
# fraction of the wall clock (lower is better).
from deeplearning4j_tpu.profiler import OpProfiler, ProfilingMode
prof = OpProfiler.get_instance()
prof.reset()
prof.set_mode(ProfilingMode.OPERATIONS)
ov_dt, ov_tok, _ = run_all(eng, concurrent=True)
prof.set_mode(ProfilingMode.DISABLED)
_DEV_SECTIONS = ("generation.prefill", "generation.decode_step",
                 "generation.spec_draft", "generation.spec_verify")
sched_device_s = sum(v["total_s"] for k, v in prof.timings().items()
                     if k in _DEV_SECTIONS)
scheduler_overhead_frac = round(
    max(0.0, (ov_dt - sched_device_s) / ov_dt), 4)
prof.reset()

# -- chaos probe (ISSUE 4): the SAME engine and workload with ~1% of
# decode steps raising an injected transient fault, plus a scripted
# cache-corrupting fault (two at full scale) forcing recompute-
# recovery — every in-flight request re-prefilled from prompt +
# emitted tokens. The gated number is recovered-tokens/sec: the
# throughput the engine still delivers while absorbing faults.
# Correctness bar: token-identical to the fault-free run, zero
# requests lost, zero recompiles (recovery reuses warmed buckets).
from deeplearning4j_tpu.serving import FaultInjector
chaos_inj = FaultInjector(seed=0, rates={"device_step": 0.01},
                          plan={"prefill": [5, 20]},
                          corrupting=("prefill",))
eng.set_fault_injector(chaos_inj)
ch_compiles = eng.metrics.compiles
ch_dt, ch_tok, ch_out = run_all(eng, concurrent=True)
ch_faults = eng.stats()["faults"]
ch_recompiles = eng.metrics.compiles - ch_compiles
eng.set_fault_injector(None)
eng.stop()

# -- paged KV cache + chunked prefill (ISSUE 3). Same mixed-length
# workload through the paged backend: tokens must be identical to the
# slot engine, the measured window compile-free, and the PEAK block
# footprint is the memory the paged pool actually needed — the dense
# cache pins num_slots * T_max regardless.
paged = GenerationEngine(lm, num_slots=N_SLOTS, max_queue=N_REQ * 2,
                         cache="paged", block_size=16,
                         prompt_buckets=[32],
                         prefill_chunk_tokens=32,
                         # sharing OFF here: the measured pass replays
                         # the warmup pass's prompts, and index hits
                         # would shift this leg's historical numbers —
                         # the sharing leg below isolates the feature
                         enable_prefix_sharing=False)
paged.warmup()
run_all(paged, concurrent=True)             # warmup pass
pg_compiles_before = paged.metrics.compiles
pg_dt, pg_tok, pg_out = run_all(paged, concurrent=True)
pg_recompiles = paged.metrics.compiles - pg_compiles_before
pg_stats = paged.stats()["paged"]
blk_bytes = paged._cache.block_nbytes()
paged_peak_bytes = pg_stats["blocks_peak_used"] * blk_bytes
paged_pool_bytes = paged.metrics.cache_bytes

# -- chunked-prefill ITL probe: short requests stream while LONG
# prompts (160 tokens) land mid-stream. With chunking the decode loop
# stalls at most one 32-token chunk per iteration; without it each
# long prefill stalls decode for the whole prompt — the p95 gap of the
# short streams is the number that moves.
LONG_P = [rs.randint(0, VOCAB, 160).tolist() for _ in range(3)]

def pct(xs, p):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(round(p / 100.0 * (len(xs) - 1))))] \
        if xs else 0.0

def itl_probe(eng2, long_prompts, n_short=4, n_tok=72):
    gaps = []
    glock = threading.Lock()
    def short_client(i):
        last = None
        mine = []
        for item in eng2.stream([1 + i, 2, 3, 4], max_tokens=n_tok,
                                temperature=0.8, seed=i,
                                timeout_ms=600_000):
            now = time.perf_counter()
            if "token" in item:
                if last is not None:
                    mine.append((now - last) * 1e3)
                last = now
        with glock:
            gaps.extend(mine)
    ts = [threading.Thread(target=short_client, args=(i,))
          for i in range(n_short)]
    for t in ts: t.start()
    time.sleep(0.2)                         # decode loop is rolling
    for j, lp in enumerate(long_prompts):
        eng2.generate(lp, max_tokens=4, seed=100 + j,
                      timeout_ms=600_000)
    for t in ts: t.join()
    return gaps

base_gaps = itl_probe(paged, [])            # no-long-prompt baseline
chunk_gaps = itl_probe(paged, LONG_P)
n_chunked = paged.stats()["paged"]["chunked_prefills"]
paged.stop()

unchunked = GenerationEngine(lm, num_slots=N_SLOTS, max_queue=N_REQ * 2,
                             cache="paged", block_size=16,
                             prompt_buckets=[32],   # whole-prompt prefill
                             enable_prefix_sharing=False)
unchunked.warmup()
itl_probe(unchunked, LONG_P[:1])            # warmup pass
flat_gaps = itl_probe(unchunked, LONG_P)
unchunked.stop()

# -- prefix sharing + persistent sessions (ISSUE 11). A fleet-wide
# 64-token system prompt (4 full 16-token blocks) shared by N_USERS
# concurrent users with short unique suffixes, run through two
# otherwise-identical paged engines — sharing ON vs OFF — at the SAME
# pool bytes. Gated claims: prefill tokens executed drop >= 50%, the
# peak block footprint supports >= 2x the users at equal pool bytes,
# temp-0 tokens identical to the unshared path, measured window
# compile-free. The multi-turn leg then drives session_id
# conversations: turn N+1 re-prefills only the tokens the session
# store has not already pinned, and after eviction + drain every
# session block is reclaimed.
SYS = rs.randint(0, VOCAB, 64).tolist()
N_USERS = 12
P_USERS = [SYS + rs.randint(0, VOCAB, 8).tolist()
           for _ in range(N_USERS)]

def stream_one(e, prompt, i, n_tok, sid=None):
    '''One streamed request -> (ttft_ms, tokens).'''
    t0 = time.perf_counter()
    first = None
    toks = []
    kw = dict(max_tokens=n_tok, temperature=0.0, seed=i,
              timeout_ms=600_000)
    if sid is not None:
        kw["session_id"] = sid
    for item in e.stream(prompt, **kw):
        if "token" in item:
            if first is None:
                first = time.perf_counter()
            toks.append(item["token"])
    return (first - t0) * 1e3, toks

def prefix_burst(e):
    ttfts = [0.0] * N_USERS
    outs = [None] * N_USERS
    def go(i):
        ttfts[i], outs[i] = stream_one(e, P_USERS[i], i, 24)
    ts = [threading.Thread(target=go, args=(i,))
          for i in range(N_USERS)]
    for t in ts: t.start()
    for t in ts: t.join()
    return ttfts, outs

def mk_prefix_engine(sharing):
    e = GenerationEngine(lm, num_slots=N_SLOTS, max_queue=N_REQ * 2,
                         cache="paged", block_size=16,
                         prompt_buckets=[32], prefill_chunk_tokens=32,
                         enable_prefix_sharing=sharing)
    e.warmup()
    # prime: the first completed request is the one that REGISTERS
    # the shared prefix — run it alone so the burst sees a warm index
    e.generate(P_USERS[0], max_tokens=4, temperature=0.0, seed=999,
               timeout_ms=600_000)
    prefix_burst(e)                         # warmup pass
    return e

shr = mk_prefix_engine(True)
b_hits = shr.metrics.prefix_hits
b_matched = shr.metrics.prefix_tokens_matched
b_prefill = shr.metrics.prefill_tokens
b_compiles = shr.metrics.compiles
shr_ttfts, shr_out = prefix_burst(shr)
shr_hits = shr.metrics.prefix_hits - b_hits
shr_matched = shr.metrics.prefix_tokens_matched - b_matched
shr_prefill = shr.metrics.prefill_tokens - b_prefill
shr_recompiles = shr.metrics.compiles - b_compiles
shr_peak = shr.stats()["paged"]["blocks_peak_used"]

# multi-turn sessions on the sharing engine: each turn's prompt is
# the FULL conversation so far, but the session pin means only the
# unseen tail is prefilled. Each conversation opens with a UNIQUE
# base prompt (not SYS) so turn 1 pays a genuine cold prefill and
# the turn-1 vs turn-N gap isolates the session win from the
# prefix-index win measured above.
SESS_BASES = [rs.randint(0, VOCAB, 64).tolist() for _ in range(4)]

def run_session(e, sid, base, turns=3):
    hist = list(base)
    tf = []
    for _ in range(turns):
        hist = hist + rs.randint(0, VOCAB, 8).tolist()
        ttft, toks = stream_one(e, hist, 7, 16, sid=sid)
        tf.append(ttft)
        hist = hist + toks
    return tf

turn_ttfts = [run_session(shr, "bench-user-%d" % i, SESS_BASES[i])
              for i in range(4)]
turn1 = [t[0] for t in turn_ttfts]
turnN = [t[-1] for t in turn_ttfts]
sess_evicted = shr.evict_sessions()
shr.clear_prefix_cache()
st_after = shr.stats()["paged"]
sess_reclaimed = (st_after["blocks_free"] == st_after["blocks_total"])
shr_cow = shr.metrics.cow_copies
shr.stop()

nsh = mk_prefix_engine(False)
nb_prefill = nsh.metrics.prefill_tokens
nsh_ttfts, nsh_out = prefix_burst(nsh)
nsh_prefill = nsh.metrics.prefill_tokens - nb_prefill
nsh_peak = nsh.stats()["paged"]["blocks_peak_used"]
# same conversation shape WITHOUT sessions: every turn re-prefills
# the full history — the TTFT gap at turn N is what sessions buy
nsh_turn_ttfts = [run_session(nsh, None, SESS_BASES[i]) for i in range(4)]
nsh_turnN = [t[-1] for t in nsh_turn_ttfts]
nsh.stop()

# -- speculative decoding (ISSUE 12): single-stream decode-bound leg
# over a LONG-CONTEXT prompt mix (32/96/128-token prompts, 48
# generated tokens each), k=3 draft proposals per round verified by
# the target in one chunk-shaped forward — vs the SAME engine config,
# workload and seeds at speculation_k=0 (every other generation leg
# also runs k=0: speculation defaults off). The draft is a same-config
# copy of the target: random weights leave an independently-drawn
# small draft's proposals uncorrelated with the target's argmax
# (chance accept ~1/VOCAB), so the bench drafts with the target's own
# weights to run the accept path at a realistic rate — accept_rate is
# recorded alongside. The measured win is the dispatch collapse on a
# dispatch-bound host: k unrolled draft steps fuse into ONE device
# call plus one verify call, so an accepted round emits 1 + accept*k
# tokens for 2 dispatches where plain decode pays one dispatch per
# token — and that holds even with a draft as expensive as the target
# (a distilled cheaper draft only widens it). ITL here is the
# per-request MEAN inter-token gap (TPOT), p99 across requests: a
# round's tokens arrive together by construction, so the per-token
# gap histogram is bimodal (near-zero within a round, round-time at
# boundaries) and its percentiles compare delivery shape, not speed.
SPEC_K = 3
SPEC_REQS = []
for i in range(8):
    plen = int(rs.choice([32, 96, 128]))
    SPEC_REQS.append((rs.randint(0, VOCAB, plen).tolist(), 48))

def run_spec_leg(e):
    '''Sequential streamed pass -> (tok/s, [per-req mean ITL ms], outs).'''
    itls, outs = [], []
    t0 = time.perf_counter(); ntok = 0
    for i, (p, n) in enumerate(SPEC_REQS):
        last = None; gaps = []; toks = []
        for item in e.stream(p, max_tokens=n, temperature=0.0, seed=i,
                             timeout_ms=600_000):
            if "token" in item:
                now = time.perf_counter()
                if last is not None:
                    gaps.append((now - last) * 1e3)
                last = now
                toks.append(item["token"])
        outs.append(toks); ntok += len(toks)
        if gaps:
            itls.append(sum(gaps) / len(gaps))
    dt = time.perf_counter() - t0
    return ntok / dt, itls, outs

spec_draft = CausalTransformerLM(vocab_size=VOCAB, d_model=DM,
                                 n_layers=NL, n_heads=NH,
                                 max_seq_len=TMAX, seed=0,
                                 implementation="plain").init()

def mk_spec_engine(k):
    e = GenerationEngine(lm, num_slots=N_SLOTS, max_queue=N_REQ * 2,
                         speculation_k=k,
                         draft_model=spec_draft if k else None)
    e.warmup()
    run_spec_leg(e)                         # warmup pass
    return e

sp0 = mk_spec_engine(0)
sp0_tps, sp0_itls, sp0_out = run_spec_leg(sp0)
sp0.stop()
sp = mk_spec_engine(SPEC_K)
sp_compiles = sp.metrics.compiles
sp_tps, sp_itls, sp_out = run_spec_leg(sp)
sp_recompiles = sp.metrics.compiles - sp_compiles
sp_spec = sp.stats()["spec"]
sp.stop()

# -- quantized KV pool (ISSUE 15): the SAME fixed-shape workload at
# EQUAL POOL BYTES across kv_dtype in {f32, bf16, int8}. The byte
# budget is set by a deliberately small f32 pool (3 resident
# requests); each leg gets as many blocks as fit that budget — so the
# int8 leg's win shows up as CONCURRENT-USER CAPACITY (gated >= 2x
# f32 at equal bytes: 4x raw int8 shrink minus the f32 scale
# sidecar), with tokens/sec per dtype and the max-|logit| relative
# error vs the exact f32 cache recorded alongside. Accuracy is
# measured at the model surface (one decode step against a cache
# prefilled at each dtype), the number docs/generation.md documents
# as the quantization tolerance.
from deeplearning4j_tpu.kernels.kv_quant import (kv_nbytes,
                                                 kv_update_slice)
from deeplearning4j_tpu.serving.kvcache import KVCache
from deeplearning4j_tpu.serving.paging import blocks_for

QBS, QP, QG = 16, 32, 32
q_shapes = [tuple(s) for s in lm.cache_shapes(QBS)]
def q_block_bytes(dt):
    return int(sum(2 * kv_nbytes((1,) + s, dt) for s in q_shapes))
q_bpr = blocks_for(QP + QG, QBS)          # blocks per resident request
budget = (3 * q_bpr + 1) * q_block_bytes("f32")
q_reqs = [(rs.randint(0, VOCAB, QP).tolist(), QG) for _ in range(12)]

def run_quant_leg(dt):
    nb = budget // q_block_bytes(dt)
    cap = (nb - 1) // q_bpr               # simultaneously-resident users
    e = GenerationEngine(lm, num_slots=min(N_SLOTS, cap), max_queue=64,
                         cache="paged", block_size=QBS, num_blocks=nb,
                         prompt_buckets=[32], prefill_chunk_tokens=32,
                         enable_prefix_sharing=False, kv_dtype=dt)
    e.warmup()
    def burst():
        outs = [None] * len(q_reqs)
        def go(i):
            p, n = q_reqs[i]
            outs[i] = e.generate(p, max_tokens=n, temperature=0.0,
                                 seed=i, timeout_ms=600_000)["tokens"]
        ts = [threading.Thread(target=go, args=(i,))
              for i in range(len(q_reqs))]
        t0 = time.perf_counter()
        for t in ts: t.start()
        for t in ts: t.join()
        return time.perf_counter() - t0, outs
    burst()                               # warmup pass
    cb = e.metrics.compiles
    dt_s, outs = burst()
    rc = e.metrics.compiles - cb
    pool_bytes = e.metrics.cache_bytes
    e.stop()
    return {"users": cap, "blocks": nb, "pool_bytes": pool_bytes,
            "tps": sum(len(t) for t in outs) / dt_s, "recompiles": rc}

q_legs = {dt: run_quant_leg(dt) for dt in ("f32", "bf16", "int8")}

# model-surface accuracy: prefill a 48-token prompt into a
# single-slot cache at each dtype, one decode step, compare logits
QT = 48
q_toks = jnp.asarray(rs.randint(0, VOCAB, (1, QT)), jnp.int32)
_, q_ks, q_vs = lm.forward_prefill(lm._params, q_toks,
                                   jnp.ones((1, QT), jnp.float32))
def q_logits(dt):
    c = KVCache(lm.cache_shapes(64), 1, kv_dtype=dt)
    kcs = [kv_update_slice(kc, k, (0, 0, 0, 0))
           for kc, k in zip(c.ks, q_ks)]
    vcs = [kv_update_slice(vc, v, (0, 0, 0, 0))
           for vc, v in zip(c.vs, q_vs)]
    lg, _, _ = lm.forward_decode(
        lm._params, q_toks[:, -1], jnp.asarray([QT], jnp.int32),
        kcs, vcs)
    return np.asarray(lg[0])
q_ref = q_logits("f32")
def q_relerr(dt):
    return float(np.max(np.abs(q_logits(dt) - q_ref))
                 / np.max(np.abs(q_ref)))

# -- hierarchical KV tier (ISSUE 16): session persistence BELOW the
# device pool. NSESS 2-turn conversations against a pool that pins
# only ~2 of them: completed sessions demote to host RAM on eviction,
# and every turn-2 resume restores its run instead of re-prefilling.
# Gated claims: live sessions >= 10x what the pool alone holds, ZERO
# evicted-session re-prefills (every turn 2 is a session hit),
# restored-turn TTFT within 2x of a hot resume on an eviction-free
# pool, tokens identical to the big-pool engine, zero post-warmup
# recompiles (restores reuse the warmed gather/scatter executables),
# and the int8 byte shrink carrying into host bytes (>= 3x more
# sessions per host GB than f32 at head_dim 16).
OBS, NSESS, O_GEN = 16, 32, 8
O_PROMPTS = [rs.randint(0, VOCAB, 32).tolist() for _ in range(NSESS)]
O_SUFFIX = [rs.randint(0, VOCAB, 4).tolist() for _ in range(NSESS)]
o_bps = blocks_for(32 + O_GEN + 4 + O_GEN - 1, OBS)  # turn-2 pin
O_BLOCKS = 2 * o_bps + o_bps + 1          # ~2 pinned + 1 active + NULL

def o_mkeng(nblocks, host_bytes=0, dt="f32"):
    e = GenerationEngine(lm, num_slots=4, max_queue=NSESS * 2 + 8,
                         cache="paged", block_size=OBS,
                         num_blocks=nblocks, prompt_buckets=[32],
                         prefill_chunk_tokens=32, kv_dtype=dt,
                         offload_host_bytes=host_bytes)
    e.warmup()
    return e

def o_run(e, tag):
    '''All turn 1s, then all turn 2s — every session is long evicted
    (and with offload, demoted) before its own resume arrives.'''
    t2_ttft, outs1, outs2 = [], [], []
    for i in range(NSESS):
        _, toks = stream_one(e, O_PROMPTS[i], i, O_GEN,
                             sid="%s-%d" % (tag, i))
        outs1.append(toks)
    miss_t1 = e.metrics.session_misses
    for i in range(NSESS):
        p2 = O_PROMPTS[i] + outs1[i] + O_SUFFIX[i]
        ttft, toks = stream_one(e, p2, i, O_GEN, sid="%s-%d" % (tag, i))
        t2_ttft.append(ttft)
        outs2.append(toks)
    return t2_ttft, outs1 + outs2, e.metrics.session_misses - miss_t1

# hot reference: pool big enough that no session is ever evicted —
# its turn-2 TTFT is the hot-resume bar AND its tokens are the
# no-offload ground truth
o_ref = o_mkeng(NSESS * (o_bps + 1) + 8)
o_run(o_ref, "wu")                          # warmup pass
o_ref.evict_sessions(); o_ref.clear_prefix_cache()
ref_t2, ref_out, _ = o_run(o_ref, "m")
o_ref.stop()

o_eng = o_mkeng(O_BLOCKS, host_bytes=64 << 20)
o_run(o_eng, "wu")                          # warmup pass
o_eng.evict_sessions(); o_eng.clear_prefix_cache(); o_eng.clear_offload()
o_c0 = o_eng.metrics.compiles
off_t2, off_out, off_reprefills = o_run(o_eng, "m")
o_recompiles = o_eng.metrics.compiles - o_c0
o_snap = o_eng.stats()["paged"]["offload"]
# f32 host cost per demoted block (park everything first)
o_eng.offload_sessions()
o_f32_pb = (o_eng.stats()["paged"]["offload"]["host_bytes"]
            / max(1, o_eng.stats()["paged"]["offload"]["host_blocks"]))
o_pool_sessions = max(1, (O_BLOCKS - 1) // o_bps)
o_eng.stop()

# int8 mini-leg: same demote-everything shape, host bytes per block
o_i8 = o_mkeng(O_BLOCKS, host_bytes=64 << 20, dt="int8")
for i in range(6):
    stream_one(o_i8, O_PROMPTS[i], i, O_GEN, sid="cap-%d" % i)
o_i8.offload_sessions()
o_i8_snap = o_i8.stats()["paged"]["offload"]
o_i8_pb = o_i8_snap["host_bytes"] / max(1, o_i8_snap["host_blocks"])
o_i8.stop()

d = jax.devices()[0]
print(json.dumps({
    "model": f"CausalTransformerLM d{DM}xL{NL} generation "
             f"({N_REQ} mixed-length requests, {N_SLOTS} slots)",
    "platform": d.platform, "device_kind": d.device_kind,
    "tokens_per_sec": round(cb_tok / cb_dt, 1),
    "sequential_tokens_per_sec": round(seq_tok / seq_dt, 1),
    "speedup_vs_sequential": round((cb_tok / cb_dt)
                                   / (seq_tok / seq_dt), 2),
    "cached_sequential_tokens_per_sec": round(cseq_tok / cseq_dt, 1),
    "speedup_vs_cached_sequential": round((cb_tok / cb_dt)
                                          / (cseq_tok / cseq_dt), 2),
    "tokens_identical_to_cached_sequential": cb_out == cseq_out,
    "total_tokens": cb_tok,
    "recompiles_post_warmup": recompiles,
    "mean_slot_occupancy": stats["slots"]["mean_occupancy"],
    "slot_utilization": stats["slots"]["utilization"],
    "ttft_ms_p50": stats["ttft_ms"]["p50"],
    "ttft_ms_p99": stats["ttft_ms"]["p99"],
    "itl_ms_p50": stats["itl_ms"]["p50"],
    "itl_ms_p99": stats["itl_ms"]["p99"],
    "paged_tokens_per_sec": round(pg_tok / pg_dt, 1),
    "tokens_identical_paged_vs_slots": pg_out == cb_out,
    "paged_recompiles_post_warmup": pg_recompiles,
    "dense_kv_cache_bytes": dense_kv_bytes,
    "paged_pool_bytes": paged_pool_bytes,
    "paged_peak_kv_bytes": paged_peak_bytes,
    "paged_peak_block_utilization": round(
        pg_stats["blocks_peak_used"] / pg_stats["blocks_total"], 4),
    "paged_memory_vs_dense": round(paged_peak_bytes / dense_kv_bytes, 4),
    "chunked_prefills": n_chunked,
    "itl_p95_short_ms_baseline": round(pct(base_gaps, 95), 2),
    "itl_p95_short_ms_longprompt_chunked": round(pct(chunk_gaps, 95), 2),
    "itl_p95_short_ms_longprompt_unchunked": round(pct(flat_gaps, 95), 2),
    "chaos_tokens_per_sec": round(ch_tok / ch_dt, 1),
    "chaos_tokens_identical": ch_out == cb_out,
    "chaos_retries": ch_faults["retries"],
    "chaos_recoveries": ch_faults["recoveries"],
    "chaos_requests_lost": sum(1 for t in ch_out if not t),
    "chaos_recompiles_post_warmup": ch_recompiles,
    "traced_tokens_per_sec": round(tr_tok / tr_dt, 1),
    "trace_overhead_frac": round(trace_overhead, 4),
    "trace_spans_recorded": trace_spans,
    "tokens_identical_traced": tr_out == cb_out,
    "scheduler_overhead_frac": scheduler_overhead_frac,
    "prefix_hit_rate": round(shr_hits / N_USERS, 4),
    "prefix_tokens_matched": shr_matched,
    "prefix_prefill_tokens_saved_frac": round(
        1.0 - shr_prefill / max(1, nsh_prefill), 4),
    "prefix_tokens_identical_vs_noshare": shr_out == nsh_out,
    "prefix_recompiles_post_warmup": shr_recompiles,
    "prefix_cow_copies": shr_cow,
    "prefix_peak_blocks_shared": shr_peak,
    "prefix_peak_blocks_noshare": nsh_peak,
    "prefix_kv_bytes_per_request": round(shr_peak * blk_bytes
                                         / N_USERS),
    "noshare_kv_bytes_per_request": round(nsh_peak * blk_bytes
                                          / N_USERS),
    "prefix_users_capacity_ratio": round(nsh_peak / max(1, shr_peak),
                                         2),
    "prefix_ttft_ms_p50": round(pct(shr_ttfts, 50), 2),
    "prefix_ttft_ms_p99": round(pct(shr_ttfts, 99), 2),
    "noshare_ttft_ms_p50": round(pct(nsh_ttfts, 50), 2),
    "session_ttft_turn1_ms": round(sum(turn1) / len(turn1), 2),
    "session_ttft_turnN_ms": round(sum(turnN) / len(turnN), 2),
    "nosession_ttft_turnN_ms": round(sum(nsh_turnN) / len(nsh_turnN),
                                     2),
    "session_turnN_speedup": round(sum(nsh_turnN) / max(1e-9,
                                                        sum(turnN)),
                                   2),
    "session_evictions": sess_evicted,
    "session_blocks_reclaimed": sess_reclaimed,
    "spec_k": SPEC_K,
    "spec_tokens_per_sec": round(sp_tps, 1),
    "spec_plain_tokens_per_sec": round(sp0_tps, 1),
    "spec_speedup_vs_plain": round(sp_tps / sp0_tps, 3),
    "spec_itl_ms_p99": round(pct(sp_itls, 99), 3),
    "spec_plain_itl_ms_p99": round(pct(sp0_itls, 99), 3),
    "spec_accept_rate": sp_spec["accept_rate"],
    "spec_verify_batches": sp_spec["verify_batches"],
    "spec_rollbacks": sp_spec["rollbacks"],
    "spec_draft_fallbacks": sp_spec["draft_fallbacks"],
    "spec_tokens_identical_vs_plain": sp_out == sp0_out,
    "spec_recompiles_post_warmup": sp_recompiles,
    "kv_equal_pool_bytes": budget,
    "kv_f32_tokens_per_sec": round(q_legs["f32"]["tps"], 1),
    "kv_bf16_tokens_per_sec": round(q_legs["bf16"]["tps"], 1),
    "kv_int8_tokens_per_sec": round(q_legs["int8"]["tps"], 1),
    "kv_f32_concurrent_users": q_legs["f32"]["users"],
    "kv_bf16_concurrent_users": q_legs["bf16"]["users"],
    "kv_int8_concurrent_users": q_legs["int8"]["users"],
    "kv_int8_concurrent_users_vs_f32": round(
        q_legs["int8"]["users"] / q_legs["f32"]["users"], 2),
    "kv_bf16_logit_rel_err": round(q_relerr("bf16"), 5),
    "kv_int8_logit_rel_err": round(q_relerr("int8"), 5),
    "kv_quant_recompiles_post_warmup": sum(
        l["recompiles"] for l in q_legs.values()),
    "offload_live_sessions": NSESS,
    "offload_pool_sessions": o_pool_sessions,
    "offload_sessions_per_pool_ratio": round(NSESS / o_pool_sessions, 2),
    "offload_evicted_reprefills": off_reprefills,
    "offload_demotions": o_snap["demotions"],
    "offload_restores": o_snap["restores"],
    "offload_prefetch_hits": o_snap["prefetch_hits"],
    "offload_restore_ttft_ms_p50": round(pct(off_t2, 50), 2),
    "offload_hot_ttft_ms_p50": round(pct(ref_t2, 50), 2),
    "offload_restore_ttft_ratio": round(
        pct(off_t2, 50) / max(1e-9, pct(ref_t2, 50)), 3),
    "offload_tokens_identical": off_out == ref_out,
    "offload_recompiles_post_warmup": o_recompiles,
    "offload_restore_ms_p50": o_snap["restore_ms"]["p50"],
    "offload_f32_host_bytes_per_block": round(o_f32_pb, 1),
    "offload_int8_host_bytes_per_block": round(o_i8_pb, 1),
    "offload_int8_capacity_vs_f32": round(o_f32_pb / o_i8_pb, 2),
    "synthetic_data": True}))
"""

FLEET_CODE = _COMMON + r"""
# Replica-fleet scenario (ISSUE 6): 3 in-process InferenceServer
# replicas of one MLP behind the occupancy-aware FleetRouter's HTTP
# front-end, 16 concurrent keep-alive clients, and ONE scripted
# rolling restart mid-run — every replica drained, stopped, rebuilt,
# and re-admitted while traffic flows. The gated number is fleet
# requests/sec END TO END (the restart window included), because that
# is the throughput a fleet under continuous deploy actually
# delivers. Correctness bar: zero client-visible failures and zero
# router-lost requests — the 503s the draining replicas emit must all
# be absorbed by the router's retry path.
import threading
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.serving import FleetRouter, InferenceServer, \
    ReplicaFleet

HIDDEN = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
N_REQ = int(sys.argv[2]) if len(sys.argv) > 2 else 96
N_CLIENTS, N_REPLICAS = 16, 3
conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3)).list()
        .layer(DenseLayer(n_out=HIDDEN, activation="relu"))
        .layer(DenseLayer(n_out=HIDDEN, activation="relu"))
        .layer(OutputLayer(n_out=10, loss="mcxent", activation="softmax"))
        .input_type_feed_forward(64).build())
model = MultiLayerNetwork(conf).init()
rs = np.random.RandomState(0)
xs = [rs.randn(1, 64).astype(np.float32) for _ in range(N_CLIENTS)]
reqs = [json.dumps({"inputs": x.tolist(),
                    "timeout_ms": 120_000}).encode() for x in xs]
# restart-free reference outputs. Compared within tolerance, not
# bitwise: coalescing pads requests into varying batch buckets, and
# cross-shape XLA reductions are not bit-deterministic (the same
# caveat the generation bench documents) — bit-identity is asserted
# where it is well-defined, on generation token ids (tests/bench).
expect = [np.asarray(model.output(x)) for x in xs]

def factory():
    s = InferenceServer(port=0, max_batch_size=16, max_latency_ms=5.0,
                        max_queue=512)
    s.register("default", model)
    s.served().warmup([1, 2, 4, 8, 16])
    return s

fleet = ReplicaFleet(poll_interval_s=0.1)
for _ in range(N_REPLICAS):
    fleet.add(factory(), factory=factory)
router = FleetRouter(fleet, hedge_after_ms=250.0,
                     hedge_budget_ratio=0.05, hedge_budget_burst=4.0)
host, port = router.serve()

def hammer(n_req, bad, lat_ms):
    import http.client

    def client(i):
        conn = http.client.HTTPConnection(host, port, timeout=120)
        for _ in range(n_req):
            t0 = time.perf_counter()
            for attempt in range(3):
                try:
                    conn.request("POST", "/predict", body=reqs[i])
                    r = conn.getresponse()
                    data = r.read()
                    if r.status != 200:
                        bad.append((i, r.status))
                    else:
                        try:
                            out = np.asarray(
                                json.loads(data)["outputs"], np.float32)
                            if not np.allclose(out, expect[i],
                                               rtol=1e-4, atol=1e-6):
                                bad.append((i, "output mismatch"))
                        except (ValueError, KeyError):
                            bad.append((i, "unparseable response"))
                    break
                except (ConnectionError, OSError,
                        http.client.HTTPException):
                    conn.close()
                    conn = http.client.HTTPConnection(host, port,
                                                      timeout=120)
                    if attempt == 2:
                        # record, never raise: a silently-dead client
                        # thread would leave requests_total nominal
                        # and zero_loss falsely true
                        bad.append((i, "connection failed x3"))
            lat_ms.append((time.perf_counter() - t0) * 1e3)
        conn.close()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(N_CLIENTS)]
    t0 = time.perf_counter()
    for t in threads: t.start()
    for t in threads: t.join()
    return time.perf_counter() - t0

def pct(v, p):
    v = sorted(v)
    return v[min(len(v) - 1, int(round(p / 100.0 * (len(v) - 1))))] \
        if v else 0.0

hammer(2, [], [])                       # warmup pass (caches + conns)
bad, lat = [], []
restart_ok = []
restart_wall = []
# at tiny (smoke-test) scale the whole traffic window is well under
# half a second — a fixed 0.5s delay would restart an idle fleet
RESTART_DELAY = 0.5 if N_REQ >= 32 else 0.05
def restart():
    time.sleep(RESTART_DELAY)           # traffic is rolling
    t0r = time.perf_counter()
    restart_ok.append(fleet.rolling_restart(drain_timeout_s=60.0,
                                            ready_timeout_s=300.0))
    restart_wall.append(time.perf_counter() - t0r)
rt = threading.Thread(target=restart)
rt.start()
dt = hammer(N_REQ, bad, lat)
rt.join()
m = fleet.metrics
n = N_CLIENTS * N_REQ
d = jax.devices()[0]
print(json.dumps({
    "model": f"MLP-{HIDDEN} replica fleet ({N_REPLICAS} replicas, "
             f"{N_CLIENTS} clients, 1 rolling restart)",
    "platform": d.platform, "device_kind": d.device_kind,
    "requests_per_sec": round(n / dt, 1),
    "requests_total": n,
    "wall_seconds": round(dt, 2),
    "p50_ms": round(pct(lat, 50), 2), "p99_ms": round(pct(lat, 99), 2),
    "client_failures": len(bad),
    "requests_lost": m.requests_lost,
    "zero_loss": len(bad) == 0 and m.requests_lost == 0,
    "restart_clean": bool(restart_ok and restart_ok[0]),
    "restart_wall_s": round(restart_wall[0], 2) if restart_wall else None,
    # the restart must land INSIDE the traffic window for the
    # zero-loss claim to mean anything; sized via N_REQ
    "restart_within_traffic": bool(restart_wall
                                   and dt > RESTART_DELAY
                                   + restart_wall[0]),
    "restarts": m.restarts,
    "retries": m.retries,
    "hedges": m.hedges,
    "hedges_won": m.hedges_won,
    "hedge_budget_denied": m.hedge_budget_denied,
    "ejections": m.ejections,
    "synthetic_data": True}))
router.stop()
fleet.stop(stop_replicas=True)
"""

CONNSCALE_CODE = _COMMON + r"""
# Connection-scale scenario (ISSUE 14 tentpole): hold ~1,000
# mostly-idle open STREAMING connections through the router while a
# probe client measures interactive /predict latency — the regime
# where thread-per-connection front-ends collapse (one OS thread per
# open conn at BOTH tiers, ~2 threads + 4 fds per idle stream in this
# single-process harness) and the event-loop front-end holds (an idle
# stream is two socket buffers and a parked coroutine). Both backends
# run at the SAME conn count; the gated numbers are the aio leg's held
# streams and probe p99, with the thread leg recorded beside them as
# the honest degradation reference. Idle-ness is real, not simulated:
# a 4-slot generator with a deep admission queue answers every stream
# 200 + chunked headers immediately, then leaves all but 4 of them
# waiting for a slot with zero token traffic.
import resource
import socket
import threading
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.serving import FleetRouter, InferenceServer, \
    ReplicaFleet
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM

N_CONNS = int(sys.argv[1]) if len(sys.argv) > 1 else 1000
N_PROBE = int(sys.argv[2]) if len(sys.argv) > 2 else 50

# fd budget: client sock + router-side sock + router->replica pair =
# 4 fds per proxied stream, all in THIS process; leave headroom
soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
try:
    resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    soft = hard
except (ValueError, OSError):
    pass
N_CONNS = min(N_CONNS, max((soft - 512) // 5, 16))

conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3)).list()
        .layer(DenseLayer(n_out=64, activation="relu"))
        .layer(OutputLayer(n_out=10, loss="mcxent", activation="softmax"))
        .input_type_feed_forward(16).build())
mlp = MultiLayerNetwork(conf).init()
lm = CausalTransformerLM(vocab_size=64, d_model=16, n_layers=1,
                         n_heads=2, max_seq_len=512, seed=0,
                         implementation="plain").init()
probe_req = json.dumps(
    {"inputs": np.random.RandomState(0).randn(1, 16).tolist(),
     "timeout_ms": 60_000}).encode()
stream_body = json.dumps(
    {"prompt": [1, 2, 3, 4], "max_tokens": 500, "stream": True,
     "temperature": 0.8, "seed": 0, "timeout_ms": 900_000}).encode()
stream_head = (b"POST /v1/models/lm/generate HTTP/1.1\r\n"
               b"Host: bench\r\nContent-Type: application/json\r\n"
               b"Content-Length: %d\r\n\r\n" % len(stream_body)
               ) + stream_body

def build(backend):
    s = InferenceServer(port=0, max_batch_size=8, max_latency_ms=2.0,
                        max_queue=256, http_backend=backend)
    s.register("default", mlp)
    s.served().warmup([1])
    g = s.register_generator("lm", lm, num_slots=4,
                             max_queue=N_CONNS + 128,
                             default_timeout_ms=900_000,
                             max_seq_len=512, prompt_buckets=[8])
    g.warmup()
    fleet = ReplicaFleet(poll_interval_s=0.5)
    fleet.add(s)
    router = FleetRouter(fleet, timeout_s=600.0)
    host, port = router.serve(backend=backend)
    return s, fleet, router, host, port

def open_streams(host, port, n, failures):
    socks = [None] * n

    def worker(lo, hi):
        for i in range(lo, hi):
            try:
                sk = socket.create_connection((host, port), timeout=30.0)
                sk.settimeout(30.0)
                sk.sendall(stream_head)
                buf = b""
                while b"\r\n\r\n" not in buf:
                    d = sk.recv(4096)
                    if not d:
                        raise ConnectionError("closed before headers")
                    buf += d
                if not buf.startswith(b"HTTP/1.1 200"):
                    raise ConnectionError(
                        buf.split(b"\r\n", 1)[0].decode("latin-1"))
                socks[i] = sk
            except Exception as e:  # record, never raise: a dead
                failures.append(repr(e))  # worker would undercount
    nw = 16
    step = (n + nw - 1) // nw
    ths = [threading.Thread(target=worker, args=(lo, min(lo + step, n)))
           for lo in range(0, n, step)]
    t0 = time.perf_counter()
    for t in ths: t.start()
    for t in ths: t.join()
    return socks, time.perf_counter() - t0

def still_open(socks):
    # an open conn either has nothing pending (mid-stream idle) or
    # buffered chunks (active / finished keep-alive); a server-side
    # close reads as EOF
    n = 0
    for sk in socks:
        if sk is None:
            continue
        try:
            sk.setblocking(False)
            try:
                n += 1 if sk.recv(65536, socket.MSG_PEEK) else 0
            except (BlockingIOError, InterruptedError):
                n += 1
            finally:
                sk.setblocking(True)
        except OSError:
            pass
    return n

def probe(host, port, n, fails):
    import http.client
    lat = []
    conn = http.client.HTTPConnection(host, port, timeout=60)
    for _ in range(n):
        t0 = time.perf_counter()
        try:
            conn.request("POST", "/predict", body=probe_req)
            r = conn.getresponse()
            r.read()
            if r.status != 200:
                fails.append(r.status)
                continue
        except (ConnectionError, OSError, http.client.HTTPException) as e:
            fails.append(repr(e))
            conn.close()
            conn = http.client.HTTPConnection(host, port, timeout=60)
            continue
        lat.append((time.perf_counter() - t0) * 1e3)
    conn.close()
    return lat

def pct(v, p):
    v = sorted(v)
    return v[min(len(v) - 1, int(round(p / 100.0 * (len(v) - 1))))] \
        if v else 0.0

def leg(backend):
    base_threads = threading.active_count()
    s, fleet, router, host, port = build(backend)
    probe(host, port, 3, [])            # warm the probe path unloaded
    conn_fails, probe_fails = [], []
    socks, est_s = open_streams(host, port, N_CONNS, conn_fails)
    time.sleep(0.5)                     # let accept/admission settle
    threads = threading.active_count() - base_threads
    lat = probe(host, port, N_PROBE, probe_fails)
    open_n = still_open(socks)
    for sk in socks:
        if sk is not None:
            try:
                sk.close()
            except OSError:
                pass
    m = router.metrics
    out = {"streaming_conns": open_n,
           "conns_attempted": N_CONNS,
           "conn_failures": len(conn_fails),
           "establish_s": round(est_s, 2),
           "server_threads": threads,
           "p50_ms": round(pct(lat, 50), 2),
           "p99_ms": round(pct(lat, 99), 2),
           "probe_failures": len(probe_fails),
           "streams_proxied": m.streams,
           "requests_lost": m.requests_lost}
    router.stop()
    fleet.stop(stop_replicas=True)
    return out

aio = leg("aio")
thr = leg("thread")
d = jax.devices()[0]
print(json.dumps({
    "model": f"conn-scale router+replica ({N_CONNS} idle streams, "
             f"{N_PROBE} interactive probes)",
    "platform": d.platform, "device_kind": d.device_kind,
    **aio,
    **{f"thread_{k}": v for k, v in thr.items()},
    "synthetic_data": True}))
"""

OVERLOAD_CODE = _COMMON + r"""
# Open-loop overload harness (ISSUE 9): PRODUCTION-shaped traffic —
# Poisson arrivals at a configured rate, NOT N looping clients. A
# closed-loop hammer self-throttles (each client waits for its answer
# before sending the next), so it can never push a service past its
# capacity and hides collapse; an open-loop generator keeps offering
# work at the configured rate no matter how slow the answers get,
# which is exactly what production traffic does. Three legs against
# ONE registry (predict model + generator per replica) through the
# FleetRouter:
#   1. capacity: a short closed-loop burst measures sustainable rps;
#   2. normal: a diurnal ramp (0.3x..0.8x capacity) of mixed
#      predict+generate, ~70/30 interactive/batch priorities;
#   3. overload: flat 2x measured capacity. Graceful degradation bar:
#      goodput (2xx/offered) >= GOODPUT_FLOOR (ideal at 2x is 0.5),
#      batch-class work sheds FIRST (priority queue fraction), queue
#      depth stays bounded (shed at admission, not after device work),
#      and ADMITTED interactive work keeps its latency SLO — p99
#      within the deadline budget, no collapse.
# TTFT/ITL are first-class: generate traffic streams through the
# router and records submit->first-token and inter-token gaps.
# CPU-JAX by design — the acceptance regime; the predict model's
# device call is a fixed 50 ms sleep so capacity is deterministic and
# small enough that 2x capacity is schedulable from one process.
import math, queue as _queue, random, threading
from deeplearning4j_tpu.serving import (FleetRouter, InferenceServer,
                                        NoReplicasError, ReplicaFleet,
                                        ServingError)
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM

DUR = float(sys.argv[1]) if len(sys.argv) > 1 else 6.0   # per open leg
CAP_DUR = min(2.5, DUR)          # closed-loop capacity burst
DEVICE_MS = 50.0                 # per device call (sleep, see below)
# the queue is DEEPER than any deadline budget allows (200 rows at 4
# rows per 50 ms call is ~2.6 s of wait, past the 2 s interactive
# budget): the deadline-aware admission check, not queue-full, must
# be what bounds queue growth under overload
MAX_BATCH, MAX_QUEUE = 4, 200
SLO_MS = 2_000.0                 # interactive deadline budget
BATCH_DEADLINE_MS = 700.0        # batch deadline budget (tighter:
#                                  batch tolerates rejection, not
#                                  staleness, and sheds first anyway)
GEN_DEADLINE_MS = 15_000.0
GOODPUT_FLOOR = 0.3              # documented: docs/serving.md
POOL = 256                       # issuing workers (>> concurrency at
#                                  capacity; arrivals never block on
#                                  completions - open loop)

class SlowMLP:
    '''Duck-typed predict model: one device call costs a fixed sleep,
    so fleet capacity is deterministic (~ replicas * batch / delay)
    and admission control's device-cost EWMA sees the real cost.'''
    def output(self, x):
        time.sleep(DEVICE_MS / 1e3)
        return np.zeros((np.asarray(x).shape[0], 4), np.float32)

lm = CausalTransformerLM(vocab_size=64, d_model=16, n_layers=1,
                         n_heads=2, max_seq_len=32, seed=0,
                         implementation="plain").init()

def factory():
    # tracing ON (ISSUE 10): every admitted request leaves admission/
    # queue/device spans in the replica's ring, decomposed into the
    # latency_breakdown block after the overload leg
    s = InferenceServer(port=0, max_batch_size=MAX_BATCH,
                        max_latency_ms=2.0, max_queue=MAX_QUEUE,
                        tracing=True, trace_ring=4096)
    s.register("default", SlowMLP())
    g = s.register_generator("lm", lm, num_slots=2, max_seq_len=32,
                             prompt_buckets=[8, 16], max_queue=8,
                             cache="paged", block_size=4, num_blocks=16)
    g.warmup()
    return s

# long-context generate class (ISSUE 16): ~13-token prompts land in
# the 16 bucket — their prefill cost and block footprint are several
# times the short class's, so under overload they probe whether
# admission keeps long-prompt TTFT bounded instead of letting the
# deep prefill starve the short streams (recorded separately below)
LONG_PROMPT = [(7 * j) % 60 + 1 for j in range(13)]

fleet = ReplicaFleet(poll_interval_s=0.1)
for _ in range(2):
    fleet.add(factory(), factory=factory)
router = FleetRouter(fleet)
X = [[0.0] * 8]

rng = random.Random(0)
rec_lock = threading.Lock()

def mkleg():
    return {"offered": 0, "ok": 0, "shed": 0, "deadline": 0, "other": 0,
            "by_prio": {"interactive": [0, 0], "batch": [0, 0]},
            # [offered, shed] per priority class
            "lat_ms": {"interactive": [], "batch": []},
            "ttft_ms": [], "itl_ms": [], "ttft_long_ms": []}

def do_predict(leg, prio, deadline_ms, t_arr):
    st, _body = router.post("/predict",
                            {"inputs": X, "timeout_ms": deadline_ms,
                             "priority": prio})
    dt_ms = (time.perf_counter() - t_arr) * 1e3
    with rec_lock:
        leg["by_prio"][prio][0] += 1
        if st == 200:
            leg["ok"] += 1
            leg["lat_ms"][prio].append(dt_ms)
        elif st == 503:
            leg["shed"] += 1; leg["by_prio"][prio][1] += 1
        elif st == 504:
            leg["deadline"] += 1; leg["by_prio"][prio][1] += 1
        else:
            leg["other"] += 1

def do_generate(leg, t_arr, long=False):
    gaps, t_first = [], None
    prompt = LONG_PROMPT if long else [1, 2, 3]
    try:
        last = None
        for it in router.stream("/v1/models/lm/generate",
                                {"prompt": prompt, "max_tokens": 8,
                                 "seed": 0, "priority": "interactive",
                                 "timeout_ms": GEN_DEADLINE_MS}):
            if "token" not in it:
                continue
            now = time.perf_counter()
            if t_first is None:
                t_first = now
            else:
                gaps.append((now - last) * 1e3)
            last = now
    except NoReplicasError:
        with rec_lock:
            leg["shed"] += 1
            leg["by_prio"]["interactive"][0] += 1
            leg["by_prio"]["interactive"][1] += 1
        return
    except ServingError:
        with rec_lock:
            leg["deadline"] += 1
            leg["by_prio"]["interactive"][0] += 1
            leg["by_prio"]["interactive"][1] += 1
        return
    with rec_lock:
        leg["by_prio"]["interactive"][0] += 1
        if t_first is None:
            leg["other"] += 1
            return
        leg["ok"] += 1
        key = "ttft_long_ms" if long else "ttft_ms"
        leg[key].append((t_first - t_arr) * 1e3)
        leg["itl_ms"].extend(gaps)

def issue(leg, kind, prio, t_arr):
    if kind == "gen":
        do_generate(leg, t_arr)
    elif kind == "genlong":
        do_generate(leg, t_arr, long=True)
    else:
        dl = SLO_MS if prio == "interactive" else BATCH_DEADLINE_MS
        do_predict(leg, prio, dl, t_arr)

# -- issuing pool: arrivals are queued with their arrival timestamp;
# latency is measured from ARRIVAL, so worker backlog (if any) counts
# against the service, never throttles the offered rate
arrivals = _queue.Queue()
def worker():
    while True:
        item = arrivals.get()
        if item is None:
            return
        leg, kind, prio, t_arr = item
        try:
            issue(leg, kind, prio, t_arr)
        except Exception:
            with rec_lock:
                leg["other"] += 1
workers = [threading.Thread(target=worker, daemon=True)
           for _ in range(POOL)]
for w in workers: w.start()

def traffic_mix(i):
    # generation arrivals at multiples of 8; every other one carries
    # the long-context prompt (ISSUE 16) — a 50/50 short/long gen mix
    if i % 16 == 8:
        return "genlong", "interactive"
    kind = "gen" if i % 8 == 0 else "predict"
    prio = "batch" if (kind == "predict" and i % 10 < 3) \
        else "interactive"
    return kind, prio

def open_loop(leg, rate_fn, duration_s):
    '''Poisson arrivals: exponential gaps at rate_fn(t), fired on the
    wall clock regardless of outstanding work (the open loop).'''
    t0 = time.perf_counter()
    t, i = 0.0, 0
    while True:
        t += rng.expovariate(max(rate_fn(t), 1e-6))
        if t >= duration_s:
            break
        delay = t0 + t - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        kind, prio = traffic_mix(i)
        with rec_lock:
            leg["offered"] += 1
        arrivals.put((leg, kind, prio, time.perf_counter()))
        i += 1
    return time.perf_counter() - t0

def drain():
    while not arrivals.empty():
        time.sleep(0.05)
    deadline = time.time() + 30
    while time.time() < deadline:
        with rec_lock:
            done = all(l["ok"] + l["shed"] + l["deadline"] + l["other"]
                       >= l["offered"] for l in legs)
        if done:
            break
        time.sleep(0.05)

def pct(v, p):
    v = sorted(v)
    return v[min(len(v) - 1, int(round(p / 100.0 * (len(v) - 1))))] \
        if v else 0.0

# -- leg 1: measured capacity (closed loop, short) -------------------
cap_leg = mkleg()
legs = [cap_leg]
def cap_client(i):
    t_end = time.perf_counter() + CAP_DUR
    j = 0
    while time.perf_counter() < t_end:
        kind, prio = traffic_mix(i * 1000 + j)
        with rec_lock:
            cap_leg["offered"] += 1
        issue(cap_leg, kind, prio, time.perf_counter())
        j += 1
cts = [threading.Thread(target=cap_client, args=(i,)) for i in range(12)]
t0 = time.perf_counter()
for t in cts: t.start()
for t in cts: t.join()
cap_dt = time.perf_counter() - t0
capacity_rps = max(cap_leg["ok"] / cap_dt, 4.0)

# -- leg 2: normal (diurnal ramp, 0.3x..0.8x capacity) ---------------
normal = mkleg(); legs.append(normal)
ramp = lambda t: capacity_rps * (0.3 + 0.5 * math.sin(
    math.pi * min(t / DUR, 1.0)))
open_loop(normal, ramp, DUR)
drain()

# -- leg 3: overload (flat 2x measured capacity) ---------------------
overload = mkleg(); legs.append(overload)
max_depth = [0]
stop_sampling = threading.Event()
def sample_depth():
    while not stop_sampling.is_set():
        for rep in router.stats()["fleet"]["replicas"]:
            models = (rep["summary"] or {}).get("models", {})
            d = (models.get("default") or {}).get("queue_depth", 0)
            max_depth[0] = max(max_depth[0], int(d or 0))
        time.sleep(0.1)
smp = threading.Thread(target=sample_depth, daemon=True)
smp.start()
over_dt = open_loop(overload, lambda t: 2.0 * capacity_rps, DUR)
drain()
stop_sampling.set(); smp.join()
for _ in range(POOL):
    arrivals.put(None)

fstats = router.stats()["fleet"]
# engine-side admission counters (all legs): sheds that spent ZERO
# device work, split by cause — summed over the in-process replicas
eng = {"shed": 0, "shed_batch": 0, "shed_deadline": 0}
for rep in fleet.replicas():
    m = rep.server.registry.get("default").batcher.metrics
    for k in eng:
        eng[k] += getattr(m, k)
# -- admitted-request latency decomposition from traces (ISSUE 10):
# the replica tracers recorded an admission verdict, queue wait, and
# device span for every request — where admitted time went under
# pressure, per component, not just the end-to-end percentile
by_kind = {"queue": [], "admission": [], "device": []}
for rep in fleet.replicas():
    for tr in rep.server.tracer.dump(limit=10_000):
        for sp in tr["spans"]:
            k = sp["kind"]
            if k in by_kind and sp["duration_ms"] is not None:
                by_kind[k].append(sp["duration_ms"])
latency_breakdown = {
    k: {"count": len(v), "p50_ms": round(pct(v, 50), 3),
        "p99_ms": round(pct(v, 99), 3)}
    for k, v in by_kind.items()}
def rate(n, d):
    return round(n / d, 4) if d else 0.0
o = overload
int_off, int_shed = o["by_prio"]["interactive"]
bat_off, bat_shed = o["by_prio"]["batch"]
int_p99 = pct(o["lat_ms"]["interactive"], 99)
ttft_p99 = pct(o["ttft_ms"], 99)
goodput = rate(o["ok"], o["offered"])
d = jax.devices()[0]
print(json.dumps({
    "model": "SlowMLP+tinyLM fleet (2 replicas, open-loop Poisson, "
             "diurnal ramp, 2x-capacity overload leg)",
    "platform": d.platform, "device_kind": d.device_kind,
    "capacity_rps": round(capacity_rps, 1),
    "normal_offered": normal["offered"],
    "normal_goodput_ratio": rate(normal["ok"], normal["offered"]),
    "normal_shed_rate": rate(normal["shed"] + normal["deadline"],
                             normal["offered"]),
    "normal_interactive_p99_ms": round(
        pct(normal["lat_ms"]["interactive"], 99), 2),
    "normal_ttft_ms_p50": round(pct(normal["ttft_ms"], 50), 2),
    "normal_ttft_ms_p99": round(pct(normal["ttft_ms"], 99), 2),
    "normal_itl_ms_p50": round(pct(normal["itl_ms"], 50), 2),
    "normal_itl_ms_p99": round(pct(normal["itl_ms"], 99), 2),
    "overload_offered_rps": round(o["offered"] / over_dt, 1),
    "overload_offered": o["offered"],
    "overload_goodput_ratio": goodput,
    "overload_goodput_floor": GOODPUT_FLOOR,
    "overload_goodput_ok": goodput >= GOODPUT_FLOOR,
    "overload_shed_rate": rate(o["shed"] + o["deadline"], o["offered"]),
    "overload_deadline_sheds": o["deadline"],
    "engine_shed_total": eng["shed"],
    "engine_shed_batch_total": eng["shed_batch"],
    "engine_shed_deadline_total": eng["shed_deadline"],
    "overload_batch_shed_rate": rate(bat_shed, bat_off),
    "overload_interactive_shed_rate": rate(int_shed, int_off),
    "overload_batch_sheds_first": (rate(bat_shed, bat_off)
                                   >= rate(int_shed, int_off)),
    "overload_interactive_p99_ms": round(int_p99, 2),
    "overload_interactive_slo_ms": SLO_MS,
    # admitted interactive work holds its SLO: queue-wait is bounded
    # by deadline-aware admission, so p99 <= budget + one device call
    "overload_interactive_slo_ok": bool(
        o["lat_ms"]["interactive"])
    and int_p99 <= SLO_MS + 4 * DEVICE_MS,
    "overload_ttft_ms_p50": round(pct(o["ttft_ms"], 50), 2),
    "overload_ttft_ms_p99": round(ttft_p99, 2),
    "overload_itl_ms_p50": round(pct(o["itl_ms"], 50), 2),
    "overload_itl_ms_p99": round(pct(o["itl_ms"], 99), 2),
    "normal_longctx_ttft_ms_p99": round(
        pct(normal["ttft_long_ms"], 99), 2),
    "overload_longctx_completed": len(o["ttft_long_ms"]),
    "overload_longctx_ttft_ms_p50": round(
        pct(o["ttft_long_ms"], 50), 2),
    "overload_longctx_ttft_ms_p99": round(
        pct(o["ttft_long_ms"], 99), 2),
    "overload_queue_depth_max": max_depth[0],
    # STRICT bound: deadline-aware admission must cap the queue below
    # its raw capacity (growth stops at ~deadline/service-time rows,
    # not at queue-full) — the "no unbounded queue growth" claim
    "overload_queue_bounded": max_depth[0] < MAX_QUEUE,
    "fleet_sheds_observed": fstats["sheds"],
    "fleet_cooldowns": fstats["cooldowns"],
    "fleet_breaker_trips": fstats["breaker_trips"],
    "fleet_goodput": fstats["goodput"],
    "fleet_shed_total": fstats["fleet_shed"],
    "requests_lost_fleet_level": fstats["requests_lost"],
    "latency_breakdown": latency_breakdown,
    "latency_queue_ms_p99": latency_breakdown["queue"]["p99_ms"],
    "latency_admission_ms_p99": latency_breakdown["admission"]["p99_ms"],
    "latency_device_ms_p99": latency_breakdown["device"]["p99_ms"],
    "synthetic_data": True}))
router.stop()
fleet.stop(stop_replicas=True)
"""

WORD2VEC_CODE = _COMMON + r"""
# BASELINE config 4: Word2Vec throughput at benchmark scale. text8 is
# 100MB of wiki text; no egress here, so a labeled synthetic corpus with
# a text8-like Zipf vocabulary is used and tokens/sec is the metric.
import time
from deeplearning4j_tpu.nlp.word2vec import Word2Vec

rs = np.random.RandomState(0)
VOCAB, N_TOK = 20000, 2_000_000
ranks = np.arange(1, VOCAB + 1)
probs = (1.0 / ranks) / np.sum(1.0 / ranks)   # Zipf, like natural text
tokens = rs.choice(VOCAB, size=N_TOK, p=probs)
words = [f"w{t}" for t in tokens]
sentences = [words[i:i + 1000] for i in range(0, N_TOK, 1000)]
w2v = Word2Vec(layer_size=128, window_size=5, min_word_frequency=5,
               negative=5, iterations=1, seed=42, batch_size=2048)
t0 = time.perf_counter()
w2v.fit(sentences)
dt = time.perf_counter() - t0
d = jax.devices()[0]
print(json.dumps({"model": "Word2Vec SG-NS (text8-scale synthetic)",
                  "platform": d.platform, "device_kind": d.device_kind,
                  "tokens_per_sec": round(N_TOK / dt, 1),
                  "n_tokens": N_TOK, "vocab": VOCAB,
                  "synthetic_data": True,
                  "wall_seconds": round(dt, 1)}))
"""

TRAINING_CHAOS_CODE = _COMMON + r"""
# Resilient-training chaos probe (ISSUE 5): steps/sec through the
# supervised step loop with ~1% injected transient step faults, an
# async step-granular checkpoint cadence against an injected-slow
# disk, and ONE scripted preemption mid-run followed by restart +
# resume. The gated number is chaos steps/sec END TO END — retries,
# checkpoint stalls, the preemption's synchronous flush, the restart's
# recompile, and the resume fast-forward all land inside the timed
# window, because that is the throughput a preemptible-TPU training
# job actually delivers. Correctness bar: the resumed run's final
# params are BIT-IDENTICAL to an uninterrupted clean run of the same
# schedule (CPU-JAX by design — the acceptance regime, same as the
# serving scenarios).
import tempfile
from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.faults import FaultInjector, PreemptionFault
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel.elastic import FaultTolerantTrainer

EPOCHS = int(sys.argv[1]) if len(sys.argv) > 1 else 8
N, BATCH, DIN = 8192, 128, 64          # 64 steps per epoch
STEPS_PER_EPOCH = N // BATCH
TOTAL_STEPS = EPOCHS * STEPS_PER_EPOCH

def build():
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=128, activation="tanh"))
            .layer(DenseLayer(n_out=64, activation="tanh"))
            .layer(OutputLayer(n_out=10, loss="mcxent",
                               activation="softmax"))
            .input_type_feed_forward(DIN).build())
    return MultiLayerNetwork(conf).init()

rs = np.random.RandomState(0)
X = rs.rand(N, DIN).astype(np.float32)
Y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, N)]

def it():
    # shuffle on: resume must replay the dead run's exact order
    return ArrayDataSetIterator(X, Y, batch=BATCH, shuffle=True, seed=3)

# -- clean reference: the same supervised loop + checkpoint cadence,
# no injector (compile inside the window, symmetric with chaos)
clean_dir = tempfile.mkdtemp(prefix="bench_tchaos_clean_")
m_clean = build()
t0 = time.perf_counter()
FaultTolerantTrainer(m_clean, clean_dir,
                     save_every_n_steps=50).fit(it(), epochs=EPOCHS)
clean_dt = time.perf_counter() - t0

# -- traced leg (ISSUE 13): the SAME clean schedule with the full
# observability plane attached — tracer, event timeline, fleet
# telemetry, StatsListener — so the gated number is the steps/sec
# cost of tracing ENABLED (< 5% in acceptance; disabled is zero-cost
# by construction, the step loop carries no tracing code at all).
from deeplearning4j_tpu.tracing import Tracer
from deeplearning4j_tpu.parallel.telemetry import (EventTimeline,
                                                   FleetTelemetry)
from deeplearning4j_tpu.ui import InMemoryStatsStorage, StatsListener
traced_dir = tempfile.mkdtemp(prefix="bench_tchaos_traced_")
m_traced = build()
m_traced.set_listeners(StatsListener(InMemoryStatsStorage(),
                                     session_id="bench",
                                     collect_params=False))
tracer = Tracer(enabled=True, ring=64)
tr_tr = FaultTolerantTrainer(m_traced, traced_dir,
                             save_every_n_steps=50,
                             tracer=tracer,
                             events=EventTimeline(),
                             fleet_telemetry=FleetTelemetry())
t0 = time.perf_counter()
tr_tr.fit(it(), epochs=EPOCHS)
traced_dt = time.perf_counter() - t0
training_trace_overhead = max(0.0, traced_dt / clean_dt - 1.0)
tr_phases = tr_tr.telemetry_snapshot()["phases"]
traced_spans = sum(len(t["spans"]) for t in tracer.dump(limit=64))
traced_identical = all(
    bool(np.array_equal(np.asarray(a), np.asarray(b)))
    for a, b in zip(jax.tree_util.tree_leaves(m_clean._params),
                    jax.tree_util.tree_leaves(m_traced._params)))

# -- chaos run: ~1% transient step faults + 20ms-slow checkpoint disk
# + a scripted preemption at the midpoint, then restart and resume
chaos_dir = tempfile.mkdtemp(prefix="bench_tchaos_")

def injector():
    return FaultInjector(seed=0, rates={"train_step": 0.01,
                                        "checkpoint_io": 1.0},
                         slow_ms={"checkpoint_io": 20.0},
                         plan={"preempt": [TOTAL_STEPS // 2]})

t0 = time.perf_counter()
m1 = build()
tr1 = FaultTolerantTrainer(m1, chaos_dir, save_every_n_steps=50,
                           fault_injector=injector())
try:
    tr1.fit(it(), epochs=EPOCHS)
    preempted = False
except PreemptionFault:
    preempted = True
# "restart": fresh process state — resume the checkpoint, new trainer,
# new injector whose preempt plan is already spent at this call count
m2 = FaultTolerantTrainer.resume(chaos_dir)
inj2 = FaultInjector(seed=0, rates={"train_step": 0.01,
                                    "checkpoint_io": 1.0},
                     slow_ms={"checkpoint_io": 20.0})
tr2 = FaultTolerantTrainer(m2, chaos_dir, save_every_n_steps=50,
                           fault_injector=inj2)
tr2.fit(it(), epochs=EPOCHS)
chaos_dt = time.perf_counter() - t0

leaves = lambda m: jax.tree_util.tree_leaves(m._params)
identical = all(bool(np.array_equal(np.asarray(a), np.asarray(b)))
                for a, b in zip(leaves(m_clean), leaves(m2)))
f1, f2 = tr1.faults_snapshot(), tr2.faults_snapshot()
d = jax.devices()[0]
print(json.dumps({
    "model": f"MLP d{DIN} supervised training "
             f"({TOTAL_STEPS} steps, 1% step faults, 1 preemption)",
    "platform": d.platform, "device_kind": d.device_kind,
    "steps_per_sec": round(TOTAL_STEPS / chaos_dt, 1),
    "clean_steps_per_sec": round(TOTAL_STEPS / clean_dt, 1),
    "chaos_vs_clean": round(clean_dt / chaos_dt, 3),
    "total_steps": int(m2._step),
    "preempted": preempted,
    "retries": f1["retries"] + f2["retries"],
    "preemptions": f1["preemptions"],
    "async_checkpoints": f1["async_checkpoints"] + f2["async_checkpoints"],
    "sync_checkpoints": f1["sync_checkpoints"] + f2["sync_checkpoints"],
    "checkpoint_stall_s": round(f1["checkpoint_stall_s"]
                                + f2["checkpoint_stall_s"], 4),
    "params_identical_to_clean": identical,
    "traced_steps_per_sec": round(TOTAL_STEPS / traced_dt, 1),
    "training_trace_overhead_frac": round(training_trace_overhead, 4),
    "training_trace_spans_recorded": traced_spans,
    "params_identical_traced": traced_identical,
    "data_wait_frac": tr_phases["data_wait_frac"],
    "checkpoint_stall_frac": tr_phases["checkpoint_stall_frac"],
    "synthetic_data": True}))
"""


TRAINING_ELASTIC_CODE = _COMMON + r"""
# Elastic-training leg of the training_chaos probe (ISSUE 7):
# steps/sec through the ELASTIC fleet path — a 4-worker compressed
# ParallelWrapper run writing SHARDED (format-v3) checkpoints, one
# scripted preemption mid-run, then restart + RE-MESHED resume onto
# 2 workers that finishes the schedule, all inside the timed window.
# The gated number is end-to-end steps/sec (compiles, shard writes,
# the preemption flush, the v3 restore + re-bucketing, and the
# re-meshed warmup compile all included), because that is what a
# shrinking spot fleet actually delivers. Resume wall time (restore +
# re-meshed step rebuild, i.e. the fleet's re-entry latency) is
# reported alongside. Requires >=4 CPU devices
# (--xla_force_host_platform_device_count, set by the harness).
import tempfile
from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.faults import FaultInjector, PreemptionFault
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import (GradientSharingAccumulator,
                                         ParallelWrapper)
from deeplearning4j_tpu.parallel.elastic import FaultTolerantTrainer

EPOCHS = int(sys.argv[1]) if len(sys.argv) > 1 else 6
N, BATCH, DIN = 4096, 64, 64               # 64 steps per epoch
STEPS_PER_EPOCH = N // BATCH
TOTAL_STEPS = EPOCHS * STEPS_PER_EPOCH
W0, W1 = 4, 2                              # preempt at 4, resume at 2

def build():
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=128, activation="tanh"))
            .layer(OutputLayer(n_out=10, loss="mcxent",
                               activation="softmax"))
            .input_type_feed_forward(DIN).build())
    return MultiLayerNetwork(conf).init()

rs = np.random.RandomState(0)
X = rs.rand(N, DIN).astype(np.float32)
Y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, N)]

def it():
    return ArrayDataSetIterator(X, Y, batch=BATCH, shuffle=True, seed=3)

# fixed-shape reference: same schedule, 4 workers throughout (the
# trajectory the re-meshed run is judged against)
ref_dir = tempfile.mkdtemp(prefix="bench_elastic_ref_")
m_ref = build()
pw_ref = ParallelWrapper(m_ref, workers=W0,
                         accumulator=GradientSharingAccumulator())
FaultTolerantTrainer(m_ref, ref_dir, save_every_n_steps=50,
                     wrapper=pw_ref,
                     sharded_checkpoints=True).fit(it(), epochs=EPOCHS)

# timed elastic run: preempt at the midpoint, resume on HALF the fleet
# — with the full observability plane attached (ISSUE 13): tracer,
# event timeline, fleet telemetry all live INSIDE the timed window,
# because a production spot fleet runs instrumented
from deeplearning4j_tpu.tracing import Tracer
from deeplearning4j_tpu.parallel.telemetry import (EventTimeline,
                                                   FleetTelemetry)
el_tracer = Tracer(enabled=True, ring=64)
el_events = EventTimeline()
el_fleet = FleetTelemetry()
el_dir = tempfile.mkdtemp(prefix="bench_elastic_")
t0 = time.perf_counter()
m1 = build()
pw1 = ParallelWrapper(m1, workers=W0,
                      accumulator=GradientSharingAccumulator())
tr1 = FaultTolerantTrainer(
    m1, el_dir, save_every_n_steps=50, wrapper=pw1,
    sharded_checkpoints=True,
    fault_injector=FaultInjector(plan={"preempt": [TOTAL_STEPS // 2]}),
    tracer=el_tracer, events=el_events, fleet_telemetry=el_fleet,
    worker_id=0)
try:
    tr1.fit(it(), epochs=EPOCHS)
    preempted = False
except PreemptionFault:
    preempted = True
# "restart on a shrunk fleet": v3 restore + re-bucket + step rebuild
t_resume = time.perf_counter()
m2 = FaultTolerantTrainer.resume(el_dir)
pw2 = ParallelWrapper(m2, workers=W1,
                      accumulator=GradientSharingAccumulator())
pw2.ensure_step()             # consumes _resume_extra, re-buckets
resume_wall_s = time.perf_counter() - t_resume
tr2 = FaultTolerantTrainer(m2, el_dir, save_every_n_steps=50,
                           wrapper=pw2, sharded_checkpoints=True,
                           tracer=el_tracer, events=el_events,
                           fleet_telemetry=el_fleet, worker_id=0)
tr2.fit(it(), epochs=EPOCHS)
elastic_dt = time.perf_counter() - t0
el_phases = tr2.telemetry_snapshot()["phases"]
el_counts = el_events.counts()
el_straggler = el_fleet.straggler()

flat = lambda m: np.concatenate(
    [np.asarray(a).ravel() for a in jax.tree_util.tree_leaves(m._params)])
ref, got = flat(m_ref), flat(m2)
rel_err = float(np.linalg.norm(ref - got) / np.linalg.norm(ref))
f1, f2 = tr1.faults_snapshot(), tr2.faults_snapshot()
d = jax.devices()[0]
print(json.dumps({
    "elastic_model": f"MLP d{DIN} compressed DP "
                     f"({TOTAL_STEPS} steps, preempt@{W0}w, "
                     f"resume@{W1}w, sharded ckpts)",
    "platform": d.platform,
    "elastic_steps_per_sec": round(TOTAL_STEPS / elastic_dt, 1),
    "elastic_resume_wall_s": round(resume_wall_s, 3),
    "elastic_total_steps": int(m2._step),
    "elastic_preempted": preempted,
    "elastic_remeshed": list(pw2.last_remesh or ()),
    "elastic_sharded_checkpoints": (f1["sharded_checkpoints"]
                                    + f2["sharded_checkpoints"]),
    "elastic_params_rel_err_vs_fixed_shape": round(rel_err, 6),
    "elastic_data_wait_frac": el_phases["data_wait_frac"],
    "elastic_checkpoint_stall_frac": el_phases["checkpoint_stall_frac"],
    "elastic_step_ewma_ms": el_straggler["slowest_ms"],
    "elastic_events": {k: el_counts.get(k, 0)
                       for k in ("preempt_broadcast", "checkpoint_commit",
                                 "re_mesh", "resume")},
    "elastic_trace_spans_recorded": sum(
        len(t["spans"]) for t in el_tracer.dump(limit=64)),
    "synthetic_data": True}))
"""


class LegFailed(Exception):
    """A leg crashed, timed out or printed no JSON result."""


def _run(name, code, env_extra, timeout, argv=()):
    """Run one leg as ``python -c code`` and return the last JSON object
    it printed. Anything else — non-zero exit, timeout, no JSON — is
    :class:`LegFailed` naming the leg: a crash must not become a
    silently missing entry."""
    env = dict(os.environ)
    env.update(env_extra)
    try:
        out = subprocess.run([sys.executable, "-c", code, *map(str, argv)],
                             env=env, capture_output=True, text=True,
                             timeout=timeout)
    except subprocess.TimeoutExpired:
        raise LegFailed(f"leg {name!r} timed out after {timeout}s")
    if out.returncode != 0:
        raise LegFailed(f"leg {name!r} exited {out.returncode}: "
                        f"{out.stderr.strip()[-2000:]}")
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    raise LegFailed(f"leg {name!r} printed no JSON result")


# Prepended to the legs that measure the chip: they refuse to start on
# anything else. (The check lives in the child — this process stays off
# JAX — and outside the leg's own code, which tests/test_bench_harness.py
# drives on the CPU at tiny sizes to validate the harness.)
_REQUIRE_CHIP = r"""
import sys, jax
_d = jax.devices()[0]
if _d.platform != "tpu":
    sys.exit(f"this leg measures the chip; JAX found platform "
             f"{_d.platform!r} ({_d.device_kind!r})")
"""


def _run_on_chip(name, code, timeout, argv=()):
    return _run(name, _REQUIRE_CHIP + code, {}, timeout, argv)


def _mfu(res):
    """Model FLOPs utilization from XLA's own cost analysis. A device
    with no entry in PEAK_FLOPS is an error, not a missing number."""
    if not res or not res.get("flops_per_step") or not res.get("ms_per_iter"):
        return None
    kind = res.get("device_kind")
    if kind not in PEAK_FLOPS:
        raise KeyError(f"no peak FLOP/s recorded for device_kind {kind!r}; "
                       f"add it to PEAK_FLOPS with its source")
    achieved = res["flops_per_step"] / (res["ms_per_iter"] / 1000.0)
    return round(achieved / PEAK_FLOPS[kind], 4)


def _sub(res):
    if not res:
        return None
    out = {"model": res.get("model"),
           "samples_per_sec": round(res.get("samples_per_sec", 0.0), 1),
           "ms_per_iter": round(res.get("ms_per_iter", 0.0), 2),
           "flops_per_step": res.get("flops_per_step"),
           "final_loss": res.get("final_loss"),
           "mfu": _mfu(res)}
    for k in ("test_accuracy", "synthetic_data", "dtype",
              "compile_seconds", "data_source"):
        if k in res:
            out[k] = res[k]
    return out


def _sanity(results):
    """Physics gates (VERDICT r3 #1) over EVERY measured model. Returns
    list of violations. The batch-scaling gate only fires when both
    sides are ResNet50 (same model, 4x batch)."""
    bad = []
    b32 = b128 = None
    for tag, r in results:
        if not r:
            continue
        m = _mfu(r)
        if m is not None and m > 1.0:
            bad.append(f"{tag}: MFU {m} > 1.0 is physically impossible — "
                       "the timer is not measuring device execution")
        model = str(r.get("model", ""))
        if model.startswith("ResNet50") and "batch 32" in model:
            b32 = b32 or r
        if model.startswith("ResNet50") and "batch 128" in model:
            b128 = r
    if b32 and b128 and b32.get("ms_per_iter") and b128.get("ms_per_iter"):
        ratio = b128["ms_per_iter"] / b32["ms_per_iter"]
        if ratio < 2.5:
            bad.append(
                f"batch scaling violated: ms/iter(b128)={b128['ms_per_iter']:.2f} "
                f"is only {ratio:.2f}x ms/iter(b32)={b32['ms_per_iter']:.2f} "
                "(a 4x batch must be ~4x slower per iter)")
    return bad


_CPU_ENV = {"JAX_PLATFORMS": "cpu"}


def main():
    from deeplearning4j_tpu.flags import flags
    skip_secondary = flags.bench_skip_secondary
    # headline: ResNet50 b32, bf16 mixed precision, honest barrier
    res = _run_on_chip("resnet50_b32", RESNET_CODE, 1500,
                       argv=[32, "bfloat16", 20])
    # secondary chip legs, STRICTLY serialized: one process per chip
    extras = {}
    r128 = None
    if not skip_secondary:
        r128 = _run_on_chip("resnet50_b128", RESNET_CODE, 1800,
                            argv=[128, "bfloat16", 10])
        extras["resnet50_b128"] = _sub(r128)
        extras["resnet50_b32_f32"] = _sub(_run_on_chip(
            "resnet50_b32_f32", RESNET_CODE, 1500,
            argv=[32, "float32", 10]))
        extras["bert_base_finetune"] = _sub(_run_on_chip(
            "bert_base_finetune", BERT_CODE, 1800, argv=["bfloat16"]))
        extras["lenet_mnist"] = _sub(_run_on_chip(
            "lenet_mnist", LENET_CODE, 900))
        extras["attention_flash_vs_xla"] = _run_on_chip(
            "attention_flash_vs_xla", ATTENTION_CODE, 1800).get("results")
        # word2vec (BASELINE config 4) is mostly host-side; it records
        # the platform it ran on
        w2v = _run("word2vec", WORD2VEC_CODE, {},
                   timeout=1200)
        extras["word2vec"] = {k: w2v[k] for k in
                              ("tokens_per_sec", "n_tokens", "vocab",
                               "synthetic_data", "wall_seconds",
                               "platform")
                              if k in w2v}
        # ETL throughput, reported separately per the reference's own
        # benchmark methodology (host-side, pinned to the CPU)
        etl = _run("etl_pipeline", ETL_CODE, _CPU_ENV, timeout=600)
        extras["etl_pipeline"] = {k: etl[k] for k in
                                  ("rows_per_sec", "rows",
                                   "wall_seconds") if k in etl}
        # serving runtime: dynamic micro-batching vs the seed
        # per-request path (CPU-JAX by design — the acceptance regime)
        srv = _run("serving", SERVING_CODE, _CPU_ENV, timeout=900)
        extras["serving"] = {k: srv[k] for k in
                             ("model", "requests_per_sec",
                              "unbatched_requests_per_sec",
                              "speedup_vs_unbatched", "p50_ms",
                              "p99_ms", "unbatched_p50_ms",
                              "unbatched_p99_ms",
                              "mean_device_batch", "batch_hist",
                              "compiles", "recompiles_post_warmup")
                             if k in srv}
        # replica fleet: occupancy-aware router over 3 replicas with a
        # scripted zero-loss rolling restart mid-run (CPU-JAX by
        # design — the acceptance regime)
        flt = _run("fleet", FLEET_CODE, _CPU_ENV, timeout=900)
        extras["fleet"] = {k: flt[k] for k in
                           ("model", "requests_per_sec",
                            "requests_total", "wall_seconds",
                            "p50_ms", "p99_ms", "client_failures",
                            "requests_lost", "zero_loss",
                            "restart_clean", "restart_wall_s",
                            "restart_within_traffic",
                            "restarts", "retries",
                            "hedges", "hedges_won",
                            "hedge_budget_denied", "ejections")
                           if k in flt}
        # connection scale (ISSUE 14): ~1,000 idle streaming conns held
        # through the router on the event-loop front-end vs the thread
        # backend at the same count, with interactive probe latency
        # measured under that load (CPU-JAX by design — host-side)
        cs = _run("connscale", CONNSCALE_CODE, _CPU_ENV, timeout=900)
        extras["connscale"] = {k: cs[k] for k in
                               ("model", "streaming_conns",
                                "conns_attempted", "conn_failures",
                                "establish_s", "server_threads",
                                "p50_ms", "p99_ms",
                                "probe_failures", "streams_proxied",
                                "requests_lost",
                                "thread_streaming_conns",
                                "thread_conn_failures",
                                "thread_establish_s",
                                "thread_server_threads",
                                "thread_p50_ms", "thread_p99_ms",
                                "thread_probe_failures",
                                "thread_requests_lost")
                               if k in cs}
        # open-loop overload harness (ISSUE 9): Poisson arrivals with
        # a diurnal ramp and a 2x-measured-capacity overload leg —
        # goodput, shed order, and admitted-interactive SLO under
        # pressure (CPU-JAX by design — the acceptance regime)
        ovl = _run("overload", OVERLOAD_CODE, _CPU_ENV, timeout=900)
        extras["overload"] = {k: ovl[k] for k in
                              ("model", "capacity_rps",
                               "normal_offered",
                               "normal_goodput_ratio",
                               "normal_shed_rate",
                               "normal_interactive_p99_ms",
                               "normal_ttft_ms_p50",
                               "normal_ttft_ms_p99",
                               "normal_itl_ms_p50",
                               "normal_itl_ms_p99",
                               "overload_offered_rps",
                               "overload_offered",
                               "overload_goodput_ratio",
                               "overload_goodput_floor",
                               "overload_goodput_ok",
                               "overload_shed_rate",
                               "overload_deadline_sheds",
                               "engine_shed_total",
                               "engine_shed_batch_total",
                               "engine_shed_deadline_total",
                               "overload_batch_shed_rate",
                               "overload_interactive_shed_rate",
                               "overload_batch_sheds_first",
                               "overload_interactive_p99_ms",
                               "overload_interactive_slo_ms",
                               "overload_interactive_slo_ok",
                               "overload_ttft_ms_p50",
                               "overload_ttft_ms_p99",
                               "overload_itl_ms_p50",
                               "overload_itl_ms_p99",
                               "normal_longctx_ttft_ms_p99",
                               "overload_longctx_completed",
                               "overload_longctx_ttft_ms_p50",
                               "overload_longctx_ttft_ms_p99",
                               "overload_queue_depth_max",
                               "overload_queue_bounded",
                               "fleet_sheds_observed",
                               "fleet_cooldowns",
                               "fleet_breaker_trips",
                               "fleet_goodput",
                               "fleet_shed_total",
                               "requests_lost_fleet_level",
                               "latency_breakdown",
                               "latency_queue_ms_p99",
                               "latency_admission_ms_p99",
                               "latency_device_ms_p99")
                              if k in ovl}
        # continuous-batching generation vs sequential per-request
        # decode (CPU-JAX by design — the acceptance regime)
        gen = _run("generation", GENERATION_CODE, _CPU_ENV, timeout=1500)
        extras["generation"] = {k: gen[k] for k in
                                ("model", "tokens_per_sec",
                                 "sequential_tokens_per_sec",
                                 "speedup_vs_sequential",
                                 "cached_sequential_tokens_per_sec",
                                 "speedup_vs_cached_sequential",
                                 "tokens_identical_to_cached_sequential",
                                 "total_tokens",
                                 "recompiles_post_warmup",
                                 "mean_slot_occupancy",
                                 "slot_utilization",
                                 "ttft_ms_p50", "ttft_ms_p99",
                                 "itl_ms_p50", "itl_ms_p99",
                                 "paged_tokens_per_sec",
                                 "tokens_identical_paged_vs_slots",
                                 "paged_recompiles_post_warmup",
                                 "dense_kv_cache_bytes",
                                 "paged_pool_bytes",
                                 "paged_peak_kv_bytes",
                                 "paged_peak_block_utilization",
                                 "paged_memory_vs_dense",
                                 "chunked_prefills",
                                 "itl_p95_short_ms_baseline",
                                 "itl_p95_short_ms_longprompt_chunked",
                                 "itl_p95_short_ms_longprompt_unchunked",
                                 "chaos_tokens_per_sec",
                                 "chaos_tokens_identical",
                                 "chaos_retries",
                                 "chaos_recoveries",
                                 "chaos_requests_lost",
                                 "chaos_recompiles_post_warmup",
                                 "traced_tokens_per_sec",
                                 "trace_overhead_frac",
                                 "trace_spans_recorded",
                                 "tokens_identical_traced",
                                 "scheduler_overhead_frac",
                                 "prefix_hit_rate",
                                 "prefix_tokens_matched",
                                 "prefix_prefill_tokens_saved_frac",
                                 "prefix_tokens_identical_vs_noshare",
                                 "prefix_recompiles_post_warmup",
                                 "prefix_cow_copies",
                                 "prefix_peak_blocks_shared",
                                 "prefix_peak_blocks_noshare",
                                 "prefix_kv_bytes_per_request",
                                 "noshare_kv_bytes_per_request",
                                 "prefix_users_capacity_ratio",
                                 "prefix_ttft_ms_p50",
                                 "prefix_ttft_ms_p99",
                                 "noshare_ttft_ms_p50",
                                 "session_ttft_turn1_ms",
                                 "session_ttft_turnN_ms",
                                 "nosession_ttft_turnN_ms",
                                 "session_turnN_speedup",
                                 "session_evictions",
                                 "session_blocks_reclaimed",
                                 "spec_k",
                                 "spec_tokens_per_sec",
                                 "spec_plain_tokens_per_sec",
                                 "spec_speedup_vs_plain",
                                 "spec_itl_ms_p99",
                                 "spec_plain_itl_ms_p99",
                                 "spec_accept_rate",
                                 "spec_verify_batches",
                                 "spec_rollbacks",
                                 "spec_draft_fallbacks",
                                 "spec_tokens_identical_vs_plain",
                                 "spec_recompiles_post_warmup",
                                 "kv_equal_pool_bytes",
                                 "kv_f32_tokens_per_sec",
                                 "kv_bf16_tokens_per_sec",
                                 "kv_int8_tokens_per_sec",
                                 "kv_f32_concurrent_users",
                                 "kv_bf16_concurrent_users",
                                 "kv_int8_concurrent_users",
                                 "kv_int8_concurrent_users_vs_f32",
                                 "kv_bf16_logit_rel_err",
                                 "kv_int8_logit_rel_err",
                                 "kv_quant_recompiles_post_warmup",
                                 "offload_live_sessions",
                                 "offload_pool_sessions",
                                 "offload_sessions_per_pool_ratio",
                                 "offload_evicted_reprefills",
                                 "offload_demotions",
                                 "offload_restores",
                                 "offload_prefetch_hits",
                                 "offload_restore_ttft_ms_p50",
                                 "offload_hot_ttft_ms_p50",
                                 "offload_restore_ttft_ratio",
                                 "offload_tokens_identical",
                                 "offload_recompiles_post_warmup",
                                 "offload_restore_ms_p50",
                                 "offload_f32_host_bytes_per_block",
                                 "offload_int8_host_bytes_per_block",
                                 "offload_int8_capacity_vs_f32")
                                if k in gen}
        # resilient-training chaos probe: supervised step loop absorbing
        # ~1% transient step faults + one scripted preemption/resume
        # (CPU-JAX by design — the acceptance regime)
        tc = _run("training_chaos", TRAINING_CHAOS_CODE, _CPU_ENV, timeout=900)
        extras["training_chaos"] = {k: tc[k] for k in
                                    ("model", "steps_per_sec",
                                     "clean_steps_per_sec",
                                     "chaos_vs_clean",
                                     "total_steps", "preempted",
                                     "retries", "preemptions",
                                     "async_checkpoints",
                                     "sync_checkpoints",
                                     "checkpoint_stall_s",
                                     "params_identical_to_clean",
                                     "traced_steps_per_sec",
                                     "training_trace_overhead_frac",
                                     "training_trace_spans_recorded",
                                     "params_identical_traced",
                                     "data_wait_frac",
                                     "checkpoint_stall_frac")
                                    if k in tc}
        # elastic leg (ISSUE 7): 4-worker compressed run with sharded
        # v3 checkpoints, scripted preemption, re-meshed resume at 2
        # workers — needs a virtual multi-device CPU mesh, so it runs
        # as its own subprocess with the device-count flag
        te = _run("training_elastic", TRAINING_ELASTIC_CODE,
                  dict(_CPU_ENV,
                       XLA_FLAGS="--xla_force_host_platform_device_count=8"),
                  timeout=900)
        extras.setdefault("training_chaos", {}).update(
            {k: te[k] for k in
             ("elastic_model", "elastic_steps_per_sec",
              "elastic_resume_wall_s", "elastic_total_steps",
              "elastic_preempted", "elastic_remeshed",
              "elastic_sharded_checkpoints",
              "elastic_params_rel_err_vs_fixed_shape",
              "elastic_data_wait_frac",
              "elastic_checkpoint_stall_frac",
              "elastic_step_ewma_ms", "elastic_events",
              "elastic_trace_spans_recorded")
             if k in te})
    # static cost model (tools/perf_audit.py — chip-independent): the
    # roofline predictions the measured numbers are judged against
    # (VERDICT r4 #2). Committed JSON, so this costs no compile time.
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "tools", "perf_audit.json")) as f:
            audit = json.load(f)
        cm = {}
        for m in audit.get("models", []):
            try:  # keep valid rows even if one model record is stale
                cm[m["model"]] = {
                    "flops": m["flops"],
                    "roofline_ms_v5e_bf16": m["roofline_ms_v5e_bf16"],
                    "pred_samples_per_sec_at_40pct_mfu":
                        m["pred_throughput_at_40pct_mfu"],
                    "stablehlo_dots": m["stablehlo_dtypes"]
                        .get("by_dtype")}
            except Exception as e:
                print(f"cost_model row skipped: {e!r}", file=sys.stderr)
        extras["cost_model"] = cm
    except Exception as e:
        # missing/stale audit file: keep the bench line flowing, but
        # say so — silently dropping the prediction table would unmoor
        # the measured numbers from their judging baseline
        print(f"cost_model unavailable: {e!r}", file=sys.stderr)
    # physics gates — hard-fail rather than publish impossible numbers
    measured = [("headline", res), ("resnet50_b128", r128)]
    measured += [(k, v) for k, v in extras.items()
                 if isinstance(v, dict) and "ms_per_iter" in v]
    violations = _sanity(measured)
    value = round(res.get("samples_per_sec", 0.0), 1)
    mfu = _mfu(res)
    # vs_baseline: BENCH_r01–r03 measured dispatch, not execution (MFU>1)
    # — not comparable. This round restarts the honest series.
    out = {
        "metric": f"{res.get('model', '?')} throughput "
                  f"({res.get('platform', '?')})",
        "value": value,
        "unit": "samples/sec",
        "vs_baseline": 1.0,
        "baseline_note": "r01-r03 BENCH values were dispatch-rate fiction "
                         "(MFU>1); honest series restarts here",
        "device_kind": res.get("device_kind"),
        "ms_per_iter": round(res.get("ms_per_iter", 0.0), 2),
        "flops_per_step": res.get("flops_per_step"),
        "final_loss": res.get("final_loss"),
        "mfu": mfu,
        "timing_contract": "timed region ends with host fetch of final "
                           "loss; every step consumes the previous step's "
                           "params so the fetch forces the full chain",
        "platform": res.get("platform"),
        "extra": extras,
    }
    for k in ("test_accuracy", "synthetic_data", "dtype",
              "compile_seconds", "data_source"):
        if k in res:
            out[k] = res[k]
    if violations:
        out["error"] = "SANITY FAILURE: " + " | ".join(violations)
        print(json.dumps(out))
        sys.exit(2)
    print(json.dumps(out))


if __name__ == "__main__":
    try:
        main()
    except LegFailed as e:
        sys.exit(f"bench.py: {e}")
