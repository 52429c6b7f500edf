#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the
chip.

One process (the only one that touches JAX) drives the two main paths
through the entry points a user calls, at GPT-2-small's published shape
(random weights from a seed; the configurations that are measured are
the benchmark's, ``BENCHMARK.json``):

1. kernels — every Pallas kernel ``impl="auto"`` reaches on a TPU, at
   the served shapes: the lowering holds a Mosaic custom call (so it is
   neither interpret mode nor the XLA form) and the result agrees with
   its XLA reference;
2. serve  — ``InferenceServer`` + ``register_generator`` + ``warmup()``,
   then real HTTP requests from client threads, on the paged and on the
   slot cache backend;
3. train  — a few ``ComputationGraph.fit()`` steps of ResNet50 b32 bf16.

Every phase is fatal on failure. With no TPU the script exits 2 before
importing the package — it never runs on the CPU. The last line of
stdout is ``{"ok": true, "device": {...}}``; everything above it is
set-up facts (seconds, bytes, which implementation ran), not metrics.

Run it through the chip tool: ``chiprun -- python3 chip_smoke.py``.
"""
from __future__ import annotations

import hashlib
import http.client
import json
import os
import sys
import threading
import time

# GPT-2-small at its published width
LM = dict(vocab_size=50257, d_model=768, n_layers=12, n_heads=12,
          d_ff=3072, max_seq_len=1024)
NUM_SLOTS = 8
BLOCK_SIZE = 16
CHUNK_TOKENS = 256
# ladders cut to what the requests below use, so a cold warmup fits the
# time limit (max_seq_len is always a bucket: the 600-token prompt lands
# there, which on the slot backend is the flash-attention prefill)
PROMPT_BUCKETS = (64, 256)
PROMPT_LENS = (5, 40, 130, 300, 600)
MAX_TOKENS = 12
NUMERICS_LENS = (300, 77)   # prefixes of the model-level logits check
TRAIN = dict(batch=32, image=224, classes=1000, steps=6)

# Tolerances, in the units of what is compared. TPU f32 matmuls run at
# default precision (one bf16 pass), so two correct routes to the same
# number differ by ~1e-2 relative; a wrong mask, block or scale moves
# attention outputs (|x| <~ 1) and logits (|x| ~ 1) by O(1).
# First chip run (PR 21): 7.7e-3, 4.6e-3 and 9.0e-3 in that order.
KERNEL_ATOL = 3e-2      # attention output vs XLA reference
GRAD_RTOL = 2e-2        # flash backward, relative to the largest grad
LOGIT_ATOL = 5e-2       # cached decode / engine choice vs full forward


class SmokeFailure(Exception):
    pass


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def require_mosaic(text, what):
    require("tpu_custom_call" in text,
            f"{what}: no Mosaic custom call in the lowered program — "
            f"interpret mode or the XLA form ran instead of the kernel")


def say(line):
    print(line, flush=True)


# ---------------------------------------------------------------------------
# phase 1: kernels
# ---------------------------------------------------------------------------
def _checked(secs, name, fn, ref_fn, args, tol, rel=False):
    """Lower ``fn`` (must hold the Mosaic call), compile, run, compare
    with the jitted XLA ``ref_fn``; adds its seconds to ``secs``."""
    import jax
    import numpy as np
    lowered = jax.jit(fn).lower(*args)
    require_mosaic(lowered.as_text(), name)
    t0 = time.perf_counter()
    exe = lowered.compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(exe(*args))
    t2 = time.perf_counter()
    ref = jax.block_until_ready(jax.jit(ref_fn)(*args))
    err = 0.0
    for o, r in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        o, r = np.asarray(o, np.float32), np.asarray(r, np.float32)
        require(o.shape == r.shape, f"{name}: shape {o.shape} != {r.shape}")
        require(np.isfinite(o).all(), f"{name}: non-finite output")
        e = float(np.abs(o - r).max())
        if rel:
            e /= float(np.abs(r).max())
        err = max(err, e)
    require(err <= tol, f"{name}: max error {err:.3g} > {tol:g} vs XLA")
    say(f"  {name}: pallas (tpu_custom_call), max err {err:.2e} "
        f"(tol {tol:g}), compile {t1 - t0:.2f}s, run {t2 - t1:.3f}s")
    secs["compile_s"] += t1 - t0
    secs["run_s"] += t2 - t1


def phase_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.kernels import (decode_attention,
                                            flash_attention,
                                            paged_attention)
    from deeplearning4j_tpu.kernels.decode_attention import \
        decode_attention_xla
    from deeplearning4j_tpu.kernels.kv_quant import quantize_rows
    from deeplearning4j_tpu.kernels.paged_attention import (
        fuse_kv, gather_blocks, paged_attention_xla)
    from deeplearning4j_tpu.parallel.longseq import dot_product_attention

    S, H, D, Bs, T = (NUM_SLOTS, LM["n_heads"],
                      LM["d_model"] // LM["n_heads"], BLOCK_SIZE,
                      LM["max_seq_len"])
    B = T // Bs
    N = S * B + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (S, H, D))
    k_pool = jax.random.normal(ks[1], (N, H, Bs, D))
    v_pool = jax.random.normal(ks[2], (N, H, Bs, D))
    # every sequence owns a scattered run of pool blocks (block 0 is the
    # reserved null block); lengths sit on and around block boundaries
    tables = jnp.asarray(np.random.RandomState(0).permutation(
        np.arange(1, N)).reshape(S, B).astype(np.int32))
    lengths = jnp.asarray(
        np.resize([1, Bs - 1, Bs, Bs + 1, 300, 777, T - 1, T], S),
        jnp.int32)
    secs = {"compile_s": 0.0, "run_s": 0.0}
    casts = {"f32": lambda x: x,
             "bf16": lambda x: x.astype(jnp.bfloat16),
             "int8": quantize_rows}
    for dt, cast in casts.items():
        pool = fuse_kv(cast(k_pool), cast(v_pool))
        _checked(secs, f"paged_attention/{dt}",
                 lambda q, pool, t, l: paged_attention(q, pool, t, l),
                 paged_attention_xla, (q, pool, tables, lengths),
                 KERNEL_ATOL)
        # the same prefix as dense per-slot panels: the slot kernel
        kc, vc = jax.jit(gather_blocks)(pool, tables)
        _checked(secs, f"decode_attention/{dt}",
                 lambda q, kc, vc, l: decode_attention(q, kc, vc, l),
                 decode_attention_xla, (q, kc, vc, lengths), KERNEL_ATOL)

    # flash attention as the engine's 1024-bucket prefill calls it
    # (causal + key-padding mask), and its backward as training would
    Bf = 2
    qf, kf, vf, w = (jax.random.normal(k, (Bf, T, H, D)) for k in ks[2:6])
    km = (jnp.arange(T)[None] < jnp.asarray([[600], [T]])).astype(
        jnp.float32)

    def ref_fwd(q, k, v, km):
        return dot_product_attention(
            q, k, v, mask=km[:, None, None, :] > 0, causal=True)

    _checked(secs, "flash_attention/fwd",
             lambda q, k, v, km: flash_attention(q, k, v, causal=True,
                                                 key_mask=km),
             ref_fwd, (qf, kf, vf, km), KERNEL_ATOL)
    _checked(secs, "flash_attention/bwd",
             jax.grad(lambda q, k, v, w: jnp.sum(
                 flash_attention(q, k, v, causal=True) * w),
                 argnums=(0, 1, 2)),
             jax.grad(lambda q, k, v, w: jnp.sum(
                 dot_product_attention(q, k, v, causal=True) * w),
                 argnums=(0, 1, 2)),
             (qf, kf, vf, w), GRAD_RTOL, rel=True)
    return secs


# ---------------------------------------------------------------------------
# phase 2: serve
# ---------------------------------------------------------------------------
def _post(port, payload, stream=False):
    """One real HTTP generate call. Returns (status, tokens, final)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", "/v1/models/lm/generate",
                     body=json.dumps(dict(payload, stream=stream)).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read().decode()
    finally:
        conn.close()
    if resp.status != 200:
        return resp.status, None, body
    if not stream:
        out = json.loads(body)
        return 200, out["tokens"], out
    items = [json.loads(line) for line in body.strip().splitlines()]
    tokens = [c["token"] for c in items if "token" in c]
    require(items[-1].get("done") is True
            and items[-1]["tokens"] == tokens,
            "streamed chunks disagree with the final object")
    return 200, tokens, items[-1]


def _stats(port):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", "/stats")
        return json.loads(conn.getresponse().read())["models"]["lm"]
    finally:
        conn.close()


def _paged_numerics(lm, ref_lm):
    """Model-level logits, outside the engine: the Pallas paged decode
    against the XLA one on the SAME pools, and chunked-prefill-then-
    decode against the uncached full forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.serving.paging import PagedKVCache

    params = lm._params
    T, B = LM["max_seq_len"], LM["max_seq_len"] // BLOCK_SIZE
    lens = NUMERICS_LENS
    pools = PagedKVCache(lm.cache_shapes(BLOCK_SIZE),
                         len(lens) * B + 1).pools
    tables = 1 + np.arange(len(lens) * B, dtype=np.int32).reshape(-1, B)
    rs = np.random.RandomState(7)
    seqs = [rs.randint(0, LM["vocab_size"], n + 1).astype(np.int32)
            for n in lens]      # prompt + the token the decode step feeds
    chunk = jax.jit(lm.forward_prefill_chunk)
    worst_prefill = 0.0
    refs = []
    for s, (seq, n) in enumerate(zip(seqs, lens)):
        full = np.zeros((1, T), np.int32)
        full[0, :n + 1] = seq
        ref = np.asarray(ref_lm.logits(full))[0]          # [T, V]
        refs.append(ref[n])
        for p0 in range(0, n, CHUNK_TOKENS):
            c = min(CHUNK_TOKENS, n - p0)
            toks = np.zeros((1, CHUNK_TOKENS), np.int32)
            toks[0, :c] = seq[p0:p0 + c]
            logits, pools, _ = chunk(params, toks, jnp.int32(p0),
                                     jnp.int32(c), pools, tables[s])
            worst_prefill = max(worst_prefill, float(np.abs(
                np.asarray(logits)[:c] - ref[p0:p0 + c]).max()))
    toks = jnp.asarray([seq[-1] for seq in seqs], jnp.int32)
    pos = jnp.asarray(lens, jnp.int32)
    out = {}
    for impl in ("pallas", "xla"):
        lowered = jax.jit(
            lambda p, t, ps, kv, tb, impl=impl:
            lm.forward_decode_paged(p, t, ps, kv, tb, impl)[0]
        ).lower(params, toks, pos, pools, tables)
        if impl == "pallas":
            require_mosaic(lowered.as_text(), "forward_decode_paged")
        out[impl] = np.asarray(lowered.compile()(
            params, toks, pos, pools, tables))
    require(np.isfinite(out["pallas"]).all(), "non-finite decode logits")
    d_impl = float(np.abs(out["pallas"] - out["xla"]).max())
    d_ref = float(np.abs(out["pallas"] - np.stack(refs)).max())
    agree = float((out["pallas"].argmax(-1)
                   == np.stack(refs).argmax(-1)).mean())
    say(f"  logits: paged decode pallas vs xla max |d| {d_impl:.2e}; "
        f"chunked prefill vs full forward {worst_prefill:.2e}; "
        f"prefill-then-decode vs full forward {d_ref:.2e} "
        f"(tol {LOGIT_ATOL:g}); greedy agreement {agree:.2f}")
    require(max(d_impl, d_ref, worst_prefill) <= LOGIT_ATOL,
            "cached logits disagree with the reference")


def phase_serve(lm, ref_lm, cache):
    import numpy as np
    from deeplearning4j_tpu.serving import InferenceServer

    paged = cache == "paged"
    opts = dict(num_slots=NUM_SLOTS, max_seq_len=LM["max_seq_len"],
                prompt_buckets=list(PROMPT_BUCKETS), cache=cache)
    if paged:
        opts.update(block_size=BLOCK_SIZE,
                    prefill_chunk_tokens=CHUNK_TOKENS)
    srv = InferenceServer(port=0)
    try:
        g = srv.register_generator("lm", lm, **opts)
        t0 = time.perf_counter()
        g.warmup()
        warm_s = time.perf_counter() - t0
        compiles = g.metrics.compiles
        require_mosaic(g.engine._get_decode_exe().as_text(),
                       f"{cache} decode executable")
        if not paged:
            require_mosaic(
                g.engine._get_prefill_exe(LM["max_seq_len"]).as_text(),
                f"slots prefill executable, {LM['max_seq_len']} bucket")

        # a handful of concurrent requests with unrelated prompts (no
        # accidental prefix hits: what each request computes must not
        # depend on arrival order), one of them streamed
        rs = np.random.RandomState(1)
        prompts = [rs.randint(0, LM["vocab_size"], n).tolist()
                   for n in PROMPT_LENS]
        results = [None] * len(prompts)

        def client(i):
            try:
                results[i] = _post(srv.port,
                                   {"prompt": prompts[i],
                                    "max_tokens": MAX_TOKENS,
                                    "temperature": 0.0}, stream=(i == 2))
            except Exception as e:  # noqa: BLE001 — reported below
                results[i] = (None, None, repr(e))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
            require(not t.is_alive(), "client thread still waiting")
        if paged:
            # one two-turn conversation: turn 2 resends the history and
            # must prefill only what the session does not already hold
            turn1 = rs.randint(0, LM["vocab_size"], 70).tolist()
            r1 = _post(srv.port, {"prompt": turn1, "max_tokens": MAX_TOKENS,
                                  "temperature": 0.0, "session_id": "c1"})
            require(r1[0] == 200, f"session turn 1: HTTP {r1[0]} {r1[2]}")
            before = _stats(srv.port)["paged"]["prefix_cache"]
            turn2 = turn1 + r1[1] + rs.randint(
                0, LM["vocab_size"], 20).tolist()
            prompts += [turn1, turn2]
            results += [r1, _post(srv.port, {
                "prompt": turn2, "max_tokens": MAX_TOKENS,
                "temperature": 0.0, "session_id": "c1"})]
        req_s = time.perf_counter() - t0

        for p, (code, tokens, final) in zip(prompts, results):
            require(code == 200, f"prompt of {len(p)}: HTTP {code} {final}")
            require(len(tokens) == MAX_TOKENS
                    and final["finish_reason"] == "length",
                    f"prompt of {len(p)}: got {len(tokens)} tokens, "
                    f"{final.get('finish_reason')}")
        st = _stats(srv.port)
        faults = st["faults"]
        require(st["compile_cache"]["compiles"] == compiles,
                f"{st['compile_cache']['compiles'] - compiles} compiles "
                f"after warmup")
        require(faults["retries"] == faults["recoveries"]
                == faults["quarantined"] == 0 and st["server_errors"] == 0
                and st["timeouts"] == 0,
                f"the engine recovered from something: {faults}, "
                f"server_errors {st['server_errors']}")
        if paged:
            pc = st["paged"]["prefix_cache"]
            require(st["paged"]["chunked_prefills"] >= 2,
                    "chunked prefill did not run")
            require(pc["session_hits"] == before["session_hits"] + 1
                    and pc["prefill_tokens"] - before["prefill_tokens"]
                    < len(turn2) // 2,
                    f"session turn 2 re-prefilled its history: {pc}")

        # the engine's greedy choices against the uncached full forward:
        # near-tied random-init logits may flip an argmax, so the check
        # is that each chosen token is within tolerance of the best one
        T = LM["max_seq_len"]
        agree = total = 0
        worst = 0.0
        for p, (_, tokens, _) in zip(prompts, results):
            full = np.zeros((1, T), np.int32)
            full[0, :len(p) + len(tokens)] = p + tokens
            # row len(p)-1+i of the full forward predicts token i
            rows = len(p) - 1 + np.arange(len(tokens))
            ref = np.asarray(ref_lm.logits(full)[0][rows])
            worst = max(worst, float(
                (ref.max(-1) - ref[np.arange(len(tokens)), tokens]).max()))
            agree += int((ref.argmax(-1) == np.asarray(tokens)).sum())
            total += len(tokens)
        require(worst <= LOGIT_ATOL,
                f"{cache}: a served token is {worst:.3g} logits below the "
                f"reference's best (tol {LOGIT_ATOL:g})")
        say(f"  {cache}: {len(prompts)} requests HTTP 200, "
            f"{total} tokens, greedy agreement with the full forward "
            f"{agree}/{total} (worst margin {worst:.2e}); decode = pallas"
            + ("" if paged else
               f", prefill@{LM['max_seq_len']} = flash pallas")
            + f"; warmup {warm_s:.1f}s ({compiles} executables), "
            f"requests {req_s:.1f}s; 0 post-warmup compiles, 0 retries/"
            f"recoveries/quarantines")
        if paged:
            _paged_numerics(lm, ref_lm)
        return {"warmup_s": warm_s, "requests_s": req_s,
                "tokens": [r[1] for r in results]}
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# phase 3: train
# ---------------------------------------------------------------------------
def phase_train():
    import jax
    import numpy as np
    from deeplearning4j_tpu.datasets import ArrayDataSetIterator
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    b, hw, ncls = TRAIN["batch"], TRAIN["image"], TRAIN["classes"]
    model = ResNet50(num_classes=ncls, seed=0,
                     input_shape=(hw, hw, 3)).init()
    model.conf.dtype = "bfloat16"   # bf16 compute, f32 master params
    rs = np.random.RandomState(0)
    x = rs.rand(b, hw, hw, 3).astype(np.float32)
    y = np.eye(ncls, dtype=np.float32)[rs.randint(0, ncls, b)]
    it = ArrayDataSetIterator(x, y, batch=b)
    before = [np.asarray(p) for p in
              jax.tree_util.tree_leaves(model.params())][:8]
    losses = []
    t0 = time.perf_counter()
    model.fit(it, epochs=1)                 # compiles, then one step
    losses.append(model.score_)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(TRAIN["steps"] - 1):
        model.fit(it, epochs=1)
        losses.append(model.score_)         # float(): waits for the step
    steps_s = time.perf_counter() - t0
    after = [np.asarray(p) for p in
             jax.tree_util.tree_leaves(model.params())][:8]
    require(all(np.isfinite(l) for l in losses),
            f"non-finite loss: {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    require(any((a != c).any() for a, c in zip(before, after)),
            "parameters did not change")
    say(f"  ResNet50 b{b} bf16 fit(): loss "
        + " ".join(f"{l:.3f}" for l in losses)
        + f"; first step incl. compile {first_s:.1f}s, "
        f"{TRAIN['steps'] - 1} more steps {steps_s:.2f}s")
    return {"first_step_s": first_s, "steps_s": steps_s, "losses": losses}


# ---------------------------------------------------------------------------
def main() -> int:
    t_start = time.perf_counter()
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}, "
              f"{dev.device_kind!r}); this script does not run on the "
              f"CPU", file=sys.stderr)
        return 2
    say(f"platform: {device['platform']}")
    say(f"device_kind: {device['kind']}")
    say(f"device_count: {device['count']}")

    from deeplearning4j_tpu.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {cache_dir} ({entries} entries at start)")

    from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM
    say("phase kernels")
    kern = phase_kernels()
    lm = CausalTransformerLM(**LM, seed=0).init()
    # same weights, attention forced to the plain XLA form: the
    # reference the served tokens and cached logits are judged against
    ref_lm = CausalTransformerLM(**LM, seed=0, implementation="plain")
    ref_lm._params = lm._params
    say("phase serve")
    serve = {c: phase_serve(lm, ref_lm, c) for c in ("paged", "slots")}
    say("phase train")
    train = phase_train()

    from deeplearning4j_tpu import runtime
    say("native runtime: "
        + ("loaded" if runtime._lib is not None else
           "not loaded — the serve and train paths run without "
           "native/libdl4jtpu_runtime.so"))
    digest = hashlib.sha256(json.dumps(
        [serve["paged"]["tokens"], serve["slots"]["tokens"],
         train["losses"]]).encode()).hexdigest()[:16]
    say(f"outputs digest: {digest}")
    say(f"seconds: kernels compile {kern['compile_s']:.1f} run "
        f"{kern['run_s']:.2f} | serve warmup(compile) paged "
        f"{serve['paged']['warmup_s']:.1f} slots "
        f"{serve['slots']['warmup_s']:.1f}, requests paged "
        f"{serve['paged']['requests_s']:.1f} slots "
        f"{serve['slots']['requests_s']:.1f} | train first step(compile) "
        f"{train['first_step_s']:.1f} steps {train['steps_s']:.2f} | "
        f"total {time.perf_counter() - t_start:.1f}")
    say(f"compile cache entries at end: {len(os.listdir(cache_dir))} "
        f"(was {entries})")
    say(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")
    say("phases passed: kernels, serve (paged, slots), train")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
