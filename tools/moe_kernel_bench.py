#!/usr/bin/env python3
"""The expert kernel alone, on the chip, at the shape of
``lfm2-8b-a1b.decode_backlog`` (32 experts of 2048 x 1792, bfloat16, 4
a token): 12 calls in one program, which is what one decode step (16
tokens, 64 token-expert pairs) or one 256-token prefill chunk (1,024
pairs) makes, against ``jax.lax.ragged_dot`` and, for the decode shape,
the dense product over all 32 experts.

    chiprun -- python3 tools/moe_kernel_bench.py [--tokens 16,256]

Prints, for each token count and form, the milliseconds per 12 calls,
the experts the routing touched, the bytes those experts hold (what the
roofline reads at 819 GB/s) and the largest error against
``ragged_dot`` in float32 at ``highest`` precision, and writes them to
``chiprun_out/moe_kernel_bench.json``. The sibling of
``tools/paged_kernel_bench.py``: the numbers PERF.md section 5 quotes
for the kernel alone. It measures nothing off a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

E, D, F, K, LAYERS = 32, 2048, 1792, 4, 12


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", default="16,256")
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    if jax.devices()[0].platform != "tpu":
        print("moe_kernel_bench: JAX found no TPU; a time from anything "
              "else is not a device number", file=sys.stderr)
        return 2
    from deeplearning4j_tpu.kernels.moe_experts import expert_ffn, silu

    def dense_product(x, w1, w3, w2, sizes):
        """Every row by every expert, its own kept under a mask: what
        a layer without grouping would run."""
        ends = jnp.cumsum(sizes)
        row = jnp.arange(x.shape[0])[None]
        mine = (row >= (ends - sizes)[:, None]) & (row < ends[:, None])
        a = jnp.einsum("md,edf->emf", x, w1,
                       preferred_element_type=jnp.float32)
        b = jnp.einsum("md,edf->emf", x, w3,
                       preferred_element_type=jnp.float32)
        y = jnp.einsum("emf,efd->emd", (silu(a) * b).astype(x.dtype), w2,
                       preferred_element_type=jnp.float32)
        return jnp.where(mine[:, :, None], y, 0.0).sum(0)

    def form(impl):
        if impl == "dense":
            return dense_product
        return lambda *a: expert_ffn(*a, impl=impl)

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    w1, w3 = (jax.random.normal(k, (E, D, F), jnp.bfloat16) * 0.02
              for k in ks[:2])
    w2 = jax.random.normal(ks[2], (E, F, D), jnp.bfloat16) * 0.02
    rs = np.random.RandomState(0)

    def step_of(impl):
        """12 calls in one program, each fed by the one before."""
        def run(x, sizes):
            def body(i, x):
                y = form(impl)(x, w1, w3, w2, sizes)
                return (x.astype(jnp.float32) + 1e-3 * y).astype(x.dtype)
            return lax.fori_loop(0, LAYERS, body, x)
        return jax.jit(run)

    def ms(f, *args, n=10):
        f(*args).block_until_ready()
        t = time.perf_counter()
        for _ in range(n):
            out = f(*args)
        out.block_until_ready()
        return (time.perf_counter() - t) / n * 1e3

    res = {}
    for tokens in (int(t) for t in a.tokens.split(",")):
        # every token picks K distinct experts, uniformly
        picks = np.concatenate([rs.permutation(E)[:K] for _ in range(tokens)])
        sizes = jnp.asarray(np.bincount(picks, minlength=E), jnp.int32)
        touched = int((np.asarray(sizes) > 0).sum())
        x = jax.random.normal(ks[3], (tokens * K, D), jnp.bfloat16)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda x, s: expert_ffn(
                x.astype(jnp.float32), w1.astype(jnp.float32),
                w3.astype(jnp.float32), w2.astype(jnp.float32), s,
                impl="ragged"))(x, sizes)
        forms = ["pallas", "ragged"] + (["dense"] if tokens <= 16 else [])
        for impl in forms:
            got = jax.jit(lambda x, s, impl=impl: form(impl)(
                x, w1, w3, w2, s))(x, sizes)
            err = float(jnp.max(jnp.abs(got - ref)))
            t = ms(step_of(impl), x, sizes)
            res[f"{tokens}.{impl}"] = {
                f"ms_per_{LAYERS}_calls": t, "max_err": err,
                "experts_touched": touched,
                "touched_bytes_a_call": touched * 3 * D * F * 2}
            print(f"{tokens:4d} tokens {impl:7s} {t:9.3f} ms / {LAYERS} "
                  f"calls   touched {touched}/{E}   roofline "
                  f"{LAYERS * touched * 3 * D * F * 2 / 819e9 * 1e3:.3f} ms"
                  f"   err {err:.2e}", flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_kernel_bench.json"),
              "w") as f:
        json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
