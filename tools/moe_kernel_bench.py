#!/usr/bin/env python3
"""The expert kernel alone, on the chip, at the shape of
``lfm2-8b-a1b.decode_backlog`` (32 experts of 2048 x 1792, bfloat16, 4
a token): 12 calls in one program, which is what one decode step (16
tokens, 64 token-expert pairs) or one 256-token prefill chunk (1,024
pairs) makes, against ``jax.lax.ragged_dot`` and, for the decode shape,
the dense product over all 32 experts.

    chiprun -- python3 tools/moe_kernel_bench.py [--tokens 16,256]
    chiprun -- python3 tools/moe_kernel_bench.py --unsort

Prints, for each token count and form, the milliseconds per 12 calls,
the experts the routing touched, the bytes those experts hold (what the
roofline reads at 819 GB/s) and the largest error against
``ragged_dot`` in float32 at ``highest`` precision, and writes them to
``chiprun_out/moe_kernel_bench.json``. The sibling of
``tools/paged_kernel_bench.py``: the numbers PERF.md section 5 quotes
for the kernel alone. It measures nothing off a TPU.

``--unsort`` times what follows the kernel instead (PR 38): the experts'
sorted output rows put back in pair order and summed with their
weights, the parent's scatter form (mask, ``.at[order].set``,
``[T, k, D]``) against ``nn/layers/moe.py::combine_sorted`` (a gather
through the inverse permutation) and against that gather alone, at
``smallthinker-21b-a3b``'s chunk (6,144 pairs x 2,560) and
``lfm2-8b-a1b``'s (1,024 x 2,048), 12 calls in one program, every call
with operands of its own (one shared operand lets XLA hoist the work
out of the loop, PR 33). Written to ``chiprun_out/moe_unsort_bench.json``.
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


#: (pairs, width, experts, experts a token) of each cell's chunk
UNSORT_SHAPES = ((6144, 2560, 64, 6), (1024, 2048, 32, 4))


def _ms(f, *args, n=10):
    """Milliseconds a call of ``f`` (compiled and warmed first)."""
    import jax
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def unsort(out_path: str) -> dict:
    """The parent's scatter form against the gather, per 12 calls."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.nn.layers.moe import combine_sorted

    def scatter_form(ys, order, experts, g, E):
        T, k = experts.shape
        flat = experts.reshape(-1)
        ys = jnp.where((flat[order] < E)[:, None], ys, 0.0)
        pairs = jnp.zeros_like(ys).at[order].set(ys).reshape(T, k, -1)
        return (pairs * g[..., None]).sum(1)

    def gather_alone(ys, order, experts, g, E):
        T, k = experts.shape
        return ys[jnp.argsort(order).reshape(T, k).T]

    forms = {"scatter": scatter_form, "gather": combine_sorted,
             "gather_alone": gather_alone}
    rs = np.random.RandomState(38)
    res = {}
    for P, D, E, K in UNSORT_SHAPES:
        T = P // K
        ops = []
        for i in range(LAYERS):
            # K distinct experts a token; the last eighth of the tokens
            # dead (no expert), as past a chunk's length
            ex = np.stack([rs.permutation(E)[:K] for _ in range(T)])
            ex[T - T // 8:] = E
            g = rs.rand(T, K).astype(np.float32)
            order = np.argsort(ex.reshape(-1), kind="stable")
            ys = jax.random.normal(jax.random.PRNGKey(i), (P, D),
                                   jnp.float32)
            ops.append((ys, jnp.asarray(order, jnp.int32),
                        jnp.asarray(ex, jnp.int32), jnp.asarray(g)))
        want = None
        for name, form in forms.items():
            f = jax.jit(lambda ops, form=form: [form(*o, E) for o in ops])
            got = f(ops)
            if name == "scatter":
                want = got
            err = None if name == "gather_alone" else max(
                float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want))
            t = _ms(f, ops)
            res[f"{P}x{D}.{name}"] = {f"ms_per_{LAYERS}_calls": t,
                                      "ms_a_call": t / LAYERS,
                                      "max_err_vs_scatter": err}
            print(f"{P:5d} x {D} {name:13s} {t:8.3f} ms / {LAYERS} calls "
                  f"({t / LAYERS:.3f} a call)   err {err}", flush=True)
    with open(out_path, "w") as f:
        json.dump(res, f, indent=1)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", default="16,256")
    ap.add_argument("--unsort", action="store_true")
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    if jax.devices()[0].platform != "tpu":
        print("moe_kernel_bench: JAX found no TPU; a time from anything "
              "else is not a device number", file=sys.stderr)
        return 2
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    if a.unsort:
        unsort(os.path.join(ROOT, "chiprun_out", "moe_unsort_bench.json"))
        return 0
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
            t = _ms(step_of(impl), x, sizes)
            res[f"{tokens}.{impl}"] = {
                f"ms_per_{LAYERS}_calls": t, "max_err": err,
                "experts_touched": touched,
                "touched_bytes_a_call": touched * 3 * D * F * 2}
            print(f"{tokens:4d} tokens {impl:7s} {t:9.3f} ms / {LAYERS} "
                  f"calls   touched {touched}/{E}   roofline "
                  f"{LAYERS * touched * 3 * D * F * 2 / 819e9 * 1e3:.3f} ms"
                  f"   err {err:.2e}", flush=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_kernel_bench.json"),
              "w") as f:
        json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
