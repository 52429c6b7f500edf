#!/usr/bin/env python3
"""The paged decode kernel alone, on the chip, at the benchmark cell's
shape (S 16, H 25, D 64, Bs 16, a table of 64, a pool of 321 blocks
``[321, 25, 16, 128]``, K and V side by side): 48 calls in one program,
which is what one decode step of GPT-2 XL makes, over three sets of
lengths.

    chiprun -- python3 tools/paged_kernel_bench.py [--kv f32|bf16|int8]
        [--chunk-blocks 2,4,8,16] [--heads 25] [--kv-heads 25]
        [--layers 48] [--blocks 321]

``--heads 32 --kv-heads 8 --layers 3 --blocks 1025 --kv bf16`` is the
grouped-query shape of ``lfm2-8b-a1b.decode_backlog`` (PR 30): three
calls a step, four query heads to a KV head.

Prints, for XLA's gather path and for the Pallas kernel at each ``G``
(pool blocks a grid step; ``--chunk-blocks`` sets it past the kernel's
own budget and cap), the milliseconds per ``--layers`` calls and the largest error against the XLA path at ``highest``
precision, and writes them to ``chiprun_out/paged_kernel_bench_<kv>.json``.
``ragged`` is 15 lanes of 122-640 keys and a free lane (4,400 live
keys), ``full`` 16 lanes of 320 (the pool holds no more), ``ones``
every lane at length 1: the walk over the table and nothing else.
A scratch tool of PR 27 (PERF.md sections 5 and 6: how ``G`` was
chosen; swept again in PR 31, when the pool's layout changed). It
measures nothing off a TPU.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

S, D, BS, B = 16, 64, 16, 64
RAGGED = [301, 122, 275, 155, 179, 314, 378, 545, 169, 363, 268, 187, 274,
          229, 1, 640]
CASES = {"ragged": RAGGED, "full": [320] * S, "ones": [1] * S}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kv", choices=("f32", "bf16", "int8"), default="f32")
    ap.add_argument("--chunk-blocks", default="2,4,8,16",
                    help="G: pool blocks one grid step attends")
    ap.add_argument("--heads", type=int, default=25, help="query heads")
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="KV heads of a pool block (default: --heads)")
    ap.add_argument("--layers", type=int, default=48,
                    help="calls in the timed program")
    ap.add_argument("--blocks", type=int, default=321, help="pool blocks")
    a = ap.parse_args(argv)
    H, N, LAYERS = a.heads, a.blocks, a.layers
    HKV = a.kv_heads or H
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    if jax.devices()[0].platform != "tpu":
        print("paged_kernel_bench: JAX found no TPU; a time from anything "
              "else is not a device number", file=sys.stderr)
        return 2
    from deeplearning4j_tpu.kernels.kv_quant import quantize_rows
    pa = importlib.import_module(
        "deeplearning4j_tpu.kernels.paged_attention")

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (S, H, D), jnp.float32)
    cast = {"f32": lambda x: x, "bf16": lambda x: x.astype(jnp.bfloat16),
            "int8": quantize_rows}[a.kv]
    pool = pa.fuse_kv(
        cast(jax.random.normal(ks[1], (N, HKV, BS, D), jnp.float32)),
        cast(jax.random.normal(ks[2], (N, HKV, BS, D), jnp.float32)))
    itemsize = {"f32": 4, "bf16": 2, "int8": 1}[a.kv]
    rs = np.random.RandomState(0)

    def tables(lens):
        """Every live lane owns a scattered run of pool blocks."""
        tbl = np.zeros((S, B), np.int32)
        free = list(rs.permutation(np.arange(1, N)))
        for s, n in enumerate(lens):
            for i in range(-(-n // BS) if n > 1 else 0):
                tbl[s, i] = free.pop()
        return jnp.asarray(tbl), jnp.asarray(lens, jnp.int32)

    def step_of(fn):
        """``--layers`` calls in one program, each fed by the one
        before."""
        def run(q, pool, tbl, lens):
            return lax.fori_loop(
                0, LAYERS,
                lambda i, x: q + 1e-3 * fn(x, pool, tbl, lens), q)
        return jax.jit(run)

    def ms(f, *args, n=10):
        f(*args).block_until_ready()
        t = time.perf_counter()
        for _ in range(n):
            out = f(*args)
        out.block_until_ready()
        return (time.perf_counter() - t) / n * 1e3

    variants = {"xla": pa.paged_attention_xla}
    for g in (int(x) for x in a.chunk_blocks.split(",")):
        def kernel(*args, g=g):
            # both are read while tracing: no budget, the cap alone
            saved = pa._VMEM_BLOCK_BUDGET, pa._MAX_BLOCKS
            pa._VMEM_BLOCK_BUDGET, pa._MAX_BLOCKS = 1 << 40, g
            try:
                return pa.paged_attention_pallas(*args)
            finally:
                pa._VMEM_BLOCK_BUDGET, pa._MAX_BLOCKS = saved
        variants[f"pallas_G{g}"] = kernel
    res = {}
    for case, lens in CASES.items():
        tbl, ln = tables(lens)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(pa.paged_attention_xla)(q, pool, tbl, ln)
        for name, fn in variants.items():
            err = float(jnp.max(jnp.abs(
                jax.jit(fn)(q, pool, tbl, ln) - ref)))
            t = ms(step_of(fn), q, pool, tbl, ln)
            res[f"{case}.{name}"] = {f"ms_per_{LAYERS}_calls": t,
                                     "max_err": err}
            print(f"{case:7s} {name:11s} {t:9.3f} ms / {LAYERS} calls   "
                  f"err {err:.2e}", flush=True)
    live = sum(RAGGED)
    print(f"ragged: {live} live keys, "
          f"{live * 2 * HKV * D * itemsize * LAYERS} pool bytes a step")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    tag = a.kv if HKV == H == 25 else f"{a.kv}_h{H}kv{HKV}"
    with open(os.path.join(ROOT, "chiprun_out",
                           f"paged_kernel_bench_{tag}.json"), "w") as f:
        json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
