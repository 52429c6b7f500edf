#!/usr/bin/env python3
"""The paged decode kernel alone, on the chip, at the benchmark cell's
shape (S 16, H 25, D 64, Bs 16, a table of 64, a pool of 321 blocks
``[321, 25, 16, 128]``, K and V side by side): 48 calls in one program,
which is what one decode step of GPT-2 XL makes, over three sets of
lengths.

    chiprun -- python3 tools/paged_kernel_bench.py [--kv f32|bf16|int8]
        [--chunk-blocks 2,4,8,16] [--heads 25] [--kv-heads 25]
        [--layers 48] [--blocks 321] [--head-dim 64] [--block-size 16]
        [--table 64] [--window W]

``--heads 32 --kv-heads 8 --layers 3 --blocks 1025 --kv bf16`` is the
grouped-query shape of ``lfm2-8b-a1b.decode_backlog`` (PR 30): three
calls a step, four query heads to a KV head. The long-context cells'
calls, heads of 128 lanes on the MXU form, bf16: ``--heads 28
--kv-heads 4 --head-dim 128 --block-size 64 --table 256 --layers 3
--blocks 3073`` (``smallthinker-21b-a3b``'s global layers), the same
with ``--table 81 --window 4096 --layers 9 --blocks 1297`` (its window
layers, a ring of 81 entries a slot), and ``--heads 20 --kv-heads 1
--head-dim 128 --block-size 64 --table 256 --layers 2 --blocks 4097``
(``jamba2-3b``).

``--prefill`` times a chunk's two pool operations instead (PR 33),
``--layers`` of each in one program, every call with a pool of its own
as every layer of the program has (a gather out of one shared pool is
hoisted out of the timed loop and reads 10x too fast): the write of one
request's 256 rows, row by row (``kv_pool_set``, what a chunk did
before PR 33: ~70 ns a row and head) and by blocks
(``kv_pool_set_span``), at ``p0`` 0 and 37; and the attention
(``paged_prefill_attention``: the span gathered and attended densely)
at the three table buckets the cell's traffic meets (16, 32, 64 blocks)
and starts ``p0`` 0 and 256. ``--prefill --heads 28 --kv-heads 4
--head-dim 128 --block-size 64 --chunk 1024 --window 4096 --layers 3
--blocks 3073 --kv bf16`` is the long-context cell's shape (PR 35):
there the table spans more than 2,048 keys and the attention is the
tiled Pallas kernel, timed at starts 0 to 13,312 in table bucket 256
and, with ``--window``, through a ring of window + chunk + one block.

Prints, for XLA's gather path and for the Pallas kernel at each ``G``
(pool blocks a grid step; ``--chunk-blocks`` sets it past the kernel's
own budget and cap), the milliseconds per ``--layers`` calls and the largest error against the XLA path at ``highest``
precision, and writes them to ``chiprun_out/paged_kernel_bench_<kv>.json``.
``ragged`` is 15 lanes of 122-640 keys and a free lane (4,400 live
keys at a table of 1,024 positions; the lengths scale with the table's
positions, or with four times a window's, every lane past it), ``full`` every lane as long as the table or the pool allows
(320 at the first cell's shape), ``ones`` every lane at length 1: with
a grid over the live (slot, chunk) pairs one grid step a lane,
before it the walk over the whole table and nothing else. A window
reads the last ``--window`` positions through a ring of ``--table``
entries. A scratch tool (PERF.md sections 5 and 6: how ``G`` was
chosen, and swept again whenever the pool's layout or the grid
changed). It measures nothing off a TPU.
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
C = 256     # --prefill: the chunk's rows
#: lengths at a table of 1,024 positions (the first cell's)
RAGGED = [301, 122, 275, 155, 179, 314, 378, 545, 169, 363, 268, 187, 274,
          229, 1, 640]


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
    ap.add_argument("--head-dim", type=int, default=D)
    ap.add_argument("--block-size", type=int, default=BS)
    ap.add_argument("--chunk", type=int, default=C,
                    help="--prefill: rows of the chunk")
    ap.add_argument("--table", type=int, default=B,
                    help="table (or ring) entries a slot of the decode call")
    ap.add_argument("--window", type=int, default=None,
                    help="the windowed call, over a ring (--prefill: also)")
    ap.add_argument("--prefill", action="store_true",
                    help="a chunk's write and attention, not the decode "
                         "step's kernel")
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

    cast = {"f32": lambda x: x, "bf16": lambda x: x.astype(jnp.bfloat16),
            "int8": quantize_rows}[a.kv]
    itemsize = {"f32": 4, "bf16": 2, "int8": 1}[a.kv]
    tag = a.kv if HKV == H == 25 else f"{a.kv}_h{H}kv{HKV}"
    if (a.head_dim, a.block_size, a.table) != (D, BS, B) or a.window:
        tag += f"_d{a.head_dim}bs{a.block_size}t{a.table}" + (
            f"w{a.window}" if a.window else "")

    def ms(f, *args, n=10):
        f(*args).block_until_ready()
        t = time.perf_counter()
        for _ in range(n):
            out = f(*args)
        out.block_until_ready()
        return (time.perf_counter() - t) / n * 1e3

    def write(res, name):
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
            json.dump(res, f, indent=1)

    if a.prefill:
        return prefill(a, pa, cast, ms, write, tag)
    Dh, Bs, Bt, W = a.head_dim, a.block_size, a.table, a.window
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (S, H, Dh), jnp.float32)
    pool = pa.fuse_kv(
        cast(jax.random.normal(ks[1], (N, HKV, Bs, Dh), jnp.float32)),
        cast(jax.random.normal(ks[2], (N, HKV, Bs, Dh), jnp.float32)))
    rs = np.random.RandomState(0)
    # a window's lanes all past it, as the long-context cells' are
    scale = (4 * W if W else Bt * Bs) / 1024
    full = Bt * Bs if W else min(Bt, (N - 1) // S) * Bs
    cases = {"ragged": [max(1, int(n * scale)) if n > 1 else n
                        for n in RAGGED],
             "full": [full] * S, "ones": [1] * S}

    def tables(lens):
        """Every live lane owns a scattered run of pool blocks (a ring:
        as many as it has touched, at most all its entries)."""
        tbl = np.zeros((S, Bt), np.int32)
        free = list(rs.permutation(np.arange(1, N)))
        for s, n in enumerate(lens):
            for i in range(min(Bt, -(-n // Bs)) if n > 1 else 0):
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

    def xla(*args):
        return pa.paged_attention_xla(*args, window=W)
    variants = {"xla": xla}
    for g in (int(x) for x in a.chunk_blocks.split(",")):
        def kernel(*args, g=g):
            # both are read while tracing: no budget, the cap alone
            saved = pa._VMEM_BLOCK_BUDGET, pa._MAX_BLOCKS
            pa._VMEM_BLOCK_BUDGET, pa._MAX_BLOCKS = 1 << 40, g
            try:
                return pa.paged_attention_pallas(*args, window=W)
            finally:
                pa._VMEM_BLOCK_BUDGET, pa._MAX_BLOCKS = saved
        variants[f"pallas_G{g}"] = kernel
    res = {}
    for case, lens in cases.items():
        tbl, ln = tables(lens)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(xla)(q, pool, tbl, ln)
        for name, fn in variants.items():
            err = float(jnp.max(jnp.abs(
                jax.jit(fn)(q, pool, tbl, ln) - ref)))
            t = ms(step_of(fn), q, pool, tbl, ln)
            res[f"{case}.{name}"] = {f"ms_per_{LAYERS}_calls": t,
                                     "max_err": err}
            print(f"{case:7s} {name:11s} {t:9.3f} ms / {LAYERS} calls   "
                  f"err {err:.2e}", flush=True)
    live = sum(min(n, W) if W else n for n in cases["ragged"])
    print(f"ragged: {live} live keys, "
          f"{live * 2 * HKV * Dh * itemsize * LAYERS} pool bytes a step")
    write(res, f"paged_kernel_bench_{tag}.json")
    return 0


def prefill(a, pa, cast, ms, write, tag) -> int:
    """``--prefill``: a chunk's write, by rows and by blocks, and its
    attention by table bucket."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    N, H, LAYERS = a.blocks, a.heads, a.layers
    HKV = a.kv_heads or H
    D, BS, C = a.head_dim, a.block_size, a.chunk
    q = jax.random.normal(jax.random.PRNGKey(7), (C, H, D), jnp.float32)
    make = jax.jit(lambda k: pa.fuse_kv(*(cast(jax.random.normal(
        kk, (N, HKV, BS, D), jnp.float32)) for kk in jax.random.split(k))))
    pools = [make(k) for k in jax.random.split(jax.random.PRNGKey(8),
                                               LAYERS)]
    rs = np.random.RandomState(1)

    def by_rows(pool, tbl, p0, k, v):
        g = p0 + jnp.arange(C)
        return pa.kv_pool_set(pool, (tbl[g // BS][:, None],
                                     jnp.arange(HKV)[None, :],
                                     (g % BS)[:, None]), k, v)

    res = {}
    kv = jax.random.normal(jax.random.PRNGKey(9), (C, HKV, D), jnp.float32)
    tbl = jnp.asarray(rs.permutation(np.arange(1, N))[:max(
        32, C // BS + 2)], jnp.int32)
    for name, fn in (("write_rows", by_rows),
                     ("write_span", pa.kv_pool_set_span)):
        f = jax.jit(lambda ps, p0, fn=fn: [fn(p, tbl, p0, kv + i, kv)
                                           for i, p in enumerate(ps)],
                    donate_argnums=0)
        for p0 in (0, 37):
            pools = jax.block_until_ready(f(pools, jnp.int32(p0)))
            t0 = time.perf_counter()
            for _ in range(10):
                pools = f(pools, jnp.int32(p0))
            jax.block_until_ready(pools)
            t = (time.perf_counter() - t0) / 10 * 1e3
            res[f"{name}.p0_{p0}"] = {f"ms_per_{LAYERS}_calls": t}
            print(f"{name:11s} p0 {p0:3d} {t:9.3f} ms / {LAYERS} calls",
                  flush=True)

    def attend(q, pools, tbl, p0, window=None):
        """``--layers`` calls in one program, each fed by the one
        before and reading a pool of its own."""
        x = q
        for pool in pools:
            x = q + 1e-3 * pa.paged_prefill_attention(
                x, pool, tbl, p0, window=window)
        return x
    attend = jax.jit(attend, static_argnames="window")
    long_context = C * 16 > pa._DENSE_SPAN_MAX   # the kernel's side
    for p0 in ((0, 4 * C, 8 * C, 13 * C) if long_context else (0, C)):
        need = (p0 + C) // BS
        for bucket in ((256,) if long_context else (16, 32, 64)):
            if bucket < need:
                continue
            tbl = np.zeros(bucket, np.int32)
            tbl[:need] = rs.permutation(np.arange(1, N))[:need]
            t = ms(attend, q, pools, jnp.asarray(tbl), jnp.int32(p0))
            res[f"attend.p0_{p0}.b{bucket}"] = {
                f"ms_per_{LAYERS}_calls": t}
            print(f"attend      p0 {p0:3d} bucket {bucket:2d} {t:9.3f} ms "
                  f"/ {LAYERS} calls", flush=True)
        if a.window:    # the same start through the window's ring
            ring = -(-(a.window + C) // BS) + 1
            tbl = rs.permutation(np.arange(1, N))[:ring]
            t = ms(attend, q, pools, jnp.asarray(tbl, jnp.int32),
                   jnp.int32(p0), a.window)
            res[f"attend_window.p0_{p0}.ring{ring}"] = {
                f"ms_per_{LAYERS}_calls": t}
            print(f"attend win  p0 {p0:5d} ring {ring:3d} {t:9.3f} ms "
                  f"/ {LAYERS} calls", flush=True)
    write(res, f"paged_prefill_bench_{tag}.json")
    return 0


if __name__ == "__main__":
    sys.exit(main())
