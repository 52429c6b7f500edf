#!/usr/bin/env python3
"""The selective scan alone, on the chip, at the shape of
``jamba2-3b.long_context_backlog`` (5,120 channels, 16 states, float32):
26 calls in one program, which is what one 1,024-row prefill chunk or
one 16-lane decode step makes; the chunk kernel against its
``impl="xla"`` control.

    chiprun -- python3 tools/selective_scan_bench.py \
        [--rows 1024,300] [--tiles 128x512,128x256] [--scan-calls 2]

(a tile other than the module's own is traced anew at each of its 26
call sites, ~1 min a form: ask for few).

Chunk: the Pallas kernel with the gate fused in (what the model runs),
the same kernel without it and the gate left to XLA, at every
``rows x channels`` tile asked for; and the ``lax.scan`` form
(``--scan-calls`` of it: a row is a device operation, 26 calls take
seconds). Step: XLA's fusion over 26 donated states (what the model
runs; the Pallas body it was measured against is in PERF.md section 6,
PR 37). Prints the milliseconds per 26 calls, the bytes' time at 819
GB/s and the largest error against the ``lax.scan`` form, and writes
them to ``chiprun_out/selective_scan_bench.json``. The sibling of
``tools/moe_kernel_bench.py``: the numbers PERF.md quotes for the
kernel alone. It measures nothing off a TPU.
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

DI, N, SLOTS, LAYERS, HBM = 5120, 16, 16, 26, 819e9


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="1024,300",
                    help="live rows of the 1,024-row chunk ('' for the "
                         "step alone)")
    ap.add_argument("--tiles", default="128x512",
                    help="row block x channel tile of the chunk kernel")
    ap.add_argument("--scan-calls", type=int, default=2)
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("selective_scan_bench: JAX found no TPU; a time from "
              "anything else is not a device number", file=sys.stderr)
        return 2
    from deeplearning4j_tpu.kernels import selective_scan as ss

    T = 1024
    ks = jax.random.split(jax.random.PRNGKey(0), 10)
    f = lambda k, *s: jax.random.normal(k, s, jnp.float32)  # noqa: E731
    c, z = f(ks[0], T, DI) * 0.4, f(ks[1], T, DI)
    dt = jax.nn.softplus(f(ks[2], T, DI) * 0.25 - 4.0)
    B, C = f(ks[3], T, N), f(ks[4], T, N)
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                          (N, DI)) * (1 + 0.1 * f(ks[5], N, DI))
    D, h0 = jnp.ones((DI,), jnp.float32), f(ks[6], N, DI) * 0.1

    def ms(fn, *args, n=5):
        jax.block_until_ready(fn(*args))
        t = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / n * 1e3

    def chunk_program(one, calls):
        """``calls`` scans in one program, each fed by the one before."""
        def run(c, h, clen):
            for _ in range(calls):
                y, h = one(c, h, clen)
                c = c + 1e-3 * y
            return c, h
        return jax.jit(run)

    res = {}
    want = {}
    for rows in (int(r) for r in a.rows.split(",") if r):
        want[rows] = jax.jit(lambda clen: ss.selective_scan_chunk_xla(
            c, dt, B, C, A, D, h0, clen, z))(rows)
        nbytes = rows * (4 * DI + 2 * N) * 4 + (3 * N * DI + DI) * 4
        scan = chunk_program(lambda c, h, n: ss.selective_scan_chunk_xla(
            c, dt, B, C, A, D, h, n, z), a.scan_calls)
        t = ms(scan, c, h0, rows, n=1) * LAYERS / a.scan_calls
        res[f"chunk.{rows}.xla"] = {f"ms_per_{LAYERS}_calls": t}
        print(f"chunk {rows:5d} rows  lax.scan         {t:9.3f} ms / "
              f"{LAYERS} calls ({a.scan_calls} timed)", flush=True)
        for tile in a.tiles.split(","):
            ss._ROW_BLOCK, ss._CHANNEL_TILE = (int(v) for v in
                                               tile.split("x"))
            kernel = ss.selective_scan_chunk_pallas.__wrapped__
            forms = {
                "gate_fused": lambda c, h, n: kernel(
                    c, dt, B, C, A, D, h, n, z),
                "gate_xla": lambda c, h, n: (lambda y, h: (
                    y * ss.silu(z), h))(*kernel(c, dt, B, C, A, D, h, n))}
            for name, one in forms.items():
                y, h = jax.jit(one)(c, h0, rows)
                err = max(float(jnp.abs(y - want[rows][0]).max()),
                          float(jnp.abs(h - want[rows][1]).max()))
                t = ms(chunk_program(one, LAYERS), c, h0, rows)
                res[f"chunk.{rows}.{tile}.{name}"] = {
                    f"ms_per_{LAYERS}_calls": t, "max_err": err,
                    "bytes_a_call": nbytes}
                print(f"chunk {rows:5d} rows  {tile:8s} {name:10s} "
                      f"{t:9.3f} ms / {LAYERS} calls   bytes' time "
                      f"{LAYERS * nbytes / HBM * 1e3:.3f} ms   err "
                      f"{err:.2e}", flush=True)

    # -- the decode step: XLA's fusion over 26 donated states -----------
    live = jnp.arange(SLOTS) < SLOTS - 1
    nbytes = SLOTS * (2 * N * DI + 3 * DI + 2 * N) * 4 + (N * DI + DI) * 4

    def run(states, c):
        out = []
        for h in states:
            y, h = ss.selective_scan_step(c, dt[:SLOTS], B[:SLOTS],
                                          C[:SLOTS], A, D, h, live)
            c = c + 1e-3 * y
            out.append(h)
        return out, c
    step = jax.jit(run, donate_argnums=0)
    states = [f(k, SLOTS, N, DI) * 0.1
              for k in jax.random.split(ks[7], LAYERS)]
    states, _ = step(states, c[:SLOTS])
    jax.block_until_ready(states)
    t0 = time.perf_counter()
    for _ in range(20):
        states, out = step(states, c[:SLOTS])
    jax.block_until_ready((states, out))
    t = (time.perf_counter() - t0) / 20 * 1e3
    res["step.xla"] = {f"ms_per_{LAYERS}_calls": t, "bytes_a_call": nbytes}
    print(f"step  {SLOTS} slots  xla fusion {t:9.3f} ms / {LAYERS} calls   "
          f"bytes' time {LAYERS * nbytes / HBM * 1e3:.3f} ms", flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "selective_scan_bench.json"), "w") as fh:
        json.dump(res, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
