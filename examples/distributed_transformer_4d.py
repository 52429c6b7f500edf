"""4D-parallel causal-LM training: data x sequence x pipeline x tensor
parallelism composed in ONE shard_mapped jitted step, plus expert
parallelism via the Switch-MoE layer (ref role: the reference's
distributed training stack — Spark parameter averaging + gradient
sharing — redesigned as compiled XLA collectives over a device mesh;
TP/PP/SP/EP go beyond what the reference supports).

Runs on a virtual 8-device CPU mesh, the same code path a real v5e
slice would take:
Run: JAX_PLATFORMS=cpu \
     XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python examples/distributed_transformer_4d.py"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.parallel.transformer import (DistributedTransformer,
                                                     make_4d_mesh)


def main(quick: bool = False):
    import jax
    n = 8
    if len(jax.devices()) < n:
        raise SystemExit(
            f"need {n} devices (run with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "JAX_PLATFORMS=cpu)")
    # dp=1, sp=2, pp=2, tp=2: ring attention over sp, GPipe
    # microbatching over pp, Megatron-style TP, DP gradient averaging
    mesh = make_4d_mesh(n, dp=1, sp=2, pp=2, tp=2)
    tf = DistributedTransformer(mesh, vocab=64, d_model=32, n_heads=4,
                                d_ff=64, seq_len=16, n_microbatches=2)

    # toy copy task: predict the next token of a repeating pattern
    rs = np.random.RandomState(0)
    pattern = rs.randint(0, 64, 8)
    tokens = np.tile(pattern, (4, tf.seq_len // len(pattern) + 1))[
        :, :tf.seq_len]
    targets = np.roll(tokens, -1, axis=1)

    losses = []
    for i in range(10 if quick else 60):
        losses.append(float(tf.train_step(tokens, targets, lr=0.1)))
    print(f"4D-parallel LM on mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}: "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    return losses[0] - losses[-1]


if __name__ == "__main__":
    main()
