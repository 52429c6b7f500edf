"""Diagnose the compressed-bus conv/Adam convergence gap (VERDICT r4 #3).

Replicates the dryrun_multichip compressed-bus section on the 8-device CPU
mesh and sweeps quantizer settings, logging per-step threshold/sparsity so
the dynamics are visible. Run:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python tools/diag_compress.py
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax

from deeplearning4j_tpu.parallel import (GradientSharingAccumulator,
                                         ParallelWrapper, make_mesh)
from deeplearning4j_tpu.datasets import ArrayDataSetIterator


def flagship(classes=4):
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer,
                                              OutputLayer, SubsamplingLayer)
    conf = (NeuralNetConfiguration.builder()
            .seed(123).updater(Adam(1e-3)).weight_init("relu").list()
            .layer(ConvolutionLayer(n_out=20, kernel=(5, 5), activation="relu"))
            .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(ConvolutionLayer(n_out=50, kernel=(5, 5), activation="relu"))
            .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=classes, loss="mcxent",
                               activation="softmax"))
            .input_type_convolutional(8, 8, 1).build())
    return MultiLayerNetwork(conf).init()


def main():
    n_devices = 8
    model_axis = 2
    mesh = make_mesh(jax.devices(), data=n_devices // model_axis,
                     model=model_axis)
    batch = (n_devices // model_axis) * 4
    rs2 = np.random.RandomState(1)
    xs = rs2.rand(batch * 4, 8, 8, 1).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[
        (xs.mean((1, 2, 3)) > xs.mean()).astype(int) * 2 +
        (xs[:, :4].mean((1, 2, 3)) > xs.mean()).astype(int)]
    n_ep = 12

    def run(name, acc):
        m = flagship()
        pw = ParallelWrapper(m, mesh=mesh, prefetch_buffer=0, accumulator=acc)
        losses = []
        for _ in range(n_ep):
            pw.fit(ArrayDataSetIterator(xs, ys, batch=batch, shuffle=False),
                   epochs=1)
            losses.append(float(m.score_))
        tail = ""
        if acc is not None:
            tail = (f" thr={float(acc.threshold):.2e}"
                    f" sparsity={float(acc.last_sparsity):.3f}")
        print(f"{name:55s} final={losses[-1]:.4f} "
              f"traj={['%.3f' % l for l in losses]}{tail}")
        return losses[-1]

    run("dense", None)
    run("update-mode: thr=1e-3 adaptive band[1e-3,0.5] x1.2",
        GradientSharingAccumulator(threshold=1e-3, adaptive=True,
                                   min_sparsity=1e-3, max_sparsity=0.5,
                                   mode="update"))
    run("update-mode fixed thr=1e-3",
        GradientSharingAccumulator(threshold=1e-3, adaptive=False,
                                   mode="update"))
    run("update-mode fixed thr=1e-4",
        GradientSharingAccumulator(threshold=1e-4, adaptive=False,
                                   mode="update"))
    run("update-mode fixed thr=1e-5",
        GradientSharingAccumulator(threshold=1e-5, adaptive=False,
                                   mode="update"))
    run("gradient-mode (opt-in): thr=1e-3 adaptive [1e-3,0.5]",
        GradientSharingAccumulator(threshold=1e-3, adaptive=True,
                                   min_sparsity=1e-3, max_sparsity=0.5,
                                   mode="gradient"))
    run("gradient-mode thr0=1e-2 adaptive [1e-3,0.3]",
        GradientSharingAccumulator(threshold=1e-2, adaptive=True,
                                   min_sparsity=1e-3, max_sparsity=0.3,
                                   mode="gradient"))


def ablations():
    """Separate the gap sources: (a) per-worker local Adam on 4-sample
    shards (no quantization), (b) quantization given perfect updater."""
    import deeplearning4j_tpu.parallel.compression as C
    import deeplearning4j_tpu.parallel as PP
    n_devices = 8
    mesh = make_mesh(jax.devices(), data=4, model=2)
    batch = 16
    rs2 = np.random.RandomState(1)
    xs = rs2.rand(batch * 4, 8, 8, 1).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[
        (xs.mean((1, 2, 3)) > xs.mean()).astype(int) * 2 +
        (xs[:, :4].mean((1, 2, 3)) > xs.mean()).astype(int)]
    n_ep = 12

    def run(name, acc):
        m = flagship()
        pw = ParallelWrapper(m, mesh=mesh, prefetch_buffer=0, accumulator=acc)
        losses = []
        for _ in range(n_ep):
            pw.fit(ArrayDataSetIterator(xs, ys, batch=batch, shuffle=False),
                   epochs=1)
            losses.append(float(m.score_))
        print(f"{name:55s} final={losses[-1]:.4f} "
              f"traj={['%.3f' % l for l in losses]}")
        return losses[-1]

    orig = C.strom_encode_decode
    # identity codec: per-worker Adam + pmean(update), NO quantization
    def identity_codec(update, residual, threshold):
        import jax.numpy as jnp
        return update + residual, jnp.zeros_like(update)
    C.strom_encode_decode = identity_codec
    try:
        run("ablation: identity codec (isolates local-Adam noise)",
            GradientSharingAccumulator(threshold=1e-3, adaptive=False,
                                       mode="update"))
    finally:
        C.strom_encode_decode = orig

    # magnitude-preserving codec inside the UPDATE-domain pipeline: the
    # library's value codec swapped in for the sign*threshold one
    C.strom_encode_decode = C.strom_value_encode_decode
    try:
        run("ablation: value codec thr=1e-3 (sparse but exact values)",
            GradientSharingAccumulator(threshold=1e-3, adaptive=True,
                                       min_sparsity=1e-3, max_sparsity=0.5,
                                       mode="update"))
    finally:
        C.strom_encode_decode = orig


if __name__ == "__main__":
    ablations() if os.environ.get("DIAG_ABLATE") else main()
