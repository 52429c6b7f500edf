"""Data-parallel training over the device mesh, dense and with the
compressed gradient-sharing bus (ref: dl4j-examples ParallelWrapper /
gradient-sharing examples). On a CPU host, run under the virtual mesh:

  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/data_parallel.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.learning import Sgd
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import (GradientSharingAccumulator,
                                         ParallelWrapper)


def _net(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=32, activation="tanh"))
            .layer(OutputLayer(n_out=2, loss="mcxent",
                               activation="softmax"))
            .input_type_feed_forward(8).build())
    return MultiLayerNetwork(conf).init()


def main(quick: bool = False):
    rs = np.random.RandomState(0)
    x = (rs.rand(1024, 8) * 2 - 1).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(-1) > 0).astype(int)]
    epochs = 5 if quick else 25

    dense = _net()
    ParallelWrapper(dense).fit(ArrayDataSetIterator(x, y, batch=128),
                               epochs=epochs)
    acc_d = dense.evaluate(ArrayDataSetIterator(x, y, batch=256)).accuracy()

    comp = _net()
    # mode="gradient" opts into the TPU-native value-preserving
    # pipeline; the default ("update") is the reference-faithful
    # sign*threshold update-domain one
    acc_obj = GradientSharingAccumulator(threshold=1e-3, adaptive=True,
                                         mode="gradient")
    ParallelWrapper(comp, accumulator=acc_obj).fit(
        ArrayDataSetIterator(x, y, batch=128), epochs=epochs)
    acc_c = comp.evaluate(ArrayDataSetIterator(x, y, batch=256)).accuracy()

    print(f"dense all-reduce acc: {acc_d:.3f}")
    print(f"compressed bus acc:   {acc_c:.3f} "
          f"(threshold {float(acc_obj.threshold):.2e}, "
          f"sparsity {float(acc_obj.last_sparsity):.4f})")
    return acc_d, acc_c


if __name__ == "__main__":
    main()
