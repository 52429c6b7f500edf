"""Cluster-training observability: a worker streams its StatsListener
updates over HTTP to a central dashboard, and an Arbiter sweep streams
per-candidate progress to the same UI (ref: dl4j-examples UI examples +
PlayUIServer.enableRemoteListener / ArbiterModule).

  JAX_PLATFORMS=cpu \
  python examples/remote_training_dashboard.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import urllib.request

import numpy as np

from deeplearning4j_tpu.arbiter import (ContinuousParameterSpace,
                                        GridSearchCandidateGenerator,
                                        LocalOptimizationRunner,
                                        OptimizationConfiguration)
from deeplearning4j_tpu.learning import Sgd
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.ui import (RemoteUIStatsStorageRouter,
                                   StatsListener, UIServer)


def _net(lr=0.1, seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(lr))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=2, loss="mcxent",
                               activation="softmax"))
            .input_type_feed_forward(6).build())
    return MultiLayerNetwork(conf).init()


def main(quick: bool = False):
    rs = np.random.RandomState(0)
    x = (rs.rand(256, 6) * 2 - 1).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(-1) > 0).astype(int)]

    # central UI server; enable_remote_listener opens /remoteReceive
    server = UIServer(port=0)
    receiver = server.enable_remote_listener()
    url = f"http://127.0.0.1:{server.port}"

    # "worker": routes its stats over HTTP instead of a local storage
    router = RemoteUIStatsStorageRouter(url)
    model = _net()
    model.set_listeners(StatsListener(router, session_id="worker0"))
    model.fit(x, y, epochs=2 if quick else 10)
    router.shutdown()

    # arbiter sweep streaming to the same dashboard
    cfg = OptimizationConfiguration(
        GridSearchCandidateGenerator(
            {"lr": ContinuousParameterSpace(0.01, 0.3)},
            discretization_count=3 if quick else 6),
        score_function=lambda v: float(abs(v["lr"] - 0.1)),
        minimize=True)
    LocalOptimizationRunner(cfg, stats_storage=receiver,
                            session_id="hpo").execute()

    overview = json.loads(urllib.request.urlopen(
        f"{url}/train/worker0/overview", timeout=10).read())
    arbiter = json.loads(urllib.request.urlopen(
        f"{url}/arbiter/hpo", timeout=10).read())
    server.stop()
    print(f"dashboard received {len(overview)} worker updates, "
          f"{len(arbiter['candidates'])} arbiter candidates")
    return len(overview), len(arbiter["candidates"])


if __name__ == "__main__":
    main()
