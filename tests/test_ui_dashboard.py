"""Training UI dashboard depth (VERDICT r3 weak #7 — ref:
`deeplearning4j-ui-parent`: TrainModule overview/model/system views,
StatsListener update stats feeding the log10 update:param ratio chart)
and EvaluationCalibration residual/probability histograms (ref:
`EvaluationCalibration.java` getResidualPlot/getProbabilityHistogram)."""
import json
import os
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.eval import EvaluationCalibration
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.ui import InMemoryStatsStorage, StatsListener, UIServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train(storage, session="s1", iters=6, **listener_kw):
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-2))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=2, loss="mcxent", activation="softmax"))
            .input_type_feed_forward(4).build())
    m = MultiLayerNetwork(conf).init()
    m.set_listeners(StatsListener(storage, session_id=session,
                                  **listener_kw))
    rs = np.random.RandomState(0)
    x = rs.rand(32, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 32)]
    m.fit(x, y, epochs=iters)
    return m


class TestStatsListenerDepth:
    def test_update_magnitudes_collected(self):
        st = InMemoryStatsStorage()
        _train(st)
        ups = st.get_updates("s1")
        assert len(ups) == 6
        assert "param_mean_magnitudes" in ups[0]
        # update magnitudes appear from the second report on
        assert "update_mean_magnitudes" not in ups[0]
        assert "update_mean_magnitudes" in ups[1]
        um = ups[1]["update_mean_magnitudes"]
        assert any(v > 0 for v in um.values()), um

    def test_histograms_optional(self):
        st = InMemoryStatsStorage()
        _train(st, session="h1", collect_histograms=True,
               histogram_bins=12)
        ups = st.get_updates("h1")
        h = ups[0]["param_histograms"]
        some = next(iter(h.values()))
        assert len(some["counts"]) == 12
        assert some["min"] <= some["max"]
        st2 = InMemoryStatsStorage()
        _train(st2, session="h2")
        assert "param_histograms" not in st2.get_updates("h2")[0]


class TestUIServerEndpoints:
    def _get(self, port, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5) as r:
            return json.loads(r.read().decode())

    def test_model_and_system_endpoints(self):
        st = InMemoryStatsStorage()
        _train(st, session="m1", collect_histograms=True)
        srv = UIServer(port=0)
        try:
            srv.attach(st)
            assert "m1" in self._get(srv.port, "/sessions")
            model = self._get(srv.port, "/train/m1/model")
            assert model["iterations"], model
            assert model["params"], "no param series"
            name, series = next(iter(model["params"].items()))
            assert len(series) == len(model["iterations"])
            assert model["updates"], "no update series"
            assert model["histograms"], "no histograms"
            sysinfo = self._get(srv.port, "/system")
            assert "python" in sysinfo and "rss_mb" in sysinfo
            page = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/", timeout=5).read().decode()
            for frag in ("score", "mags", "ratio", "hist", "sys"):
                assert f'id={frag}' in page, frag
        finally:
            srv.stop()


class TestCalibrationDepth:
    def test_residual_plot_shifts_with_error(self):
        rs = np.random.RandomState(0)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 600)]
        good = np.clip(y + rs.rand(600, 3) * 0.08, 0, 1)
        good /= good.sum(-1, keepdims=True)
        bad = np.full((600, 3), 1 / 3.0)
        ev_good, ev_bad = EvaluationCalibration(), EvaluationCalibration()
        ev_good.eval(y, good)
        ev_bad.eval(y, bad)
        rg, rb = ev_good.residual_plot(), ev_bad.residual_plot()
        # good predictions: residual mass near 0; uniform: mass near 1/3
        centers = (np.arange(20) + 0.5) / 20
        assert np.average(centers, weights=rg) < \
            np.average(centers, weights=rb)
        # per-class residuals sum to the aggregate
        per = sum(ev_good.residual_plot(c) for c in range(3))
        np.testing.assert_array_equal(per, rg)

    def test_probability_histograms(self):
        rs = np.random.RandomState(1)
        y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 400)]
        pred = np.clip(y * 0.9 + 0.05 + rs.rand(400, 2) * 0.02, 0, 1)
        pred /= pred.sum(-1, keepdims=True)
        ev = EvaluationCalibration()
        ev.eval(y, pred)
        all0 = ev.probability_histogram(0)
        true0 = ev.probability_histogram(0, when_true=True)
        assert all0.sum() == 400          # every sample contributes
        assert true0.sum() == float((y.argmax(-1) == 0).sum())
        # when the true class IS 0, its predicted prob is high:
        centers = (np.arange(20) + 0.5) / 20
        assert np.average(centers, weights=true0) > 0.7
        # ECE still works alongside
        assert 0.0 <= ev.expected_calibration_error() <= 1.0

    def test_mask_excludes_rows_everywhere(self):
        rs = np.random.RandomState(3)
        y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 100)]
        p = np.clip(y * 0.8 + 0.1, 0, 1)
        mask = np.zeros(100, np.float32)
        mask[:60] = 1.0
        ev = EvaluationCalibration()
        ev.eval(y, p, mask=mask)
        assert ev.residual_plot().sum() == 120      # 60 rows x 2 classes
        assert ev.probability_histogram(0).sum() == 60
        _, _, counts = ev.reliability_curve()
        assert counts.sum() == 60

    def test_class_count_mismatch_raises(self):
        ev = EvaluationCalibration()
        ev.eval(np.eye(3, dtype=np.float32),
                np.full((3, 3), 1 / 3.0))
        import pytest as _pytest
        with _pytest.raises(ValueError, match="3 classes"):
            ev.eval(np.eye(2, dtype=np.float32),
                    np.full((2, 2), 0.5))

    def test_binary_path(self):
        rs = np.random.RandomState(2)
        y = (rs.rand(300) > 0.5).astype(np.float32)
        p = np.clip(y * 0.8 + 0.1 + rs.rand(300) * 0.05, 0, 1)
        ev = EvaluationCalibration()
        ev.eval(y, p)
        assert ev.residual_plot().sum() == 600  # 2 classes x 300
        assert ev.probability_histogram(1).sum() == 300


class TestEvaluationExtras:
    """top-N accuracy / MCC / G-measure (ref: Evaluation.java topNAccuracy,
    matthewsCorrelation, gMeasure)."""

    def test_top_n_accuracy(self):
        from deeplearning4j_tpu.eval import Evaluation
        rs = np.random.RandomState(0)
        y = np.eye(5, dtype=np.float32)[rs.randint(0, 5, 400)]
        # predictions: true class gets rank 2 half the time
        pred = rs.rand(400, 5).astype(np.float32) * 0.1
        true_cls = y.argmax(-1)
        flip = rs.rand(400) < 0.5
        pred[np.arange(400), true_cls] += np.where(flip, 1.0, 0.45)
        top_idx = pred.argsort(-1)
        ev1 = Evaluation(top_n=1)
        ev3 = Evaluation(top_n=3)
        ev1.eval(y, pred)
        ev3.eval(y, pred)
        assert ev3.top_n_accuracy() >= ev1.accuracy()
        assert ev3.top_n_accuracy() > 0.9      # rank<=2 nearly always
        assert ev1.top_n_accuracy() == ev1.accuracy()
        assert "Top-3" in ev3.stats()

    def test_mcc_and_gmeasure(self):
        from deeplearning4j_tpu.eval import Evaluation
        y = np.eye(2, dtype=np.float32)[[0, 0, 1, 1]]
        perfect = y.copy()
        ev = Evaluation()
        ev.eval(y, perfect)
        assert ev.matthews_correlation(0) == pytest.approx(1.0)
        assert ev.gmeasure() == pytest.approx(1.0)
        anti = 1.0 - y
        ev2 = Evaluation()
        ev2.eval(y, anti)
        assert ev2.matthews_correlation(0) == pytest.approx(-1.0)


class TestRemoteStatsRouting:
    """Cluster-training observability (VERDICT r4 #7 — ref:
    PlayUIServer.java:401 enableRemoteListener +
    RemoteUIStatsStorageRouter): a worker PROCESS posts its
    StatsListener updates over HTTP to a central UI server."""

    def test_two_process_round_trip(self, tmp_path):
        import subprocess
        import sys
        import time as _time
        from deeplearning4j_tpu.ui import UIServer

        server = UIServer(port=0)
        try:
            server.enable_remote_listener()
            url = f"http://127.0.0.1:{server.port}"
            worker = (
                "import sys, numpy as np\n"
                f"sys.path.insert(0, {repr(str(ROOT))})\n"
                "from deeplearning4j_tpu.learning import Sgd\n"
                "from deeplearning4j_tpu.nn import (MultiLayerNetwork,\n"
                "    NeuralNetConfiguration)\n"
                "from deeplearning4j_tpu.nn.layers import (DenseLayer,\n"
                "    OutputLayer)\n"
                "from deeplearning4j_tpu.ui import (\n"
                "    RemoteUIStatsStorageRouter, StatsListener)\n"
                "conf = (NeuralNetConfiguration.builder().seed(0)\n"
                "        .updater(Sgd(0.1)).weight_init('xavier').list()\n"
                "        .layer(DenseLayer(n_out=8, activation='tanh'))\n"
                "        .layer(OutputLayer(n_out=2, loss='mcxent',\n"
                "                           activation='softmax'))\n"
                "        .input_type_feed_forward(4).build())\n"
                "m = MultiLayerNetwork(conf).init()\n"
                f"router = RemoteUIStatsStorageRouter({url!r})\n"
                "m.set_listeners(StatsListener(router,\n"
                "                session_id='worker0'))\n"
                "rs = np.random.RandomState(0)\n"
                "x = rs.rand(64, 4).astype(np.float32)\n"
                "y = np.eye(2, dtype=np.float32)[(x.sum(-1) > 2)\n"
                "                                .astype(int)]\n"
                "m.fit(x, y, epochs=3)\n"
                "router.shutdown()\n"
                "print('WORKER_DONE', router.dropped)\n")
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            out = subprocess.run([sys.executable, "-c", worker],
                                 capture_output=True, text=True,
                                 timeout=240, env=env)
            assert "WORKER_DONE 0" in out.stdout, (out.stdout,
                                                   out.stderr[-2000:])
            # updates arrived in the receiver storage and serve over the
            # dashboard endpoints
            deadline = _time.time() + 10
            ups = []
            while _time.time() < deadline and not ups:
                ups = server._remote_storage.get_updates("worker0")
                _time.sleep(0.2)
            assert ups, "no remote updates received"
            assert any("score" in u for u in ups)
            import json as _json
            import urllib.request
            got = _json.loads(urllib.request.urlopen(
                f"{url}/train/worker0/overview", timeout=10).read())
            assert got and "score" in got[0], got[:1]
        finally:
            server.stop()

    def test_post_without_enable_is_403(self):
        import urllib.error
        import urllib.request
        from deeplearning4j_tpu.ui import UIServer
        server = UIServer(port=0)
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/remoteReceive",
                data=b'{"session_id": "s", "update": {}}',
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=5)
            assert e.value.code == 403
        finally:
            server.stop()

    def test_router_survives_dead_server_without_blocking(self):
        from deeplearning4j_tpu.ui import RemoteUIStatsStorageRouter
        import time as _time
        r = RemoteUIStatsStorageRouter("http://127.0.0.1:1",  # closed
                                       max_retries=1,
                                       retry_backoff_s=0.01)
        t0 = _time.time()
        for i in range(50):
            r.put_update("s", {"iteration": i})
        assert _time.time() - t0 < 1.0  # put never blocks on the wire
        r.shutdown()
        assert r.dropped == 50
