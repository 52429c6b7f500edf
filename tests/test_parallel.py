"""Distributed tests on the virtual 8-device CPU mesh — the TPU analogue of
the reference's DummyTransport/local[n] pattern (SURVEY.md §4.2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.learning import Adam, Sgd
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import (ParallelInference, ParallelWrapper,
                                          batch_sharded, make_mesh, replicated)


def _conf(seed=7):
    return (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=2, loss="mcxent", activation="softmax"))
            .input_type_feed_forward(4).build())


def _data(n=512, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 4).astype(np.float32) * 2 - 1
    y = (x.sum(-1) > 0).astype(np.int64)
    return x, np.eye(2, dtype=np.float32)[y]


def test_mesh_construction():
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    mesh = make_mesh()
    assert mesh.shape["data"] == 8 and mesh.shape["model"] == 1
    mesh2 = make_mesh(data=4, model=2)
    assert mesh2.shape["data"] == 4 and mesh2.shape["model"] == 2
    with pytest.raises(ValueError):
        make_mesh(data=3, model=3)


def test_parallel_fit_converges():
    x, y = _data()
    model = MultiLayerNetwork(_conf()).init()
    pw = ParallelWrapper(model)
    assert pw.num_workers == 8
    pw.fit(ArrayDataSetIterator(x, y, batch=64), epochs=30)
    ev = model.evaluate(ArrayDataSetIterator(x, y, batch=128))
    assert ev.accuracy() > 0.95, ev.stats()


def test_parallel_matches_single_device():
    """DP over n devices with global batch B must equal single-device
    training with batch B (sync all-reduce semantics — the reference's
    averaging mode only approximates this; the compiled SPMD step is
    exact)."""
    x, y = _data(256)
    m1 = MultiLayerNetwork(_conf(seed=3)).init()
    m2 = MultiLayerNetwork(_conf(seed=3)).init()
    # identical init (same seed)
    for k in m1._params:
        for pn in m1._params[k]:
            np.testing.assert_array_equal(np.asarray(m1._params[k][pn]),
                                          np.asarray(m2._params[k][pn]))
    it1 = ArrayDataSetIterator(x, y, batch=64)
    it2 = ArrayDataSetIterator(x, y, batch=64)
    m1.fit(it1, epochs=3)
    ParallelWrapper(m2, prefetch_buffer=0).fit(it2, epochs=3)
    out1 = np.asarray(m1.output(x[:32]))
    out2 = np.asarray(m2.output(x[:32]))
    np.testing.assert_allclose(out1, out2, atol=2e-5)


def _graph_conf():
    from deeplearning4j_tpu.nn.conf import InputType
    return (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
            .weight_init("xavier").graph_builder().add_inputs("in")
            .set_input_types(InputType.feed_forward(4))
            .add_layer("d1", DenseLayer(n_out=4, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=2), "d1")
            .set_outputs("out").build())


@pytest.mark.parametrize("kind", ["multilayer", "graph", "sharded"])
def test_a_training_step_donates_the_state_it_carries(kind):
    """Parameters, updater state and layer state go into a step and
    come out of it: undonated, a step holds two copies of all three.
    Read from what jit recorded for the lowered step's arguments."""
    from deeplearning4j_tpu.nn import ComputationGraph
    from deeplearning4j_tpu.parallel import jit_sharded_step
    x, y = _data(64)
    if kind == "graph":
        model = ComputationGraph(_graph_conf()).init()
        step = model._make_step()
        batch = (model._as_inputs(x), model._as_labels(y),
                 model._as_masks(None))
    else:
        model = MultiLayerNetwork(_conf()).init()
        step = (jit_sharded_step(model, make_mesh()) if kind == "sharded"
                else model._make_step())
        batch = (jnp.asarray(x), jnp.asarray(y), None)
    carried = (model._params, model._opt_state, model._net_state)
    lowered = step.lower(*carried, jnp.asarray(0), *batch,
                         jax.random.PRNGKey(0))
    args, _ = lowered.args_info
    leaves = jax.tree_util.tree_leaves
    assert leaves(carried), "the model carries no state to donate"
    assert all(a.donated for a in leaves(args[:3]))
    assert not any(a.donated for a in leaves(args[3:]))


def test_batch_sharding_layout():
    mesh = make_mesh()
    x = jnp.zeros((64, 4))
    xs = jax.device_put(x, batch_sharded(mesh))
    # each device holds 64/8 rows
    shard_shapes = {s.data.shape for s in xs.addressable_shards}
    assert shard_shapes == {(8, 4)}


def test_parallel_inference():
    x, y = _data(128)
    model = MultiLayerNetwork(_conf()).init()
    pi = ParallelInference(model)
    out = pi.output(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(model.output(x)), atol=1e-6)


def test_model_axis_sharding_compiles():
    """A (data=4, model=2) mesh must compile and run the same step — the
    model axis is a no-op for replicated params but validates the 2D mesh
    path end-to-end."""
    x, y = _data(128)
    model = MultiLayerNetwork(_conf()).init()
    pw = ParallelWrapper(model, mesh=make_mesh(data=4, model=2))
    pw.fit(ArrayDataSetIterator(x, y, batch=32), epochs=2)
    assert np.isfinite(model.score_)
