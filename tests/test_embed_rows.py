"""An embedding table kept ``nn/functional.py::lane_padded`` gives, through
``embed_rows``, the rows ``table[ids]`` gives, bit for bit: at a width the
device keeps row-major as it is (2,048, a multiple of 128) and at one it
pads (1,600, GPT-2 XL's), for a decode step's 16 ids and a prefill
chunk's 256, the table's first and last rows among them.
``CausalTransformerLM`` keeps its token and position tables so, however
it gets them. That the padded tables are read with no copy of a table is
``tests/test_chip_bringup.py``'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.functional import embed_rows, lane_padded
from deeplearning4j_tpu.zoo.transformer_lm import CausalTransformerLM

V = 1031


def _table(width):
    return jax.random.normal(jax.random.PRNGKey(width), (V, width))


def _ids(n):
    ids = np.random.RandomState(n).randint(0, V, size=n)
    ids[0], ids[n // 2], ids[-1] = 0, V - 1, V - 1
    return jnp.asarray(ids, jnp.int32)


@pytest.mark.parametrize("n", [16, 256])
@pytest.mark.parametrize("width", [1600, 2048])
def test_the_rows_are_the_gathers_bit_for_bit(width, n):
    table, ids = _table(width), _ids(n)
    padded = lane_padded(table)
    assert padded.shape == (V, -(-width // 128) * 128)
    got = jax.jit(embed_rows, static_argnums=2)(padded, ids, width)
    assert got.shape == (n, width)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(table)[np.asarray(ids)])
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jax.jit(
                                      lambda t, i: t[i])(table, ids)))


@pytest.mark.parametrize("width", [1600, 2048])
def test_ids_of_any_shape_and_out_of_range_index_as_a_gather_does(width):
    """A prompt's ``[B, T]`` ids; ``-1`` wraps to the last row and ids
    past the table clamp to it, as ``table[ids]`` does."""
    table = _table(width)
    ids = jnp.asarray([[0, 5, V - 1], [-1, V, V + 7]], jnp.int32)
    got = embed_rows(lane_padded(table), ids, width)
    assert got.shape == (2, 3, width)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(table[ids]))


def test_the_lm_keeps_its_tables_lane_padded_however_it_gets_them():
    """From ``init`` and when handed tables of its own width (as the
    benchmark's served module hands them); the columns past ``d_model``
    are zeros and the logits are those of the unpadded tables."""
    lm = CausalTransformerLM(vocab_size=61, d_model=32, n_layers=1,
                             n_heads=4, max_seq_len=16, seed=0).init()
    assert lm._params["tok"].shape == (61, 128)
    assert lm._params["pos"].shape == (16, 128)
    tokens = jnp.asarray([[3, 60, 0, 7]], jnp.int32)
    want = lm.logits(tokens)
    bare = dict(lm._params, tok=lm._params["tok"][:, :32],
                pos=lm._params["pos"][:, :32])
    np.testing.assert_array_equal(np.asarray(lm.forward_prefill(
        bare, tokens)[0]), np.asarray(want))
    lm._params = bare
    assert lm._params["tok"].shape == (61, 128)
    assert not np.asarray(lm._params["tok"][:, 32:]).any()
    np.testing.assert_array_equal(np.asarray(lm.logits(tokens)),
                                  np.asarray(want))
    lm._params = None
    assert lm._params is None
