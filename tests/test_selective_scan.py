"""The selective scan (``kernels/selective_scan.py``): the chunk's Pallas
body (interpret mode) against its XLA form against a Python loop in
float64, at shapes that cross a row block, a channel tile and the
eight-row inner tile; the state handed on over split chunks; rows past
``chunk_len`` and dead lanes leaving the state bit for bit."""
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import selective_scan as ss

# float32 sums of at most 16 products a row and a recurrence whose
# factor is at most 1: a few ulps of values of magnitude ~10
TOL = 5e-6


def draw(T, Di, N, seed=0):
    rs = np.random.RandomState(seed)
    f = lambda *s: rs.randn(*s).astype(np.float32)          # noqa: E731
    return dict(c=f(T, Di), dt=np.log1p(np.exp(f(T, Di) - 2)),
                B=f(T, N), C=f(T, N), A=-np.exp(0.5 * f(N, Di)), D=f(Di),
                h0=f(N, Di), z=f(T, Di))


def loop(a, clen, gated, h0=None):
    """The recurrence row by row in float64."""
    c, dt, B, C, A, D, z = (np.asarray(a[k], np.float64)
                            for k in ("c", "dt", "B", "C", "A", "D", "z"))
    h = np.asarray(a["h0"] if h0 is None else h0, np.float64)
    y = np.zeros_like(c)
    for t in range(clen):
        h = np.exp(dt[t][None] * A) * h + (dt[t] * c[t])[None] * B[t][:, None]
        y[t] = (h * C[t][:, None]).sum(0) + D * c[t]
        if gated:
            y[t] *= z[t] / (1 + np.exp(-z[t]))
    return y, h


def chunk(a, clen, gated, impl, h0=None, rows=slice(None)):
    j = {k: jnp.asarray(v[rows] if k in ("c", "dt", "B", "C", "z") else v)
         for k, v in a.items()}
    y, h = ss.selective_scan_chunk(
        j["c"], j["dt"], j["B"], j["C"], j["A"], j["D"],
        j["h0"] if h0 is None else jnp.asarray(h0), clen,
        j["z"] if gated else None, impl=impl)
    return np.asarray(y), np.asarray(h)


#: (rows, channels, states): one tile; two row blocks of 128 and two
#: channel tiles of 512 at the cell's 16 states
SHAPES = [(32, 256, 16), (256, 1024, 16), (16, 128, 4)]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
@pytest.mark.parametrize("T,Di,N", SHAPES)
def test_a_chunk_equals_the_loop(T, Di, N, gated, impl):
    a = draw(T, Di, N)
    y, h = chunk(a, T, gated, impl)
    want_y, want_h = loop(a, T, gated)
    assert np.abs(y - want_y).max() <= TOL * max(1, np.abs(want_y).max())
    assert np.abs(h - want_h).max() <= TOL * max(1, np.abs(want_h).max())


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("clen", [0, 1, 7, 8, 129, 200, 255])
def test_rows_past_chunk_len_leave_the_state_and_come_back_zero(impl, clen):
    """Whatever the padded rows hold (NaN here): the state after the
    call is the state after row ``chunk_len - 1``, bit for bit the
    state the same rows give alone, and ``y`` is zero past them."""
    T, Di, N = 256, 512, 16
    a = draw(T, Di, N, seed=1)
    for k in ("c", "dt", "z", "B", "C"):
        a[k][clen:] = np.nan
    y, h = chunk(a, clen, True, impl)
    want_y, want_h = loop(a, clen, True)
    assert np.isfinite(y).all() and np.isfinite(h).all()
    assert (y[clen:] == 0).all()
    assert np.abs(y - want_y).max() <= TOL * 10
    assert np.abs(h - want_h).max() <= TOL * 10
    if clen == 0:
        assert (h == a["h0"]).all()
    if clen and clen % 128 == 0:
        return
    # the same live rows with other padding: the same bits
    b = {k: v.copy() for k, v in a.items()}
    for k in ("c", "dt", "z", "B", "C"):
        b[k][clen:] = 3.0
    y2, h2 = chunk(b, clen, True, impl)
    assert (y2 == y).all() and (h2 == h).all()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_state_is_handed_on_over_split_chunks(impl):
    """One chunk of 256 rows against the same rows as 128 + 128 and as
    a first chunk of 100 live rows then the rest: ``h0`` carries it."""
    T, Di, N = 256, 512, 16
    a = draw(T, Di, N, seed=2)
    y, h = chunk(a, T, False, impl)
    ya, ha = chunk(a, 128, False, impl, rows=slice(0, 128))
    yb, hb = chunk(a, 128, False, impl, h0=ha, rows=slice(128, 256))
    assert np.abs(np.concatenate([ya, yb]) - y).max() <= TOL * 10
    assert np.abs(hb - h).max() <= TOL * 10
    yc, hc = chunk(a, 100, False, impl, rows=slice(0, 128))
    rest = {k: (np.concatenate([v[100:], v[:100]])
                if k in ("c", "dt", "B", "C", "z") else v)
            for k, v in a.items()}
    yd, hd = chunk(rest, 156, False, impl, h0=hc)
    assert np.abs(np.concatenate([yc[:100], yd[:156]]) - y).max() <= TOL * 10
    assert np.abs(hd - h).max() <= TOL * 10


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_kernel_and_the_scan_agree_to_the_last_bits(impl):
    a = draw(128, 512, 16, seed=3)
    y, h = chunk(a, 128, True, impl)
    y0, h0 = chunk(a, 128, True, "xla")
    assert np.abs(y - y0).max() <= 2e-6 * np.abs(y0).max()
    assert np.abs(h - h0).max() <= 2e-6 * np.abs(h0).max()


def test_a_step_updates_live_lanes_and_leaves_dead_ones():
    S, Di, N = 5, 256, 16
    a = draw(S, Di, N, seed=4)
    rs = np.random.RandomState(5)
    h = rs.randn(S, N, Di).astype(np.float32)
    live = np.array([True, False, True, True, False])
    for k in ("c", "dt", "B", "C"):
        a[k][1] = np.nan        # what a dead lane computes is nobody's
    y, hn = ss.selective_scan_step(
        *(jnp.asarray(a[k]) for k in ("c", "dt", "B", "C", "A", "D")),
        jnp.asarray(h), jnp.asarray(live))
    y, hn = np.asarray(y), np.asarray(hn)
    for s in range(S):
        if not live[s]:
            assert (hn[s] == h[s]).all()
            continue
        one = {k: (v[s:s + 1] if k in ("c", "dt", "B", "C", "z") else v)
               for k, v in a.items()}
        want_y, want_h = loop(one, 1, False, h0=h[s])
        assert np.abs(hn[s] - want_h).max() <= TOL * 10
        assert np.abs(y[s] - want_y[0]).max() <= TOL * 10


def test_unknown_impl_and_ragged_shapes_are_refused():
    a = draw(16, 128, 4)
    with pytest.raises(ValueError, match="impl"):
        chunk(a, 16, False, "cuda")
    b = draw(12, 128, 4)
    with pytest.raises(ValueError, match="multiple"):
        chunk(b, 12, False, "pallas")
