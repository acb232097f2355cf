"""Chunked gated linear attention: chunked == naive == recurrent, plus
hypothesis sweeps over shapes."""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="shape sweeps need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.models import gla

RS = np.random.RandomState(7)


def make(b, l, h, n, p, scale=0.3):
    q = jnp.asarray(RS.randn(b, l, h, n).astype(np.float32)) * scale
    k = jnp.asarray(RS.randn(b, l, h, n).astype(np.float32)) * scale
    v = jnp.asarray(RS.randn(b, l, h, p).astype(np.float32))
    ld = -jnp.abs(jnp.asarray(RS.randn(b, l, h).astype(np.float32))) * 0.5
    g = jnp.asarray(RS.randn(b, l, h).astype(np.float32)) * 0.3
    return q, k, v, ld, g


@given(b=st.integers(1, 3), nc=st.integers(1, 4), h=st.integers(1, 3),
       n=st.sampled_from([4, 8]), p=st.sampled_from([4, 16]),
       chunk=st.sampled_from([8, 16, 32]))
@settings(max_examples=25, deadline=None)
def test_chunked_equals_reference(b, nc, h, n, p, chunk):
    l = nc * chunk
    q, k, v, ld, g = make(b, l, h, n, p)
    y, _ = gla.chunked_gla(q, k, v, ld, g, chunk=chunk)
    yref = gla.gla_reference(q, k, v, ld, g)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               rtol=3e-4, atol=3e-4)


def test_recurrent_equals_chunked():
    q, k, v, ld, g = make(2, 64, 2, 8, 8)
    y, s = gla.chunked_gla(q, k, v, ld, g, chunk=16)
    state = jnp.zeros((2, 2, 8, 8))
    ys = []
    for t in range(64):
        yt, state = gla.gla_step(q[:, t], k[:, t], v[:, t], ld[:, t],
                                 g[:, t], state)
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)), np.asarray(y),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s),
                               rtol=3e-4, atol=3e-4)


def test_state_continuation():
    q, k, v, ld, g = make(1, 96, 2, 4, 4)
    y_full, s_full = gla.chunked_gla(q, k, v, ld, g, chunk=16)
    y1, s1 = gla.chunked_gla(q[:, :48], k[:, :48], v[:, :48], ld[:, :48],
                             g[:, :48], chunk=16)
    y2, s2 = gla.chunked_gla(q[:, 48:], k[:, 48:], v[:, 48:], ld[:, 48:],
                             g[:, 48:], chunk=16, initial_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=3e-4, atol=3e-4)


def test_no_gain_defaults_to_zero():
    q, k, v, ld, _ = make(1, 32, 1, 4, 4)
    y1, _ = gla.chunked_gla(q, k, v, ld, None, chunk=16)
    y2, _ = gla.chunked_gla(q, k, v, ld, jnp.zeros((1, 32, 1)), chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-6)


def test_unroll_flag_equivalence():
    from repro.models import flags

    q, k, v, ld, g = make(1, 64, 2, 4, 8)
    y1, s1 = gla.chunked_gla(q, k, v, ld, g, chunk=16)
    with flags.unroll_for_accounting():
        y2, s2 = gla.chunked_gla(q, k, v, ld, g, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)


def _recurrence(q, k, v, ld, g, state):
    ys = []
    for t in range(q.shape[1]):
        yt, state = gla.gla_step(q[:, t], k[:, t], v[:, t], ld[:, t],
                                 None if g is None else g[:, t], state)
        ys.append(yt)
    return jnp.stack(ys, 1), state


def test_carried_state_with_masked_pads_matches_recurrence():
    """A chunk that starts from a carried state and ends in pads (zero
    input, zero log-decay) gives the sequential recurrence's outputs on
    its real tokens and leaves the state where they left it."""
    q, k, v, ld, g = make(2, 48, 3, 8, 4)
    s0 = jnp.asarray(RS.randn(2, 3, 8, 4).astype(np.float32))
    real = 29
    keep = (jnp.arange(48) < real)[None, :, None]
    v_pad = jnp.where(keep[..., None], v, 0.0)
    ld_pad = jnp.where(keep, ld, 0.0)
    y, s = gla.chunked_gla(q, k, v_pad, ld_pad, g, chunk=16,
                           initial_state=s0)
    y_ref, s_ref = _recurrence(q[:, :real], k[:, :real], v[:, :real],
                               ld[:, :real], g[:, :real], s0)
    np.testing.assert_allclose(np.asarray(y[:, :real]), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=3e-4, atol=3e-4)


def test_fast_decay_stays_exact():
    """Heads that decay by e^-20 a token (a Mamba-2 head with A = 16 and
    Δ past 1): every exponential stays in range, so the chunked form
    still equals the recurrence instead of underflowing to zero."""
    q, k, v, _, _ = make(1, 64, 2, 4, 4)
    ld = jnp.full((1, 64, 2), -20.0).at[:, :, 1].set(-0.01)
    y, s = gla.chunked_gla(q, k, v, ld, None, chunk=64)
    y_ref, s_ref = _recurrence(q, k, v, ld, None, jnp.zeros((1, 2, 4, 4)))
    assert np.all(np.isfinite(np.asarray(y)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=3e-4, atol=3e-4)
