"""The Mamba-2 mixer's cached paths: a prefill chunk from a carried state
runs the chunked SSD, and a chunk padded past its real length leaves the
SSM and conv state as the exact-size chunk does, to float32 rounding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import mamba2

CFG = dataclasses.replace(get_config("granite-4.0-h-micro").reduced(),
                          compute_dtype="float32")


def _setup(seed=0):
    p = mamba2.init(jax.random.PRNGKey(seed), CFG)
    rng = np.random.RandomState(seed)
    p["a_log"] = jnp.asarray(rng.uniform(0, 2.7, p["a_log"].shape), jnp.float32)
    p["dt_bias"] = jnp.asarray(rng.uniform(-6, -2, p["dt_bias"].shape),
                               jnp.float32)
    x = jnp.asarray(rng.randn(2, 40, CFG.d_model).astype(np.float32))
    return p, x


def _run_tokens(p, x, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = mamba2.apply(p, x[:, t:t + 1], CFG, state=state)
        ys.append(y)
    return jnp.concatenate(ys, 1), state


def test_chunks_from_a_carried_state_match_token_steps():
    p, x = _setup()
    state = mamba2.init_state(CFG, 2, jnp.float32)
    y_steps, s_steps = _run_tokens(p, x, state)
    y1, s1 = mamba2.apply(p, x[:, :17], CFG, state=state)
    y2, s2 = mamba2.apply(p, x[:, 17:], CFG, state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_steps), rtol=1e-4, atol=1e-4)
    for k in ("ssm", "conv"):
        np.testing.assert_allclose(np.asarray(s2[k]), np.asarray(s_steps[k]),
                                   rtol=1e-4, atol=1e-4)


def test_padded_chunk_leaves_the_state_of_the_exact_chunk():
    p, x = _setup(1)
    _, state = mamba2.apply(p, x[:, :9], CFG,
                            state=mamba2.init_state(CFG, 2, jnp.float32))
    real = 11
    y_exact, s_exact = mamba2.apply(p, x[:, 9:9 + real], CFG, state=state)
    # pad with other tokens: a pad that leaked would move the state
    padded = jnp.concatenate([x[:, 9:9 + real], 5.0 * x[:, :21]], 1)
    y_pad, s_pad = mamba2.apply(p, padded, CFG, state=state,
                                length=jnp.int32(real))
    np.testing.assert_allclose(np.asarray(y_pad[:, :real]),
                               np.asarray(y_exact), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_pad["ssm"]),
                               np.asarray(s_exact["ssm"]), rtol=1e-5,
                               atol=1e-6)
    # (the projections of a longer chunk may round otherwise in the
    # last bit: the matmul blocks its rows differently)
    np.testing.assert_allclose(np.asarray(s_pad["conv"]),
                               np.asarray(s_exact["conv"]), rtol=1e-5,
                               atol=1e-6)
    # without its length the same chunk lets the pads in
    _, s_leak = mamba2.apply(p, padded, CFG, state=state)
    assert float(jnp.max(jnp.abs(s_leak["ssm"] - s_exact["ssm"]))) > 1e-2
