"""The plain reference against the program's own full forward, at
reduced sizes on the CPU, by logits.  With float32 compute on both sides
the two are the same equations in another order, so they agree to
float32 rounding; a wrong norm, rotation, head grouping or head would
move logits by their own scale (about 2.5 here)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchutil import tiny_model

import families
from harness import weights
from reference import model as ref

CONFIGS = ["qwen3-0.6b", "h2o-danube-1.8b"]


def _ref_logits(w, model, tokens):
    """Every position's reference logits (the reference computes gaps;
    here targets 0..V-1 at one row each read the whole row)."""
    c = dict(ref.config(model))
    h = ref._hidden(w, tokens, c, False)
    return np.asarray(jnp.matmul(h, ref._head(w, c), precision=ref.HI))


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_matches_program_forward(config):
    from repro.models import lm

    model = tiny_model(config)
    fam = families.load(model)
    cfg = dataclasses.replace(fam.arch_config(model, config),
                              compute_dtype="float32")
    w = weights.make(fam, model, seed=3)
    tokens = np.random.default_rng(0).integers(0, 512, 48).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = lm.forward(fam.program_params(w, fam.dims(model)),
                             {"tokens": jnp.asarray(tokens)[None]}, cfg)
    got = _ref_logits(w, model, jnp.asarray(tokens))
    want = np.asarray(want[0])
    assert np.std(want) > 1.0            # logits spread over units
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


@pytest.mark.parametrize("config", CONFIGS)
def test_gaps_read_the_served_tokens(config):
    """``gaps`` is zero where the target is the reference's argmax and
    the logit difference elsewhere; the fp8 control departs from it."""
    model = tiny_model(config)
    w = weights.make(families.load(model), model, seed=4)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 512, 256),
                         jnp.int32)
    logits = _ref_logits(w, model, tokens)
    top = logits.argmax(-1).astype(np.int32)
    cfg = ref.config(model)
    g, _ = ref.gaps(w, tokens, jnp.asarray(top), cfg=cfg)
    assert float(jnp.max(jnp.abs(g))) < 1e-5
    other = (top + 1) % 512
    g, gc = ref.gaps(w, tokens, jnp.asarray(other), cfg=cfg, control=True)
    want = logits.max(-1) - logits[np.arange(256), other]
    np.testing.assert_allclose(np.asarray(g), want, atol=1e-4)
    assert 0 < float(jnp.max(gc)) < float(jnp.max(g))
