"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program: ``make`` returns them
in the reference's flat layout, with the leaves and scales of the
configuration's family (``bench/families``); the family's
``program_params`` hands the same arrays to the program in its own
pytree, and the reference makes them again from the seed once the
program is gone, so it takes nothing the program made.
"""
from __future__ import annotations

import functools

import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    import jax

    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(word))


@functools.lru_cache(maxsize=None)
def _maker(fam, dims_items: tuple, dtype: str):
    import jax
    import jax.numpy as jnp

    dm = dict(dims_items)
    leaves = fam.leaves(dm)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = {}
        for k, (name, shape) in zip(keys, sorted(leaves.items())):
            mean, std = fam.scale(name, shape, dm)
            out[name] = (mean + std * jax.random.normal(
                k, shape, jnp.float32)).astype(dtype)
        return out

    return jax.jit(make)


def make(fam, model: dict, seed: int, dtype: str = "float32") -> dict:
    """All weights of ``model`` (of family ``fam``) from ``seed``, on the
    default device."""
    return _maker(fam, tuple(sorted(fam.dims(model).items())),
                  dtype)(seed_key(seed))
