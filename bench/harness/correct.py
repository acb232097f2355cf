"""Whether the timed path served the right tokens.

Once the window has closed and the program is gone, a sample of the
requests the window finished, drawn from the seed and always holding the
longest, is run through the plain float32 reference of the
configuration's family (``bench/reference/<family.REFERENCE>.py``) over
prompt plus served tokens.  For each served token the reference
gives the gap by which that token's logit lies below its best; the
number compared is the widest gap of the sample.  Greedy decoding serves
the token the program scores highest, so a sound program's gaps are its
rounding, and a wrong one's are the logits' own scale.

The control puts the reference in the program's place at the next
precision down (fp8): at each position of the same sequences it reads
the gap of the token the fp8 forward puts first.
"""
from __future__ import annotations

import math

import numpy as np

import families

from . import traffic, weights

SAMPLE_TOKENS = 384      # served tokens the sample aims at,
SAMPLE_MIN_REQUESTS = 4  # over at least this many requests
SAMPLE_REQUESTS = 16
_SAMPLE_STREAM = 7


def sample(clients: list, seed: int) -> list:
    done = [c for c in clients if c.status == "completed" and c.tokens]
    if not done:
        return []
    longest = max(done, key=lambda c: (len(c.prompt) + len(c.tokens),
                                       -c.req.index))
    rest = [c for c in done if c is not longest]
    picks, total = [longest], len(longest.tokens)
    for i in traffic.rng(seed, _SAMPLE_STREAM).permutation(len(rest)):
        if (total >= SAMPLE_TOKENS and len(picks) >= SAMPLE_MIN_REQUESTS) \
                or len(picks) >= SAMPLE_REQUESTS:
            break
        picks.append(rest[i])
        total += len(rest[i].tokens)
    return picks


def compare(fam, model: dict, seed: int, picks: list, max_len: int, *,
            control: bool = False) -> dict:
    """Widest reference gap of the served tokens (and of the control's
    first choices with ``control``) over ``picks``; ``fam`` is the
    configuration's family."""
    import jax

    ref = families.reference(fam)
    w = weights.make(fam, model, seed, "float32")
    cfg = ref.config(model)
    length = -(-max_len // ref.HEAD_BLOCK) * ref.HEAD_BLOCK
    worst, checked = 0.0, 0
    ctl_gaps = []
    for c in picks:
        n, plen = len(c.tokens), len(c.prompt)
        seq = np.zeros(length, np.int32)
        seq[:plen] = c.prompt
        seq[plen:plen + n - 1] = c.tokens[:-1]
        tgt = np.zeros(length, np.int32)
        tgt[plen - 1:plen - 1 + n] = c.tokens
        g, gc = jax.device_get(ref.gaps(w, seq, tgt, cfg=cfg,
                                        control=control))
        g = g[plen - 1:plen - 1 + n]
        worst = max(worst, float(np.max(g)) if np.all(np.isfinite(g))
                    else math.inf)
        if control:
            ctl_gaps.append(float(np.max(gc[plen - 1:plen - 1 + n])))
        checked += n
    out = {"logit_gap": worst, "requests": len(picks), "tokens": checked}
    if control:
        out["control_logit_gap"] = max(ctl_gaps) if ctl_gaps else math.nan
    return out
