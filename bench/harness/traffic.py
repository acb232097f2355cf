"""The one traffic generator: it reads a mix file (``bench/traffic/*.json``)
and a seed, and plans the requests of one run.

Every seed gets the same set of sizes and gaps, in another order.  Prompt
and output lengths are the stratified quantiles of the mix's clipped
lognormals (the arithmetic of ``serve/loadgen.heavy_tailed_trace``:
median-parameterised, rounded, clipped), inter-arrival gaps are the
stratified quantiles of an exponential at the mix's rate (Poisson
arrivals, as ``serve/loadgen.poisson_trace``).  The seed only permutes
them and draws the token ids, so two seeds load the system alike and
differ in order and content.

Mix keys: ``loop`` (``open``: sends at due times; ``closed``: ``clients``
callers, each sending its next request when the last one finished),
``rate_rps``, ``clients``, ``requests`` (closed loop: size of the request
set the callers draw from in turn, from its start again once it is
spent), and ``prompt`` and ``output`` (``median``, ``sigma``, ``min``,
``max``).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

# Streams of one seed: request order, window prompt tokens, warm-up
# prompt tokens.
_ORDER, _TOKENS, _WARM = range(3)


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float | None      # open loop: seconds after the window opens
    prompt_len: int
    new_tokens: int


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def lognormal_lengths(spec: dict, n: int) -> list[int]:
    """The ``n`` stratified quantiles of ``round(median * exp(sigma *
    Z))``, clipped to ``[min, max]``."""
    nd = NormalDist()
    out = []
    for i in range(n):
        v = spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), spec["min"]), spec["max"])))
    return out


def exponential_gaps(rate: float, n: int) -> list[float]:
    """The ``n`` stratified quantiles of an exponential of mean 1/rate."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def n_requests(mix: dict, seconds: float) -> int:
    """Size of a run's request set: an open loop plans a quarter more
    than its rate fills the window with (what falls past the window is
    never sent); a closed loop draws from the mix's fixed set."""
    if mix["loop"] == "open":
        return int(math.ceil(mix["rate_rps"] * seconds * 1.25)) + 8
    return int(mix["requests"])


def plan(mix: dict, seed: int, seconds: float) -> list[Request]:
    """The run's requests, in sending order."""
    n = n_requests(mix, seconds)
    r = rng(seed, _ORDER)
    prompt = r.permutation(lognormal_lengths(mix["prompt"], n))
    new = r.permutation(lognormal_lengths(mix["output"], n))
    if mix["loop"] == "open":
        due = np.cumsum(r.permutation(exponential_gaps(mix["rate_rps"], n)))
    else:
        due = [None] * n
    return [Request(i, None if due[i] is None else float(due[i]),
                    int(prompt[i]), int(new[i])) for i in range(n)]


def prompts(reqs: list[Request], vocab: int, seed: int, *,
            warm: bool = False) -> list[np.ndarray]:
    """Token ids of each request's prompt; ``warm`` draws other tokens,
    for the warm-up that compiles the window's shapes."""
    r = rng(seed, _WARM if warm else _TOKENS)
    return [r.integers(0, vocab, size=q.prompt_len, dtype=np.int32)
            for q in reqs]
