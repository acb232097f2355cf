"""Architecture families, found by name.

A configuration file's ``equations.family`` names a module
``bench/families/<family>.py``; without the key the family is ``dense``.
The family owns everything the harness knows of an architecture, so a
configuration of another architecture comes as new files only (its
family module and its reference), with no edit to the harness:

- ``arch_config(model, name)``: the program's ``ArchConfig``;
- ``dims(model)``: the sizes the rest read, a flat dict of hashable
  values, ``vocab`` (the range prompts draw their token ids from) among
  them;
- ``leaves(dm)``: leaf name -> shape, in the reference's flat layout;
- ``scale(name, shape, dm)``: the (mean, std) a leaf draws from;
- ``program_params(w, dm)``: the same arrays in the program's pytree;
- ``REFERENCE``: the module ``bench/reference/<REFERENCE>.py``, with
  ``config(model)``, ``HEAD_BLOCK`` and ``gaps(w, tokens, targets, *,
  cfg, control)`` (see ``harness/correct.py``);
- ``prefill_flops(dm, start, stop)``: operations of prompt positions
  ``[start, stop)``;
- ``decode_work(dm, dtype, iterations, kv_lens_sum, tokens)``: (flops,
  bytes) of ``iterations`` decode loop iterations that produced
  ``tokens`` lane-tokens over live contexts summing to ``kv_lens_sum``;
  the bytes include every per-lane state a step reads and writes.

The counts are what the algorithm needs, not what an implementation
happens to move: weights once per step at the stated compute dtype (the
embedding's rows are a lookup and not counted; the output head is), the
live state of each lane (an attention layer's ``kv_len`` rows, not the
pool's ``max_len``; a recurrent layer's whole state, read and written),
and two operations per weight per token plus the attention over the live
context.  A later kernel cannot make these counts stale.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DEFAULT = "dense"
BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}     # per element


def load(model: dict, bench: Path = BENCH):
    """The family module that ``model``'s configuration file names, from
    the benchmark directory ``bench``."""
    name = model.get("equations", {}).get("family", DEFAULT)
    if not name.isidentifier():
        raise ValueError(f"equations.family {name!r} is not a module name")
    return named(name, bench)


def named(name: str, bench: Path = BENCH):
    """The family module ``<bench>/families/<name>.py``."""
    return _module(Path(bench) / "families" / f"{name}.py")


def reference(fam):
    """The reference module of family ``fam``, beside it in its
    benchmark directory."""
    return _module(Path(fam.__file__).parents[1] / "reference"
                   / f"{fam.REFERENCE}.py")


@functools.cache
def _module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no {path.parent.name} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
