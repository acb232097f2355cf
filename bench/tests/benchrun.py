"""Drive one tiny run of a cell on the CPU (the harness's look for a chip
skipped), optionally with the timed path broken underneath."""
import time

from benchutil import tiny_cell

from harness import cli

SEED = 2**31 + 4242
SECONDS = 4.0


def run(cell_name: str, *, control: bool = False, **mix_over) -> dict:
    return cli.run_cell(tiny_cell(cell_name, **mix_over), SEED, SECONDS,
                        False, time.perf_counter(), control=control)


def break_kv_merge(monkeypatch):
    """Fault: the decode step returns the KV cache unchanged."""
    from repro.serve import decode_loop

    monkeypatch.setattr(decode_loop, "masked_merge",
                        lambda caches, new, active: caches)


def alter_tokens(monkeypatch):
    """Fault: every decoded token is altered where it is produced (the
    argmax's neighbour is emitted and fed back)."""
    from repro.serve import decode_loop

    real = decode_loop.make_lane_step

    def lanes_plus_one(cfg, **kw):
        lanes = real(cfg, **kw)

        def wrapped(*a):
            toks, caches = lanes(*a)
            return (toks + 1) % cfg.vocab_size, caches

        return wrapped

    monkeypatch.setattr(decode_loop, "make_lane_step", lanes_plus_one)


def gap(result) -> float:
    return result["compared"]["logit_gap"]["value"]

