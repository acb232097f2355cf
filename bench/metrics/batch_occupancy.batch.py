"""Lane-tokens the decode loop produced per slot it iterated over:
``decode_tokens / (decode_loop_iters * n_slots)`` over the span."""


def read(run):
    iters = run.window.counters["decode_loop_iters"]
    if not iters:
        return None
    return 100.0 * run.window.counters["decode_tokens"] / (iters * run.n_slots)
