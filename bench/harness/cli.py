"""One run of one cell: ``bench/run.py`` is its entry point.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed on the device, the scheduler, its compiles
and a warm-up over the window's prompt shapes) runs first and is timed
as ``setup_s``; then the window; then, with the program's state freed,
the reference check.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` traces the window and reports its per-layer
metrics.  The last line of standard output is the result as JSON; the
numbers compared for ``correct`` close standard error and the result.
A run that finds no TPU, or fewer chips than the cell needs, exits 1
and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import math
import shutil
import sys
import tempfile
import time

from . import correct, reading, serving, spec, traffic, weights, xtrace


def parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info():
    import jax

    devs = jax.devices()
    return devs, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}


def check_chips(cell) -> dict | None:
    """The device record, or None (after saying why) without a TPU or
    with fewer chips than the cell asks for."""
    _, dev = device_info()
    if dev["platform"] != "tpu":
        log(f"bench: no TPU found (JAX platform {dev['platform']!r}); "
            f"the benchmark does not fall back to the CPU")
        return None
    if dev["count"] < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, found "
            f"{dev['count']}")
        return None
    return dev


def place_cache() -> str:
    """JAX's persistent compilation cache at its fixed directory (see
    ``repro.launch.compile_cache``), every program written to it."""
    import jax

    from repro.launch.compile_cache import place_compile_cache

    where = place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def warm_set(reqs: list) -> list:
    """One request per prompt length of the run's whole request set.
    Every seed's set holds the same lengths (``traffic``), so every run
    warms, and after a checkout's first run finds in the cache, the same
    programs, whatever part of the set its window reaches."""
    seen, out = set(), []
    for r in sorted(reqs, key=lambda r: r.prompt_len):
        if r.prompt_len not in seen:
            seen.add(r.prompt_len)
            out.append(r)
    return out


def log_window(run, counter) -> None:
    """What the window did, on standard error (earlier lines than the
    compared numbers): compiles inside it, the generator's lateness,
    requests served, and with a trace the device time per program."""
    win, summary = run.window, run.trace
    if summary is not None:
        log("device seconds by program: " + ", ".join(
            f"{k} {v:.3f} ({summary.program_runs[k]} runs)" for k, v in
            sorted(summary.programs.items(), key=lambda kv: -kv[1])[:8]))
        log(f"trace: {summary.program_runs.get('fused', 0)} fused runs for "
            f"{win.counters['decode_dispatches']} decode dispatches in the "
            f"span of {win.span_s:.3f}s")
    log(f"set-up: {win.setup_compiles[0]} compiles "
        f"({win.setup_compiles[1]:.2f}s), {win.setup_compiles[2]} "
        f"persistent-cache hits before the window")
    log(f"window: {win.seconds:g}s, span {win.span_s:.3f}s, "
        f"{win.compiles[0]} compiles ({win.compiles[1]:.2f}s) and "
        f"{win.compiles[2]} persistent-cache hits inside it; counters "
        f"{json.dumps(win.counters)}")
    if counter.names:
        log("compiled or loaded inside the window: " + ", ".join(
            f"{k} x{v}" for k, v in counter.names.most_common()))
    lat = sorted(win.lateness_s)
    if lat:
        log(f"generator lateness: median {1e3 * lat[len(lat) // 2]:.2f} ms, "
            f"max {1e3 * lat[-1]:.2f} ms over {len(lat)} sends")
    ttft = reading.ttfts_ms(run)
    if ttft:
        log(f"time to first token over requests due: median "
            f"{reading.percentile(ttft, 50):.1f} ms, p95 "
            f"{reading.percentile(ttft, 95):.1f} ms")
        halves = reading.ttft_halves_ms(run)
        log("time to first token, median by half of the window: "
            + " / ".join("none" if h is None else f"{h:.1f} ms"
                         for h in halves))
    t_end = win.t0 + win.seconds
    due = reading.due_in_window(run)
    log(f"requests: {len(due)} due in the window, "
        f"{sum(1 for c in due if c.times and c.times[0] < t_end)} with a "
        f"first token by its close, "
        f"{sum(1 for c in due if c.status == 'completed')} completed, "
        f"{sum(1 for c in due if c.status == 'failed')} refused"
        + "".join(f"; {c.error}" for c in due if c.error)[:300])


def scheduler(cell, seed: int):
    """The program's scheduler for ``cell``, serving weights made from
    ``seed`` by its family."""
    fam = cell.family
    cfg = fam.arch_config(cell.model, cell.config_name)
    params = fam.program_params(
        weights.make(fam, cell.model, seed, cfg.param_dtype),
        fam.dims(cell.model))
    return serving.build_scheduler(cfg, params, cell.model)


def clients_and_warm_prompts(cell, seed: int, seconds: float):
    """The run's clients, in sending order, and its warm-up prompts."""
    mix, vocab = cell.traffic, cell.family.dims(cell.model)["vocab"]
    reqs = traffic.plan(mix, seed, seconds)
    clients = [serving.Client(r, p) for r, p in zip(
        reqs, traffic.prompts(reqs, vocab, seed), strict=True)]
    warm = traffic.prompts(warm_set(reqs), vocab, seed, warm=True)
    return clients, warm


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             *, control: bool = False, peaks: dict | None = None) -> dict:
    """Set up, measure and check one run; returns the result record."""
    mix = cell.traffic
    counter = serving.CompileCounter()
    sched = scheduler(cell, seed)
    log(f"set-up: weights and pool made at "
        f"{time.perf_counter() - t_start:.1f}s")
    sched.warmup()
    log(f"set-up: scheduler warm-up done at "
        f"{time.perf_counter() - t_start:.1f}s")
    clients, warm_prompts = clients_and_warm_prompts(cell, seed, seconds)
    log(f"set-up: {len(warm_prompts)} warm-up prompts, "
        f"{counter.compiles} compiles so far ({counter.compile_s:.1f}s), "
        f"{counter.cache_hits} persistent-cache hits")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    win = serving.run_window(sched, mix, clients, warm_prompts, seconds,
                             counter, trace_dir=trace_dir)
    setup_s = win.t0 - t_start
    devs, dev = device_info()
    stats = [d.memory_stats() or {} for d in devs]
    dev["memory_peak_bytes"] = max(s.get("peak_bytes_in_use", 0)
                                   for s in stats)
    summary = None
    if trace_dir is not None:
        files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        summary = xtrace.reduce(files[0], serving.WINDOW_SPAN)
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
    run = reading.Run(cell=cell, dims=cell.family.dims(cell.model),
                      dtype=cell.model["torch_dtype"],
                      n_slots=sched.pool.n_slots, setup_s=setup_s, window=win,
                      trace=summary, peaks=peaks or {})
    log_window(run, counter)
    max_len = sched.max_len
    del sched
    gc.collect()

    picks = correct.sample(win.clients, seed)
    t_ref = time.perf_counter()
    cmp = correct.compare(cell.family, cell.model, seed, picks, max_len,
                          control=control)
    log(f"reference: {cmp['requests']} requests, {cmp['tokens']} served "
        f"tokens checked in {time.perf_counter() - t_ref:.1f}s; the "
        f"program's widest gap {cmp['logit_gap']!r}")
    limit = float(cell.limits["logit_gap"])
    # The control's run stands in the program's place: its own gap
    # decides, so a limit that passes the lower precision shows here.
    judged = cmp["control_logit_gap"] if control else cmp["logit_gap"]
    ok = bool(picks) and judged <= limit

    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = spec.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    due = reading.due_in_window(run)
    result = {"correct": ok, "attempted": len(due),
              "failed": sum(1 for c in due if c.status == "failed"),
              "metrics": metrics, "device": dev}
    if summary is not None:
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.ops[:10]],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:10]]}
    if control:
        result["program_logit_gap"] = cmp["logit_gap"]
        compared = {"control_logit_gap": {"value": judged, "limit": limit}}
    else:
        compared = {"logit_gap": {"value": judged, "limit": limit}}
    result["compared"] = compared
    return result


def main(argv, t_start: float, *, control: bool = False) -> int:
    args = parse(argv)
    if not math.isfinite(args.seconds) or args.seconds <= 0:
        log("bench: --seconds must be positive")
        return 2
    cell = spec.load_cell(args.workload)
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError:
        log(f"bench: the program is not in this checkout ({spec.ROOT / 'src'})")
        return 1
    dev = check_chips(cell)
    if dev is None:
        return 1
    peaks = spec.load_json(spec.BENCH / "peaks.json")
    if dev["kind"] not in peaks:
        log(f"bench: no peaks for device kind {dev['kind']!r} in "
            f"bench/peaks.json")
        return 1
    log(f"device: {dev}; compile cache: {place_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, control=control, peaks=peaks[dev["kind"]])
    for name, c in result["compared"].items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
