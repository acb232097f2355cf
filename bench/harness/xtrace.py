"""From a profiler trace (``.xplane.pb``) to device busy time, per-program
device time, the operations that took the most device time, and idle
gaps with what the host was doing in each.

Read with ``jax.profiler.ProfileData`` alone.  A device is a plane named
``/device:TPU:<n>``.  Its ``XLA Modules`` line holds one event per
program execution (``jit_<function>(<id>)``); its ``XLA Ops`` line one
event per operation, named by the operation's HLO text, with a loop's
body operations nested inside the loop's own event.

- Busy time is the union of the operation intervals inside the window.
- An operation's time is its self time (children nested in it taken
  out), summed per program and operation kind (``fused/select_fusion``:
  the HLO name without its ``%`` and its numeric suffix).
- An idle gap is a stretch of the window in which no operation runs.
  Each of ``GAP_S`` or more is put down to the innermost host span
  (``TraceAnnotation``, or the runtime's own host events) covering more
  than half of it; shorter gaps, between the operations of one program,
  are summed under ``SHORT_GAPS``.

The window is the span of the host event named ``window_name``, or the
span of the device's events without one.  Host and device clocks agree
to a millisecond or two, which is nothing against a window of seconds.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_MODULE_ID = re.compile(r"\(\d+\)$")
_OP_KIND = re.compile(r"^%?([A-Za-z_-]+?)(?:[.\d]*)(?: = |$)")
GAP_S = 10e-6
SHORT_GAPS = "between ops (<10us)"
NO_HOST_SPAN = "no host span (host waiting)"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over devices
    n_devices: int
    programs: dict                     # program name -> device seconds
    program_runs: dict                 # program name -> executions
    ops: list                          # [(program/op kind, self seconds)]
    idle_gaps: list                    # [(host activity, seconds)]


def program_name(module: str) -> str:
    """``jit_fused(1234)`` -> ``fused``."""
    name = _MODULE_ID.sub("", module)
    return name[4:] if name.startswith("jit_") else name


def op_kind(hlo: str) -> str:
    """``%select_fusion.65 = (bf16[...]) fusion(...)`` -> ``select_fusion``."""
    m = _OP_KIND.match(hlo)
    return m.group(1) if m else hlo.split(" ", 1)[0]


def _events(line):
    for e in line.events:
        a = float(e.start_ns)
        yield e.name, a, a + float(e.duration_ns)


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events):
    """(name, start, self seconds) of nested events: each event's
    duration less the parts its children cover."""
    out = []
    stack: list = []                  # [name, start, end, child_ns]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            n, s, e, kids = stack.pop()
            out.append((n, s, (e - s - kids) / 1e9))
        if stack:
            stack[-1][3] += b - a
        stack.append([name, a, b, 0.0])
    out.extend((n, s, (e - s - kids) / 1e9) for n, s, e, kids in stack)
    return out


def reduce(path: str, window_name: str | None = None) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            lines = {line.name: list(_events(line)) for line in plane.lines}
            devices.append((lines.get("XLA Modules", []),
                            lines.get("XLA Ops", [])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                # ``$file:line fn`` events are the Python tracer's.
                host.extend(ev for ev in _events(line)
                            if ev[2] > ev[1] and not ev[0].startswith("$"))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    spans = [(a, b) for n, a, b in host if n == window_name]
    if spans:
        lo, hi = min(a for a, _ in spans), max(b for _, b in spans)
    else:
        every = [ev for mods, ops in devices for ev in mods + ops]
        lo, hi = min(a for _, a, _ in every), max(b for _, _, b in every)

    def clipped(events):
        for name, a, b in events:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                yield name, a, b

    programs, runs, ops = (collections.Counter() for _ in range(3))
    idle = collections.Counter()
    busy_total = 0.0
    host = sorted((a, b, n) for n, a, b in host if n != window_name)
    for mods, op_events in devices:
        mods = sorted(clipped(mods), key=lambda e: e[1])
        for name, a, b in mods:
            programs[program_name(name)] += (b - a) / 1e9
            runs[program_name(name)] += 1
        op_events = list(clipped(op_events))
        starts = [a for _, a, _ in mods]
        for name, a, s in _self_times(op_events):
            i = _covering(starts, mods, a)
            where = program_name(mods[i][0]) if i is not None else "?"
            ops[f"{where}/{op_kind(name)}"] += s
        busy = _union((a, b) for _, a, b in (op_events or mods))
        busy_total += sum(b - a for a, b in busy) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for name, s in _attribute(gaps, host):
            idle[name] += s
    n = len(devices)
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy_total / n, n_devices=n,
        programs={k: v / n for k, v in programs.items()},
        program_runs={k: v // n for k, v in runs.items()},
        ops=[(k, v / n) for k, v in ops.most_common()],
        idle_gaps=[(k, v / n) for k, v in idle.most_common()])


def _covering(starts, mods, t):
    """Index of the module event running at time ``t``, or None."""
    i = bisect.bisect_right(starts, t) - 1
    return i if i >= 0 and mods[i][2] >= t else None


def _attribute(gaps, host):
    """(activity, seconds) per gap: the shortest host event that covers
    more than half of it.  ``gaps`` and ``host`` (start, end, name) are
    sorted by start; gaps are disjoint, so one sweep serves them all."""
    active, i = [], 0
    for a, b in gaps:
        if b - a < GAP_S * 1e9:
            yield SHORT_GAPS, (b - a) / 1e9
            continue
        while i < len(host) and host[i][0] < b:
            active.append(host[i])
            i += 1
        active = [ev for ev in active if ev[1] > a]
        best = None
        for ha, hb, name in active:
            if min(b, hb) - max(a, ha) > 0.5 * (b - a) \
                    and (best is None or hb - ha < best[0]):
                best = (hb - ha, name)
        yield (best[1] if best else NO_HOST_SPAN), (b - a) / 1e9
