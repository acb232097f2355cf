"""Roofline analysis from a compiled dry-run artifact.

``compiled.cost_analysis()`` is per-device (verified: a (1024,1024)@8-way
matmul reports 2·M³/8 flops), so:

    compute    = flops_per_device    / peak_flops          (s)
    memory     = bytes_per_device    / hbm_bw              (s)
    collective = collective_bytes_per_device / link_bw     (s)

collective bytes are parsed from the post-partitioning HLO text
(``compiled.as_text()``): for every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute, sum the operand shape
bytes (the assignment's convention).  MODEL_FLOPS = 6·N(active)·D for
training, 2·N(active)·tokens for serve steps; the useful-fraction
MODEL_FLOPS / HLO_FLOPs exposes remat/redundancy waste.
"""
from __future__ import annotations

import dataclasses
import json
import re

from ..configs.base import ArchConfig, ShapeConfig
from ..core.hardware import TPU_V5E, HardwareSpec

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "f8e4m3b11fnuz": 1, "f4e2m1fn": 1,
}

_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective byte totals from partitioned HLO text.

    Post-optimization HLO prints operands as names, so sizes are taken
    from the RESULT shape(s) printed between '=' and the op name.  For
    all-reduce / all-to-all / collective-permute the result equals the
    operand size; for all-gather the result is the gathered buffer, which
    matches ring wire traffic ((g-1)/g ≈ 1×result); for reduce-scatter
    the result understates wire traffic by ~g — noted, rare in our
    modules (GSPMD emits AR+AG pairs).
    """
    out: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        eq = line.rfind("=", 0, m.start())
        if eq < 0:
            continue
        kind = m.group(1)
        result_part = line[eq:m.start()]
        total = sum(_shape_bytes(d, s)
                    for d, s in _SHAPE_RE.findall(result_part))
        out[kind] = out.get(kind, 0.0) + total
        counts[kind] = counts.get(kind, 0) + 1
    out["total"] = sum(v for k, v in out.items() if k != "total")
    out["wire_total"] = wire_bytes(out)
    return {"bytes": out, "counts": counts}


def wire_bytes(byte_map: dict) -> float:
    """Ring-wire traffic model: an all-reduce traverses the ring twice
    (reduce-scatter + all-gather phases ⇒ 2× buffer bytes); the others
    move ~1× their result bytes."""
    total = 0.0
    for kind, v in byte_map.items():
        if kind in ("total", "wire_total"):
            continue
        total += 2.0 * v if kind == "all-reduce" else v
    return total


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collective_detail: dict
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    useful_fraction: float
    peak_memory_bytes: float
    argument_bytes: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time: overlapped model = max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute time / bound step time (MFU-at-the-roofline)."""
        ideal = self.model_flops / (self.chips * TPU_V5E.peak_flops)
        return ideal / self.step_time_s if self.step_time_s > 0 else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(dominant=self.dominant, step_time_s=self.step_time_s,
                 roofline_fraction=self.roofline_fraction)
        return d


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    # decode: one token per sequence, plus the KV/state read is memory not
    # flops — 2·N_active·batch
    return 2.0 * n_active * shape.global_batch


def analyze(compiled, *, cfg: ArchConfig, shape: ShapeConfig,
            mesh_name: str, chips: int,
            hw: HardwareSpec = TPU_V5E) -> RooflineReport:
    flops, byts, cb, coll = extract_costs(compiled)
    ma = compiled.memory_analysis()
    peak = float(getattr(ma, "temp_size_in_bytes", 0)
                 + getattr(ma, "output_size_in_bytes", 0))
    args = float(getattr(ma, "argument_size_in_bytes", 0))
    mf = model_flops(cfg, shape)
    return RooflineReport(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=byts,
        collective_bytes_per_device=cb, collective_detail=coll,
        compute_s=flops / hw.peak_flops,
        memory_s=byts / hw.mem_bw,
        collective_s=cb / hw.link_bw,
        model_flops=mf,
        useful_fraction=mf / (flops * chips) if flops else 0.0,
        peak_memory_bytes=peak,
        argument_bytes=args,
    )


def extract_costs(compiled) -> tuple[float, float, float, dict]:
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)),
            float(coll["bytes"].get("wire_total", 0.0)), coll)


def analyze_calibrated(full_compiled, comp_group, comp_base,
                       multiplier: float, *, cfg: ArchConfig,
                       shape: ShapeConfig, mesh_name: str, chips: int,
                       hw: HardwareSpec = TPU_V5E) -> RooflineReport:
    """Roofline with loop-calibrated totals.

    XLA cost analysis counts a while-loop body once (verified), so the
    layer-group scan and grad-accum scan undercount.  ``comp_group`` is
    the cell lowered with exactly one pattern group (inner loops unrolled
    via flags.unroll_for_accounting) and ``comp_base`` with zero layers;
    total = base + multiplier · (group − base), multiplier = n_layers /
    period.  ``full_compiled`` (the deliverable artifact) provides the
    memory analysis.
    """
    fa, ba, ca_, coll_a = extract_costs(comp_group)
    fb, bb, cb_, coll_b = extract_costs(comp_base)
    flops = fb + multiplier * (fa - fb)
    byts = bb + multiplier * (ba - bb)
    coll = cb_ + multiplier * (ca_ - cb_)
    ma = full_compiled.memory_analysis()
    peak = float(getattr(ma, "temp_size_in_bytes", 0)
                 + getattr(ma, "output_size_in_bytes", 0))
    args = float(getattr(ma, "argument_size_in_bytes", 0))
    mf = model_flops(cfg, shape)
    return RooflineReport(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=byts,
        collective_bytes_per_device=coll,
        collective_detail={"group": coll_a, "base": coll_b,
                           "multiplier": multiplier},
        compute_s=flops / hw.peak_flops,
        memory_s=byts / hw.mem_bw,
        collective_s=coll / hw.link_bw,
        model_flops=mf,
        useful_fraction=mf / (flops * chips) if flops else 0.0,
        peak_memory_bytes=peak,
        argument_bytes=args,
    )


def flash_attention_hbm_bytes(cfg: ArchConfig, shape: ShapeConfig, *,
                              chips: int, passes: float = 4.0,
                              dtype_bytes: int = 2) -> float:
    """Per-device HBM traffic of the Pallas flash kernel for one step.

    Per layer, forward: q read + out write (2·T·Hq·D·b) plus K/V streamed
    once per q-block row (nq · 2·T·Hkv·D·b · visible-fraction; causal ⇒
    ~0.55 of tiles visible; SWA caps visible keys at the window).
    ``passes``: fwd(1) + bwd(2) + remat-fwd(1) = 4 for training, 1 for
    prefill.  Used by the §Perf flash-adjusted memory term together with
    an ``attn_impl="skip"`` lowering that removes the jnp attention's
    accounted bytes.
    """
    from ..kernels import tuning

    s = shape.seq_len
    tokens = shape.global_batch * s
    d = cfg.head_dim_
    bq, _ = tuning.plan_attention(s, s, d, bytes_per_elem=dtype_bytes)
    nq = max(s // bq, 1)
    visible = 0.55 if cfg.attn_window is None else min(
        cfg.attn_window / s + 0.5 / nq, 1.0)
    attn_layers = sum(1 for k in cfg.layer_kinds()
                      if k in ("attn", "shared_attn", "cross_attn"))
    per_layer = (2.0 * tokens * cfg.n_heads * d * dtype_bytes
                 + nq * 2.0 * tokens * cfg.n_kv_heads * d * dtype_bytes
                 * visible)
    return passes * attn_layers * per_layer / chips


def save_report(report: RooflineReport, path: str) -> None:
    with open(path, "w") as f:
        json.dump(report.to_dict(), f, indent=2)


def format_row(r: RooflineReport) -> str:
    return (f"{r.arch:22s} {r.shape:12s} {r.mesh:6s} "
            f"cmp {r.compute_s*1e3:9.3f}ms  mem {r.memory_s*1e3:9.3f}ms  "
            f"col {r.collective_s*1e3:9.3f}ms  dom={r.dominant:10s} "
            f"useful {r.useful_fraction*100:5.1f}%  "
            f"roofline {r.roofline_fraction*100:5.1f}%  "
            f"hbm {(r.argument_bytes+r.peak_memory_bytes)/2**30:6.2f}GiB")
