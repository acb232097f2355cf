"""Roofline analysis unit tests (HLO collective parsing + model flops)
and the HLO reshard auditor (analysis/hlo_audit.py): parsing, policy,
and the end-to-end gate demonstration on an emulated serving mesh."""
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import hlo_audit, roofline
from repro.configs import get_config
from repro.configs.base import SHAPES

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
HLO = (FIXTURES / "collectives.hlo.txt").read_text()
LOOP_HLO = (FIXTURES / "fused_loop.hlo.txt").read_text()


def test_collective_bytes_parser():
    out = roofline.collective_bytes(HLO)
    b = out["bytes"]
    assert b["all-reduce"] == 16 * 4096 * 1024 * 4
    assert b["all-gather"] == 1024 * 128 * 2
    assert b["all-to-all"] == 64 * 256 * 1
    assert b["collective-permute"] == 2 * 2 * 4
    assert b["reduce-scatter"] == 128 * 4
    assert b["total"] == sum(v for k, v in b.items()
                             if k not in ("total", "wire_total"))
    # ring wire model: all-reduce counts twice (RS + AG phases)
    assert b["wire_total"] == b["total"] + b["all-reduce"]
    assert out["counts"]["all-reduce"] == 1


def test_parser_ignores_done_ops_and_noise():
    out = roofline.collective_bytes(HLO)
    # all-gather-done must not double count
    assert out["counts"].get("all-gather") == 1


def test_model_flops_train_vs_decode():
    cfg = get_config("qwen3-0.6b")
    tr = roofline.model_flops(cfg, SHAPES["train_4k"])
    de = roofline.model_flops(cfg, SHAPES["decode_32k"])
    n = cfg.active_param_count()
    assert tr == pytest.approx(6 * n * 4096 * 256)
    assert de == pytest.approx(2 * n * 128)


def test_model_flops_moe_uses_active():
    cfg = get_config("grok-1-314b")
    assert cfg.active_param_count() < cfg.param_count() * 0.5
    tr = roofline.model_flops(cfg, SHAPES["train_4k"])
    assert tr == pytest.approx(6 * cfg.active_param_count() * 4096 * 256)


def test_analyze_on_real_compiled():
    """End-to-end on a tiny real computation (1 device)."""
    cfg = get_config("qwen3-0.6b").reduced()
    f = jax.jit(lambda x: (x @ x).sum())
    comp = f.lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    rep = roofline.analyze(comp, cfg=cfg, shape=SHAPES["train_4k"],
                           mesh_name="t", chips=1)
    assert rep.flops_per_device > 0
    assert rep.compute_s > 0
    assert rep.dominant in ("compute", "memory", "collective")
    d = rep.to_dict()
    assert "roofline_fraction" in d and "step_time_s" in d


# ---------------------------------------------------------------------------
# HLO reshard auditor (analysis/hlo_audit.py)
# ---------------------------------------------------------------------------

def test_audit_computation_split_and_body_closure():
    comps = hlo_audit.computations(LOOP_HLO)
    assert {"fused_computation.1", "body.2", "cond.3",
            "main.10"} <= set(comps)
    bodies = hlo_audit.loop_body_texts(LOOP_HLO)
    assert list(bodies) == ["body.2"]
    # the closure pulls in the fusion the body calls= ...
    assert "collective-permute.9" in bodies["body.2"]
    # ... but not the entry computation around the loop
    assert "all-gather.90" not in bodies["body.2"]


def test_audit_sharded_policy():
    """With model parallelism, the plan predicts all-reduce and tiny
    argmax all-gathers; the cache-pool gather and the permute (hidden
    inside a called fusion) are violations."""
    rep = hlo_audit.audit_hlo(
        LOOP_HLO, hlo_audit.AuditPolicy(model_parallel=2))
    assert rep.n_bodies == 1
    assert rep.counts() == {"all-reduce": 1, "all-gather": 2,
                            "collective-permute": 1}
    assert not rep.ok
    bad = {(op.kind, op.result_bytes) for op, _ in rep.violations}
    assert bad == {("all-gather", 4 * 2 * 32 * 16 * 4),
                   ("collective-permute", 4 * 2 * 4)}
    # the sanctioned ops are present but not violations
    assert rep.copy_count == 1 and rep.copy_bytes == 4 * 64 * 4


def test_audit_unsharded_rejects_all_collectives():
    rep = hlo_audit.audit_hlo(
        LOOP_HLO, hlo_audit.AuditPolicy(model_parallel=1))
    assert len(rep.violations) == 4
    assert all("unsharded" in reason for _, reason in rep.violations)


def test_audit_clean_single_device_body():
    clean = LOOP_HLO
    for op in ("all-reduce.3 = f32[4,64]{1,0} all-reduce",
               "all-gather.4 = f32[1,2]{1,0} all-gather",
               "all-gather.5 = f32[4,2,32,16]{3,2,1,0} all-gather",
               "collective-permute.9 = f32[4,2]{1,0} collective-permute"):
        name, rest = op.split(" = ")
        clean = clean.replace(
            op, name + " = " + rest.replace("-", "_ne_"))
    rep = hlo_audit.audit_hlo(clean,
                              hlo_audit.AuditPolicy(model_parallel=1))
    assert rep.ok and rep.counts() == {}
    assert rep.n_bodies == 1


def test_audit_report_serialises():
    rep = hlo_audit.audit_hlo(
        LOOP_HLO, hlo_audit.AuditPolicy(model_parallel=2))
    d = rep.to_dict()
    assert d["ok"] is False and d["n_loop_bodies"] == 1
    assert d["violations"][0]["kind"] in ("all-gather",
                                          "collective-permute")
    assert "reason" in d["violations"][0]
    assert "hlo-audit" in hlo_audit.format_report(rep)


def test_audit_counts_pool_shaped_selects_and_copies():
    """The mechanism counter: loop-body selects and copies whose result
    has a pool leaf's shape, TPU tiled layouts included, each op once
    however many loop closures share it."""
    bodies = hlo_audit.loop_body_texts(LOOP_HLO)
    assert hlo_audit.pool_shaped_ops(bodies, {(4, 64)}) == \
        {"select": 0, "copy": 1}
    assert hlo_audit.pool_shaped_ops(bodies, {(4, 2, 32, 16)}) == \
        {"select": 0, "copy": 0}      # the all-gather is not counted
    line = ("  %select.1 = bf16[24,8,2048,128]{3,2,1,0:T(8,128)(2,1)} "
            "select(%p, %a, %b)\n")
    assert hlo_audit.pool_shaped_ops({"outer": line, "inner": line},
                                     {(24, 8, 2048, 128)}) == \
        {"select": 1, "copy": 0}
    rep = hlo_audit.audit_hlo(LOOP_HLO,
                              hlo_audit.AuditPolicy(model_parallel=2),
                              pool_shapes={(4, 64)})
    assert (rep.pool_selects, rep.pool_copies) == (0, 1)
    assert rep.to_dict()["pool_copies"] == 1
    assert "pool-shaped selects=0 copies=1" in hlo_audit.format_report(rep)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "h2o-danube-1.8b"])
def test_fused_step_writes_pool_in_place(arch):
    """The contiguous fused decode step writes each active lane's K/V
    token into the carried pool: its loop bodies hold no select and no
    copy with a pool leaf's shape (a whole-pool ``where`` merge, a
    copied carry).  h2o-danube's reduced window (16 rows of a 64-row
    pool) makes its pool a ring."""
    from repro.models import init_caches, init_params
    from repro.serve.decode_loop import make_fused_decode_step

    cfg = get_config(arch).reduced()
    caches = init_caches(cfg, 4, 64, window=cfg.attn_window)
    step = make_fused_decode_step(cfg, window=cfg.attn_window, max_depth=4)
    z = jnp.zeros(4, jnp.int32)
    text = step.lower(init_params(jax.random.PRNGKey(0), cfg), caches,
                      z, z, z).compile().as_text()
    bodies = hlo_audit.loop_body_texts(text)
    assert bodies
    shapes = {tuple(x.shape) for x in jax.tree.leaves(caches)}
    assert hlo_audit.pool_shaped_ops(bodies, shapes) == \
        {"select": 0, "copy": 0}


AUDIT_GATE = """
from repro.analysis import hlo_audit

rc_clean = hlo_audit.main(["--mesh", "4,2"])
assert rc_clean == 0, f"clean mesh audit failed: rc={rc_clean}"
rc_bad = hlo_audit.main(["--mesh", "4,2", "--inject-reshard"])
assert rc_bad == 1, f"injected reshard not caught: rc={rc_bad}"
print("AUDIT_GATE_OK")
"""


def test_audit_gate_on_emulated_mesh(subproc):
    """The CI gate end to end, on the 4x2 host-emulated serving mesh:
    the live fused step audits clean; rebuilding it with the deliberate
    mid-loop reshard (decode_loop._inject_reshard) must fail the audit
    — the pool gathers are cache-row-sized, far over the argmax-lane
    threshold."""
    r = subproc(AUDIT_GATE)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "AUDIT_GATE_OK" in r.stdout
    assert "VIOLATION" in r.stdout          # the injected run printed it
    assert "all-gather" in r.stdout
    # the live step's tokens land in place on the sharded pool too
    assert "pool-shaped selects=0 copies=0" in r.stdout
