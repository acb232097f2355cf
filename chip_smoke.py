#!/usr/bin/env python3
"""Serve qwen3-0.6b at its published widths on a TPU through the normal
serving path, and check every emitted token against the model's own
full forward.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # 2x2 serving mesh vs one device

One chip, one process, in phases; any failed phase exits non-zero and the
result line is not printed:

1. device    -- the platform must be ``tpu`` (there is no CPU fallback);
2. kernels   -- every Pallas kernel compiled with Mosaic
                (``interpret=False``) against its jnp oracle;
3. contiguous pool -- 8 requests (prompts of 64-512 tokens, 32 new
                tokens each) into 8 slots, ``max_len`` 1024, fused decode
                loop with ``dispatch_depth="auto"``;
4. paged pool -- the same traffic shape sharing a 128-token prefix; the
                prefix-hit count is printed;
5. kernels on the served path -- phase 3 again with a ``KernelTuner``,
                so the served step's RMSNorms run as compiled Pallas;
                flash attention runs compiled in a teacher-forced
                ``lm.forward(attn_impl="flash")`` over the same sequences.

Weights are random (``lm.init_params`` from ``--seed``), held in an
in-memory ``CalibrationCache``; strict mode is on.  The reference for an
emitted token is one teacher-forced, no-cache ``lm.forward`` over prompt
plus generated tokens on the same chip: the emitted token's reference
logit must lie within ``TOP1_TOL`` of the reference maximum.  Random
weights give near-ties and bf16 sums run in another order in the cached
and the full forward, so argmax identity is not the test.  This is the
model's own forward, not an independent float32 reference.

``--four-chips`` runs only the mesh comparison: fused serving on a
``make_serve_mesh(2, 2)`` mesh (2 replicas x 2-way tensor parallel)
against the single-device fused path on the same prompts.  Prefill logits
of the tensor-parallel weights must agree with the single-device ones
within ``MESH_LOGIT_MEAN_TOL`` / ``MESH_LOGIT_MAX_TOL``, the mesh-served
tokens must pass the reference check, and the greedy-token agreement
between the two servers is printed.

Numbers printed before the last line (compile seconds, tokens/s) are
information, not measurements of record.  The last line of standard
output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
SRC = REPO / "src"

ARCH = "qwen3-0.6b"
N_SLOTS = 8
N_REQUESTS = 8
MAX_LEN = 1024
NEW_TOKENS = 32
PROMPT_MIN, PROMPT_MAX = 64, 512
SHARED_PREFIX = 128

# Reference logit gap allowed for an emitted token: 8 bf16 spacings at
# the magnitude of these logits (|logit| in [2, 4): spacing 2^-6).
TOP1_TOL = 0.125
# Tensor-parallel against single-device prefill logits (the last prompt
# position, every vocab entry).  A wrong shard order moves logits by
# their own scale (~0.6 here), far past both bounds.
MESH_LOGIT_MEAN_TOL = 0.05
MESH_LOGIT_MAX_TOL = 0.5


class PhaseError(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


# ---------------------------------------------------------------- compiles
class CompileClock:
    """Seconds JAX spends in backend compilation (a persistent-cache hit
    is counted at the cost of its read), and persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.hits, self.requests

    def since(self, snap) -> str:
        s, h, r = snap
        return (f"{self.seconds - s:.1f}s compiling "
                f"({self.hits - h} of {self.requests - r} persistent-cache "
                f"hits)")


# ---------------------------------------------------------------- traffic
def make_prompts(vocab: int, seed: int, *, shared_prefix: int = 0):
    """N_REQUESTS prompts with lengths drawn from [PROMPT_MIN, PROMPT_MAX];
    with ``shared_prefix`` every prompt starts with the same tokens."""
    import numpy as np

    rng = np.random.RandomState(seed)
    lo = max(PROMPT_MIN, shared_prefix + 1)
    lens = rng.randint(lo, PROMPT_MAX + 1, size=N_REQUESTS)
    prefix = rng.randint(0, vocab, size=shared_prefix)
    return [np.concatenate([prefix, rng.randint(0, vocab, size=n - len(prefix))])
            .astype(np.int32) for n in lens]


def serve(sched, waves) -> tuple[list[list[int]], float]:
    """Submit each wave of prompts and run the scheduler idle before the
    next; returns the emitted tokens per prompt and the serving seconds."""
    rids = []
    t0 = time.perf_counter()
    for wave in waves:
        rids += [sched.submit(p, max_new_tokens=NEW_TOKENS) for p in wave]
        sched.run_until_idle()
    dt = time.perf_counter() - t0
    outs = sched.results()
    return [outs[r] for r in rids], dt


# --------------------------------------------------------------- reference
def make_reference(cfg, attn_impl: str = "chunked"):
    """jit'd teacher-forced check: one full no-cache ``lm.forward`` over
    prompt + emitted tokens (padded to MAX_LEN; causal, so the padding
    never reaches a checked position).  Returns, per emitted token, the
    gap between the reference maximum and the token's reference logit,
    and whether the token is the reference argmax."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm

    @jax.jit
    def gaps(params, toks, start, served):
        logits, _ = lm.forward(params, {"tokens": toks}, cfg,
                               attn_impl=attn_impl)
        rows = jax.lax.dynamic_slice_in_dim(
            logits[0], start, served.shape[0], axis=0).astype(jnp.float32)
        got = jnp.take_along_axis(rows, served[:, None], axis=1)[:, 0]
        return jnp.max(rows, axis=-1) - got, jnp.argmax(rows, axis=-1) == served

    def run(params, prompts, outs) -> tuple[float, int, int]:
        import numpy as np

        worst, exact, total = 0.0, 0, 0
        for i, (prompt, out) in enumerate(zip(prompts, outs, strict=True)):
            check(len(out) == NEW_TOKENS,
                  f"request {i}: {len(out)} tokens emitted, want {NEW_TOKENS}")
            seq = np.zeros((1, MAX_LEN), np.int32)
            seq[0, :len(prompt)] = prompt
            seq[0, len(prompt):len(prompt) + NEW_TOKENS - 1] = out[:-1]
            gap, top = jax.device_get(gaps(
                params, jnp.asarray(seq), jnp.int32(len(prompt) - 1),
                jnp.asarray(out, jnp.int32)))
            check(bool(np.all(np.isfinite(gap))),
                  f"request {i}: non-finite reference logits")
            bad = np.flatnonzero(gap > TOP1_TOL)
            check(bad.size == 0,
                  f"request {i}: emitted token {bad[0] if bad.size else -1} "
                  f"is {float(gap.max()):.4f} below the reference top-1 "
                  f"(tolerance {TOP1_TOL})")
            worst = max(worst, float(gap.max()))
            exact += int(top.sum())
            total += len(out)
        return worst, exact, total

    return run


def report_reference(name: str, result) -> None:
    worst, exact, total = result
    print(f"  {name}: reference check passed: {exact}/{total} tokens are "
          f"the reference argmax, worst gap {worst:.4f} "
          f"(tolerance {TOP1_TOL})")


# ------------------------------------------------------------------ phases
def new_scheduler(cfg, params, **kw):
    from repro.core.acc import AdaptiveCoreChunk
    from repro.core.adaptive import adaptive
    from repro.core.calibration import CalibrationCache
    from repro.core.executor import SequentialExecutor
    from repro.serve import ServeScheduler

    acc = AdaptiveCoreChunk(cache=CalibrationCache())   # in memory only
    return ServeScheduler(cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN,
                          executor=adaptive(SequentialExecutor(), acc),
                          dispatch_depth="auto", **kw)


def phase_serve(name, cfg, params, clock, waves, reference, **kw):
    sched = new_scheduler(cfg, params, **kw)
    snap = clock.snapshot()
    t0 = time.perf_counter()
    sched.warmup()
    print(f"  {name}: warmup {time.perf_counter() - t0:.1f}s, "
          f"{clock.since(snap)}")
    prompts = [p for wave in waves for p in wave]
    outs, dt = serve(sched, waves)
    gen = sum(len(o) for o in outs)
    print(f"  {name}: {gen} tokens for {len(prompts)} requests in "
          f"{dt:.2f}s ({gen / dt:.1f} tok/s, {sched.decode_dispatches} "
          f"fused dispatches, {len(sched.trace)} ticks)")
    report_reference(name, reference(params, prompts, outs))
    return sched, outs


def phase_kernels() -> None:
    """Each Pallas kernel compiled for the chip against its jnp oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels import ref as R
    from repro.kernels.flash_attention import paged_flash_attention_pallas

    key = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def close(name, got, want, tol):
        """Max error, relative to the oracle's largest magnitude (>= 1)."""
        want = want.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
            / max(1.0, float(jnp.max(jnp.abs(want))))
        check(err <= tol, f"{name}: relative max error {err:.3g} > {tol}")
        return f"{name} {err:.1e}"

    x = jax.random.normal(next(key), (1 << 20,), jnp.float32)
    rows = [close("reduce_sum", ops.reduce_sum(x), R.reduce_sum_ref(x), 1e-5),
            close("inclusive_scan", ops.inclusive_scan(x),
                  R.inclusive_scan_ref(x), 1e-5),
            close("adjacent_difference", ops.adjacent_difference(x),
                  R.adjacent_difference_ref(x), 1e-6),
            close("artificial_work", ops.artificial_work(x, iters=64),
                  R.artificial_work_ref(x, 64), 1e-5)]
    h = jax.random.normal(next(key), (256, 1024), jnp.bfloat16)
    g = jax.random.normal(next(key), (1024,), jnp.float32)
    rows.append(close("rmsnorm", ops.rmsnorm(h, g), R.rmsnorm_ref(h, g),
                      1e-2))
    q = jax.random.normal(next(key), (1, 16, 512, 128), jnp.bfloat16)
    k = jax.random.normal(next(key), (1, 8, 512, 128), jnp.bfloat16)
    v = jax.random.normal(next(key), (1, 8, 512, 128), jnp.bfloat16)
    want = R.attention_ref(q, k, v, causal=True)
    rows.append(close("flash_attention", ops.flash_attention(q, k, v),
                      want, 2e-2))
    # The same K/V scattered into shuffled 16-token pages.
    ps, n_blocks = 16, 512 // 16
    perm = np.random.RandomState(0).permutation(n_blocks) + 1
    kp = jnp.zeros(((n_blocks + 1) * ps, 8, 128), jnp.bfloat16)
    vp = jnp.zeros_like(kp)
    for j, page in enumerate(perm):
        kp = kp.at[page * ps:(page + 1) * ps].set(
            k[0, :, j * ps:(j + 1) * ps].transpose(1, 0, 2))
        vp = vp.at[page * ps:(page + 1) * ps].set(
            v[0, :, j * ps:(j + 1) * ps].transpose(1, 0, 2))
    table = jnp.asarray(perm, jnp.int32)[None]
    lens = jnp.asarray([512], jnp.int32)
    rows.append(close("paged_flash_attention (prefill 128)",
                      paged_flash_attention_pallas(
                          q[:, :, -128:], kp, vp, table, lens, page_size=ps),
                      want[:, :, -128:], 2e-2))
    rows.append(close("paged_flash_attention (decode)",
                      paged_flash_attention_pallas(
                          q[:, :, -1:], kp, vp, table, lens, page_size=ps),
                      want[:, :, -1:], 2e-2))
    print("  compiled kernels vs oracles (max error): " + ", ".join(rows))


def run_one_chip(cfg, params, clock, seed: int) -> None:
    from repro.core.calibration import CalibrationCache
    from repro.kernels.autotune import KernelTuner
    from repro.models import flags

    print("phase kernels")
    phase_kernels()

    reference = make_reference(cfg)
    prompts = make_prompts(cfg.vocab_size, seed)
    print("phase contiguous pool")
    sched, _ = phase_serve("contiguous", cfg, params, clock, [prompts],
                           reference)
    costs = sched.decode_cost_analysis()
    print(f"  contiguous: decode step per loop iteration "
          f"{costs['flops_per_device'] / 1e9:.2f} GFLOP, "
          f"{costs['hbm_bytes_per_device'] / 1e9:.3f} GB accessed "
          f"(XLA cost analysis)")
    del sched

    print("phase paged pool, shared prefix")
    shared = make_prompts(cfg.vocab_size, seed + 1,
                          shared_prefix=SHARED_PREFIX)
    # The first request publishes the prefix; the rest arrive after it.
    sched, _ = phase_serve("paged", cfg, params, clock,
                           [shared[:1], shared[1:]], reference, paged=True)
    st = sched.pool.prefix_stats()
    print(f"  paged: page_size {st['page_size']}, prefix hits "
          f"{st['prefix_hits']}/{st['prefix_lookups']}, "
          f"{st['prefill_tokens_avoided']} prefill tokens avoided, "
          f"{st['cow_copies']} copy-on-write pages")
    check(st["prefix_hits"] >= N_REQUESTS - 1,
          f"paged: {st['prefix_hits']} prefix hits, want "
          f"{N_REQUESTS - 1}")
    del sched

    print("phase kernels on the served path")
    tuner = KernelTuner(CalibrationCache(), repeats=2)
    sched, outs = phase_serve("kernel-tuned", cfg, params, clock,
                              [prompts], reference, kernel_tuner=tuner)
    check(tuner.searches + tuner.cache_hits > 0,
          "kernel-tuned: no Pallas kernel was planned into the served step")
    print(f"  kernel-tuned: {tuner.searches} measured block searches "
          f"(rmsnorm as compiled Pallas in prefill and decode)")
    del sched
    with flags.kernel_tuner(tuner):
        flash_ref = make_reference(cfg, attn_impl="flash")
        report_reference("flash forward (Pallas flash attention + rmsnorm)",
                         flash_ref(params, prompts, outs))


def run_four_chips(cfg, params, clock, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_serve_mesh
    from repro.models import lm

    print("phase mesh 2x2 against one device")
    mesh = make_serve_mesh(2, 2)
    prompts = make_prompts(cfg.vocab_size, seed)
    reference = make_reference(cfg)
    mesh_sched, mesh_outs = phase_serve("mesh 2x2", cfg, params, clock,
                                        [prompts], reference, mesh=mesh)
    one_sched, one_outs = phase_serve("one device", cfg, params, clock,
                                      [prompts], reference)
    same = sum(int(a == b) for x, y in zip(mesh_outs, one_outs, strict=True)
               for a, b in zip(x, y, strict=True))
    print(f"  greedy tokens identical between mesh and one device: "
          f"{same}/{N_REQUESTS * NEW_TOKENS}")

    @jax.jit
    def last_logits(p, toks):
        return lm.forward(p, {"tokens": toks}, cfg)[0][0, -1].astype(
            jnp.float32)

    worst_mean = worst_max = 0.0
    for i, prompt in enumerate(prompts):
        toks = jnp.asarray(prompt)[None]
        a = last_logits(mesh_sched.params, toks)
        b = last_logits(one_sched.params, toks)
        d = np.abs(np.asarray(jax.device_get(a - b)))
        check(d.mean() <= MESH_LOGIT_MEAN_TOL and d.max() <= MESH_LOGIT_MAX_TOL,
              f"request {i}: mesh prefill logits differ by mean "
              f"{d.mean():.4f} / max {d.max():.4f} (tolerance "
              f"{MESH_LOGIT_MEAN_TOL} / {MESH_LOGIT_MAX_TOL})")
        worst_mean = max(worst_mean, float(d.mean()))
        worst_max = max(worst_max, float(d.max()))
    print(f"  prefill logits, tensor-parallel vs one device: worst mean "
          f"|diff| {worst_mean:.4f}, worst max |diff| {worst_max:.4f} "
          f"(tolerance {MESH_LOGIT_MEAN_TOL} / {MESH_LOGIT_MAX_TOL})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 2x2 mesh serving comparison (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the prompts")
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: FAIL: the repro package is not at {SRC}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"phase device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU found (JAX platform "
              f"{dev.platform!r}); this script does not fall back to the CPU",
              file=sys.stderr)
        return 1
    from repro.kernels.tuning import resolve_interpret

    if resolve_interpret(None):
        print("chip_smoke: FAIL: Pallas kernels would run in the "
              "interpreter on this backend", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: FAIL: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.core import strict
    from repro.launch.compile_cache import place_compile_cache
    from repro.models import lm

    strict.enable()
    print(f"phase compile cache: {place_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        cfg = get_config(ARCH)
        params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        print(f"model {cfg.name}: {cfg.n_layers} layers, d {cfg.d_model}, "
              f"{cfg.n_heads}/{cfg.n_kv_heads} heads of {cfg.head_dim_}, "
              f"vocab {cfg.vocab_size}, {n_params / 1e6:.1f}M params "
              f"({cfg.param_dtype} weights, {cfg.compute_dtype} compute)")
        if args.four_chips:
            run_four_chips(cfg, params, clock, args.seed)
        else:
            run_one_chip(cfg, params, clock, args.seed)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAIL", file=sys.stderr)
        return 1
    print(f"total {time.perf_counter() - t0:.1f}s, {clock.seconds:.1f}s "
          f"compiling, {clock.hits}/{clock.requests} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
