"""Training driver.

Real entry point for CPU/TPU runs (reduced configs train end-to-end on
this container; full configs need the real pod):

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --reduced --steps 50 --batch 8 --seq 64 --ckpt /tmp/run1

Features wired here: acc-planned microbatching, fault-tolerant driver
(checkpoint/restart), optional int8-compressed DP, elastic restart.
"""
from __future__ import annotations

import argparse
import time

import jax

from ..configs import ARCH_NAMES, get_config
from ..core import strict
from ..core.adaptive import adaptive
from ..core.executor import MeshExecutor
from ..data import make_batch
from ..models import lm
from ..optim import AdamWConfig, adamw
from ..runtime import FaultTolerantTrainer
from ..train import make_train_step
from . import mesh as mesh_lib
from .compile_cache import place_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_NAMES), required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=None,
                    help="grad-accum microbatches (default: acc decides)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--no-cal-cache", action="store_true",
                    help="do not persist T0/t_iter calibrations to disk")
    ap.add_argument("--cal-cache-dir", default=None,
                    help="calibration cache dir (default: "
                         "$REPRO_CAL_CACHE_DIR or ~/.cache/repro-acc)")
    ap.add_argument("--kernel-autotune", action="store_true",
                    help="measured Pallas blocks for model-layer kernels "
                         "(winners persist in the calibration cache, "
                         "shared with serving)")
    ap.add_argument("--explain-decisions", action="store_true",
                    help="dump the ExecutionModel decision trace: the "
                         "train plan and kernel-block choices with the "
                         "policy and inputs that produced them")
    ap.add_argument("--strict", action="store_true",
                    help="strict runtime mode (same guards as "
                         "REPRO_STRICT=1): the train step runs with "
                         "implicit device->host transfers disallowed")
    args = ap.parse_args()

    if args.strict:
        strict.enable()
    place_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M "
          f"devices={len(jax.devices())}")

    # One in-memory cache view per process: save() rewrites the whole
    # file, so two views over one path would clobber each other's writes.
    from ..core.calibration import CalibrationCache

    cache = CalibrationCache() if args.no_cal_cache \
        else CalibrationCache.persistent(args.cal_cache_dir)

    accum = args.accum
    if accum is None:
        # acc decision over this host's devices
        from ..configs.base import ShapeConfig
        from ..core.acc import AdaptiveCoreChunk
        from ..train.autotune import choose_plan

        mesh = mesh_lib.make_host_mesh()
        # acc rides on the executor; calibrations persist across runs
        mexec = adaptive(MeshExecutor(mesh), AdaptiveCoreChunk(cache=cache))
        shape = ShapeConfig("cli", args.seq, args.batch, "train")
        plan = choose_plan(cfg, shape, mexec)
        accum = plan.accum
        print(f"acc plan: data_parallel={plan.data_parallel} accum={accum} "
              f"(N_C raw {plan.decision.n_cores_unclamped:.1f})")

    opt_cfg = AdamWConfig(lr=args.lr)
    opt_state = adamw.init_state(params)
    if args.kernel_autotune:
        from ..models import flags
        from ..train.autotune import make_kernel_tuner

        # Global flag, read at jit-trace time: the one compiled train
        # step bakes in the measured blocks (same store serving reads).
        flags.KERNEL_TUNER = make_kernel_tuner(cache)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, accum=accum, remat=True))

    def data_iter():
        i = 0
        while True:
            yield make_batch(cfg, args.batch, args.seq, kind="train", seed=i)
            i += 1

    trainer = FaultTolerantTrainer(step_fn, args.ckpt,
                                   save_every=args.save_every)
    t0 = time.time()
    params, opt_state, log = trainer.run(params, opt_state, data_iter(),
                                         num_steps=args.steps)
    dt = time.time() - t0
    for i, m in enumerate(log):
        if i % args.log_every == 0 or i == len(log) - 1:
            print(f"step {i:5d} loss {m['loss']:.4f} "
                  f"gnorm {m['grad_norm']:.3f}")
    tok_s = args.batch * args.seq * len(log) / dt
    print(f"done: {len(log)} steps in {dt:.1f}s ({tok_s:.0f} tok/s)")
    if args.explain_decisions:
        from ..core.model import ExecutionModel

        # The acc plan and any kernel-autotune searches share the engine
        # bound to this process's calibration cache.
        print(ExecutionModel.of(cache).explain())


if __name__ == "__main__":
    main()
