"""Compile the Pallas kernels of the serving path for a described TPU v5e
chip, at qwen3-0.6b widths (d 1024, 16/8 heads of 128).

Nothing runs: the TPU compiler builds each kernel for a chip that is
described, not attached, and refuses what the chip would refuse (tiles
that do not divide the layout, scoped VMEM past the kernel limit).  Each
case compiles at a block the planners or the autotuner can pick — the
analytic prior and the largest VMEM-budgeted candidate — and checks that
the program holds the Mosaic kernel (``tpu_custom_call``).  The fused
decode step is compiled too, for what the TPU's layouts do to its loop
body, and at granite-4.0-h-micro's full size for the memory it needs.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis import hlo_audit
from repro.configs import get_config
from repro.kernels import autotune, tuning
from repro.kernels.adjacent_difference import adjacent_difference_pallas
from repro.kernels.artificial_work import artificial_work_pallas
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           paged_flash_attention_pallas)
from repro.kernels.reduce_scan import inclusive_scan_pallas, reduce_sum_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.models import init_caches, init_params
from repro.serve.decode_loop import make_fused_decode_step

D_MODEL, HEAD_DIM, HQ, HKV = 1024, 128, 16, 8
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiles(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [D_MODEL, HEAD_DIM])
@pytest.mark.parametrize("which", [0, 1], ids=["prior", "largest"])
def test_rmsnorm(one_chip, d, which):
    # ops.rmsnorm's prior leads the candidates; the largest is the
    # VMEM budget's cap.
    rows = 8192
    cands = autotune.candidates_1d(rows, bytes_per_elem=d * 2,
                                   arrays_in_vmem=2, align=tuning.SUBLANE,
                                   prior=128)
    block = (cands[0], max(cands))[which]
    _compiles(lambda x, g: rmsnorm_pallas(x, g, block_rows=block,
                                          interpret=False),
              one_chip, ((rows, d), BF16), ((d,), F32))


def _flash_blocks():
    s = 2048
    cands = autotune.candidates_attention(s, s, HEAD_DIM)
    largest = max(cands, key=lambda c: tuning.attention_live_bytes(
        *c, HEAD_DIM, 2))
    return [tuning.plan_attention(s, s, HEAD_DIM), largest]


@pytest.mark.parametrize("which", [0, 1], ids=["prior", "largest"])
def test_flash_attention(one_chip, which):
    bq, bk = _flash_blocks()[which]
    s = 2048
    _compiles(lambda q, k, v: flash_attention_pallas(
        q, k, v, block_q=bq, block_kv=bk, interpret=False), one_chip,
        ((1, HQ, s, HEAD_DIM), BF16), ((1, HKV, s, HEAD_DIM), BF16),
        ((1, HKV, s, HEAD_DIM), BF16))


@pytest.mark.parametrize("sq", [1, 128], ids=["decode", "prefill_chunk"])
def test_paged_flash_attention(one_chip, sq):
    lanes, max_len, ps = 8, 1024, 16
    rows = (1 + lanes * (max_len // ps)) * ps
    _compiles(lambda q, k, v, pt, lens: paged_flash_attention_pallas(
        q, k, v, pt, lens, page_size=ps, interpret=False), one_chip,
        ((lanes, HQ, sq, HEAD_DIM), BF16), ((rows, HKV, HEAD_DIM), BF16),
        ((rows, HKV, HEAD_DIM), BF16), ((lanes, max_len // ps), I32),
        ((lanes,), I32))


_ONE_D = {
    "reduce_sum": (1, lambda b: lambda x: reduce_sum_pallas(
        x, block=b, interpret=False)),
    "inclusive_scan": (2, lambda b: lambda x: inclusive_scan_pallas(
        x, block=b, interpret=False)),
    "adjacent_difference": (3, lambda b: lambda x: adjacent_difference_pallas(
        x, block=b, interpret=False)),
    "artificial_work": (2, lambda b: lambda x: artificial_work_pallas(
        x, iters=16, block=b, interpret=False)),
}


@pytest.mark.parametrize("kernel", sorted(_ONE_D))
@pytest.mark.parametrize("which", ["prior", "largest", "smallest"])
def test_one_d_kernels(one_chip, kernel, which):
    n = 1 << 22
    arrays, make = _ONE_D[kernel]
    cands = autotune.candidates_1d(n, bytes_per_elem=4,
                                   arrays_in_vmem=arrays)
    block = {"prior": cands[0], "largest": max(cands),
             "smallest": min(cands)}[which]
    _compiles(make(block), one_chip, ((n // block * block,), F32))


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_fused_decode_step_writes_pool_in_place(one_chip, dtype):
    """On the chip's layouts too, the fused decode step's loop bodies
    hold no select and no copy with a pool leaf's shape: a token write
    narrower than a layout tile makes the TPU compiler lay the cache out
    by heads and copy the whole pool at every step."""
    cfg = get_config("qwen3-0.6b").reduced()

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    caches = jax.eval_shape(lambda: init_caches(cfg, 4, 256, dtype=dtype))
    lanes = jax.ShapeDtypeStruct((4,), I32, sharding=one_chip)
    text = make_fused_decode_step(cfg, max_depth=4).lower(
        on_chip(params), on_chip(caches), lanes, lanes, lanes
    ).compile().as_text()
    bodies = hlo_audit.loop_body_texts(text)
    assert bodies
    shapes = {tuple(x.shape) for x in jax.tree.leaves(caches)}
    assert hlo_audit.pool_shaped_ops(bodies, shapes) == \
        {"select": 0, "copy": 0}


def test_granite_pool_fits_one_chip(one_chip):
    """granite-4.0-h-micro at its published 40 layers, on the pool of its
    benchmark cell (32 slots x 4,096): the fused decode step and the
    largest prefill bucket compile for a v5e, and the decode step, the
    weights and the pool leave at least 1.5 GiB of its 15.75 GiB free —
    the slot count ``bench/configs/granite-4.0-h-micro.json`` states."""
    from repro.models import lm
    from repro.serve.scheduler import DEFAULT_CHUNK_BUCKETS

    cfg = get_config("granite-4.0-h-micro")
    slots, max_len = 32, 4096

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def ints(shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(lambda: init_caches(cfg, slots, max_len)))
    mem = make_fused_decode_step(cfg).lower(
        params, pool, ints((slots,)), ints((slots,)), ints((slots,))
    ).compile().memory_analysis()
    peak = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert peak <= (15.75 - 1.5) * 2**30, peak / 2**30

    def prefill_chunk(params, row, piece, pos, last):
        return lm.forward_cached(params, piece, row, pos, cfg,
                                 logit_index=last, length=last + 1)

    row = on_chip(jax.eval_shape(lambda: init_caches(cfg, 1, max_len)))
    mem = jax.jit(prefill_chunk).lower(
        params, row, ints((1, DEFAULT_CHUNK_BUCKETS[-1])), ints(()), ints(())
    ).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 2**30
