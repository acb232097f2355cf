"""The language model: embedding → layer stack → head, for all assigned
families (dense / MoE / hybrid / SSM / VLM / audio).

The layer stack is organised as ``lax.scan`` over *pattern groups*: the
block pattern (e.g. zamba2's mamba×5 + shared-attn, llama-vision's
attn×3 + cross + attn) repeats every ``period`` layers, so parameters are
stacked over ``n_layers // period`` groups and the group body is compiled
once — essential for 88-layer dry-run compiles.  Leftover layers (when
period ∤ n_layers) run unscanned.

Entry points:
  * ``forward``      — full-sequence (train / one-shot prefill), scan path.
  * ``forward_cached`` — serve path with per-layer caches (KV ring buffers,
    SSM/xLSTM states), python loop over layers.
  * ``forward_step`` — ``forward_cached`` that leaves the caches as they
    are and returns each layer's update; a one-token step's attention
    update is the token it writes (``attention.KVWrite``), for the
    caller to write in place (``commit``, ``decode_loop.masked_merge``).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import attention, common as cm, ffn, flags, mamba2, moe, xlstm

AUX_LOSS_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ArchConfig, kind: str) -> dict:
    kg = cm.KeyGen(key)
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    p: dict[str, Any] = {"ln1": cm.rmsnorm_init(d, dt)}
    if kind == "attn":
        p["attn"] = attention.init(kg(), cfg)
        p["ln2"] = cm.rmsnorm_init(d, dt)
        if cfg.n_experts:
            p["moe"] = moe.init(kg(), cfg)
        elif cfg.d_ff:
            p["ffn"] = ffn.init(kg(), cfg)
    elif kind == "cross_attn":
        p["xattn"] = attention.init(kg(), cfg, cross=True)
        p["ln2"] = cm.rmsnorm_init(d, dt)
        if cfg.d_ff:
            p["ffn"] = ffn.init(kg(), cfg)
    elif kind == "shared_attn":
        # Per-use projection only; the block itself is shared (top level).
        p["proj"] = cm.linear_init(kg(), d, d, dtype=dt)
    elif kind == "mamba2":
        p["mamba"] = mamba2.init(kg(), cfg)
        if cfg.mamba_ffn:
            p["ln2"] = cm.rmsnorm_init(d, dt)
            p["ffn"] = ffn.init(kg(), cfg)
    elif kind == "mlstm":
        p["mlstm"] = xlstm.mlstm_init(kg(), cfg)
    elif kind == "slstm":
        p["slstm"] = xlstm.slstm_init(kg(), cfg)
    else:
        raise ValueError(kind)
    return p


def init_params(key, cfg: ArchConfig) -> dict:
    kg = cm.KeyGen(key)
    dt = jnp.dtype(cfg.param_dtype)
    kinds = cfg.layer_kinds()
    period = len(cfg.block_pattern)
    n_groups = cfg.n_layers // period

    params: dict[str, Any] = {
        "embed": cm.embedding_init(kg(), cfg.vocab_size, cfg.d_model, dt),
        "final_norm": cm.rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = cm.linear_init(kg(), cfg.d_model,
                                           cfg.vocab_size, dtype=dt)
    if "shared_attn" in cfg.block_pattern:
        params["shared_block"] = {
            "ln1": cm.rmsnorm_init(cfg.d_model, dt),
            "attn": attention.init(kg(), cfg),
            "ln2": cm.rmsnorm_init(cfg.d_model, dt),
            "ffn": ffn.init(kg(), cfg),
        }

    # stacked group params: blocks[f"pos{i}"] has leading dim n_groups
    if n_groups:
        blocks = {}
        for i, kind in enumerate(cfg.block_pattern):
            per_group = [_block_init(kg(), cfg, kind) for _ in range(n_groups)]
            blocks[f"pos{i}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *per_group) \
                if n_groups > 1 else jax.tree.map(
                    lambda x: x[None], per_group[0])
        params["blocks"] = blocks
    params["tail"] = [
        _block_init(kg(), cfg, kinds[n_groups * period + j])
        for j in range(cfg.n_layers - n_groups * period)]
    return params


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _residual(x, y, cfg: ArchConfig):
    """``x + y``, the branch ``y`` scaled by the residual multiplier."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def _embed(params, tokens, cfg: ArchConfig):
    """The tokens' embeddings times the embedding multiplier."""
    x = cm.embed(params["embed"], tokens, jnp.dtype(cfg.compute_dtype))
    if cfg.embed_multiplier != 1.0:
        x = x * cfg.embed_multiplier
    return flags.constrain(x)


def _apply_shared(shared: dict, proj: dict, x, cfg: ArchConfig, *,
                  positions, window, impl, cache=None, cache_pos=None,
                  stepwise=False):
    h = cm.rmsnorm(shared["ln1"], x, cfg.norm_eps)
    with jax.named_scope("attention"):
        a, new_cache = attention.self_attention(
            shared["attn"], h, cfg, positions=positions, window=window,
            impl=impl, cache=cache, cache_pos=cache_pos, stepwise=stepwise)
    h = h + a
    h2 = cm.rmsnorm(shared["ln2"], h, cfg.norm_eps)
    with jax.named_scope("mlp"):
        h = h + ffn.apply(shared["ffn"], h2, cfg)
    return x + cm.linear(proj, h, jnp.dtype(cfg.compute_dtype)), new_cache


def _apply_block(kind: str, p: dict, x, cfg: ArchConfig, *,
                 positions, window, impl, shared=None, frontend_feats=None,
                 cache=None, cache_pos=None, stepwise=False, length=None):
    """Returns (x, aux_loss, new_cache).  ``length``: real tokens of a
    padded chunk (recurrent layers leave their state as those left it)."""
    cd = jnp.dtype(cfg.compute_dtype)
    aux = jnp.zeros((), jnp.float32)
    new_cache = None
    if kind == "attn":
        h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
        with jax.named_scope("attention"):
            a, new_cache = attention.self_attention(
                p["attn"], h, cfg, positions=positions, window=window,
                impl=impl, cache=cache, cache_pos=cache_pos,
                stepwise=stepwise)
        x = _residual(x, a, cfg)
        h = cm.rmsnorm(p["ln2"], x, cfg.norm_eps)
        with jax.named_scope("mlp"):
            if cfg.n_experts:
                y, aux = moe.apply(p["moe"], h, cfg)
                x = _residual(x, y, cfg)
            elif cfg.d_ff:
                x = _residual(x, ffn.apply(p["ffn"], h, cfg), cfg)
    elif kind == "cross_attn":
        h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
        feats = frontend_feats
        if feats is None:
            raise ValueError("cross_attn block needs frontend_feats")
        x = _residual(x, attention.cross_attention(
            p["xattn"], h, feats.astype(cd), cfg, impl=impl), cfg)
        h = cm.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if cfg.d_ff:
            x = _residual(x, ffn.apply(p["ffn"], h, cfg), cfg)
    elif kind == "shared_attn":
        x, new_cache = _apply_shared(
            shared, p["proj"], x, cfg, positions=positions,
            window=window, impl=impl, cache=cache, cache_pos=cache_pos,
            stepwise=stepwise)
    elif kind == "mamba2":
        h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
        with jax.named_scope("ssm"):
            y, new_cache = mamba2.apply(p["mamba"], h, cfg, state=cache,
                                        length=length)
        x = _residual(x, y, cfg)
        if cfg.mamba_ffn:
            h = cm.rmsnorm(p["ln2"], x, cfg.norm_eps)
            with jax.named_scope("mlp"):
                x = _residual(x, ffn.apply(p["ffn"], h, cfg), cfg)
    elif kind == "mlstm":
        h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
        y, new_cache = xlstm.mlstm_apply(p["mlstm"], h, cfg, state=cache)
        x = _residual(x, y, cfg)
    elif kind == "slstm":
        h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
        y, new_cache = xlstm.slstm_apply(p["slstm"], h, cfg, state=cache)
        x = _residual(x, y, cfg)
    else:
        raise ValueError(kind)
    return x, aux, new_cache


@jax.named_scope("head")
def _logits(params, cfg: ArchConfig, x):
    cd = jnp.dtype(cfg.compute_dtype)
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].astype(cd).T
    else:
        logits = cm.linear(params["lm_head"], x, cd)
    if cfg.logits_divisor != 1.0:
        logits = logits / cfg.logits_divisor
    return logits


# ---------------------------------------------------------------------------
# full-sequence forward (train / one-shot prefill)
# ---------------------------------------------------------------------------

def forward(params: dict, batch: dict, cfg: ArchConfig, *,
            attn_impl: str = "chunked", window: int | None = None,
            remat: bool = False) -> tuple[jax.Array, jax.Array]:
    """batch: {"tokens": (B, S) int32, optional "frontend_feats"}.
    Returns (logits (B, S, V), aux_loss)."""
    tokens = batch["tokens"]
    bsz, s = tokens.shape
    window = window if window is not None else cfg.attn_window
    x = _embed(params, tokens, cfg)
    positions = jnp.arange(s)
    feats = batch.get("frontend_feats")
    period = len(cfg.block_pattern)

    def group_body(carry, group_params):
        h, aux = carry
        for i, kind in enumerate(cfg.block_pattern):
            h, a, _ = _apply_block(
                kind, group_params[f"pos{i}"], h, cfg,
                positions=positions, window=window, impl=attn_impl,
                shared=params.get("shared_block"), frontend_feats=feats)
            h = flags.constrain(h)
            aux = aux + a
        return (h, aux), None

    body = jax.checkpoint(group_body) if remat else group_body
    aux0 = jnp.zeros((), jnp.float32)
    if "blocks" in params:
        (x, aux), _ = jax.lax.scan(body, (x, aux0), params["blocks"])
    else:
        aux = aux0
    kinds = cfg.layer_kinds()
    n_groups = (cfg.n_layers // period) if "blocks" in params else 0
    for j, p in enumerate(params["tail"]):
        kind = kinds[n_groups * period + j]
        x, a, _ = _apply_block(
            kind, p, x, cfg, positions=positions, window=window,
            impl=attn_impl, shared=params.get("shared_block"),
            frontend_feats=feats)
        x = flags.constrain(x)
        aux = aux + a
    return _logits(params, cfg, x), aux


def loss_fn(params: dict, batch: dict, cfg: ArchConfig, *,
            attn_impl: str = "chunked", remat: bool = False) -> jax.Array:
    logits, aux = forward(params, batch, cfg, attn_impl=attn_impl,
                          remat=remat)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    # SPMD-friendly NLL for vocab-sharded logits: logsumexp and a masked
    # sum both reduce over the sharded vocab dim (psum), no sharded gather.
    lse = jax.nn.logsumexp(lf, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    picked = jnp.sum(jnp.where(vocab_iota == labels[..., None], lf, 0.0),
                     axis=-1)
    return jnp.mean(lse - picked) + AUX_LOSS_WEIGHT * aux


# ---------------------------------------------------------------------------
# cached forward (serve: chunked prefill + decode)
# ---------------------------------------------------------------------------

def _layer_params(params, cfg: ArchConfig, layer: int):
    period = len(cfg.block_pattern)
    n_groups = cfg.n_layers // period if "blocks" in params else 0
    if layer < n_groups * period:
        g, i = divmod(layer, period)
        return jax.tree.map(lambda a: a[g], params["blocks"][f"pos{i}"])
    return params["tail"][layer - n_groups * period]


def forward_cached(params: dict, tokens: jax.Array, caches: list, pos,
                   cfg: ArchConfig, *, window: int | None = None,
                   frontend_feats=None, logit_index=None,
                   all_logits: bool = False, length=None
                   ) -> tuple[jax.Array, list]:
    """tokens: (B, L_new); caches: per-layer state list; pos: scalar count
    of tokens already cached.  Returns (logits of one position, caches):
    the last position by default, or ``logit_index`` (int or traced
    scalar) — the serving scheduler pads prefill chunks to a bucketed
    length and needs the logits of the last *real* token.  With
    ``all_logits`` the head runs over every fed position (``(B, L_new,
    V)``) — the speculative verify needs the model's prediction after
    each draft token in one batched forward, and each position then
    attends as a one-token decode step would, so the two agree token for
    token.  ``length`` (int or traced scalar) counts the real tokens of
    a padded chunk: a recurrent layer's state is left as they left it,
    so the pad tokens change nothing (attention masks them by
    position)."""
    logits, updates = forward_step(
        params, tokens, caches, pos, cfg, window=window,
        frontend_feats=frontend_feats, logit_index=logit_index,
        all_logits=all_logits, length=length)
    return logits, commit(caches, updates)


def commit(caches: list, updates: list, keep=None) -> list:
    """The caches after ``forward_step``'s updates, for the batch rows
    whose ``keep`` is True (every row when None).  A ``KVWrite`` is
    written into its cache in place, row by row; any other update (a
    prefill chunk's cache, a recurrent state) replaces the kept rows."""
    old, tree = jax.tree.flatten(caches)
    new = tree.flatten_up_to(updates)
    writes = [i for i, u in enumerate(new)
              if isinstance(u, attention.KVWrite)]

    def kept(o, u):
        if keep is None:
            return u
        return jnp.where(keep.reshape((-1,) + (1,) * (o.ndim - 1)), u, o)

    out = [o if i in writes else kept(o, u)
           for i, (o, u) in enumerate(zip(old, new, strict=True))]
    if writes:
        rows = jnp.ones(old[writes[0]].shape[0], bool) \
            if keep is None else keep
        written = attention.write_tokens(
            [old[i] for i in writes], [new[i] for i in writes], rows)
        for i, c in zip(writes, written, strict=True):
            out[i] = c
    return tree.unflatten(out)


def forward_step(params: dict, tokens: jax.Array, caches: list, pos,
                 cfg: ArchConfig, *, window: int | None = None,
                 frontend_feats=None, logit_index=None,
                 all_logits: bool = False, length=None
                 ) -> tuple[jax.Array, list]:
    """``forward_cached`` without the cache writes: returns (logits,
    updates).  For a one-token step (``L_new == 1``) an attention layer's
    update is ``{"k": KVWrite, "v": KVWrite}`` — the token and the row it
    goes to — so a caller holding a donated pool writes only that token;
    otherwise (a prefill chunk, a recurrent state) it is the layer's new
    cache or state, as ``forward_cached`` returns it."""
    window = window if window is not None else cfg.attn_window
    x = _embed(params, tokens, cfg)
    l_new = tokens.shape[1]
    positions = pos + jnp.arange(l_new)
    kinds = cfg.layer_kinds()
    updates = []
    for layer, kind in enumerate(kinds):
        p = _layer_params(params, cfg, layer)
        x, _, update = _apply_block(
            kind, p, x, cfg, positions=positions, window=window,
            impl="chunked", shared=params.get("shared_block"),
            frontend_feats=frontend_feats,
            cache=caches[layer], cache_pos=pos, stepwise=all_logits,
            length=length)
        x = flags.constrain(x)
        updates.append(update)
    if all_logits:
        xs = x
    elif logit_index is None:
        xs = x[:, -1:]
    else:
        xs = jax.lax.dynamic_slice_in_dim(x, logit_index, 1, axis=1)
    return _logits(params, cfg, xs), updates


def init_caches(cfg: ArchConfig, batch: int, max_len: int, *,
                window: int | None = None, dtype=None) -> list:
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    window = window if window is not None else cfg.attn_window
    caches: list = []
    for kind in cfg.layer_kinds():
        if kind in ("attn", "shared_attn"):
            caches.append(attention.init_cache(
                cfg, batch, max_len, window=window, dtype=dtype))
        elif kind == "cross_attn":
            caches.append(None)  # image KV recomputed from feats
        elif kind == "mamba2":
            caches.append(mamba2.init_state(cfg, batch, dtype))
        elif kind == "mlstm":
            caches.append(xlstm.mlstm_init_state(cfg, batch))
        elif kind == "slstm":
            caches.append(xlstm.slstm_init_state(cfg, batch))
        else:
            raise ValueError(kind)
    return caches
