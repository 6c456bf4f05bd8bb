"""Model assembly: decoder stacks for every assigned architecture family.

One parameterized decoder covers dense / MoE / hybrid (Jamba) / SSM (xLSTM)
/ audio / VLM families.  Layers are grouped into *periods* (the repeating
block pattern: 1 for homogeneous stacks, 8 for Jamba's 7-Mamba+1-attention,
4 for xLSTM's 3-mLSTM+1-sLSTM) and the period stack is executed with
``jax.lax.scan`` over stacked parameters — keeping HLO size (and hence
dry-run compile time and SPMD partitioning cost) independent of depth —
with optional rematerialization.

All model code is mesh-agnostic: distribution happens through pjit sharding
constraints (runtime/sharding.py) plus the ``ExecContext`` islands.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .attention import (attn_apply, attn_cache_init, attn_decode,
                        attn_decode_paged, attn_init, attn_paged_cache_init,
                        attn_prefill, attn_prefill_paged)
from .context import ExecContext
from .layers import (chunked_lm_loss, cross_entropy, dense, dense_init,
                     embed, embed_init, mlp_apply, mlp_init, rmsnorm,
                     rmsnorm_init)
from .moe import moe_apply, moe_init
from .ssm import mamba_apply, mamba_cache_init, mamba_decode, mamba_init
from .xlstm import (mlstm_apply, mlstm_cache_init, mlstm_decode, mlstm_init,
                    slstm_apply, slstm_cache_init, slstm_decode, slstm_init)

__all__ = ["period_length", "block_kinds", "init_params", "forward",
           "loss_fn", "init_cache", "init_paged_cache", "decode_step",
           "prefill_forward", "supports_cached_prefill",
           "supports_paged_cache"]

AUX_LOSS_WEIGHT = 0.01


# --------------------------------------------------------------------- #
# block pattern
# --------------------------------------------------------------------- #
def period_length(cfg: ModelConfig) -> int:
    if cfg.family == "ssm":
        return max(cfg.slstm_every, 1)
    if cfg.attn_every > 0:
        import math
        return math.lcm(cfg.attn_every, cfg.moe_every)
    return cfg.moe_every if cfg.num_experts > 0 else 1


def block_kinds(cfg: ModelConfig) -> list[tuple[str, str]]:
    """Per-layer (mixer, ffn) kinds for one period."""
    P = period_length(cfg)
    kinds = []
    for j in range(P):
        if cfg.family == "ssm":
            mixer = "slstm" if j % cfg.slstm_every == cfg.slstm_every - 1 \
                else "mlstm"
        elif cfg.attn_every > 0:
            mixer = "attn" if j % cfg.attn_every == cfg.attn_offset else "mamba"
        else:
            mixer = "attn"
        if cfg.d_ff == 0:
            ffn = "none"
        elif cfg.num_experts > 0 and j % cfg.moe_every == cfg.moe_every - 1:
            ffn = "moe"
        else:
            ffn = "dense"
        kinds.append((mixer, ffn))
    return kinds


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #
def _init_sub(rng, cfg: ModelConfig, mixer: str, ffn: str):
    r1, r2, r3 = jax.random.split(rng, 3)
    sub: dict[str, Any] = {"norm1": rmsnorm_init(cfg.d_model)}
    if mixer == "attn":
        sub["attn"] = attn_init(r1, cfg)
    elif mixer == "mamba":
        sub["mamba"] = mamba_init(r1, cfg.d_model, expand=cfg.mamba_expand,
                                  d_state=cfg.mamba_d_state,
                                  d_conv=cfg.mamba_d_conv)
    elif mixer == "mlstm":
        sub["mlstm"] = mlstm_init(r1, cfg.d_model, cfg.num_heads,
                                  expand=cfg.mamba_expand)
    elif mixer == "slstm":
        sub["slstm"] = slstm_init(r1, cfg.d_model)
    if ffn != "none":
        sub["norm2"] = rmsnorm_init(cfg.d_model)
        if ffn == "moe":
            sub["moe"] = moe_init(r2, cfg.d_model, cfg.d_ff, cfg.num_experts,
                                  cfg.mlp)
        else:
            sub["mlp"] = mlp_init(r2, cfg.d_model, cfg.d_ff, cfg.mlp)
    return sub


def init_params(rng, cfg: ModelConfig):
    kinds = block_kinds(cfg)
    P = period_length(cfg)
    n_periods = cfg.num_layers // P
    assert cfg.num_layers % P == 0, (cfg.num_layers, P)

    r_embed, r_layers, r_head = jax.random.split(rng, 3)

    def init_period(r):
        rs = jax.random.split(r, len(kinds))
        return {f"sub_{j}": _init_sub(rs[j], cfg, *kinds[j])
                for j in range(len(kinds))}

    period_rngs = jax.random.split(r_layers, n_periods)
    layers = jax.vmap(init_period)(period_rngs)

    params: dict[str, Any] = {"layers": layers,
                              "final_norm": rmsnorm_init(cfg.d_model)}
    if cfg.family != "audio":
        params["embed"] = embed_init(r_embed, cfg.vocab_size, cfg.d_model)
    if not cfg.tie_embeddings or cfg.family == "audio":
        params["lm_head"] = dense_init(r_head, cfg.d_model, cfg.vocab_size)
    # params are *stored* in the compute dtype (bf16 in production): the
    # FSDP all-gather then moves half the bytes (§Perf iteration 1); the
    # fp32 master lives in the optimizer state (optim/adamw.py).
    dtype = jnp.dtype(cfg.dtype)
    if dtype != jnp.float32:
        params = jax.tree.map(lambda p: p.astype(dtype), params)
    return params


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #
def inputs_to_embeds(params, cfg: ModelConfig, batch):
    dtype = jnp.dtype(cfg.dtype)
    if cfg.frontend == "audio_frames":
        return batch["frame_embeds"].astype(dtype)
    x = embed(params["embed"], batch["tokens"], dtype)
    if cfg.frontend == "vit_patches":
        x = jnp.where(batch["patch_mask"][..., None],
                      batch["patch_embeds"].astype(dtype), x)
    return x


def _apply_sub(sub, cfg: ModelConfig, ctx: ExecContext, x, mixer: str,
               ffn: str):
    h = rmsnorm(sub["norm1"], x, cfg.norm_eps)
    if mixer == "attn":
        with jax.named_scope("attention"):
            mx = attn_apply(sub["attn"], cfg, ctx, h)
    elif mixer == "mamba":
        mx = mamba_apply(sub["mamba"], h, ctx, d_state=cfg.mamba_d_state,
                         d_conv=cfg.mamba_d_conv)
    elif mixer == "mlstm":
        mx = mlstm_apply(sub["mlstm"], h, ctx, num_heads=cfg.num_heads)
    else:
        mx = slstm_apply(sub["slstm"], h, ctx)
    x = x + mx
    aux = jnp.zeros((), jnp.float32)
    if ffn != "none":
        h = rmsnorm(sub["norm2"], x, cfg.norm_eps)
        if ffn == "moe":
            f, aux = moe_apply(sub["moe"], h, ctx, top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor,
                               kind=cfg.mlp)
        else:
            with jax.named_scope("mlp"):
                f = mlp_apply(sub["mlp"], h, cfg.mlp)
        x = x + f
    return x, aux


def _trunk(params, cfg: ModelConfig, ctx: ExecContext, batch,
           *, remat: bool = True):
    """batch -> (final hidden states (B, T, d_model), aux_loss scalar).

    Remat policy: the residual stream between sublayers is saved; each
    sublayer's interior (attention logits, SSM scan operands, expert
    buffers) is rematerialized in the backward pass — peak memory is the
    *max* over sublayers rather than the sum over a period (critical for
    Jamba's 7-Mamba periods whose scan operands are large).
    """
    kinds = block_kinds(cfg)
    x = ctx.constrain(inputs_to_embeds(params, cfg, batch))

    def sub_fn(j):
        mixer, ffn = kinds[j]

        def apply(sub, x):
            y, a = _apply_sub(sub, cfg, ctx, x, mixer, ffn)
            return ctx.constrain(y), a

        return jax.checkpoint(apply) if remat else apply

    sub_fns = [sub_fn(j) for j in range(len(kinds))]

    def period_body(carry, period_params):
        x, aux = carry
        for j in range(len(kinds)):
            x, a = sub_fns[j](period_params[f"sub_{j}"], x)
            aux = aux + a
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(period_body,
                               (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    return x, aux


def _logits(params, cfg: ModelConfig, x):
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if "lm_head" in params:
        return dense(params["lm_head"], x)
    return x @ params["embed"]["e"].T.astype(x.dtype)


def forward(params, cfg: ModelConfig, ctx: ExecContext, batch,
            *, remat: bool = True):
    """batch -> (logits (B, T, vocab), aux_loss scalar)."""
    x, aux = _trunk(params, cfg, ctx, batch, remat=remat)
    return _logits(params, cfg, x), aux


def loss_fn(params, cfg: ModelConfig, ctx: ExecContext, batch,
            *, remat: bool = True):
    x, aux = _trunk(params, cfg, ctx, batch, remat=remat)
    with jax.named_scope("head_loss"):
        ce = cross_entropy(_logits(params, cfg, x), batch["labels"])
    return ce + AUX_LOSS_WEIGHT * aux, {"ce": ce, "aux": aux}


def loss_fn_chunked_head(params, cfg: ModelConfig, ctx: ExecContext, batch,
                         *, remat: bool = True, chunk: int = 128):
    """Loss with the fused chunked head (local/unsharded execution only:
    under CP the token axis is mesh-sharded and the logits are already
    distributed — see EXPERIMENTS.md §Perf iteration 3)."""
    x, aux = _trunk(params, cfg, ctx, batch, remat=remat)
    with jax.named_scope("head_loss"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        head = params["lm_head"]["w"] if "lm_head" in params \
            else params["embed"]["e"].T
        ce = chunked_lm_loss(x, head, batch["labels"], chunk=chunk)
    return ce + AUX_LOSS_WEIGHT * aux, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------- #
# prefill: forward pass that writes the KV cache directly
# --------------------------------------------------------------------- #
def supports_cached_prefill(cfg: ModelConfig) -> bool:
    """Cache-writing prefill needs every mixer to be attention (KV caches
    are written by position; recurrent mixers would need final-state
    extraction from the scan — those archs fall back to replay prefill)."""
    return all(mixer == "attn" for mixer, _ in block_kinds(cfg))


def supports_paged_cache(cfg: ModelConfig) -> bool:
    """Paged KV needs position-addressed caches in every mixer — i.e.
    attention-only stacks.  Recurrent mixers (Mamba/xLSTM) carry dense
    per-slot scan states that have no block structure; those archs keep
    the dense slot-stripe layout."""
    return supports_cached_prefill(cfg)


def prefill_forward(params, cfg: ModelConfig, cache, batch, pos, active,
                    *, with_logits: bool = True, block_tables=None,
                    block_size: int = 0, view_blocks: int = 0):
    """Forward one prompt chunk and write its KV into the cache in the
    same pass — no prompt replay through ``decode_step``.

    batch: the usual forward inputs for a (B, T) chunk ("tokens" or
    "frame_embeds"/"patch_*").  pos (B, T) int32: global cache positions
    of the chunk tokens.  active (B, T) bool: which tokens are real
    (False = padding past a short prompt or an idle slot — they neither
    write the cache nor influence outputs).  Returns (logits (B, T,
    vocab) or None, new cache).  Chunked calls with increasing ``pos``
    windows implement chunked prefill: each chunk attends the full
    cached prefix.  ``with_logits=False`` skips the lm_head — only the
    final chunk's last token ever feeds sampling, so earlier chunks
    need not pay the (T, vocab) projection.

    ``block_tables`` (B, nk) switches to the paged pool layout
    (``init_paged_cache``): chunk KV is scattered at table-resolved
    physical positions and attention runs over the request's gathered
    logical prefix of ``view_blocks`` blocks (``block_size`` tokens
    each) — see :func:`repro.models.attention.attn_prefill_paged`.

    MoE routing runs *drop-free* (capacity lifted to the chunk size):
    the decode path routes one token per step and never drops, so a
    capacity-clipped prefill would write KV inconsistent with the cache
    the decode path builds (the PR-3 root cause of the old decode-vs-
    forward xfail, now on the serving side).
    """
    assert supports_cached_prefill(cfg), \
        f"{cfg.name}: cache-writing prefill requires attention-only mixers"
    kinds = block_kinds(cfg)
    x = inputs_to_embeds(params, cfg, batch)
    # cap >= n for any routing needs capacity_factor >= E / top_k
    drop_free_cf = max(cfg.capacity_factor,
                       float(cfg.num_experts) / max(cfg.top_k, 1)) \
        if cfg.num_experts else cfg.capacity_factor

    def period_body(carry, scanned):
        x = carry
        period_params, period_cache = scanned
        new_cache = {}
        for j, (mixer, ffn) in enumerate(kinds):
            sub = period_params[f"sub_{j}"]
            h = rmsnorm(sub["norm1"], x, cfg.norm_eps)
            if block_tables is not None:
                mx, nc = attn_prefill_paged(
                    sub["attn"], cfg, h, pos, period_cache[f"sub_{j}"],
                    active, block_tables, block_size=block_size,
                    view_blocks=view_blocks)
            else:
                mx, nc = attn_prefill(sub["attn"], cfg, h, pos,
                                      period_cache[f"sub_{j}"], active)
            new_cache[f"sub_{j}"] = nc
            x = x + mx
            if ffn != "none":
                h = rmsnorm(sub["norm2"], x, cfg.norm_eps)
                if ffn == "moe":
                    f, _ = moe_apply(sub["moe"], h, None, top_k=cfg.top_k,
                                     capacity_factor=drop_free_cf,
                                     kind=cfg.mlp)
                else:
                    f = mlp_apply(sub["mlp"], h, cfg.mlp)
                x = x + f
        return x, new_cache

    x, new_cache = jax.lax.scan(period_body, x, (params["layers"], cache))
    if not with_logits:
        return None, new_cache
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if "lm_head" in params:
        logits = dense(params["lm_head"], x)
    else:
        logits = x @ params["embed"]["e"].T.astype(x.dtype)
    return logits, new_cache


# --------------------------------------------------------------------- #
# decode
# --------------------------------------------------------------------- #
def _sub_cache_init(cfg: ModelConfig, mixer: str, batch: int, max_len: int,
                    dtype):
    if mixer == "attn":
        return attn_cache_init(cfg, batch, max_len, dtype)
    if mixer == "mamba":
        return mamba_cache_init(batch, cfg.d_model, expand=cfg.mamba_expand,
                                d_state=cfg.mamba_d_state,
                                d_conv=cfg.mamba_d_conv, dtype=dtype)
    if mixer == "mlstm":
        return mlstm_cache_init(batch, cfg.d_model, cfg.num_heads,
                                expand=cfg.mamba_expand, dtype=dtype)
    return slstm_cache_init(batch, cfg.d_model, dtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    kinds = block_kinds(cfg)
    P = period_length(cfg)
    n_periods = cfg.num_layers // P
    period = {f"sub_{j}": _sub_cache_init(cfg, kinds[j][0], batch, max_len,
                                          dtype)
              for j in range(len(kinds))}
    return jax.tree.map(
        lambda a: jnp.zeros((n_periods,) + a.shape, a.dtype), period)


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int):
    """Global paged KV pool: per attention sub-layer
    (num_kv_heads, num_blocks * block_size, head_dim) — no slot axis.
    Requires :func:`supports_paged_cache` (attention-only mixers)."""
    assert supports_paged_cache(cfg), \
        f"{cfg.name}: paged KV requires attention-only mixers"
    dtype = jnp.dtype(cfg.dtype)
    kinds = block_kinds(cfg)
    P = period_length(cfg)
    n_periods = cfg.num_layers // P
    period = {f"sub_{j}": attn_paged_cache_init(cfg, num_blocks,
                                                block_size, dtype)
              for j in range(len(kinds))}
    return jax.tree.map(
        lambda a: jnp.zeros((n_periods,) + a.shape, a.dtype), period)


def decode_step(params, cfg: ModelConfig, cache, batch, pos_t, *,
                attn_impl: str = "flash", attn_shards: int = 1,
                block_k: int = 256, interpret: bool | None = None,
                block_tables=None, block_size: int = 0,
                write_mask=None):
    """One decode step.

    batch: {"tokens": (B,) int32} (or {"frame_embeds": (B, d)} for audio).
    pos_t: (B,) int32 current positions.  Returns (logits (B, vocab),
    new cache).

    ``attn_impl`` picks the decode attention: ``"flash"`` (default) is
    the fused flash-decode kernel with the cache split into
    ``attn_shards`` LSE-merged segments; ``"dense"`` the XLA softmax
    oracle (see :func:`repro.models.attention.attn_decode`).

    ``block_tables`` (B, nk) switches to the paged pool layout: the new
    token's KV scatters at its table-resolved physical position and
    attention indirects through the table (``attn_decode_paged``);
    ``write_mask`` (B,) bool drops pool writes for idle / prefilling
    rows (the pool has no row axis to mask after the fact).
    """
    dtype = jnp.dtype(cfg.dtype)
    kinds = block_kinds(cfg)
    if cfg.frontend == "audio_frames":
        x = batch["frame_embeds"].astype(dtype)
    else:
        x = embed(params["embed"], batch["tokens"], dtype)

    def period_body(x, scanned):
        period_params, period_cache = scanned
        new_cache = {}
        for j, (mixer, ffn) in enumerate(kinds):
            sub = period_params[f"sub_{j}"]
            c = period_cache[f"sub_{j}"]
            h = rmsnorm(sub["norm1"], x[:, None], cfg.norm_eps)[:, 0]
            if mixer == "attn" and block_tables is not None:
                mx, nc = attn_decode_paged(
                    sub["attn"], cfg, h, pos_t, c, block_tables,
                    write_mask, impl=attn_impl, block_size=block_size,
                    interpret=interpret)
            elif mixer == "attn":
                mx, nc = attn_decode(sub["attn"], cfg, h, pos_t, c,
                                     impl=attn_impl, shards=attn_shards,
                                     block_k=block_k, interpret=interpret)
            elif mixer == "mamba":
                mx, nc = mamba_decode(sub["mamba"], h,
                                      c, d_state=cfg.mamba_d_state,
                                      d_conv=cfg.mamba_d_conv)
            elif mixer == "mlstm":
                mx, nc = mlstm_decode(sub["mlstm"], h, c,
                                      num_heads=cfg.num_heads)
            else:
                mx, nc = slstm_decode(sub["slstm"], h, c)
            x = x + mx
            new_cache[f"sub_{j}"] = nc
            if ffn != "none":
                h = rmsnorm(sub["norm2"], x[:, None], cfg.norm_eps)
                if ffn == "moe":
                    f, _ = moe_apply(sub["moe"], h, None, top_k=cfg.top_k,
                                     capacity_factor=cfg.capacity_factor,
                                     kind=cfg.mlp)
                else:
                    f = mlp_apply(sub["mlp"], h, cfg.mlp)
                x = x + f[:, 0]
        return x, new_cache

    x, new_cache = jax.lax.scan(period_body, x,
                                (params["layers"], cache))
    x = rmsnorm(params["final_norm"], x[:, None], cfg.norm_eps)[:, 0]
    if "lm_head" in params:
        logits = dense(params["lm_head"], x)
    else:
        logits = x @ params["embed"]["e"].T.astype(x.dtype)
    return logits, new_cache
