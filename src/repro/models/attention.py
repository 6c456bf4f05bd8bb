"""GQA attention block: projections + RoPE + (CP-aware) masked attention.

The inner attention is ``ctx.attn`` — locally a doc-masked kernel, under CP
the FlashCP shard_map island.  qk_norm (Qwen3) is per-head RMS norm applied
before RoPE.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import _he, rope

__all__ = ["attn_init", "attn_apply", "attn_cache_init",
           "attn_paged_cache_init", "attn_decode", "attn_decode_paged",
           "attn_prefill", "attn_prefill_paged"]


def attn_init(rng, cfg):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    rs = jax.random.split(rng, 4)
    p = {
        "wq": _he(rs[0], (d, hq * hd), d),
        "wk": _he(rs[1], (d, hkv * hd), d),
        "wv": _he(rs[2], (d, hkv * hd), d),
        "wo": _he(rs[3], (hq * hd, d), hq * hd),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _head_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g).astype(x.dtype)


def _project(p, cfg, x):
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, T, cfg.num_heads, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, T, cfg.num_kv_heads, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, T, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = _head_norm(q, p["q_norm"], cfg.norm_eps)
        k = _head_norm(k, p["k_norm"], cfg.norm_eps)
    return (q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2))


def attn_apply(p, cfg, ctx, x):
    """x (B, T, d) -> (B, T, d)."""
    B, T, _ = x.shape
    q, k, v = _project(p, cfg, x)
    q = rope(q, ctx.pos, cfg.rope_theta)
    k = rope(k, ctx.pos, cfg.rope_theta)
    out = ctx.attn(q, k, v)                       # (B, Hq, T, hd)
    out = out.swapaxes(1, 2).reshape(B, T, cfg.num_heads * cfg.resolved_head_dim)
    return out @ p["wo"].astype(x.dtype)


# ------------------------------------------------------------------ #
# decode: one new token against a (sequence-sharded) KV cache
# ------------------------------------------------------------------ #
def attn_cache_init(cfg, batch: int, max_len: int, dtype):
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cfg.num_kv_heads, max_len, hd), dtype),
        "v": jnp.zeros((batch, cfg.num_kv_heads, max_len, hd), dtype),
    }


def attn_paged_cache_init(cfg, num_blocks: int, block_size: int, dtype):
    """Global paged KV pool for one attention sub-layer: no batch axis —
    every request indexes the same pool through its block table (the
    token axis is flat; physical position = block_id * block_size +
    offset).  HBM scales with *allocated* blocks, not slots x max_len."""
    hd = cfg.resolved_head_dim
    shape = (cfg.num_kv_heads, num_blocks * block_size, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attn_decode(p, cfg, x_t, pos_t, cache, *, impl: str = "flash",
                shards: int = 1, block_k: int = 256,
                interpret: bool | None = None):
    """x_t (B, d); pos_t (B,) current positions.

    ``impl="flash"`` (default) runs the fused Pallas flash-decode kernel
    per cache shard (``shards`` contiguous S-segments, 1 = whole cache)
    and merges the (o, m, l) partials on the online-LSE substrate —
    blocks past each request's length are never fetched, so ragged
    batches pay only for the cache they use.  ``impl="dense"`` keeps the
    XLA dense softmax as the parity oracle.  ``interpret=None`` picks
    Pallas interpret mode automatically off-TPU."""
    B, d = x_t.shape
    hd = cfg.resolved_head_dim
    q, k, v = _project(p, cfg, x_t[:, None, :])
    q = rope(q, pos_t[:, None], cfg.rope_theta)            # (B,Hq,1,hd)
    k = rope(k, pos_t[:, None], cfg.rope_theta)

    # scatter the new KV at pos_t (per sample) — in-place update, not a
    # full-cache rewrite (the decode step is HBM-bound on the cache read).
    bi = jnp.arange(B)[:, None]
    hi = jnp.arange(cfg.num_kv_heads)[None, :]
    kc = cache["k"].at[bi, hi, pos_t[:, None]].set(k[:, :, 0, :])
    vc = cache["v"].at[bi, hi, pos_t[:, None]].set(v[:, :, 0, :])

    if impl == "flash":
        from repro.kernels.flash_decode import flash_decode_sharded
        from repro.kernels.ops import resolve_interpret

        out = flash_decode_sharded(
            q[:, :, 0, :], kc, vc, pos_t, shards=shards,
            scale=hd ** -0.5, block_k=block_k,
            interpret=resolve_interpret(interpret))
        out = out.reshape(B, cfg.num_heads * hd).astype(x_t.dtype)
    elif impl == "dense":
        from repro.kernels.flash_decode import decode_reference

        out = decode_reference(q[:, :, 0, :], kc, vc, pos_t) \
            .reshape(B, cfg.num_heads * hd).astype(x_t.dtype)
    else:
        raise ValueError(f"unknown decode attention impl {impl!r}")
    return out @ p["wo"].astype(x_t.dtype), {"k": kc, "v": vc}


def attn_prefill(p, cfg, x, pos, cache, active):
    """Chunked-prefill attention: write this chunk's KV straight into the
    cache, then attend the chunk's queries against the cache prefix.

    x (B, T, d) chunk activations; pos (B, T) *global* cache positions of
    the chunk tokens (monotone per row); active (B, T) bool — False rows/
    tokens (padding past a short prompt, idle slots) neither write the
    cache nor produce output.  Causality falls out of the position mask:
    every cache entry at position <= pos[b, t] was written by this or an
    earlier chunk, and entries past the chunk are masked (unwritten or
    future).  Returns (out (B, T, d), new cache).
    """
    from repro.kernels.ref import mha_reference

    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    q, k, v = _project(p, cfg, x)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)

    S = cache["k"].shape[2]
    # scatter the chunk's KV at its global positions; inactive tokens are
    # routed out of bounds and dropped, leaving the cache untouched there
    bi = jnp.arange(B)[:, None, None]
    hi = jnp.arange(cfg.num_kv_heads)[None, :, None]
    ti = jnp.where(active, pos, S)[:, None, :]
    kc = cache["k"].at[bi, hi, ti].set(k, mode="drop")
    vc = cache["v"].at[bi, hi, ti].set(v, mode="drop")

    q_doc = jnp.where(active, 0, -1).astype(jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    kv_doc = jnp.zeros((B, S), jnp.int32)
    out = mha_reference(q, kc, vc, q_doc, pos, kv_doc, kv_pos,
                        scale=hd ** -0.5)
    out = out.swapaxes(1, 2).reshape(B, T, cfg.num_heads * hd)
    return out @ p["wo"].astype(x.dtype), {"k": kc, "v": vc}


# ------------------------------------------------------------------ #
# paged: block-table indirection into a global KV pool
# ------------------------------------------------------------------ #
def _phys_positions(tables, pos, active, block_size, nbtok):
    """Logical position -> flat pool position via the block table; tokens
    outside ``active`` route out of bounds (scatter mode="drop")."""
    blk = jnp.take_along_axis(tables, jnp.maximum(pos, 0) // block_size,
                              axis=1)
    phys = blk * block_size + jnp.maximum(pos, 0) % block_size
    return jnp.where(active & (pos >= 0), phys, nbtok)


def attn_decode_paged(p, cfg, x_t, pos_t, cache, tables, active, *,
                      impl: str = "flash", block_size: int,
                      interpret: bool | None = None):
    """One decode token against the paged pool.

    x_t (B, d); pos_t (B,) logical positions; tables (B, nk) block
    tables; active (B,) — inactive rows (idle / still-prefilling slots)
    never write the pool.  ``impl="flash"`` runs the block-table Pallas
    kernel; ``"dense"`` gathers the logical view and runs the XLA
    softmax oracle.  The caller must have made the written block private
    (refcount 1) — copy-on-write happens host-side in the engine.
    """
    B, _ = x_t.shape
    hd = cfg.resolved_head_dim
    q, k, v = _project(p, cfg, x_t[:, None, :])
    q = rope(q, pos_t[:, None], cfg.rope_theta)            # (B,Hq,1,hd)
    k = rope(k, pos_t[:, None], cfg.rope_theta)

    nbtok = cache["k"].shape[1]
    phys = _phys_positions(tables, pos_t[:, None], active[:, None],
                           block_size, nbtok)[:, 0]         # (B,)
    hi = jnp.arange(cfg.num_kv_heads)[:, None]
    kc = cache["k"].at[hi, phys[None, :]].set(
        k[:, :, 0].swapaxes(0, 1).astype(cache["k"].dtype), mode="drop")
    vc = cache["v"].at[hi, phys[None, :]].set(
        v[:, :, 0].swapaxes(0, 1).astype(cache["v"].dtype), mode="drop")

    lengths = jnp.where(active, pos_t, -1)
    if impl == "flash":
        from repro.kernels.flash_decode import flash_decode_paged
        from repro.kernels.ops import resolve_interpret

        out = flash_decode_paged(q[:, :, 0, :], kc, vc, lengths, tables,
                                 block_size=block_size, scale=hd ** -0.5,
                                 interpret=resolve_interpret(interpret))
    elif impl == "dense":
        from repro.kernels.flash_decode import paged_decode_reference

        out = paged_decode_reference(q[:, :, 0, :], kc, vc, lengths,
                                     tables, block_size=block_size,
                                     scale=hd ** -0.5)
    else:
        raise ValueError(f"unknown decode attention impl {impl!r}")
    out = out.reshape(B, cfg.num_heads * hd).astype(x_t.dtype)
    return out @ p["wo"].astype(x_t.dtype), {"k": kc, "v": vc}


def attn_prefill_paged(p, cfg, x, pos, cache, active, tables, *,
                       block_size: int, view_blocks: int):
    """Chunked-prefill attention through the block table (B = 1).

    Writes the chunk's roped KV at its physical pool positions, then
    attends the chunk's queries against the request's *gathered* logical
    prefix (``view_blocks`` blocks — the pow2 bucket covering the chunk
    end, so attention is O(C * view) not O(C * pool)).  active tokens
    beyond the prompt neither write nor contribute (same contract as
    :func:`attn_prefill`).
    """
    from repro.kernels.ref import mha_reference

    B, T, _ = x.shape
    assert B == 1, "paged prefill runs one request at a time"
    hd = cfg.resolved_head_dim
    q, k, v = _project(p, cfg, x)
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)

    nbtok = cache["k"].shape[1]
    phys = _phys_positions(tables, pos, active, block_size, nbtok)  # (1,T)
    hi = jnp.arange(cfg.num_kv_heads)[:, None]
    kc = cache["k"].at[hi, phys[0][None, :]].set(
        k[0].astype(cache["k"].dtype), mode="drop")
    vc = cache["v"].at[hi, phys[0][None, :]].set(
        v[0].astype(cache["v"].dtype), mode="drop")

    # gather the logical prefix view [0, view_blocks * bs)
    S = view_blocks * block_size
    s_log = jnp.arange(S, dtype=jnp.int32)
    vblk = jnp.take_along_axis(
        tables, (s_log // block_size)[None, :], axis=1)[0]
    vphys = vblk * block_size + s_log % block_size
    kv_view = kc[:, vphys][None], vc[:, vphys][None]       # (1,Hkv,S,hd)

    q_doc = jnp.where(active, 0, -1).astype(jnp.int32)
    kv_pos = jnp.broadcast_to(s_log[None], (B, S))
    kv_doc = jnp.zeros((B, S), jnp.int32)
    out = mha_reference(q, kv_view[0], kv_view[1], q_doc, pos, kv_doc,
                        kv_pos, scale=hd ** -0.5)
    out = out.swapaxes(1, 2).reshape(B, T, cfg.num_heads * hd)
    return out @ p["wo"].astype(x.dtype), {"k": kc, "v": vc}
