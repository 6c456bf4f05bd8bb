"""Pallas TPU flash-decode kernel: one query token vs a long KV cache.

The decode shapes (decode_32k, long_500k) are HBM-bound on the cache read
(§Roofline) — the fused kernel streams K/V blocks through VMEM once,
keeping the online-softmax state in registers/VMEM, and *clamps* the
block index map at the request length so blocks past the end of a shorter
request are never fetched (ragged batches pay only for what they use).

Layout: q (B, Hq, D); k, v (B, Hkv, S, D); lengths (B,) — position t
attends to cache[0..t] inclusive (the current token's KV must already be
written at position lengths[b]).  A negative length marks a request with
no visible KV (e.g. an empty CP shard): nothing is fetched and the
partial is the merge identity.  GQA: the kernel processes one KV head's
whole query group per grid cell, so each cache block is read exactly once
per KV head.

Two output modes:

* ``partial=False`` (default) — the normalized attention output
  (B, Hq, D), zeros for empty rows.
* ``partial=True`` — the merge-ready triple ``(o, m, l)``: the
  *unnormalized* fp32 accumulator (B, Hq, D), the running row max
  (B, Hq) and the running row sum (B, Hq).  Partials from disjoint KV
  subsets combine with :func:`repro.core.cp_attention.merge_partials`
  and normalize with ``finalize_partial`` — the same online-LSE
  substrate the CP training islands run on.  Under CP serving the cache
  is sequence-sharded: each rank runs the kernel on its shard (local
  length = global length minus the shard offset, clamped) and ranks
  merge with the standard LSE combine — :func:`flash_decode_sharded` is
  the single-process form, ``merge_partials_axis`` the shard_map form.

Paged variant (:func:`flash_decode_paged`): the serving engine's KV
lives in a *global block pool* — per-layer arrays of shape
(Hkv, num_blocks, block_size, D) shared by every request — and each
request owns a *block table* mapping its logical block index to a
physical pool block.  The kernel prefetches the table alongside the
lengths and resolves the physical block inside the BlockSpec index map,
so the HBM fetch pattern is identical to the dense kernel's (one block
per grid step, clamped at the request length); only the *address* is
indirected.  Shared prefix blocks (serve/prefix.py) are therefore read
straight from the pool with no gather or copy.
``paged_decode_reference`` is the XLA gather + dense-softmax oracle.

Forward-only (inference); validated against ``decode_reference`` in
interpret mode (tests/test_kernels.py, tests/test_serve.py,
tests/test_paged.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_decode", "flash_decode_sharded", "flash_decode_paged",
           "decode_reference", "paged_decode_reference", "gather_paged_kv"]

NEG = -1e30
DEFAULT_BLOCK_K = 256


def decode_reference(q, k, v, lengths, *, scale=None):
    """Dense jnp oracle.  q (B,Hq,D); k,v (B,Hkv,S,D); lengths (B,)."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D) * scale
    s = jnp.einsum("bhgd,bhsd->bhgs", qf, k.astype(jnp.float32))
    mask = (jnp.arange(S)[None, :] <= lengths[:, None])[:, None, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    out = jnp.where(lengths[:, None, None, None] >= 0, out, 0.0)
    return out.reshape(B, Hq, D).astype(q.dtype)


def _decode_body(len_ref, q_ref, k_ref, v_ref, refs,
                 scale: float, block_k: int, num_blocks: int,
                 partial: bool):
    """Shared online-softmax body for the dense-cache and paged kernels
    (they differ only in how the BlockSpec index map finds the KV block;
    the visit math is identical — positions are *logical*)."""
    if partial:
        o_ref, om_ref, ol_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    b, h, kb = (pl.program_id(i) for i in range(3))

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    @pl.when(kb * block_k <= length)
    def _visit():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
        k = k_ref[0, 0]                               # (bk, D)
        s = jax.lax.dot_general(
            q, k.T.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, bk)
        pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        valid = pos <= length                         # (1, bk)
        s = jnp.where(valid, s, NEG)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        vv = v_ref[0, 0]
        pv = jax.lax.dot_general(
            p.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == num_blocks - 1)
    def _finalize():
        if partial:
            o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)
            om_ref[0, 0] = m_ref[...]
            ol_ref[0, 0] = l_ref[...]
        else:
            l = l_ref[:, :1]
            out = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30),
                            0.0)
            o_ref[0, 0] = out.astype(o_ref.dtype)


def _decode_kernel(len_ref,                      # scalar prefetch
                   q_ref, k_ref, v_ref, *refs, scale, block_k,
                   num_blocks, partial):
    _decode_body(len_ref, q_ref, k_ref, v_ref, refs, scale, block_k,
                 num_blocks, partial)


def _decode_kernel_paged(len_ref, tab_ref,       # scalar prefetch
                         q_ref, k_ref, v_ref, *refs, scale, block_k,
                         num_blocks, partial):
    # the table is consumed by the BlockSpec index map only
    _decode_body(len_ref, q_ref, k_ref, v_ref, refs, scale, block_k,
                 num_blocks, partial)


def flash_decode(q, k, v, lengths, *, scale=None,
                 block_k: int = DEFAULT_BLOCK_K, interpret: bool = False,
                 partial: bool = False):
    """q (B, Hq, D); k, v (B, Hkv, S, D); lengths (B,).

    ``partial=False`` -> normalized output (B, Hq, D).
    ``partial=True`` -> merge-ready ``(o, m, l)``: fp32 accumulator
    (B, Hq, D), row max (B, Hq), row sum (B, Hq).
    """
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    while S % block_k:
        block_k //= 2
    nk = S // block_k

    def kv_block(b, h, kb, len_ref):
        # clamp past-the-end blocks to the last needed block: Pallas's
        # revisiting pipeline turns the repeat into a no-op fetch.  The
        # lower clamp covers negative lengths (nothing visible on this
        # shard): the fetch lands on block 0 but _visit never fires.
        last_needed = jnp.clip(len_ref[b] // block_k, 0, nk - 1)
        return (b, h, jnp.minimum(kb, last_needed), 0)

    out_specs = [pl.BlockSpec((1, 1, G, D), lambda b, h, kb, s_: (b, h, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, Hkv, G, D),
                                      jnp.float32 if partial else q.dtype)]
    if partial:
        stat_spec = pl.BlockSpec((1, 1, G, 128),
                                 lambda b, h, kb, s_: (b, h, 0, 0))
        out_specs += [stat_spec, stat_spec]
        out_shape += [jax.ShapeDtypeStruct((B, Hkv, G, 128), jnp.float32)] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, kb, s_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_block),
            pl.BlockSpec((1, 1, block_k, D), kv_block),
        ],
        out_specs=out_specs if partial else out_specs[0],
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, scale=float(scale),
                               block_k=block_k, num_blocks=nk,
                               partial=partial)
    q4 = q.reshape(B, Hkv, G, D)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=out_shape if partial else out_shape[0],
        interpret=interpret,
        name="flash_decode",
    )(lengths, q4, k, v)
    if not partial:
        return out.reshape(B, Hq, D)
    o, m, l = out
    return (o.reshape(B, Hq, D), m[..., 0].reshape(B, Hq),
            l[..., 0].reshape(B, Hq))


def flash_decode_sharded(q, k, v, lengths, *, shards: int, scale=None,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: bool = False):
    """Decode attention over a sequence-sharded cache, merged on the
    online-LSE substrate.

    The cache's S axis is split into ``shards`` contiguous segments
    (shard s owns positions [s*S/N, (s+1)*S/N)); each segment runs
    :func:`flash_decode` in partial mode against its *local* length
    (global length minus the segment offset, clamped), and the partials
    fold through ``merge_partials`` + ``finalize_partial`` — bit-for-bit
    the combine a CP decode island performs across ranks, executed
    in-process.  ``shards=1`` degenerates to one partial + finalize.
    """
    from repro.core.cp_attention import finalize_partial, merge_partials

    _, _, S, _ = k.shape
    assert S % shards == 0, (S, shards)
    Sl = S // shards
    parts = []
    for s in range(shards):
        local_len = jnp.clip(lengths - s * Sl, -1, Sl - 1)
        parts.append(flash_decode(
            q, k[:, :, s * Sl:(s + 1) * Sl], v[:, :, s * Sl:(s + 1) * Sl],
            local_len, scale=scale, block_k=block_k, interpret=interpret,
            partial=True))
    return finalize_partial(merge_partials(parts), q.dtype)


# --------------------------------------------------------------------- #
# paged decode: block-table indirection into a global KV block pool
# --------------------------------------------------------------------- #
def gather_paged_kv(pool, tables, block_size: int):
    """Materialize each request's logical KV view from the pool.

    pool (Hkv, NBtok, D) with NBtok = num_blocks * block_size; tables
    (B, nk) physical block ids per logical block.  Returns
    (B, Hkv, nk * block_size, D) — the dense layout the XLA oracle and
    the prefill attention expect.  Unwritten table slots point at block
    0; their values are garbage and must be masked by position.
    """
    B, nk = tables.shape
    off = jnp.arange(block_size, dtype=jnp.int32)
    phys = (tables[:, :, None] * block_size + off[None, None, :]) \
        .reshape(B, nk * block_size)                  # (B, S_logical)
    return pool[:, phys].transpose(1, 0, 2, 3)        # (B, Hkv, S, D)


def paged_decode_reference(q, k_pool, v_pool, lengths, tables,
                           *, block_size: int, scale=None):
    """XLA gather + dense-softmax oracle for the paged kernel.

    q (B, Hq, D); k_pool/v_pool (Hkv, num_blocks * block_size, D);
    tables (B, nk) int32; lengths (B,) — logical positions, as in
    :func:`decode_reference`."""
    k = gather_paged_kv(k_pool, tables, block_size)
    v = gather_paged_kv(v_pool, tables, block_size)
    return decode_reference(q, k, v, lengths, scale=scale)


def flash_decode_paged(q, k_pool, v_pool, lengths, tables,
                       *, block_size: int, scale=None,
                       interpret: bool = False):
    """Flash decode over a paged KV pool.

    q (B, Hq, D); k_pool/v_pool (Hkv, num_blocks * block_size, D) —
    the global pool, flat on the token axis; tables (B, nk) int32 maps
    each request's logical block to its physical pool block (unwritten
    slots must hold a valid index, conventionally 0); lengths (B,)
    logical positions (negative = nothing visible, output zeros).

    Grid and visit math are identical to :func:`flash_decode` with
    ``block_k = block_size`` — the only difference is the KV BlockSpec
    index map, which resolves ``tables[b, kb]`` (clamped at the last
    needed block, as the dense kernel clamps ``kb``) so blocks past a
    request's length are never fetched and shared prefix blocks are
    fetched from their single pool-resident copy.
    """
    B, Hq, D = q.shape
    Hkv, NBtok, _ = k_pool.shape
    assert NBtok % block_size == 0, (NBtok, block_size)
    G = Hq // Hkv
    nk = tables.shape[1]
    if scale is None:
        scale = D ** -0.5
    k4 = k_pool.reshape(Hkv, NBtok // block_size, block_size, D)
    v4 = v_pool.reshape(Hkv, NBtok // block_size, block_size, D)

    def kv_block(b, h, kb, len_ref, tab_ref):
        # same past-the-end clamp as the dense kernel, then the table
        # lookup turns the logical block into a physical pool block
        last_needed = jnp.clip(len_ref[b] // block_size, 0, nk - 1)
        return (h, tab_ref[b, jnp.minimum(kb, last_needed)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, kb, l_, t_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_size, D), kv_block),
            pl.BlockSpec((1, 1, block_size, D), kv_block),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, kb, l_, t_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel_paged, scale=float(scale),
                               block_k=block_size, num_blocks=nk,
                               partial=False)
    q4 = q.reshape(B, Hkv, G, D)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
        name="flash_decode_paged",
    )(lengths, tables, q4, k4, v4)
    return out.reshape(B, Hq, D)
