"""Jit-ready attention ops.

* ``doc_flash_attention`` — the Pallas kernel pair (fwd + custom-VJP bwd)
  from :mod:`repro.kernels.doc_attention`.  TPU is the target; pass
  ``interpret=True`` to validate on CPU.  ``grid`` selects the kernel
  schedule: ``"rect"`` launches the padded rectangular visit grid,
  ``"flat"`` the flattened 1D work queue (one grid step per actual
  visit; see the kernel module docstring).
* ``doc_attention_xla``  — chunked pure-XLA implementation with identical
  semantics.  Used for CPU training runs and for the multi-pod dry-run
  (Pallas TPU kernels cannot lower on the CPU backend); differentiable by
  ordinary JAX AD.

Both implement the doc-mask visibility rule defined in ``ref.py``, and
both expose a **partial mode** for the CP overlap engine
(:mod:`repro.core.cp_attention`): instead of a finished attention output
they emit a merge-ready partial whose combination across KV subsets via
online-LSE rescaling reproduces full attention exactly:

* ``doc_flash_attention(..., partial=True)`` returns ``(o, lse)`` — the
  subset-normalized output plus its log-sum-exp.  The custom VJP folds
  the incoming ``d lse`` into the flash backward's ``delta`` term
  (``ds = p * (dp - (delta - dlse))``), so the same Pallas backward
  kernels serve the merged objective with exact gradients.
* ``doc_attention_xla(..., partial=True)`` returns the raw online-softmax
  triple ``(o_unnormalized, m, l)``; plain JAX AD differentiates it.

The two partial forms are interchangeable under the same merge: a
normalized ``(o, lse)`` pair is the triple ``(o, m=lse, l=1)``.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.table_layout import GRID_TABLE_HALF

from . import doc_attention as da
from .ref import doc_mask

__all__ = ["doc_flash_attention", "doc_attention_xla", "resolve_interpret"]


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> Pallas interpret mode exactly when JAX's default backend
    is not a TPU; on a chip the kernels always compile with Mosaic."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _float0_zero(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def _split_tables(tables: tuple, grid: str):
    """(fwd/dQ tables, dKV reverse tables) halves of the combined tuple.

    rect: (kv_idx, kv_nvis | q_idx, q_nvis)
    flat: (fq_row, fq_col, fq_flags | rq_row, rq_col, rq_flags)
    """
    half = GRID_TABLE_HALF[grid]
    if len(tables) != 2 * half:
        raise ValueError(
            f"grid={grid!r} needs {2 * half} table arrays, got "
            f"{len(tables)}")
    return tables[:half], tables[half:]


# ===================================================================== #
# Pallas path
# ===================================================================== #
@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12))
def _attn(q, k, v, q_doc, q_pos, kv_doc, kv_pos, tables,
          scale, block_q, block_k, grid, interpret):
    fwd_t, _ = _split_tables(tables, grid)
    out, _ = da.flash_fwd(
        q, k, v, q_doc, q_pos, kv_doc, kv_pos, fwd_t,
        scale=scale, block_q=block_q, block_k=block_k, grid=grid,
        interpret=interpret)
    return out


def _attn_fwd(q, k, v, q_doc, q_pos, kv_doc, kv_pos, tables,
              scale, block_q, block_k, grid, interpret):
    fwd_t, _ = _split_tables(tables, grid)
    out, lse = da.flash_fwd(
        q, k, v, q_doc, q_pos, kv_doc, kv_pos, fwd_t,
        scale=scale, block_q=block_q, block_k=block_k, grid=grid,
        interpret=interpret)
    res = (q, k, v, out, lse, q_doc, q_pos, kv_doc, kv_pos, tables)
    return out, res


def _flash_bwd(res, do, dlse, *, scale, block_q, block_k, grid, interpret):
    """Shared dq/dkv backward; ``dlse`` folds an (o, lse)-mode lse
    cotangent into delta (None for plain attention)."""
    (q, k, v, out, lse, q_doc, q_pos, kv_doc, kv_pos, tables) = res
    fwd_t, rev_t = _split_tables(tables, grid)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    dq = da.flash_bwd_dq(
        q, k, v, do, lse, delta, q_doc, q_pos, kv_doc, kv_pos,
        fwd_t, scale=scale, block_q=block_q, block_k=block_k, grid=grid,
        interpret=interpret)
    dk, dv = da.flash_bwd_dkv(
        q, k, v, do, lse, delta, q_doc, q_pos, kv_doc, kv_pos,
        rev_t, scale=scale, block_q=block_q, block_k=block_k, grid=grid,
        interpret=interpret)
    zeros = tuple(_float0_zero(x) for x in
                  (q_doc, q_pos, kv_doc, kv_pos))
    return (dq, dk, dv) + zeros + (tuple(_float0_zero(t) for t in tables),)


def _attn_bwd(scale, block_q, block_k, grid, interpret, res, do):
    return _flash_bwd(res, do, None, scale=scale, block_q=block_q,
                      block_k=block_k, grid=grid, interpret=interpret)


_attn.defvjp(_attn_fwd, _attn_bwd)


# ===================================================================== #
# Pallas partial mode: (o, lse) with exact gradients through both
# ===================================================================== #
@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12))
def _attn_partial(q, k, v, q_doc, q_pos, kv_doc, kv_pos, tables,
                  scale, block_q, block_k, grid, interpret):
    fwd_t, _ = _split_tables(tables, grid)
    return da.flash_fwd(
        q, k, v, q_doc, q_pos, kv_doc, kv_pos, fwd_t,
        scale=scale, block_q=block_q, block_k=block_k, grid=grid,
        interpret=interpret)


def _attn_partial_fwd(q, k, v, q_doc, q_pos, kv_doc, kv_pos, tables,
                      scale, block_q, block_k, grid, interpret):
    out, res = _attn_fwd(q, k, v, q_doc, q_pos, kv_doc, kv_pos, tables,
                         scale, block_q, block_k, grid, interpret)
    return (out, res[4]), res


def _attn_partial_bwd(scale, block_q, block_k, grid, interpret, res, cts):
    """Backward of the (o, lse) pair with the standard flash kernels.

    With p = exp(s - lse): d s = p * (do . v - delta) + p * dlse, so the
    lse cotangent folds into the delta argument as ``delta - dlse`` and
    the unmodified dq / dkv kernels compute exact gradients of both
    outputs.  (d lse / d v = 0, which the dkv kernel respects for free.)
    """
    do, dlse = cts
    return _flash_bwd(res, do, dlse, scale=scale, block_q=block_q,
                      block_k=block_k, grid=grid, interpret=interpret)


_attn_partial.defvjp(_attn_partial_fwd, _attn_partial_bwd)


def doc_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    q_doc: jax.Array, q_pos: jax.Array,
    kv_doc: jax.Array, kv_pos: jax.Array,
    tables: Any,
    *,
    scale: float | None = None,
    block_q: int = da.DEFAULT_BLOCK_Q,
    block_k: int = da.DEFAULT_BLOCK_K,
    grid: str = "rect",
    interpret: bool = False,
    partial: bool = False,
) -> jax.Array:
    """Document-masked causal flash attention (Pallas TPU kernel).

    ``tables`` is a :class:`~repro.kernels.doc_attention.BlockTables` or
    the matching array tuple for ``grid``: the rectangular 4-tuple
    ``(kv_idx, kv_nvis, q_idx, q_nvis)`` for ``grid="rect"``, the
    flattened work-queue 6-tuple ``(fq_row, fq_col, fq_flags, rq_row,
    rq_col, rq_flags)`` for ``grid="flat"``.

    ``partial=True`` returns ``(o, lse)`` — the KV-subset-normalized
    output and its log-sum-exp (``-inf`` on rows with nothing visible) —
    for online-LSE merging across subsets; gradients flow through both.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if isinstance(tables, da.BlockTables):
        block_q, block_k = tables.block_q, tables.block_k
        tables = tables.flat_as_jax() if grid == "flat" else tables.as_jax()
    else:
        tables = tuple(tables)
    fn = _attn_partial if partial else _attn
    return fn(q, k, v, q_doc, q_pos, kv_doc, kv_pos, tables,
              float(scale), block_q, block_k, grid, interpret)


# ===================================================================== #
# XLA fallback path (CPU training + dry-run lowering)
# ===================================================================== #
def doc_attention_xla(
    q: jax.Array, k: jax.Array, v: jax.Array,
    q_doc: jax.Array, q_pos: jax.Array,
    kv_doc: jax.Array, kv_pos: jax.Array,
    *,
    scale: float | None = None,
    q_chunk: int = 512,
    partial: bool = False,
):
    """Chunked dense attention with the doc-mask semantics of ``ref.py``.

    Chunking over the query axis bounds the live logits tensor to
    ``(B, Hq, q_chunk, Tk)`` — the XLA analogue of flash attention's
    working-set control (full flash semantics are only needed on TPU where
    the Pallas kernel takes over).

    ``partial=True`` returns the unnormalized online-softmax triple
    ``(o, m, l)`` in f32 (``m = -1e30`` on rows with nothing visible) for
    online-LSE merging across KV subsets; differentiable by plain JAX AD.
    """
    B, Hq, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    if Tq % q_chunk != 0:
        q_chunk = Tq
    nq = Tq // q_chunk

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def one_chunk(args):
        qc, qdc, qpc = args
        qc = qc.astype(jnp.float32).reshape(B, Hkv, G, q_chunk, D)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qc, kf) * scale
        mask = doc_mask(qdc, qpc, kv_doc, kv_pos)
        if partial:
            s = jnp.where(mask[:, None, None], s, da.NEG)
            m = jnp.max(s, axis=-1)
            p = jnp.where(mask[:, None, None], jnp.exp(s - m[..., None]), 0.0)
            l = jnp.sum(p, axis=-1)
            o = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
            return (o.reshape(B, Hq, q_chunk, D),
                    m.reshape(B, Hq, q_chunk), l.reshape(B, Hq, q_chunk))
        s = jnp.where(mask[:, None, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
        o = jnp.where(l > 0, o / jnp.maximum(l, 1e-30), 0.0)
        return o.reshape(B, Hq, q_chunk, D)

    if nq == 1:
        out = one_chunk((q, q_doc, q_pos))
        if partial:
            return out
    else:
        qs = q.reshape(B, Hq, nq, q_chunk, D).transpose(2, 0, 1, 3, 4)
        qds = q_doc.reshape(B, nq, q_chunk).transpose(1, 0, 2)
        qps = q_pos.reshape(B, nq, q_chunk).transpose(1, 0, 2)
        # checkpointed: the backward recomputes each chunk's logits
        # instead of saving all nq of them (the full (Tq, Tk) matrix)
        outs = jax.lax.map(jax.checkpoint(one_chunk),
                           (qs, qds, qps))              # (nq, B, Hq, qc, *)
        if partial:
            o, m, l = outs
            return (o.transpose(1, 2, 0, 3, 4).reshape(B, Hq, Tq, D),
                    m.transpose(1, 2, 0, 3).reshape(B, Hq, Tq),
                    l.transpose(1, 2, 0, 3).reshape(B, Hq, Tq))
        out = outs.transpose(1, 2, 0, 3, 4).reshape(B, Hq, Tq, D)
    return out.astype(q.dtype)
