"""Device-side context parallelism: shard_map islands over the CP axis.

Every strategy now runs on one **partial-attention + online-LSE merge
substrate**: attention against any KV subset yields a merge-ready partial
``(o, m, l)`` (unnormalized accumulator, row max, row sum — or the
equivalent normalized ``(o, lse, 1)`` form the Pallas kernel emits), and
partials merge by the usual flash rescaling in any order.  Communication
strategies differ only in *which* KV subsets exist and how they move:

* ``flashcp`` / ``contiguous`` — **sharding-aware communication** (§3.2):
  only the compacted non-last-shard KV buffer (Eq. 5 volume) moves.
  ``overlap="chunked"`` (default) moves it in N-1 ``ppermute`` ring hops:
  local-KV attention runs concurrently with hop 0, and each arriving
  buffer attends while the next hop is in flight — the XLA latency-hiding
  scheduler overlaps the whole exchange with compute.  ``overlap="none"``
  keeps the original single blocking all-gather island (parity baseline).
  Backward is the JAX transpose either way — reduce-scatter (monolithic)
  or the reversed ppermute chain (chunked) of dKV at the same reduced
  volume (the paper's 4x factor).
* ``allgather`` — full-KV exchange (Eq. 4): Llama3 CP and Per-Doc CP;
  the same ``overlap`` switch applies with the full local KV as the
  hop payload.
* ``ring`` — Ring-Attention (Zigzag): N-1 hops of full local KV.
  ``overlap="chunked"`` is the substrate engine (Pallas-capable);
  ``overlap="none"`` selects the frozen pure-XLA seed loop.

Any strategy runs the Pallas block-sparse kernel per subset when
``impl="pallas"`` and per-rank visit tables are threaded in (the planner
emits them — :func:`repro.planner.encode.emit_visit_tables`; the data
pipeline forwards them as ``tab_*`` plan arrays).  ``grid`` picks the
kernel schedule: ``"flat"`` walks the flattened work-queue tables (one
grid step per actual visit), ``"rect"`` the padded rectangular layout
(parity baseline); the table key families differ accordingly
(``*_{kv,q}_{idx,nvis}`` vs ``*_{fq,rq}_{row,col,flags}``).

A self-ownership subtlety of the compact buffer: the monolithic all-gather
includes this rank's own contribution, which is *also* present as local
KV.  The island marks its own gathered segment invisible (doc id -2) so no
KV pair is double-counted.  The chunked exchange never attends its own
buffer (N-1 hops visit exactly the other ranks), so no masking is needed.

The SSM island implements cross-rank recurrence for Mamba/xLSTM: local
chunked scans + an all-gather of per-rank (decay, state) summaries with an
associative prefix combine — O(state) communication, no serialization
across ranks.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.compat import axis_size, shard_map
from repro.table_layout import GRID_TABLE_HALF, table_keys
from jax.sharding import PartitionSpec as P

from repro.models.context import ExecContext, local_ssm_scan

__all__ = ["make_cp_context", "resolve_overlap", "CP_AXIS",
           "merge_partials", "finalize_partial", "merge_partials_axis"]

CP_AXIS = "model"
NEG = -1e30
#: jax.named_scope of the KV exchange's collectives (op_name in the HLO)
EXCHANGE_SCOPE = "cp_exchange"


def resolve_overlap(strategy: str, impl: str, overlap: str) -> str:
    """Effective overlap mode for (strategy, impl).

    Ring has no monolithic Pallas form — its only kernel-capable engine
    is the chunked substrate — so ring+pallas upgrades ``"none"`` to
    ``"chunked"``.  The single source of truth for table emission
    (data/pipeline.py), AOT input specs (launch/steps.py), and the
    context dispatch below.
    """
    if overlap not in ("none", "chunked"):
        raise ValueError(f"unknown overlap mode {overlap!r}")
    if strategy in ("ring", "ring_zigzag") and impl == "pallas":
        return "chunked"
    return overlap


# ===================================================================== #
# helpers
# ===================================================================== #
def _take_tokens(x, idx):
    """x (b, H, T, D); idx (b, S) with -1 padding -> (b, H, S, D), zeroed
    at padding."""
    safe = jnp.maximum(idx, 0)[:, None, :, None]
    out = jnp.take_along_axis(x, safe, axis=2)
    return out * (idx >= 0)[:, None, :, None].astype(x.dtype)


# ===================================================================== #
# partial-attention + online-LSE merge substrate
# ===================================================================== #
def _merge_step(acc, part):
    """Online-LSE merge of two partials; associative and (to fp rounding)
    commutative — hop order never changes the result beyond tolerance."""
    ao, am, al = acc
    o, m, l = part
    m_new = jnp.maximum(am, m)
    c1 = jnp.exp(am - m_new)
    c2 = jnp.exp(m - m_new)
    return (ao * c1[..., None] + o * c2[..., None], m_new, al * c1 + l * c2)


def merge_partials(parts):
    """Fold a sequence of (o, m, l) partials into one (tests/benchmarks)."""
    acc = None
    for p in parts:
        acc = p if acc is None else _merge_step(acc, p)
    return acc


def merge_partials_axis(part, axis_name):
    """Collective form of :func:`merge_partials`: fold one (o, m, l)
    partial per rank across a mesh axis (inside shard_map/pmap).  The
    global row max moves via pmax; every rank rescales to it and psums
    the accumulator and the sum — the distributed flash-decode LSE merge
    (serving: each rank attends its cache shard, then merges here)."""
    o, m, l = part
    m_g = jax.lax.pmax(m, axis_name)
    c = jnp.exp(m - m_g)
    o_g = jax.lax.psum(o * c[..., None], axis_name)
    l_g = jax.lax.psum(l * c, axis_name)
    return o_g, m_g, l_g


def finalize_partial(part, dtype):
    """Normalize a merged partial into the attention output (0 where no
    KV was visible)."""
    o, _, l = part
    out = jnp.where(l[..., None] > 0, o / jnp.maximum(l[..., None], 1e-30),
                    0.0)
    return out.astype(dtype)


def _partial_masked_attention(q, k, v, q_doc, q_pos, kv_doc, kv_pos, *,
                              impl, scale, q_chunk, interpret, tables=None,
                              block_q=128, block_k=128, grid="rect"):
    """Merge-ready partial against one KV subset, on either kernel.

    The Pallas kernel emits the normalized ``(o, lse)`` form, re-expressed
    as the triple ``(o, m=lse, l=1)``; the two forms are interchangeable
    under :func:`_merge_step` (``o * exp(lse - M)`` recovers the
    unnormalized accumulator either way).  ``lse`` is clamped to the
    finite NEG stand-in so empty rows contribute weight exp(NEG - M) = 0
    and their cotangent is dropped by the clamp's gradient.
    """
    from repro.kernels import ops as kops

    if impl == "pallas":
        assert tables is not None, "pallas CP attention needs host tables"
        o, lse = kops.doc_flash_attention(
            q, k, v, q_doc, q_pos, kv_doc, kv_pos, tables, scale=scale,
            interpret=interpret, block_q=block_q, block_k=block_k,
            grid=grid, partial=True)
        m = jnp.maximum(lse, NEG)
        return o.astype(jnp.float32), m, jnp.ones_like(m)
    return kops.doc_attention_xla(q, k, v, q_doc, q_pos, kv_doc, kv_pos,
                                  scale=scale, q_chunk=q_chunk, partial=True)


def _masked_attention(q, k, v, q_doc, q_pos, kv_doc, kv_pos, *, impl,
                      q_chunk, interpret, tables=None, block_q=128,
                      block_k=128, grid="rect"):
    from repro.kernels import ops as kops

    if impl == "pallas":
        assert tables is not None, "pallas CP attention needs host tables"
        return kops.doc_flash_attention(q, k, v, q_doc, q_pos, kv_doc,
                                        kv_pos, tables, interpret=interpret,
                                        block_q=block_q, block_k=block_k,
                                        grid=grid)
    return kops.doc_attention_xla(q, k, v, q_doc, q_pos, kv_doc, kv_pos,
                                  q_chunk=q_chunk)


# ===================================================================== #
# islands
# ===================================================================== #
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _quantized_gather(x, axis_name):
    """int8 all-gather with per-(batch, head, token) scales — beyond-paper
    comm compression of the Eq. 5 KV exchange (EXPERIMENTS.md §Perf #6).

    Straight-through backward: ``round`` has zero gradient, so the VJP is
    defined explicitly as the transpose of a plain gather — a full-precision
    reduce-scatter of dKV (gradients stay exact; only the forward KV wire
    is quantized)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q8 = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127,
                  127).astype(jnp.int8)
    g8 = jax.lax.all_gather(q8, axis_name, axis=2, tiled=True)
    gs = jax.lax.all_gather(scale.astype(jnp.float32), axis_name, axis=2,
                            tiled=True)
    return (g8.astype(jnp.float32) * gs).astype(x.dtype)


def _quantized_gather_fwd(x, axis_name):
    return _quantized_gather(x, axis_name), None


def _quantized_gather_bwd(axis_name, _, g):
    return (jax.lax.psum_scatter(g, axis_name, scatter_dimension=2,
                                 tiled=True),)


_quantized_gather.defvjp(_quantized_gather_fwd, _quantized_gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _quantized_ppermute(x, axis_name, perm):
    """int8 ppermute hop with per-(batch, head, token) scales — the
    chunked-exchange counterpart of :func:`_quantized_gather`.

    Straight-through backward: the hop's transpose is the inverse
    ppermute of the full-precision cotangent, so gradients stay exact and
    only the forward KV wire is quantized.  Each hop requantizes the
    arriving (already dequantized) buffer, so per-hop error accumulates
    over the ring — bounded by hops x one quantization step.
    """
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q8 = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127,
                  127).astype(jnp.int8)
    g8 = jax.lax.ppermute(q8, axis_name, perm)
    gs = jax.lax.ppermute(scale.astype(jnp.float32), axis_name, perm)
    return (g8.astype(jnp.float32) * gs).astype(x.dtype)


def _quantized_ppermute_fwd(x, axis_name, perm):
    return _quantized_ppermute(x, axis_name, perm), None


def _quantized_ppermute_bwd(axis_name, perm, _, g):
    inv = tuple((d, s) for (s, d) in perm)
    return (jax.lax.ppermute(g, axis_name, inv),)


_quantized_ppermute.defvjp(_quantized_ppermute_fwd, _quantized_ppermute_bwd)


@jax.named_scope(EXCHANGE_SCOPE)
def _gather_kv(k, v, kv_comm_dtype):
    """All-gather of K and V over the CP axis (int8 on the wire if asked)."""
    if kv_comm_dtype == "int8":
        return _quantized_gather(k, CP_AXIS), _quantized_gather(v, CP_AXIS)
    return (jax.lax.all_gather(k, CP_AXIS, axis=2, tiled=True),
            jax.lax.all_gather(v, CP_AXIS, axis=2, tiled=True))


def _wire_permute(x, perm, kv_comm_dtype):
    if kv_comm_dtype == "int8":
        return _quantized_ppermute(x, CP_AXIS, perm)
    return jax.lax.ppermute(x, CP_AXIS, perm)


# ===================================================================== #
# chunked-exchange engine: attend arriving KV while the next hop flies
# ===================================================================== #
def _run_hops(init_part, payload, n_hops, attend, hop_xs=None,
              kv_comm_dtype="native"):
    """Ring-rotate ``payload = (kc, vc, dc, pc)`` for ``n_hops`` hops,
    merging ``attend(kc, vc, dc, pc, xs)`` partials onto ``init_part``.

    Transfer/compute pipelining: the payload is launched to the neighbor
    *before* any remote attention (that first hop flies while the caller's
    local-KV partial computes), and each scan iteration forwards the
    arrived buffer in the same breath as attending it — the forward
    depends only on the buffer, never on the attention, so the XLA
    latency-hiding scheduler keeps hop h+1 in flight under hop h's
    compute.  The final hop is attended outside the scan and not
    forwarded, so total wire volume is exactly ``n_hops`` buffer hops —
    the same bytes as the monolithic all-gather, pipelined.  The scan
    transpose reverses the ppermute chain, routing each hop's dKV back to
    the owning rank at the same wire volume as the forward exchange.
    """
    if n_hops <= 0:
        return init_part
    N = axis_size(CP_AXIS)
    perm = tuple((i, (i + 1) % N) for i in range(N))

    @jax.named_scope(EXCHANGE_SCOPE)
    def fwd(kc, vc, dc, pc):
        return (_wire_permute(kc, perm, kv_comm_dtype),
                _wire_permute(vc, perm, kv_comm_dtype),
                jax.lax.ppermute(dc, CP_AXIS, perm),
                jax.lax.ppermute(pc, CP_AXIS, perm))

    payload = fwd(*payload)       # hop 1 in flight under the local partial

    def step(carry, xs):
        kc, vc, dc, pc, acc, m, l = carry
        nxt = fwd(kc, vc, dc, pc)
        part = attend(kc, vc, dc, pc, xs)
        acc, m, l = _merge_step((acc, m, l), part)
        return (*nxt, acc, m, l), None

    xs_scan = xs_last = None
    if hop_xs is not None:
        xs_scan = tuple(a[:n_hops - 1] for a in hop_xs)
        xs_last = tuple(a[n_hops - 1] for a in hop_xs)
    carry, _ = jax.lax.scan(step, (*payload, *init_part), xs_scan,
                            length=n_hops - 1)
    last = attend(*carry[:4], xs_last)
    return _merge_step(carry[4:], last)


def _unpack_rank_tables(tabs):
    """Strip the sharded-to-1 rank dim of per-rank table arrays."""
    if tabs is None:
        return None
    return tuple(t[:, 0] for t in tabs)


def _hop_xs_of(hop_tabs):
    """(b, H, ...) hop tables -> scan xs with the hop axis leading."""
    if hop_tabs is None:
        return None
    return tuple(jnp.moveaxis(t, 1, 0) for t in hop_tabs)


def _flashcp_island(q, k, v, doc, pos, send_idx, gath_doc, gath_pos,
                    *, impl, q_chunk, interpret, tables=None, block_q=128,
                    block_k=128, grid="rect", kv_comm_dtype="native"):
    b = q.shape[0]
    N = axis_size(CP_AXIS)
    me = jax.lax.axis_index(CP_AXIS)
    buf = send_idx.shape[-1]

    sidx = send_idx[:, 0]                       # (b, buf)
    ksel = _take_tokens(k, sidx)
    vsel = _take_tokens(v, sidx)
    kg, vg = _gather_kv(ksel, vsel, kv_comm_dtype)

    # hide my own gathered segment (those tokens are already local KV)
    seg = jnp.arange(N * buf, dtype=jnp.int32) // buf
    gdoc = jnp.where((seg == me)[None, :], -2, gath_doc)

    kv_k = jnp.concatenate([k, kg], axis=2)
    kv_v = jnp.concatenate([v, vg], axis=2)
    kv_doc = jnp.concatenate([doc, gdoc], axis=1)
    kv_pos = jnp.concatenate([pos, gath_pos], axis=1)

    tabs = None
    if tables is not None:
        tabs = tuple(t[:, 0] if t.ndim > 2 and t.shape[1] == 1 else t
                     for t in tables)
    return _masked_attention(q, kv_k, kv_v, doc, pos, kv_doc, kv_pos,
                             impl=impl, q_chunk=q_chunk, interpret=interpret,
                             tables=tabs, block_q=block_q, block_k=block_k,
                             grid=grid)


def _flashcp_island_chunked(q, k, v, doc, pos, send_idx, gath_doc, gath_pos,
                            *, impl, scale, q_chunk, interpret,
                            loc_tables=None, hop_tables=None, block_q=128,
                            block_k=128, grid="rect",
                            kv_comm_dtype="native"):
    """Overlapped sharding-aware exchange: the compacted Eq.-5 buffer
    moves in N-1 ppermute hops; each arriving buffer attends while the
    next hop is in flight, and local-KV attention overlaps hop 0.  After
    hop h a rank holds the buffer of rank (me - h) mod N, so the N-1 hops
    visit exactly the other ranks — the monolithic island's self-segment
    masking is unnecessary by construction."""
    N = axis_size(CP_AXIS)
    me = jax.lax.axis_index(CP_AXIS)
    buf = send_idx.shape[-1]

    sidx = send_idx[:, 0]                       # (b, buf)
    ksel = _take_tokens(k, sidx)
    vsel = _take_tokens(v, sidx)
    # this rank's slice of the (replicated) gathered-buffer metadata
    my_doc = jax.lax.dynamic_slice_in_dim(gath_doc, me * buf, buf, axis=1)
    my_pos = jax.lax.dynamic_slice_in_dim(gath_pos, me * buf, buf, axis=1)

    attend = functools.partial(
        _partial_masked_attention, impl=impl, scale=scale, q_chunk=q_chunk,
        interpret=interpret, block_q=block_q, block_k=block_k, grid=grid)
    init = attend(q, k, v, doc, pos, doc, pos,
                  tables=_unpack_rank_tables(loc_tables))

    def hop_attend(kc, vc, dc, pc, xs):
        return attend(q, kc, vc, doc, pos, dc, pc, tables=xs)

    part = _run_hops(init, (ksel, vsel, my_doc, my_pos), N - 1, hop_attend,
                     hop_xs=_hop_xs_of(_unpack_rank_tables(hop_tables)),
                     kv_comm_dtype=kv_comm_dtype)
    return finalize_partial(part, q.dtype)


def _allgather_island(q, k, v, doc, pos, *, impl, q_chunk, interpret,
                      tables=None, block_q=128, block_k=128, grid="rect",
                      kv_comm_dtype="native"):
    kg, vg = _gather_kv(k, v, kv_comm_dtype)
    with jax.named_scope(EXCHANGE_SCOPE):
        gdoc = jax.lax.all_gather(doc, CP_AXIS, axis=1, tiled=True)
        gpos = jax.lax.all_gather(pos, CP_AXIS, axis=1, tiled=True)
    return _masked_attention(q, kg, vg, doc, pos, gdoc, gpos, impl=impl,
                             q_chunk=q_chunk, interpret=interpret,
                             tables=_unpack_rank_tables(tables),
                             block_q=block_q, block_k=block_k, grid=grid)


def _gather_island_chunked(q, k, v, doc, pos, *, impl, scale, q_chunk,
                           interpret, loc_tables=None, hop_tables=None,
                           block_q=128, block_k=128, grid="rect",
                           kv_comm_dtype="native"):
    """Overlapped full-KV exchange (allgather strategies, ring): the full
    local KV ring-rotates in N-1 hops on the merge substrate — identical
    results to the monolithic gather, with the wire pipelined behind
    per-hop attention."""
    attend = functools.partial(
        _partial_masked_attention, impl=impl, scale=scale, q_chunk=q_chunk,
        interpret=interpret, block_q=block_q, block_k=block_k, grid=grid)
    init = attend(q, k, v, doc, pos, doc, pos,
                  tables=_unpack_rank_tables(loc_tables))

    def hop_attend(kc, vc, dc, pc, xs):
        return attend(q, kc, vc, doc, pos, dc, pc, tables=xs)

    part = _run_hops(init, (k, v, doc, pos), axis_size(CP_AXIS) - 1,
                     hop_attend,
                     hop_xs=_hop_xs_of(_unpack_rank_tables(hop_tables)),
                     kv_comm_dtype=kv_comm_dtype)
    return finalize_partial(part, q.dtype)


def _ring_island(q, k, v, doc, pos, *, q_chunk, scale):
    """Seed Ring-Attention loop (pure XLA), kept as the ``overlap="none"``
    parity baseline; the chunked engine generalizes it with Pallas-kernel
    hops and int8 wire support."""
    b, Hq, T, D = q.shape
    N = axis_size(CP_AXIS)
    perm = [(i, (i + 1) % N) for i in range(N)]

    acc = jnp.zeros((b, Hq, T, D), jnp.float32)
    m = jnp.full((b, Hq, T), NEG, jnp.float32)
    l = jnp.zeros((b, Hq, T), jnp.float32)

    def step(carry, _):
        kc, vc, dc, pc, acc, m, l = carry
        part = _partial_masked_attention(
            q, kc, vc, doc, pos, dc, pc, impl="xla", scale=scale,
            q_chunk=q_chunk, interpret=False)
        acc, m, l = _merge_step((acc, m, l), part)
        with jax.named_scope(EXCHANGE_SCOPE):
            kc = jax.lax.ppermute(kc, CP_AXIS, perm)
            vc = jax.lax.ppermute(vc, CP_AXIS, perm)
            dc = jax.lax.ppermute(dc, CP_AXIS, perm)
            pc = jax.lax.ppermute(pc, CP_AXIS, perm)
        return (kc, vc, dc, pc, acc, m, l), None

    (kc, vc, dc, pc, acc, m, l), _ = jax.lax.scan(
        step, (k, v, doc, pos, acc, m, l), None, length=N)
    return finalize_partial((acc, m, l), q.dtype)


def _moe_island(x, topi, gates, wi, wg, wo, *, kind, capacity_factor,
                top_k):
    """Expert-parallel dispatch: local capacity-clipped routing buffers
    exchanged with all-to-all over the ``model`` axis (experts are sharded
    over that axis), expert FFN on owned experts, all-to-all back, local
    weighted combine."""
    from repro.models.moe import (capacity, combine_local, dispatch_local,
                                  expert_ffn)

    b, t, d = x.shape
    N = axis_size(CP_AXIS)
    E_local = wi.shape[0]
    E = E_local * N
    n = b * t
    cap = capacity(n, E, top_k, capacity_factor)

    buf, slot, tok_s, gat_s, keep = dispatch_local(
        x.reshape(n, d), topi.reshape(n, -1), gates.reshape(n, -1), E, cap)
    # (E, cap, d) -> exchange: rank r receives all ranks' slices for its
    # E/N experts -> (E/N, N*cap, d)
    buf = jax.lax.all_to_all(buf, CP_AXIS, split_axis=0, concat_axis=1,
                             tiled=True)
    y = expert_ffn(buf, wi, wg, wo, kind)
    y = jax.lax.all_to_all(y, CP_AXIS, split_axis=1, concat_axis=0,
                           tiled=True)                     # (E, cap, d)
    out = combine_local(y, slot, tok_s, gat_s, keep, n)
    return out.reshape(b, t, d)


def _selective_scan_island(dt, A, Bm, Cm, xf, reset):
    """Fused chunkwise selective scan with CP rank hand-off.

    Pass 1 computes each rank's (decay, state) summary; an all-gather +
    associative prefix combine yields each rank's initial state; pass 2
    produces y with chunk-local memory (models/context.py).
    """
    from repro.models.context import local_selective_scan

    N = axis_size(CP_AXIS)
    me = jax.lax.axis_index(CP_AXIS)

    A_rank, S_rank = local_selective_scan(dt, A, Bm, Cm, xf, reset,
                                          summary_only=True)
    gA = jax.lax.all_gather(A_rank, CP_AXIS, axis=0)
    gS = jax.lax.all_gather(S_rank, CP_AXIS, axis=0)

    def comb(carry, j):
        A_c, S_c = carry
        take = j < me
        A_n = jnp.where(take, gA[j] * A_c, A_c)
        S_n = jnp.where(take, gS[j] + gA[j] * S_c, S_c)
        return (A_n, S_n), None

    init = (jnp.ones_like(A_rank), jnp.zeros_like(S_rank))
    (_, S0), _ = jax.lax.scan(comb, init, jnp.arange(N))
    return local_selective_scan(dt, A, Bm, Cm, xf, reset, init_state=S0)


def _ssm_island(a, x):
    """Cross-rank recurrence: local scan + associative prefix combine."""
    N = axis_size(CP_AXIS)
    me = jax.lax.axis_index(CP_AXIS)

    h_loc = local_ssm_scan(a, x)
    # decay track kept at a's (possibly broadcast/singleton) shape
    cum_a = local_ssm_scan(a, jnp.zeros_like(a), init=jnp.ones_like(a[:, 0]))

    A_tot = cum_a[:, -1]                        # (b, ...)
    h_last = h_loc[:, -1]
    gA = jax.lax.all_gather(A_tot, CP_AXIS, axis=0)     # (N, b, ...)
    gH = jax.lax.all_gather(h_last, CP_AXIS, axis=0)

    def comb(carry, j):
        A_c, H_c = carry
        take = j < me
        A_n = jnp.where(take, gA[j] * A_c, A_c)
        H_n = jnp.where(take, gH[j] + gA[j] * H_c, H_c)
        return (A_n, H_n), None

    init = (jnp.ones_like(A_tot), jnp.zeros_like(h_last))
    (_, H_prev), _ = jax.lax.scan(comb, init, jnp.arange(N))
    return h_loc + cum_a * jnp.expand_dims(H_prev, 1)


# ===================================================================== #
# context factory
# ===================================================================== #
MONO_TABLE_KEYS = table_keys("tab_", "rect")
LOC_TABLE_KEYS = table_keys("tab_loc_", "rect")
HOP_TABLE_KEYS = table_keys("tab_hop_", "rect")


def make_cp_context(
    mesh,
    plan_arrays: dict[str, Any],
    *,
    strategy: str = "flashcp",
    impl: str = "xla",
    batch_axes=("data",),
    head_dim: int,
    q_chunk: int = 512,
    overlap: str = "chunked",
    interpret: bool = False,
    tables: tuple | None = None,
    block_q: int = 128,
    block_k: int = 128,
    grid: str = "rect",
    kv_comm_dtype: str = "native",
) -> ExecContext:
    """Build the ExecContext driving a CP training/prefill step.

    ``plan_arrays`` are the (jnp) outputs of
    :func:`repro.planner.encode.encode_plan_batch`, in global (B, ·) view,
    optionally extended with per-rank Pallas visit tables (``tab_*`` keys,
    :func:`repro.planner.encode.emit_visit_tables`).

    ``overlap="chunked"`` (default) runs the overlapped chunked-KV
    exchange engine; ``overlap="none"`` the original monolithic islands.
    ``impl="pallas"`` requires visit tables matching ``grid``: the
    rectangular 4-tuple layout for ``grid="rect"`` (``tables=`` or
    ``tab_*`` plan arrays) or the flattened work-queue 6-tuple layout
    for ``grid="flat"`` (``tab_*{fq,rq}_*`` plan arrays); the chunked
    engine takes per-rank local + per-hop sets either way (``tab_loc_*``
    / ``tab_hop_*``).
    """
    overlap = resolve_overlap(strategy, impl, overlap)
    if grid not in ("rect", "flat"):
        raise ValueError(f"unknown kernel grid {grid!r}")
    doc = plan_arrays["doc"]
    pos = plan_arrays["pos"]
    b = tuple(batch_axes) if isinstance(batch_axes, (tuple, list)) \
        else (batch_axes,)
    B = b[0] if len(b) == 1 else b      # P dim entry: name or tuple of names
    scale = head_dim ** -0.5
    n_tab = 2 * GRID_TABLE_HALF[grid]   # arrays per table set

    qkv_spec = P(B, None, CP_AXIS, None)
    tok_spec = P(B, CP_AXIS)

    def _plan_tables(keys):
        if all(k in plan_arrays for k in keys):
            return tuple(plan_arrays[k] for k in keys)
        return None

    def _table_specs(arrs):
        return [P(B, CP_AXIS, *([None] * (a.ndim - 2))) for a in arrs]

    def _chunked_tables(what):
        if impl != "pallas":
            return ()
        loc = _plan_tables(table_keys("tab_loc_", grid))
        hop = _plan_tables(table_keys("tab_hop_", grid))
        if loc is None or hop is None:
            raise ValueError(
                f"pallas {what} with overlap='chunked' needs per-rank "
                f"local + per-hop grid={grid!r} visit tables "
                "(tab_loc_*/tab_hop_* plan arrays; see "
                "repro.planner.encode.emit_visit_tables)")
        return loc + hop

    def _mono_tables(what):
        if impl != "pallas":
            return ()
        mono = tables if tables is not None \
            else _plan_tables(table_keys("tab_", grid))
        if mono is None:
            raise ValueError(
                f"pallas {what} needs grid={grid!r} visit tables "
                "(tables= or tab_* plan arrays; see "
                "repro.planner.encode.emit_visit_tables)")
        return tuple(mono)

    if strategy in ("flashcp", "contiguous"):
        base_args = (plan_arrays["send_idx"], plan_arrays["gath_doc"],
                     plan_arrays["gath_pos"])
        base_specs = [qkv_spec, qkv_spec, qkv_spec, tok_spec, tok_spec,
                      P(B, CP_AXIS, None), P(B, None), P(B, None)]
        if overlap == "chunked":
            tabs = _chunked_tables("flashcp")

            def island(q, k, v, d_, p_, si, gd, gp, *tt):
                return _flashcp_island_chunked(
                    q, k, v, d_, p_, si, gd, gp, impl=impl, scale=scale,
                    q_chunk=q_chunk, interpret=interpret,
                    loc_tables=tt[:n_tab] or None,
                    hop_tables=tt[n_tab:] or None,
                    block_q=block_q, block_k=block_k, grid=grid,
                    kv_comm_dtype=kv_comm_dtype)
        else:
            tabs = _mono_tables("flashcp")

            def island(q, k, v, d_, p_, si, gd, gp, *tt):
                return _flashcp_island(
                    q, k, v, d_, p_, si, gd, gp, impl=impl, q_chunk=q_chunk,
                    interpret=interpret, tables=tt or None,
                    block_q=block_q, block_k=block_k, grid=grid,
                    kv_comm_dtype=kv_comm_dtype)

        in_specs = base_specs + _table_specs(tabs)
        args = base_args + tabs

        def attn(q, k, v):
            f = shard_map(island, mesh=mesh, in_specs=tuple(in_specs),
                          out_specs=qkv_spec, check_vma=False)
            return f(q, k, v, doc, pos, *args)

    elif strategy in ("allgather", "llama3", "per_doc", "ring",
                      "ring_zigzag"):
        is_ring = strategy in ("ring", "ring_zigzag")
        if overlap == "chunked":
            tabs = _chunked_tables(strategy)

            def island(q, k, v, d_, p_, *tt):
                return _gather_island_chunked(
                    q, k, v, d_, p_, impl=impl, scale=scale,
                    q_chunk=q_chunk, interpret=interpret,
                    loc_tables=tt[:n_tab] or None,
                    hop_tables=tt[n_tab:] or None,
                    block_q=block_q, block_k=block_k, grid=grid,
                    kv_comm_dtype=kv_comm_dtype)
        elif is_ring:
            tabs = ()
            island = functools.partial(_ring_island, q_chunk=q_chunk,
                                       scale=scale)
        else:
            tabs = _mono_tables(strategy)

            def island(q, k, v, d_, p_, *tt):
                return _allgather_island(
                    q, k, v, d_, p_, impl=impl, q_chunk=q_chunk,
                    interpret=interpret, tables=tt or None,
                    block_q=block_q, block_k=block_k, grid=grid,
                    kv_comm_dtype=kv_comm_dtype)

        in_specs = [qkv_spec, qkv_spec, qkv_spec, tok_spec, tok_spec] \
            + _table_specs(tabs)

        def attn(q, k, v):
            f = shard_map(island, mesh=mesh, in_specs=tuple(in_specs),
                          out_specs=qkv_spec, check_vma=False)
            return f(q, k, v, doc, pos, *tabs)

    else:
        raise ValueError(f"unknown CP strategy {strategy!r}")

    def ssm_scan(a, x):
        a_spec = P(B, CP_AXIS, *([None] * (a.ndim - 2)))
        x_spec = P(B, CP_AXIS, *([None] * (x.ndim - 2)))
        f = shard_map(_ssm_island, mesh=mesh,
                          in_specs=(a_spec, x_spec), out_specs=x_spec,
                          check_vma=False)
        return f(a, x)

    def selective_scan(dt, A, Bm, Cm, xf, reset):
        tok = P(B, CP_AXIS)
        tok3 = P(B, CP_AXIS, None)
        f = shard_map(
            _selective_scan_island, mesh=mesh,
            in_specs=(tok3, P(None, None), tok3, tok3, tok3, tok),
            out_specs=tok3, check_vma=False)
        return f(dt, A, Bm, Cm, xf, reset)

    def ep_dispatch(x, topi, gates, params, *, kind, capacity_factor):
        tok3 = P(B, CP_AXIS, None)
        expert = P("model", None, None)
        island = functools.partial(_moe_island, kind=kind,
                                   capacity_factor=capacity_factor,
                                   top_k=topi.shape[-1])
        wg = params.get("wg")
        if wg is None:
            wg = params["wi"]      # unused by gelu path; keeps arity static
        f = shard_map(
            island, mesh=mesh,
            in_specs=(tok3, tok3, tok3, expert, expert, expert),
            out_specs=tok3, check_vma=False)
        return f(x, topi, gates, params["wi"], wg, params["wo"])

    from jax.sharding import NamedSharding

    return ExecContext(doc=doc, pos=pos, attn=attn, ssm_scan=ssm_scan,
                       selective_scan=selective_scan,
                       act_sharding=NamedSharding(mesh, P(B, CP_AXIS, None)),
                       extras={"ep_dispatch": ep_dispatch})
