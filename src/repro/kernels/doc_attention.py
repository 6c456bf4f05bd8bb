"""Pallas TPU kernels: document-masked causal flash attention with
block-level sparsity (the compute hot-spot of FlashCP training).

TPU adaptation of the paper's kernel-efficiency insight (§2.3, Fig. 3):
instead of CUDA varlen batching, we exploit the *structure* FlashCP's
planner creates — whole documents laid out contiguously — with
splash-attention-style **visit tables**: the host enumerates, per query
block, exactly the KV blocks that contain any visible (same-document,
causal) key, and the kernel fetches KV via scalar-prefetched ``index_map``
lookups, so *skipped blocks are never fetched from HBM, let alone
computed*.

Two grid schedules walk those tables (``grid=`` on every kernel entry):

* ``grid="rect"`` — the original rectangular launch ``(B, H, nq, V)``
  where ``V`` is the *maximum* per-row visit count.  Padded visit slots
  repeat the previous block index (a no-op refetch under Pallas's
  revisiting pipeline) and are compute-gated by the per-row counts, but
  every padded slot still costs a grid step: on imbalanced document
  mixes the longest row's ``V`` taxes all ``B * nq`` rows.
* ``grid="flat"`` — a **flattened 1D work queue** ``(B, H, S)``: the
  host emits the CSR-style visit list itself, one grid step per *actual*
  visit, with per-step ``(row, col)`` owner metadata and
  FIRST/LAST/VALID flags marking block-row boundaries
  (``build_work_queue``).  Rows are sorted by descending visit count
  (greedy LPT — long rows schedule first, so a core-split grid stays
  balanced on skewed doc mixes), each row's steps stay contiguous (the
  accumulator scratch carries one row at a time), and zero-visit rows
  get a single sentinel step that writes their zero output.  Padding
  waste is erased: total steps equal the visit count (plus one sentinel
  per empty row and a pow2 tail bucket).

**GQA head packing.**  ``H`` above is not the query-head count: one grid
step covers one KV head and one visit, and computes it for ``hps`` query
heads of that KV head's group at once (``_heads_per_step``: the largest
divisor of the group whose step fits ``STEP_VMEM_BUDGET``; MHA gives 1).
Query-side blocks (q, dO, out, dQ, lse/delta tiles) are ``(1, hps,
block_q, ·)``, viewed inside the kernel as ``hps * block_q`` matmul rows;
the K/V block and the doc mask (``_visible``, one ``(block_q, block_k)``
tile broadcast over the heads) are fetched and built once per step.
fwd and dQ run ``H = Hkv * group // hps`` head blocks.  dK/dV runs
``(B, Hkv, nk, Vq, chunks)`` (rect) or ``(B, Hkv, S, chunks)`` (flat):
``dV += Pᵀ·dO`` and ``dK += dSᵀ·Q`` contract over all packed rows in one
matmul each, and the innermost ``chunks = group // hps`` axis (1 when
the whole group fits) walks the rest of the group with init/finalize on
its first/last index.  Per head every kernel computes what the one-head
step computes; only the summation order over heads inside dK/dV
differs.  Each entry point records ``attn.heads_per_step`` and
``attn.grid_steps`` in a ``repro.obs`` span ``attn.kernel_build`` when
it builds its ``pallas_call`` (at trace time, once per compile).

Whole-doc placement ⇒ long contiguous visible ranges ⇒ few partial blocks
and maximal MXU occupancy — exactly the paper's "kernel efficiency" axis,
re-expressed for the TPU memory hierarchy (HBM→VMEM streaming + 128×128
MXU tiles).

Layout (GQA): q (B, Hq, Tq, D); k, v (B, Hkv, Tk, D); per-token metadata
``q_doc/q_pos`` (B, Tq) and ``kv_doc/kv_pos`` (B, Tk) int32; doc id < 0 is
padding.  Visibility: same doc AND q_pos >= kv_pos.  The entry points
re-tile the metadata and the lse/delta row statistics into the
lane/sublane-broadcast layout the TPU block-shape rule admits (see
``_tile_meta``) and return lse as (B, Hq, Tq).

The pure-jnp oracle lives in ``ref.py``; jit'd wrappers + custom VJP in
``ops.py``.  All kernels are validated against the oracle with
``interpret=True`` sweeps in tests/test_kernels.py; flat-vs-rect parity
and queue/permutation properties live in tests/test_workqueue.py.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.table_layout import (FLAG_FIRST, FLAG_LAST, FLAG_VALID,
                                GRID_TABLE_HALF)

__all__ = [
    "BlockTables",
    "build_block_tables",
    "build_work_queue",
    "flash_fwd",
    "flash_bwd_dq",
    "flash_bwd_dkv",
    "DEFAULT_BLOCK_Q",
    "DEFAULT_BLOCK_K",
    "FLAG_FIRST",
    "FLAG_LAST",
    "FLAG_VALID",
]

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG = -1e30  # finite -inf stand-in inside kernels (no nan from inf-inf)

KIND_SKIP, KIND_PARTIAL, KIND_FULL = 0, 1, 2


# ===================================================================== #
# host-side visit tables
# ===================================================================== #
@dataclasses.dataclass
class BlockTables:
    """Scalar-prefetch tables driving the sparse grid.

    Rectangular layout (``grid="rect"``):
      fwd:  for each (b, q-block): the KV blocks to visit.
      bwd:  for each (b, kv-block): the Q blocks that visit it (reverse
      map).  Padded slots repeat the last valid index (cheap revisits)
      and are gated by the ``*_nvis`` counts.

    Flattened work-queue layout (``grid="flat"``): per sample, the same
    visit sets as a CSR step list — ``fq_*`` walks q-block rows (fwd +
    dQ), ``rq_*`` kv-block rows (dKV).  ``*_row``/``*_col`` are the
    per-step owner block and visited block; ``*_flags`` carries the
    FIRST/LAST/VALID row-boundary bits.  Rows are LPT-ordered
    (descending visit count) and zero-visit rows hold one !VALID
    sentinel step so their output still gets written.  The queues are
    derived lazily from the rectangular tables on first access
    (rect-only consumers never pay the flatten cost).
    """

    kv_idx: np.ndarray    # (B, nq, Vk) int32
    kv_nvis: np.ndarray   # (B, nq)     int32
    q_idx: np.ndarray     # (B, nk, Vq) int32
    q_nvis: np.ndarray    # (B, nk)     int32
    block_q: int
    block_k: int
    # occupancy stats — the kernel-efficiency metric used by benchmarks
    visited_frac: float   # visited blocks / all blocks
    full_frac: float      # fully-visible blocks / visited blocks
    # lazily-built flattened work queues (same visit sets, 1D schedule)
    _queues: tuple = dataclasses.field(default=None, repr=False)

    def _flat(self):
        if self._queues is None:
            self._queues = (*build_work_queue(self.kv_idx, self.kv_nvis),
                            *build_work_queue(self.q_idx, self.q_nvis))
        return self._queues

    @property
    def fq_row(self):     # (B, Sf) int32 owner q block per step
        return self._flat()[0]

    @property
    def fq_col(self):     # (B, Sf) int32 visited KV block
        return self._flat()[1]

    @property
    def fq_flags(self):   # (B, Sf) int32 FIRST|LAST|VALID bits
        return self._flat()[2]

    @property
    def rq_row(self):     # (B, Sr) int32 owner KV block per step
        return self._flat()[3]

    @property
    def rq_col(self):     # (B, Sr) int32 visiting Q block
        return self._flat()[4]

    @property
    def rq_flags(self):   # (B, Sr) int32
        return self._flat()[5]

    def as_jax(self):
        return (jnp.asarray(self.kv_idx), jnp.asarray(self.kv_nvis),
                jnp.asarray(self.q_idx), jnp.asarray(self.q_nvis))

    def flat_as_jax(self):
        """The 6-tuple the ``grid="flat"`` kernels consume."""
        return tuple(jnp.asarray(a) for a in self._flat())

    def grid_steps(self) -> dict[str, int]:
        """Executed grid-step counts per head block of both schedules —
        the padding-waste accounting the kernel-efficiency bench
        reports."""
        B, nq, Vk = self.kv_idx.shape
        _, nk, Vq = self.q_idx.shape
        return {
            "rect_fwd": B * nq * Vk,
            "rect_rev": B * nk * Vq,
            "flat_fwd": int(self.fq_row.shape[0] * self.fq_row.shape[1]),
            "flat_rev": int(self.rq_row.shape[0] * self.rq_row.shape[1]),
            "visits": int(self.kv_nvis.sum()),
        }


def _pad_lists(lists: list[list[int]], width: int) -> np.ndarray:
    out = np.zeros((len(lists), width), dtype=np.int32)
    for i, l in enumerate(lists):
        if l:
            out[i, : len(l)] = l
            out[i, len(l):] = l[-1]  # repeat-last padding => no-op refetch
    return out


def build_work_queue(idx: np.ndarray, nvis: np.ndarray, *,
                     pad_to_steps: int | None = None):
    """Flatten rectangular visit tables into the 1D work-queue schedule.

    ``idx`` (B, R, V) / ``nvis`` (B, R) are one direction of a
    :class:`BlockTables` (kv_idx/kv_nvis for the fwd+dQ queue,
    q_idx/q_nvis for the dKV reverse queue).  Returns ``(row, col,
    flags)``, each (B, S) int32, where per sample the steps are the
    row-major visit list re-ordered so rows run in descending visit
    count (greedy LPT — the longest block-rows schedule first) with each
    row's visits contiguous and in ascending block order.  Rows with
    zero visits contribute one sentinel step (``FLAG_VALID`` unset,
    FIRST|LAST set) so the kernel still zero-initializes and writes
    their output block.  Samples are padded to a common ``S`` (and to
    ``pad_to_steps`` if given) by repeating the final step with flags 0
    — a no-op refetch that never re-triggers init/finalize.
    """
    idx = np.asarray(idx, np.int32)
    nvis = np.asarray(nvis)
    B, R, V = idx.shape
    nv = nvis.astype(np.int64)
    counts = np.maximum(nv, 1)              # sentinel step for empty rows
    s_real = counts.sum(axis=1)
    S = int(s_real.max()) if B else 1
    if pad_to_steps is not None:
        assert pad_to_steps >= S, (pad_to_steps, S)
        S = pad_to_steps
    row = np.zeros((B, S), np.int32)
    col = np.zeros((B, S), np.int32)
    flags = np.zeros((B, S), np.int32)
    for b in range(B):
        order = np.argsort(-nv[b], kind="stable")
        oc = counts[b][order]
        total = int(s_real[b])
        excl = np.cumsum(oc) - oc
        owner = np.repeat(order, oc).astype(np.int64)
        offs = np.arange(total, dtype=np.int64) - np.repeat(excl, oc)
        valid = offs < nv[b][owner]
        row[b, :total] = owner
        col[b, :total] = idx[b][owner, np.minimum(offs, V - 1)]
        flags[b, :total] = (
            FLAG_FIRST * (offs == 0)
            + FLAG_LAST * (offs == counts[b][owner] - 1)
            + FLAG_VALID * valid)
        if total < S:                        # repeat-last no-op pad tail
            row[b, total:] = row[b, total - 1]
            col[b, total:] = col[b, total - 1]
    return row, col, flags


_BIG = np.int32(1 << 30)     # invalid-token sentinel in int32 summaries
_KEY = np.int64(1) << 31     # (doc, pos) composite-key stride


def _scatter_lists(rows: np.ndarray, vals: np.ndarray, nvis: np.ndarray,
                   width: int) -> np.ndarray:
    """(R, width) visit table from sorted pair lists.

    ``rows`` must be ascending; ``vals`` ascending within each row (the
    order ``np.nonzero`` / interval expansion produce).  Padded slots
    repeat the last valid index (a Pallas revisit no-op fetch); empty rows
    are zeros — identical layout to the legacy list-of-lists builder.
    """
    R = nvis.shape[0]
    starts = np.zeros(R + 1, np.int64)
    np.cumsum(nvis, out=starts[1:])
    slot = np.arange(vals.shape[0], dtype=np.int64) - starts[rows]
    idx = np.zeros((R, width), np.int32)
    idx[rows, slot] = vals
    pad = np.arange(width, dtype=np.int32)[None, :] >= nvis[:, None]
    last = idx[np.arange(R), np.maximum(nvis - 1, 0)]
    np.copyto(idx, np.broadcast_to(last[:, None], idx.shape), where=pad)
    return idx


def _summ32(doc: np.ndarray, pos: np.ndarray, blk: int):
    """Per-block int32 summaries: (dmin, dmax, pmin, pmax, all_valid,
    single_doc).  Empty blocks encode as dmin=BIG / dmax=-1, which makes
    the any-valid guards of the pair classification implicit."""
    d = doc.reshape(doc.shape[0], -1, blk)
    p = pos.reshape(pos.shape[0], -1, blk)
    valid = d >= 0
    dmin = np.where(valid, d, _BIG).min(-1).astype(np.int32)
    dmax = np.where(valid, d, -1).max(-1).astype(np.int32)
    pmin = np.where(valid, p, _BIG).min(-1).astype(np.int32)
    pmax = np.where(valid, p, -1).max(-1).astype(np.int32)
    return dmin, dmax, pmin, pmax, valid.all(-1), dmin == dmax


def _detect_segments(kdmin, kdmax, kpmin, kpmax, ksingle) -> np.ndarray:
    """Cut points splitting one row's KV blocks into runs whose summaries
    are (doc, pos)-monotone — the property the interval path needs.  A
    fully plan-ordered row is one segment; a FlashCP concat layout
    ``[local | gathered buffers]`` autosplits at each buffer boundary."""
    nonempty = kdmax >= 0
    edmin = np.where(nonempty, kdmin, _BIG)
    edmax = np.where(nonempty, kdmax, _BIG)
    brk = edmin[1:] < edmax[:-1]
    same = ksingle[1:] & ksingle[:-1] & (kdmin[1:] == kdmin[:-1])
    brk |= same & ((kpmin[1:] < kpmin[:-1]) | (kpmax[1:] < kpmax[:-1]))
    return np.flatnonzero(brk) + 1


def _pairs_dense(qs, ks):
    """O(nq*nk) classification of one row -> (visited pairs, full count).

    The seed's boolean logic with the validity guards folded into int32
    sentinel summaries (empty blocks can never satisfy the overlap test)."""
    qdmin, qdmax, qpmin, qpmax, q_all, qsing = qs
    kdmin, kdmax, kpmin, kpmax, k_all, ksing = ks
    vis = qdmax[:, None] >= kdmin[None, :]
    vis &= kdmax[None, :] >= qdmin[:, None]
    qd_s = np.where(qsing, qdmin, np.int32(-3))
    kd_s = np.where(ksing, kdmin, np.int32(-4))
    sd = qd_s[:, None] == kd_s[None, :]
    anti = sd & (qpmax[:, None] < kpmin[None, :])
    np.logical_not(anti, out=anti)
    vis &= anti
    qpf = np.where(q_all, qpmin, np.int32(-1))
    kpf = np.where(k_all, kpmax, _BIG)
    full = qpf[:, None] >= kpf[None, :]
    full &= sd
    full &= vis
    qrows, cols = np.nonzero(vis)
    nvis = np.count_nonzero(vis, axis=-1).astype(np.int32)
    return qrows, cols.astype(np.int32), nvis, int(full.sum())


def _pairs_intervals(qs, ks, cuts, nk):
    """Sorted-segment classification of one row in O((nq + pairs) log nk).

    Within a monotone KV segment the visited set of a q block is an index
    interval [lo, hi) (binary search on the doc summaries) minus an
    anti-causal *suffix* of its own doc's single-block run — at most two
    intervals per (q block, segment), expanded to pair lists with the
    same repeat/cumsum construction the plan encoder uses.  Exactly
    reproduces the dense classification (same summaries, same rules).
    """
    qdmin, qdmax, qpmin, qpmax, q_all, qsing = qs
    kdmin, kdmax, kpmin, kpmax, k_all, ksing = ks
    nq = qdmin.shape[0]
    nonempty = kdmax >= 0
    edmin = np.where(nonempty, kdmin, _BIG)
    edmax = np.where(nonempty, kdmax, _BIG)
    qd64 = qdmin.astype(np.int64) * _KEY
    bounds = [0, *cuts.tolist(), nk]
    S = len(bounds) - 1
    starts = np.zeros((nq, S, 2), np.int64)
    lens = np.zeros((nq, S, 2), np.int64)
    n_full = 0
    for si in range(S):
        s, e = bounds[si], bounds[si + 1]
        lo = s + np.searchsorted(edmax[s:e], qdmin)
        hi = s + np.searchsorted(edmin[s:e], qdmax, side="right")
        hi = np.maximum(hi, lo)
        # anti-causal suffix of the q-doc's single-block run
        sidx = s + np.flatnonzero(ksing[s:e])
        anti_lo = anti_hi = hi
        if sidx.size:
            skey = kdmin[sidx].astype(np.int64) * _KEY + kpmin[sidx]
            r1 = np.searchsorted(skey, qd64 + (_KEY - 1), side="right")
            cnt = r1 - np.searchsorted(skey, qd64 + qpmax, side="right")
            cnt = np.where(qsing & (r1 > 0), cnt, 0)
            last = sidx[np.maximum(r1 - 1, 0)]
            anti_hi = np.where(cnt > 0, last + 1, hi)
            anti_lo = anti_hi - np.where(cnt > 0, cnt, 0)
            fidx = sidx[k_all[sidx]]
            if fidx.size:
                fkey = kdmin[fidx].astype(np.int64) * _KEY + kpmax[fidx]
                nf = (np.searchsorted(fkey, qd64 + qpmin, side="right")
                      - np.searchsorted(fkey, qd64))
                n_full += int(nf[qsing & q_all].sum())
        starts[:, si, 0] = lo
        lens[:, si, 0] = np.maximum(anti_lo - lo, 0)
        starts[:, si, 1] = anti_hi
        lens[:, si, 1] = np.maximum(hi - anti_hi, 0)
    flat_lens = lens.reshape(-1)
    flat_starts = starts.reshape(-1)
    total = int(flat_lens.sum())
    ar = np.arange(total, dtype=np.int64)
    excl = np.cumsum(flat_lens) - flat_lens
    cols = (ar + np.repeat(flat_starts - excl, flat_lens)).astype(np.int32)
    nvis = lens.sum((1, 2)).astype(np.int32)
    qrows = np.repeat(np.arange(nq, dtype=np.int64), nvis)
    return qrows, cols, nvis, n_full


def build_block_tables(
    q_doc: np.ndarray,
    q_pos: np.ndarray,
    kv_doc: np.ndarray,
    kv_pos: np.ndarray,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    legacy: bool = False,
) -> BlockTables:
    """Classify every (q-block, kv-block) pair as skip / partial / full.

    Sound conservatism: a pair is *skipped* only when provably no element
    is visible; *full* only when provably all elements are visible (the
    kernel then pays no masking).  Anything uncertain is partial.
    Within a block, FlashCP's executor lays tokens out sorted by
    (doc, pos), which makes the min/max summaries tight.

    The visit lists are built by pure-numpy sort/cumsum construction:
    plan-ordered (doc, pos)-monotone KV segments resolve each q block's
    visits to at most two index intervals per segment via binary search
    on the block summaries (cost scales with the number of *visited*
    pairs), with a dense sentinel-folded classification as the fallback
    for arbitrary layouts.  ``legacy=True`` selects the original
    O(nq x nk) Python list-of-lists construction, kept only as the
    parity/benchmark baseline.
    """
    q_doc = np.asarray(q_doc); q_pos = np.asarray(q_pos)
    kv_doc = np.asarray(kv_doc); kv_pos = np.asarray(kv_pos)
    B, Tq = q_doc.shape
    _, Tk = kv_doc.shape
    assert Tq % block_q == 0 and Tk % block_k == 0, (Tq, block_q, Tk, block_k)
    nq, nk = Tq // block_q, Tk // block_k

    if legacy:
        return _build_block_tables_legacy(q_doc, q_pos, kv_doc, kv_pos,
                                          block_q=block_q, block_k=block_k)

    qsum = _summ32(q_doc, q_pos, block_q)
    ksum = _summ32(kv_doc, kv_pos, block_k)

    per_row = []
    n_visited = n_full = 0
    for b in range(B):
        qs = tuple(a[b] for a in qsum)
        ks = tuple(a[b] for a in ksum)
        cuts = _detect_segments(ks[0], ks[1], ks[2], ks[3], ks[5])
        if cuts.size + 1 > max(8, nk // 8):
            qrows, cols, nvis, nf = _pairs_dense(qs, ks)
        else:
            qrows, cols, nvis, nf = _pairs_intervals(qs, ks, cuts, nk)
        per_row.append((qrows, cols, nvis))
        n_visited += int(nvis.sum())
        n_full += nf

    Vk = max(1, max(int(r[2].max()) if r[2].size else 0 for r in per_row))
    rev_nvis = [np.bincount(cols, minlength=nk).astype(np.int32)
                for _, cols, _ in per_row]
    Vq = max(1, max(int(n.max()) if n.size else 0 for n in rev_nvis))

    kv_idx = np.zeros((B, nq, Vk), np.int32)
    kv_nvis = np.zeros((B, nq), np.int32)
    q_idx = np.zeros((B, nk, Vq), np.int32)
    q_nvis = np.zeros((B, nk), np.int32)
    for b, (qrows, cols, nvis) in enumerate(per_row):
        kv_idx[b] = _scatter_lists(qrows, cols, nvis, Vk)
        kv_nvis[b] = nvis
        order = np.lexsort((qrows, cols))      # by kv block, q ascending
        q_idx[b] = _scatter_lists(cols[order], qrows[order].astype(np.int32),
                                  rev_nvis[b], Vq)
        q_nvis[b] = rev_nvis[b]

    return BlockTables(
        kv_idx=kv_idx, kv_nvis=kv_nvis, q_idx=q_idx, q_nvis=q_nvis,
        block_q=block_q, block_k=block_k,
        visited_frac=n_visited / max(B * nq * nk, 1),
        full_frac=n_full / max(n_visited, 1),
    )


def _build_block_tables_legacy(q_doc, q_pos, kv_doc, kv_pos, *, block_q,
                               block_k) -> BlockTables:
    """The seed implementation, frozen as the parity/benchmark baseline."""
    B, Tq = q_doc.shape
    _, Tk = kv_doc.shape
    nq, nk = Tq // block_q, Tk // block_k

    def summarize(doc, pos, blk):
        d = doc.reshape(B, -1, blk)
        p = pos.reshape(B, -1, blk)
        valid = d >= 0
        big = np.int64(1 << 40)
        dmin = np.where(valid, d, big).min(-1)
        dmax = np.where(valid, d, -1).max(-1)
        pmin = np.where(valid, p, big).min(-1)
        pmax = np.where(valid, p, -1).max(-1)
        any_valid = valid.any(-1)
        all_valid = valid.all(-1)
        return dmin, dmax, pmin, pmax, any_valid, all_valid

    qdmin, qdmax, qpmin, qpmax, q_any, q_all = summarize(q_doc, q_pos, block_q)
    kdmin, kdmax, kpmin, kpmax, k_any, k_all = summarize(kv_doc, kv_pos, block_k)

    # broadcast to (B, nq, nk)
    def bq_(x):
        return x[:, :, None]

    def bk_(x):
        return x[:, None, :]

    overlap = (bq_(qdmax) >= bk_(kdmin)) & (bk_(kdmax) >= bq_(qdmin))
    single_doc = (bq_(qdmin) == bq_(qdmax)) & (bk_(kdmin) == bk_(kdmax)) \
        & (bq_(qdmin) == bk_(kdmin))
    # single shared doc and strictly anti-causal -> nothing visible
    anti = single_doc & (bq_(qpmax) < bk_(kpmin))
    visited = overlap & ~anti & bq_(q_any) & bk_(k_any)
    full = single_doc & (bq_(qpmin) >= bk_(kpmax)) & bq_(q_all) & bk_(k_all)
    full &= visited

    kinds = np.where(visited, np.where(full, KIND_FULL, KIND_PARTIAL),
                     KIND_SKIP).astype(np.int32)

    kv_lists = [[int(k) for k in np.nonzero(kinds[b, qi])[0]]
                for b in range(B) for qi in range(nq)]
    q_lists = [[int(q) for q in np.nonzero(kinds[b, :, ki])[0]]
               for b in range(B) for ki in range(nk)]
    Vk = max(1, max((len(l) for l in kv_lists), default=0))
    Vq = max(1, max((len(l) for l in q_lists), default=0))

    kv_idx = _pad_lists(kv_lists, Vk).reshape(B, nq, Vk)
    kv_nvis = np.array([len(l) for l in kv_lists], np.int32).reshape(B, nq)
    q_idx = _pad_lists(q_lists, Vq).reshape(B, nk, Vq)
    q_nvis = np.array([len(l) for l in q_lists], np.int32).reshape(B, nk)

    n_visited = int((kinds != KIND_SKIP).sum())
    n_full = int((kinds == KIND_FULL).sum())
    return BlockTables(
        kv_idx=kv_idx, kv_nvis=kv_nvis, q_idx=q_idx, q_nvis=q_nvis,
        block_q=block_q, block_k=block_k,
        visited_frac=n_visited / max(kinds.size, 1),
        full_frac=n_full / max(n_visited, 1),
    )


# ===================================================================== #
# shared kernel helpers
# ===================================================================== #
# TPU block-shape rule: the last two dims of every block are multiples of
# (8, 128) or span the whole array.  Per-token vectors therefore enter the
# kernels pre-tiled (the layout of JAX's own TPU flash attention): q-side
# doc/pos and the per-row lse/delta statistics broadcast along lanes, so a
# q block is a (block_q, LANES) column tile; kv-side doc/pos broadcast
# along sublanes, so a kv block is a (SUBLANES, block_k) row tile.
LANES = 128
SUBLANES = 8

#: VMEM one grid step may hold, as :func:`_step_vmem_bytes` counts it:
#: the TPU compiler's default scoped-VMEM limit on v5e (16 MiB) less
#: room for the compiler's own internal scratch.
STEP_VMEM_BUDGET = 14 * 2**20


def _step_vmem_bytes(hps: int, block_q: int, block_k: int,
                     head_dim: int) -> int:
    """VMEM one grid step holds with ``hps`` packed query heads: the
    heaviest of the three kernels' double-buffered in/out blocks, scratch
    and ``(hps * block_q, block_k)`` f32 temporaries, at 4 bytes an
    element whatever the operands' dtype (so the count is the same for
    bf16 and f32 inputs)."""
    rows = hps * block_q
    qd = 4 * rows * head_dim               # one packed (hps, bq, D) block
    qs = 4 * rows * LANES                  # one packed lse/delta tile
    tile = 4 * rows * block_k              # one (hps*bq, bk) temporary
    kv = 4 * block_k * head_dim
    meta = 4 * 2 * (block_q * LANES + SUBLANES * block_k)
    fwd = 2 * (2 * qd + qs + 2 * kv + meta) + qd + 2 * qs + 4 * tile + qd
    dq = 2 * (3 * qd + 2 * qs + 2 * kv + meta) + qd + 4 * tile + 2 * qd
    dkv = 2 * (2 * qd + 2 * qs + 4 * kv + meta) + 2 * kv + 6 * tile + 2 * qd
    return max(fwd, dq, dkv)


def _heads_per_step(group: int, block_q: int, block_k: int,
                    head_dim: int) -> int:
    """Query heads one grid step carries: the largest divisor of the GQA
    ``group`` whose step footprint fits :data:`STEP_VMEM_BUDGET`.  Group
    1 (MHA) gives 1, a one-head step; 1 is the floor whatever the
    budget."""
    return max(h for h in range(1, group + 1) if group % h == 0 and (
        h == 1 or _step_vmem_bytes(h, block_q, block_k, head_dim)
        <= STEP_VMEM_BUDGET))


def _record_build(hps: int, grid: tuple) -> None:
    """Trace-time record of one kernel build: the packing width and the
    grid steps it launches (once per compiled program, not per call)."""
    with obs.span("attn.kernel_build"):
        obs.count("attn.heads_per_step", hps)
        obs.count("attn.grid_steps", math.prod(grid))


def _tile_meta(q_doc, q_pos, kv_doc, kv_pos):
    """(B, T) int32 metadata -> q-side (B, Tq, LANES), kv-side
    (B, SUBLANES, Tk)."""
    def col(x):
        return jnp.broadcast_to(x[:, :, None], x.shape + (LANES,))

    def row(x):
        return jnp.broadcast_to(x[:, None, :],
                                (x.shape[0], SUBLANES, x.shape[1]))

    return col(q_doc), col(q_pos), row(kv_doc), row(kv_pos)


def _row_stat(x):
    """(B, H, T) f32 per-row statistic -> (B, H, T, LANES)."""
    return jnp.broadcast_to(x[..., None], x.shape + (LANES,))


def _visible(qd_ref, qp_ref, kd_ref, kp_ref):
    """(bq, bk) doc-causal mask of the step's tile; one document layout
    serves every packed head, so it is built once and broadcast."""
    qd = qd_ref[0, :, :1]                         # (bq, 1) column
    qp = qp_ref[0, :, :1]
    kd = kd_ref[0, :1, :]                         # (1, bk) row
    kp = kp_ref[0, :1, :]
    return (qd == kd) & (qp >= kp) & (qd >= 0) & (kd >= 0)


def _rows(x):
    """Packed heads as matmul rows: (hps, bq, X) -> (hps * bq, X)."""
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2])


def _heads(x, hps):
    """Matmul rows back to packed heads: (hps * bq, X) -> (hps, bq, X)."""
    return x.reshape(hps, x.shape[0] // hps, x.shape[1])


def _dot_f32(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


# ===================================================================== #
# forward kernel: shared row bodies + the two grid schedules
# ===================================================================== #
def _fwd_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)


def _fwd_visit(q_ref, k_ref, v_ref, qd_ref, qp_ref, kd_ref, kp_ref,
               acc_ref, m_ref, l_ref, scale):
    hps = q_ref.shape[1]
    q = _rows(q_ref[0].astype(jnp.float32))                    # (hps*bq, D)
    k = k_ref[0, 0]
    s = _heads(_dot_f32(q, k.T.astype(jnp.float32)) * scale,
               hps)                                            # (hps, bq, bk)
    vis = _visible(qd_ref, qp_ref, kd_ref, kp_ref)
    s = jnp.where(vis, s, NEG)

    m_prev = m_ref[:, :, :1]
    l_prev = l_ref[:, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                            # NEG-NEG -> 1
    p = jnp.where(vis, jnp.exp(s - m_new), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
    vv = v_ref[0, 0]
    pv = _heads(_dot_f32(_rows(p).astype(vv.dtype), vv), hps)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _fwd_finalize(out_ref, lse_ref, acc_ref, m_ref, l_ref):
    l = l_ref[:, :, :1]
    m = m_ref[:, :, :1]
    out = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0)
    out_ref[0] = out.astype(out_ref.dtype)
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), -jnp.inf)
    lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fwd_kernel(kv_idx_ref, kv_nvis_ref,           # scalar prefetch
                q_ref, k_ref, v_ref,
                qd_ref, qp_ref, kd_ref, kp_ref,    # metadata tiles
                out_ref, lse_ref,                  # outputs
                acc_ref, m_ref, l_ref,             # VMEM scratch
                *, scale: float, num_visits: int):
    b, _, qi, vi = (pl.program_id(i) for i in range(4))

    @pl.when(vi == 0)
    def _init():
        _fwd_init(acc_ref, m_ref, l_ref)

    @pl.when(vi < kv_nvis_ref[b, qi])
    def _visit():
        _fwd_visit(q_ref, k_ref, v_ref, qd_ref, qp_ref, kd_ref, kp_ref,
                   acc_ref, m_ref, l_ref, scale)

    @pl.when(vi == num_visits - 1)
    def _finalize():
        _fwd_finalize(out_ref, lse_ref, acc_ref, m_ref, l_ref)


def _fwd_kernel_flat(row_ref, col_ref, flags_ref,  # scalar prefetch (B, S)
                     q_ref, k_ref, v_ref,
                     qd_ref, qp_ref, kd_ref, kp_ref,
                     out_ref, lse_ref,
                     acc_ref, m_ref, l_ref,
                     *, scale: float):
    """Work-queue schedule: one grid step per actual visit.  Row
    boundaries arrive as prefetched FIRST/LAST flags instead of the
    rectangular grid's ``vi == 0`` / ``vi == V-1`` positions; sentinel
    and pad steps clear VALID so they fetch (a repeat) but never
    compute."""
    b, _, s = (pl.program_id(i) for i in range(3))
    flags = flags_ref[b, s]

    @pl.when((flags & FLAG_FIRST) != 0)
    def _init():
        _fwd_init(acc_ref, m_ref, l_ref)

    @pl.when((flags & FLAG_VALID) != 0)
    def _visit():
        _fwd_visit(q_ref, k_ref, v_ref, qd_ref, qp_ref, kd_ref, kp_ref,
                   acc_ref, m_ref, l_ref, scale)

    @pl.when((flags & FLAG_LAST) != 0)
    def _finalize():
        _fwd_finalize(out_ref, lse_ref, acc_ref, m_ref, l_ref)


def _check_grid(grid: str, tables) -> tuple:
    tables = tuple(tables)
    want = GRID_TABLE_HALF.get(grid)
    if want is None:
        raise ValueError(f"unknown kernel grid {grid!r}")
    if len(tables) != want:
        raise ValueError(
            f"grid={grid!r} kernels take {want} table arrays, got "
            f"{len(tables)}")
    return tables


def flash_fwd(q, k, v, q_doc, q_pos, kv_doc, kv_pos,
              tables, *,
              scale: float, block_q: int = DEFAULT_BLOCK_Q,
              block_k: int = DEFAULT_BLOCK_K, grid: str = "rect",
              interpret: bool = False):
    """Forward pass.  ``tables`` is ``(kv_idx, kv_nvis)`` for
    ``grid="rect"``, ``(fq_row, fq_col, fq_flags)`` for ``grid="flat"``.
    """
    B, Hq, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    group = Hq // Hkv
    hps = _heads_per_step(group, block_q, block_k, D)
    chunks = group // hps                 # head blocks per KV head
    nq = Tq // block_q
    tables = _check_grid(grid, tables)
    q_blk = (1, hps, block_q, D)
    stat_blk = (1, hps, block_q, LANES)

    if grid == "flat":
        row_t, col_t, flags_t = tables
        S = row_t.shape[-1]

        def q_map(b, h, s, row, col, flags):
            return (b, h, row[b, s], 0)

        def kv_map(b, h, s, row, col, flags):
            return (b, h // chunks, col[b, s], 0)

        def q_meta(b, h, s, row, col, flags):
            return (b, row[b, s], 0)

        def kv_meta(b, h, s, row, col, flags):
            return (b, 0, col[b, s])

        grid_shape = (B, Hkv * chunks, S)
        kernel = functools.partial(_fwd_kernel_flat, scale=scale)
        inputs = (row_t, col_t, flags_t)
    else:
        kv_idx, kv_nvis = tables
        V = kv_idx.shape[-1]

        def q_map(b, h, qi, vi, *s):
            return (b, h, qi, 0)

        def kv_map(b, h, qi, vi, kv_idx, kv_nvis):
            return (b, h // chunks, kv_idx[b, qi, vi], 0)

        def q_meta(b, h, qi, vi, *s):
            return (b, qi, 0)

        def kv_meta(b, h, qi, vi, kv_idx, kv_nvis):
            return (b, 0, kv_idx[b, qi, vi])

        grid_shape = (B, Hkv * chunks, nq, V)
        kernel = functools.partial(_fwd_kernel, scale=scale, num_visits=V)
        inputs = (kv_idx, kv_nvis)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(inputs),
        grid=grid_shape,
        in_specs=[
            pl.BlockSpec(q_blk, q_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, block_q, LANES), q_meta),
            pl.BlockSpec((1, block_q, LANES), q_meta),
            pl.BlockSpec((1, SUBLANES, block_k), kv_meta),
            pl.BlockSpec((1, SUBLANES, block_k), kv_meta),
        ],
        out_specs=[pl.BlockSpec(q_blk, q_map),
                   pl.BlockSpec(stat_blk, q_map)],
        scratch_shapes=[
            pltpu.VMEM((hps, block_q, D), jnp.float32),
            pltpu.VMEM((hps, block_q, LANES), jnp.float32),
            pltpu.VMEM((hps, block_q, LANES), jnp.float32),
        ],
    )
    _record_build(hps, grid_shape)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Tq, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="doc_attn_fwd",
    )(*inputs, q, k, v, *_tile_meta(q_doc, q_pos, kv_doc, kv_pos))
    return out, lse[..., 0]


# ===================================================================== #
# backward: dQ  (q-block rows; rect grid over visits or flat work queue)
# ===================================================================== #
def _dq_visit(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
              qd_ref, qp_ref, kd_ref, kp_ref, dq_acc, scale):
    hps = q_ref.shape[1]
    q = _rows(q_ref[0].astype(jnp.float32))
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = _rows(do_ref[0].astype(jnp.float32))
    lse = lse_ref[0, :, :, :1]                        # (hps, bq, 1)
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
    delta = dl_ref[0, :, :, :1]

    s = _heads(_dot_f32(q, k.T.astype(jnp.float32)) * scale, hps)
    vis = _visible(qd_ref, qp_ref, kd_ref, kp_ref)
    p = jnp.where(vis, jnp.exp(s - lse_safe), 0.0)
    dp = _heads(_dot_f32(do, v.T.astype(jnp.float32)), hps)
    ds = p * (dp - delta) * scale
    dq_acc[...] += _heads(_dot_f32(_rows(ds).astype(k.dtype), k), hps)


def _dq_kernel(kv_idx_ref, kv_nvis_ref,
               q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               qd_ref, qp_ref, kd_ref, kp_ref,
               dq_ref,
               dq_acc,
               *, scale: float, num_visits: int):
    b, _, qi, vi = (pl.program_id(i) for i in range(4))

    @pl.when(vi == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(vi < kv_nvis_ref[b, qi])
    def _visit():
        _dq_visit(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                  qd_ref, qp_ref, kd_ref, kp_ref, dq_acc, scale)

    @pl.when(vi == num_visits - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dq_kernel_flat(row_ref, col_ref, flags_ref,
                    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                    qd_ref, qp_ref, kd_ref, kp_ref,
                    dq_ref,
                    dq_acc,
                    *, scale: float):
    b, _, s = (pl.program_id(i) for i in range(3))
    flags = flags_ref[b, s]

    @pl.when((flags & FLAG_FIRST) != 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when((flags & FLAG_VALID) != 0)
    def _visit():
        _dq_visit(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                  qd_ref, qp_ref, kd_ref, kp_ref, dq_acc, scale)

    @pl.when((flags & FLAG_LAST) != 0)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def flash_bwd_dq(q, k, v, do, lse, delta, q_doc, q_pos, kv_doc, kv_pos,
                 tables, *, scale: float,
                 block_q: int = DEFAULT_BLOCK_Q,
                 block_k: int = DEFAULT_BLOCK_K, grid: str = "rect",
                 interpret: bool = False):
    """dQ pass; ``tables`` as in :func:`flash_fwd` (the same q-block
    work queue drives both)."""
    B, Hq, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    group = Hq // Hkv
    hps = _heads_per_step(group, block_q, block_k, D)
    chunks = group // hps
    nq = Tq // block_q
    tables = _check_grid(grid, tables)
    q_blk = (1, hps, block_q, D)
    stat_blk = (1, hps, block_q, LANES)

    if grid == "flat":
        row_t, col_t, flags_t = tables
        S = row_t.shape[-1]

        def q_map(b, h, s, row, col, flags):
            return (b, h, row[b, s], 0)

        def kv_map(b, h, s, row, col, flags):
            return (b, h // chunks, col[b, s], 0)

        def q_meta(b, h, s, row, col, flags):
            return (b, row[b, s], 0)

        def kv_meta(b, h, s, row, col, flags):
            return (b, 0, col[b, s])

        grid_shape = (B, Hkv * chunks, S)
        kernel = functools.partial(_dq_kernel_flat, scale=scale)
        inputs = (row_t, col_t, flags_t)
    else:
        kv_idx, kv_nvis = tables
        V = kv_idx.shape[-1]

        def q_map(b, h, qi, vi, *s):
            return (b, h, qi, 0)

        def kv_map(b, h, qi, vi, kv_idx, kv_nvis):
            return (b, h // chunks, kv_idx[b, qi, vi], 0)

        def q_meta(b, h, qi, vi, *s):
            return (b, qi, 0)

        def kv_meta(b, h, qi, vi, kv_idx, kv_nvis):
            return (b, 0, kv_idx[b, qi, vi])

        grid_shape = (B, Hkv * chunks, nq, V)
        kernel = functools.partial(_dq_kernel, scale=scale, num_visits=V)
        inputs = (kv_idx, kv_nvis)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(inputs),
        grid=grid_shape,
        in_specs=[
            pl.BlockSpec(q_blk, q_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec(q_blk, q_map),
            pl.BlockSpec(stat_blk, q_map),
            pl.BlockSpec(stat_blk, q_map),
            pl.BlockSpec((1, block_q, LANES), q_meta),
            pl.BlockSpec((1, block_q, LANES), q_meta),
            pl.BlockSpec((1, SUBLANES, block_k), kv_meta),
            pl.BlockSpec((1, SUBLANES, block_k), kv_meta),
        ],
        out_specs=[pl.BlockSpec(q_blk, q_map)],
        scratch_shapes=[pltpu.VMEM((hps, block_q, D), jnp.float32)],
    )
    _record_build(hps, grid_shape)
    (dq,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hq, Tq, D), q.dtype)],
        interpret=interpret,
        name="doc_attn_dq",
    )(*inputs, q, k, v, do, _row_stat(lse), _row_stat(delta),
      *_tile_meta(q_doc, q_pos, kv_doc, kv_pos))
    return dq


# ===================================================================== #
# backward: dK, dV  (kv-block rows x head chunks; rect grid or flat queue)
# ===================================================================== #
def _dkv_visit(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               qd_ref, qp_ref, kd_ref, kp_ref, dk_acc, dv_acc, scale):
    hps = q_ref.shape[1]
    q = _rows(q_ref[0].astype(jnp.float32))
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = _rows(do_ref[0].astype(jnp.float32))
    lse = lse_ref[0, :, :, :1]
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
    delta = dl_ref[0, :, :, :1]

    s = _heads(_dot_f32(q, k.T.astype(jnp.float32)) * scale,
               hps)                                        # (hps, bq, bk)
    vis = _visible(qd_ref, qp_ref, kd_ref, kp_ref)
    p = jnp.where(vis, jnp.exp(s - lse_safe), 0.0)
    # one matmul contracts over every packed head's rows
    dv_acc[...] += _dot_f32(_rows(p).T.astype(do.dtype), do)
    dp = _heads(_dot_f32(do, v.T.astype(jnp.float32)), hps)
    ds = p * (dp - delta) * scale
    dk_acc[...] += _dot_f32(_rows(ds).T.astype(q.dtype), q)


def _dkv_kernel(q_idx_ref, q_nvis_ref,
                q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                qd_ref, qp_ref, kd_ref, kp_ref,
                dk_ref, dv_ref,
                dk_acc, dv_acc,
                *, scale: float, num_visits: int, chunks: int):
    b, _, ki, vqi, gi = (pl.program_id(i) for i in range(5))

    @pl.when((vqi == 0) & (gi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(vqi < q_nvis_ref[b, ki])
    def _visit():
        _dkv_visit(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                   qd_ref, qp_ref, kd_ref, kp_ref, dk_acc, dv_acc, scale)

    @pl.when((vqi == num_visits - 1) & (gi == chunks - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dkv_kernel_flat(row_ref, col_ref, flags_ref,
                     q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                     qd_ref, qp_ref, kd_ref, kp_ref,
                     dk_ref, dv_ref,
                     dk_acc, dv_acc,
                     *, scale: float, chunks: int):
    b, _, s, gi = (pl.program_id(i) for i in range(4))
    flags = flags_ref[b, s]

    @pl.when(((flags & FLAG_FIRST) != 0) & (gi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when((flags & FLAG_VALID) != 0)
    def _visit():
        _dkv_visit(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                   qd_ref, qp_ref, kd_ref, kp_ref, dk_acc, dv_acc, scale)

    @pl.when(((flags & FLAG_LAST) != 0) & (gi == chunks - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_bwd_dkv(q, k, v, do, lse, delta, q_doc, q_pos, kv_doc, kv_pos,
                  tables, *, scale: float,
                  block_q: int = DEFAULT_BLOCK_Q,
                  block_k: int = DEFAULT_BLOCK_K, grid: str = "rect",
                  interpret: bool = False):
    """dK/dV pass.  ``tables`` is the *reverse* map: ``(q_idx, q_nvis)``
    for ``grid="rect"``, ``(rq_row, rq_col, rq_flags)`` for
    ``grid="flat"`` (rows are KV blocks, cols the visiting Q blocks)."""
    B, Hq, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    group = Hq // Hkv
    hps = _heads_per_step(group, block_q, block_k, D)
    chunks = group // hps                 # innermost grid axis
    nk = Tk // block_k
    tables = _check_grid(grid, tables)
    q_blk = (1, hps, block_q, D)
    stat_blk = (1, hps, block_q, LANES)

    if grid == "flat":
        row_t, col_t, flags_t = tables
        S = row_t.shape[-1]

        def q_map(b, hkv, s, gi, row, col, flags):
            return (b, hkv * chunks + gi, col[b, s], 0)

        def q_meta(b, hkv, s, gi, row, col, flags):
            return (b, col[b, s], 0)

        def kv_map(b, hkv, s, gi, row, col, flags):
            return (b, hkv, row[b, s], 0)

        def kv_meta(b, hkv, s, gi, row, col, flags):
            return (b, 0, row[b, s])

        grid_shape = (B, Hkv, S, chunks)
        kernel = functools.partial(_dkv_kernel_flat, scale=scale,
                                   chunks=chunks)
        inputs = (row_t, col_t, flags_t)
    else:
        q_idx, q_nvis = tables
        Vq = q_idx.shape[-1]

        def q_map(b, hkv, ki, vqi, gi, q_idx, q_nvis):
            return (b, hkv * chunks + gi, q_idx[b, ki, vqi], 0)

        def q_meta(b, hkv, ki, vqi, gi, q_idx, q_nvis):
            return (b, q_idx[b, ki, vqi], 0)

        def kv_map(b, hkv, ki, vqi, gi, *s):
            return (b, hkv, ki, 0)

        def kv_meta(b, hkv, ki, vqi, gi, *s):
            return (b, 0, ki)

        grid_shape = (B, Hkv, nk, Vq, chunks)
        kernel = functools.partial(_dkv_kernel, scale=scale, num_visits=Vq,
                                   chunks=chunks)
        inputs = (q_idx, q_nvis)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(inputs),
        grid=grid_shape,
        in_specs=[
            pl.BlockSpec(q_blk, q_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec(q_blk, q_map),
            pl.BlockSpec(stat_blk, q_map),
            pl.BlockSpec(stat_blk, q_map),
            pl.BlockSpec((1, block_q, LANES), q_meta),
            pl.BlockSpec((1, block_q, LANES), q_meta),
            pl.BlockSpec((1, SUBLANES, block_k), kv_meta),
            pl.BlockSpec((1, SUBLANES, block_k), kv_meta),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), kv_map),
            pl.BlockSpec((1, 1, block_k, D), kv_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
    )
    _record_build(hps, grid_shape)
    dk, dv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Tk, D), v.dtype),
        ],
        interpret=interpret,
        name="doc_attn_dkv",
    )(*inputs, q, k, v, do, _row_stat(lse), _row_stat(delta),
      *_tile_meta(q_doc, q_pos, kv_doc, kv_pos))
    return dk, dv
