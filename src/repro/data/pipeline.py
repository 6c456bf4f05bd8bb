"""Deterministic, host-sharded training data pipeline.

Per batch, per packed sequence:
  1. draw document lengths from the dataset distribution (seeded);
  2. resolve the configured CP planner through the
     :mod:`repro.planner` registry and plan (PlanCache-accelerated —
     replayed steps after a restart and recurring mixes hit the cache);
  3. encode the plan (permutation + comm metadata, vectorized single-pass
     batch encoding, :mod:`repro.planner.encode`);
  4. synthesize tokens and next-token labels (label masking at document
     finals and padding), all in *plan order*.

Determinism & elasticity: the stream for (seed, dp_rank, step) is a pure
function — after a failure the restarted pipeline replays exactly by
seeking ``start_step`` (used by the fault-tolerant training driver), and a
re-sharded (elastic) job re-splits ranks without touching earlier history.
The cache preserves this: exact-signature hits return plans identical to
a cold run (the first miss stores the planner's own output).

A background thread prefetches ``prefetch`` batches ahead of the consumer;
multi-sequence batches plan/encode through the planner worker pool.

**Global-dispatch mode** (:func:`make_dispatch_batch`, DESIGN.md
§Dispatch): instead of every DP rank sampling independently, one seeded
pool of documents is drawn per global step and the
:mod:`repro.dispatch` dispatcher sizes the CP subgroups and LPT-balances
the pool across them; rows are emitted group-major so the batch axis
shards contiguously over the re-tiled mesh's group axis.  Per-group
batches may be *ragged* (bins keep documents whole), so each row carries
its valid-token count in ``seq_tokens`` and padded positions stay masked
(``labels == -1``).  The legacy per-rank stream is untouched — dispatch
off is bit-identical to previous releases.
"""

from __future__ import annotations

import dataclasses
import functools
import queue
import threading
from typing import Any, Iterator

import numpy as np

from repro.planner import (PlanCache, encode_plan_batch, get_planner,
                           plan_many)
from repro.planner.encode import PlanEncoding  # noqa: F401  (re-export)
from .distributions import make_rng
from .packing import pack_sequence, sample_doc_pool

__all__ = ["PipelineConfig", "make_batch", "make_dispatch_batch",
           "data_iterator", "dispatch_iterator", "Prefetcher"]

#: reserved dp_rank for the global-dispatch rng stream — real ranks are
#: always >= 0, so dispatch batches never collide with a per-rank stream.
DISPATCH_RANK = -1


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    dataset: str = "wlb_llm"
    context_len: int = 131072
    batch_per_host: int = 1
    cp_size: int = 8
    strategy: str = "flashcp"
    vocab_size: int = 50304
    seed: int = 0
    buf_len: int | None = None   # fixed Eq.5 bucket (None -> per-batch)
    align: int = 128             # T_loc alignment (Pallas block size)
    target_imbalance: float = 1.05
    # planner-subsystem knobs
    cache_plans: bool = True
    cache_granularity: int = 1   # 1 = exact signatures (plan-identical)
    cache_entries: int = 256
    planner_workers: int = 0     # 0 = auto (serial on small hosts)
    # Pallas visit-table emission (attention_impl="pallas" steps)
    emit_tables: bool = False
    table_overlap: str = "chunked"   # matches RunConfig.cp_overlap
    table_grid: str = "flat"         # matches RunConfig.kernel_grid
    table_block_q: int = 128
    table_block_k: int = 128


@functools.lru_cache(maxsize=32)
def _planner_state(cfg: PipelineConfig):
    """(planner, kwargs, cache) resolved once per config."""
    planner = get_planner(cfg.strategy)
    kwargs = {}
    if planner.info.supports_target_ratio:
        kwargs["target_ratio"] = cfg.target_imbalance
    cache = PlanCache(planner, cfg.cp_size,
                      granularity=cfg.cache_granularity,
                      max_entries=cfg.cache_entries,
                      planner_kwargs=kwargs) if cfg.cache_plans else None
    return planner, kwargs, cache


def _plan(cfg: PipelineConfig, doc_lens):
    planner, kwargs, cache = _planner_state(cfg)
    if cache is not None:
        return cache.plan(doc_lens)
    return planner(doc_lens, cfg.cp_size, **kwargs)


def _synthesize_row(tokens_row, labels_row, lens, perm, rng,
                    vocab_size: int) -> None:
    """Synthesize one sequence's tokens in packed order, then permute to
    plan order (writes into the supplied batch rows).

    Zipfian unigrams + repetition bigrams give the stream learnable
    structure (uniform tokens would pin the loss at ln(vocab)).  One zipf
    + one uniform draw per sequence — the draw order is part of the
    pipeline's determinism contract.
    """
    n_tok = int(lens.sum())
    packed = ((rng.zipf(1.3, n_tok) - 1) % vocab_size
              ).astype(np.int32)
    rep = rng.random(n_tok) < 0.25
    rep[0] = False
    idx = np.arange(n_tok)
    prev = np.maximum(idx - 1, 0)
    packed = np.where(rep, packed[prev], packed)
    valid = perm >= 0
    tokens_row[valid] = packed[perm[valid]]
    # next-token labels: valid unless last token of its document
    nxt = perm + 1
    is_final = np.zeros_like(valid)
    ends = np.cumsum(lens) - 1
    is_final[valid] = np.isin(perm[valid], ends)
    lab_ok = valid & ~is_final
    labels_row[lab_ok] = packed[np.minimum(nxt[lab_ok],
                                           len(packed) - 1)]


def _synthesize_tokens(doc_lens_list, perm_stack, rngs,
                       vocab_size: int) -> tuple[np.ndarray, np.ndarray]:
    """Batch token synthesis.  ``rngs`` is either one shared Generator
    (legacy per-rank stream: rows draw sequentially, in row order) or a
    list of per-row Generators (dispatch: each row's stream is keyed to
    its *content*, so tokens are invariant to the LPT row order and to
    the chosen CP degree)."""
    B, C_pad = perm_stack.shape
    tokens = np.full((B, C_pad), -1, np.int32)
    labels = np.full((B, C_pad), -1, np.int32)
    for b, lens in enumerate(doc_lens_list):
        rng = rngs[b] if isinstance(rngs, list) else rngs
        _synthesize_row(tokens[b], labels[b], lens, perm_stack[b], rng,
                        vocab_size)
    return tokens, labels


def _emit_tables(cfg: PipelineConfig, stack: dict,
                 num_workers: int) -> dict[str, np.ndarray]:
    """Pallas visit tables for a batch-encoded stack at ``num_workers``."""
    from repro.core.cp_attention import resolve_overlap
    from repro.planner import emit_visit_tables
    exec_style = get_planner(cfg.strategy).info.exec_style
    style_needs_gath = exec_style in ("flashcp", "contiguous")
    overlap = resolve_overlap(exec_style, "pallas", cfg.table_overlap)
    return emit_visit_tables(
        stack["doc"], stack["pos"],
        stack["gath_doc"] if style_needs_gath else None,
        stack["gath_pos"] if style_needs_gath else None,
        num_workers=num_workers, strategy=exec_style,
        overlap=overlap, grid=cfg.table_grid,
        block_q=cfg.table_block_q, block_k=cfg.table_block_k)


def _batch_stats(encs) -> dict:
    return {
        "comm_tokens": max(e.comm_tokens for e in encs),
        "buf_len": encs[0].buf_len,
        "t_loc": encs[0].t_loc,
        "imbalance": float(np.mean([e.imbalance for e in encs])),
    }


def make_batch(cfg: PipelineConfig, step: int, dp_rank: int = 0,
               dp_size: int = 1) -> dict[str, Any]:
    """Build one host-local batch for (step, dp_rank)."""
    rng = make_rng(hash((cfg.seed, dp_rank, step)) % (2 ** 63))
    doc_lens_list = [pack_sequence(cfg.dataset, cfg.context_len, rng)
                     for _ in range(cfg.batch_per_host)]
    plans = plan_many(lambda lens: _plan(cfg, lens), doc_lens_list,
                      workers=cfg.planner_workers)

    stack, encs = encode_plan_batch(plans, buf_len=cfg.buf_len,
                                    align=cfg.align)
    tokens, labels = _synthesize_tokens(doc_lens_list, stack["perm"], rng,
                                        cfg.vocab_size)

    batch = {k: v for k, v in stack.items()}
    if cfg.emit_tables:
        batch.update(_emit_tables(cfg, stack, cfg.cp_size))
    batch["tokens"] = tokens
    batch["labels"] = labels
    batch["stats"] = _batch_stats(encs)
    return batch


def make_dispatch_batch(cfg: PipelineConfig, dcfg, step: int,
                        device_speeds=None) -> dict[str, Any]:
    """Build one *global* batch through the adaptive DP×CP dispatcher.

    One seeded document pool per step (all DP ranks see the same stream),
    dispatched by :func:`repro.dispatch.dispatch_step`: the CP degree
    adapts to the pool's length profile, rows are emitted group-major
    (row ``r`` belongs to subgroup ``r // seqs_per_group`` of the
    re-tiled mesh), and every row plans/encodes through the ordinary
    registry path at the chosen degree.  ``t_loc`` is pinned to
    ``C / cp`` so the batch keeps one static shape per degree even when
    bins are ragged; ``cfg.cp_size`` is ignored (the dispatcher owns the
    degree).

    Extra keys vs :func:`make_batch`: ``seq_tokens`` (per-row valid
    tokens — ragged rows pad with masked labels), ``group_id`` (per-row
    subgroup), and ``stats["dispatch"]`` (degree decision, imbalances,
    candidate table, pool profile).

    ``device_speeds`` (optional, length ``data * model``): measured
    relative device speeds from the straggler monitor — the dispatcher
    then LPT-balances *completion time* instead of raw load and sizes
    bin targets capacity-proportionally (DESIGN.md §Recovery).  Token
    content is unaffected (row streams are content-keyed), only the
    row→group placement shifts.
    """
    from repro.dispatch import dispatch_step

    rng = make_rng(hash((cfg.seed, DISPATCH_RANK, step)) % (2 ** 63))
    pool = sample_doc_pool(cfg.dataset, dcfg.seqs * cfg.context_len, rng,
                           max_doc_len=cfg.context_len,
                           min_docs=dcfg.seqs)
    dplan = dispatch_step(pool, dcfg, cfg.context_len,
                          device_speeds=device_speeds)
    g = dplan.cp_degree
    assert all(len(r) for r in dplan.rows), \
        "dispatch produced an empty sequence bin (pool too small for seqs)"

    gcfg = dataclasses.replace(cfg, cp_size=g)
    plans = plan_many(lambda lens: _plan(gcfg, lens), dplan.rows,
                      workers=cfg.planner_workers)
    stack, encs = encode_plan_batch(plans, buf_len=cfg.buf_len,
                                    t_loc=cfg.context_len // g,
                                    align=cfg.align)
    # per-row token streams keyed to row *content* (the pool documents in
    # the bin), so tokens are invariant to LPT row order and CP degree —
    # the same pool dispatched at any degree yields the same data.
    row_rngs = [make_rng(hash((cfg.seed, DISPATCH_RANK, step)
                              + tuple(int(i) for i in docs)) % (2 ** 63))
                for docs in dplan.row_docs]
    tokens, labels = _synthesize_tokens(dplan.rows, stack["perm"], row_rngs,
                                        cfg.vocab_size)

    batch = {k: v for k, v in stack.items()}
    if cfg.emit_tables:
        batch.update(_emit_tables(cfg, stack, g))
    batch["tokens"] = tokens
    batch["labels"] = labels
    batch["seq_tokens"] = np.asarray([int(r.sum()) for r in dplan.rows],
                                     np.int32)
    batch["group_id"] = dplan.group_of_row.astype(np.int32)
    batch["stats"] = _batch_stats(encs)
    batch["stats"]["dispatch"] = {**dplan.stats(),
                                  "profile": dplan.profile.as_dict()}
    return batch


def data_iterator(cfg: PipelineConfig, start_step: int = 0, dp_rank: int = 0,
                  dp_size: int = 1) -> Iterator[dict[str, Any]]:
    step = start_step
    while True:
        yield make_batch(cfg, step, dp_rank, dp_size)
        step += 1


def dispatch_iterator(cfg: PipelineConfig, dcfg, start_step: int = 0,
                      speeds_fn=None) -> Iterator[dict[str, Any]]:
    """Global-dispatch batch stream (one iterator per job, not per rank).

    ``speeds_fn``: optional zero-arg callable returning the current
    device-speed vector (or None) — sampled once per batch so a live
    straggler monitor can steer placement without rebuilding the stream.
    """
    step = start_step
    while True:
        speeds = speeds_fn() if speeds_fn is not None else None
        yield make_dispatch_batch(cfg, dcfg, step, device_speeds=speeds)
        step += 1


class Prefetcher:
    """Background-thread prefetch with bounded queue (skip-ahead capable).

    ``dispatch``: a :class:`repro.dispatch.DispatchConfig` switches the
    stream to global-dispatch batches (``dp_rank`` is then unused — the
    dispatcher is rank-global by construction).
    """

    def __init__(self, cfg: PipelineConfig, start_step: int = 0,
                 dp_rank: int = 0, prefetch: int = 2, dispatch=None,
                 speeds_fn=None):
        self._q: queue.Queue = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run,
            args=(cfg, start_step, dp_rank, dispatch, speeds_fn),
            daemon=True)
        self._thread.start()

    def _run(self, cfg, start_step, dp_rank, dispatch=None, speeds_fn=None):
        it = dispatch_iterator(cfg, dispatch, start_step, speeds_fn) \
            if dispatch is not None else \
            data_iterator(cfg, start_step, dp_rank)
        for batch in it:
            if self._stop.is_set():
                return
            self._q.put(batch)

    def __next__(self):
        return self._q.get()

    def __iter__(self):
        return self

    def close(self):
        self._stop.set()
        try:
            self._q.get_nowait()
        except queue.Empty:
            pass
