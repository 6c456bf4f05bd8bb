"""Continuous-batching CP serving engine over a paged KV block pool.

KV layouts (``kv_layout``):

* **paged** (default for attention-only archs) — one global pool of
  ``num_blocks`` x ``block_size`` token positions per attention
  sub-layer (``models.init_paged_cache``); each request holds a block
  table mapping logical to physical blocks (``block_pool.BlockPool``
  does the host-side accounting).  KV memory scales with *live tokens*,
  not ``num_slots x max_len``; identical prompt prefixes share blocks
  through ``prefix.PrefixCache`` (written once, refcounted,
  copy-on-write when a shared block must be appended).
* **dense** — the PR-4 per-slot stripe layout, kept as the parity
  oracle (paged and dense greedy decodes must agree bitwise) and for
  recurrent archs (Jamba/xLSTM scan states have no block structure).

Each :meth:`step` spends one **token budget** (SplitFuse-style,
``scheduler.plan_step``): decode-ready slots get their decode token
first, the remaining budget trickles prompt chunks in — a long prompt
prefills *alongside* in-flight decodes instead of stalling them.
``unified=False`` restores serial prefill-then-decode as the stall
baseline.

Three jitted program families: chunked cache-writing **prefill**
(per-slot dense view or block-table scatter/gather), ragged **decode**
(flash-decode kernel, block-table indirected for paged), and keyed
**sampling** — every request samples from its own
``fold_in(fold_in(engine_key, rid), n_generated)`` key stream, so
results are per-request reproducible regardless of batch composition.

Resilience (DESIGN.md §Serving-resilience): admission is bounded and
deadline-aware (``max_queue`` / ``admission`` / ``admit_lookahead``), a
watchdog quarantines requests with non-finite logits or stalled slots
(the decode programs return a per-row finite mask so NaN never reaches
a healthy request's results), and :meth:`snapshot` /
:meth:`restore_snapshot` persist the whole engine mid-decode through
the checkpoint manager's atomic-commit path — a killed engine restores
with zero request loss and bitwise token parity.
"""

from __future__ import annotations

import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import (decode_step, init_cache, init_paged_cache,
                          init_params, prefill_forward,
                          supports_cached_prefill, supports_paged_cache)
from .block_pool import BlockPool
from .prefix import PrefixCache
from .resilience import (AdmissionConfig, ChaosInjector, Watchdog,
                         restore_engine, snapshot_engine)
from .sampling import sample_tokens_keyed, sample_tokens_keyed_jit
from .scheduler import Request, Scheduler, SlotState

__all__ = ["ServeEngine"]


def _slot_view(cache, slot):
    return jax.tree.map(
        lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=1), cache)


def _slot_write(cache, view, slot):
    return jax.tree.map(
        lambda l, nl: jax.lax.dynamic_update_slice_in_dim(
            l, nl.astype(l.dtype), slot, axis=1), cache, view)


def _mask_rows(new, old, active):
    """Keep ``new`` only on active slot rows (row axis 1 of every dense
    cache leaf: (P, B, ...))."""
    def sel(n, o):
        m = active.reshape((1, -1) + (1,) * (n.ndim - 2))
        return jnp.where(m, n.astype(o.dtype), o)
    return jax.tree.map(sel, new, old)


class ServeEngine:
    """Drive requests through budgeted prefill + continuous decode.

    Parameters: ``kv_layout`` "auto" (paged when the arch supports it) /
    "paged" / "dense"; ``block_size`` tokens per KV block;
    ``num_blocks`` pool size (0 = dense-equivalent capacity
    ``num_slots * ceil(max_len/block_size)``); ``token_budget`` tokens
    per step (0 = ``num_slots + prefill_chunk``); ``prefix_cache``
    shares identical prompt prefixes across requests (paged only);
    ``unified=False`` serializes prefill before decode (stall baseline).
    ``decode_impl`` "flash" (default) or "dense" (XLA softmax oracle);
    ``attn_shards`` splits the *dense* decode cache into LSE-merged
    segments; ``interpret=None`` auto-selects Pallas interpret off-TPU.

    Resilience knobs: ``max_queue`` bounds the queue (0 = unbounded),
    ``admission`` picks the overload policy ("fifo" sheds the incoming
    request, "deadline" sheds the least-slack one), ``admit_lookahead``
    lets placeable requests jump a pool-blocked head (0 = strict FIFO)
    under ``starvation_limit``; ``watchdog=False`` disables fault
    quarantine (the pre-resilience engine, kept for the chaos
    regression tests); ``stall_patience`` is the consecutive
    planned-but-no-progress steps before a slot aborts; ``chaos`` takes
    a :class:`~.resilience.ChaosInjector`.
    """

    def __init__(self, cfg: ModelConfig, params=None, *,
                 num_slots: int = 4, max_len: int = 256,
                 prefill_chunk: int = 64, decode_impl: str = "flash",
                 attn_shards: int = 1, block_k: int = 256,
                 interpret: bool | None = None, seed: int = 0,
                 kv_layout: str = "auto", block_size: int = 16,
                 num_blocks: int = 0, token_budget: int = 0,
                 prefix_cache: bool = True, unified: bool = True,
                 max_queue: int = 0, admission: str = "fifo",
                 admit_lookahead: int = 4, starvation_limit: int = 8,
                 watchdog: bool = True, stall_patience: int = 8,
                 chaos: ChaosInjector | None = None):
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.prefill_chunk = min(prefill_chunk, max_len)
        self.decode_impl = decode_impl
        self.cached_prefill = supports_cached_prefill(cfg)

        if kv_layout == "auto":
            # paged when the arch can (attention-only mixers); sharded
            # decode (LSE-merged stripe segments) is a dense-layout
            # feature, so attn_shards>1 keeps the stripes
            kv_layout = "paged" if supports_paged_cache(cfg) \
                and attn_shards == 1 else "dense"
        elif kv_layout == "paged" and not supports_paged_cache(cfg):
            raise ValueError(
                f"{cfg.name}: paged KV requires attention-only mixers "
                "(recurrent scan states have no block structure)")
        elif kv_layout not in ("paged", "dense"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if kv_layout == "paged" and attn_shards > 1:
            raise ValueError("attn_shards>1 is a dense-layout feature "
                             "(LSE-merged stripe segments)")
        self.layout = kv_layout

        if params is None:
            # jitted: the f32 draws fuse into the stored-dtype weights
            # instead of a full f32 copy of the model living first
            params = jax.jit(init_params, static_argnums=1)(
                jax.random.PRNGKey(seed), cfg)
        self.params = params

        self.block_size = block_size
        self._nk = -(-max_len // block_size)     # table width in blocks
        if kv_layout == "paged":
            self.num_blocks = num_blocks or num_slots * self._nk
            self.pool = BlockPool(self.num_blocks, block_size)
            self.prefix = PrefixCache(block_size) if prefix_cache else None
            self.cache = init_paged_cache(cfg, self.num_blocks, block_size)
        else:
            self.num_blocks = 0
            self.pool = None
            self.prefix = None
            self.cache = init_cache(cfg, num_slots, max_len)

        self.sched = Scheduler(
            num_slots, max_len, prefill_chunk=self.prefill_chunk,
            token_budget=token_budget, unified=unified,
            admission=AdmissionConfig(
                max_queue=max_queue, policy=admission,
                lookahead=admit_lookahead,
                starvation_limit=starvation_limit))
        self.sched.on_retire = self._on_retire
        self.watchdog = Watchdog(stall_patience) if watchdog else None
        self.chaos = chaos
        self._seed = seed
        self._snap_mgrs: dict[str, Any] = {}
        self._base_key = jax.random.PRNGKey(seed)
        self._next_rid = 0
        self.stats: dict[str, Any] = {
            "prefill_tokens": 0, "prefill_chunk_tokens": 0,
            "prefill_cached_tokens": 0, "prefill_steps": 0,
            "prefill_decode_steps": 0, "prefill_s": 0.0,
            "decode_tokens": 0, "decode_steps": 0, "decode_s": 0.0,
            "admitted": 0, "retired": 0, "steps": 0,
            "stalled_decode_steps": 0, "cow_copies": 0,
            "admission_backoffs": 0,
            "pool_block_steps": 0, "live_token_steps": 0,
            "chaos_delay_s": 0.0, "snapshots": 0,
            # reason-keyed terminal counters, aliased from the
            # scheduler (single source; mutated in place)
            "rejected_by_reason": self.sched.outcomes["rejected"],
            "shed_by_reason": self.sched.outcomes["shed"],
            "aborted_by_reason": self.sched.outcomes["aborted"]}

        bs = block_size
        dec_kw = dict(attn_impl=decode_impl, attn_shards=attn_shards,
                      block_k=block_k, interpret=interpret)

        def _decode_batch(tok, frames):
            if cfg.frontend == "audio_frames":
                # modality gap of the stubbed EnCodec frontend: generated
                # steps have no codec->frame embedder, so continuation
                # frames are zeros; *prompt* frames flow through prefill.
                return {"frame_embeds": frames}
            return {"tokens": tok}

        def _sample_guarded(logits, poison, key, rids, counts, temps, topk):
            # chaos NaN lands here (post-attention, pre-sampler — the
            # observable effect of a corrupted KV page); the per-row
            # finite mask travels back so the host can quarantine the
            # poisoned row without a second device round trip
            logits = logits.astype(jnp.float32)
            logits = jnp.where(poison[:, None], jnp.nan, logits)
            finite = jnp.all(jnp.isfinite(logits), axis=-1)
            nxt = sample_tokens_keyed(key, rids, counts, logits, temps,
                                      topk)
            return nxt, logits, finite

        def decode_fn(params, cache, tok, pos_t, active, poison, key,
                      rids, counts, temps, topk):
            frames = jnp.zeros((num_slots, cfg.d_model), jnp.dtype(cfg.dtype))
            logits, new_cache = decode_step(
                params, cfg, cache, _decode_batch(tok, frames), pos_t,
                **dec_kw)
            new_cache = _mask_rows(new_cache, cache, active)
            nxt, logits, finite = _sample_guarded(
                logits, poison, key, rids, counts, temps, topk)
            return nxt, logits, finite, new_cache

        def decode_paged_fn(params, cache, tok, pos_t, tables, active,
                            poison, key, rids, counts, temps, topk):
            frames = jnp.zeros((num_slots, cfg.d_model), jnp.dtype(cfg.dtype))
            logits, new_cache = decode_step(
                params, cfg, cache, _decode_batch(tok, frames), pos_t,
                attn_impl=decode_impl, block_k=block_k,
                interpret=interpret, block_tables=tables, block_size=bs,
                write_mask=active)
            nxt, logits, finite = _sample_guarded(
                logits, poison, key, rids, counts, temps, topk)
            return nxt, logits, finite, new_cache

        def _chunk_batch(tokens, frames):
            batch = {"tokens": tokens}
            if cfg.frontend == "audio_frames":
                batch = {"frame_embeds": frames}
            elif cfg.frontend == "vit_patches":
                T = tokens.shape[1]
                batch["patch_embeds"] = jnp.zeros(
                    (1, T, cfg.d_model), jnp.dtype(cfg.dtype))
                batch["patch_mask"] = jnp.zeros((1, T), bool)
            return batch

        def prefill_chunk_fn(params, cache, slot, tokens, frames, pos,
                             active, *, with_logits, s_view):
            view = _slot_view(cache, slot)
            # crop the attended cache to the pow2 bucket covering this
            # chunk's end: prefill attention is O(C * s_view), not
            # O(C * max_len) (attn caches are (P, 1, Hkv, S, hd))
            crop = jax.tree.map(lambda l: l[:, :, :, :s_view], view)
            logits, ncrop = prefill_forward(
                params, cfg, crop, _chunk_batch(tokens, frames), pos,
                active, with_logits=with_logits)
            nview = jax.tree.map(
                lambda f, n: jax.lax.dynamic_update_slice_in_dim(
                    f, n.astype(f.dtype), 0, axis=3), view, ncrop)
            return logits, _slot_write(cache, nview, slot)

        def prefill_paged_fn(params, cache, table, tokens, frames, pos,
                             active, *, with_logits, view_blocks):
            logits, new_cache = prefill_forward(
                params, cfg, cache, _chunk_batch(tokens, frames), pos,
                active, with_logits=with_logits, block_tables=table,
                block_size=bs, view_blocks=view_blocks)
            return logits, new_cache

        def replay_fn(params, cache, tok, frames, pos_t, active):
            logits, new_cache = decode_step(
                params, cfg, cache, _decode_batch(tok, frames), pos_t,
                **dec_kw)
            return logits, _mask_rows(new_cache, cache, active)

        def copy_block_fn(cache, src, dst):
            # copy-on-write: clone pool block src -> dst (flat token
            # axis 2 of every paged leaf (P, Hkv, NB*bs, hd))
            def cp(l):
                blk = jax.lax.dynamic_slice_in_dim(l, src * bs, bs, axis=2)
                return jax.lax.dynamic_update_slice_in_dim(
                    l, blk, dst * bs, axis=2)
            return jax.tree.map(cp, cache)

        # the cache argument is donated everywhere: the engine always
        # replaces self.cache with the program's output, so XLA can
        # update the KV buffers in place instead of keeping two copies
        self._decode_fn = jax.jit(decode_fn, donate_argnums=(1,))
        self._decode_paged_fn = jax.jit(decode_paged_fn, donate_argnums=(1,))
        self._replay_fn = jax.jit(replay_fn, donate_argnums=(1,))
        self._copy_block_fn = jax.jit(copy_block_fn, donate_argnums=(0,))
        self._prefill_fns: dict[tuple, Any] = {}
        self._prefill_chunk_body = prefill_chunk_fn
        self._prefill_paged_body = prefill_paged_fn

    # ------------------------------------------------------------- #
    def _prefill_fn(self, with_logits: bool, view: int):
        """Jitted prefill-chunk program per (head?, view bucket); only
        the final chunk pays the (T, vocab) head projection.  ``view``
        is the dense s_view (tokens) or paged view_blocks (blocks)."""
        key = (self.layout, with_logits, view)
        if key not in self._prefill_fns:
            if self.layout == "paged":
                body = functools.partial(self._prefill_paged_body,
                                         with_logits=with_logits,
                                         view_blocks=view)
            else:
                body = functools.partial(self._prefill_chunk_body,
                                         with_logits=with_logits,
                                         s_view=view)
            self._prefill_fns[key] = jax.jit(body, donate_argnums=(1,))
        return self._prefill_fns[key]

    def _view_bucket(self, end: int) -> int:
        """pow2 cache-view bucket covering prefix ``end`` (tokens), in
        this layout's view unit — bounds jit specialization to
        O(log(max_len)) prefill variants."""
        if self.layout == "paged":
            v = 1
            while v * self.block_size < end:
                v *= 2
            return min(v, self._nk)
        s = self.prefill_chunk
        while s < end:
            s *= 2
        return min(s, self.max_len)

    def _prefill_buckets(self, prompt_len: int):
        """(is_last, view bucket) per chunk of an un-budget-split
        ``prompt_len`` prompt — the variants warmup precompiles."""
        C = self.prefill_chunk
        n_chunks = -(-prompt_len // C)
        return [(ci == n_chunks - 1, self._view_bucket((ci + 1) * C))
                for ci in range(n_chunks)]

    # ------------------------------------------------------------- #
    def submit(self, tokens, *, max_new: int = 16, temperature: float = 0.0,
               top_k: int = 0, eos_id: int = -1, frames=None,
               deadline_steps: int = -1, priority: int = 0) -> int:
        """Queue one request; returns its request id.  Oversized
        requests land in the results dict with status="rejected";
        overload victims (bounded queue) with status="shed".
        ``deadline_steps`` is the engine-step budget the request must
        finish within (-1 = none); lower ``priority`` sheds first."""
        rid = self._next_rid
        self._next_rid += 1
        self.sched.clock = self.stats["steps"]
        self.sched.submit(Request(
            rid=rid, tokens=np.asarray(tokens, np.int32), max_new=max_new,
            temperature=temperature, top_k=top_k, eos_id=eos_id,
            frames=None if frames is None
            else np.asarray(frames, np.float32),
            deadline_steps=deadline_steps, priority=priority))
        return rid

    # ------------------------------------------------------------- #
    # paged block accounting (host side)
    def _place(self, req: Request) -> dict | None:
        """Reserve KV for one request at admission.  Paged: match the
        prefix cache, then allocate the remaining blocks all-or-nothing
        (evicting unreferenced cached blocks if short); None = backoff,
        the request stays queued at the FIFO head."""
        if self.layout != "paged":
            return {}
        bs = self.block_size
        Tp, mn = req.prompt_len, req.max_new
        matched = [] if self.prefix is None \
            else self.prefix.match(req.tokens)
        nk_req = -(-(Tp + mn - 1) // bs)
        while True:
            m = len(matched) * bs
            # a fully-cached prompt still recomputes its final token (its
            # logits seed sampling): reserve the copy-on-write spare for
            # the shared block that write lands in
            start = min(m, Tp - 1)
            n_fresh = nk_req - len(matched)
            n_spare = 1 if m >= Tp else 0
            need = n_fresh + n_spare
            # retain the matched chain BEFORE evicting: at refcount >= 2
            # the LRU sweep's refcount==1 check cannot free blocks this
            # request is about to read (evicted-and-reallocated matched
            # blocks would alias fresh blocks in the table — silent
            # prefix corruption)
            self.pool.retain(matched)
            if self.prefix is not None and self.pool.free_count < need:
                self.prefix.evict(need - self.pool.free_count, self.pool)
            got = self.pool.alloc(need)
            if got is not None:
                break
            self.pool.release(matched)      # drop the reservation; the
            if not matched:                 # cache ref remains
                self.stats["admission_backoffs"] += 1
                return None
            # retry with a shorter match: the popped tail becomes an
            # evictable leaf again, and a no-longer-full match drops the
            # spare — trade cached tokens for fit before backing off
            matched.pop()
        self.stats["prefill_cached_tokens"] += m
        return {"table": matched + got[:n_fresh], "cached": m,
                "start": start, "spare": got[n_fresh] if n_spare else None}

    def _on_retire(self, slot: int, st: SlotState) -> None:
        self.stats["retired"] += 1
        if self.watchdog is not None:
            self.watchdog.clear(slot)
        if self.layout == "paged":
            self.pool.release(st.table)
            if st.spare is not None:
                self.pool.release([st.spare])

    def _ensure_private(self, st: SlotState, bi: int) -> None:
        """Copy-on-write logical block ``bi`` of this request's table if
        it is shared (prefix-cached with other readers)."""
        if bi >= len(st.table):
            return
        bid = st.table[bi]
        if not self.pool.is_shared(bid):
            return
        if st.spare is not None:
            nb, st.spare = st.spare, None
        else:
            got = self.pool.alloc(1)
            if got is None:
                raise RuntimeError("copy-on-write with exhausted pool")
            nb = got[0]
        self.cache = self._copy_block_fn(
            self.cache, jnp.asarray(bid, jnp.int32),
            jnp.asarray(nb, jnp.int32))
        self.pool.release([bid])
        st.table[bi] = nb
        self.stats["cow_copies"] += 1

    def _tables_matrix(self) -> np.ndarray:
        tab = np.zeros((self.num_slots, self._nk), np.int32)
        for s in self.sched.active_slots:
            t = self.sched.slots[s].table
            tab[s, :len(t)] = t
        return tab

    # ------------------------------------------------------------- #
    def _first_token(self, req: Request, logits_row) -> int:
        """Sample a request's first token (count 0 of its key stream)
        from the prefill's last logits."""
        tok = sample_tokens_keyed_jit(
            self._base_key, jnp.asarray([req.rid], jnp.int32),
            jnp.zeros((1,), jnp.int32),
            logits_row[None].astype(jnp.float32),
            jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_k], jnp.int32))
        return int(np.asarray(tok)[0])

    def _run_prefill_chunk(self, slot: int, start: int, n: int) -> bool:
        """Prefill prompt tokens [start, start+n) of one slot; on the
        final chunk, sample the first token and start decoding.
        Returns False when the slot was aborted (non-finite final
        logits — the poisoned request is quarantined before its blocks
        reach the prefix cache)."""
        sc = self.sched
        st = sc.slots[slot]
        req = st.request
        C = self.prefill_chunk
        Tp = req.prompt_len
        t0 = time.perf_counter()
        toks = np.zeros((1, C), np.int32)
        toks[0, :n] = req.tokens[start:start + n]
        frames = np.zeros((1, C, self.cfg.d_model), np.float32)
        if req.frames is not None:
            frames[0, :n] = req.frames[start:start + n]
        pos = jnp.asarray(np.arange(start, start + C, dtype=np.int32)[None])
        active = jnp.asarray((np.arange(C) < n)[None])
        with_logits = start + n >= Tp
        fn = self._prefill_fn(with_logits, self._view_bucket(start + C))
        if self.layout == "paged":
            # only this chunk's first block can be prefix-shared (later
            # blocks are freshly allocated): COW it before writing
            self._ensure_private(st, start // self.block_size)
            table = jnp.asarray(self._tables_matrix()[slot][None])
            logits, self.cache = fn(self.params, self.cache, table,
                                    jnp.asarray(toks), jnp.asarray(frames),
                                    pos, active)
        else:
            logits, self.cache = fn(self.params, self.cache,
                                    jnp.asarray(slot, jnp.int32),
                                    jnp.asarray(toks), jnp.asarray(frames),
                                    pos, active)
        sc.note_prefill(slot, n)
        self.stats["prefill_steps"] += 1
        self.stats["prefill_chunk_tokens"] += n
        if with_logits:
            row = np.asarray(logits[0, n - 1], np.float32)
            if self.chaos is not None \
                    and self.chaos.poisons(req.rid, self.stats["steps"]):
                row = np.full_like(row, np.nan)
            if self.watchdog is not None and not np.isfinite(row).all():
                self.stats["prefill_s"] += time.perf_counter() - t0
                sc.abort(slot, "non-finite prefill logits at step "
                         f"{self.stats['steps']}", kind="nan_logits")
                return False
            if self.layout == "paged" and self.prefix is not None:
                nfull = Tp // self.block_size
                if nfull:
                    self.prefix.insert(req.tokens[:nfull * self.block_size],
                                       st.table[:nfull], self.pool)
            first = self._first_token(req, row)
            self.stats["prefill_tokens"] += Tp
            sc.start(slot, first)
        self.stats["prefill_s"] += time.perf_counter() - t0
        return True

    def _prefill_replay(self, slot: int, req: Request) -> bool:
        """Recurrent-mixer fallback (dense layout): feed the whole
        prompt through the decode path one token at a time at admission,
        updates masked to this slot's row.  Audio prompts replay their
        *real* frame embeddings.  Returns False when the slot was
        aborted (non-finite final logits)."""
        t0 = time.perf_counter()
        B = self.num_slots
        onehot = jnp.zeros((B,), bool).at[slot].set(True)
        logits = None
        for t in range(req.prompt_len):
            tok = jnp.zeros((B,), jnp.int32).at[slot].set(
                int(req.tokens[t]))
            frames = jnp.zeros((B, self.cfg.d_model), jnp.float32)
            if req.frames is not None:
                frames = frames.at[slot].set(jnp.asarray(req.frames[t]))
            pos_t = jnp.zeros((B,), jnp.int32).at[slot].set(t)
            logits, self.cache = self._replay_fn(
                self.params, self.cache, tok, frames, pos_t, onehot)
            self.stats["prefill_decode_steps"] += 1
        row = np.asarray(logits[slot], np.float32)
        if self.chaos is not None \
                and self.chaos.poisons(req.rid, self.stats["steps"]):
            row = np.full_like(row, np.nan)
        self.stats["prefill_tokens"] += req.prompt_len
        self.stats["prefill_chunk_tokens"] += req.prompt_len
        self.stats["prefill_s"] += time.perf_counter() - t0
        if self.watchdog is not None and not np.isfinite(row).all():
            self.sched.abort(slot, "non-finite prefill logits at step "
                             f"{self.stats['steps']}", kind="nan_logits")
            return False
        self.sched.start(slot, self._first_token(req, row))
        return True

    # ------------------------------------------------------------- #
    def _decode_once(self, decode_slots: list[int]) -> list[int]:
        """One batched decode step; poisoned rows (non-finite logits)
        are quarantined — only healthy slots record their token.
        Returns the healthy slots."""
        sc = self.sched
        B = self.num_slots
        dmask = np.zeros((B,), bool)
        dmask[decode_slots] = True
        tok = np.zeros((B,), np.int32)
        poison = np.zeros((B,), bool)
        for s in decode_slots:
            tok[s] = sc.slots[s].generated[-1]
            if self.chaos is not None and self.chaos.poisons(
                    sc.slots[s].request.rid, self.stats["steps"]):
                poison[s] = True
        lengths = np.where(dmask, sc.lengths(), 0).astype(np.int32)
        t0 = time.perf_counter()
        common = (jnp.asarray(tok), jnp.asarray(lengths))
        tail = (jnp.asarray(poison), self._base_key,
                jnp.asarray(sc.rids()), jnp.asarray(sc.sample_counts()),
                jnp.asarray(sc.temperatures()), jnp.asarray(sc.top_ks()))
        if self.layout == "paged":
            # safety net: a decode write must never land in a shared
            # block (prefix sharing covers full *prompt* blocks only,
            # and full-match COW happens at prefill — this should not
            # fire, but a silent shared-block write would corrupt
            # another request's prefix)
            for s in decode_slots:
                self._ensure_private(
                    sc.slots[s], sc.slots[s].length // self.block_size)
            nxt, _, finite, self.cache = self._decode_paged_fn(
                self.params, self.cache, *common,
                jnp.asarray(self._tables_matrix()), jnp.asarray(dmask),
                *tail)
        else:
            nxt, _, finite, self.cache = self._decode_fn(
                self.params, self.cache, *common, jnp.asarray(dmask), *tail)
        nxt = np.asarray(jax.block_until_ready(nxt))
        self.stats["decode_s"] += time.perf_counter() - t0
        self.stats["decode_steps"] += 1
        if self.watchdog is None:
            healthy = list(decode_slots)
        else:
            finite = np.asarray(finite)
            healthy = [s for s in decode_slots if finite[s]]
            for s in decode_slots:
                if not finite[s]:
                    sc.abort(s, "non-finite decode logits at step "
                             f"{self.stats['steps']}", kind="nan_logits")
        self.stats["decode_tokens"] += len(healthy)
        sc.record(nxt, healthy)
        return healthy

    # ------------------------------------------------------------- #
    def step(self) -> bool:
        """One engine step: admit what fits, spend the token budget on
        prefill chunks + decode tokens, then run the fault watchdog.
        Returns False when idle."""
        sc = self.sched
        step_no = self.stats["steps"]
        sc.clock = step_no
        if self.chaos is not None:
            self.chaos.maybe_kill(step_no)
            d = self.chaos.delay(step_no)
            if d > 0:
                time.sleep(d)
                self.stats["chaos_delay_s"] += d
        placed = sc.admit(self._place)
        self.stats["admitted"] += len(placed)
        if not self.cached_prefill:
            for slot, req in placed:
                self._prefill_replay(slot, req)
        if sc.queue and not placed and not sc.active_slots:
            # the head backed off even into an idle engine (its block
            # working set exceeds the pool after full prefix-cache
            # eviction): reject it instead of wedging the loop — the
            # requests queued behind it must still run
            req = sc.queue.popleft()
            nk = -(-(req.prompt_len + req.max_new - 1) // self.block_size)
            sc.reject(req, f"working set of {nk} KV blocks exceeds the "
                      f"{self.num_blocks}-block pool",
                      kind="pool_unplaceable")
        n_ready = sum(1 for s in sc.active_slots
                      if sc.slots[s].decode_ready)
        prefill_items, decode_slots = sc.plan_step()
        planned = {s for s, _, _ in prefill_items} | set(decode_slots)
        if self.chaos is not None:
            # a stuck slot's planned work is dropped before execution
            # (a wedged device callback) — the watchdog must catch it
            def _stuck(s):
                return self.chaos.is_stuck(sc.slots[s].request.rid,
                                           step_no)
            prefill_items = [it for it in prefill_items
                             if not _stuck(it[0])]
            decode_slots = [s for s in decode_slots if not _stuck(s)]
        progressed: set[int] = set()
        for slot, start, n in prefill_items:
            if self._run_prefill_chunk(slot, start, n):
                progressed.add(slot)
            else:
                planned.discard(slot)       # aborted, not stalled
        if decode_slots:
            healthy = self._decode_once(decode_slots)
            progressed |= set(healthy)
            planned -= set(decode_slots) - set(healthy)
        elif n_ready:
            # decode-ready slots got no token this step (serial mode
            # draining a long prefill) — the stall the unified budget
            # eliminates
            self.stats["stalled_decode_steps"] += 1
        if self.watchdog is not None:
            for slot, n_stalled in self.watchdog.observe(planned,
                                                         progressed):
                sc.abort(slot, f"no scheduler progress for {n_stalled} "
                         f"planned steps (stuck slot {slot})",
                         kind="stall")
        self.stats["steps"] += 1
        self.stats["live_token_steps"] += sum(
            sc.slots[s].length for s in sc.active_slots)
        if self.layout == "paged":
            self.stats["pool_block_steps"] += self.pool.allocated_count
        return sc.has_work

    def run(self, max_steps: int = 100_000, *, snapshot_every: int = 0,
            snapshot_dir: str | None = None, drain_at: int = -1,
            ) -> dict[int, dict[str, Any]]:
        """Drain the queue; returns {rid: {"status", "tokens",
        "prompt_len", ...}} — every submitted rid is present with
        status "ok", "rejected", "shed", or "aborted" (hitting
        ``max_steps`` aborts the in-flight requests with their partial
        tokens rather than dropping them).  ``snapshot_every`` persists
        the engine to ``snapshot_dir`` every N steps; ``drain_at``
        stops at that engine step with a final snapshot (orderly
        drain — a restored engine resumes the in-flight work)."""
        if (snapshot_every > 0 or drain_at >= 0) and not snapshot_dir:
            raise ValueError("snapshot_every/drain_at require "
                             "snapshot_dir")
        steps = 0
        while True:
            if drain_at >= 0 and self.stats["steps"] >= drain_at \
                    and self.sched.has_work:
                self.snapshot(snapshot_dir)
                break
            more = self.step()
            steps += 1
            if snapshot_every > 0 and steps % snapshot_every == 0:
                self.snapshot(snapshot_dir)
            if not more:
                break
            if steps >= max_steps:
                self.sched.abort_all(
                    f"engine step cap {max_steps} reached")
                break
        return self.sched.finished

    # ------------------------------------------------------------- #
    def warmup(self, prompt_len: int | None = None) -> None:
        """Compile the decode + prefill + sampling programs outside the
        timed window (all-inactive calls leave cache *values* untouched;
        outputs are reassigned because the cache argument is donated).
        ``prompt_len`` warms every prefill-chunk variant a prompt of
        that length uses (default: a single-chunk prompt)."""
        B = self.num_slots
        zi = jnp.zeros((B,), jnp.int32)
        zmask = jnp.zeros((B,), bool)
        zf = jnp.zeros((B,), jnp.float32)
        tail = (zmask, self._base_key, zi, zi, zf, zi)
        if self.layout == "paged":
            ztab = jnp.zeros((B, self._nk), jnp.int32)
            _, _, _, self.cache = self._decode_paged_fn(
                self.params, self.cache, zi, zi, ztab, zmask, *tail)
        else:
            _, _, _, self.cache = self._decode_fn(
                self.params, self.cache, zi, zi, zmask, *tail)
        sample_tokens_keyed_jit(
            self._base_key, jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32),
            jnp.zeros((1, self.cfg.vocab_size), jnp.float32),
            jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32))
        C = self.prefill_chunk
        if not self.cached_prefill:
            _, self.cache = self._replay_fn(
                self.params, self.cache, zi,
                jnp.zeros((B, self.cfg.d_model), jnp.float32), zi, zmask)
            return
        zchunk = (jnp.zeros((1, C), jnp.int32),
                  jnp.zeros((1, C, self.cfg.d_model), jnp.float32),
                  jnp.asarray(np.arange(C, dtype=np.int32)[None]),
                  jnp.zeros((1, C), bool))
        lead = jnp.zeros((1, self._nk), jnp.int32) \
            if self.layout == "paged" else jnp.asarray(0, jnp.int32)
        for is_last, view in set(self._prefill_buckets(prompt_len or C)):
            _, self.cache = self._prefill_fn(is_last, view)(
                self.params, self.cache, lead, *zchunk)

    # ------------------------------------------------------------- #
    def _snapshot_manager(self, directory: str):
        if directory not in self._snap_mgrs:
            from repro.checkpoint import CheckpointManager
            self._snap_mgrs[directory] = CheckpointManager(directory)
        return self._snap_mgrs[directory]

    def snapshot(self, directory: str) -> int:
        """Persist the full engine state (KV cache, scheduler, block
        pool, prefix trie, per-request RNG counters) atomically; see
        :func:`~.resilience.snapshot_engine`.  Returns the snapshot's
        step id."""
        step = snapshot_engine(self, directory)
        self.stats["snapshots"] += 1
        return step

    def restore_snapshot(self, directory: str,
                         step: int | None = None) -> int:
        """Resume from a snapshot taken by an engine with identical
        geometry (call after construction + :meth:`warmup`; warmup's
        all-inactive calls leave cache *values* untouched, so the order
        does not matter).  Returns the restored step id."""
        return restore_engine(self, directory, step)

    def latency_percentiles(self, statuses=("ok",)) -> dict[str, float]:
        """p50/p99 request latency (submit -> terminal entry) over
        ``finished`` entries with the given statuses, in engine steps
        and wall seconds."""
        fin = [e for e in self.sched.finished.values()
               if e["status"] in statuses and "latency_steps" in e]
        if not fin:
            return {"n": 0, "p50_steps": 0.0, "p99_steps": 0.0,
                    "p50_s": 0.0, "p99_s": 0.0}
        steps = np.asarray([e["latency_steps"] for e in fin], np.float64)
        secs = np.asarray([e["latency_s"] for e in fin], np.float64)
        return {"n": len(fin),
                "p50_steps": float(np.percentile(steps, 50)),
                "p99_steps": float(np.percentile(steps, 99)),
                "p50_s": float(np.percentile(secs, 50)),
                "p99_s": float(np.percentile(secs, 99))}

    # ------------------------------------------------------------- #
    def kv_cache_bytes(self) -> int:
        """Device bytes of the KV store (pool or stripes)."""
        return int(sum(l.nbytes for l in jax.tree.leaves(self.cache)))

    def kv_token_capacity(self) -> int:
        """Token positions the KV store can hold."""
        if self.layout == "paged":
            return self.num_blocks * self.block_size
        return self.num_slots * self.max_len

    def throughput(self) -> dict[str, float]:
        """``prefill_tok_s`` counts *computed* tokens only — prefix-cache
        hits skip compute and must not inflate the rate; the effective
        rate (prompt tokens served, cached included) is reported
        separately."""
        s = self.stats
        dt = max(s["prefill_s"], 1e-9)
        return {
            "prefill_tok_s": s["prefill_chunk_tokens"] / dt,
            "prefill_effective_tok_s": s["prefill_tokens"] / dt,
            "decode_tok_s": s["decode_tokens"] / max(s["decode_s"], 1e-9),
        }
