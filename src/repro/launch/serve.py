"""Serving driver: paged-KV continuous-batching engine CLI.

Builds a :class:`repro.serve.ServeEngine`, submits a ragged mix of
requests, and drains it: budgeted cache-writing prefill + ragged
flash-decode over a paged KV block pool (``--kv-layout dense`` keeps
the per-slot stripe layout as the parity oracle; ``--decode-impl
dense`` selects the XLA softmax attention oracle), greedy or
temperature/top-k sampling, and slot admission/retirement mid-flight
(more requests than ``--slots`` exercises continuous batching).
``--shared-prefix N`` prepends the same N-token system prompt to every
request, exercising prefix-cache block sharing; ``--serial`` disables
the unified token-budget step (prefill drains before any decode — the
stall baseline).

Prefill and decode are timed and counted separately — the prompt tokens
and the prefill-produced first token are *prefill* output; decode tok/s
covers decode steps only.

CPU-scale example:

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2_3b \
        --smoke --requests 4 --prompt-len 64 --gen 16 --shared-prefix 16

``--attn-shards N`` splits the dense-layout decode cache into N
LSE-merged segments — the in-process form of the CP-sharded cache merge
(the shard_map form is checked in tests/multidevice/decode_cp_check.py).

Resilience (DESIGN.md §Serving-resilience): ``--max-queue`` bounds the
queue and ``--admission deadline`` sheds the least-slack request under
overload (``--deadline N`` attaches an N-step deadline to every
request); ``--chaos-nan RID:STEP`` / ``--chaos-stuck RID:STEP`` /
``--chaos-delay STEP:SECONDS`` inject faults the watchdog must
quarantine; ``--kill-at STEP`` with ``--snapshot-every N
--snapshot-dir D`` kills the engine mid-run and restores it from the
latest snapshot in-process (``--drain-at STEP`` is the orderly
variant: snapshot + stop + restore).  Every submitted request ends in
the results dict — ok, rejected, shed, or aborted; nothing is lost.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import get_config, reduce_for_smoke
from repro.configs.base import RunConfig
from repro.launch.compile_cache import setup_compile_cache
from repro.serve import EngineKilled, ServeEngine, parse_chaos

_RC = RunConfig()   # serve defaults live on RunConfig (single source)


def serve(args) -> dict:
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)

    B = args.requests
    Tp = args.prompt_len
    gen = args.gen
    slots = getattr(args, "slots", 0) or min(B, 8)
    ragged = getattr(args, "ragged", True)
    rng = np.random.default_rng(getattr(args, "seed", 0))

    shared_prefix = getattr(args, "shared_prefix", 0)

    # ragged prompt mix: lengths in [Tp/4, Tp], one request at the full Tp
    lens = np.full((B,), Tp, np.int64)
    if ragged and B > 1:
        lens[1:] = rng.integers(max(1, Tp // 4), Tp + 1, (B - 1,))
    lens = np.maximum(lens, shared_prefix + 1)
    max_len = int(lens.max() + gen)

    chaos = parse_chaos(getattr(args, "chaos_nan", ()),
                        getattr(args, "chaos_stuck", ()),
                        getattr(args, "chaos_delay", ()),
                        kill_at=getattr(args, "kill_at", -1))
    snapshot_dir = getattr(args, "snapshot_dir", "")
    snapshot_every = getattr(args, "snapshot_every", 0)
    drain_at = getattr(args, "drain_at", -1)

    def build(with_chaos):
        return ServeEngine(
            cfg, num_slots=slots, max_len=max_len,
            prefill_chunk=getattr(args, "prefill_chunk", 64),
            decode_impl=getattr(args, "decode_impl", "flash"),
            attn_shards=getattr(args, "attn_shards", 1),
            seed=getattr(args, "seed", 0),
            kv_layout=getattr(args, "kv_layout", _RC.kv_layout),
            block_size=getattr(args, "block_size", _RC.serve_block_size),
            num_blocks=getattr(args, "num_blocks", 0),
            token_budget=getattr(args, "token_budget",
                                 _RC.serve_token_budget),
            prefix_cache=getattr(args, "prefix_cache", True),
            unified=getattr(args, "unified", True),
            max_queue=getattr(args, "max_queue", _RC.serve_max_queue),
            admission=getattr(args, "admission", _RC.serve_admission),
            admit_lookahead=getattr(args, "admit_lookahead",
                                    _RC.serve_admit_lookahead),
            watchdog=getattr(args, "watchdog", True),
            stall_patience=getattr(args, "stall_patience",
                                   _RC.serve_stall_patience),
            chaos=with_chaos)

    eng = build(chaos)
    eng.warmup(prompt_len=int(lens.max()))

    sys_prompt = rng.integers(0, cfg.vocab_size, shared_prefix) \
        .astype(np.int32)
    temperature = getattr(args, "temperature", 0.0)
    top_k = getattr(args, "top_k", 0)
    deadline = getattr(args, "deadline", -1)
    for i in range(B):
        frames = None
        if cfg.frontend == "audio_frames":
            # the request's *real* frame embeddings — these reach the KV
            # cache through prefill (the old driver replayed zeros)
            frames = rng.standard_normal(
                (int(lens[i]), cfg.d_model)).astype(np.float32)
        toks = rng.integers(0, cfg.vocab_size,
                            int(lens[i]) - shared_prefix).astype(np.int32)
        eng.submit(np.concatenate([sys_prompt, toks]),
                   max_new=gen, temperature=temperature, top_k=top_k,
                   frames=frames, deadline_steps=deadline)

    max_steps = getattr(args, "max_steps", 100_000)
    t0 = time.perf_counter()
    restored_from = None
    try:
        results = eng.run(max_steps=max_steps,
                          snapshot_every=snapshot_every,
                          snapshot_dir=snapshot_dir or None,
                          drain_at=drain_at)
        interrupted = drain_at >= 0 and eng.sched.has_work
        if interrupted:
            print(f"[serve] drained at step {eng.stats['steps']} "
                  f"into {snapshot_dir}")
    except EngineKilled as e:
        print(f"[serve] {e}; restoring from {snapshot_dir}")
        interrupted = True
    if interrupted:
        # restart-from-snapshot round trip, in-process: a fresh engine
        # (no chaos — the fault fired) resumes the in-flight work
        eng = build(None)
        eng.warmup(prompt_len=int(lens.max()))
        step = eng.restore_snapshot(snapshot_dir)
        restored_from = step
        print(f"[serve] restored snapshot at step {step}; resuming")
        results = eng.run(max_steps=max_steps)
    wall = time.perf_counter() - t0

    # the resilience contract: every submitted request terminates in
    # the results dict — a lost rid is a bug, fail loudly
    missing = [r for r in range(B) if r not in results]
    assert not missing, f"requests lost from results: {missing}"

    s = eng.stats
    tp = eng.throughput()
    print(f"[serve] {cfg.name}: {B} requests ({slots} slots, "
          f"prompts {lens.min()}..{lens.max()}, gen {gen}, "
          f"kv_layout={eng.layout}, decode_impl={eng.decode_impl})")
    print(f"[serve] prefill: {s['prefill_tokens']} prompt tokens "
          f"({s['prefill_chunk_tokens']} computed, "
          f"{s['prefill_cached_tokens']} prefix-cached) in "
          f"{s['prefill_steps']} chunk steps + "
          f"{s['prefill_decode_steps']} replay steps, "
          f"{s['prefill_s']:.2f}s ({tp['prefill_tok_s']:.1f} computed "
          f"tok/s, {tp['prefill_effective_tok_s']:.1f} effective)")
    print(f"[serve] decode:  {s['decode_tokens']} tokens in "
          f"{s['decode_steps']} steps, {s['decode_s']:.2f}s "
          f"({tp['decode_tok_s']:.1f} tok/s); wall {wall:.2f}s; "
          f"stalled decode steps {s['stalled_decode_steps']}")
    if eng.layout == "paged":
        ps = eng.pool.stats()
        print(f"[serve] pool:    {ps['allocated']}/{ps['num_blocks']} "
              f"blocks live (peak {ps['peak_allocated']}, block_size "
              f"{ps['block_size']}), cow {s['cow_copies']}, "
              f"backoffs {s['admission_backoffs']}")
        if eng.prefix is not None:
            xs = eng.prefix.stats()
            print(f"[serve] prefix:  {xs['nodes']} cached blocks, "
                  f"hit rate {xs['hit_rate']:.2f} "
                  f"({xs['hit_tokens']} tokens skipped)")
    statuses = {}
    for r in results.values():
        statuses[r["status"]] = statuses.get(r["status"], 0) + 1
    lat = eng.latency_percentiles()
    print(f"[serve] outcomes: {statuses}; "
          f"rejected {s['rejected_by_reason']}, "
          f"shed {s['shed_by_reason']}, "
          f"aborted {s['aborted_by_reason']}")
    print(f"[serve] latency (ok): p50 {lat['p50_steps']:.0f} steps / "
          f"{lat['p50_s'] * 1e3:.0f} ms, p99 {lat['p99_steps']:.0f} "
          f"steps / {lat['p99_s'] * 1e3:.0f} ms"
          + (f"; restored from step {restored_from}"
             if restored_from is not None else ""))
    return {"results": results, "stats": dict(s), "throughput": tp,
            "prompt_lens": lens, "kv_layout": eng.layout,
            "latency": lat, "restored_from": restored_from,
            "pool": None if eng.pool is None else eng.pool.stats(),
            "prefix": None if eng.prefix is None else eng.prefix.stats(),
            "tokens": {r: results[r]["tokens"] for r in results
                       if results[r]["status"] == "ok"}}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="starcoder2_3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=0,
                    help="cache slots (0 = min(requests, 8))")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    dest="prefill_chunk")
    ap.add_argument("--decode-impl", choices=("flash", "dense"),
                    default="flash", dest="decode_impl")
    ap.add_argument("--attn-shards", type=int, default=1,
                    dest="attn_shards")
    ap.add_argument("--kv-layout", choices=("auto", "paged", "dense"),
                    default=_RC.kv_layout, dest="kv_layout")
    ap.add_argument("--block-size", type=int,
                    default=_RC.serve_block_size, dest="block_size",
                    help="tokens per paged KV block")
    ap.add_argument("--num-blocks", type=int, default=0,
                    dest="num_blocks",
                    help="pool blocks (0 = dense-equivalent capacity)")
    ap.add_argument("--token-budget", type=int,
                    default=_RC.serve_token_budget, dest="token_budget",
                    help="tokens per unified step "
                         "(0 = slots + prefill_chunk)")
    ap.add_argument("--no-prefix-cache", action="store_false",
                    dest="prefix_cache",
                    help="disable cross-request prefix block sharing")
    ap.add_argument("--serial", action="store_false", dest="unified",
                    help="drain prefill before decode (stall baseline)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    dest="shared_prefix",
                    help="shared system-prompt tokens per request")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0, dest="top_k")
    ap.add_argument("--uniform", action="store_false", dest="ragged",
                    help="all prompts at --prompt-len (default: ragged)")
    ap.add_argument("--seed", type=int, default=0)
    # resilience (DESIGN.md §Serving-resilience)
    ap.add_argument("--max-queue", type=int,
                    default=_RC.serve_max_queue, dest="max_queue",
                    help="queue bound (0 = unbounded)")
    ap.add_argument("--admission", choices=("fifo", "deadline"),
                    default=_RC.serve_admission,
                    help="overload policy: shed incoming (fifo) or "
                         "least-slack (deadline)")
    ap.add_argument("--admit-lookahead", type=int,
                    default=_RC.serve_admit_lookahead,
                    dest="admit_lookahead",
                    help="requests that may jump a pool-blocked head "
                         "(0 = strict FIFO)")
    ap.add_argument("--deadline", type=int, default=-1,
                    help="deadline_steps attached to every request "
                         "(-1 = none)")
    ap.add_argument("--no-watchdog", action="store_false",
                    dest="watchdog",
                    help="disable fault quarantine (pre-resilience "
                         "engine)")
    ap.add_argument("--stall-patience", type=int,
                    default=_RC.serve_stall_patience,
                    dest="stall_patience")
    ap.add_argument("--chaos-nan", action="append", default=[],
                    dest="chaos_nan", metavar="RID:STEP",
                    help="poison a request's logits to NaN from STEP on")
    ap.add_argument("--chaos-stuck", action="append", default=[],
                    dest="chaos_stuck", metavar="RID:STEP",
                    help="drop a request's planned work from STEP on")
    ap.add_argument("--chaos-delay", action="append", default=[],
                    dest="chaos_delay", metavar="STEP:SECONDS",
                    help="inject a latency spike at STEP")
    ap.add_argument("--kill-at", type=int, default=-1, dest="kill_at",
                    help="raise EngineKilled at this step (restore "
                         "needs --snapshot-every + --snapshot-dir)")
    ap.add_argument("--drain-at", type=int, default=-1, dest="drain_at",
                    help="orderly drain: snapshot + stop at this step, "
                         "then restore and finish in-process")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    dest="snapshot_every",
                    help="snapshot the engine every N steps")
    ap.add_argument("--snapshot-dir", default="", dest="snapshot_dir")
    ap.add_argument("--max-steps", type=int, default=100_000,
                    dest="max_steps")
    args = ap.parse_args()
    setup_compile_cache()
    serve(args)


if __name__ == "__main__":
    main()
