"""Overlapped vs blocking CP execution + visit-table builder benchmarks.

Measures, on the simulated 4-way CPU CP mesh (subprocess, so the forced
device count never leaks into the caller's JAX runtime):

* wall-clock time of one flashcp attention step, blocking all-gather
  island (``overlap="none"``) vs chunked ppermute exchange
  (``overlap="chunked"``);
* **exposed** (un-overlapped) collective time and collective count of
  both lowered programs, via the two-resource schedule model of
  :mod:`repro.launch.hlo_analysis`;
* host time of the vectorized ``build_block_tables`` vs the legacy
  list-based builder at 131072 tokens / 128-token blocks (16-doc packed
  layout — the long-context regime FlashCP plans for).

Emits ``name,us_per_call,derived`` CSV rows (run.py suite ``overlap``)
and writes machine-readable ``BENCH_overlap.json`` at the repo root.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RESULT_JSON = os.path.join(ROOT, "BENCH_overlap.json")

N_CP = 4
CTX = 8192
DOC_LENS = [2500, 900, 1800, 1400, 700, 892]   # multi-doc long-context mix


def _child() -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import make_mesh, set_mesh
    from repro.core.cp_attention import make_cp_context
    from repro.kernels.doc_attention import build_block_tables
    from repro.launch.hlo_analysis import analyze_hlo, schedule_model
    from repro.planner import encode_plan_batch, get_planner

    rng = np.random.default_rng(0)
    results: dict = {"config": {"cp": N_CP, "context_len": CTX,
                                "doc_lens": DOC_LENS}}

    # ---- blocking vs chunked flashcp execution ------------------------ #
    mesh = make_mesh((1, N_CP), ("data", "model"))
    doc_lens = np.asarray(DOC_LENS, np.int64)
    assert doc_lens.sum() == CTX
    plan = get_planner("flashcp")(doc_lens, N_CP)
    stack, _ = encode_plan_batch([plan], align=128)
    arrays = {k: jnp.asarray(v) for k, v in stack.items()}
    C_pad = stack["doc"].shape[1]
    B, HQ, HKV, D = 1, 4, 2, 64
    sh = NamedSharding(mesh, P(None, None, "model", None))
    q = jax.device_put(jnp.asarray(
        rng.standard_normal((B, HQ, C_pad, D)).astype(np.float32)), sh)
    k = jax.device_put(jnp.asarray(
        rng.standard_normal((B, HKV, C_pad, D)).astype(np.float32)), sh)
    v = jax.device_put(jnp.asarray(
        rng.standard_normal((B, HKV, C_pad, D)).astype(np.float32)), sh)

    exec_res = {}
    for ov in ("none", "chunked"):
        with set_mesh(mesh):
            ctx = make_cp_context(mesh, arrays, strategy="flashcp",
                                  impl="xla", batch_axes=(None,),
                                  head_dim=D, q_chunk=512, overlap=ov)
            fn = jax.jit(ctx.attn)
            fn(q, k, v).block_until_ready()        # compile + warm
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                fn(q, k, v).block_until_ready()
                times.append(time.perf_counter() - t0)
            txt = fn.lower(q, k, v).compile().as_text()
        sc = schedule_model(txt)
        hc = analyze_hlo(txt)
        exec_res[ov] = {
            "wallclock_us": min(times) * 1e6,
            "exposed_comm_us": sc.exposed_comm_s * 1e6,
            "comm_busy_us": sc.comm_busy_s * 1e6,
            "modeled_makespan_us": sc.makespan_s * 1e6,
            "collective_count": sc.collective_count,
            "collective_wire_bytes": hc.collective_wire_bytes,
        }
        print(f"overlap_exec_{ov}_wallclock,"
              f"{exec_res[ov]['wallclock_us']:.0f},")
        print(f"overlap_exec_{ov}_exposed_comm_us,,"
              f"{exec_res[ov]['exposed_comm_us']:.2f}")
        print(f"overlap_exec_{ov}_collectives,,"
              f"{exec_res[ov]['collective_count']:.0f}")
    reduction = (exec_res["none"]["exposed_comm_us"]
                 / max(exec_res["chunked"]["exposed_comm_us"], 1e-9))
    exec_res["exposed_comm_reduction_x"] = reduction
    print(f"overlap_exposed_comm_reduction,,{reduction:.2f}x")
    results["execution"] = exec_res

    # ---- vectorized vs legacy build_block_tables ---------------------- #
    T, blk, n_docs = 131072, 128, 16
    d = np.repeat(np.arange(n_docs, dtype=np.int32), T // n_docs)[None]
    p = np.tile(np.arange(T // n_docs, dtype=np.int32), n_docs)[None]

    def best(f, n):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            f()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    tv = best(lambda: build_block_tables(d, p, d, p, block_q=blk,
                                         block_k=blk), 5)
    tl = best(lambda: build_block_tables(d, p, d, p, block_q=blk,
                                         block_k=blk, legacy=True), 3)
    a = build_block_tables(d, p, d, p, block_q=blk, block_k=blk)
    b = build_block_tables(d, p, d, p, block_q=blk, block_k=blk,
                           legacy=True)
    parity = all(np.array_equal(getattr(a, n), getattr(b, n))
                 for n in ("kv_idx", "kv_nvis", "q_idx", "q_nvis"))
    results["block_tables"] = {
        "tokens": T, "block": blk, "num_docs": n_docs,
        "vectorized_us": tv * 1e6, "legacy_us": tl * 1e6,
        "speedup_x": tl / tv, "parity": parity,
    }
    print(f"block_tables_vectorized_131k,{tv*1e6:.0f},")
    print(f"block_tables_legacy_131k,{tl*1e6:.0f},")
    print(f"block_tables_speedup,,{tl/tv:.1f}x")
    print(f"block_tables_parity,,{parity}")

    with open(RESULT_JSON, "w") as f:
        json.dump(results, f, indent=1)
    print(f"overlap_json,,{RESULT_JSON}")


def run():
    """run.py suite entry: spawn the forced-device-count child and relay
    its CSV rows."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_overlap", "--child"],
        capture_output=True, text=True, timeout=1800, env=env, cwd=ROOT)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_overlap child failed:\n{proc.stderr[-4000:]}")
    for line in proc.stdout.splitlines():
        if line.count(",") == 2:
            yield line


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child()
    else:
        for row in run():
            print(row)
