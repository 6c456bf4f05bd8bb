"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic, limits and metrics are found by name
from ``BENCHMARK.json`` and the files under ``bench/`` (see
``bench/harness/catalog.py``).  The run makes its inputs and weights from
``--seed``, warms up every step program the cell's stream can meet, runs
the measured window for ``--seconds``, checks the first steps of the timed
path against the float32 reference, and prints one JSON line as the last
line of standard output::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, "breakdown": {...}, "compared": {...}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of a few steps after the
window.  The numbers compared with the reference are also the last lines
of standard error.  Without a TPU, or with fewer chips than the cell
needs, the run exits 3 and prints no result.

JAX's persistent compile cache is kept at ``<checkout>/.jax_cache`` (or
where ``JAX_COMPILATION_CACHE_DIR`` says), so only a cell's first run in a
checkout compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
# libtpu logs to a fixed path under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

EXIT_NO_CHIP = 3


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def setup_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def metric_values(cell, metrics, rec) -> dict:
    from harness.catalog import metric_reader

    out = {}
    for m in metrics:
        v = metric_reader(cell.bench_dir, m.name)(rec)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness.catalog import load_cell
    from harness.device import NoChip, load_peaks, pick_devices

    cell = load_cell(args.workload, ROOT)
    peaks = load_peaks(cell.bench_dir)
    cache = setup_compile_cache()
    try:
        devices = pick_devices(cell.chips, peaks)
    except NoChip as e:
        log(f"{e}; nothing was run")
        return EXIT_NO_CHIP
    import repro  # noqa: F401  (fails here when the program is absent)
    from harness.catalog import load_module

    kind = devices[0].device_kind
    log(f"{cell.name}: {len(devices)} x {kind}, seed {args.seed}, "
        f"{args.seconds:g}s window, trace {args.trace}; compile cache "
        f"{cache}")
    runner = load_module(cell.bench_dir / "runners"
                         / f"{cell.traffic.kind}.py")
    out = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     devices, t_start=T_START,
                     work_dir=str(ROOT / ".bench_run"), log=log)
    rec = out["record"]
    rec.peaks = peaks[kind]
    metrics = metric_values(cell, cell.per_layer if args.trace
                            else cell.end_to_end, rec)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and rec.trace is not None:
        device["busy_s"] = rec.trace.mean_busy_s()
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = {"device_ops": rec.trace.top_ops(10),
                               "idle_gaps": rec.trace.named_gaps(10)}
    result["compared"] = out["compared"]
    print(f"compiles inside the window: {out['in_window_compiles']}",
          flush=True)
    print(json.dumps(result), flush=True)
    for name, c in out["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
