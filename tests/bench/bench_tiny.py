"""A tiny training cell, written as files into a temporary checkout, for
driving the benchmark's runner on the CPU (no chip, Pallas in interpret
mode).  Its widths are tiny; its configuration, traffic and limits
files have the layout of the real cells'.  Its limits are set by the rule
of the real cells (lower^0.4 x upper^0.6) from readings of this tiny cell
on the CPU, since a cell's limits hold at its own size only: over eight
seeds, sound runs read at most 4.0e-5, 1.6e-3, 0.0101, 4.3e-4 (loss_rel,
grad_leaf, grad_row, delta_leaf); the float8 control (three seeds) at
least 2.4e-4, 0.016, 0.119, 5.3e-3, which set the upper readings."""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

LIMITS = {"loss_rel": 1.2e-4, "grad_leaf": 6.4e-3, "grad_row": 0.044,
          "delta_leaf": 1.9e-3}
SHARED = ("harness", "runners", "reference", "flops", "metrics", "kernels",
          "peaks.json")


def make_root(tmp: pathlib.Path, *, cp: int = 1, layers: int = 2,
              context: int = 256, stream_steps: int = 8) -> pathlib.Path:
    """A checkout under ``tmp`` holding one cell, ``tiny.t``."""
    tmp = pathlib.Path(tmp)
    (tmp / "bench").mkdir(parents=True, exist_ok=True)
    for name in SHARED:
        os.symlink(BENCH / name, tmp / "bench" / name)
    for d in ("configs", "traffic", "limits"):
        (tmp / "bench" / d).mkdir()
    cfg = json.loads((BENCH / "configs" / "starcoder2_3b.json").read_text())
    cfg.update(num_hidden_layers=layers, hidden_size=64,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               intermediate_size=128, vocab_size=512,
               mesh={"data": 1, "model": cp})
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    tr = json.loads((BENCH / "traffic" / "wlb_llm.8k.json").read_text())
    tr.update(context_len=context, stream_steps=stream_steps, docs=[
        {"p": 0.2, "dist": "pareto", "alpha": 1.1, "scale": 64, "lo": 64,
         "hi": 4096},
        {"p": 0.8, "dist": "lognormal", "mu": 4.0, "sigma": 1.0, "lo": 16,
         "hi": 4096}])
    (tmp / "bench/traffic/tiny.json").write_text(json.dumps(tr))
    (tmp / "bench/limits/tiny.t.json").write_text(json.dumps(LIMITS))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny.t", "config": "tiny",
                           "traffic": "tiny", "chips": cp, "why": "test"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run_tiny(root: pathlib.Path, *, seed: int = 2**33 + 7,
             seconds: float = 0.3, trace: bool = False, transform=None,
             workload: str = "tiny.t"):
    """Run ``workload`` of ``root`` on the CPU devices; returns the
    runner's output dict."""
    import jax

    from harness.catalog import load_cell, load_module

    cell = load_cell(workload, root)
    runner = load_module(cell.bench_dir / "runners" / "train.py")
    return runner.run(cell, seed, seconds, trace,
                      jax.devices()[:cell.chips], t_start=time.perf_counter(),
                      work_dir=str(root / ".bench_run"),
                      log=lambda m: None, transform=transform)
