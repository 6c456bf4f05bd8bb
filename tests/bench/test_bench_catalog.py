"""A configuration, a traffic mix and a per-layer metric are found by name
from files of their own: adding them touches no file that is there."""

from __future__ import annotations

import json
import shutil

import pytest

from bench_tiny import BENCH, REPO, make_root, run_tiny
from harness.traffic import draw_step
from harness import check
from harness.catalog import load_benchmark, load_cell, metric_reader


SEED = 2**33 + 11


def test_committed_cells_load():
    bench = load_benchmark(REPO)
    for w in bench["workloads"]:
        cell = load_cell(w["name"], REPO, bench)
        assert cell.chips == (cell.config["mesh"]["data"]
                              * cell.config["mesh"]["model"])
        assert [m.name for m in cell.end_to_end][-1] == "setup_s"
        for m in cell.end_to_end + cell.per_layer:
            assert callable(metric_reader(cell.bench_dir, m.name))
        assert set(cell.limits) == set(check.NAMES)


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        load_cell("no.such.cell", REPO)


def test_new_config_traffic_and_metric_as_files(tmp_path):
    root = make_root(tmp_path)
    b = root / "bench"
    # a new per-layer metric: a reader file of its own
    (b / "metrics").unlink()
    shutil.copytree(BENCH / "metrics", b / "metrics")
    (b / "metrics" / "window_steps.py").write_text(
        "def read(rec):\n    return len(rec.window)\n")
    # a new model family: its reference and its counts, as files; the
    # family's own count of attention pairs fills each step's record
    for d in ("flops", "reference"):
        (b / d).unlink()
        shutil.copytree(BENCH / d, b / d)
        shutil.copy(b / d / "dense.py", b / d / "stub.py")
    with open(b / "flops" / "stub.py", "a") as f:
        f.write("\n\ndef attention_pairs(cfg, doc_lens):\n"
                "    return 1000 + len(doc_lens)\n")
    # a new configuration and a new traffic mix, as files
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["num_hidden_layers"] = 1
    cfg["family"] = "stub"
    (b / "configs" / "tiny_one.json").write_text(json.dumps(cfg))
    tr = json.loads((b / "traffic" / "tiny.json").read_text())
    tr["docs"] = [{"p": 1.0, "dist": "lognormal", "mu": 3.5, "sigma": 0.5,
                   "lo": 16, "hi": 256}]
    (b / "traffic" / "short.json").write_text(json.dumps(tr))
    (b / "limits" / "tiny_one.short.json").write_text(
        (b / "limits" / "tiny.t.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_one", "source": "test",
                             "file": "bench/configs/tiny_one.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_one.short",
                               "config": "tiny_one", "traffic": "short",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "window_steps", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "train step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["tiny_one.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = load_cell("tiny_one.short", root)
    assert cell.config["num_hidden_layers"] == 1
    assert dict(cell.traffic.docs[0])["mu"] == 3.5
    assert "window_steps" in [m.name for m in cell.per_layer]
    assert "window_steps" not in [m.name for m in
                                  load_cell("tiny.t", root).per_layer]

    import run as bench_run
    out = run_tiny(root, workload="tiny_one.short", seed=SEED)
    rec = out["record"]
    rec.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    got = bench_run.metric_values(cell, cell.per_layer, rec)
    assert got["window_steps"]["value"] == out["attempted"] >= 1
    assert got["window_steps"]["unit"] == "steps"
    assert rec.window and all(
        r["pairs"] == sum(1000 + len(x.doc_lens) for x in draw_step(
            cell.traffic, SEED, r["step"], cfg["vocab_size"]))
        for r in rec.window)
    # trace readers find nothing to read without a trace, and stay silent
    assert "device_idle_pct" not in got and "attn_roofline" not in got
