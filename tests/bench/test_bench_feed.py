"""The feed builds a step's arrays with the program's own PipelineConfig:
the same arrays as the feed's earlier composition from run options alone
(so the same step programs and compile-cache entries), and the same as the
program's ``data/pipeline.make_batch`` for the lengths and tokens it
draws."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench_tiny import make_root
from harness.catalog import load_cell, load_module
from harness.feed import build_batch, plan_config, signature
from harness.traffic import SeqSample, draw_step, packed_labels

STEPS = 8
SEEDS = (5, 2**40 + 17)


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """The planner options the feed once took from the run options."""
    strategy: str
    cp: int
    overlap: str
    grid: str
    target_imbalance: float
    align: int = 128
    block_q: int = 128
    block_k: int = 128


def earlier_arrays(spec: PlanSpec, samples) -> dict:
    """The feed's earlier composition of the planner calls, kept as the
    reference of the arrays the feed builds."""
    from repro.core.cp_attention import resolve_overlap
    from repro.planner import (emit_visit_tables, encode_plan_batch,
                               get_planner, plan_many)

    planner = get_planner(spec.strategy)
    kwargs = {}
    if planner.info.supports_target_ratio:
        kwargs["target_ratio"] = spec.target_imbalance
    plans = plan_many(lambda lens: planner(lens, spec.cp, **kwargs),
                      [s.doc_lens for s in samples], workers=1)
    stack, _ = encode_plan_batch(plans, align=spec.align, workers=1)
    exec_style = planner.info.exec_style
    arrays = {k: v for k, v in stack.items() if k != "perm"}
    arrays.update(emit_visit_tables(
        stack["doc"], stack["pos"],
        stack["gath_doc"] if exec_style in ("flashcp", "contiguous")
        else None,
        stack["gath_pos"] if exec_style in ("flashcp", "contiguous")
        else None,
        num_workers=spec.cp, strategy=exec_style,
        overlap=resolve_overlap(exec_style, "pallas", spec.overlap),
        grid=spec.grid, block_q=spec.block_q, block_k=spec.block_k,
        cache=False))
    perm = stack["perm"]
    tokens = np.full(perm.shape, -1, np.int32)
    labels = np.full(perm.shape, -1, np.int32)
    for b, s in enumerate(samples):
        valid = perm[b] >= 0
        tokens[b, valid] = s.tokens[perm[b, valid]]
        labels[b, valid] = s.labels[perm[b, valid]]
    arrays["tokens"] = tokens
    arrays["labels"] = labels
    return arrays


def _cell(tmp_path, cp):
    cell = load_cell("tiny.t", make_root(tmp_path, cp=cp))
    runner = load_module(cell.bench_dir / "runners" / "train.py")
    cfg = runner.model_config(cell.config, cell.traffic.context_len)
    run = runner.run_config(cell.config)
    return cell, cfg, run


def assert_same_arrays(got: dict, want: dict) -> None:
    assert sorted(got) == sorted(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    assert signature(got) == signature(want)


# CP 1 is the tiny cell; CP 4 the layout of test_cp4_exchange
@pytest.mark.parametrize("cp", [1, 4])
@pytest.mark.parametrize("seed", SEEDS)
def test_same_arrays_as_the_earlier_feed(tmp_path, cp, seed):
    cell, cfg, run = _cell(tmp_path, cp)
    pipe = plan_config(cfg, run, cp, cell.traffic)
    assert (pipe.cache_plans, pipe.planner_workers) == (False, 1)
    spec = PlanSpec(strategy=run.cp_strategy, cp=cp,
                    overlap=run.cp_overlap, grid=run.kernel_grid,
                    target_imbalance=run.target_imbalance)
    for step in range(STEPS):
        samples = draw_step(cell.traffic, seed, step, cfg.vocab_size)
        hb = build_batch(pipe, samples, step)
        assert hb.step == step and hb.samples is samples
        assert_same_arrays(hb.arrays, earlier_arrays(spec, samples))


@pytest.mark.parametrize("cp", [1, 4])
def test_same_arrays_as_the_programs_make_batch(tmp_path, cp):
    """Given the lengths and tokens ``make_batch`` draws, the feed builds
    the arrays ``make_batch`` builds (its plan and table caches on)."""
    from repro.data.distributions import make_rng
    from repro.data.packing import pack_sequence
    from repro.data.pipeline import make_batch

    cell, cfg, run = _cell(tmp_path, cp)
    pipe = plan_config(cfg, run, cp, cell.traffic)
    prog = dataclasses.replace(pipe, dataset="wlb_llm", seed=11,
                               cache_plans=True, planner_workers=0)
    for step in range(STEPS):
        want = make_batch(prog, step)
        rng = make_rng(hash((prog.seed, 0, step)) % (2 ** 63))
        samples = []
        for b in range(prog.batch_per_host):
            lens = pack_sequence(prog.dataset, prog.context_len, rng)
            perm = want["perm"][b]
            packed = np.empty(int(lens.sum()), np.int32)
            packed[perm[perm >= 0]] = want["tokens"][b][perm >= 0]
            samples.append(SeqSample(lens, packed,
                                     packed_labels(packed, lens)))
        got = build_batch(pipe, samples, step).arrays
        assert_same_arrays(got, {k: v for k, v in want.items()
                                 if k not in ("perm", "stats")})
