"""The comparison that decides ``correct``, driven through a whole run of a
tiny training cell on the CPU: a sound run passes, and a run whose timed
path is broken underneath, or the float8 control in the program's place,
fails.  The limits are the tiny cell's own (``bench_tiny.LIMITS``), set by
the real cells' rule from the tiny cell's readings."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_tiny import LIMITS, REPO, make_root, run_tiny


def test_sound_run_is_correct(tmp_path):
    out = run_tiny(make_root(tmp_path))
    assert out["in_window_compiles"] == 0
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["correct"], out["compared"]


def test_state_left_unchanged_fails(tmp_path, monkeypatch):
    import repro.launch.steps as steps
    monkeypatch.setattr(steps, "adamw_update",
                        lambda params, grads, state, **kw: (params, state))
    out = run_tiny(make_root(tmp_path))
    assert not out["correct"]
    assert out["compared"]["delta_leaf"]["value"] >= 0.99


def test_half_batch_left_out_fails(tmp_path, monkeypatch):
    import repro.launch.steps as steps
    real = steps.loss_fn

    def half(params, cfg, ctx, batch, **kw):
        lab = batch["labels"]
        keep = lab.shape[-1] // 2
        lab = lab.at[..., keep:].set(-1)
        return real(params, cfg, ctx, {**batch, "labels": lab}, **kw)

    monkeypatch.setattr(steps, "loss_fn", half)
    out = run_tiny(make_root(tmp_path))
    assert not out["correct"], out["compared"]


def test_altered_token_fails(tmp_path):
    def alter(hb):
        toks = hb.arrays["tokens"].copy()
        toks[:, 0] = (toks[:, 0] + 1) % 512
        hb.arrays["tokens"] = toks
        return hb

    out = run_tiny(make_root(tmp_path), transform=alter)
    assert not out["correct"], out["compared"]


EXCHANGE = r"""
import sys, json
sys.path.insert(0, {tests!r})
import repro.core.cp_attention as cpa
if {broken}:
    cpa._run_hops = lambda init_part, *a, **k: init_part
from bench_tiny import make_root, run_tiny
out = run_tiny(make_root({root!r}, cp=4))
print(json.dumps({{"correct": out["correct"],
                   "compared": out["compared"]}}))
"""


@pytest.mark.parametrize("broken", [False, True],
                         ids=["exchange", "exchange_left_out"])
def test_cp4_exchange(tmp_path, broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    code = EXCHANGE.format(tests=str(REPO / "tests" / "bench"),
                           broken=broken, root=str(tmp_path / "root"))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["correct"] is (not broken), out["compared"]


def test_float8_control_fails(tmp_path):
    """The reference computed with float8 matmuls, read against the float32
    reference, is not correct by the cell's limits."""
    import jax

    from harness import check
    from harness.catalog import load_cell, load_module
    from repro.launch.steps import build_train_step
    from repro.launch.mesh import make_local_mesh
    from repro.configs.base import ShapeConfig

    root = make_root(tmp_path)
    cell = load_cell("tiny.t", root)
    runner = load_module(cell.bench_dir / "runners" / "train.py")
    ref_mod = load_module(cell.bench_dir / "reference" / "dense.py")
    cfg = runner.model_config(cell.config, cell.traffic.context_len)
    bundle = build_train_step(
        cfg, make_local_mesh(1, 1), runner.run_config(cell.config),
        ShapeConfig("t", cell.traffic.context_len, 1, "train"))
    params_abs = bundle.abstract_inputs[0]
    devs = jax.devices()[:1]
    seed = 123456789012
    ref = runner.reference_readings(cell, seed, params_abs, ref_mod, devs,
                                    log=lambda m: None)
    ctl = runner.reference_readings(cell, seed, params_abs, ref_mod, devs,
                                    mm_dtype=jax.numpy.float8_e4m3fn,
                                    log=lambda m: None)
    assert np.all(np.isfinite(ctl.grad_norms))
    ok, compared = check.judge(check.compare(ctl, ref), LIMITS)
    assert not ok, compared


def test_reference_computes_with_stored_weights(tmp_path):
    """The reference computes with its float32 master weights rounded to the
    configuration's stored dtype: an update below half a bfloat16 unit in
    the last place leaves its loss where it was, as it leaves the model's
    bfloat16 weights, while the gradient still reaches the master copy."""
    import jax
    import jax.numpy as jnp

    from harness import weights
    from harness.catalog import load_cell, load_module
    from harness.traffic import draw_step
    from repro.launch.steps import _abstract_state

    cell = load_cell("tiny.t", make_root(tmp_path))
    runner = load_module(cell.bench_dir / "runners" / "train.py")
    ref_mod = load_module(cell.bench_dir / "reference" / "dense.py")
    params_abs, _ = _abstract_state(runner.model_config(
        cell.config, cell.traffic.context_len))
    master = weights.make_init(params_abs, widen=True)(
        weights.seed_words(2**40 + 3))
    batch = ref_mod.packed_inputs(draw_step(cell.traffic, 5, 0,
                                            cell.config["vocab_size"]))

    def loss(config, p):
        fn = ref_mod._model(config, None, 128, 1024)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(fn))(p, batch)

    base, grads = loss(cell.config, master)
    # down the gradient by 2**-12 of each weight: under half a bfloat16
    # unit in the last place (at least 2**-9 of the weight)
    nudged = jax.tree.map(lambda x, g: x - jnp.sign(g) * jnp.abs(x) * 2.0**-12,
                          master, grads)
    assert float(loss(cell.config, nudged)[0]) == float(base)
    assert all(float(jnp.sum(g * g)) > 0 for g in jax.tree.leaves(grads))
    as_f32 = dict(cell.config, torch_dtype="float32")
    assert float(loss(as_f32, nudged)[0]) != float(loss(as_f32, master)[0])
