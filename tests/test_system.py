"""End-to-end system tests: the real training driver (loss goes down,
checkpoint/restart is bit-deterministic, failure injection recovers), the
serving driver, and the roofline/HLO analyzer on a known graph."""

import dataclasses
import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest


def _args(**kw):
    base = dict(arch="starcoder2_3b", smoke=True, mesh="1x1",
                strategy="flashcp", attention_impl="xla", dataset="wlb_llm",
                seq_len=256, batch=2, steps=8, lr=1e-3, q_chunk=128,
                grad_compression="none", checkpoint_dir="", ckpt_every=0,
                log_every=100, resume=False, prefetch=False, no_remat=False,
                fail_at=-1)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_training_loss_decreases(tmp_path):
    from repro.launch.train import train
    out = train(_args(checkpoint_dir=str(tmp_path), steps=12))
    losses = out["losses"]
    assert out["final_step"] == 12
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.05
    assert all(np.isfinite(losses))


def test_training_failure_recovery_is_deterministic(tmp_path):
    """Inject a failure; the recovered run must replay the identical loss
    trajectory (deterministic pipeline + checkpoint restore)."""
    from repro.launch.train import train
    ref = train(_args(checkpoint_dir=str(tmp_path / "a"), steps=6))
    out = train(_args(checkpoint_dir=str(tmp_path / "b"), steps=6,
                      ckpt_every=2, resume=True, fail_at=4))
    # the recovered run covers all 6 steps; the post-restore replay of the
    # final steps must reproduce the uninterrupted run exactly
    assert out["final_step"] == 6
    np.testing.assert_allclose(out["losses"][-3:], ref["losses"][-3:],
                               rtol=1e-6)


def test_training_with_compression(tmp_path):
    from repro.launch.train import train
    out = train(_args(checkpoint_dir=str(tmp_path), steps=8,
                      grad_compression="int8"))
    assert np.isfinite(out["losses"]).all()
    assert np.mean(out["losses"][-2:]) < np.mean(out["losses"][:2]) + 0.1


def test_serve_driver():
    from repro.launch.serve import serve
    out = serve(types.SimpleNamespace(arch="starcoder2_3b", smoke=True,
                                      requests=2, prompt_len=32, gen=4))
    assert sorted(out["tokens"]) == [0, 1]
    for toks in out["tokens"].values():
        assert toks.shape == (4,) and (toks >= 0).all()
    # no prompt replay: prefill is chunk steps only, and the decode
    # window excludes the prefill-produced first token (3 per request).
    # Under the unified scheduler slots enter decode as soon as their own
    # prefill completes, so the decode window can span up to 2*3 steps
    # depending on prompt-length skew — but never stalls.
    assert out["stats"]["prefill_decode_steps"] == 0
    assert out["stats"]["prefill_steps"] > 0
    assert out["stats"]["decode_tokens"] == 2 * 3
    assert 3 <= out["stats"]["decode_steps"] <= 6
    assert out["stats"]["stalled_decode_steps"] == 0


# --------------------------------------------------------------------- #
def test_hlo_analyzer_counts_trip_counts():
    """Known graph: scan of k matmuls must report k x the flops."""
    from repro.launch.hlo_analysis import analyze_hlo

    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    x = jnp.zeros((64, 64))
    compiled = jax.jit(f).lower(x, x).compile()
    cost = analyze_hlo(compiled.as_text())
    expect = 7 * 2 * 64 ** 3
    assert expect * 0.99 <= cost.flops <= expect * 1.2


def test_hlo_analyzer_collectives():
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.roofline import roofline_terms

    terms = roofline_terms(197e12, 819e9, 50e9)
    assert terms["compute_s"] == pytest.approx(1.0)
    assert terms["memory_s"] == pytest.approx(1.0)
    assert terms["collective_s"] == pytest.approx(1.0)

    # known single-collective graph
    from repro.compat import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    # trivial: no collectives on a 1x1 mesh
    compiled = jax.jit(lambda x: x + 1).lower(jnp.zeros((8, 8))).compile()
    cost = analyze_hlo(compiled.as_text())
    assert cost.collective_wire_bytes == 0


def test_dryrun_cell_records_schema():
    """The dry-run record for one tiny local cell has the full schema the
    EXPERIMENTS.md tables read (run on the saved full-matrix results if
    present, else skip)."""
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "..",
                        "dryrun_results.json")
    if not os.path.exists(path):
        pytest.skip("dry-run matrix not yet generated")
    recs = json.load(open(path))
    ok = [r for r in recs if r["status"] == "ok"]
    assert ok, "no successful dry-run records"
    for r in ok:
        assert {"arch", "shape", "mesh", "memory", "cost", "collectives",
                "roofline"} <= set(r)
        assert r["roofline"]["dominant"] in ("compute", "memory",
                                             "collective")


# --------------------------------------------------------------------- #
def test_decode_matches_forward_logits():
    """Serving-path consistency: token-by-token decode with the KV/SSM
    caches must reproduce the teacher-forced forward logits at every
    position (binds attn_apply/attn_decode, rope positions, cache updates
    and — for hybrid archs — the mamba train/decode paths together).

    Root cause of the former xfail (seed "jamba numeric drift"): it was
    never kernel numerics — the attention decode path matches at ~2e-6
    (starcoder below; flash_decode's f32 accumulation and softmax scale
    are validated against the forward oracle in test_kernels.py) and so
    does the mamba recurrence in isolation.  Jamba is *MoE*: the
    teacher-forced forward routes all B*T tokens through
    capacity-clipped dispatch jointly (capacity_factor=1.25 -> experts
    overflow and *late tokens get dropped*), while one-token decode
    steps route B tokens at a time and essentially never drop.  The
    divergence is a documented semantic property of capacity-based
    routing, not numeric drift — so the hybrid arch is checked with
    capacity lifted to the drop-free regime, where decode must (and
    does) match tightly.
    """
    import dataclasses
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.configs import ARCHS, reduce_for_smoke
    from repro.models import (decode_step, forward, init_cache, init_params,
                              make_local_context)

    B, T = 2, 24

    def worst_decode_diff(cfg):
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T))
                             .astype(np.int32))
        doc = jnp.zeros((B, T), jnp.int32)
        pos = jnp.asarray(np.tile(np.arange(T, dtype=np.int32), (B, 1)))
        ctx = make_local_context(doc, pos, q_chunk=8)
        params = init_params(jax.random.PRNGKey(0), cfg)
        ref_logits, _ = forward(params, cfg, ctx, {"tokens": tokens},
                                remat=False)
        cache = init_cache(cfg, B, T)
        # jitted like the serving engine: one compile per cfg (eager
        # flash-decode interpret re-traces the kernel every step)
        dec = jax.jit(lambda p, c, b, t: decode_step(p, cfg, c, b, t))
        worst = 0.0
        for t in range(T):
            lg, cache = dec(params, cache,
                            {"tokens": tokens[:, t]},
                            jnp.full((B,), t, jnp.int32))
            worst = max(worst, float(np.max(np.abs(
                np.asarray(lg) - np.asarray(ref_logits[:, t])))))
        return worst

    # attention-only arch: strict parity, no routing in the way
    assert worst_decode_diff(reduce_for_smoke(ARCHS["starcoder2_3b"])) \
        < 1e-4

    # hybrid MoE arch: strict parity once capacity clipping can't drop
    # tokens (cap >= all routed tokens)
    jamba = reduce_for_smoke(ARCHS["jamba_v0_1_52b"])
    dropfree = dataclasses.replace(jamba, capacity_factor=float(B * T))
    assert worst_decode_diff(dropfree) < 1e-4


# --------------------------------------------------------------------- #
def test_layers_cuts_depth_only():
    """``--layers`` cuts the model to N layers and leaves every width as
    the config states it."""
    from repro.configs import get_config
    from repro.launch.train import model_config
    full = get_config("starcoder2_3b")
    cut = model_config(_args(smoke=False, layers=2))
    assert cut.num_layers == 2
    assert cut == dataclasses.replace(full, num_layers=2)


def test_compile_cache_dir_from_env_is_left_alone(monkeypatch):
    from repro.launch.compile_cache import setup_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert setup_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_defaults_to_fixed_checkout_path(monkeypatch):
    import pathlib
    from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                            setup_compile_cache)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = setup_compile_cache()
        assert got == setup_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        root = pathlib.Path(__file__).resolve().parents[1]
        assert CHECKOUT_CACHE_DIR == root / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
