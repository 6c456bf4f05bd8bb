"""Run FlashCP's train and serve paths once on a TPU, through their entry
points, at starcoder2_3b's published widths with random weights.

    python3 chip_smoke.py           # one chip: the train phase, then serve
    python3 chip_smoke.py --four    # four chips: CP 4 step vs one device

One chip:

* **train** -- ``repro.launch.train.train`` on a depth cut (``LAYERS`` of
  30 layers, widths untouched) over 8192-token packed sequences of the
  wlb_llm mix, mesh 1x1, Pallas attention on the flat grid with chunked
  overlap.  The train step is first compiled ahead of time: its
  ``memory_analysis`` must leave ``HEADROOM`` of the chip's memory free
  and its HLO must hold the three Pallas attention kernels as
  ``tpu_custom_call`` instructions named ``doc_attn_fwd``,
  ``doc_attn_dq`` and ``doc_attn_dkv``.  The
  loss must be finite and falling, and the first step's loss and grad
  norm must match the XLA attention path on the same params and batch
  within ``FIRST_STEP_RTOL``.
* **serve** -- ``repro.launch.serve.serve`` on the full 30-layer model
  with paged KV and the flash-decode kernel: 4 ragged requests, prompts
  up to 1024 tokens, 32 new tokens each; every request must end ``ok``.

``--four`` runs only the CP path: one train step on a 1x4 mesh at CP 4
(flashcp, Pallas, chunked ppermute overlap, the same depth cut) and the
same global batch and params on one device through the same step
builder; loss and grad norm must agree within ``CP_RTOL``.

Data and weights are made from ``--seed``; nothing is downloaded.  The
last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check raises before it.  Without a TPU the script exits 2 and prints no
result.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import re
import shutil
import sys
import time
import types

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "starcoder2_3b"
# deepest cut whose compiled train step leaves HEADROOM of a v5e's 16 GiB
# free at 8192 tokens (memory_analysis: 12.58 GiB at 3 layers, 13.94 GiB
# at 4, over 15 GiB at 5)
LAYERS = 4
SEQ = 8192
TRAIN_STEPS = 4
HEADROOM = 0.10
# bf16 weights and activations with f32 accumulation: the Pallas and XLA
# attention paths round differently, a few bf16 ulps (2**-8) of the loss;
# the grad norm sums rounding over every parameter, so it gets twice that
FIRST_STEP_RTOL = {"loss": 1e-2, "grad_norm": 2e-2}
# Adam's first update moves every weight by about lr, which shifts a
# matmul's output by about lr * fan_in against an output of init_std *
# sqrt(fan_in): at 3072 wide, lr 3e-4 is a ~60% shift and the loss of a
# fresh model rose from 11.05 to 19.36 in four steps on the chip; 5e-5
# keeps it near a tenth.  The smoke checks descent, not a schedule.
LR = 5e-5
CP_RTOL = 2e-2
# the pallas_call names of kernels/doc_attention.py
ATTN_KERNELS = ("doc_attn_fwd", "doc_attn_dq", "doc_attn_dkv")
SERVE = dict(requests=4, prompt_len=1024, gen=32, prefill_chunk=512)
SCRATCH = os.path.join(ROOT, ".smoke")


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def train_args(seed: int, **kw) -> types.SimpleNamespace:
    """Arguments of ``repro.launch.train.train`` for the smoke runs."""
    base = dict(arch=ARCH, smoke=False, layers=LAYERS, mesh="1x1",
                strategy="flashcp", attention_impl="pallas",
                dataset="wlb_llm", seq_len=SEQ, batch=1, steps=TRAIN_STEPS,
                lr=LR, q_chunk=128, grad_compression="none",
                checkpoint_dir=os.path.join(SCRATCH, "ckpt"), ckpt_every=0,
                log_every=1, resume=False, prefetch=False, no_remat=False,
                fail_at=-1, seed=seed)
    base.update(kw)
    return types.SimpleNamespace(**base)


def device_memory_limit() -> int:
    import jax
    return jax.devices()[0].memory_stats()["bytes_limit"]


def peak_bytes() -> int:
    import jax
    return jax.devices()[0].memory_stats().get("peak_bytes_in_use", -1)


def assert_kernels_compiled(hlo: str, what: str) -> None:
    for name in ATTN_KERNELS:
        check(re.search(rf'%{name}(\.\d+)? = [^\n]*custom_call_target='
                        r'"tpu_custom_call"', hlo) is not None,
              f"{what}: no tpu_custom_call named {name} in the compiled "
              "HLO -- the Pallas attention kernels are not compiled in")


def one_step(cfg, mesh, run, args, batch, *, want_hlo=False):
    """Build the driver's train step on ``mesh``, compile it, and run it
    once from fresh params (``run.seed``) on ``batch``.  Returns
    ``(metrics, compile_s, run_s, hlo)``."""
    import jax
    import jax.numpy as jnp
    from repro.compat import set_mesh
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import build_train_step
    from repro.launch.train import device_put_batch
    from repro.models import init_params
    from repro.optim import adamw_init

    shape = ShapeConfig("custom", args.seq_len, args.batch, "train")
    bundle = build_train_step(cfg, mesh, run, shape, q_chunk=args.q_chunk)
    p_shard, o_shard, b_shard, _ = bundle.in_shardings
    step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                   out_shardings=bundle.out_shardings,
                   donate_argnums=bundle.donate_argnums)
    with set_mesh(mesh):
        params = jax.device_put(
            init_params(jax.random.PRNGKey(run.seed), cfg), p_shard)
        opt = jax.device_put(adamw_init(params), o_shard)
        db = device_put_batch(batch, b_shard)
        db = {k: db[k] for k in bundle.abstract_inputs[2]}
        s0 = jnp.asarray(0, jnp.int32)
        t0 = time.perf_counter()
        compiled = step.lower(params, opt, db, s0).compile()
        compile_s = time.perf_counter() - t0
        hlo = compiled.as_text() if want_hlo else ""
        t0 = time.perf_counter()
        _, _, metrics = compiled(params, opt, db, s0)
        metrics = {k: float(v) for k, v in metrics.items()}
        run_s = time.perf_counter() - t0
    return metrics, compile_s, run_s, hlo


def train_phase(seed: int, **overrides) -> None:
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import make_batch
    from repro.launch import train as T
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import build_train_step

    args = train_args(seed, **overrides)
    shutil.rmtree(args.checkpoint_dir, ignore_errors=True)
    cfg = T.model_config(args)
    run = T.run_config(args)
    log(f"train: {cfg.name} {cfg.num_layers} layers d_model {cfg.d_model} "
        f"heads {cfg.num_heads}/{cfg.num_kv_heads} head_dim "
        f"{cfg.resolved_head_dim} d_ff {cfg.d_ff} vocab {cfg.vocab_size}; "
        f"{args.seq_len} tokens x {args.batch} ({args.dataset}), "
        f"attention {run.attention_impl} grid {run.kernel_grid} overlap "
        f"{run.cp_overlap}")
    mesh = make_local_mesh(1, 1)
    shape = ShapeConfig("custom", args.seq_len, args.batch, "train")

    # ahead-of-time compile at the worst-case table widths: fit + kernels
    t0 = time.perf_counter()
    compiled = build_train_step(cfg, mesh, run, shape,
                                q_chunk=args.q_chunk).lower().compile()
    aot_s = time.perf_counter() - t0
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    limit = device_memory_limit()
    log(f"train step compiled ahead of time in {aot_s:.1f}s: needs "
        f"{need / 2**30:.2f} GiB (args {m.argument_size_in_bytes / 2**30:.2f}"
        f", temp {m.temp_size_in_bytes / 2**30:.2f}) of "
        f"{limit / 2**30:.2f} GiB")
    check(need <= (1 - HEADROOM) * limit,
          f"train step needs {need} B, over {1 - HEADROOM:.0%} of {limit} B")
    assert_kernels_compiled(compiled.as_text(), "train step")
    del compiled

    t0 = time.perf_counter()
    out = T.train(args)
    log(f"train entry point: {len(out['losses'])} steps in "
        f"{time.perf_counter() - t0:.1f}s (compiles and the final save "
        f"included); checkpoint save {out['ckpt_save_s']:.2f}s; peak "
        f"{peak_bytes() / 2**30:.2f} GiB")
    shutil.rmtree(args.checkpoint_dir, ignore_errors=True)
    losses, gnorm0 = out["losses"], out["grad_norms"][0]
    log(f"losses {losses}")
    del out
    gc.collect()

    # reference: the XLA attention path, same params and step-0 batch
    xrun = T.run_config(train_args(seed, **{**overrides,
                                            "attention_impl": "xla"}))
    batch = make_batch(T.pipeline_config(args, cfg, run, 1), 0)
    ref, c_s, r_s, _ = one_step(cfg, mesh, xrun, args, batch)
    del batch
    gc.collect()
    log(f"xla reference step: compile {c_s:.1f}s, run {r_s:.2f}s")
    for key, got in (("loss", losses[0]), ("grad_norm", gnorm0)):
        rel = abs(got - ref[key]) / abs(ref[key])
        log(f"first-step {key}: pallas {got!r} vs xla {ref[key]!r} (rel "
            f"diff {rel:.2e}, tolerance {FIRST_STEP_RTOL[key]})")
        check(rel <= FIRST_STEP_RTOL[key], f"pallas vs xla first-step {key} "
              f"differ by {rel:.3e} relative")
    check(len(losses) == args.steps and all(map(math.isfinite, losses)),
          f"train losses not finite: {losses}")
    check(losses[-1] < losses[0], f"train loss did not fall: {losses}")


def serve_phase(seed: int, **overrides) -> None:
    import jax
    from repro.launch.serve import serve

    live = sum(a.nbytes for a in jax.live_arrays())
    log(f"serve: {live / 2**30:.2f} GiB still live on the device before "
        "the serve phase")
    args = types.SimpleNamespace(**{
        "arch": ARCH, "smoke": False, "slots": 0, "kv_layout": "paged",
        "decode_impl": "flash", "seed": seed, **SERVE, **overrides})
    t0 = time.perf_counter()
    out = serve(args)
    log(f"serve entry point: {time.perf_counter() - t0:.1f}s (compiles "
        f"and weight init included); peak {peak_bytes() / 2**30:.2f} GiB")
    check(out["kv_layout"] == "paged", f"kv layout {out['kv_layout']}")
    res = out["results"]
    check(sorted(res) == list(range(args.requests)),
          f"requests lost: {sorted(res)}")
    for rid, r in sorted(res.items()):
        check(r["status"] == "ok", f"request {rid} ended {r['status']}")
        check(len(r["tokens"]) == args.gen,
              f"request {rid}: {len(r['tokens'])} of {args.gen} tokens")
    log(f"all {len(res)} requests ok, {args.gen} tokens each")


def four_chip_phase(seed: int, **overrides) -> None:
    import jax
    from repro.data.pipeline import make_batch
    from repro.launch import train as T
    from repro.launch.mesh import make_local_mesh

    devs = jax.devices()
    check(len(devs) == 4, f"--four needs 4 devices, found {len(devs)}")
    args = train_args(seed, **{"mesh": "1x4", "steps": 1, **overrides})
    cfg = T.model_config(args)
    run = T.run_config(args)
    out = {}
    for cp, mesh in ((4, make_local_mesh(1, 4)),
                     (1, make_local_mesh(1, 1, devices=devs[:1]))):
        batch = make_batch(T.pipeline_config(args, cfg, run, cp), 0)
        metrics, c_s, r_s, hlo = one_step(cfg, mesh, run, args, batch,
                                          want_hlo=True)
        assert_kernels_compiled(hlo, f"CP {cp} train step")
        n_perm = hlo.count("collective-permute")
        log(f"CP {cp}: loss {metrics['loss']!r} grad_norm "
            f"{metrics['grad_norm']!r}; compile {c_s:.1f}s, step "
            f"{r_s:.2f}s; {n_perm} collective-permute mentions in its HLO")
        if cp == 4:
            check(n_perm > 0, "CP 4 step has no collective-permute: the "
                  "chunked ppermute exchange is missing")
        out[cp] = metrics
        del batch
        gc.collect()
    for key in ("loss", "grad_norm"):
        a, b = out[4][key], out[1][key]
        rel = abs(a - b) / abs(b)
        log(f"{key}: CP 4 {a!r} vs one device {b!r} (rel diff {rel:.2e}, "
            f"tolerance {CP_RTOL})")
        check(math.isfinite(a) and rel <= CP_RTOL,
              f"CP 4 vs one-device {key} differ by {rel:.3e} relative")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four", action="store_true",
                    help="run only the CP 4 path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    import jax
    from repro.kernels.ops import resolve_interpret

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{dev[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    check(not resolve_interpret(None), "Pallas would run in interpret mode")
    cache = {"hits": 0, "requests": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1

    jax.monitoring.register_event_listener(on_event)
    log(f"device {dev[0].device_kind} x{len(dev)}; compile cache "
        f"{cache_dir}")
    t0 = time.perf_counter()
    if opts.four:
        four_chip_phase(opts.seed)
    else:
        train_phase(opts.seed)
        gc.collect()
        serve_phase(opts.seed)
    log(f"done in {time.perf_counter() - t0:.1f}s; compile cache hits "
        f"{cache['hits']} of {cache['requests']} cacheable compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
