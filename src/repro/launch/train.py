"""End-to-end fault-tolerant training driver.

Wires together: data pipeline (FlashCP planning per batch) -> pjit'd
train step (CP attention islands, FSDP params) -> AdamW -> async
checkpointing -> elastic degree-replanning supervision (restart /
shrink-to-survivors, DESIGN.md §Recovery) -> straggler-adaptive planner
targets and capacity-proportional dispatch.

CPU-scale example (quickstart-sized model, real training):

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2_3b \
        --smoke --steps 20 --seq-len 512 --batch 2 --mesh 1x1

Fault-injection example (lose host 3 of a simulated 4-host 2x4 grid at
step 6; the run shrinks the data axis, reshards the checkpoint onto the
survivors and finishes):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train --smoke --mesh 2x4 \
        --hosts 4 --batch 8 --steps 12 --ckpt-every 2 --dispatch \
        --fail-at 6:3

Autotune example (search the run-config knobs with the cost-model
tuner before training; ``--autotune-dry`` prints the pick and exits):

    PYTHONPATH=src python -m repro.launch.train --smoke --mesh 1x2 \
        --batch 2 --autotune --autotune-dry

Production shapes lower through the same path (see launch/dryrun.py for
the no-hardware variant).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.compat import set_mesh
from repro.configs import RunConfig, get_config, reduce_for_smoke
from repro.configs.base import ModelConfig, ShapeConfig
from repro.data.pipeline import (PipelineConfig, Prefetcher,
                                 make_batch, make_dispatch_batch)
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import (make_group_mesh, make_local_mesh,
                               make_production_mesh)
from repro.launch.steps import build_train_step, effective_strategy
from repro.planner import get_planner
from repro.models import init_params
from repro.optim import adamw_init
from repro.runtime import (ElasticSupervisor, FailureInjector, FailurePolicy,
                           HostTopology, StragglerMonitor, StragglerSim,
                           parse_fail_spec, parse_straggle_specs)


def device_put_batch(batch, shardings):
    out = {}
    for k, v in batch.items():
        if k == "stats" or k == "perm":
            continue
        out[k] = jax.device_put(jnp.asarray(v), shardings.get(k))
    return out


def _ft_setup(args, n_dev: int, model_axis: int):
    """Fault-tolerance plumbing shared by both train loops.

    Builds the simulated host topology (``--hosts``, default one host per
    data row), the failure policy (min_hosts = hosts needed to still hold
    the model/CP axis after a shrink), the straggler monitor, and the
    injection hooks (``--fail-at STEP[:HOSTS]``, ``--straggle
    HOST:FACTOR``).  See DESIGN.md §Recovery.
    """
    fail_step, fail_hosts = parse_fail_spec(getattr(args, "fail_at", -1))
    factors = parse_straggle_specs(getattr(args, "straggle", None))
    hosts = getattr(args, "hosts", 0) or max(n_dev // model_axis, 1)
    if n_dev % hosts:
        raise ValueError(f"--hosts {hosts} must divide the device "
                         f"count {n_dev}")
    dph = n_dev // hosts
    for h in list(fail_hosts) + list(factors):
        if not 0 <= h < hosts:
            raise ValueError(f"host {h} out of range for --hosts {hosts}")
    topology = HostTopology(num_hosts=hosts, devices_per_host=dph)
    policy = FailurePolicy(
        min_hosts=max(1, -(-model_axis // dph)),
        max_restarts=getattr(args, "max_restarts", 10))
    monitor = StragglerMonitor()
    injector = FailureInjector(fail_step, fail_hosts)
    sim = StragglerSim(factors)
    return topology, policy, monitor, injector, sim


def _final_save(ckpt: CheckpointManager, step: int, state) -> float:
    """Blocking save of the whole final state; returns its seconds."""
    t0 = time.perf_counter()
    ckpt.save(step, state, blocking=True)
    dt = time.perf_counter() - t0
    print(f"[train] final checkpoint (step {step}) saved to "
          f"{ckpt.directory} in {dt:.2f}s", flush=True)
    return dt


def _effective_accum(batch: int, groups: int, accum: int) -> int:
    """Grad-accumulation factor actually usable at this tiling: micro
    slicing needs ``batch % (groups * accum) == 0``; otherwise run the
    whole batch in one micro-step (global batch is preserved either way —
    accum only relieves per-step residency)."""
    return accum if accum > 1 and batch % (groups * accum) == 0 else 1


def _train_dispatch(args, cfg, run: RunConfig, mesh_axes) -> dict:
    """Adaptive-dispatch training loop (DESIGN.md §Dispatch, §Recovery).

    Per step, the dispatcher sizes the CP subgroups from the batch's
    document-length profile; the device grid is re-tiled with
    :func:`make_group_mesh` and one jitted step per (tiling, degree,
    accum) is built lazily.  A degree switch re-shards params/optimizer
    onto the new tiling (a rare, amortized device_put: degrees are sticky
    while the data mix is).  The per-step loss is token-weighted across
    groups by construction — the global masked CE mean divides by the
    step's global valid-token count.

    Supervision wraps the loop: an injected (or, on a cluster, detected)
    :class:`TrainingFailure` naming lost hosts triggers an elastic
    shrink — the supervisor re-derives the surviving grid, the dispatch
    config's data axis shrinks with it, state restores from the latest
    checkpoint *resharded* onto the first resumed batch's degree, and the
    deterministic (seed, step) stream replays to the failure point.
    ``plan.accum_factor`` micro-batches each step when the shrunk grid
    must preserve the global batch.  Straggler wall-times feed per-host
    speed EMAs; the dispatcher LPT-balances *completion time* with them
    (capacity-proportional placement) and jitter tightens its imbalance
    target.
    """
    from repro.dispatch import DispatchConfig

    D, M = mesh_axes
    topology, policy, monitor, injector, sim = _ft_setup(args, D * M, M)
    supervisor = ElasticSupervisor(topology, policy, data=D, model=M,
                                   monitor=monitor)
    align = 128 if run.attention_impl == "pallas" \
        else (1 if D * M == 1 else 16)
    strategy = effective_strategy(cfg, run.cp_strategy)
    pipe_cfg = PipelineConfig(
        dataset=args.dataset, context_len=args.seq_len,
        batch_per_host=args.batch, cp_size=M, strategy=strategy,
        vocab_size=cfg.vocab_size, seed=run.seed, align=align,
        emit_tables=(run.attention_impl == "pallas" and cfg.uses_attention),
        table_overlap=run.cp_overlap, table_grid=run.kernel_grid)
    shape = ShapeConfig("dispatch", args.seq_len, args.batch, "train")

    # mutable current-topology state; on_restore rewrites it on a shrink
    cur = {
        "data": D, "devices": None, "accum": 1, "key": None,
        "dcfg": DispatchConfig(
            data=D, model=M, seqs=args.batch,
            target_imbalance=run.dispatch_target_imbalance,
            min_cp=run.dispatch_min_cp, quantum=align),
    }
    bundles: dict[tuple, tuple] = {}

    def bundle_key(g: int) -> tuple:
        groups = cur["data"] * M // g
        return (cur["data"], g,
                _effective_accum(args.batch, groups, cur["accum"]))

    def degree(g: int):
        key = bundle_key(g)
        if key not in bundles:
            mesh_g = make_group_mesh(cur["data"], M, g,
                                     devices=cur["devices"])
            bundle = build_train_step(cfg, mesh_g, run, shape,
                                      q_chunk=args.q_chunk, accum=key[2])
            step_fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                              out_shardings=bundle.out_shardings,
                              donate_argnums=bundle.donate_argnums)
            bundles[key] = (mesh_g, bundle, step_fn)
        return key, bundles[key]

    ckpt = CheckpointManager(run.checkpoint_dir, keep=2)
    start = 0
    if args.resume and ckpt.latest_step() is not None:
        start = ckpt.latest_step()

    def make_stream(step):
        """(prefetcher-or-None, first batch) starting at ``step``."""
        if args.prefetch:
            pf = Prefetcher(pipe_cfg, start_step=step, dispatch=cur["dcfg"],
                            speeds_fn=supervisor.device_speeds)
            return pf, next(pf)
        return None, make_dispatch_batch(pipe_cfg, cur["dcfg"], step,
                                         device_speeds=
                                         supervisor.device_speeds())

    it, first = make_stream(start)
    pending = {"batch": first}
    g0 = first["stats"]["dispatch"]["cp_degree"]
    key0, (mesh0, bundle0, _) = degree(g0)
    p_shard, o_shard = bundle0.in_shardings[:2]
    with set_mesh(mesh0):
        if start:
            # the pipeline is a pure function of (seed, step), so the
            # resumed stream replays exactly; state reshards onto the
            # first resumed batch's degree
            start, state, _ = ckpt.restore(
                shardings={"params": p_shard, "opt": o_shard})
            print(f"[train] resumed from step {start}")
        else:
            params = jax.device_put(
                init_params(jax.random.PRNGKey(run.seed), cfg), p_shard)
            opt = jax.device_put(adamw_init(params), o_shard)
            state = {"params": params, "opt": opt}
    cur["key"] = key0
    losses = []
    switches = [0]

    def one_step(step: int) -> None:
        nonlocal state
        t0 = time.time()
        injector.maybe_fail(step)
        if pending["batch"] is not None:
            batch, pending["batch"] = pending["batch"], None
        elif it is not None:
            batch = next(it)
        else:
            batch = make_dispatch_batch(pipe_cfg, cur["dcfg"], step,
                                        device_speeds=
                                        supervisor.device_speeds())
        ds = batch["stats"]["dispatch"]
        g = ds["cp_degree"]
        key, (mesh_g, bundle_g, step_fn) = degree(g)
        if key != cur["key"]:
            p_s, o_s = bundle_g.in_shardings[:2]
            state = {"params": jax.device_put(state["params"], p_s),
                     "opt": jax.device_put(state["opt"], o_s)}
            cur["key"] = key
            switches[0] += 1
        b_shard = bundle_g.in_shardings[2]
        with set_mesh(mesh_g):
            db = device_put_batch(batch, b_shard)
            db = {k: v for k, v in db.items()
                  if k in bundle_g.abstract_inputs[2]}
            p, o, metrics = step_fn(state["params"], state["opt"], db,
                                    jnp.asarray(step, jnp.int32))
        state = {"params": p, "opt": o}
        loss = float(metrics["loss"])
        losses.append(loss)
        # feed measured (straggler-inflated, if simulated) wall times into
        # the per-host speed EMAs; under jitter, tighten the dispatcher's
        # imbalance target (live only on the non-prefetch path — the
        # prefetch thread samples speeds but holds its config)
        sim.observe(monitor, time.time() - t0,
                    supervisor.surviving_hosts())
        if it is None:
            tgt = round(monitor.adjusted_target(), 2)
            if abs(tgt - cur["dcfg"].target_imbalance) > 1e-9:
                cur["dcfg"] = dataclasses.replace(
                    cur["dcfg"], target_imbalance=tgt)
        if step % args.log_every == 0:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"ce {float(metrics['ce']):.4f} "
                  f"cp {g} groups {ds['n_groups']} "
                  f"tok_imb {ds['token_imbalance']:.3f} "
                  f"work_imb {ds['work_imbalance']:.3f} "
                  f"tokens {int(metrics['tokens'])} "
                  f"{time.time()-t0:.2f}s", flush=True)
        if args.ckpt_every and step and step % args.ckpt_every == 0:
            ckpt.save(step + 1, state, blocking=False)

    def on_restore(action, plan):
        nonlocal state, it
        try:
            ckpt.wait()         # settle any in-flight async save
        except RuntimeError as err:
            print(f"[train] pending checkpoint save failed: {err}")
        if it is not None:
            it.close()
        if plan is not None:    # elastic shrink: retile over survivors
            cur["data"] = plan.data_axis
            cur["devices"] = [jax.devices()[i] for i in plan.devices]
            cur["accum"] = plan.accum_factor
            cur["dcfg"] = dataclasses.replace(cur["dcfg"],
                                              data=plan.data_axis)
            bundles.clear()
        resume = ckpt.latest_step() or 0
        it, first = make_stream(resume)
        pending["batch"] = first
        g = first["stats"]["dispatch"]["cp_degree"]
        key, (mesh_g, bundle_g, _) = degree(g)
        p_s, o_s = bundle_g.in_shardings[:2]
        with set_mesh(mesh_g):
            if ckpt.latest_step() is not None:
                _, st, _ = ckpt.restore(
                    shardings={"params": p_s, "opt": o_s})
                state = st
            else:
                params = jax.device_put(
                    init_params(jax.random.PRNGKey(run.seed), cfg), p_s)
                state = {"params": params,
                         "opt": jax.device_put(adamw_init(params), o_s)}
        cur["key"] = key
        print(f"[train] restored step {resume} after {action.value} "
              f"(mesh {cur['data']}x{M}, accum {key[2]})", flush=True)
        return resume

    final = supervisor.run(one_step, start_step=start,
                           total_steps=args.steps, on_restore=on_restore)
    save_s = _final_save(ckpt, final, state)
    if it:
        it.close()
    print(f"[train] dispatch: {switches[0]} degree switches over "
          f"{args.steps} steps; tilings used: {sorted(bundles)}")
    return {"final_step": final, "losses": losses, "ckpt_save_s": save_s,
            "recoveries": policy.restarts,
            "dead_hosts": sorted(supervisor.dead),
            "mesh": (cur["data"], M), "accum": cur["accum"],
            "degree_switches": switches[0]}


def model_config(args) -> ModelConfig:
    """``--arch`` resolved, reduced by ``--smoke``, cut by ``--layers``."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    layers = getattr(args, "layers", 0)
    if layers:
        full = cfg.num_layers
        cfg = dataclasses.replace(cfg, num_layers=layers)
        print(f"[train] depth cut: {cfg.name} at {layers} of {full} layers, "
              f"widths unchanged ({cfg.param_count() / 1e9:.3f}B params)",
              flush=True)
    return cfg


def run_config(args) -> RunConfig:
    """The :class:`RunConfig` the driver trains under for ``args``."""
    steps = args.steps
    return RunConfig(arch=args.arch, cp_strategy=args.strategy,
                     attention_impl=args.attention_impl, lr=args.lr,
                     total_steps=steps, warmup_steps=max(steps // 10, 1),
                     grad_compression=args.grad_compression,
                     checkpoint_dir=args.checkpoint_dir,
                     remat=not args.no_remat,
                     dispatch="adaptive" if getattr(args, "dispatch", False)
                     else "off",
                     dispatch_target_imbalance=getattr(args, "dispatch_target",
                                                       1.1),
                     dispatch_min_cp=getattr(args, "dispatch_min_cp", 1),
                     seed=getattr(args, "seed", 0))


def pipeline_config(args, cfg: ModelConfig, run: RunConfig,
                    cp: int) -> PipelineConfig:
    """The per-rank batch stream of the non-dispatch loop."""
    return PipelineConfig(
        dataset=args.dataset, context_len=args.seq_len,
        batch_per_host=args.batch, cp_size=cp,
        strategy=effective_strategy(cfg, run.cp_strategy),
        vocab_size=cfg.vocab_size, seed=run.seed,
        # pallas tables need block-divisible rank slices
        align=128 if run.attention_impl == "pallas"
        else (1 if cp == 1 else 16),
        emit_tables=(run.attention_impl == "pallas" and cfg.uses_attention),
        table_overlap=run.cp_overlap, table_grid=run.kernel_grid)


def train(args) -> dict:
    cfg = model_config(args)
    if args.mesh == "prod":
        mesh = make_production_mesh()
    else:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_local_mesh(d, m)
    cp = mesh.shape["model"]
    d_axis = mesh.shape["data"]

    run = run_config(args)
    dispatch = run.dispatch == "adaptive"
    shape = ShapeConfig("custom", args.seq_len, args.batch, "train")
    # resolve through the planner registry: unknown --strategy fails fast
    # with the list of registered planners.
    get_planner(run.cp_strategy)
    if getattr(args, "autotune", False):
        from repro.autotune import autotune_run
        run, tuned = autotune_run(
            run, cfg, data=d_axis, model=cp, context_len=args.seq_len,
            seqs=args.batch, dataset=args.dataset,
            cache_dir=getattr(args, "autotune_cache", ""),
            top_k=getattr(args, "autotune_topk", 8))
        dispatch = run.dispatch == "adaptive"
        print(f"[autotune] {'cache hit' if tuned.cached else 'searched'} "
              f"{tuned.n_candidates} candidates (top-{tuned.top_k} "
              f"measured): {run.cp_strategy}/{run.cp_overlap}/"
              f"{run.kernel_grid}/{run.dispatch}/{run.kv_comm_dtype} "
              f"frontier_rho={tuned.spearman_frontier:.2f}", flush=True)
        if getattr(args, "autotune_dry", False):
            return {"final_step": 0, "losses": [],
                    "autotune": {"best": tuned.best.as_dict(),
                                 "key": tuned.key, "cached": tuned.cached,
                                 "n_candidates": tuned.n_candidates},
                    "run_config": tuned.run_config}
    if dispatch:
        return _train_dispatch(args, cfg, run, (d_axis, cp))

    topology, policy, monitor, injector, sim = _ft_setup(args, d_axis * cp,
                                                         cp)
    supervisor = ElasticSupervisor(topology, policy, data=d_axis, model=cp,
                                   monitor=monitor)

    pipe_cfg = pipeline_config(args, cfg, run, cp)

    def build(mesh_, accum: int):
        bundle = build_train_step(cfg, mesh_, run, shape,
                                  q_chunk=args.q_chunk, accum=accum)
        step_fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                          out_shardings=bundle.out_shardings,
                          donate_argnums=bundle.donate_argnums)
        return bundle, step_fn

    bundle, step_fn = build(mesh, 1)
    cur = {"mesh": mesh, "bundle": bundle, "step_fn": step_fn,
           "accum": 1, "pipe": pipe_cfg}
    p_shard, o_shard = bundle.in_shardings[:2]

    ckpt = CheckpointManager(run.checkpoint_dir, keep=2)
    start = 0
    with set_mesh(mesh):
        if args.resume and ckpt.latest_step() is not None:
            start, state, _ = ckpt.restore(
                shardings={"params": p_shard, "opt": o_shard})
            print(f"[train] resumed from step {start}")
        else:
            params = jax.device_put(
                init_params(jax.random.PRNGKey(run.seed), cfg), p_shard)
            state = {"params": params,
                     "opt": jax.device_put(adamw_init(params), o_shard)}
    losses, grad_norms = [], []
    it = Prefetcher(pipe_cfg, start_step=start) if args.prefetch else None

    def one_step(step: int) -> None:
        nonlocal state
        t0 = time.time()
        injector.maybe_fail(step)
        batch = next(it) if it else make_batch(cur["pipe"], step)
        bundle_c = cur["bundle"]
        with set_mesh(cur["mesh"]):
            db = device_put_batch(batch, bundle_c.in_shardings[2])
            # tolerate missing optional keys for this strategy
            db = {k: v for k, v in db.items() if k in
                  bundle_c.abstract_inputs[2]}
            p, o, metrics = cur["step_fn"](state["params"], state["opt"],
                                           db, jnp.asarray(step, jnp.int32))
        state = {"params": p, "opt": o}
        loss = float(metrics["loss"])
        losses.append(loss)
        grad_norms.append(float(metrics["grad_norm"]))
        # straggler loop: per-host wall times (inflated when simulated)
        # feed the speed EMAs; jitter tightens the planner's target
        # imbalance for subsequent batches (live on the non-prefetch path)
        sim.observe(monitor, time.time() - t0,
                    supervisor.surviving_hosts())
        if it is None:
            tgt = round(monitor.adjusted_target(), 2)
            if abs(tgt - cur["pipe"].target_imbalance) > 1e-9:
                cur["pipe"] = dataclasses.replace(
                    cur["pipe"], target_imbalance=tgt)
        if step % args.log_every == 0:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"ce {float(metrics['ce']):.4f} "
                  f"gnorm {grad_norms[-1]:.2f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"imb {batch['stats']['imbalance']:.3f} "
                  f"comm_tok {batch['stats']['comm_tokens']} "
                  f"{time.time()-t0:.2f}s", flush=True)
        if args.ckpt_every and step and step % args.ckpt_every == 0:
            ckpt.save(step + 1, state, blocking=False)

    def on_restore(action, plan):
        nonlocal state, it
        try:
            ckpt.wait()
        except RuntimeError as err:
            print(f"[train] pending checkpoint save failed: {err}")
        if it is not None:
            it.close()
            it = None
        if plan is not None:    # elastic shrink onto the survivors
            devs = [jax.devices()[i] for i in plan.devices]
            mesh_new = make_local_mesh(plan.data_axis, cp, devices=devs)
            accum = _effective_accum(args.batch, plan.data_axis,
                                     plan.accum_factor)
            bundle_new, fn_new = build(mesh_new, accum)
            cur.update(mesh=mesh_new, bundle=bundle_new, step_fn=fn_new,
                       accum=accum)
        p_s, o_s = cur["bundle"].in_shardings[:2]
        with set_mesh(cur["mesh"]):
            latest = ckpt.latest_step()
            if latest is None:
                params = jax.device_put(
                    init_params(jax.random.PRNGKey(run.seed), cfg), p_s)
                state = {"params": params,
                         "opt": jax.device_put(adamw_init(params), o_s)}
                resume = 0
            else:
                resume, st, _ = ckpt.restore(
                    shardings={"params": p_s, "opt": o_s})
                state = st
        if args.prefetch:
            # the replayed stream is a pure function of (seed, step):
            # rebuild the prefetcher at the resume step (the old thread's
            # queue had run ahead of the failure)
            it = Prefetcher(cur["pipe"], start_step=resume)
        print(f"[train] restored step {resume} after {action.value} "
              f"(mesh {cur['mesh'].shape['data']}x{cp}, "
              f"accum {cur['accum']})", flush=True)
        return resume

    final = supervisor.run(one_step, start_step=start,
                           total_steps=args.steps, on_restore=on_restore)
    with set_mesh(cur["mesh"]):
        save_s = _final_save(ckpt, final, state)
    if it:
        it.close()
    return {"final_step": final, "losses": losses, "grad_norms": grad_norms,
            "ckpt_save_s": save_s, "recoveries": policy.restarts,
            "dead_hosts": sorted(supervisor.dead),
            "mesh": (cur["mesh"].shape["data"], cp),
            "accum": cur["accum"]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2_3b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-trainable)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model to this many layers, widths "
                         "unchanged (0 = the config's full depth)")
    ap.add_argument("--mesh", default="1x1", help="DxM or 'prod'")
    ap.add_argument("--strategy", default="flashcp")
    ap.add_argument("--attention-impl", default="xla")
    ap.add_argument("--dataset", default="wlb_llm")
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights and the data stream")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--q-chunk", type=int, default=128)
    ap.add_argument("--grad-compression", default="none")
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--prefetch", action="store_true")
    ap.add_argument("--dispatch", action="store_true",
                    help="adaptive DP×CP token dispatch (per-batch CP "
                         "group sizing + cross-rank balancing)")
    ap.add_argument("--dispatch-target", type=float, default=1.1,
                    help="max cross-group token/workload imbalance before "
                         "the dispatcher escalates the CP degree")
    ap.add_argument("--dispatch-min-cp", type=int, default=1)
    ap.add_argument("--autotune", action="store_true",
                    help="search run-config knobs (strategy/overlap/grid/"
                         "dispatch/kv dtype) with the cost-model autotuner "
                         "before training (DESIGN.md §Autotune)")
    ap.add_argument("--autotune-cache", default="",
                    help="directory for the content-addressed tune result "
                         "cache ('' = no persistence)")
    ap.add_argument("--autotune-topk", type=int, default=8,
                    help="measured-trial frontier size")
    ap.add_argument("--autotune-dry", action="store_true",
                    help="tune and print the selected config, skip training")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--fail-at", default="", metavar="STEP[:HOSTS]",
                    help="inject a failure at STEP; ':h1,h2' marks those "
                         "hosts lost (elastic-shrink path) instead of a "
                         "transient fault (restart path)")
    ap.add_argument("--straggle", action="append", default=None,
                    metavar="HOST:FACTOR",
                    help="simulate HOST running FACTOR× slower "
                         "(repeatable; feeds the straggler monitor)")
    ap.add_argument("--hosts", type=int, default=0,
                    help="simulated host count for fault injection "
                         "(0 = one host per data row)")
    ap.add_argument("--max-restarts", type=int, default=10)
    args = ap.parse_args()
    setup_compile_cache()
    out = train(args)
    if out["losses"]:
        print(f"[train] done at step {out['final_step']}; "
              f"loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f}")
    else:
        print(f"[train] done at step {out['final_step']} "
              f"(no training steps ran)")


if __name__ == "__main__":
    main()
