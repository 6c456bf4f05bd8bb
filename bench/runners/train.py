"""Runner of a training cell: a closed loop of synchronous train steps.

Set-up (all of it counts as ``setup_s``):

1. the model configuration from the cell's file (every key reaches the
   program's ``ModelConfig``, or is a key that describes the file; any
   other key, or a value the program cannot compute, fails here), the run
   options it pins, the program's ``PipelineConfig`` built from both, the
   mesh, and the program's train step from
   ``repro.launch.steps.build_train_step``, jitted as the program's
   ``launch/train.py`` jits it;
2. the stream's step programs: the feed's planner calls are run over one
   pass of the stream (``stream_steps`` steps of the traffic file) to find
   every distinct set of input shapes the visit-table and Eq. 5 buckets
   produce, and each is compiled once (or read back from the persistent
   compile cache);
3. the weights, made on the device from the seed, and the program's
   optimizer state;
4. the feed thread, and the first three steps, through the same call and
   feed as the window: their losses, the first gradient and the change of
   the weights over the three are kept for the comparison.

The window then sends steps until ``seconds`` have passed, keeping
``AHEAD_STEPS`` of them queued on the chips beyond the one it waits for,
and then waits for every step it sent: all of them count, over the time
to the end of the last.  Sending a step waits for its batch
(``next_batch``), copies it to the chips (``h2d``) and dispatches the
compiled step (``dispatch``); its outputs are then waited for in order
(``sync``), and a step's time runs from the end of the step before it.
With ``trace`` a few more steps are traced after the window closes, so
tracing costs the window nothing.

After the window the peak memory is read, the program's state freed, and
the float32 reference run over the first three steps' batches.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import json
import math
import os
import shutil
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from harness import check, weights
from harness.catalog import Cell, load_module
from harness.device import memory_peak_bytes
from harness.feed import Feed, build_batch, plan_config, signature
from harness.spans import Spans
from harness.trace import (TraceSummary, events_from_xplane, find_xplane,
                           save_events)
from harness.traffic import draw_step

__all__ = ["Session", "Trainer", "run", "reference_readings",
           "model_config", "run_config", "TRACE_STEPS", "CHECK_STEPS"]

CHECK_STEPS = 3
TRACE_STEPS = 4
#: steps queued on the chips beyond the one waited for, so that a stall of
#: the host does not leave the chips idle: about six seconds at the 1.04 s
#: mean step of starcoder2_3b.wlb_llm.8k.  A count and not a time, so that
#: every run queues alike and its window holds the same steps of the stream.
AHEAD_STEPS = 6

#: configuration-file keys -> ModelConfig fields, where the names differ;
#: every other model key goes to the field of its own name
_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads",
           "intermediate_size": "d_ff", "norm_epsilon": "norm_eps",
           "torch_dtype": "dtype", "tie_word_embeddings": "tie_embeddings"}
#: published activations -> the program's MLP that computes them
_ACT = {"gelu_pytorch_tanh": "gelu", "silu": "glu"}
#: keys that describe the file rather than the model
_FILE_KEYS = ("arch", "family", "source", "published", "reduced",
              "departures", "assumed", "deployment", "mesh", "run",
              "optimizer", "optimizer_why")


def model_config(config: dict, context_len: int):
    """The program's ModelConfig for a configuration file run at
    ``context_len`` tokens a sequence: each model key of the file reaches
    the field of its name (or the one ``_FIELDS`` names), and set-up fails,
    naming the key, on a key that is neither a field nor a key of the file
    (a published ``sliding_window``, ``use_bias`` or ``norm_type`` among
    them: the program has no such field), on two keys for one field, on an
    activation the program does not compute, and on sequences longer than
    ``max_position_embeddings`` (RoPE keeps no table, so the key sizes
    nothing else)."""
    from repro.configs import get_config

    base = get_config(config["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    kw, by = {}, {}
    for key, value in config.items():
        if key in _FILE_KEYS:
            continue
        if key == "max_position_embeddings":
            if context_len > value:
                raise ValueError(f"max_position_embeddings {value}: the "
                                 f"traffic packs {context_len} tokens")
            continue
        field = "mlp" if key == "hidden_act" else _FIELDS.get(key, key)
        if field not in fields:
            raise ValueError(f"configuration key {key!r} is neither a field "
                             "of the program's ModelConfig nor a key that "
                             "describes the file")
        if field in by:
            raise ValueError(f"configuration keys {by[field]!r} and {key!r} "
                             f"both set the field {field!r}")
        by[field] = key
        if key == "hidden_act":
            if value not in _ACT:
                raise NotImplementedError(
                    f"hidden_act {value!r}: the program's MLPs compute "
                    f"{sorted(_ACT)}")
            value = _ACT[value]
        kw[field] = value
    return dataclasses.replace(base, **kw)


def run_config(config: dict):
    from repro.configs import RunConfig

    opt = config["optimizer"]
    return RunConfig(arch=config["arch"], **config["run"], lr=opt["lr"],
                     weight_decay=opt["weight_decay"],
                     warmup_steps=opt["warmup_steps"],
                     total_steps=opt["total_steps"],
                     grad_clip=opt["grad_clip"])


class _Counter:
    """Counts backend compiles (``jax.monitoring``)."""

    def __init__(self):
        from jax._src import dispatch

        self.n = 0
        self.cache_hits = 0

        def on_duration(event, *_a, **_k):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                self.n += 1

        def on_event(event, **_k):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class Session:
    """The program's train step for a cell, built once, with its compiled
    step programs (one per distinct set of input shapes)."""

    def __init__(self, cell: Cell, devices, log=print):

        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_local_mesh
        from repro.launch.steps import build_train_step

        self.cell, self.devices, self.log = cell, list(devices), log
        config, traffic = cell.config, cell.traffic
        self.compiles = _Counter()
        self.cfg = model_config(config, traffic.context_len)
        self.run_cfg = run_config(config)
        data, model = config["mesh"]["data"], config["mesh"]["model"]
        if data * model != cell.chips:
            raise ValueError(f"mesh {data}x{model} does not fill the cell's "
                             f"{cell.chips} chips")
        self.mesh = make_local_mesh(data, model, devices=self.devices)
        shape = ShapeConfig("bench", traffic.context_len, traffic.batch,
                            "train")
        bundle = build_train_step(self.cfg, self.mesh, self.run_cfg, shape,
                                  q_chunk=128)
        self.step_fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                               out_shardings=bundle.out_shardings,
                               donate_argnums=bundle.donate_argnums)
        self.p_shard, self.o_shard, self.b_shard, _ = bundle.in_shardings
        (self.params_abs, self.opt_abs, batch_abs,
         self.step_abs) = bundle.abstract_inputs
        self.keys = sorted(batch_abs)
        self.pipeline = plan_config(self.cfg, self.run_cfg, model, traffic)
        self.flops = load_module(cell.bench_dir / "flops"
                                 / f"{config['family']}.py")
        self.programs: dict[tuple, object] = {}
        b1 = config["optimizer"]["b1"]
        self.grad_of_mu = jax.jit(lambda mu: (
            jnp.stack(weights.leaf_norms(mu, 1.0 / (1.0 - b1))),
            weights.row_norms(mu["embed"]["e"], 1.0 / (1.0 - b1))))
        self.change_norms = weights.make_change_norms(self.params_abs)
        self.init = weights.make_init(self.params_abs, self.p_shard)

    def prepare(self, seed: int, steps: int) -> None:
        """Compile every step program the first ``steps`` stream steps of
        ``seed`` need and the session does not have yet."""

        traffic = self.cell.traffic
        t0 = time.perf_counter()
        need = {}
        for s in range(steps):
            hb = build_batch(self.pipeline, draw_step(
                traffic, seed, s, self.pipeline.vocab_size), s)
            arrs = {k: hb.arrays[k] for k in self.keys}
            sig = signature(arrs)
            if sig not in self.programs:
                need.setdefault(sig, {k: jax.ShapeDtypeStruct(v.shape,
                                                              v.dtype)
                                      for k, v in arrs.items()})
        t_plan = time.perf_counter() - t0
        t0 = time.perf_counter()
        n0, h0 = self.compiles.n, self.compiles.cache_hits
        for sig, b_abs in need.items():
            self.programs[sig] = self.step_fn.lower(
                self.params_abs, self.opt_abs, b_abs, self.step_abs
            ).compile()
        self.log(f"set-up: {len(self.programs)} step programs for {steps} "
                 f"stream steps (shapes {t_plan:.1f}s, {len(need)} compiled "
                 f"or read back in {time.perf_counter() - t0:.1f}s; "
                 f"{self.compiles.n - n0} backend compiles, "
                 f"{self.compiles.cache_hits - h0} cache hits)")

    def start(self, seed: int, spans: Spans, transform=None) -> "Trainer":
        return Trainer(self, seed, spans, transform)


class Trainer:
    """Weights and optimizer state from ``seed``, the feed, and the step
    loop that drives the session's compiled programs."""

    def __init__(self, session: Session, seed: int, spans: Spans,
                 transform=None):

        from repro.compat import set_mesh
        from repro.optim import adamw_init

        self.s, self.seed, self.spans = session, seed, spans
        self.words = weights.seed_words(seed)
        with set_mesh(session.mesh):
            params = session.init(self.words)
            opt = jax.jit(adamw_init, out_shardings=session.o_shard)(params)
        self.state = {"params": params, "opt": opt}
        del params, opt
        self.log: list[dict] = []
        self.feed = Feed(session.cell.traffic, session.pipeline, seed, spans,
                         transform=transform)

    def dispatch(self, step: int) -> dict:
        """Send step ``step`` to the chips without waiting for it."""
        s, spans = self.s, self.spans
        with spans.span("next_batch"):
            hb = self.feed.get()
        if hb.step != step:
            raise RuntimeError(f"feed out of order: {hb.step} at {step}")
        ts = time.perf_counter()
        with spans.span("h2d"):
            db = {k: jax.device_put(hb.arrays[k], s.b_shard[k])
                  for k in s.keys}
        program = s.programs.get(signature({k: hb.arrays[k]
                                            for k in s.keys}))
        if program is None:
            raise RuntimeError(f"stream step {hb.step} needs a step program "
                               "the set-up did not build")
        with spans.span("dispatch"):
            p, o, m = program(self.state["params"], self.state["opt"], db,
                              np.int32(step))
        self.state["params"], self.state["opt"] = p, o
        return {"step": step, "ts": ts, "metrics": m, "hb": hb}

    def finish(self, sent: dict, since: float | None = None) -> dict:
        """Wait for a dispatched step's metrics: one program gives them
        with the step's weights, which the next step may already have
        taken over.  Its time ``s`` runs from ``since`` (the end of the
        step before it, when steps are queued), or from the copy of its
        inputs to the chips where that is later."""
        with self.spans.span("sync"):
            jax.block_until_ready(sent["metrics"])
        te = time.perf_counter()
        hb, config = sent["hb"], self.s.cell.config
        rec = {"step": sent["step"],
               "s": te - (sent["ts"] if since is None else max(since,
                                                               sent["ts"])),
               "end": te, "loss": float(sent["metrics"]["loss"]),
               "tokens": hb.tokens,
               "pairs": sum(self.s.flops.attention_pairs(config, x.doc_lens)
                            for x in hb.samples)}
        self.log.append(rec)
        return rec

    def step(self, step: int) -> dict:
        """One synchronous step."""
        return self.finish(self.dispatch(step))

    def run(self, step: int, ahead: int, *, until: float | None = None,
            stop: int | None = None) -> list[dict]:
        """Steps from ``step`` on, ``ahead`` of them queued on the chips
        beyond the one waited for, until the host clock passes ``until``
        or step ``stop`` is reached; then every step sent is waited for.
        Each step's time runs from the end of the one before it (from the
        copy of its inputs, for the first)."""
        queue, done = collections.deque(), []
        last = time.perf_counter()
        while (until is None or time.perf_counter() < until) and \
                (stop is None or step < stop):
            queue.append(self.dispatch(step))
            step += 1
            while len(queue) > ahead:
                done.append(self.finish(queue.popleft(), last))
                last = done[-1]["end"]
        while queue:
            done.append(self.finish(queue.popleft(), last))
            last = done[-1]["end"]
        return done

    def first_steps(self) -> check.Readings:
        """Steps 0..2, with the program's readings for the comparison."""
        losses, grad, rows = [], None, None
        for st in range(CHECK_STEPS):
            losses.append(self.step(st)["loss"])
            if st == 0:
                grad, rows = map(np.asarray, self.s.grad_of_mu(
                    self.state["opt"]["mu"]))
        opt = self.state["opt"]
        master = opt["master"] if "master" in opt else self.state["params"]
        change = np.asarray(self.s.change_norms(master, self.words))
        return check.Readings(losses, grad, change, rows)

    def close(self) -> None:
        self.feed.close()
        self.state.clear()
        gc.collect()


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices, *,
        t_start: float, work_dir: str, log=print, transform=None) -> dict:

    os.makedirs(work_dir, exist_ok=True)
    spans = Spans()
    session = Session(cell, devices, log)
    session.prepare(seed, cell.traffic.stream_steps)
    trainer = session.start(seed, spans, transform)
    try:
        prog = trainer.first_steps()

        # -- the measured window ------------------------------------------ #
        n_compiles = session.compiles.n
        t_ws = time.perf_counter()
        setup_s = t_ws - t_start
        ws_ns = time.perf_counter_ns()
        first = [r["s"] for r in trainer.log]
        ahead = AHEAD_STEPS
        window = trainer.run(CHECK_STEPS, ahead, until=t_ws + seconds)
        t_we = window[-1]["end"]
        we_ns = time.perf_counter_ns()
        step = window[-1]["step"] + 1
        in_window_compiles = session.compiles.n - n_compiles
        log(f"window: {len(window)} steps in {t_we - t_ws:.3f}s, {ahead} "
            f"queued ahead (checked steps took "
            f"{', '.join(f'{x:.3f}' for x in first)}s); compiles inside "
            f"the window: {in_window_compiles}")

        # -- the traced stretch, after the window ------------------------- #
        summary, traced = None, []
        if trace:
            tdir = os.path.join(work_dir, "trace")
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            spans.annotate = True
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                traced = trainer.run(step, ahead, stop=step + TRACE_STEPS)
            finally:
                jax.profiler.stop_trace()
                spans.annotate = False
            events = events_from_xplane(find_xplane(tdir))
            save_events(events, os.path.join(work_dir,
                                             "trace_events.json.gz"))
            shutil.rmtree(tdir, ignore_errors=True)
            summary = TraceSummary(events)
            log(f"trace: {len(events)} events, "
                f"{len(summary.devices)} device planes")
        peak = memory_peak_bytes(devices)
        log_all = list(trainer.log)
    finally:
        trainer.close()

    # -- free the program's state, then the reference -------------------- #
    params_abs, flops, pipeline = (session.params_abs, session.flops,
                                   session.pipeline)
    del session
    gc.collect()
    reference = load_module(cell.bench_dir / "reference"
                            / f"{cell.config['family']}.py")
    ref = reference_readings(cell, seed, params_abs, reference, devices,
                             log=log)
    ok, compared = check.judge(check.compare(prog, ref), cell.limits)
    with open(os.path.join(work_dir, "readings.json"), "w") as f:
        json.dump({"leaves": weights.leaf_names(params_abs),
                   "program": prog.as_dict(), "reference": ref.as_dict()}, f)
    finite = all(math.isfinite(r["loss"]) for r in log_all)
    failed = sum(not math.isfinite(r["loss"]) for r in window)

    rec = types.SimpleNamespace(
        cell=cell, config=cell.config, chips=cell.chips, flops=flops,
        pipeline=pipeline, peaks=None, setup_s=setup_s,
        window_s=t_we - t_ws, window=window,
        spans=spans, window_ns=(ws_ns, we_ns), trace=summary,
        traced=traced, in_window_compiles=in_window_compiles,
        bench_dir=cell.bench_dir)
    return {"correct": bool(ok and finite and in_window_compiles == 0),
            "attempted": len(window), "failed": failed, "record": rec,
            "memory_peak_bytes": peak, "compared": compared,
            "in_window_compiles": in_window_compiles,
            "prog": prog, "ref": ref}


def reference_readings(cell: Cell, seed: int, params_abs, reference,
                       devices, *, mm_dtype=None, log=print, memo=None):
    """The float32 reference's readings over the first three steps'
    batches (packed order, drawn anew from the seed).  ``memo`` (a dict)
    keeps the jitted functions for later calls with other seeds."""

    config, traffic = cell.config, cell.traffic
    opt = config["optimizer"]
    mesh = Mesh(np.asarray(list(devices)), ("model",))
    abs_f32 = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape,
                                                          jnp.float32),
                           params_abs)
    shard = reference.param_shardings(mesh, abs_f32)
    memo = {} if memo is None else memo
    if "init" not in memo:
        memo["init"] = weights.make_init(params_abs, shard, widen=True)
        memo["zeros"] = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                                out_shardings=shard)
        memo["change"] = weights.make_change_norms(params_abs)
    if ("step", mm_dtype) not in memo:
        memo["step", mm_dtype] = reference.make_step(
            config, opt, mm_dtype=mm_dtype, shardings=shard, mesh=mesh)
    step = memo["step", mm_dtype]
    words = weights.seed_words(seed)
    t0 = time.perf_counter()
    params = memo["init"](words)
    mu, nu = memo["zeros"](params), memo["zeros"](params)
    losses, grads, rows = [], None, None
    for s in range(CHECK_STEPS):
        batch = reference.packed_inputs(draw_step(
            traffic, seed, s, config["vocab_size"]))
        params, mu, nu, loss, norms, row_norms = step(
            params, mu, nu, jnp.int32(s),
            jnp.float32(reference.lr_at(s, opt)), batch)
        losses.append(float(loss))
        if s == 0:
            grads, rows = np.asarray(norms), np.asarray(row_norms)
    change = np.asarray(memo["change"](params, words))
    del params, mu, nu
    log(f"reference: {CHECK_STEPS} float32 steps in "
        f"{time.perf_counter() - t0:.1f}s")
    return check.Readings(losses, grads, change, rows)
