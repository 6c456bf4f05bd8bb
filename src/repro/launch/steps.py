"""Step builders: assemble jit-able train / prefill / decode steps with
shardings and dry-run input specs for any (arch x shape x mesh x strategy).

Used by the training driver, the serving driver, and the multi-pod dry-run
(which lowers these steps against ShapeDtypeStructs).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.core.cp_attention import make_cp_context
from repro.kernels.ops import resolve_interpret
from repro.planner import get_planner, pick_buffer_bucket
from repro.models import decode_step as model_decode_step
from repro.models import forward, init_cache, init_params, loss_fn
from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         compress_tree, warmup_cosine)
from repro.runtime.sharding import (batch_axes_of, batch_specs, cache_specs,
                                    param_shardings)

__all__ = ["effective_strategy", "train_input_specs", "decode_input_specs",
           "build_train_step", "build_prefill_step", "build_decode_step",
           "StepBundle"]


def effective_strategy(cfg: ModelConfig, requested: str) -> str:
    """Recurrent-state architectures need token order preserved across CP
    ranks; planners declare that capability in their registry metadata
    (``PlannerInfo.preserves_token_order``) — anything else is swapped for
    contiguous sharding (sharding-aware comm still applies).
    See DESIGN.md §Arch-applicability."""
    if cfg.family in ("hybrid", "ssm"):
        # unknown names raise here (listing registered planners) instead
        # of being silently replaced by contiguous.
        if get_planner(requested).info.preserves_token_order:
            return requested
        return "contiguous"
    return requested


def exec_strategy_of(plan_strategy: str) -> str:
    """Execution-strategy name for the device-side CP context, resolved
    from the planner registry (unknown names pass through for custom
    execution styles)."""
    try:
        return get_planner(plan_strategy).info.exec_style
    except KeyError:
        return plan_strategy


def default_buf_len(seq_len: int, cp: int) -> int:
    """Static Eq.5 bucket for fixed-shape lowering: half the local KV
    (representative of measured FlashCP savings; the pipeline may emit any
    bucket <= full local KV at runtime)."""
    return pick_buffer_bucket(max(seq_len // (2 * cp), 1), seq_len // cp)


# --------------------------------------------------------------------- #
# input specs (dry-run stand-ins; the pipeline produces matching arrays)
# --------------------------------------------------------------------- #
def train_input_specs(cfg: ModelConfig, shape: ShapeConfig, cp: int,
                      *, strategy: str = "flashcp",
                      buf_len: int | None = None,
                      attention_impl: str = "xla",
                      overlap: str = "chunked",
                      grid: str = "flat",
                      block_q: int = 128,
                      block_k: int = 128,
                      dispatch: bool = False) -> dict[str, Any]:
    B, C = shape.global_batch, shape.seq_len
    N = cp
    buf = buf_len or default_buf_len(C, N)
    f32, i32 = jnp.float32, jnp.int32
    bf16 = jnp.dtype(cfg.dtype)
    s = {
        "tokens": jax.ShapeDtypeStruct((B, C), i32),
        "labels": jax.ShapeDtypeStruct((B, C), i32),
        "doc": jax.ShapeDtypeStruct((B, C), i32),
        "pos": jax.ShapeDtypeStruct((B, C), i32),
    }
    if dispatch:
        # ragged dispatch batches: per-row valid tokens + CP subgroup id
        s["seq_tokens"] = jax.ShapeDtypeStruct((B,), i32)
        s["group_id"] = jax.ShapeDtypeStruct((B,), i32)
    if exec_strategy_of(strategy) in ("flashcp", "contiguous"):
        s["send_idx"] = jax.ShapeDtypeStruct((B, N, buf), i32)
        s["gath_doc"] = jax.ShapeDtypeStruct((B, N * buf), i32)
        s["gath_pos"] = jax.ShapeDtypeStruct((B, N * buf), i32)
    if attention_impl == "pallas" and cfg.uses_attention:
        from repro.core.cp_attention import resolve_overlap
        from repro.planner import visit_table_shapes
        exec_strat = exec_strategy_of(strategy)
        shapes = visit_table_shapes(
            B, N, C // N, buf, strategy=exec_strat,
            overlap=resolve_overlap(exec_strat, attention_impl, overlap),
            block_q=block_q, block_k=block_k, grid=grid)
        s.update({k: jax.ShapeDtypeStruct(v, i32)
                  for k, v in shapes.items()})
    if cfg.frontend == "audio_frames":
        s["frame_embeds"] = jax.ShapeDtypeStruct((B, C, cfg.d_model), bf16)
        del s["tokens"]
    if cfg.frontend == "vit_patches":
        s["patch_embeds"] = jax.ShapeDtypeStruct((B, C, cfg.d_model), bf16)
        s["patch_mask"] = jax.ShapeDtypeStruct((B, C), jnp.bool_)
    return s


def decode_input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, Any]:
    B = shape.global_batch
    bf16 = jnp.dtype(cfg.dtype)
    batch = {"pos_t": jax.ShapeDtypeStruct((B,), jnp.int32)}
    if cfg.frontend == "audio_frames":
        batch["frame_embeds"] = jax.ShapeDtypeStruct((B, cfg.d_model), bf16)
    else:
        batch["tokens"] = jax.ShapeDtypeStruct((B,), jnp.int32)
    cache = jax.eval_shape(
        functools.partial(init_cache, cfg, B, shape.seq_len))
    return {"batch": batch, "cache": cache}


# --------------------------------------------------------------------- #
@dataclasses.dataclass
class StepBundle:
    """A jit-ready step with its shardings (AOT-lowerable)."""
    fn: Any
    in_shardings: Any
    out_shardings: Any
    abstract_inputs: tuple
    donate_argnums: tuple = ()

    def lower(self):
        jitted = jax.jit(self.fn, in_shardings=self.in_shardings,
                         out_shardings=self.out_shardings,
                         donate_argnums=self.donate_argnums)
        return jitted.lower(*self.abstract_inputs)


def _plan_keys(batch):
    return {k: batch[k] for k in batch
            if k in ("doc", "pos", "send_idx", "gath_doc", "gath_pos")
            or k.startswith("tab_")}


def _abstract_state(cfg: ModelConfig, rng=None):
    """Abstract (no-allocation) params + optimizer state."""
    rng = jax.random.PRNGKey(0) if rng is None else rng
    params = jax.eval_shape(functools.partial(init_params, rng=rng, cfg=cfg))
    opt = jax.eval_shape(adamw_init, params)
    return params, opt


# --------------------------------------------------------------------- #
def build_train_step(cfg: ModelConfig, mesh, run: RunConfig,
                     shape: ShapeConfig, *, abstract: bool = True,
                     q_chunk: int = 512, block_q: int = 128,
                     block_k: int = 128,
                     interpret: bool | None = None,
                     accum: int = 1) -> StepBundle:
    """One fused (loss + grad + optimizer) training step.

    ``accum > 1`` splits the batch into that many micro-batches and
    accumulates gradients before the single optimizer update — the
    elastic recovery path (DESIGN.md §Recovery) uses it to preserve the
    global batch after a mesh shrink (ElasticPlan.accum_factor).  Rows
    are group-major on the batch axis; micro ``m`` takes rows
    ``[g*spg + m*spg/accum, g*spg + (m+1)*spg/accum)`` of every group
    ``g`` (a sharding-preserving reshape — each device slices its own
    rows locally).  Gradients are token-weighted across micros, so the
    accumulated update equals the fused one on the same batch: the
    global masked CE mean is ``Σ ce_sum / Σ tokens`` either way.

    ``interpret=None`` runs Pallas kernels in interpret mode only off-TPU.
    """
    interpret = resolve_interpret(interpret)
    plan_strategy = effective_strategy(cfg, run.cp_strategy)
    exec_strategy = exec_strategy_of(plan_strategy)
    baxes = batch_axes_of(mesh)
    cp = mesh.shape["model"]
    if accum > 1:
        G = mesh.shape["data"]
        B_total = shape.global_batch
        assert B_total % (G * accum) == 0, \
            (f"accum {accum} needs per-group rows divisible: "
             f"batch {B_total}, groups {G}")

    def _micro(batch, m):
        """Micro-batch m: a sharding-preserving strided row slice."""
        def sl(v):
            B = v.shape[0]
            x = v.reshape((G, accum, B // (G * accum)) + v.shape[1:])
            return x[:, m].reshape((B // accum,) + v.shape[1:])
        return {k: sl(v) for k, v in batch.items()}

    def _ctx_of(batch):
        return make_cp_context(
            mesh, _plan_keys(batch), strategy=exec_strategy,
            impl=run.attention_impl, batch_axes=baxes,
            head_dim=cfg.resolved_head_dim, q_chunk=q_chunk,
            overlap=run.cp_overlap, interpret=interpret,
            block_q=block_q, block_k=block_k, grid=run.kernel_grid,
            kv_comm_dtype=run.kv_comm_dtype)

    def _loss_and_grads(params, batch):
        # loss_fn's CE is a *global* masked mean: sum(ce * mask) /
        # sum(mask) over the whole (possibly ragged) batch, so dispatch
        # groups of unequal token counts are token-weighted — a group
        # holding 30% of the step's valid tokens contributes 30% of the
        # loss and of the gradient, never 1/n_groups.
        ctx = _ctx_of(batch)
        return jax.value_and_grad(
            lambda p: loss_fn(p, cfg, ctx, batch, remat=run.remat),
            has_aux=True)(params)

    def train_step(params, opt_state, batch, step):
        if accum == 1:
            (loss, metrics), grads = _loss_and_grads(params, batch)
        else:
            g_sum, metr_sum = None, None
            loss_sum = tok_sum = 0.0
            for m in range(accum):
                mb = _micro(batch, m)
                (l_m, metr_m), g_m = _loss_and_grads(params, mb)
                tok = jnp.sum(mb["labels"] >= 0).astype(jnp.float32)
                add = lambda a, b: a + b    # noqa: E731
                g_m = jax.tree.map(lambda g: g * tok, g_m)
                g_sum = g_m if g_sum is None else \
                    jax.tree.map(add, g_sum, g_m)
                metr_m = jax.tree.map(lambda v: v * tok, metr_m)
                metr_sum = metr_m if metr_sum is None else \
                    jax.tree.map(add, metr_sum, metr_m)
                loss_sum = loss_sum + l_m * tok
                tok_sum = tok_sum + tok
            denom = jnp.maximum(tok_sum, 1.0)
            grads = jax.tree.map(lambda g: g / denom, g_sum)
            metrics = jax.tree.map(lambda v: v / denom, metr_sum)
            loss = loss_sum / denom
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, run.grad_clip)
            if run.grad_compression != "none":
                grads, _ = compress_tree(grads, jax.tree.map(
                    lambda g: jnp.zeros_like(g, jnp.float32), grads),
                    run.grad_compression)
            lr = warmup_cosine(step, base_lr=run.lr,
                               warmup_steps=run.warmup_steps,
                               total_steps=run.total_steps)
            params, opt_state = adamw_update(params, grads, opt_state,
                                             lr=lr,
                                             weight_decay=run.weight_decay)
        out_metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr,
                       "tokens": jnp.sum(batch["labels"] >= 0),
                       **metrics}
        return params, opt_state, out_metrics

    params_s, opt_s = _abstract_state(cfg)
    batch_s = train_input_specs(cfg, shape, cp, strategy=plan_strategy,
                                attention_impl=run.attention_impl,
                                overlap=run.cp_overlap,
                                grid=run.kernel_grid,
                                block_q=block_q, block_k=block_k,
                                dispatch=(run.dispatch != "off"))
    p_shard = param_shardings(mesh, params_s)
    o_shard = param_shardings(mesh, opt_s)
    b_spec = batch_specs(mesh, {k: v.shape for k, v in batch_s.items()})
    b_shard = {k: NamedSharding(mesh, s) for k, s in b_spec.items()}
    scalar = NamedSharding(mesh, P())

    return StepBundle(
        fn=train_step,
        in_shardings=(p_shard, o_shard, b_shard, scalar),
        out_shardings=(p_shard, o_shard, None),
        abstract_inputs=(params_s, opt_s, batch_s,
                         jax.ShapeDtypeStruct((), jnp.int32)),
        donate_argnums=(0, 1),
    )


def build_prefill_step(cfg: ModelConfig, mesh, run: RunConfig,
                       shape: ShapeConfig, *, q_chunk: int = 512,
                       block_q: int = 128, block_k: int = 128,
                       interpret: bool | None = None) -> StepBundle:
    interpret = resolve_interpret(interpret)
    plan_strategy = effective_strategy(cfg, run.cp_strategy)
    exec_strategy = exec_strategy_of(plan_strategy)
    baxes = batch_axes_of(mesh)
    cp = mesh.shape["model"]

    def prefill_step(params, batch):
        ctx = make_cp_context(
            mesh, _plan_keys(batch), strategy=exec_strategy,
            impl=run.attention_impl, batch_axes=baxes,
            head_dim=cfg.resolved_head_dim, q_chunk=q_chunk,
            overlap=run.cp_overlap, interpret=interpret,
            block_q=block_q, block_k=block_k, grid=run.kernel_grid,
            kv_comm_dtype=run.kv_comm_dtype)
        logits, _ = forward(params, cfg, ctx, batch, remat=run.remat)
        # serving prefill returns the last-position logits per sequence
        return logits[:, -1, :]

    params_s, _ = _abstract_state(cfg)
    batch_s = train_input_specs(cfg, shape, cp, strategy=plan_strategy,
                                attention_impl=run.attention_impl,
                                overlap=run.cp_overlap,
                                grid=run.kernel_grid,
                                block_q=block_q, block_k=block_k)
    batch_s.pop("labels")
    p_shard = param_shardings(mesh, params_s)
    b_spec = batch_specs(mesh, {k: v.shape for k, v in batch_s.items()})
    b_shard = {k: NamedSharding(mesh, s) for k, s in b_spec.items()}

    return StepBundle(
        fn=prefill_step,
        in_shardings=(p_shard, b_shard),
        out_shardings=None,
        abstract_inputs=(params_s, batch_s),
    )


def build_decode_step(cfg: ModelConfig, mesh, run: RunConfig,
                      shape: ShapeConfig) -> StepBundle:
    baxes = batch_axes_of(mesh)

    # the fused flash kernel has no GSPMD partitioning rule: with the
    # cache S axis sharded over ``model`` the pjit path needs the
    # shard_map LSE-merge island (tests/multidevice/decode_cp_check.py),
    # so meshes that actually shard the cache keep the dense oracle here;
    # in-process shard emulation lives in ServeEngine(attn_shards=)
    impl = run.decode_impl if mesh.shape.get("model", 1) == 1 else "dense"

    def decode(params, cache, batch):
        logits, new_cache = model_decode_step(params, cfg, cache,
                                              batch, batch["pos_t"],
                                              attn_impl=impl)
        return logits, new_cache

    params_s, _ = _abstract_state(cfg)
    specs = decode_input_specs(cfg, shape)
    p_shard = param_shardings(mesh, params_s)
    c_shard = cache_specs(mesh, specs["cache"])
    b = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
    B = specs["batch"]["pos_t"].shape[0]
    need = int(np.prod([mesh.shape[a] for a in
                        (b if isinstance(b, tuple) else (b,))])) if b else 1
    Bk = b if (b and B % need == 0) else None
    b_shard = {k: NamedSharding(mesh, P(*([Bk] + [None] * (v.ndim - 1))))
               for k, v in specs["batch"].items()}

    return StepBundle(
        fn=decode,
        in_shardings=(p_shard, c_shard, b_shard),
        out_shardings=(None, c_shard),
        abstract_inputs=(params_s, specs["cache"], specs["batch"]),
        donate_argnums=(1,),
    )
