"""Execution context: the seam between model code and the CP runtime.

Model code (transformer blocks, Mamba, xLSTM) is written against this
protocol and never mentions meshes or collectives.  The runtime constructs:

* a **local** context (single device / no CP) — used by smoke tests, CPU
  examples and decode-per-device;
* a **CP** context (:mod:`repro.core.cp_attention`) whose ``attn`` performs
  FlashCP sharding-aware communication + document-masked flash attention
  inside a ``shard_map`` island, and whose ``ssm_scan`` performs local
  chunked scans with cross-rank boundary-state exchange.

Conventions:
* ``doc``/``pos`` are per-token metadata in *plan order* — the order tokens
  physically live in the (possibly CP-permuted) sequence buffers.
* ``attn(q, k, v)``: q (B, Hq, T, D); k, v (B, Hkv, T, D) -> (B, Hq, T, D).
* ``ssm_scan(a, x)``: elementwise recurrence h_t = a_t * h_{t-1} + x_t over
  the T axis of (B, T, ...) arrays.  Document resets are encoded by the
  caller as ``a_t = 0`` at document starts (pos == 0).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

__all__ = ["ExecContext", "make_local_context", "local_ssm_scan"]


@dataclasses.dataclass
class ExecContext:
    doc: jax.Array
    pos: jax.Array
    attn: Callable[[jax.Array, jax.Array, jax.Array], jax.Array]
    ssm_scan: Callable[[jax.Array, jax.Array], jax.Array]
    # fused chunkwise selective scan (Mamba): (dt, A, Bm, Cm, xf, reset)->y
    selective_scan: Callable | None = None
    # NamedSharding for (B, T, d) activations — anchors XLA's sharding
    # propagation on the residual stream (None in local mode)
    act_sharding: Any = None
    is_decode: bool = False
    extras: dict[str, Any] = dataclasses.field(default_factory=dict)

    def constrain(self, x: jax.Array) -> jax.Array:
        if self.act_sharding is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.act_sharding)


# --------------------------------------------------------------------- #
# local (no-CP) implementations
# --------------------------------------------------------------------- #
def local_ssm_scan(a: jax.Array, x: jax.Array, *, init: jax.Array | None = None,
                   chunk: int = 64) -> jax.Array:
    """h_t = a_t * h_{t-1} + x_t along axis 1, chunk-rematerialized.

    ``init`` is h_{-1} (default zeros).  The chunked form bounds live memory
    to one chunk of (a, x, h) plus one boundary state per chunk — the XLA
    analogue of a fused scan kernel.
    """
    B, T = x.shape[:2]
    carry0 = jnp.zeros_like(x[:, 0]) if init is None else init

    if T % chunk != 0 or T <= chunk:
        x0 = x[:, 0] + a[:, 0] * carry0
        x = x.at[:, 0].set(x0)
        pair = jax.lax.associative_scan(_combine, (a, x), axis=1)
        return pair[1]

    nc = T // chunk
    a_c = a.reshape(B, nc, chunk, *a.shape[2:]).swapaxes(0, 1)
    x_c = x.reshape(B, nc, chunk, *x.shape[2:]).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, inp):
        ac, xc = inp
        # inject carry into the first element, then scan inside the chunk
        x0 = xc[:, 0] + ac[:, 0] * carry
        xc = xc.at[:, 0].set(x0)
        _, h = jax.lax.associative_scan(_combine, (ac, xc), axis=1)
        return h[:, -1], h

    _, hs = jax.lax.scan(body, carry0, (a_c, x_c))
    return hs.swapaxes(0, 1).reshape(B, T, *x.shape[2:])


def _combine(left, right):
    a_l, x_l = left
    a_r, x_r = right
    return a_l * a_r, x_r + a_r * x_l


# --------------------------------------------------------------------- #
# fused chunkwise selective scan (Mamba)
# --------------------------------------------------------------------- #
def local_selective_scan(dt, A, Bm, Cm, xf, reset, *, chunk: int = 64,
                         init_state=None, summary_only: bool = False,
                         unroll: int = 8):
    """y_t = C_t · h_t with h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t.

    dt, xf (B, T, di); Bm, Cm (B, T, S); A (di, S); reset (B, T) — 0 at
    document starts.  Fused form (§Perf iteration 4): a *sequential*
    ``lax.scan`` over time builds the per-token decay/update on the fly
    inside checkpointed chunk bodies, so the (T, di, S) state tensors are
    never materialized — the only live state is the (B, di, S) carry plus
    one chunk of residuals during the backward recompute.  (The earlier
    associative-scan form materialized ~12 chunk-sized f32 tensors per
    Mamba layer and dominated Jamba's memory roofline.)

    ``init_state`` (B, di, S) seeds the recurrence (CP rank hand-off);
    ``summary_only`` returns (decay product, final state) for the CP
    prefix exchange without producing y.
    """
    B, T, di = dt.shape
    ck = chunk
    while T % ck:
        ck //= 2
    nc = T // ck

    def chunked(v):
        # (nc, ck, B, ...) — outer scan over chunks, inner over time
        return v.reshape(B, nc, ck, *v.shape[2:]) \
            .swapaxes(0, 1).swapaxes(1, 2)

    dt_c, Bm_c, Cm_c, xf_c, rs_c = map(chunked, (dt, Bm, Cm, xf, reset))
    h0 = jnp.zeros((B, di, A.shape[-1]), jnp.float32) \
        if init_state is None else init_state.astype(jnp.float32)

    if summary_only:
        @jax.checkpoint
        def chunk_sum(carry, inp):
            def step(c, sl):
                h, pA = c
                dtc, Bc, xc, rc = sl
                a = jnp.exp(dtc.astype(jnp.float32)[..., None] * A) \
                    * rc[:, None, None]
                h = a * h + (dtc * xc).astype(jnp.float32)[..., None] \
                    * Bc[:, None, :]
                return (h, pA * a), None
            return jax.lax.scan(step, carry, inp, unroll=unroll)[0], None

        ones = jnp.ones_like(h0)
        (h, pA), _ = jax.lax.scan(chunk_sum, (h0, ones),
                                  (dt_c, Bm_c, xf_c, rs_c))
        return pA, h

    @jax.checkpoint
    def chunk_body(h, inp):
        def step(h, sl):
            dtc, Bc, Cc, xc, rc = sl
            a = jnp.exp(dtc.astype(jnp.float32)[..., None] * A) \
                * rc[:, None, None]
            h = a * h + (dtc * xc).astype(jnp.float32)[..., None] \
                * Bc[:, None, :]
            y = jnp.einsum("bds,bs->bd", h, Cc.astype(jnp.float32))
            return h, y
        return jax.lax.scan(step, h, inp, unroll=unroll)

    h, ys = jax.lax.scan(chunk_body, h0, (dt_c, Bm_c, Cm_c, xf_c, rs_c))
    # ys (nc, ck, B, di) -> (B, T, di)
    return ys.swapaxes(1, 2).swapaxes(0, 1).reshape(B, T, di)


def make_local_context(doc: jax.Array, pos: jax.Array,
                       attention_impl: str = "xla",
                       interpret: bool | None = None,
                       q_chunk: int = 512,
                       grid: str = "flat") -> ExecContext:
    """Single-device context: full-sequence doc-masked attention.

    ``grid`` picks the Pallas kernel schedule (flattened work queue by
    default; ``"rect"`` for the rectangular baseline); ``interpret=None``
    runs the kernels in interpret mode only off-TPU."""
    from repro.kernels import ops as kops

    interpret = kops.resolve_interpret(interpret)

    tabs_cache: list = []   # visit tables depend only on (doc, pos): built
    # once on first use instead of per attn call

    def attn(q, k, v):
        if attention_impl == "pallas":
            if not tabs_cache:
                import numpy as np
                from repro.kernels.doc_attention import build_block_tables
                tabs_cache.append(build_block_tables(
                    np.asarray(doc), np.asarray(pos),
                    np.asarray(doc), np.asarray(pos)))
            return kops.doc_flash_attention(q, k, v, doc, pos, doc, pos,
                                            tabs_cache[0], grid=grid,
                                            interpret=interpret)
        return kops.doc_attention_xla(q, k, v, doc, pos, doc, pos,
                                      q_chunk=q_chunk)

    return ExecContext(doc=doc, pos=pos, attn=attn,
                       ssm_scan=functools.partial(local_ssm_scan),
                       selective_scan=local_selective_scan)
