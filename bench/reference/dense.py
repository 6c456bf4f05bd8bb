"""Plain float32 reference of a dense decoder's training step.

What it computes follows the configuration file as it is run (see the
file's ``departures`` for where that differs from the published model):

* token embedding lookup;
* per layer: RMSNorm, q/k/v projections, rotary embedding of each token's
  position inside its own document (rotate-half form, ``rope_theta``),
  causal attention inside each document with grouped kv heads and scale
  ``head_dim ** -0.5``, the output projection, a residual add, RMSNorm, a
  tanh-approximated GELU MLP, a residual add;
* a final RMSNorm, the LM head (the embedding's transpose where the
  configuration ties them), and the mean cross-entropy over tokens whose
  label is not ``-1``;
* global-norm gradient clipping and AdamW (bias-corrected, decoupled weight
  decay on every weight) at a linear-warmup cosine learning rate.

The weights are kept in float32 (the master copy that AdamW updates) and
used as the configuration stores them: every weight is rounded to the
configuration's ``torch_dtype`` where the model reads it, and the gradient
is taken at the rounded weights and applied to the master copy.  (An
update smaller than half a unit in the last place of a bfloat16 weight
does not change the weight the model computes with; a float32 reference
that computed with its master copy would see the first steps of warm-up
move the loss where the model as configured cannot.)

It works on the batch in packed (natural) order, with document ids and
positions made from the document lengths, and imports nothing of the
program.  Attention runs over blocks of queries against all keys, masked
to the query's document and to earlier positions; each block and each
layer is rematerialized in the backward pass, and the MLP and the loss
are taken over blocks of tokens, so that the float32 step fits on the
chips it runs on.
All matmuls run at ``highest`` precision.

``mm_dtype`` (for the control only) rounds both operands of every matmul,
and the incoming gradient of each rounding in the backward pass, to that
dtype (float8 e4m3 with a per-tensor scale to its largest value).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["packed_inputs", "make_step", "param_shardings", "lr_at"]

F8_MAX = 448.0


def packed_inputs(samples) -> dict:
    """(B, T) tokens, labels, document ids and in-document positions."""
    doc = [np.repeat(np.arange(len(s.doc_lens)), s.doc_lens)
           for s in samples]
    pos = [np.concatenate([np.arange(d) for d in s.doc_lens])
           for s in samples]
    return {"tokens": np.stack([s.tokens for s in samples]).astype(np.int32),
            "labels": np.stack([s.labels for s in samples]).astype(np.int32),
            "doc": np.stack(doc).astype(np.int32),
            "pos": np.stack(pos).astype(np.int32)}


def lr_at(step: int, opt: dict) -> float:
    base, warm = opt["lr"], opt["warmup_steps"]
    if step < warm:
        return base * step / max(warm, 1)
    frac = min(max((step - warm) / max(opt["total_steps"] - warm, 1), 0.0),
               1.0)
    r = opt["min_lr_ratio"]
    return base * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * frac)))


def _quantizer(dtype):
    if dtype is None:
        return lambda x: x

    def q(x):
        amax = jnp.max(jnp.abs(x))
        s = jnp.where(amax > 0, F8_MAX / amax, 1.0)
        return (x * s).astype(dtype).astype(jnp.float32) / s

    @jax.custom_vjp
    def rnd(x):
        return q(x)

    rnd.defvjp(lambda x: (q(x), None), lambda _, g: (q(g),))
    return rnd


def _model(cfg: dict, mm_dtype, q_block: int, loss_block: int,
           act_sharding=None):
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    eps = cfg["norm_epsilon"]
    theta = cfg["rope_theta"]
    rnd = _quantizer(mm_dtype)
    stored = jnp.dtype(cfg.get("torch_dtype", "float32"))

    def w(p):                       # a weight as stored; gradient to master
        # reduce_precision, not a round trip through the dtype, which the
        # TPU compiler may drop as excess precision
        if stored == jnp.float32:
            return p
        fi = jnp.finfo(stored)
        return p + jax.lax.stop_gradient(jax.lax.reduce_precision(
            p, exponent_bits=fi.nexp, mantissa_bits=fi.nmant) - p)

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b))

    def keep(x):                    # the residual stream kept per layer
        if act_sharding is None:
            return x
        return jax.lax.with_sharding_constraint(x, act_sharding)

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g

    def rope(x, pos):                       # x (B, T, h, hd), pos (B, T)
        half = hd // 2
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos.astype(jnp.float32)[..., None, None] * inv
        c, s = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    def gelu(x):
        return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                       * (x + 0.044715 * x ** 3)))

    def attention(q, k, v, doc, pos):
        # q (B, T, H, hd); k, v (B, T, KV, hd) -> (B, T, H, hd)
        B, T = doc.shape
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        qbl = math.gcd(q_block, T)
        nb = T // qbl

        @jax.checkpoint
        def block(i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                a, i * qbl, qbl, axis=1)
            qb, db, pb = sl(q), sl(doc), sl(pos)
            s = jnp.einsum("bqhd,bkhd->bhqk", rnd(qb), rnd(k)) * hd ** -0.5
            vis = (db[:, :, None] == doc[:, None, :]) & \
                (pos[:, None, :] <= pb[:, :, None])
            s = jnp.where(vis[:, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", rnd(p), rnd(v))

        out = jax.lax.map(block, jnp.arange(nb))     # (nb, B, qb, H, hd)
        return out.transpose(1, 0, 2, 3, 4).reshape(B, T, H, hd)

    def layer(x, lp, doc, pos):
        B, T, _ = x.shape
        lp = jax.tree.map(w, lp)
        a = lp["attn"]
        h = norm(x, lp["norm1"]["g"])
        q = mm(h, a["wq"]).reshape(B, T, H, hd)
        k = mm(h, a["wk"]).reshape(B, T, KV, hd)
        v = mm(h, a["wv"]).reshape(B, T, KV, hd)
        o = attention(rope(q, pos), rope(k, pos), v, doc, pos)
        x = x + mm(o.reshape(B, T, H * hd), a["wo"])
        return x + mlp(norm(x, lp["norm2"]["g"]), lp["mlp"])

    def mlp(h, m):
        B, T, d = h.shape
        bl = math.gcd(loss_block, T)

        @jax.checkpoint
        def block(i):
            hb = jax.lax.dynamic_slice_in_dim(h, i * bl, bl, axis=1)
            return mm(gelu(mm(hb, m["wi"])), m["wo"])

        out = jax.lax.map(block, jnp.arange(T // bl))   # (nb, B, bl, d)
        return out.transpose(1, 0, 2, 3).reshape(B, T, d)

    def loss(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        doc, pos = batch["doc"], batch["pos"]
        B, T = tokens.shape
        emb = w(params["embed"]["e"])
        x = keep(jnp.take(emb, tokens, axis=0))
        body = jax.checkpoint(
            lambda x, lp: (keep(layer(x, lp, doc, pos)), None))
        x, _ = jax.lax.scan(body, x, params["layers"]["sub_0"])
        x = norm(x, w(params["final_norm"]["g"]))
        if "lm_head" in params:
            hw, head = w(params["lm_head"]["w"]), "btd,dv->btv"
        else:                       # tied: the embedding, not a transpose
            hw, head = emb, "btd,vd->btv"
        lbl = math.gcd(loss_block, T)
        nb = T // lbl

        @jax.checkpoint
        def ce_block(i):
            xb = jax.lax.dynamic_slice_in_dim(x, i * lbl, lbl, axis=1)
            lb = jax.lax.dynamic_slice_in_dim(labels, i * lbl, lbl, axis=1)
            logits = jnp.einsum(head, rnd(xb), rnd(hw))
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[..., None],
                                       axis=-1)[..., 0]
            m = (lb >= 0).astype(jnp.float32)
            return jnp.sum((lse - gold) * m), jnp.sum(m)

        s, n = jax.lax.map(ce_block, jnp.arange(nb))
        return jnp.sum(s) / jnp.maximum(jnp.sum(n), 1.0)

    return loss


def param_shardings(mesh, params):
    """Shard the reference's weights and Adam moments over the mesh's
    ``model`` axis: projections by output column, output matrices by input
    row, the head by vocabulary column, the embedding by vocabulary row."""
    def spec(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        last = name.rsplit("/", 1)[-1]
        n = mesh.shape["model"]
        if last in ("wq", "wk", "wv", "wi") and leaf.shape[-1] % n == 0:
            return P(None, None, "model")
        if last == "wo" and leaf.shape[-2] % n == 0:
            return P(None, "model", None)
        if last == "w" and leaf.shape[-1] % n == 0:
            return P(None, "model")
        if last == "e" and leaf.shape[0] % n == 0:
            return P("model", None)
        return P()
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return treedef.unflatten([NamedSharding(mesh, spec(p, l))
                              for p, l in flat])


def make_step(cfg: dict, opt: dict, *, mm_dtype=None, q_block: int = 128,
              loss_block: int = 1024, shardings=None, mesh=None):
    """``f(params, mu, nu, count, lr, batch) -> (params, mu, nu, loss,
    grad_norms, row_norms)``: one reference training step, as two jitted calls (the
    loss and its gradient, then the clipped AdamW update in place), so that
    the optimizer's temporaries never sit beside the backward pass's.
    ``grad_norms`` are the per-leaf norms of the clipped gradient, in the
    tree's flattening order, and ``row_norms`` the norms of its token
    embedding's rows.  ``params``, ``mu`` and ``nu`` are donated.
    With a ``mesh`` of several devices the residual stream that each layer
    keeps for the backward pass is split over them by sequence."""
    act = None
    if mesh is not None and mesh.shape["model"] > 1:
        act = NamedSharding(mesh, P(None, "model", None))
    loss_fn = _model(cfg, mm_dtype, q_block, loss_block, act)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    wd, clip = opt["weight_decay"], opt["grad_clip"]

    def grad(params, batch):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss_fn)(params, batch)

    def update(params, mu, nu, g, count, lr):
        leaves = jax.tree.leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        c = (count + 1).astype(jnp.float32)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)

        def upd(p, m, v):
            mh = m / (1 - b1 ** c)
            vh = v / (1 - b2 ** c)
            return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)

        params = jax.tree.map(upd, params, mu, nu)
        norms = jnp.stack([jnp.sqrt(jnp.sum(x * x))
                           for x in jax.tree.leaves(g)])
        rows = jnp.sqrt(jnp.sum(g["embed"]["e"] ** 2, -1))
        return params, mu, nu, norms, rows

    gk, uk = {}, {}
    if shardings is not None:
        gk = dict(in_shardings=(shardings, None),
                  out_shardings=(None, shardings))
        uk = dict(in_shardings=(shardings,) * 4 + (None, None),
                  out_shardings=(shardings,) * 3 + (None, None))
    grad = jax.jit(grad, **gk)
    update = jax.jit(update, donate_argnums=(0, 1, 2), **uk)

    def step(params, mu, nu, count, lr, batch):
        loss, g = grad(params, batch)
        params, mu, nu, norms, rows = update(params, mu, nu, g, count, lr)
        return params, mu, nu, loss, norms, rows

    step.grad, step.update = grad, update
    return step
