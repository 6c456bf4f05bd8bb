"""Pallas kernel validation: interpret-mode allclose vs the jnp oracle
across shape/dtype sweeps (fwd, dq, dkv) on both grid schedules, the
GQA head-packing width and its trace-time record, plus block-table
soundness properties (hypothesis)."""

import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis", reason="property tests need the hypothesis dev dependency")
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.kernels import doc_attention as da
from repro.kernels.doc_attention import build_block_tables
from repro.kernels.ops import doc_attention_xla, doc_flash_attention
from repro.kernels.ref import doc_mask, mha_reference

RNG = np.random.default_rng(0)


def _layout(B, Tq, Tk, n_docs, *, q_pad=0, kv_pad=0, seed=0):
    rng = np.random.default_rng(seed)
    kv_doc = np.sort(rng.integers(0, n_docs, (B, Tk)).astype(np.int32), 1)
    kv_pos = np.zeros_like(kv_doc)
    for b in range(B):
        for d in np.unique(kv_doc[b]):
            m = kv_doc[b] == d
            kv_pos[b, m] = np.arange(m.sum())
    idx = np.sort(rng.choice(Tk, Tq, replace=False))
    q_doc, q_pos = kv_doc[:, idx].copy(), kv_pos[:, idx].copy()
    if q_pad:
        q_doc[:, -q_pad:] = -1
    if kv_pad:
        kv_doc[:, -kv_pad:] = -1
    return q_doc, q_pos, kv_doc, kv_pos


def _tensors(B, Hq, Hkv, Tq, Tk, D, dtype, seed=1):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, Hq, Tq, D)).astype(dtype)
    k = rng.standard_normal((B, Hkv, Tk, D)).astype(dtype)
    v = rng.standard_normal((B, Hkv, Tk, D)).astype(dtype)
    return map(jnp.asarray, (q, k, v))


CASES = [
    # B, Hq, Hkv, Tq, Tk, D, bq, bk, docs, dtype, tol
    (2, 4, 2, 64, 128, 16, 16, 16, 4, np.float32, 2e-5),
    (1, 6, 1, 96, 96, 32, 16, 32, 3, np.float32, 2e-5),   # MQA, rect blocks
    (2, 2, 2, 64, 64, 8, 32, 16, 5, np.float32, 2e-5),
    (1, 4, 4, 64, 128, 64, 64, 64, 2, np.float32, 2e-5),
    (2, 4, 2, 64, 128, 16, 16, 16, 4, jnp.bfloat16, 3e-2),
    # starcoder2_3b's attention widths: one step carries a 12-head group
    (1, 24, 2, 256, 256, 128, 128, 128, 3, np.float32, 2e-5),
]
GRIDS = ("rect", "flat")


@pytest.mark.parametrize("B,Hq,Hkv,Tq,Tk,D,bq,bk,docs,dtype,tol", CASES)
def test_fwd_matches_oracle(B, Hq, Hkv, Tq, Tk, D, bq, bk, docs, dtype, tol):
    qd, qp, kd, kp = _layout(B, Tq, Tk, docs, q_pad=3, kv_pad=5)
    q, k, v = _tensors(B, Hq, Hkv, Tq, Tk, D, dtype)
    tabs = build_block_tables(qd, qp, kd, kp, block_q=bq, block_k=bk)
    ref = mha_reference(q, k, v, *map(jnp.asarray, (qd, qp, kd, kp)))
    for grid in GRIDS:
        out = doc_flash_attention(q, k, v,
                                  *map(jnp.asarray, (qd, qp, kd, kp)),
                                  tabs, grid=grid, interpret=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol, err_msg=grid)


@pytest.mark.parametrize("B,Hq,Hkv,Tq,Tk,D,bq,bk,docs,dtype,tol",
                         CASES[:3] + CASES[-1:])
def test_bwd_matches_oracle(B, Hq, Hkv, Tq, Tk, D, bq, bk, docs, dtype, tol):
    qd, qp, kd, kp = _layout(B, Tq, Tk, docs, q_pad=2)
    q, k, v = _tensors(B, Hq, Hkv, Tq, Tk, D, dtype)
    tabs = build_block_tables(qd, qp, kd, kp, block_q=bq, block_k=bk)
    jqd, jqp, jkd, jkp = map(jnp.asarray, (qd, qp, kd, kp))

    g_rf = jax.grad(lambda *a: jnp.sum(mha_reference(
        *a, jqd, jqp, jkd, jkp) ** 2), (0, 1, 2))(q, k, v)
    for grid in GRIDS:
        g_pl = jax.grad(lambda *a: jnp.sum(doc_flash_attention(
            *a, jqd, jqp, jkd, jkp, tabs, grid=grid,
            interpret=True) ** 2), (0, 1, 2))(q, k, v)
        for a, b, nm in zip(g_pl, g_rf, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=25 * tol, rtol=25 * tol,
                                       err_msg=f"{grid} d{nm}")


# --------------------------------------------------------------------- #
# GQA head packing: how many query heads one grid step carries
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("group,D", [(1, 128), (12, 128), (48, 128),
                                     (9, 128), (8, 128), (6, 128), (4, 128),
                                     (7, 64)])
def test_heads_per_step_is_largest_fitting_divisor(group, D):
    hps = da._heads_per_step(group, 128, 128, D)
    assert group % hps == 0
    fits = [h for h in range(1, group + 1) if group % h == 0
            and da._step_vmem_bytes(h, 128, 128, D) <= da.STEP_VMEM_BUDGET]
    assert hps == max(fits, default=1)


def test_heads_per_step_at_published_groups(monkeypatch):
    # MHA keeps the one-head step; starcoder2_3b packs its whole group
    assert da._heads_per_step(1, 128, 128, 128) == 1
    assert da._heads_per_step(12, 128, 128, 128) == 12
    # granite_34b's 48-head group does not fit one step: a divisor does
    hps = da._heads_per_step(48, 128, 128, 128)
    assert 1 < hps < 48 and 48 % hps == 0
    assert da._step_vmem_bytes(hps, 128, 128, 128) <= da.STEP_VMEM_BUDGET
    # a smaller budget packs fewer heads; one nothing fits leaves one
    # head a step, never zero
    monkeypatch.setattr(da, "STEP_VMEM_BUDGET",
                        da._step_vmem_bytes(4, 128, 128, 128))
    assert da._heads_per_step(12, 128, 128, 128) == 4
    monkeypatch.setattr(da, "STEP_VMEM_BUDGET", 1)
    assert da._heads_per_step(12, 128, 128, 128) == 1


@pytest.mark.parametrize("grid", GRIDS)
def test_kernel_builds_record_packing(grid):
    """Tracing the three kernels at starcoder2_3b's widths records, per
    build, 12 heads a step and a grid of one step per KV head and slot."""
    B, Hq, Hkv, T, D, blk = 2, 24, 2, 1024, 128, 128
    nb, S = T // blk, 20
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    sd = jax.ShapeDtypeStruct
    q, kv = sd((B, Hq, T, D), bf16), sd((B, Hkv, T, D), bf16)
    meta = [sd((B, T), i32)] * 4
    stat = sd((B, Hq, T), f32)
    if grid == "rect":
        fwd_t = (sd((B, nb, 5), i32), sd((B, nb), i32))
        rev_t = (sd((B, nb, 6), i32), sd((B, nb), i32))
        want = [B * Hkv * nb * 5, B * Hkv * nb * 5, B * Hkv * nb * 6]
    else:
        fwd_t = (sd((B, S), i32),) * 3
        rev_t = (sd((B, S + 3), i32),) * 3
        want = [B * Hkv * S, B * Hkv * S, B * Hkv * (S + 3)]
    kw = dict(scale=D ** -0.5, block_q=blk, block_k=blk, grid=grid)
    t0 = time.perf_counter_ns()
    jax.eval_shape(lambda *a: da.flash_fwd(*a[:7], a[7:], **kw),
                   q, kv, kv, *meta, *fwd_t)
    jax.eval_shape(lambda *a: da.flash_bwd_dq(*a[:10], a[10:], **kw),
                   q, kv, kv, q, stat, stat, *meta, *fwd_t)
    jax.eval_shape(lambda *a: da.flash_bwd_dkv(*a[:10], a[10:], **kw),
                   q, kv, kv, q, stat, stat, *meta, *rev_t)
    builds = [r.counts for r in obs.records(since_ns=t0)
              if r.name == "attn.kernel_build"]
    assert [c["attn.heads_per_step"] for c in builds] == [12, 12, 12]
    assert [c["attn.grid_steps"] for c in builds] == want


def test_xla_path_matches_oracle():
    qd, qp, kd, kp = _layout(2, 64, 128, 4, kv_pad=7)
    q, k, v = _tensors(2, 4, 2, 64, 128, 16, np.float32)
    ref = mha_reference(q, k, v, *map(jnp.asarray, (qd, qp, kd, kp)))
    for chunk in (16, 64, 999):
        out = doc_attention_xla(q, k, v, *map(jnp.asarray,
                                              (qd, qp, kd, kp)),
                                q_chunk=chunk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_empty_rows_produce_zeros():
    """Fully-padded queries must output exactly zero (not NaN)."""
    qd, qp, kd, kp = _layout(1, 32, 32, 2)
    qd[:, :] = -1
    q, k, v = _tensors(1, 2, 2, 32, 32, 8, np.float32)
    tabs = build_block_tables(qd, qp, kd, kp, block_q=8, block_k=8)
    out = doc_flash_attention(q, k, v, *map(jnp.asarray, (qd, qp, kd, kp)),
                              tabs, interpret=True)
    assert np.all(np.asarray(out) == 0)


# --------------------------------------------------------------------- #
# block-table soundness: skip only provably-invisible, full only
# provably-all-visible
# --------------------------------------------------------------------- #
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), docs=st.integers(1, 6))
def test_block_tables_sound(seed, docs):
    B, Tq, Tk, bq, bk = 1, 64, 64, 8, 16
    qd, qp, kd, kp = _layout(B, Tq, Tk, docs, seed=seed)
    tabs = build_block_tables(qd, qp, kd, kp, block_q=bq, block_k=bk)
    mask = np.asarray(doc_mask(*map(jnp.asarray, (qd, qp, kd, kp))))[0]
    visited = np.zeros((Tq // bq, Tk // bk), bool)
    for qi in range(Tq // bq):
        for vi in range(int(tabs.kv_nvis[0, qi])):
            visited[qi, tabs.kv_idx[0, qi, vi]] = True
    for qi in range(Tq // bq):
        for ki in range(Tk // bk):
            blk = mask[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            if blk.any():
                assert visited[qi, ki], f"visible block ({qi},{ki}) skipped"
    # reverse tables agree with forward tables
    fwd = {(qi, tabs.kv_idx[0, qi, vi]) for qi in range(Tq // bq)
           for vi in range(int(tabs.kv_nvis[0, qi]))}
    bwd = {(tabs.q_idx[0, ki, vi], ki) for ki in range(Tk // bk)
           for vi in range(int(tabs.q_nvis[0, ki]))}
    assert fwd == bwd


def test_whole_doc_layout_has_higher_block_occupancy():
    """The paper's kernel-efficiency claim, kernel-side: contiguous whole
    docs produce denser visit tables than fine-grained interleavings."""
    B, T = 1, 256
    # whole-doc: one 256-token doc
    d1 = np.zeros((B, T), np.int32)
    p1 = np.arange(T, dtype=np.int32)[None]
    t1 = build_block_tables(d1, p1, d1, p1, block_q=32, block_k=32)
    # fine-grained: 16 docs of 16 tokens
    d2 = np.repeat(np.arange(16, dtype=np.int32), 16)[None]
    p2 = np.tile(np.arange(16, dtype=np.int32), 16)[None]
    t2 = build_block_tables(d2, p2, d2, p2, block_q=32, block_k=32)
    assert t1.full_frac > t2.full_frac
    assert t2.visited_frac < t1.visited_frac  # short docs: sparser visits


# --------------------------------------------------------------------- #
# flash-decode kernel (inference hot spot)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("B,Hq,Hkv,S,D,bk,dtype,tol", [
    (2, 4, 2, 128, 16, 32, np.float32, 2e-5),
    (1, 8, 1, 256, 32, 64, np.float32, 2e-5),    # MQA
    (3, 4, 4, 64, 64, 16, np.float32, 2e-5),
    (2, 4, 2, 128, 16, 32, jnp.bfloat16, 3e-2),
])
def test_flash_decode_matches_reference(B, Hq, Hkv, S, D, bk, dtype, tol):
    from repro.kernels.flash_decode import decode_reference, flash_decode
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, Hq, D))).astype(dtype)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D))).astype(dtype)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D))).astype(dtype)
    # ragged per-request lengths, incl. one empty-ish and one full
    lengths = jnp.asarray(
        rng.integers(0, S - 1, (B,)).astype(np.int32)).at[0].set(S - 1)
    ref = decode_reference(q, k, v, lengths)
    out = flash_decode(q, k, v, lengths, block_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("length", [0, 7, 31, 32, 127])  # < block_k, ==S-1
def test_flash_decode_clamp_boundaries(length):
    """Index-map clamp correctness at the block edges: lengths below one
    block, at a block boundary, and at the cache end S-1."""
    from repro.kernels.flash_decode import decode_reference, flash_decode
    B, Hq, Hkv, S, D, bk = 2, 4, 2, 128, 16, 32
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)).astype(np.float32))
    lengths = jnp.asarray([length, S - 1], jnp.int32)
    ref = decode_reference(q, k, v, lengths)
    out = flash_decode(q, k, v, lengths, block_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_matches_model_decode_attention():
    """The kernel agrees with the model's decode-attention math."""
    from repro.kernels.flash_decode import decode_reference
    B, Hq, Hkv, S, D = 2, 4, 2, 64, 16
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)).astype(np.float32))
    t = jnp.asarray([10, 63], jnp.int32)
    # model path (attention.py): explicit mask + softmax
    G = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D) * D ** -0.5
    s = jnp.einsum("bhgd,bhsd->bhgs", qf, k)
    mask = (jnp.arange(S)[None, :] <= t[:, None])[:, None, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    ref = jnp.einsum("bhgs,bhsd->bhgd", p, v).reshape(B, Hq, D)
    out = decode_reference(q, k, v, t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
