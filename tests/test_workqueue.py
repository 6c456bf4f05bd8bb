"""Flattened work-queue kernel scheduling (grid="flat").

Properties of :func:`build_work_queue` — the queue is an exact
permutation of the rectangular visit set with contiguous LPT-ordered
rows and correct FIRST/LAST/VALID boundary flags — plus interpret-mode
fwd+grad parity of the flat vs rect kernel schedules across GQA group
sizes (with whole and chunked head packing) and across the CP table
emission (per-rank concat layouts and chunked hop tables for CP in
{2, 4}).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:      # parity tests below run regardless
    HAVE_HYPOTHESIS = False

from repro.kernels import doc_attention as da
from repro.kernels.doc_attention import (FLAG_FIRST, FLAG_LAST, FLAG_VALID,
                                         build_block_tables,
                                         build_work_queue)
from repro.kernels.ops import doc_flash_attention
from repro.kernels.ref import mha_reference
from repro.planner import emit_visit_tables, visit_table_shapes

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


def _check_queue_properties(idx, nvis, row, col, flags):
    """One queue direction against its rectangular source tables."""
    B, R, V = idx.shape
    for b in range(B):
        steps = [(int(r), int(c), int(f))
                 for r, c, f in zip(row[b], col[b], flags[b])]
        # 1. valid steps are an exact permutation of the rect visit set
        rect = sorted((r, int(idx[b, r, vi]))
                      for r in range(R) for vi in range(int(nvis[b, r])))
        flat = sorted((r, c) for r, c, f in steps if f & FLAG_VALID)
        assert flat == rect, f"sample {b}: queue is not a permutation"
        # 2. per row: contiguous steps, exactly one FIRST (at the start)
        #    and one LAST (at the end); every row appears (sentinels
        #    cover empty rows)
        seen = []
        for r, c, f in steps:
            if f & (FLAG_FIRST | FLAG_LAST | FLAG_VALID):
                if not seen or seen[-1] != r:
                    seen.append(r)
        assert sorted(seen) == list(range(R)), f"sample {b}: rows missing"
        assert len(set(seen)) == len(seen), f"sample {b}: row split"
        per_row = {}
        for r, c, f in steps:
            if f & (FLAG_FIRST | FLAG_LAST | FLAG_VALID):
                per_row.setdefault(r, []).append(f)
        for r, fl in per_row.items():
            assert fl[0] & FLAG_FIRST and sum(bool(f & FLAG_FIRST)
                                              for f in fl) == 1
            assert fl[-1] & FLAG_LAST and sum(bool(f & FLAG_LAST)
                                              for f in fl) == 1
            want = int(nvis[b, r])
            assert sum(bool(f & FLAG_VALID) for f in fl) == want
            assert len(fl) == max(want, 1)   # empty rows: one sentinel
        # 3. LPT: rows appear in non-increasing visit-count order
        counts = [int(nvis[b, r]) for r in seen]
        assert counts == sorted(counts, reverse=True), \
            f"sample {b}: not LPT-ordered"
        # 4. pad tail never re-triggers init/finalize/compute
        tail = steps[sum(max(int(nvis[b, r]), 1) for r in range(R)):]
        assert all(f == 0 for _, _, f in tail)


def _queue_permutation_case(seed, docs, q_pad):
    B, Tq, Tk, bq, bk = 2, 64, 64, 8, 16
    qd, qp, kd, kp = _layout(B, Tq, Tk, docs, seed=seed, q_pad=q_pad)
    t = build_block_tables(qd, qp, kd, kp, block_q=bq, block_k=bk)
    _check_queue_properties(t.kv_idx, t.kv_nvis, t.fq_row, t.fq_col,
                            t.fq_flags)
    _check_queue_properties(t.q_idx, t.q_nvis, t.rq_row, t.rq_col,
                            t.rq_flags)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000), docs=st.integers(1, 6),
           q_pad=st.integers(0, 12))
    def test_work_queue_is_exact_row_permutation(seed, docs, q_pad):
        _queue_permutation_case(seed, docs, q_pad)
else:
    @pytest.mark.parametrize("seed,docs,q_pad",
                             [(0, 1, 0), (1, 3, 5), (2, 6, 12),
                              (3, 4, 0), (4, 2, 7)])
    def test_work_queue_is_exact_row_permutation(seed, docs, q_pad):
        """Fixed-seed fallback when hypothesis is unavailable."""
        _queue_permutation_case(seed, docs, q_pad)


def test_work_queue_all_empty_rows():
    """Fully-padded metadata: every row is a sentinel, nothing valid."""
    qd = np.full((1, 32), -1, np.int32)
    qp = np.zeros((1, 32), np.int32)
    t = build_block_tables(qd, qp, qd, qp, block_q=8, block_k=8)
    assert not np.any(t.fq_flags & FLAG_VALID)
    assert np.count_nonzero(t.fq_flags & FLAG_FIRST) == 4   # one per row
    _check_queue_properties(t.kv_idx, t.kv_nvis, t.fq_row, t.fq_col,
                            t.fq_flags)


def test_work_queue_pad_to_steps():
    qd, qp, kd, kp = _layout(1, 64, 64, 3, seed=7)
    t = build_block_tables(qd, qp, kd, kp, block_q=8, block_k=8)
    S = t.fq_row.shape[-1]
    row, col, flags = build_work_queue(t.kv_idx, t.kv_nvis,
                                       pad_to_steps=S + 13)
    assert row.shape == (1, S + 13)
    np.testing.assert_array_equal(row[:, :S], t.fq_row)
    assert not np.any(flags[:, S:])
    _check_queue_properties(t.kv_idx, t.kv_nvis, row, col, flags)


# --------------------------------------------------------------------- #
# interpret-mode parity: flat vs rect schedules
# --------------------------------------------------------------------- #
def _tensors(B, Hq, Hkv, Tq, Tk, D, seed=1):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, Hq, Tq, D)).astype(np.float32)
    k = rng.standard_normal((B, Hkv, Tk, D)).astype(np.float32)
    v = rng.standard_normal((B, Hkv, Tk, D)).astype(np.float32)
    return map(jnp.asarray, (q, k, v))


def _flat_rect_parity(Hq, Hkv):
    """Flat and rect schedules agree with each other and with the oracle,
    values and gradients."""
    B, Tq, Tk, D, bq, bk = 2, 64, 128, 16, 16, 16
    qd, qp, kd, kp = _layout(B, Tq, Tk, 4, q_pad=3, kv_pad=5)
    q, k, v = _tensors(B, Hq, Hkv, Tq, Tk, D)
    tabs = build_block_tables(qd, qp, kd, kp, block_q=bq, block_k=bk)
    jqd, jqp, jkd, jkp = map(jnp.asarray, (qd, qp, kd, kp))
    ref = mha_reference(q, k, v, jqd, jqp, jkd, jkp)

    outs, grads = {}, {}
    for grid in ("rect", "flat"):
        outs[grid] = doc_flash_attention(q, k, v, jqd, jqp, jkd, jkp,
                                         tabs, grid=grid, interpret=True)
        np.testing.assert_allclose(np.asarray(outs[grid]), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=grid)
        grads[grid] = jax.grad(
            lambda *a, g=grid: jnp.sum(doc_flash_attention(
                *a, jqd, jqp, jkd, jkp, tabs, grid=g,
                interpret=True) ** 2), (0, 1, 2))(q, k, v)
    # flat vs rect: the same visit set in a different order — bitwise-
    # level agreement is not guaranteed (fp reassociation), tight
    # tolerance is
    np.testing.assert_allclose(np.asarray(outs["flat"]),
                               np.asarray(outs["rect"]),
                               atol=1e-5, rtol=1e-5)
    for a, b, nm in zip(grads["flat"], grads["rect"], "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{nm}")


def _partial_parity(Hq, Hkv):
    """The (o, lse) partial form — the CP merge substrate input — agrees
    across schedules, including the dlse backward fold."""
    B, T, D = 1, 64, 16
    qd, qp, kd, kp = _layout(B, T, T, 3, q_pad=4)
    q, k, v = _tensors(B, Hq, Hkv, T, T, D)
    tabs = build_block_tables(qd, qp, kd, kp, block_q=16, block_k=16)
    jqd, jqp, jkd, jkp = map(jnp.asarray, (qd, qp, kd, kp))

    def run(grid):
        def f(q, k, v):
            o, lse = doc_flash_attention(q, k, v, jqd, jqp, jkd, jkp,
                                         tabs, grid=grid, interpret=True,
                                         partial=True)
            lse = jnp.where(jnp.isfinite(lse), lse, 0.0)
            return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse)
        return jax.value_and_grad(f, (0, 1, 2))(q, k, v)

    lr, gr = run("rect")
    lf, gf = run("flat")
    np.testing.assert_allclose(float(lf), float(lr), rtol=1e-6)
    for a, b, nm in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4, err_msg=f"d{nm}")


def _cap_heads_per_step(monkeypatch, group, hps):
    """Shrink the step budget so that the kernels pack ``hps`` of the
    group's heads a step and walk the rest on the head-chunk axis."""
    monkeypatch.setattr(da, "STEP_VMEM_BUDGET",
                        da._step_vmem_bytes(hps, 16, 16, 16))
    assert da._heads_per_step(group, 16, 16, 16) == hps


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2), (4, 1), (24, 2)])
def test_flat_matches_rect_fwd_and_grad_gqa(Hq, Hkv):
    """Flat and rect schedules agree (and match the oracle) for MHA,
    GQA and MQA group sizes, up to starcoder2_3b's group of 12."""
    _flat_rect_parity(Hq, Hkv)


@pytest.mark.parametrize("Hq,Hkv,hps", [(24, 2, 4), (12, 1, 3)])
def test_flat_matches_rect_with_head_chunks(monkeypatch, Hq, Hkv, hps):
    """Fewer heads a step than the group: the residual head-chunk axis
    accumulates dK/dV across chunks on both schedules."""
    _cap_heads_per_step(monkeypatch, Hq // Hkv, hps)
    _flat_rect_parity(Hq, Hkv)


def test_flat_partial_mode_matches_rect():
    _partial_parity(4, 2)


@pytest.mark.parametrize("Hq,Hkv,hps", [(24, 2, 12), (24, 2, 4)])
def test_flat_partial_mode_matches_rect_packed(monkeypatch, Hq, Hkv, hps):
    """The partial form at starcoder2_3b's group, whole and in chunks."""
    _cap_heads_per_step(monkeypatch, Hq // Hkv, hps)
    _partial_parity(Hq, Hkv)


# --------------------------------------------------------------------- #
# CP table emission: per-rank flat tables across CP sizes
# --------------------------------------------------------------------- #
def _enc(cp, lens=(70, 23, 100, 40, 23), B=2):
    from repro.planner.baselines import BASELINE_PLANNERS
    from repro.planner import encode_plan_batch
    plans = [BASELINE_PLANNERS["flashcp"](np.asarray(lens, np.int64), cp)
             for _ in range(B)]
    return encode_plan_batch(plans, align=16)


@pytest.mark.parametrize("cp", [2, 4])
def test_emitted_flat_tables_match_rect_per_rank(cp):
    """Monolithic concat layout, per (sample, rank): the emitted flat
    queue drives the kernel to the same outputs and gradients as the
    emitted rect tables (the CP{2,4} island-level parity of the grid
    switch, without needing simulated devices)."""
    stack, encs = _enc(cp)
    tabs = emit_visit_tables(stack["doc"], stack["pos"],
                             stack["gath_doc"], stack["gath_pos"],
                             num_workers=cp, strategy="flashcp",
                             overlap="none", grid="both",
                             block_q=16, block_k=16)
    t_loc, buf = encs[0].t_loc, encs[0].buf_len
    Hq, Hkv, D = 4, 2, 8
    rng = np.random.default_rng(3)
    for b in (0, 1):
        for r in range(cp):
            qd = stack["doc"][b, r * t_loc:(r + 1) * t_loc][None]
            qp = stack["pos"][b, r * t_loc:(r + 1) * t_loc][None]
            gd = stack["gath_doc"][b].copy()
            gd[r * buf:(r + 1) * buf] = -2          # self-masked segment
            kd = np.concatenate([qd[0], gd])[None]
            kp = np.concatenate([qp[0], stack["gath_pos"][b]])[None]
            Tq, Tk = qd.shape[1], kd.shape[1]
            q, k, v = _tensors(1, Hq, Hkv, Tq, Tk, D,
                               seed=int(rng.integers(1 << 30)))
            jqd, jqp, jkd, jkp = map(jnp.asarray, (qd, qp, kd, kp))

            rect = tuple(jnp.asarray(tabs[f"tab_{n}"][b, r][None])
                         for n in ("kv_idx", "kv_nvis", "q_idx", "q_nvis"))
            flat = tuple(jnp.asarray(tabs[f"tab_{n}"][b, r][None])
                         for n in ("fq_row", "fq_col", "fq_flags",
                                   "rq_row", "rq_col", "rq_flags"))

            def loss(grid, tt):
                def f(q, k, v):
                    return jnp.sum(doc_flash_attention(
                        q, k, v, jqd, jqp, jkd, jkp, tt, grid=grid,
                        block_q=16, block_k=16, interpret=True) ** 2)
                return jax.value_and_grad(f, (0, 1, 2))(q, k, v)

            lr, gr = loss("rect", rect)
            lf, gf = loss("flat", flat)
            np.testing.assert_allclose(float(lf), float(lr), rtol=1e-5,
                                       err_msg=f"b{b} rank{r}")
            for a, bb, nm in zip(gf, gr, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(bb), atol=5e-4, rtol=5e-4,
                    err_msg=f"b{b} rank{r} d{nm}")


@pytest.mark.parametrize("cp", [2, 4])
def test_emitted_flat_hop_tables_match_direct_build(cp):
    """Chunked emission: hop h of rank r must equal the directly-built
    queue of (q_r, payload of rank (r-1-h) mod N) — the same rotation
    contract the rect emitter test pins, for the flat layout."""
    stack, encs = _enc(cp)
    tabs = emit_visit_tables(stack["doc"], stack["pos"],
                             stack["gath_doc"], stack["gath_pos"],
                             num_workers=cp, strategy="flashcp",
                             overlap="chunked", grid="flat",
                             pad_to="exact", block_q=16, block_k=16)
    t_loc, buf = encs[0].t_loc, encs[0].buf_len
    b = 0
    for r in range(cp):
        qd = stack["doc"][b, r * t_loc:(r + 1) * t_loc][None]
        qp = stack["pos"][b, r * t_loc:(r + 1) * t_loc][None]
        for h in range(cp - 1):
            src = (r - 1 - h) % cp
            kd = stack["gath_doc"][b, src * buf:(src + 1) * buf][None]
            kp = stack["gath_pos"][b, src * buf:(src + 1) * buf][None]
            ref = build_block_tables(qd, qp, kd, kp, block_q=16,
                                     block_k=16)
            got = tabs["tab_hop_fq_row"][b, r, h]
            S = ref.fq_row.shape[-1]
            np.testing.assert_array_equal(got[:S], ref.fq_row[0])
            np.testing.assert_array_equal(
                tabs["tab_hop_fq_flags"][b, r, h][:S], ref.fq_flags[0])
            assert not np.any(tabs["tab_hop_fq_flags"][b, r, h][S:])


def test_emitter_full_pad_matches_flat_spec_shapes():
    cp = 4
    stack, encs = _enc(cp)
    B = stack["doc"].shape[0]
    for overlap in ("none", "chunked"):
        tabs = emit_visit_tables(stack["doc"], stack["pos"],
                                 stack["gath_doc"], stack["gath_pos"],
                                 num_workers=cp, strategy="flashcp",
                                 overlap=overlap, grid="both",
                                 block_q=16, block_k=16, pad_to="full")
        shapes = visit_table_shapes(B, cp, encs[0].t_loc, encs[0].buf_len,
                                    strategy="flashcp", overlap=overlap,
                                    block_q=16, block_k=16, grid="both")
        assert set(tabs) == set(shapes)
        for key, shape in shapes.items():
            assert tabs[key].shape == shape, (key, tabs[key].shape, shape)
