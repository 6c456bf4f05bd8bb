"""Operation and byte counts of bench/flops/, against hand-worked cases,
and the benchmark's traffic generator: deterministic per seed, packed to
the context exactly, and a draw-for-draw copy of the program's
samplers."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench_tiny import BENCH
from harness.catalog import load_module
from harness.traffic import (Traffic, causal_pairs, draw_step,
                             pack_sequence, packed_labels, pass_order,
                             sample_doc_length, stream_lengths, step_rng)

flops = load_module(BENCH / "flops" / "dense.py")

TINY = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
        "vocab_size": 10}


def test_causal_pairs_by_hand():
    assert causal_pairs([1]) == 1
    assert causal_pairs([3, 2]) == 6 + 3
    assert causal_pairs([8192]) == 8192 * 8193 // 2


def test_attention_pairs_by_hand():
    assert flops.attention_pairs(TINY, [1]) == 1
    assert flops.attention_pairs(TINY, np.array([3, 2])) == 6 + 3
    assert flops.attention_pairs(TINY, [131072]) == 131072 * 131073 // 2
    with pytest.raises(NotImplementedError):
        flops.attention_pairs(dict(TINY, sliding_window=4), [8])


def test_dense_attention_pairs_are_causal_pairs_on_cell_1():
    """On every step of a pass of cell 1's traffic, and of the next pass,
    the family's count is the causal count the runner used to record."""
    tr = _traffic("wlb_llm.8k")
    cfg = json.loads((BENCH / "configs" / "starcoder2_3b.json").read_text())
    for step in range(2 * tr.stream_steps):
        for s in draw_step(tr, 2**33 + 5, step, 49152):
            got = flops.attention_pairs(cfg, s.doc_lens)
            assert type(got) is int
            assert got == causal_pairs(s.doc_lens)


def test_matmul_params_by_hand():
    # per layer: q 8*8, k 8*4, v 8*4, o 8*8, mlp 2*8*16 -> 448; head 80
    assert flops.matmul_params(TINY) == 2 * 448 + 80


def test_model_flops_by_hand():
    tokens, pairs = 5, 9
    dense = 6 * (2 * 448 + 80) * tokens
    attn = 12 * 2 * 2 * 4 * pairs
    assert flops.attention_flops(TINY, pairs) == attn
    assert flops.model_flops(TINY, tokens, pairs) == dense + attn


def test_attention_bytes_by_hand():
    # per layer: T * hd * 2 B * (4 * 2 heads + 4 * 1 kv) + 2 * T * 2 * 4
    T = 3
    assert flops.attention_bytes(TINY, T) == 2 * (3 * 4 * 2 * 12
                                                  + 2 * 3 * 2 * 4)


def test_published_starcoder2_3b_step():
    cfg = json.loads((BENCH / "configs" / "starcoder2_3b.json").read_text())
    # six layers of the published widths and one head (tied or not, the
    # head is a matmul of d_model x vocab)
    assert flops.matmul_params(cfg) == 6 * 95_944_704 + 3072 * 49152
    # dense part of an 8192-token step: 35.7 TFLOP
    assert 6 * flops.matmul_params(cfg) * 8192 == pytest.approx(3.5718e13,
                                                                rel=1e-3)


def _traffic(name):
    return Traffic.from_dict(name, json.loads(
        (BENCH / "traffic" / f"{name}.json").read_text()))


@pytest.mark.parametrize("seed", [7, 2**63 + 12345])   # and past 32 bits
def test_stream_is_deterministic_and_packed(seed):
    tr = _traffic("wlb_llm.8k")
    a = draw_step(tr, seed, 3, 49152)
    b = draw_step(tr, seed, 3, 49152)
    c = draw_step(tr, seed + 1, 3, 49152)
    assert len(a) == tr.batch
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.doc_lens, y.doc_lens)
        np.testing.assert_array_equal(x.tokens, y.tokens)
        assert int(x.doc_lens.sum()) == tr.context_len
        assert x.tokens.shape == (tr.context_len,)
        assert x.tokens.min() >= 0 and x.tokens.max() < 49152
        assert (x.labels == -1).sum() == len(x.doc_lens)
    assert not np.array_equal(a[0].tokens, c[0].tokens)


def test_labels_are_next_token_within_document():
    toks = np.array([5, 6, 7, 8, 9], np.int32)
    lab = packed_labels(toks, np.array([2, 3]))
    np.testing.assert_array_equal(lab, [6, -1, 8, 9, -1])


# the program's pile mix as a traffic file would state it: one component
PILE = [{"p": 1.0, "dist": "lognormal", "mu": 6.9, "sigma": 1.0, "lo": 32,
         "hi": 65536}]


@pytest.mark.parametrize("program", ["wlb_llm", "pile"])
def test_copies_the_programs_sampler(program):
    from repro.data.distributions import sample_doc_length as prog_sample
    from repro.data.packing import pack_sequence as prog_pack

    docs = _traffic("wlb_llm.8k").docs if program == "wlb_llm" else PILE
    r1, r2 = step_rng(7, 0), step_rng(7, 0)
    for _ in range(500):
        assert sample_doc_length(docs, r1) == prog_sample(program, r2)
    r1, r2 = step_rng(9, 1), step_rng(9, 1)
    for _ in range(20):
        np.testing.assert_array_equal(
            pack_sequence(docs, 8192, r1), prog_pack(program, 8192, r2))


def test_pass_is_the_same_for_every_seed_and_spreads_its_work():
    tr = _traffic("wlb_llm.8k")
    n = tr.stream_steps
    for step in (0, 5, n + 5):
        a = draw_step(tr, 11, step, 49152)[0]
        b = draw_step(tr, 2**40 + 1, step, 49152)[0]
        np.testing.assert_array_equal(a.doc_lens, b.doc_lens)
        assert not np.array_equal(a.tokens, b.tokens)
    order = pass_order(tr)
    assert sorted(order) == list(range(n))
    lens = stream_lengths(tr)
    work = np.array([causal_pairs(lens[i][0]) for i in order], float)
    # any half pass, wherever it starts, carries the pass's mean work to
    # within 5% (a random order of this pass: 12%)
    halves = [work[np.arange(s, s + n // 2) % n].mean() for s in range(n)]
    assert max(abs(h / work.mean() - 1) for h in halves) < 0.05
