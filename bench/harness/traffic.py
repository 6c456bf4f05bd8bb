"""The benchmark's traffic generator: packed training sequences from a
traffic file of parameters and a seed.

A traffic file (``bench/traffic/<name>.json``) holds::

    {"kind": "train",              # runner under bench/runners/
     "context_len": 8192,          # tokens per packed sequence
     "batch": 1,                   # sequences per step (global)
     "stream_steps": 32,           # steps in one pass over the stream
     "stream_seed": 20260617,      # draws the pass's document lengths
     "min_doc_len": 16,            # scraps shorter than this merge back
     "docs": [                     # document-length mixture
        {"p": 0.10, "dist": "pareto", "alpha": 1.1, "scale": 8192,
         "lo": 8192, "hi": 131072},
        {"p": 0.90, "dist": "lognormal", "mu": 7.8, "sigma": 1.1,
         "lo": 64, "hi": 131072}],
     "tokens": {"zipf_a": 1.3, "repeat_p": 0.25}}

The length mixtures and the packing are copies of the program's
``data/distributions.py`` and ``data/packing.py``, with the same draw
order: a mixture of more than one component first draws a uniform to
pick the component; a lognormal draw is clipped to ``[lo, hi]``; a Pareto
draw is ``(pareto(alpha) + 1) * scale`` clipped likewise.  Packing draws
documents until the window is full and truncates the last one, so a
sequence sums exactly to ``context_len`` and carries no padding.

Tokens are Zipfian unigrams with repetition bigrams, drawn per sequence in
packed order (a copy of the program's token synthesis).  Labels are the
next token within the same document, ``-1`` at each document's last token.

The document lengths of a stream are the same for every seed: the
``stream_steps`` steps of one pass are drawn once from the file's
``stream_seed``, and a run goes through the pass again and again.  The
pass is ordered so that a stretch of consecutive steps carries about the
mean work of the mix: the steps are sorted by their attention work and
laid out in van der Corput order (heavy and light alternate at every
scale).  So runs on different seeds do the same work, and a window that
covers part of a pass still sees the mix; the run's seed draws the tokens
(and the weights).  Tokens come from a generator seeded from
``(seed, step)`` through ``numpy.random.SeedSequence``, so any seed that
fits in 64 bits is valid, the same seed always gives the same stream, and
no two steps share their tokens.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

__all__ = ["Traffic", "SeqSample", "step_rng", "sample_doc_length",
           "pack_sequence", "synth_tokens", "packed_labels", "draw_step",
           "causal_pairs", "stream_lengths", "pass_order"]


@dataclasses.dataclass(frozen=True)
class Traffic:
    name: str
    kind: str
    context_len: int
    batch: int
    stream_steps: int
    stream_seed: int
    docs: tuple
    min_doc_len: int = 16
    zipf_a: float = 1.3
    repeat_p: float = 0.25

    @classmethod
    def from_dict(cls, name: str, d: dict) -> "Traffic":
        tok = d.get("tokens", {})
        return cls(name=name, kind=d["kind"],
                   context_len=int(d["context_len"]), batch=int(d["batch"]),
                   stream_steps=int(d["stream_steps"]),
                   stream_seed=int(d["stream_seed"]),
                   docs=tuple(tuple(sorted(c.items())) for c in d["docs"]),
                   min_doc_len=int(d.get("min_doc_len", 16)),
                   zipf_a=float(tok.get("zipf_a", 1.3)),
                   repeat_p=float(tok.get("repeat_p", 0.25)))


@dataclasses.dataclass
class SeqSample:
    """One packed sequence in packed (natural) order."""
    doc_lens: np.ndarray    # (n_docs,) int64, sums to context_len
    tokens: np.ndarray      # (context_len,) int32
    labels: np.ndarray      # (context_len,) int32, -1 at document ends


def step_rng(seed: int, step: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         int(step)]))


def _draw(rng: np.random.Generator, comp: dict) -> int:
    dist = comp["dist"]
    if dist == "lognormal":
        x = rng.lognormal(mean=comp["mu"], sigma=comp["sigma"])
    elif dist == "pareto":
        x = (rng.pareto(comp["alpha"]) + 1.0) * comp["scale"]
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return int(np.clip(x, comp["lo"], comp["hi"]))


def sample_doc_length(docs, rng: np.random.Generator) -> int:
    """One document length from the mixture ``docs`` (dicts, or tuples of
    their items)."""
    docs = [dict(c) for c in docs]
    if len(docs) == 1:
        return _draw(rng, docs[0])
    u = rng.random()
    acc = 0.0
    for comp in docs[:-1]:
        acc += comp["p"]
        if u < acc:
            return _draw(rng, comp)
    return _draw(rng, docs[-1])


def pack_sequence(docs, context_len: int, rng: np.random.Generator, *,
                  min_doc_len: int = 16) -> np.ndarray:
    lens: list[int] = []
    total = 0
    while total < context_len:
        d = min(sample_doc_length(docs, rng), context_len - total)
        if d < min_doc_len and total + d < context_len and lens:
            lens[-1] += d
        else:
            lens.append(d)
        total += d
    out = np.asarray(lens, dtype=np.int64)
    if int(out.sum()) != context_len:
        raise RuntimeError("packing did not fill the window exactly")
    return out


def synth_tokens(n_tok: int, vocab: int, rng: np.random.Generator, *,
                 zipf_a: float, repeat_p: float) -> np.ndarray:
    packed = ((rng.zipf(zipf_a, n_tok) - 1) % vocab).astype(np.int32)
    rep = rng.random(n_tok) < repeat_p
    rep[0] = False
    prev = np.maximum(np.arange(n_tok) - 1, 0)
    return np.where(rep, packed[prev], packed).astype(np.int32)


def packed_labels(tokens: np.ndarray, doc_lens: np.ndarray) -> np.ndarray:
    labels = np.empty_like(tokens)
    labels[:-1] = tokens[1:]
    labels[np.cumsum(doc_lens) - 1] = -1
    return labels


@functools.lru_cache(maxsize=8)
def stream_lengths(traffic: Traffic) -> tuple:
    """The document lengths of one pass, ``[step][row] -> lengths``, in the
    order they were drawn from ``stream_seed``."""
    out = []
    for i in range(traffic.stream_steps):
        rng = step_rng(traffic.stream_seed, i)
        out.append(tuple(pack_sequence(traffic.docs, traffic.context_len,
                                       rng, min_doc_len=traffic.min_doc_len)
                         for _ in range(traffic.batch)))
    return tuple(out)


def _radical_inverse(i: int) -> float:
    x, f = 0.0, 0.5
    while i:
        x += f * (i & 1)
        i >>= 1
        f /= 2
    return x


def pass_order(traffic: Traffic) -> list[int]:
    """Steps of a pass (indices into :func:`stream_lengths`) sorted by
    attention work and laid out in van der Corput order."""
    lens = stream_lengths(traffic)
    by_work = sorted(range(len(lens)), key=lambda i: (
        sum(causal_pairs(r) for r in lens[i]), i))
    slots = sorted(range(len(lens)), key=_radical_inverse)
    order = [0] * len(lens)
    for rank, slot in enumerate(slots):
        order[slot] = by_work[rank]
    return order


def draw_step(traffic: Traffic, seed: int, step: int,
              vocab: int) -> list[SeqSample]:
    """The ``traffic.batch`` packed sequences of run step ``step``."""
    order = pass_order(traffic)
    lens_rows = stream_lengths(traffic)[order[step % len(order)]]
    rng = step_rng(seed, step)
    out = []
    for lens in lens_rows:
        toks = synth_tokens(traffic.context_len, vocab, rng,
                            zipf_a=traffic.zipf_a,
                            repeat_p=traffic.repeat_p)
        out.append(SeqSample(lens, toks, packed_labels(toks, lens)))
    return out


def causal_pairs(doc_lens) -> int:
    """Query-key pairs of per-document causal attention: sum d(d+1)/2."""
    d = np.asarray(doc_lens, dtype=np.int64)
    return int(np.sum(d * (d + 1) // 2))
