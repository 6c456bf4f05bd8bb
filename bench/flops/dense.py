"""Operations and bytes of a dense decoder's training step (the family of
starcoder2), counted from the configuration and the document lengths.

Model FLOPs of one step (no recomputation counted):

* ``6 * P * T`` for the matmuls, where ``P`` counts the weights that take
  part in a matmul: per layer the q, k, v and output projections and the
  two MLP matrices, and the LM head (a matmul with the embedding's
  transpose where the two are tied); the embedding lookup is left out;
* ``12 * layers * heads * head_dim * pairs`` for document-causal attention,
  where ``pairs`` is the sum over sequences of :func:`attention_pairs`,
  the query-key pairs the family's attention computes (for dense,
  document-causal attention over whole documents: ``d (d + 1) / 2`` per
  document): 4 per pair and head dimension in the forward pass (QK^T and
  PV), 8 in the backward (dV, dP, dQ, dK).

Attention bytes of one layer, the least any kernel must move: Q, O, dO and
dQ (``T * heads * head_dim`` each) and K, V, dK and dV
(``T * kv_heads * head_dim`` each) in the activation dtype, and the two
float32 row statistics (log-sum-exp and delta, ``T * heads`` each).
"""

from __future__ import annotations

from harness.traffic import causal_pairs

__all__ = ["attention_pairs", "matmul_params", "model_flops",
           "attention_flops", "attention_bytes"]


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, kv, hd


def attention_pairs(cfg: dict, doc_lens) -> int:
    """Query-key pairs of one packed sequence with documents of
    ``doc_lens`` tokens: each token attends to itself and every token
    before it in its document.  This family counts attention over whole
    documents only; a configuration that states a sliding window is
    refused here, so that its pairs are never counted as causal ones."""
    if cfg.get("sliding_window") is not None:
        raise NotImplementedError("dense attention pairs are counted over "
                                  "whole documents; this file counts no "
                                  "sliding window")
    return causal_pairs(doc_lens)


def matmul_params(cfg: dict) -> int:
    d, h, kv, hd = _dims(cfg)
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d \
        + 2 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def attention_flops(cfg: dict, pairs: int) -> float:
    _, h, _, hd = _dims(cfg)
    return 12.0 * cfg["num_hidden_layers"] * h * hd * pairs


def model_flops(cfg: dict, tokens: int, pairs: int) -> float:
    return 6.0 * matmul_params(cfg) * tokens + attention_flops(cfg, pairs)


def attention_bytes(cfg: dict, tokens: int, act_bytes: int = 2) -> float:
    _, h, kv, hd = _dims(cfg)
    per_layer = tokens * hd * act_bytes * (4 * h + 4 * kv) \
        + 2 * tokens * h * 4
    return float(cfg["num_hidden_layers"] * per_layer)
