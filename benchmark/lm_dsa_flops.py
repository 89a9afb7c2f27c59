"""FLOPs the ``keye_vl2`` next-token step REQUIRES per token, and the
operations and HBM bytes of its attention core ``dsa_core`` (grouped-query
attention over the keys a learned indexer selects), from shapes. A
multiply-add counts 2.

``shape`` is the ``flops`` group of the configuration's file: the widths
as published, the layer table, the indexer's sizes, the experts held and
the sequence length.

Forward, per token:

- a mixer: the q and output projections over ``num_attention_heads``
  heads, the k and v projections over ``num_key_value_heads``, the
  indexer's three projections (``indexer_num_heads`` heads of
  ``indexer_head_dim``, ONE key head, a weight a head), and the core at
  the mean over positions of the keys a query KEEPS, scores and values
  ``head_dim`` wide each: the selected pairs are ``min(t + 1, topk)`` a
  query, topk (topk + 1) / 2 + (T - topk) topk a sequence
  (``lm_gqa_flops.band_pairs`` at a window of topk: the same count,
  other keys);
- the index scores: EVERY causal pair, T (T + 1) / 2, through
  ``indexer_num_heads`` products of ``indexer_head_dim`` (which keys a
  query keeps is not known before all of them are scored);
- a routed layer: the router over all the experts and the EXPECTED rows
  this shard's experts get (top_k * held / experts a token, each through
  one gated expert of three matrices);
- the head over the vocabulary held. The embedding is a gather.

Training is 3 x forward (backward: 2 x) but for the index scores, whose
backward is the index loss's and reaches the SELECTED pairs alone (two
products a pair and head). Not counted: recomputation under remat, the
masked pairs a pass over every causal tile computes and throws away (the
program's core computes all 134.2 M pairs a head at 16,384 tokens for the
31.46 M it keeps), the index loss's target (the core's own probabilities,
summed over heads: a second pass over the selected pairs in this program,
no required work), the counting passes of the selection, norms, the
rotary turn, softmax, the losses, the optimizer update.
"""

from __future__ import annotations

import lm_gqa_flops


def selected_pairs(tokens: int, topk: int) -> int:
    """(query, key) pairs a sequence keeps: min(t + 1, topk) a query."""
    return lm_gqa_flops.band_pairs(tokens, topk)


def forward_flops_per_token(shape: dict) -> dict:
    """By part: {"dsa", "index", "ffn", "head"} FLOPs a token, forward
    ("index": the score planes over every causal pair, alone)."""
    d, t = shape["hidden_size"], shape["seq_len"]
    h, hk, dh = (shape["num_attention_heads"], shape["num_key_value_heads"],
                 shape["head_dim"])
    hi, di = shape["indexer_num_heads"], shape["indexer_head_dim"]
    proj = 2.0 * (2 * d * h * dh + 2 * d * hk * dh) \
        + 2.0 * (d * hi * di + d * di + d * hi)
    core = 2.0 * selected_pairs(t, shape["index_topk"]) / t * h * 2 * dh
    scores = 2.0 * lm_gqa_flops.band_pairs(t, None) / t * hi * di
    rows = (shape["num_experts_per_tok"] * shape["experts_held"]
            / shape["num_experts"])
    moe = (2.0 * d * shape["num_experts"]
           + rows * 2.0 * 3 * d * shape["moe_intermediate_size"])
    layers = len(shape["layers"])
    return {"dsa": layers * (proj + core), "index": layers * scores,
            "ffn": layers * moe, "head": 2.0 * d * shape["vocab_size"]}


def train_flops_per_token(shape: dict) -> float:
    fwd = forward_flops_per_token(shape)
    t = shape["seq_len"]
    index_bwd = (len(shape["layers"]) * 2.0 * 2.0
                 * selected_pairs(t, shape["index_topk"]) / t
                 * shape["indexer_num_heads"] * shape["indexer_head_dim"])
    return (3.0 * (fwd["dsa"] + fwd["ffn"] + fwd["head"]) + fwd["index"]
            + index_bwd)


def dsa_core_train(tokens: int, topk: int, heads: int, kv_heads: int,
                   head_dim: int, act_bytes: int = 2) -> tuple:
    """(operations, bytes) of forward + backward of ONE layer's
    ``dsa_core`` over one sequence of ``tokens``: the SELECTED pairs,
    scores and values, x 3 for training without recomputation; q, k, v, o
    and their four cotangents once each in the activation type (k and v
    at their own head count), and the selection itself once a pass at a
    bit a causal pair's place (T x T / 8 bytes)."""
    ops = 3.0 * selected_pairs(tokens, topk) * heads * 2.0 * (2 * head_dim)
    nbytes = (2.0 * act_bytes * tokens * head_dim * (2 * heads + 2 * kv_heads)
              + 2.0 * tokens * tokens / 8)
    return ops, nbytes
