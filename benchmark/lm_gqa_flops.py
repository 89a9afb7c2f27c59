"""FLOPs the ``smallthinker`` next-token step REQUIRES per token, and the
operations and HBM bytes of its attention core ``gqa_core``, from shapes.
A multiply-add counts 2.

``shape`` is the ``flops`` group of the configuration's file: the widths
as published, the layer table, the window, the experts held and the
sequence length.

Forward, per token:

- a mixer: the q and output projections over ``num_attention_heads``
  heads, the k and v projections over ``num_key_value_heads``, and the
  core at the mean over positions of the keys a query sees, scores and
  values ``head_dim`` wide each: the (query, key) pairs inside the band
  are T (T + 1) / 2 on a global layer and W (W + 1) / 2 + (T - W) W on a
  window layer (W keys with the query's own; T where T < W);
- a routed layer: the router over all the experts and the EXPECTED rows
  this shard's experts get (top_k * held / experts a token, each through
  one gated expert of three matrices);
- the head over the vocabulary held. The embedding is a gather.

Training is 3 x forward (backward: 2 x). Not counted: recomputation
under remat, the tiles' halves outside the band, norms, the rotary turn,
softmax, the loss, the optimizer update.
"""

from __future__ import annotations


def band_pairs(tokens: int, window: int | None) -> int:
    """(query, key) pairs a causal layer computes over one sequence:
    key j for query t where j <= t and, with a window, j > t - window."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def window_of(shape: dict, mixer: str):
    """The layer kind's window: ``sliding_window_size`` on a window layer,
    None (every key up to the query's own) on a global one."""
    return shape["sliding_window_size"] if mixer == "swa" else None


def forward_flops_per_token(shape: dict) -> dict:
    """By part: {"swa", "full_attn", "ffn", "head"} FLOPs a token, forward."""
    d, t = shape["hidden_size"], shape["seq_len"]
    h, hk, dh = (shape["num_attention_heads"], shape["num_key_value_heads"],
                 shape["head_dim"])
    proj = 2.0 * (2 * d * h * dh + 2 * d * hk * dh)
    rows = (shape["moe_num_active_primary_experts"] * shape["experts_held"]
            / shape["moe_num_primary_experts"])
    moe = (2.0 * d * shape["moe_num_primary_experts"]
           + rows * 2.0 * 3 * d * shape["moe_ffn_hidden_size"])
    out = {"swa": 0.0, "full_attn": 0.0, "ffn": 0.0,
           "head": 2.0 * d * shape["vocab_size"]}
    for mixer, _ in shape["layers"]:
        out[mixer] += proj + 2.0 * band_pairs(t, window_of(shape, mixer)) / t \
            * h * 2 * dh
        out["ffn"] += moe
    return out


def train_flops_per_token(shape: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(shape).values())


def gqa_core_train(tokens: int, window: int | None, heads: int, kv_heads: int,
                   head_dim: int, act_bytes: int = 2) -> tuple:
    """(operations, bytes) of forward + backward of ONE layer's
    ``gqa_core`` over one sequence of ``tokens``: the pairs inside the
    band, scores and values, x 3 for training without recomputation; q,
    k, v, o and their four cotangents once each in the activation type
    (k and v at their own head count: grouped heads never write them out
    a group's times)."""
    ops = 3.0 * band_pairs(tokens, window) * heads * 2.0 * (head_dim + head_dim)
    nbytes = 2.0 * act_bytes * tokens * head_dim * (2 * heads + 2 * kv_heads)
    return ops, nbytes
