"""FLOPs the ``deepseek_v3`` next-token step REQUIRES per token, and the
operations of its latent attention core (``mla_core``), from shapes. A
multiply-add counts 2.

``shape`` is the ``flops`` group of the configuration's file: the widths
as published, the layer table, the experts held and the sequence length.

Forward, per token:

- a mixer's projections: q (D x H (d_nope + d_rope)), kv_a (D x (r +
  d_rope)), kv_b (r x H (d_nope + d_v)) and o (H d_v x D);
- its causal core at the mean over positions of the keys a query sees
  (``lm_gqa_flops.band_pairs``, no window), scores d_nope + d_rope wide
  and values d_v wide for each of the H heads: the published 192 + 128, not
  the 256 + 128 the kernels' padded operands multiply;
- the dense SwiGLU of the leading layers; a routed layer: the router over
  all the experts, the shared experts as ONE gated MLP of
  ``n_shared_experts`` widths and the EXPECTED rows this shard's experts
  get (top_k * held / experts a token, each through one gated expert of
  three matrices);
- the head over the vocabulary held. The embedding is a gather.

Training is 3 x forward (backward: 2 x). Not counted: recomputation under
remat, norms, the rotary turn, softmax, the loss, the optimizer update.
"""

from __future__ import annotations

import lm_gqa_flops


def forward_flops_per_token(shape: dict) -> dict:
    """By part: {"mla_proj", "mla_core", "ffn", "head"} FLOPs a token,
    forward."""
    d, t, h = shape["hidden_size"], shape["seq_len"], shape["num_attention_heads"]
    nope, rope = shape["qk_nope_head_dim"], shape["qk_rope_head_dim"]
    dv, rank = shape["v_head_dim"], shape["kv_lora_rank"]
    proj = 2.0 * (d * h * (nope + rope) + d * (rank + rope)
                  + rank * h * (nope + dv) + h * dv * d)
    core = mla_core_forward_ops(t, h, nope + rope, dv) / t
    width = shape["moe_intermediate_size"]
    rows = (shape["num_experts_per_tok"] * shape["experts_held"]
            / shape["n_routed_experts"])
    moe = (2.0 * d * shape["n_routed_experts"]
           + 2.0 * 3 * d * width * shape["n_shared_experts"]
           + rows * 2.0 * 3 * d * width)
    dense = 2.0 * 3 * d * shape["intermediate_size"]
    out = {"mla_proj": 0.0, "mla_core": 0.0, "ffn": 0.0,
           "head": 2.0 * d * shape["vocab_size"]}
    for _, ffn in shape["layers"]:
        out["mla_proj"] += proj
        out["mla_core"] += core
        out["ffn"] += dense if ffn == "dense" else moe
    return out


def train_flops_per_token(shape: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(shape).values())


def mla_core_forward_ops(tokens: int, heads: int, qk: int, dv: int) -> float:
    """Operations of ONE forward pass of one layer's core over one
    sequence: every causal pair of ``heads`` heads, a score ``qk`` deep
    and a value ``dv`` wide."""
    return lm_gqa_flops.band_pairs(tokens, None) * heads * 2.0 * (qk + dv)


def mla_core_train_ops(tokens: int, heads: int, qk: int, dv: int) -> float:
    """Forward + backward of ONE layer's ``mla_core`` over one sequence:
    x 3 for training without recomputation."""
    return 3.0 * mla_core_forward_ops(tokens, heads, qk, dv)
