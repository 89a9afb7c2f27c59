"""FLOPs the ``lfm2_moe`` next-token step REQUIRES per token, the HBM
bytes of the gated short convolution's chain (``sconv_chain``) and the
operations of the 64-wide attention core (``gqa_core``), from shapes. A
multiply-add counts 2.

``shape`` is the ``flops`` group of the configuration's file: the widths
as published, the layer table, the experts held and the sequence length.

Forward, per token:

- a conv mixer: ``in_proj`` (D x 3 D) and ``out_proj`` (D x D), and the
  chain's 2 + 2 W multiplies and adds a channel (the two gates, W taps);
- the attention mixer: the q, k, v and output projections and the causal
  core at the mean over positions of the keys a query sees, scores and
  values ``head_dim`` wide each (``lm_gqa_flops.band_pairs``, no window);
- the dense SwiGLU of the leading layers; a routed layer: the router over
  all the experts and the EXPECTED rows this shard's experts get (top_k *
  held / experts a token, each through one gated expert of three
  matrices);
- the head over the vocabulary held (the embedding table, read a second
  time). The embedding is a gather.

Training is 3 x forward (backward: 2 x). Not counted: recomputation under
remat, norms, the rotary turn, softmax, the loss, the optimizer update.
"""

from __future__ import annotations

import lm_gqa_flops


def forward_flops_per_token(shape: dict) -> dict:
    """By part: {"conv", "full_attn", "ffn", "head"} FLOPs a token, forward."""
    d, t = shape["hidden_size"], shape["seq_len"]
    conv = 2.0 * (d * 3 * d + d * d) + (2.0 + 2.0 * shape["conv_L_cache"]) * d
    h, hk = shape["num_attention_heads"], shape["num_key_value_heads"]
    dh = d // h
    attn = (2.0 * (2 * d * h * dh + 2 * d * hk * dh)
            + 2.0 * lm_gqa_flops.band_pairs(t, None) / t * h * 2 * dh)
    dense = 2.0 * 3 * d * shape["intermediate_size"]
    rows = (shape["num_experts_per_tok"] * shape["experts_held"]
            / shape["num_experts"])
    moe = (2.0 * d * shape["num_experts"]
           + rows * 2.0 * 3 * d * shape["moe_intermediate_size"])
    out = {"conv": 0.0, "full_attn": 0.0, "ffn": 0.0,
           "head": 2.0 * d * shape["vocab_size"]}
    for mixer, ffn in shape["layers"]:
        out[mixer] += conv if mixer == "conv" else attn
        out["ffn"] += dense if ffn == "dense" else moe
    return out


def train_flops_per_token(shape: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(shape).values())


def sconv_chain_train_bytes(tokens: int, channels: int, act_bytes: int = 2) -> float:
    """HBM bytes forward + backward of ONE layer's chain have to move over
    ``tokens`` tokens: planes of ``[tokens, channels]`` in the activation
    type, 4 forward (B, C and u read, y written) and 7 backward (dy, B, C
    and u read; dB, dC and du written). The taps and their gradient are
    ``W x channels`` numbers: nothing."""
    return float(4 + 7) * tokens * channels * act_bytes


def attn_core_train_ops(tokens: int, heads: int, head_dim: int) -> float:
    """Operations of forward + backward of ONE layer's ``gqa_core`` over
    one sequence: every causal pair, scores and values ``head_dim`` wide,
    x 3 for training without recomputation
    (``lm_gqa_flops.gqa_core_train``'s count at this width)."""
    return lm_gqa_flops.gqa_core_train(tokens, None, heads, heads, head_dim)[0]
