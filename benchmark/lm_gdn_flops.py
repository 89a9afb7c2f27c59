"""FLOPs the ``qwen3_next`` next-token step REQUIRES per token, and the
operations and HBM bytes of ``gdn_core`` (the delta rule with ONE decay a
head), from shapes. A multiply-add counts 2. (Its other core,
``gqa_core`` at 16 query heads on 2 of 256 + 256, is counted by
``lm_gqa_flops.gqa_core_train`` as it stands: every causal pair, no
window.)

``shape`` is the ``flops`` group of the configuration's file: the widths
as published, the layer table, the experts held and the sequence length.

Forward, per token:

- a Gated DeltaNet mixer: the qkvz, ba and output projections, the one
  short convolution over the joined q, k, v channels, and the delta rule
  at the RECURRENCE's count (decay, read, write and query of a d_k x d_v
  state: 7 d_k d_v a value head) — the least any schedule of it computes;
  the chunked form the program runs computes more (``gdn_core_train``)
  and the difference is not required work;
- the gated attention mixer: the q-with-gate, k, v and output projections
  and the causal core at the mean over positions of the keys a query
  sees, scores and values ``head_dim`` wide each
  (``lm_gqa_flops.band_pairs``, no window);
- a routed layer: the router over all the experts, the shared expert and
  its gate, and the EXPECTED rows this shard's experts get (top_k * held
  / experts a token, each through one gated expert of three matrices);
- the head over the vocabulary held. The embedding is a gather.

Training is 3 x forward (backward: 2 x). Not counted: recomputation
under remat, norms, activations, the rotary turn, softmax, the loss, the
optimizer update.
"""

from __future__ import annotations

import lm_flops
import lm_gqa_flops


def forward_flops_per_token(shape: dict) -> dict:
    """By part: {"gdn", "gated_attn", "ffn", "head"} FLOPs a token, forward."""
    d, t = shape["hidden_size"], shape["seq_len"]
    hk, hv = shape["linear_num_key_heads"], shape["linear_num_value_heads"]
    dk, dv = shape["linear_key_head_dim"], shape["linear_value_head_dim"]
    nk, nv = hk * dk, hv * dv
    gdn = (2.0 * (d * (2 * nk + 2 * nv) + d * 2 * hv + nv * d)
           + 2.0 * shape["linear_conv_kernel_dim"] * (2 * nk + nv)
           + 7.0 * hv * dk * dv)
    h, hkv, dh = (shape["num_attention_heads"], shape["num_key_value_heads"],
                  shape["head_dim"])
    attn = (2.0 * (d * h * 2 * dh + 2 * d * hkv * dh + h * dh * d)
            + 2.0 * lm_gqa_flops.band_pairs(t, None) / t * h * 2 * dh)
    rows = (shape["num_experts_per_tok"] * shape["experts_held"]
            / shape["num_experts"])
    moe = (2.0 * d * shape["num_experts"]
           + 2.0 * 3 * d * shape["shared_expert_intermediate_size"] + 2.0 * d
           + rows * 2.0 * 3 * d * shape["moe_intermediate_size"])
    out = {"gdn": 0.0, "gated_attn": 0.0, "ffn": 0.0,
           "head": 2.0 * d * shape["vocab_size"]}
    for mixer, _ in shape["layers"]:
        out[mixer] += gdn if mixer == "gdn" else attn
        out["ffn"] += moe
    return out


def train_flops_per_token(shape: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(shape).values())


def gdn_core_bytes(tokens: int, key_heads: int, value_heads: int, dk: int,
                   dv: int, act_bytes: int = 2) -> float:
    """HBM bytes ONE forward pass of the delta rule with a scalar gate has
    to move: q and k at their own head count (a key head's two value
    heads never need it written out twice) and v in the activation type,
    the log decay and beta (float32, ONE number a value head and token
    each) in, the float32 output out. The state never has to leave the
    chip's fast memory inside a sequence."""
    per_token = (2 * key_heads * dk * act_bytes
                 + value_heads * (dv * act_bytes + 4 + 4 + 4 * dv))
    return float(per_token) * tokens


def gdn_core_train(tokens: int, key_heads: int, value_heads: int, dk: int,
                   dv: int, chunk: int = 64, act_bytes: int = 2) -> tuple:
    """(operations, bytes) of forward + backward of one layer's
    ``gdn_core`` a step. The operations are the chunked delta rule's
    matmuls (``lm_flops.kda_core_ops``: two score planes below the
    diagonal, the triangular inverse, the products with the state), which
    ONE decay a head does not change: it needs no halving levels, the
    plane e^{G_t - G_s} is an outer difference of one vector a head. The
    backward computes twice the forward's products, reads the inputs and
    the output's cotangent again and writes a gradient the size of every
    input."""
    ops = 3.0 * lm_flops.kda_core_ops(tokens, value_heads, dk, dv, chunk)
    fwd = gdn_core_bytes(tokens, key_heads, value_heads, dk, dv, act_bytes)
    out = 4.0 * dv * tokens * value_heads
    return ops, fwd + (fwd - out) + out + (fwd - out)
