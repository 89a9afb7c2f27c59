"""FLOPs the ``kimi_linear`` next-token step REQUIRES per token, and the
operations and HBM bytes of its one kernel-to-be, ``kda_core``, from
shapes. A multiply-add counts 2.

``shape`` is the ``flops`` group of the configuration's file: the widths
as published, the layer table, the experts held and the sequence length.

Forward, per token:

- a KDA mixer: the q, k, v and output projections, the two low-rank
  pairs (decay, output gate), the beta projection, the three short
  convolutions, and the delta rule at the RECURRENCE's count (decay,
  read, write and query of a d x d state: 7 d^2 a head) — the least any
  schedule of it computes; the chunked form the program runs computes
  more (``kda_core_ops``) and the difference is not required work;
- the MLA mixer: its four projections and the causal core at the mean
  over positions, (T + 1) / 2 keys a query, d_nope + d_rope wide for the
  scores and d_v for the values;
- the dense SwiGLU; per routed layer the router, the shared expert and
  the EXPECTED rows this shard's experts get (top_k * held / experts a
  token, each through one expert);
- the head over the vocabulary held. The embedding is a gather.

Training is 3 x forward (backward: 2 x). Not counted: recomputation
under remat, norms, activations, softmax, the loss, the optimizer update.
"""

from __future__ import annotations


def _swiglu(d: int, width: int) -> float:
    return 2.0 * 3 * d * width


def forward_flops_per_token(shape: dict) -> dict:
    """By part: {"kda", "mla", "ffn", "head"} FLOPs a token, forward."""
    d = shape["hidden_size"]
    h, dk = shape["kda_num_heads"], shape["kda_head_dim"]
    kda = (2.0 * (4 * d * h * dk + 2 * (d * dk + dk * h * dk) + d * h)
           + 2.0 * 3 * shape["short_conv_kernel_size"] * h * dk
           + 7.0 * h * dk * dk)
    hm = shape["num_attention_heads"]
    qk = shape["qk_nope_head_dim"] + shape["qk_rope_head_dim"]
    dv, rank = shape["v_head_dim"], shape["kv_lora_rank"]
    keys = (shape["seq_len"] + 1) / 2.0
    mla = (2.0 * (d * hm * qk + d * (rank + shape["qk_rope_head_dim"])
                  + rank * hm * (shape["qk_nope_head_dim"] + dv) + hm * dv * d)
           + 2.0 * keys * hm * (qk + dv))
    w = shape["moe_intermediate_size"]
    rows = (shape["num_experts_per_token"] * shape["experts_held"]
            / shape["num_experts"])
    moe = (2.0 * d * shape["num_experts"]
           + _swiglu(d, w * shape["num_shared_experts"]) + rows * _swiglu(d, w))
    out = {"kda": 0.0, "mla": 0.0, "ffn": 0.0,
           "head": 2.0 * d * shape["vocab_size"]}
    for mixer, ffn in shape["layers"]:
        out[mixer] += kda if mixer == "kda" else mla
        out["ffn"] += _swiglu(d, shape["intermediate_size"]) if ffn == "dense" else moe
    return out


def train_flops_per_token(shape: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(shape).values())


def kda_core_ops(tokens: int, heads: int, dk: int, dv: int, chunk: int = 64) -> float:
    """Matmul operations of ONE forward pass of the chunked delta rule
    (``dinov3_tpu/ops/kda.py``) over ``tokens`` tokens of ``heads`` heads:
    per chunk of C tokens and head, the strictly lower halves of the two
    C x C score planes (A, P: C (C - 1) / 2 products each, made block by
    block), the log2(C) - 1 squarings and as many products of the triangular
    inverse, T (V - K S), P U, and the three products with the d x d
    state (K S, Q S, K^T U)."""
    c = chunk
    doublings = max(c.bit_length() - 2, 0)
    per_chunk = (2.0 * c * (c - 1) * dk          # A, P below the diagonal
                 + 2.0 * 2 * doublings * c ** 3  # the inverse's factors
                 + 2.0 * 2 * c * c * dv          # T rhs, P U
                 + 2.0 * 3 * c * dk * dv)        # K S, Q S, K^T U
    return per_chunk * heads * (-(-tokens // c))


def kda_core_bytes(tokens: int, heads: int, dk: int, dv: int,
                   act_bytes: int = 2) -> float:
    """HBM bytes ONE forward pass has to move: q, k, v in the activation
    type, the log decay (float32, one a key channel) and beta in, the
    float32 output out. The state never has to leave the chip's fast
    memory inside a sequence."""
    per_token_head = (2 * dk + dv) * act_bytes + 4 * dk + 4 + 4 * dv
    return float(per_token_head) * tokens * heads


def kda_core_train(tokens: int, heads: int, dk: int, dv: int, chunk: int = 64,
                   act_bytes: int = 2) -> tuple:
    """(operations, bytes) of forward + backward of one layer's
    ``kda_core`` a step: the backward computes twice the forward's
    products, reads the inputs and the output's cotangent again and
    writes a gradient the size of every input."""
    ops = 3.0 * kda_core_ops(tokens, heads, dk, dv, chunk)
    fwd = kda_core_bytes(tokens, heads, dk, dv, act_bytes)
    out = 4.0 * dv * tokens * heads
    return ops, fwd + (fwd - out) + out + (fwd - out)
