"""FLOPs the ``nemotron_h`` next-token step REQUIRES per token, and the
operations and HBM bytes of its three kernels' scopes (``ssd_core``,
``gqa_core``, the un-gated experts' products), from shapes. A
multiply-add counts 2.

``shape`` is the ``flops`` group of the configuration's file: the widths
as published, the block table, the experts held and the sequence length.

Forward, per token:

- a Mamba-2 block's two projections: in_proj D x (2 H P + 2 G N + H) and
  out_proj H P x D;
- its scan, stated at the PUBLISHED ``chunk_size`` L (128) whatever chunk
  the kernels take, so that the roofline reads the same work whatever
  implements it: inside a chunk C B^T a GROUP (G x 2 N a causal pair) and
  the masked product with u a HEAD (H x 2 P a pair) over (L + 1) / 2
  causal pairs a token; to and from the state 2 x H x 2 N P (C S and the
  write u B^T);
- the attention block's four projections; its causal core at the mean
  over positions of the keys a query sees (``lm_gqa_flops.band_pairs``,
  no window), scores and values ``head_dim`` wide for each query head;
- a routed block: the router over all the experts, the ONE shared
  un-gated MLP of two matrices and the EXPECTED rows this shard's experts
  get (top_k * held / experts a token, each through one un-gated expert
  of two matrices);
- the head over the vocabulary held. The embedding is a gather.

Training is 3 x forward (backward: 2 x). Not counted: recomputation under
remat, the convolution, norms, gates, softmax, the loss, the optimizer
update.
"""

from __future__ import annotations

import lm_gqa_flops


def ssd_scan_forward_flops_per_token(shape: dict) -> float:
    """One block's scan, forward, a token, at the published chunk."""
    h, p = shape["mamba_num_heads"], shape["mamba_head_dim"]
    g, n, chunk = shape["n_groups"], shape["ssm_state_size"], shape["chunk_size"]
    inside = (chunk + 1) / 2.0 * (g * 2.0 * n + h * 2.0 * p)
    return inside + 2.0 * h * 2.0 * n * p


def forward_flops_per_token(shape: dict) -> dict:
    """By part: {"ssm_proj", "ssd_core", "attn_proj", "attn_core", "ffn",
    "head"} FLOPs a token, forward."""
    d, t = shape["hidden_size"], shape["seq_len"]
    h, p = shape["mamba_num_heads"], shape["mamba_head_dim"]
    g, n = shape["n_groups"], shape["ssm_state_size"]
    inner = h * p
    ssm_proj = 2.0 * (d * (2 * inner + 2 * g * n + h) + inner * d)
    qh, kh, dh = (shape["num_attention_heads"], shape["num_key_value_heads"],
                  shape["head_dim"])
    attn_proj = 2.0 * (2 * d * qh * dh + 2 * d * kh * dh)
    attn_core = attn_core_forward_ops(t, qh, dh) / t
    rows = (shape["num_experts_per_tok"] * shape["experts_held"]
            / shape["n_routed_experts"])
    moe = (2.0 * d * shape["n_routed_experts"]
           + 2.0 * 2 * d * shape["moe_shared_expert_intermediate_size"]
           + rows * 2.0 * 2 * d * shape["moe_intermediate_size"])
    out = {"ssm_proj": 0.0, "ssd_core": 0.0, "attn_proj": 0.0,
           "attn_core": 0.0, "ffn": 0.0,
           "head": 2.0 * d * shape["vocab_size"]}
    for mixer, ffn in shape["layers"]:
        if mixer == "ssm":
            out["ssm_proj"] += ssm_proj
            out["ssd_core"] += ssd_scan_forward_flops_per_token(shape)
        elif mixer == "full_attn":
            out["attn_proj"] += attn_proj
            out["attn_core"] += attn_core
        if ffn == "moe":
            out["ffn"] += moe
    return out


def train_flops_per_token(shape: dict) -> float:
    return 3.0 * sum(forward_flops_per_token(shape).values())


def ssd_scan_train_ops(tokens: int, shape: dict) -> float:
    """Forward + backward of ONE block's scan over ``tokens`` tokens: x 3
    for training without recomputation."""
    return 3.0 * tokens * ssd_scan_forward_flops_per_token(shape)


def ssd_scan_train_bytes(tokens: int, shape: dict, act_bytes: int = 2) -> float:
    """HBM bytes forward + backward of ONE block's scan have to move over
    ``tokens`` tokens: forward the joined plane [u | B | C] and dt read, y
    written; backward the plane, dt and dy read, the plane's and dt's
    cotangents written. The states a chunk that the forward rule keeps
    for the backward are the implementation's, not counted."""
    h, p = shape["mamba_num_heads"], shape["mamba_head_dim"]
    joined = h * p + 2 * shape["n_groups"] * shape["ssm_state_size"]
    forward = (joined + h * p) * act_bytes + h * 4
    backward = (2 * joined + h * p) * act_bytes + 2 * h * 4
    return float(tokens) * (forward + backward)


def attn_core_forward_ops(tokens: int, heads: int, head_dim: int) -> float:
    """Operations of ONE forward pass of the attention block's core over
    one sequence: every causal pair of ``heads`` query heads, scores and
    values ``head_dim`` wide."""
    return lm_gqa_flops.band_pairs(tokens, None) * heads * 2.0 * 2 * head_dim


def attn_core_train_ops(tokens: int, heads: int, head_dim: int) -> float:
    return 3.0 * attn_core_forward_ops(tokens, heads, head_dim)


def experts_train_ops(rows: float, shape: dict) -> float:
    """Forward + backward of ONE routed block's two grouped products over
    ``rows`` routed rows (x 3: training without recomputation)."""
    return 3.0 * rows * 2.0 * 2 * shape["hidden_size"] \
        * shape["moe_intermediate_size"]
