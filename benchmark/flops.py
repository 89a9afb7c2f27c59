"""FLOPs the DINOv3 pretrain step REQUIRES per image, from shapes.

A multiply-add counts 2. Per transformer block on ``t`` tokens of
sequence length ``n``: ``2*t*(4*d*d + 2*d*ffn) + 4*t*n*d`` (qkv, proj,
fc1, fc2; scores and the weighted sum). Per image and step:

- teacher forward on the 2 global crops;
- student forward and backward (3x forward) on the global and the local
  crops, the block stack scaled by the share of rows that subset
  drop-path keeps (``1 - drop_path_rate``: dropped rows skip the branch);
- the patch embedding of every crop;
- the DINO head (MLP + prototypes) on every CLS token — student 3x on all
  crops, teacher 1x on the global ones — and the iBOT head on the masked
  patch tokens, student 3x and teacher 1x.

Not counted: pad tokens of crop packing, padding of the masked-token
buffers to their capacity, recomputation, the losses, Sinkhorn, norms,
activations and the optimizer update (elementwise, under 1% together).

``count="required"`` takes the EXPECTED number of masked tokens (half the
global crops masked, ratios spread over ``mask_ratio_min_max``) and the
nominal keep share. ``count="executed_shapes"`` takes what the two-pass
program's shapes execute instead — the masked-token buffers at their
capacity (``max ratio * tokens`` per global crop) and the floored keep
counts — and is there only to be laid against XLA's ``cost_analysis`` of
that program (``FLOPS_r05.json``) in ``tests/test_flops.py``.
"""

from __future__ import annotations

ARCHS = {  # embed dim, depth, heads, ffn ratio: the published widths
    "vit_small": (384, 12, 6, 4.0),
    "vit_base": (768, 12, 12, 4.0),
    "vit_large": (1024, 24, 16, 4.0),
}


def block_stack(tokens: float, seq: int, d: int, ffn: int, depth: int) -> float:
    return depth * (2.0 * tokens * (4 * d * d + 2 * d * ffn) + 4.0 * tokens * seq * d)


def head(tokens: float, d: int, hidden: int, bottleneck: int, nlayers: int,
         prototypes: int) -> float:
    dims = [d] + [hidden] * (nlayers - 1) + [bottleneck]
    mlp = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return 2.0 * tokens * (mlp + bottleneck * prototypes)


def pretrain_flops_per_image(shape: dict, count: str = "required",
                             batch: int | None = None) -> float:
    """``shape``: arch, patch_size, global/local crop sizes and numbers,
    n_prefix, drop_path_rate, the two heads' sizes, mask_ratio_min_max and
    mask_sample_probability (the ``flops`` group of a configuration's
    file). ``batch`` is needed for ``executed_shapes`` only."""
    if count not in ("required", "executed_shapes"):
        raise ValueError(count)
    d, depth, _, ratio = ARCHS[shape["arch"]]
    ffn = int(d * ratio)
    p = shape["patch_size"]
    n_g, n_l = shape["global_crops_number"], shape["local_crops_number"]
    t_g = (shape["global_crops_size"] // p) ** 2
    t_l = (shape["local_crops_size"] // p) ** 2
    s_g, s_l = t_g + shape["n_prefix"], t_l + shape["n_prefix"]
    rate = shape["drop_path_rate"]
    lo, hi = shape["mask_ratio_min_max"]
    if count == "required":
        keep_g = keep_l = 1.0 - rate
        masked = n_g * shape["mask_sample_probability"] * 0.5 * (lo + hi) * t_g
    else:
        rows_g, rows_l = n_g * batch, n_l * batch
        keep_g = max(1, int(rows_g * (1.0 - rate))) / rows_g
        keep_l = max(1, int(rows_l * (1.0 - rate))) / rows_l
        masked = n_g * max(1, int(t_g * hi))
    teacher = block_stack(n_g * s_g, s_g, d, ffn, depth)
    student = 3.0 * (keep_g * block_stack(n_g * s_g, s_g, d, ffn, depth)
                     + keep_l * block_stack(n_l * s_l, s_l, d, ffn, depth))
    patch = 2.0 * (n_g * t_g + n_l * t_l) * (p * p * 3) * d
    embed = patch * 3.0 + 2.0 * n_g * t_g * (p * p * 3) * d
    h = shape["head"]
    dino = head(3.0 * (n_g + n_l) + n_g, d, h["hidden_dim"], h["bottleneck_dim"],
                h["nlayers"], h["dino_prototypes"])
    ibot = head(4.0 * masked, d, h["hidden_dim"], h["bottleneck_dim"],
                h["nlayers"], h["ibot_prototypes"])
    return teacher + student + embed + dino + ibot
