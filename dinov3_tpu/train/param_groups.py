"""Per-parameter lr/wd multiplier trees.

(reference: dinov3_jax/train/param_groups.py — same semantics: ViT layerwise
lr decay, patch-embed lr multiplier, DINO-head wd multiplier, zero wd for
biases/norms/layerscale gammas (and a decoder's ``A_log``; its ``dt_bias``
and ``router_bias`` are biases by name; a tied ``token_embed``, embedding and
head in one leaf, decays as the matrix it is, under ONE rule), last-layer (prototypes) freeze flag — but
emitted as *multiplier pytrees* consumed by one custom optax chain instead
of string labels for ``optax.multi_transform``. This removes the reference's
per-group adamw instances and their late-binding lr/wd closure bug
(SURVEY.md §2.9.4), and extends naturally to ``nn.scan``-stacked blocks,
where the multiplier becomes a broadcastable [L, 1, ...] array.)
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict, unflatten_dict


def _layer_id(path: tuple[str, ...], num_layers: int) -> int | None:
    """0 for embeddings/tokens, i+1 for block i, num_layers+1 for the rest.
    None for an nn.scan-stacked blocks leaf (per-layer array handled by
    caller)."""
    name = ".".join(path)
    if any(tok in name for tok in
           ("pos_embed", "patch_embed", "mask_token", "cls_token",
            "storage_tokens")):
        return 0
    for seg in path:
        if seg.startswith("blocks_"):
            return int(seg.split("blocks_")[1]) + 1
        if seg == "blocks":
            return None  # scanned stack: leading dim is the layer axis
    return num_layers + 1


def infer_num_layers(flat_paths) -> int:
    n = 0
    for path in flat_paths:
        for seg in path:
            if seg.startswith("blocks_"):
                n = max(n, int(seg.split("blocks_")[1]) + 1)
    return n


def build_multiplier_trees(
    params: Any,
    num_layers: int | None = None,
    layerwise_decay: float = 1.0,
    patch_embed_lr_mult: float = 1.0,
    dino_head_wd_multiplier: float = 1.0,
) -> tuple[Any, Any, Any]:
    """(lr_mult, wd_mult, is_last_layer) pytrees matching ``params``.

    Leaves are scalars, or [L, 1, ..] arrays for scanned block stacks.
    """
    flat = flatten_dict(params)
    if num_layers is None:
        num_layers = infer_num_layers(flat.keys()) or _scan_depth(flat)
    lr_mult, wd_mult, last_layer = {}, {}, {}
    for path, leaf in flat.items():
        name = ".".join(str(p) for p in path)
        lid = _layer_id(tuple(str(p) for p in path), num_layers)
        if lid is None:
            L = leaf.shape[0]
            ids = np.arange(1, L + 1)
            rates = layerwise_decay ** (num_layers + 1 - ids)
            lr = rates.reshape((L,) + (1,) * (leaf.ndim - 1))
            lr = jnp.asarray(lr, jnp.float32)
        else:
            lr = layerwise_decay ** (num_layers + 1 - lid)
        wd = 1.0
        if "dino_head" in name:
            wd = dino_head_wd_multiplier
        if (
            name.endswith("bias")
            or "norm" in name
            or path[-1] in ("gamma", "A_log", "D")
        ):
            wd = 0.0
        if "patch_embed" in name:
            lr = lr * patch_embed_lr_mult
        # the DINO/iBOT head prototype layer is the "last layer" whose lr is
        # frozen early in training (reference "last_layer"; ours "prototypes")
        is_last = "prototypes" in name or "last_layer" in name
        lr_mult[path] = lr
        wd_mult[path] = wd
        last_layer[path] = is_last
    return (
        unflatten_dict(lr_mult),
        unflatten_dict(wd_mult),
        unflatten_dict(last_layer),
    )


def _scan_depth(flat) -> int:
    for path, leaf in flat.items():
        if "blocks" in path:
            return leaf.shape[0]
    return 0
