"""Output-check weights, made by the benchmark from ``--seed``.

The recipe's init is useless for an output check: LayerScale starts at
1e-5, so the output hardly depends on the blocks and a precision fault
inside them is scaled out of sight. ``fill`` makes, in ONE jitted call on
the device and in the type asked for, a tree shaped like the program's
own backbone tree (only its names and shapes are taken from the
program): every leaf N(0, 0.02) except LayerScale ``gamma`` and norm
``scale`` = 1.

``reference_weights`` renames such a backbone tree — stacked ``blocks/block/*``
leaves of a scanned stack, or ``blocks_<i>/*`` of an unrolled one — into
the plain layout ``reference/vit_fp32.py`` documents.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ONES = ("gamma", "scale")
FILL_STREAM = 7  # the stream of ``seed_key`` that ``fill`` draws from


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed) % (1 << 62)
    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, seed >> 31)
    return jax.random.fold_in(key, stream)


def fill_leaves(abstract_tree, key, dtype):
    """The tree of ``abstract_tree``'s shapes, filled from ``key``; call it
    inside a jitted function."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        if str(getattr(path[-1], "key", path[-1])) in ONES:
            out.append(jnp.ones(leaf.shape, dtype))
        else:
            k = jax.random.fold_in(key, i)
            out.append((0.02 * jax.random.normal(k, leaf.shape, jnp.float32))
                       .astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def fill(abstract_tree, seed: int, dtype):
    """The tree of ``abstract_tree``'s shapes, filled from ``seed`` in one
    jitted call."""
    return jax.jit(lambda key: fill_leaves(abstract_tree, key, dtype))(
        seed_key(seed, FILL_STREAM))


_BLOCK_NAMES = {
    "norm1_scale": ("norm1", "scale"), "norm1_bias": ("norm1", "bias"),
    "qkv_kernel": ("attn", "qkv_kernel"), "qkv_bias": ("attn", "qkv_bias"),
    "proj_kernel": ("attn", "proj_kernel"), "proj_bias": ("attn", "proj_bias"),
    "ls1": ("ls1", "gamma"),
    "norm2_scale": ("norm2", "scale"), "norm2_bias": ("norm2", "bias"),
    "fc1_kernel": ("mlp", "fc1", "kernel"), "fc1_bias": ("mlp", "fc1", "bias"),
    "fc2_kernel": ("mlp", "fc2", "kernel"), "fc2_bias": ("mlp", "fc2", "bias"),
    "ls2": ("ls2", "gamma"),
}


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


@jax.jit
def _unstack(stacked: dict) -> list:
    depth = next(iter(stacked.values())).shape[0]
    return [{k: v[i] for k, v in stacked.items()} for i in range(depth)]


def reference_weights(tree) -> dict:
    """The program-shaped backbone ``tree`` in the reference's layout."""
    if "blocks" in tree:  # scanned stack: leaves carry a leading [depth]
        stacked = {k: _get(tree["blocks"]["block"], p)
                   for k, p in _BLOCK_NAMES.items()}
        blocks = _unstack(stacked)  # one program, not one slice per leaf and block
    else:
        depth = sum(1 for k in tree if k.startswith("blocks_"))
        blocks = [{k: _get(tree[f"blocks_{i}"], p)
                   for k, p in _BLOCK_NAMES.items()} for i in range(depth)]
    return {
        "patch_kernel": tree["patch_embed"]["kernel"],
        "patch_bias": tree["patch_embed"]["bias"],
        "cls_token": tree["cls_token"],
        "storage_tokens": tree.get("storage_tokens"),
        "norm_scale": tree["norm"]["scale"],
        "norm_bias": tree["norm"]["bias"],
        "blocks": blocks,
    }


@jax.jit
def _stack(blocks: list) -> dict:
    return {k: jnp.stack([b[k] for b in blocks]) for k in blocks[0]}


def _head(h) -> dict:
    n = sum(1 for k in h if k.startswith("mlp_"))
    out = {"prototypes": h["prototypes"]}
    for i in range(n):
        out[f"w{i}"], out[f"b{i}"] = h[f"mlp_{i}"]["kernel"], h[f"mlp_{i}"]["bias"]
    return out


def step_weights(student) -> dict:
    """The program-shaped ``student`` tree ({"backbone", "dino_head",
    "ibot_head"}) in the layout of ``reference/ssl_step_fp32.py``: the
    blocks' leaves stacked along a leading [depth]."""
    bb = reference_weights(student["backbone"])
    if bb.pop("storage_tokens") is not None:
        raise ValueError("the step reference has no storage tokens")
    bb["mask_token"] = student["backbone"]["mask_token"]
    bb["blocks"] = _stack(bb["blocks"])
    return {"backbone": bb, "dino_head": _head(student["dino_head"]),
            "ibot_head": _head(student["ibot_head"])}
