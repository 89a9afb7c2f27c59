"""Weights of the ``nemotron_h`` decoder from ``--seed``, and the renaming
of the program's tree into the layout of ``reference/nemotron_h_fp32.py``.

``fill_leaves`` makes a tree shaped like the program's own (only names
and shapes are taken from the program): the norm scales 1 (the grouped
norm's among them); every matrix N(0, 0.02), embedding and head among
them; the projections that write into the residual stream (a Mamba-2
block's ``out_proj``, the attention's ``o_proj``, the experts' ``w2`` and
the shared expert's ``fc2``) N(0, 0.02 / sqrt(52)): ``rescale_prenorm_residual``
at the PUBLISHED depth (52 blocks, ONE write a block); the convolution's
taps and bias uniform on +-1/sqrt(4), a depthwise ``Conv1d``'s default;
``A_log`` = log U(1, 16); ``dt_bias`` the inverse softplus of
exp(U(log 1e-3, log 1e-1)) floored at 1e-4 (``time_step_min`` / ``_max`` /
``_floor``); ``D`` ones; the router's selection bias N(0, 0.02) —
NON-zero, so that a selection without it differs, and fixed: it takes no
gradient and no decay. The configuration's file lists all of it under
``assumed``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import weights

STD = 0.02
PUBLISHED_BLOCKS = 52
RESIDUAL_OUT_STD = STD / math.sqrt(PUBLISHED_BLOCKS)
RESIDUAL_OUT = ("out_proj", "o_proj", "w2", "fc2")
TIME_STEP = (1e-3, 1e-1, 1e-4)   # min, max, floor
_SSM = {"win": ("in_proj", "kernel"), "conv": ("conv",),
        "conv_bias": ("conv_bias",), "A_log": ("A_log",),
        "dt_bias": ("dt_bias",), "D": ("D",), "gnorm": ("norm_scale",),
        "wout": ("out_proj", "kernel")}
_ATTN = {"wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"),
         "wv": ("v_proj", "kernel"), "wo": ("o_proj", "kernel")}


def _leaf(names, shape, k):
    last = names[-1]
    if last in ("scale", "norm_scale", "D"):
        return jnp.ones(shape, jnp.float32)
    if last in ("conv", "conv_bias"):
        bound = 4 ** -0.5
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    if last == "A_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if last == "dt_bias":
        lo, hi, floor = TIME_STEP
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(lo), math.log(hi))), floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    std = RESIDUAL_OUT_STD if set(names) & set(RESIDUAL_OUT) else STD
    return std * jax.random.normal(k, shape, jnp.float32)


def fill_leaves(abstract_tree, key, dtype=jnp.float32):
    """Call it inside a jitted function."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    out = [_leaf([str(getattr(p, "key", p)) for p in path], leaf.shape,
                 jax.random.fold_in(key, i)).astype(dtype)
           for i, (path, leaf) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def fill(abstract_tree, seed: int, dtype=jnp.float32):
    return jax.jit(lambda key: fill_leaves(abstract_tree, key, dtype))(
        weights.seed_key(seed, weights.FILL_STREAM))


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def reference_tree(backbone) -> dict:
    """A tree shaped like the program's ``params["student"]["backbone"]``
    (weights, a gradient, per-leaf norms) in the reference's layout: a
    block is {``norm``, ``mixer``} or {``norm``, ``ffn``}."""
    layers = []
    for i in range(sum(1 for k in backbone if k.startswith("layers_"))):
        lw = backbone[f"layers_{i}"]
        block = {"norm": lw["norm"]["scale"]}
        if "experts" in lw:
            block["ffn"] = {
                **{k: lw["experts"][k]
                   for k in ("router", "router_bias", "w1", "w2")},
                "shared": {"w1": lw["shared"]["fc1"]["kernel"],
                           "w2": lw["shared"]["fc2"]["kernel"]}}
        else:
            names, mixer = ((_SSM, lw["ssm"]) if "ssm" in lw
                            else (_ATTN, lw["attn"]))
            block["mixer"] = {k: _get(mixer, p) for k, p in names.items()}
        layers.append(block)
    return {"embed": backbone["token_embed"], "head": backbone["lm_head"],
            "norm": backbone["norm"]["scale"], "layers": layers}
