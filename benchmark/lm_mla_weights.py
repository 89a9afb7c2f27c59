"""Weights of the ``deepseek_v3`` decoder from ``--seed``, and the renaming
of the program's tree into the layout of ``reference/kanana2_fp32.py``.

``fill_leaves`` makes a tree shaped like the program's own (only names
and shapes are taken from the program): the norm scales 1; every matrix
N(0, 0.02), embedding and head among them; the projections that write
into the residual stream (the mixer's ``o_proj``, the dense FFN's, the
experts' and the shared experts' ``w3``) N(0, 0.02 / sqrt(2 x 48)): the
scaled initialisation of a residual output at the PUBLISHED depth (48
layers, two writes a layer), as the other all-attention families' fills
have it (``lm_dsa_weights.py`` says what unscaled writes do to the routers
of a stack of softmax attention layers over random tokens); the router's
selection bias N(0, 0.02) — NON-zero, so that a selection without it
differs, and fixed: it takes no gradient and no decay. The configuration's
file lists all of it under ``assumed``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import weights

STD = 0.02
PUBLISHED_LAYERS = 48
RESIDUAL_OUT_STD = STD / math.sqrt(2 * PUBLISHED_LAYERS)
RESIDUAL_OUT = ("o_proj", "w3")
_MLA = {"wq": ("q_proj", "kernel"), "wkva": ("kv_a", "kernel"),
        "kv_norm": ("kv_a_norm", "scale"), "wkvb": ("kv_b", "kernel"),
        "wo": ("o_proj", "kernel")}


def fill_leaves(abstract_tree, key, dtype=jnp.float32):
    """Call it inside a jitted function."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        names = [str(getattr(p, "key", p)) for p in path]
        if names[-1] == "scale":
            x = jnp.ones(leaf.shape, jnp.float32)
        else:
            std = RESIDUAL_OUT_STD if set(names) & set(RESIDUAL_OUT) else STD
            x = std * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, jnp.float32)
        out.append(x.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def fill(abstract_tree, seed: int, dtype=jnp.float32):
    return jax.jit(lambda key: fill_leaves(abstract_tree, key, dtype))(
        weights.seed_key(seed, weights.FILL_STREAM))


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _swiglu(t):
    return {"w12": t["w12"]["kernel"], "w3": t["w3"]["kernel"]}


def reference_tree(backbone) -> dict:
    """A tree shaped like the program's ``params["student"]["backbone"]``
    (weights, a gradient, per-leaf norms) in the reference's layout."""
    layers = []
    for i in range(sum(1 for k in backbone if k.startswith("layers_"))):
        lw = backbone[f"layers_{i}"]
        if "mlp" in lw:
            ffn = _swiglu(lw["mlp"])
        else:
            ffn = {**{k: lw["experts"][k]
                      for k in ("router", "router_bias", "w12", "w3")},
                   "shared": _swiglu(lw["shared"])}
        layers.append({"norm1": lw["norm1"]["scale"], "norm2": lw["norm2"]["scale"],
                       "mixer": {k: _get(lw["mla"], p) for k, p in _MLA.items()},
                       "ffn": ffn})
    return {"embed": backbone["token_embed"], "head": backbone["lm_head"],
            "norm": backbone["norm"]["scale"], "layers": layers}
