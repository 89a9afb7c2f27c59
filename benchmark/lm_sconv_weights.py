"""Weights of the ``lfm2_moe`` decoder from ``--seed``, and the renaming
of the program's tree into the layout of ``reference/lfm2_moe_fp32.py``.

``fill_leaves`` makes a tree shaped like the program's own (only names
and shapes are taken from the program): the norm scales 1; every matrix
N(0, 0.02), the token embedding among them (it is the head too: logits of
unit scale); the projections that write into the residual stream (a conv
mixer's ``out_proj``, the attention's ``o_proj``, the dense FFN's and the
experts' ``w3``) N(0, 0.02 / sqrt(2 x 40)): the scaled initialisation of
a residual output at the PUBLISHED depth (40 layers, two writes a layer);
the convolution's taps uniform on +-1/sqrt(3), a depthwise ``Conv1d``'s
default; the router's selection bias N(0, 0.005) — NON-zero, so that a
selection without it differs (one token in seven chooses another set of
experts), and fixed: it takes no gradient and no decay. The
configuration's file lists all of it under ``assumed``, with why the bias
is not the N(0, 0.1) ISSUE 41 asked for: the bias is drawn from
``--seed``, and at 0.1 the rows this shard's 8 experts draw at the FIRST
step read 1.03 +- 0.36 of their even share a layer (this program at the
published widths, 40 seeds): 3 seeds of 40 have a layer past the row
capacity of 2.0 at step 0 (their run is not ``correct``), and the step's
time follows the rows held, so the rate differs by seed by several times
the benchmark's bound on it (PERF.md section 6, PR 41).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import weights

STD = 0.02
BIAS_STD = 0.005
PUBLISHED_LAYERS = 40
RESIDUAL_OUT_STD = STD / math.sqrt(2 * PUBLISHED_LAYERS)
RESIDUAL_OUT = ("out_proj", "o_proj", "w3")
_CONV = {"win": ("in_proj", "kernel"), "conv": ("conv",),
         "wout": ("out_proj", "kernel")}
_ATTN = {"wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"),
         "wv": ("v_proj", "kernel"), "q_norm": ("q_norm", "scale"),
         "k_norm": ("k_norm", "scale"), "wo": ("o_proj", "kernel")}


def fill_leaves(abstract_tree, key, dtype=jnp.float32):
    """Call it inside a jitted function."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        names = [str(getattr(p, "key", p)) for p in path]
        k = jax.random.fold_in(key, i)
        if names[-1] == "scale":
            x = jnp.ones(leaf.shape, jnp.float32)
        elif names[-1] == "conv":
            bound = leaf.shape[0] ** -0.5
            x = jax.random.uniform(k, leaf.shape, jnp.float32, -bound, bound)
        else:
            std = (BIAS_STD if names[-1] == "router_bias" else
                   RESIDUAL_OUT_STD if set(names) & set(RESIDUAL_OUT) else STD)
            x = std * jax.random.normal(k, leaf.shape, jnp.float32)
        out.append(x.astype(dtype))
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
    (weights, a gradient, per-leaf norms) in the reference's layout: ONE
    ``embed`` leaf, embedding and head."""
    layers = []
    for i in range(sum(1 for k in backbone if k.startswith("layers_"))):
        lw = backbone[f"layers_{i}"]
        names, mixer = (_CONV, lw["conv"]) if "conv" in lw else (_ATTN, lw["attn"])
        if "mlp" in lw:
            ffn = {"w12": lw["mlp"]["w12"]["kernel"], "w3": lw["mlp"]["w3"]["kernel"]}
        else:
            ffn = {k: lw["experts"][k]
                   for k in ("router", "router_bias", "w12", "w3")}
        layers.append({"norm1": lw["norm1"]["scale"], "norm2": lw["norm2"]["scale"],
                       "mixer": {k: _get(mixer, p) for k, p in names.items()},
                       "ffn": ffn})
    return {"embed": backbone["token_embed"], "norm": backbone["norm"]["scale"],
            "layers": layers}
