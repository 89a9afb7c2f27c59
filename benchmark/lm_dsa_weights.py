"""Weights of the ``keye_vl2`` decoder from ``--seed``, and the renaming
of the program's tree into the layout of ``reference/keye_vl2_fp32.py``.

``fill_leaves`` makes a tree shaped like the program's own (only names
and shapes are taken from the program): the norm scales 1 and LayerNorm's
bias 0, every other leaf N(0, 0.02), except the token embedding, N(0, 1),
and the two projections that write into the residual stream (a mixer's
``o_proj``, the experts' ``w3``), which are N(0, 0.02 / sqrt(2 x 48)):
the scaled initialisation of a residual output, at the PUBLISHED depth (48
layers, two writes a layer). The configuration's file lists it under
``assumed``: every layer here is a softmax attention layer, and over
thousands of random tokens such a layer averages away what differs
between tokens and passes on what they share — with every write at
N(0, 0.02) the stream is one common vector after two layers and the
routers send every token to the same experts (``lm_gqa_weights.py``,
PERF.md section 6, PR 32, found it on the other all-attention family).
With these the routers spread, as ``lm_gdn_weights.py``'s do.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import weights

STD = 0.02
EMBED_STD = 1.0
PUBLISHED_LAYERS = 48
RESIDUAL_OUT_STD = STD / math.sqrt(2 * PUBLISHED_LAYERS)
_MIXER = {"wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"),
          "wv": ("v_proj", "kernel"), "wo": ("o_proj", "kernel"),
          "q_norm": ("q_norm", "scale"), "k_norm": ("k_norm", "scale"),
          "wiq": ("index_q_proj", "kernel"), "wik": ("index_k_proj", "kernel"),
          "ik_scale": ("index_k_norm", "scale"),
          "ik_bias": ("index_k_norm", "bias"),
          "wiw": ("index_w_proj", "kernel")}


def fill_leaves(abstract_tree, key, dtype=jnp.float32):
    """Call it inside a jitted function."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        names = [str(getattr(p, "key", p)) for p in path]
        if names[-1] == "scale":
            x = jnp.ones(leaf.shape, jnp.float32)
        elif names[-1] == "bias":
            x = jnp.zeros(leaf.shape, jnp.float32)
        else:
            std = (EMBED_STD if names[-1] == "token_embed" else
                   RESIDUAL_OUT_STD if "o_proj" in names or names[-1] == "w3"
                   else STD)
            x = std * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, jnp.float32)
        out.append(x.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def fill(abstract_tree, seed: int, dtype=jnp.float32):
    return jax.jit(lambda key: fill_leaves(abstract_tree, key, dtype))(
        weights.seed_key(seed, weights.FILL_STREAM))


def reference_tree(backbone) -> dict:
    """A tree shaped like the program's ``params["student"]["backbone"]``
    (weights, a gradient, per-leaf norms) in the reference's layout."""
    layers = []
    for i in range(sum(1 for k in backbone if k.startswith("layers_"))):
        lw = backbone[f"layers_{i}"]
        layers.append({
            "norm1": lw["norm1"]["scale"], "norm2": lw["norm2"]["scale"],
            "mixer": {k: lw["attn"][a][b] for k, (a, b) in _MIXER.items()},
            "ffn": {k: lw["experts"][k] for k in ("router", "w12", "w3")}})
    return {"embed": backbone["token_embed"], "head": backbone["lm_head"],
            "norm": backbone["norm"]["scale"], "layers": layers}
