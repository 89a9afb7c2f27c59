"""Weights of the ``smallthinker`` decoder from ``--seed``, and the
renaming of the program's tree into the layout of
``reference/smallthinker_fp32.py``.

``fill_leaves`` makes a tree shaped like the program's own (only names
and shapes are taken from the program): the norm scales 1, every other
leaf N(0, 0.02), except the two projections that write into the residual
stream (a mixer's ``o_proj``, the experts' ``w3``), which are
N(0, 0.02 / sqrt(2 x 52)): the scaled initialisation of a residual
output, at the PUBLISHED depth (52 layers, two writes a layer). The
configuration's file lists it under ``assumed`` and says what N(0, 0.02)
there does to this model: a softmax attention over thousands of random
tokens averages away what differs between tokens and passes on what they
share, so after two layers the stream is one common vector, and a router
that reads the stream un-normed sends every token to the same six
experts (PERF.md section 6, PR 32).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import weights

STD = 0.02
EMBED_STD = 1.0
PUBLISHED_LAYERS = 52
RESIDUAL_OUT_STD = STD / math.sqrt(2 * PUBLISHED_LAYERS)
_MIXER = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj"}


def fill_leaves(abstract_tree, key, dtype=jnp.float32):
    """Call it inside a jitted function."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        names = [str(getattr(p, "key", p)) for p in path]
        if names[-1] == "scale":
            x = jnp.ones(leaf.shape, jnp.float32)
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
            "mixer": {k: lw["attn"][p]["kernel"] for k, p in _MIXER.items()},
            "ffn": {k: lw["experts"][k] for k in ("router", "w12", "w3")}})
    return {"embed": backbone["token_embed"], "head": backbone["lm_head"],
            "norm": backbone["norm"]["scale"], "layers": layers}
