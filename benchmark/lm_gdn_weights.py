"""Weights of the ``qwen3_next`` decoder from ``--seed``, and the
renaming of the program's tree into the layout of
``reference/qwen3_next_fp32.py`` (the program holds every matrix's
columns in the published grouping: a renaming and nothing else).

``fill_leaves`` makes a tree shaped like the program's own (only names
and shapes are taken from the program): every matrix N(0, 0.02); the
zero-centred norms' scales 0 (a scale of 1 + w); the delta rule's output
norm 1; ``A_log`` the log of a uniform draw on (0, 16) and ``dt_bias`` 1,
a value head each — the released modelling code's initial values, from
memory, which the configuration's file lists under ``assumed``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import weights

STD = 0.02
A_FLOOR = 1e-6  # no A_log is -inf


def fill_leaves(abstract_tree, key, dtype=jnp.float32):
    """Call it inside a jitted function."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if name == "scale":
            x = jnp.zeros(leaf.shape, jnp.float32)
        elif name in ("o_norm_scale", "dt_bias"):
            x = jnp.ones(leaf.shape, jnp.float32)
        elif name == "A_log":
            x = jnp.log(jax.random.uniform(k, leaf.shape, jnp.float32,
                                           A_FLOOR, 16.0))
        else:
            x = STD * jax.random.normal(k, leaf.shape, jnp.float32)
        out.append(x.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def fill(abstract_tree, seed: int, dtype=jnp.float32):
    return jax.jit(lambda key: fill_leaves(abstract_tree, key, dtype))(
        weights.seed_key(seed, weights.FILL_STREAM))


_GDN = {"wqkvz": ("in_proj_qkvz", "kernel"), "wba": ("in_proj_ba", "kernel"),
        "conv": ("conv",), "A_log": ("A_log",), "dt_bias": ("dt_bias",),
        "o_norm": ("o_norm_scale",), "wo": ("o_proj", "kernel")}
_ATTN = {"wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"),
         "wv": ("v_proj", "kernel"), "q_norm": ("q_norm", "scale"),
         "k_norm": ("k_norm", "scale"), "wo": ("o_proj", "kernel")}


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def reference_tree(backbone) -> dict:
    """A tree shaped like the program's ``params["student"]["backbone"]``
    (weights, a gradient, per-leaf norms) in the reference's layout."""
    layers = []
    for i in range(sum(1 for k in backbone if k.startswith("layers_"))):
        lw = backbone[f"layers_{i}"]
        names, mixer = (_GDN, lw["gdn"]) if "gdn" in lw else (_ATTN, lw["attn"])
        layers.append({
            "norm1": lw["norm1"]["scale"], "norm2": lw["norm2"]["scale"],
            "mixer": {k: _get(mixer, p) for k, p in names.items()},
            "ffn": {**{k: lw["experts"][k] for k in ("router", "w12", "w3")},
                    "shared": {k: lw["shared"][k]["kernel"] for k in ("w12", "w3")},
                    "shared_gate": lw["shared_gate"]["kernel"]}})
    return {"embed": backbone["token_embed"], "head": backbone["lm_head"],
            "norm": backbone["norm"]["scale"], "layers": layers}
