"""Weights of the ``kimi_linear`` decoder from ``--seed``, and the
renaming of the program's tree into the layout of
``reference/kimi_linear_fp32.py``.

``fill_leaves`` makes a tree shaped like the program's own (only its
names and shapes are taken from the program): every leaf N(0, 0.02),
except the norm scales (1), ``A_log`` (log of a uniform draw on [1, 16))
and ``dt_bias`` (the inverse softplus of a log-uniform draw on
[1e-3, 1e-1]) — the released code's initial values, which the
configuration's file lists under ``assumed``: with N(0, 0.02) there the
decay of every channel would be exp(-softplus(0)) = 0.5 a token and the
state would hold four tokens. The router's selection bias is N(0, 0.02)
like a weight and stays at that value (it takes no gradient).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import weights

ONES = ("scale", "o_norm_scale")


def fill_leaves(abstract_tree, key, dtype=jnp.float32):
    """Call it inside a jitted function."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if name in ONES:
            x = jnp.ones(leaf.shape, jnp.float32)
        elif name == "A_log":
            x = jnp.log(jax.random.uniform(k, leaf.shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, leaf.shape, jnp.float32,
                                            jnp.log(1e-3), jnp.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        else:
            x = 0.02 * jax.random.normal(k, leaf.shape, jnp.float32)
        out.append(x.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def fill(abstract_tree, seed: int, dtype=jnp.float32):
    return jax.jit(lambda key: fill_leaves(abstract_tree, key, dtype))(
        weights.seed_key(seed, weights.FILL_STREAM))


_KDA = {"wq": ("q_proj", "kernel"), "wk": ("k_proj", "kernel"),
        "wv": ("v_proj", "kernel"), "cq": ("q_conv",), "ck": ("k_conv",),
        "cv": ("v_conv",), "wf1": ("f_a", "kernel"), "wf2": ("f_b", "kernel"),
        "A_log": ("A_log",), "dt_bias": ("dt_bias",), "wb": ("b_proj", "kernel"),
        "wg1": ("g_a", "kernel"), "wg2": ("g_b", "kernel"),
        "o_norm": ("o_norm_scale",), "wo": ("o_proj", "kernel")}
_MLA = {"wq": ("q_proj", "kernel"), "wkva": ("kv_a", "kernel"),
        "kv_norm": ("kv_a_norm", "scale"), "wkvb": ("kv_b", "kernel"),
        "wo": ("o_proj", "kernel")}


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
        names, mixer = (_KDA, lw["kda"]) if "kda" in lw else (_MLA, lw["mla"])
        if "mlp" in lw:
            ffn = _swiglu(lw["mlp"])
        else:
            ffn = {**{k: lw["experts"][k]
                      for k in ("router", "router_bias", "w12", "w3")},
                   "shared": _swiglu(lw["shared"])}
        layers.append({"norm1": lw["norm1"]["scale"], "norm2": lw["norm2"]["scale"],
                       "mixer": {k: _get(mixer, p) for k, p in names.items()},
                       "ffn": ffn})
    return {"embed": backbone["token_embed"], "head": backbone["lm_head"],
            "norm": backbone["norm"]["scale"], "layers": layers}
