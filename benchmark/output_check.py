"""The comparison that decides ``correct`` for a backbone's features
(the serving driver's; a training step's is ``step_check.py``).

The program's features (CLS and mean-pooled patch features, as the timed
path produced them) are laid against ``reference/vit_fp32.py`` on the
same weights (``weights.fill``, from ``--seed``) and the same images. The
number compared is, per feature kind, the largest relative L2 distance
over the images: ``||got - want|| / ||want||`` of each 1-D feature — steady
from seed to seed where a widest element-wise gap swings. Its limit and
the readings it was set from are in the configuration's file
(``check``); ``PERF.md`` repeats them.
"""

from __future__ import annotations

import numpy as np

import weights
from reference import vit_fp32


def abstract_backbone(model, images):
    """Names and shapes of the program's backbone tree (no arrays)."""
    import flax.linen as nn
    import jax

    tree = jax.eval_shape(lambda: model.init(jax.random.key(0), images[:1]))
    return nn.meta.unbox(tree["params"])


def reference_features(tree, image_batches: list, arch: dict,
                       precision: str = "fp32") -> list:
    """[(cls [B, D], pooled [B, D]) float32 numpy, ...], one per batch
    (each batch one resolution), block by block."""
    w = weights.reference_weights(tree)
    out = []
    for x in image_batches:
        cls, pooled = vit_fp32.features(
            w, x, patch=int(arch["patch_size"]), heads=int(arch["num_heads"]),
            rope_base=float(arch["rope_base"]), precision=precision)
        out.append((np.asarray(cls, np.float32), np.asarray(pooled, np.float32)))
    return out


def rel_l2(got, want) -> float:
    """Largest ||got - want|| / ||want|| over the rows of [B, D] arrays."""
    got = np.asarray(got, np.float32).reshape(-1, np.shape(want)[-1])
    want = np.asarray(want, np.float32).reshape(got.shape)
    if not np.isfinite(got).all():
        return float("inf")
    num = np.linalg.norm(got - want, axis=-1)
    den = np.maximum(np.linalg.norm(want, axis=-1), 1e-30)
    return float(np.max(num / den))


def gaps(got: list, want: list) -> dict:
    """{"cls_rel_l2", "pooled_rel_l2"} over lists of (cls, pooled)."""
    return {
        "cls_rel_l2": max(rel_l2(g[0], w[0]) for g, w in zip(got, want)),
        "pooled_rel_l2": max(rel_l2(g[1], w[1]) for g, w in zip(got, want)),
    }


def check(name: str, value, limit, ok) -> dict:
    """One number compared, as the harness prints it beside its limit."""
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


def checks_from_gaps(g: dict, limits: dict, prefix: str) -> list:
    return [check(f"{prefix}_{k}", v, limits[k], v <= limits[k])
            for k, v in g.items()]
