"""The comparison that decides ``correct`` for a training step.

The timed step's first steps (the compiled step and state the window then
drives) are laid against ``reference/ssl_step_fp32.py`` on the same
seed-made weights, the same batches and the same stochastic-depth draws.
Five numbers, each with a limit of its own in the configuration's file
(``check``; ``PERF.md`` repeats the readings they were set from):

- ``loss_rel_gap``: each step's total loss, largest relative gap. Hardly
  moved by a lower precision; there for a part of the batch left out.
- ``loss_terms_gap``: the widest gap of one of the loss's named terms,
  against the total loss (KoLeo passes through zero, so a gap relative to
  the term itself means nothing). There for a term computed wrongly whose
  weight in the total is small.
- ``grad_norm_gap``: the norm of the first gradient as the optimizer gets
  it (the program's from its first moment after one step), by the worst
  leaf: the gap between the two norms against the reference's norm of that
  leaf or of the median leaf, whichever is larger. The number a lower
  precision moves.
- ``param_change_gap``: the same for the norm of each leaf's change after
  the steps. There for a step that returns its state unchanged (1.0).
- ``teacher_change_gap``: the teacher's change as one norm, relative. There
  for an EMA left out.
"""

from __future__ import annotations

import numpy as np

from output_check import check

NUMBERS = ("loss_rel_gap", "loss_terms_gap", "grad_norm_gap",
           "param_change_gap", "teacher_change_gap")


def _flat(tree) -> np.ndarray:
    import jax

    return np.concatenate([np.ravel(np.asarray(x, np.float64))
                           for x in jax.tree.leaves(tree)])


def leaf_gaps(got, want) -> np.ndarray:
    """|got - want| / max(want, median of want), per leaf."""
    got, want = _flat(got), _flat(want)
    if got.shape != want.shape:
        raise ValueError(f"{got.shape} leaves against {want.shape}")
    if not np.isfinite(got).all():
        return np.full(got.shape, np.inf)
    return np.abs(got - want) / np.maximum(want, np.median(want))


def worst_leaf_gap(got, want) -> float:
    return float(np.max(leaf_gaps(got, want)))


def _rel(got: float, want: float) -> float:
    if not np.isfinite(got):
        return float("inf")
    return abs(got - want) / max(abs(want), 1e-30)


def gaps(program: dict, reference: dict) -> dict:
    """``program`` and ``reference``: {"losses": [per step {term: value}],
    "grad_norms", "change_norms": trees of one layout, "teacher_change"}."""
    pairs = list(zip(program["losses"], reference["losses"]))
    return {
        "loss_rel_gap": max(_rel(p["total_loss"], r["total_loss"]) for p, r in pairs),
        "loss_terms_gap": max(_rel(r["total_loss"] + p[k] - r[k], r["total_loss"])
                              for p, r in pairs for k in r if k != "total_loss"),
        "grad_norm_gap": worst_leaf_gap(program["grad_norms"], reference["grad_norms"]),
        "param_change_gap": worst_leaf_gap(program["change_norms"],
                                           reference["change_norms"]),
        "teacher_change_gap": _rel(program["teacher_change"],
                                   reference["teacher_change"]),
    }


def checks_from_gaps(g: dict, limits: dict) -> list:
    return [check(f"step_{k}", g[k], limits[k], g[k] <= limits[k]) for k in NUMBERS]
