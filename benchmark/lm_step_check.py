"""The comparison that decides ``correct`` for the decoder's next-token
step.

The timed step's first steps (the compiled step and state the window
then drives) are laid against ``reference/kimi_linear_fp32.py`` on the
same seed-made weights, the same tokens and the program's own expert
choices. Each number has a limit of its own in the configuration's file
(``check``, with the readings it was set from):

- ``loss_rel_gap``: each step's loss, largest relative gap.
- ``grad_diff_gap_<group>``: the first gradient as the optimizer gets it
  (the program's from its first moment after one step), leaf by leaf: the
  norm of the DIFFERENCE of the two leaves against the reference leaf's
  norm (or a hundredth of the median leaf's norm, whichever is larger: a
  leaf whose gradient is all but zero has no direction to compare). Two
  gradients of one norm can point anywhere; this number is what a lower
  precision or a part left out moves. The worst leaf of each GROUP has a
  limit of its own (``GROUPS``: the KDA and MLA mixers with their
  pre-norms, the FFNs with theirs, head and embedding with the final
  norm, the routers): a router's gradient carries the noise of top-k ties
  (0.06-0.11 in sound runs), three times what a mixer's leaf reads, and
  one limit over all leaves would let a mixer computed in a lower
  precision hide under it.
- ``param_change_gap``: each leaf's change after the steps, the gap
  between the two norms against the reference's norm of that leaf or of
  the median leaf. AdamW's first steps are all but sign(g) * lr: where a
  gradient's element is near zero its sign is rounding's, so the
  difference of two changes measures nothing. There for a step that
  returns its state, a wrong rate or a decay on the wrong leaves.
- ``router_agreement_share``: the share of the program's expert choices
  that the reference's own router makes too, least of the steps (a lower
  limit). The reference FOLLOWS the program's choices; this says how far
  apart the two routers are.
"""

from __future__ import annotations

import numpy as np

from output_check import check
from step_check import _flat, _rel, leaf_gaps, worst_leaf_gap

GROUPS = ("mixers", "ffn", "head_embed", "router")
UPPER = ("loss_rel_gap", *(f"grad_diff_gap_{g}" for g in GROUPS),
         "param_change_gap")
LOWER = ("router_agreement_share",)


def leaf_paths(tree) -> list:
    import jax

    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def group_of(path: str) -> str:
    """The group of a leaf of the reference's layout, from its path
    (``layers/3/mixer/wq``, ``layers/1/ffn/router``, ``head``)."""
    parts = path.split("/")
    if parts[0] != "layers":
        return "head_embed"
    if parts[2] == "mixer" or parts[2] == "norm1":
        return "mixers"
    return "router" if parts[-1] in ("router", "router_bias") else "ffn"


def diff_gaps(diff_norms, want_norms) -> np.ndarray:
    """||got - want|| / max(||want||, median ||want|| / 100), per leaf."""
    diff, want = _flat(diff_norms), _flat(want_norms)
    if not np.isfinite(diff).all():
        return np.full(diff.shape, np.inf)
    return diff / np.maximum(want, np.median(want) / 100.0)


def gaps(program: dict, reference: dict) -> dict:
    """``program``: {"losses": [per step], "change_norms": tree};
    ``reference``: what ``kimi_linear_fp32.first_steps`` returns, plus
    "grad_diff_norms": per leaf, ||program's first gradient - its own||."""
    grad = diff_gaps(reference["grad_diff_norms"], reference["grad_norms"])
    groups = np.array([group_of(p) for p in leaf_paths(reference["grad_norms"])])
    return {
        "loss_rel_gap": max(_rel(p, r) for p, r in
                            zip(program["losses"], reference["losses"])),
        **{f"grad_diff_gap_{g}": float(np.max(grad[groups == g], initial=0.0))
           for g in GROUPS},
        "param_change_gap": worst_leaf_gap(program["change_norms"],
                                           reference["change_norms"]),
        "router_agreement_share": float(reference["router_agreement"]),
    }


def worst_leaves(program: dict, reference: dict) -> list:
    """``[(label, grad_diff gap, change gap), ...]`` for the run's log: each
    group's worst leaf by its gradient's gap and the worst by its change,
    each with the two gradients' norms (the other side's against the
    reference's), then all leaves as one."""
    paths = leaf_paths(reference["grad_norms"])
    groups = np.array([group_of(p) for p in paths])
    grad = diff_gaps(reference["grad_diff_norms"], reference["grad_norms"])
    change = leaf_gaps(program["change_norms"], reference["change_norms"])
    want, got = _flat(reference["grad_norms"]), _flat(reference["other_grad_norms"])
    picked = [int(np.argmax(np.where(groups == g, grad, -1.0))) for g in GROUPS]
    picked.append(int(np.argmax(change)))
    rows = [(f"{paths[i]} (|g| {got[i]:.3g} against {want[i]:.3g})",
             float(grad[i]), float(change[i])) for i in dict.fromkeys(picked)]
    total = (f"all leaves (|g| {np.sqrt(np.sum(got ** 2)):.4g} against "
             f"{np.sqrt(np.sum(want ** 2)):.4g})")
    return [*rows, (total, float(np.max(grad)), float(np.max(change)))]


def checks_from_gaps(g: dict, limits: dict) -> list:
    return ([check(f"step_{k}", g[k], limits[k], g[k] <= limits[k]) for k in UPPER]
            + [check(f"step_{k}", g[k], limits[k], g[k] >= limits[k]) for k in LOWER])
