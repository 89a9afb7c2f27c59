"""The comparison that decides ``correct`` for the ``lfm2_moe`` decoder's
step: ``lm_step_check.py``'s seven numbers and rules (that file says what
each is), laid against ``reference/lfm2_moe_fp32.py`` on the same
seed-made weights, the same tokens and the program's own expert choices,
with the leaf groups this family needs:

- ``grad_diff_gap_conv``: the gated short convolutions' leaves
  (``win``, the taps ``conv``, ``wout``, with their pre-norm) are a group
  of their own: a wrong taps gradient must not hide among attention's
  leaves, whose worst reads higher.
- ``grad_diff_gap_mixers``: the attention layer's leaves with its
  pre-norm.
- ``grad_diff_gap_head_embed``: the ONE tied leaf (embedding and head at
  once: its gradient is the sum of both uses) and the final norm.
"""

from __future__ import annotations

import numpy as np

import lm_step_check as base
from output_check import check
from step_check import _flat, _rel, leaf_gaps, worst_leaf_gap

CONV = ("win", "conv", "wout")
GROUPS = ("conv", "mixers", "ffn", "head_embed", "router")
UPPER = ("loss_rel_gap", *(f"grad_diff_gap_{g}" for g in GROUPS),
         "param_change_gap")
LOWER = base.LOWER

leaf_paths = base.leaf_paths
diff_gaps = base.diff_gaps


def conv_layers(paths) -> set:
    """The layers (``layers/<i>``) whose mixer is a convolution."""
    return {p.split("/")[1] for p in paths
            if p.startswith("layers/") and p.split("/")[2:] == ["mixer", "conv"]}


def group_of(path: str, conv: set) -> str:
    parts = path.split("/")
    if parts[0] == "layers" and parts[1] in conv \
            and parts[2] in ("mixer", "norm1"):
        return "conv"
    return base.group_of(path)


def _groups(paths) -> np.ndarray:
    conv = conv_layers(paths)
    return np.array([group_of(p, conv) for p in paths])


def gaps(program: dict, reference: dict) -> dict:
    """``program``: {"losses": [per step], "change_norms": tree};
    ``reference``: what ``lfm2_moe_fp32.first_steps`` returns, plus
    "grad_diff_norms"."""
    grad = diff_gaps(reference["grad_diff_norms"], reference["grad_norms"])
    groups = _groups(leaf_paths(reference["grad_norms"]))
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
    """``lm_step_check.worst_leaves`` over this file's groups."""
    paths = leaf_paths(reference["grad_norms"])
    groups = _groups(paths)
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
