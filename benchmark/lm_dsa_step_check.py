"""The comparison that decides ``correct`` for the ``keye_vl2`` decoder's
step: ``lm_step_check.py``'s seven numbers, leaf groups and rules (that
file says what each is), laid against ``reference/keye_vl2_fp32.py`` on
the same seed-made weights, the same tokens, the program's own expert
choices AND the program's own selection of keys, plus what a layer that
selects its keys adds:

- ``grad_diff_gap_indexer``: the indexer's leaves (``wiq``, ``wik``, the
  LayerNorm's scale and bias, ``wiw``) are a group of their own: their
  gradient comes from another loss (the index loss) and must not hide
  among the mixers'.
- ``index_loss_rel_gap``: each step's sum over layers of the index loss
  (the program's ring column ``lm_index_loss``), largest relative gap.
- ``index_agreement_share``: of the pairs the program selected in rows
  t >= topk, the share the reference's own float32 indexer selects too,
  least of the steps (a LOWER limit). The reference FOLLOWS the program's
  selection; this says how far apart the two indexers are.
- ``dsa_select_excess``: the queries whose selected count was not
  min(t + 1, topk), summed over every step of the run: 0, exactly.
"""

from __future__ import annotations

import numpy as np

import lm_step_check as base
from output_check import check
from step_check import _flat, _rel, leaf_gaps, worst_leaf_gap

INDEXER = ("wiq", "wik", "ik_scale", "ik_bias", "wiw")
GROUPS = ("mixers", "indexer", "ffn", "head_embed", "router")
UPPER = ("loss_rel_gap", *(f"grad_diff_gap_{g}" for g in GROUPS),
         "param_change_gap", "index_loss_rel_gap")
LOWER = ("router_agreement_share", "index_agreement_share")

leaf_paths = base.leaf_paths
diff_gaps = base.diff_gaps


def group_of(path: str) -> str:
    parts = path.split("/")
    if parts[0] == "layers" and parts[2] == "mixer" and parts[-1] in INDEXER:
        return "indexer"
    return base.group_of(path)


def gaps(program: dict, reference: dict) -> dict:
    """``program``: {"losses", "index_losses": [per step], "change_norms":
    tree, "rows": every ring row of the run so far (the list the rig
    appends to)}; ``reference``: what ``keye_vl2_fp32.first_steps``
    returns, plus "grad_diff_norms"."""
    grad = diff_gaps(reference["grad_diff_norms"], reference["grad_norms"])
    groups = np.array([group_of(p) for p in leaf_paths(reference["grad_norms"])])
    return {
        "loss_rel_gap": max(_rel(p, r) for p, r in
                            zip(program["losses"], reference["losses"])),
        **{f"grad_diff_gap_{g}": float(np.max(grad[groups == g], initial=0.0))
           for g in GROUPS},
        "param_change_gap": worst_leaf_gap(program["change_norms"],
                                           reference["change_norms"]),
        "index_loss_rel_gap": max(
            _rel(p, r) for p, r in zip(program["index_losses"],
                                       reference["index_losses"])),
        "router_agreement_share": float(reference["router_agreement"]),
        "index_agreement_share": float(reference["index_agreement"]),
        # (a control put in the program's place has no ring rows)
        "dsa_select_excess": int(sum(r["dsa_select_excess"]
                                     for r in program.get("rows", ()))),
    }


def worst_leaves(program: dict, reference: dict) -> list:
    """``lm_step_check.worst_leaves`` over this file's groups."""
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
            + [check(f"step_{k}", g[k], limits[k], g[k] >= limits[k]) for k in LOWER]
            + [check("dsa_select_excess", g["dsa_select_excess"], 0,
                     g["dsa_select_excess"] == 0)])
