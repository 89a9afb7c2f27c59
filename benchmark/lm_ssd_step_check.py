"""The comparison that decides ``correct`` for the ``nemotron_h``
decoder's step: ``lm_step_check.py``'s seven numbers and rules (that file
says what each is), laid against ``reference/nemotron_h_fp32.py`` on the
same seed-made weights, the same tokens and the program's own expert
choices, with the leaf groups this family needs — a block is ONE
sublayer, so its ``norm`` goes with whichever part it has:

- ``grad_diff_gap_scan``: the leaves only the recurrence and its
  convolution reach (``A_log``, ``dt_bias``, ``D``, the taps ``conv`` and
  ``conv_bias``) are a group of their own, so that a fault of the scan's
  decay, step or skip, or of the taps' gradient, reads in a number by
  itself and the worst-leaf listing names one of them.
- ``grad_diff_gap_mixers``: the Mamba-2 blocks' other leaves (``win``,
  ``gnorm``, ``wout``) and the attention block's, each with its pre-norm.
- ``grad_diff_gap_ffn``: the routed blocks' experts and shared MLP with
  their pre-norm; ``grad_diff_gap_router``: router and selection bias.

``lm_step_check`` names its groups in module globals; this file runs a
copy of that module of its own with this family's groups in them (as
``lm_mla_step_check.py`` does), so ``gaps``, ``worst_leaves`` and
``checks_from_gaps`` are that file's, line for line.
"""

from __future__ import annotations

import os

import lm_step_check as base
from run import load_module

SCAN = ("A_log", "dt_bias", "D", "conv", "conv_bias")
GROUPS = ("scan", "mixers", "ffn", "head_embed", "router")
UPPER = ("loss_rel_gap", *(f"grad_diff_gap_{g}" for g in GROUPS),
         "param_change_gap")
LOWER = base.LOWER


def group_of(path: str) -> str:
    """``layers/0/mixer/A_log``, ``layers/1/ffn/shared/w1``,
    ``layers/5/norm``, ``head``. The reference's ``Shape`` fixes which
    blocks are routed; here the path says it: a block's ``norm`` is told
    apart by ``ROUTED`` (set from the paths before the groups are
    read)."""
    parts = path.split("/")
    if parts[0] != "layers":
        return "head_embed"
    if parts[2] == "mixer":
        return "scan" if parts[-1] in SCAN else "mixers"
    if parts[2] == "norm":
        return "ffn" if parts[1] in ROUTED else "mixers"
    return "router" if parts[-1] in ("router", "router_bias") else "ffn"


ROUTED: set = set()


def leaf_paths(tree) -> list:
    """``lm_step_check.leaf_paths``, noting which blocks are routed."""
    paths = base.leaf_paths(tree)
    ROUTED.clear()
    ROUTED.update(p.split("/")[1] for p in paths
                  if p.startswith("layers/") and p.split("/")[2] == "ffn")
    return paths


_own = load_module(os.path.dirname(os.path.abspath(__file__)), "lm_step_check")
_own.GROUPS, _own.UPPER, _own.group_of = GROUPS, UPPER, group_of
_own.leaf_paths = leaf_paths

gaps = _own.gaps
worst_leaves = _own.worst_leaves
checks_from_gaps = _own.checks_from_gaps
