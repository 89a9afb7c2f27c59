"""The comparison that decides ``correct`` for the ``deepseek_v3``
decoder's step: ``lm_step_check.py``'s seven numbers and rules (that file
says what each is), laid against ``reference/kanana2_fp32.py`` on the
same seed-made weights, the same tokens and the program's own expert
choices, with one more leaf group:

- ``grad_diff_gap_turned``: the two leaves of every layer whose outputs
  the rotary turn acts on (``wq``: the query projection, ``wkva``: the
  latent and the ONE shared key) are a group of their own, so that a turn
  left out or laid on the wrong channel pairs reads in a number by itself
  and the worst-leaf listing names one of them.
- ``grad_diff_gap_mixers``: the mixers' other leaves (``kv_norm``,
  ``wkvb``, ``wo``) with their pre-norm.

``lm_step_check`` names its groups in module globals; this file runs a
copy of that module of its own with this family's groups in them (as
``lm_mla_phase_table.py`` does with its vocabulary), so ``gaps``,
``worst_leaves`` and ``checks_from_gaps`` are that file's, line for line.
"""

from __future__ import annotations

import os

import lm_step_check as base
from run import load_module

TURNED = ("wq", "wkva")
GROUPS = ("turned", "mixers", "ffn", "head_embed", "router")
UPPER = ("loss_rel_gap", *(f"grad_diff_gap_{g}" for g in GROUPS),
         "param_change_gap")
LOWER = base.LOWER


def group_of(path: str) -> str:
    parts = path.split("/")
    if parts[0] == "layers" and parts[2] == "mixer" and parts[-1] in TURNED:
        return "turned"
    return base.group_of(path)


_own = load_module(os.path.dirname(os.path.abspath(__file__)), "lm_step_check")
_own.GROUPS, _own.UPPER, _own.group_of = GROUPS, UPPER, group_of

leaf_paths = _own.leaf_paths
gaps = _own.gaps
worst_leaves = _own.worst_leaves
checks_from_gaps = _own.checks_from_gaps
