"""Driver ``lm_mla_train_steps``: ``lm_train_steps`` for the
``deepseek_v3`` family.

The loop, its spans and counters, the state made from ``--seed``, the
first steps the check reads and the rules of the check's numbers are
``lm_train_steps``'s, line for line: this file runs a copy of that module
of its own in which the names that say WHICH decoder is checked stand for
this family's files — the reference (``reference/kanana2_fp32.py`` where
it says ``kimi_linear_fp32``: the same ``Recipe`` / ``Shape`` /
``first_steps`` surface), the renaming of the program's leaves into the
reference's layout (``lm_mla_weights.py`` where it says ``lm_weights``:
its own fill) and the check (``lm_mla_step_check.py`` where it says
``lm_step_check``: the leaves the rotary turn acts on a group of their
own) — as ``lm_gqa_train_steps.py``, ``lm_gdn_train_steps.py``,
``lm_dsa_train_steps.py`` and ``lm_sconv_train_steps.py`` do for their
families.

One sequence counts as one image: ``train_img_per_s_chip`` x 16,384 =
tokens/s/chip.

End-to-end metrics computed here: ``setup_s``, ``train_img_per_s_chip``.
"""

from __future__ import annotations

import lm_mla_step_check
import lm_mla_weights
from reference import kanana2_fp32
from run import DRIVER_DIR, load_module

_base = load_module(DRIVER_DIR, "lm_train_steps")  # this module's own copy
_base.kimi_linear_fp32 = kanana2_fp32
_base.lm_weights = lm_mla_weights
_base.lm_step_check = lm_mla_step_check

train_steps = _base.train_steps
Rig = _base.Rig
run = _base.run
