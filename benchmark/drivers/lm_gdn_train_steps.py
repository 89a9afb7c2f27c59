"""Driver ``lm_gdn_train_steps``: ``lm_train_steps`` for the
``qwen3_next`` family.

The loop, its spans and counters, the state made from ``--seed``, the
first steps the check reads and the check's seven numbers
(``lm_step_check.py``) are ``lm_train_steps``'s, line for line: this file
runs a copy of that module of its own in which the two names that say
WHICH decoder is checked stand for this family's files — the reference
(``reference/qwen3_next_fp32.py`` where it says ``kimi_linear_fp32``: the
same ``Recipe`` / ``Shape`` / ``first_steps`` surface) and the renaming
of the program's leaves into the reference's layout
(``lm_gdn_weights.py`` where it says ``lm_weights``: its own fill, another
``reference_tree``), as ``lm_gqa_train_steps.py`` does for its family
(PERF.md section 7 asks a benchmark PR for the two hooks).

One sequence counts as one image: ``train_img_per_s_chip`` x 8,192 =
tokens/s/chip.

End-to-end metrics computed here: ``setup_s``, ``train_img_per_s_chip``.
"""

from __future__ import annotations

import lm_gdn_weights
from reference import qwen3_next_fp32
from run import DRIVER_DIR, load_module

_base = load_module(DRIVER_DIR, "lm_train_steps")  # this module's own copy
_base.kimi_linear_fp32 = qwen3_next_fp32
_base.lm_weights = lm_gdn_weights

train_steps = _base.train_steps
Rig = _base.Rig
run = _base.run
