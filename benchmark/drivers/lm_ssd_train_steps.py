"""Driver ``lm_ssd_train_steps``: ``lm_train_steps`` for the
``nemotron_h`` family.

The loop, its spans and counters, the state made from ``--seed``, the
first steps the check reads and the rules of the check's numbers are
``lm_train_steps``'s, line for line: this file runs a copy of that module
of its own in which the names that say WHICH decoder is checked stand for
this family's files — the reference (``reference/nemotron_h_fp32.py``
where it says ``kimi_linear_fp32``: the same ``Recipe`` / ``Shape`` /
``first_steps`` surface), the renaming of the program's leaves into the
reference's layout (``lm_ssd_weights.py`` where it says ``lm_weights``:
its own fill) and the check (``lm_ssd_step_check.py`` where it says
``lm_step_check``: the leaves only the recurrence reaches a group of
their own) — as ``lm_gqa_train_steps.py``, ``lm_gdn_train_steps.py``,
``lm_dsa_train_steps.py``, ``lm_sconv_train_steps.py`` and
``lm_mla_train_steps.py`` do for their families.

One counter more than that module's: ``lm_ssd_moe_rows_a_block``, the
rows routed to this shard's experts in the fullest routed block, mean
over the window's steps (the ring's ``moe_rows_fill`` times the buffer's
rows): what ``lm_ssd_experts_roofline_pct`` counts the experts' products
from. The copy's ``Rig`` is kept where ``run`` can read its rows after
the window; nothing of the loop changes.

One sequence counts as one image: ``train_img_per_s_chip`` x 8,192 =
tokens/s/chip.

End-to-end metrics computed here: ``setup_s``, ``train_img_per_s_chip``.
"""

from __future__ import annotations

import math

import lm_ssd_step_check
import lm_ssd_weights
from reference import nemotron_h_fp32
from run import DRIVER_DIR, load_module

_base = load_module(DRIVER_DIR, "lm_train_steps")  # this module's own copy
_base.kimi_linear_fp32 = nemotron_h_fp32
_base.lm_weights = lm_ssd_weights
_base.lm_step_check = lm_ssd_step_check

train_steps = _base.train_steps
_rigs = []


class Rig(_base.Rig):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        _rigs[:] = [self]   # the latest: ``run`` reads its rows after the window


_base.Rig = Rig


def run(ctx):
    result = _base.run(ctx)
    rig = _rigs[0]
    dc = rig.setup.meta.student_backbone.cfg
    from dinov3_tpu.ops.ffn import routed_rows_capacity

    cap = routed_rows_capacity(
        rig.batch // rig.chips * int(rig.cfg.lm.seq_len),
        dc.num_experts_per_token, dc.num_experts,
        dc.num_experts // dc.expert_shards, dc.expert_rows_factor)
    warm = int(rig.mix["warmup_steps"])
    window = rig.rows[warm:warm + result.attempted]
    fills = [r["moe_rows_fill"] for r in window
             if math.isfinite(r["moe_rows_fill"])]
    if fills:
        result.counters["lm_ssd_moe_rows_a_block"] = cap * sum(fills) / len(fills)
    return result
