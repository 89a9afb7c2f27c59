"""Step program (deepseek_v3 decoder): device time a step under the scope mla_rope inside mla_mixer (the two rotary turns alone: the last 64 channels of every query head and the ONE shared key head, neighbouring channels a pair, float32), forward and backward. Read from
the device trace by lm_mla_phase_table.py (lm_mla_phases.json); None where
the trace carries no such scope (a latent layer that turns nothing, as
kimi_linear's). Moves train_img_per_s_chip."""

import lm_mla_phase_table


def read(run):
    # (no operation under the scope sums to 0: nothing to read)
    return lm_mla_phase_table.metric(run, "lm_mla_rope_ms_per_step") or None
