"""Step program (nemotron_h decoder): device time a step under the phase full_attn_mixer (the attention block from its pre-norm to its residual add: four projections and the causal core at 32 | 2 heads of 128, no rotation), forward and backward. Read from
the device trace by lm_ssd_phase_table.py (lm_ssd_phases.json); None where
the trace carries no such phase. Moves train_img_per_s_chip."""

import lm_ssd_phase_table


def read(run):
    # (no operation under the phase sums to 0: nothing to read)
    return lm_ssd_phase_table.metric(run, "lm_ssd_attn_ms_per_step") or None
