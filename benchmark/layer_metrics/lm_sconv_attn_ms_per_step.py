"""Step program (lfm2_moe decoder): device time a step under the attention layer's mixer (full_attn_mixer: pre-norm, the q, k and v projections, the q and k head norms, the rotary turn of the whole 64-wide head, the causal core, output projection, residual add), forward and backward. Read from
the device trace by lm_sconv_phase_table.py (lm_sconv_phases.json); None where
the trace carries no such phase. Moves train_img_per_s_chip."""

import lm_sconv_phase_table


def read(run):
    return lm_sconv_phase_table.metric(run, "lm_sconv_attn_ms_per_step")
