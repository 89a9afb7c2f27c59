"""Step program (lfm2_moe decoder): device time a step under the four gated short convolution layers' mixers (sconv_mixer: pre-norm, in_proj, the chain y = C * conv3(B * u), out_proj, residual add), forward and backward. Read from
the device trace by lm_sconv_phase_table.py (lm_sconv_phases.json); None where
the trace carries no such phase. Moves train_img_per_s_chip."""

import lm_sconv_phase_table


def read(run):
    return lm_sconv_phase_table.metric(run, "lm_sconv_ms_per_step")
