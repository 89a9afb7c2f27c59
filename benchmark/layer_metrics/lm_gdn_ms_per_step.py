"""Step program (qwen3_next decoder): device time a step under the three Gated DeltaNet layers' mixers (gdn_mixer: pre-norm, the qkvz and ba projections, the short convolution, the delta rule, the gated output norm, output projection, residual add), forward and backward. Read from
the device trace by lm_gdn_phase_table.py (lm_gdn_phases.json); None where
the trace carries no such phase. Moves train_img_per_s_chip."""

import lm_gdn_phase_table


def read(run):
    return lm_gdn_phase_table.metric(run, "lm_gdn_ms_per_step")
