"""Step program (smallthinker decoder): device time a step under the three window layers' mixers (swa_mixer: pre-norm, q/k/v projections, rotary turn, the banded core, output projection, residual add), forward and backward. Read from
the device trace by lm_gqa_phase_table.py (lm_gqa_phases.json); None where
the trace carries no such phase. Moves train_img_per_s_chip."""

import lm_gqa_phase_table


def read(run):
    return lm_gqa_phase_table.metric(run, "lm_swa_ms_per_step")
