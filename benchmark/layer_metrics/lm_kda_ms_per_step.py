"""Step program (decoder): device time a step under a KDA layer's mixer (projections, short convolutions, the delta rule, gate and output projection), all four layers, forward and backward. Read from
the device trace by lm_phase_table.py (lm_phases.json); None where the
trace carries no such phase. Moves train_img_per_s_chip."""

import lm_phase_table


def read(run):
    return lm_phase_table.metric(run, "lm_kda_ms_per_step")
