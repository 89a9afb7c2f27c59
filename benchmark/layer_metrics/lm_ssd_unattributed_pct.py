"""Step program (nemotron_h decoder): share of all device time of the traced steps
under no phase of lm_ssd_phases.json (a scope renamed in the program shows
here). None where the trace carries no such phase. Moves
train_img_per_s_chip."""

import lm_ssd_phase_table


def read(run):
    return lm_ssd_phase_table.metric(run, "lm_ssd_unattributed_pct")
