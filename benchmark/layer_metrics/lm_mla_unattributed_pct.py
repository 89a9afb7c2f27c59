"""Step program (deepseek_v3 decoder): share of all device time of the traced steps
under no phase of lm_mla_phases.json (a scope renamed in the program shows
here). None where the trace carries no such phase. Moves
train_img_per_s_chip."""

import lm_mla_phase_table


def read(run):
    return lm_mla_phase_table.metric(run, "lm_mla_unattributed_pct")
