"""Step program: share of all device time of the traced steps under no phase
of phases.json (a scope renamed in the program shows here). Read from the
device trace by phase_reduce.py; None where the trace carries no phase.
Moves train_img_per_s_chip."""

import phase_reduce


def read(run):
    return phase_reduce.metric(run, "train_unattributed_pct")
