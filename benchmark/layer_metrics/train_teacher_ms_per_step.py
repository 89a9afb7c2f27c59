"""Step program: device time a step under the scope teacher_backbone (the
frozen teacher's backbone forward over the global crops). Read from the
device trace by phase_reduce.py; None where the trace carries no phase.
Moves train_img_per_s_chip."""

import phase_reduce


def read(run):
    return phase_reduce.metric(run, "train_teacher_ms_per_step")
