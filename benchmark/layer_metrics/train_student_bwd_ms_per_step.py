"""Step program: device time a step under transpose(jvp(student_backbone)):
the student backbone's backward pass, recomputation under remat included.
Read from the device trace by phase_reduce.py; None where the trace
carries no phase. Moves train_img_per_s_chip."""

import phase_reduce


def read(run):
    return phase_reduce.metric(run, "train_student_bwd_ms_per_step")
