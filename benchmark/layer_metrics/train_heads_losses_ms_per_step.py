"""Step program: device time a step under teacher_targets + student_heads +
losses, forward and backward: both pairs of heads, centering and the DINO
/ iBOT / KoLeo / Gram losses over the [capacity, 65536] planes (ROADMAP
A4's quantity). Read from the device trace by phase_reduce.py; None where
the trace carries no phase. Moves train_img_per_s_chip."""

import phase_reduce


def read(run):
    return phase_reduce.metric(run, "train_heads_losses_ms_per_step")
