"""Step program (decoder): device time a step under the dense SwiGLU of layer 1 and the routed + shared experts of layers 2-5, forward and backward. Read from
the device trace by lm_phase_table.py (lm_phases.json); None where the
trace carries no such phase. Moves train_img_per_s_chip."""

import lm_phase_table


def read(run):
    return lm_phase_table.metric(run, "lm_ffn_ms_per_step")
