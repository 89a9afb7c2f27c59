"""Step program (decoder): device time a step under the delta rule alone (scope kda_core inside kda_mixer: ops/kda.py's chunked scan), forward and backward. Read from
the device trace by lm_phase_table.py (lm_phases.json); None where the
trace carries no such phase. Moves train_img_per_s_chip."""

import lm_phase_table


def read(run):
    return lm_phase_table.metric(run, "lm_kda_core_ms_per_step")
