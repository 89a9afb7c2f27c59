"""Step program (decoder): device time a step under the routed experts alone (scope moe_experts inside moe_ffn: row gather, two grouped matmuls, scatter-add), forward and backward. Read from
the device trace by lm_phase_table.py (lm_phases.json); None where the
trace carries no such phase. Moves train_img_per_s_chip."""

import lm_phase_table


def read(run):
    return lm_phase_table.metric(run, "lm_moe_experts_ms_per_step")
