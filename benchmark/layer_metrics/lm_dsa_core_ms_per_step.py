"""Kernels (keye_vl2 decoder): device time a step under the scope dsa_core inside dsa_mixer (ops/attention.py causal_blockwise_attention alone: the causal kernel pair under a per-query selection, 32 query heads on 4 key/value heads of 128, a masked pass over every causal tile), forward and backward. Read from
the device trace by lm_dsa_phase_table.py (lm_dsa_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_dsa_phase_table


def read(run):
    return lm_dsa_phase_table.metric(run, "lm_dsa_core_ms_per_step")
