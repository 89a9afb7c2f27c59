"""Kernels (smallthinker decoder): device time a step under the scope gqa_core of all four mixers (the tiles of ops/attention.py causal_blockwise_attention alone: scores, masks, online softmax, values), forward and backward. Read from
the device trace by lm_gqa_phase_table.py (lm_gqa_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_gqa_phase_table


def read(run):
    return lm_gqa_phase_table.gqa_core_ms(run)
