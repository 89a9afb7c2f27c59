"""Kernels (lfm2_moe decoder): device time a step under the scope gqa_core inside full_attn_mixer (ops/attention.py causal_blockwise_attention alone: the causal kernel pair at 32 query heads on 8 key/value heads of 64 + 64, two key/value heads a lane group), forward and backward. Read from
the device trace by lm_sconv_phase_table.py (lm_sconv_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_sconv_phase_table


def read(run):
    return lm_sconv_phase_table.metric(run, "lm_sconv_attn_core_ms_per_step")
