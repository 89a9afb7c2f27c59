"""Kernels (qwen3_next decoder): device time a step under the scope gqa_core inside gated_attn_mixer (ops/attention.py causal_blockwise_attention alone: the causal kernel pair at 16 query heads on 2 key/value heads of 256 + 256), forward and backward. Read from
the device trace by lm_gdn_phase_table.py (lm_gdn_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_gdn_phase_table


def read(run):
    return lm_gdn_phase_table.metric(run, "lm_gated_attn_core_ms_per_step")
