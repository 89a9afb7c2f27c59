"""Kernels (nemotron_h decoder): device time a step under the scope gqa_core inside full_attn_mixer (ops/attention.py causal_blockwise_attention: the causal kernel pair with SIXTEEN query heads a key/value head, or the plain tiles), forward and backward. Read from
the device trace by lm_ssd_phase_table.py (lm_ssd_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_ssd_phase_table


def read(run):
    # (no operation under the scope sums to 0: nothing to read)
    return lm_ssd_phase_table.metric(run, "lm_ssd_attn_core_ms_per_step") or None
