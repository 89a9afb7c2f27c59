"""Kernels (deepseek_v3 decoder): device time a step under the scope mla_core inside mla_mixer (the repeat of the ONE shared key head over the 32 heads, the pad of q and k from 192 to 256 lanes and ops/attention.py causal_blockwise_attention: the causal kernel pair at 32 heads of 192 | 128, or the plain tiles), forward and backward. Read from
the device trace by lm_mla_phase_table.py (lm_mla_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_mla_phase_table


def read(run):
    # (no operation under the scope sums to 0: nothing to read)
    return lm_mla_phase_table.metric(run, "lm_mla_core_ms_per_step") or None
