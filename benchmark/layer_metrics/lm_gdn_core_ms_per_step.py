"""Kernels (qwen3_next decoder): device time a step under the scope gdn_core of the three Gated DeltaNet mixers (ops/kda.py kda_chunked alone, with the repeat of q and k over a key head's value heads and the broadcast of the one decay a head over the key channels), forward and backward. Read from
the device trace by lm_gdn_phase_table.py (lm_gdn_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_gdn_phase_table


def read(run):
    return lm_gdn_phase_table.metric(run, "lm_gdn_core_ms_per_step")
