"""Kernels (nemotron_h decoder): device time a step under the scope ssd_core inside ssm_mixer (ops/ssd.py ssd_chunked and nothing else: the kernel pair ssd_chunk_fwd / ssd_chunk_bwd with the running sum of dt a and the two layouts of it that XLA makes beside them, or the plain scan), forward and backward. Read from
the device trace by lm_ssd_phase_table.py (lm_ssd_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_ssd_phase_table


def read(run):
    # (no operation under the scope sums to 0: nothing to read)
    return lm_ssd_phase_table.metric(run, "lm_ssd_core_ms_per_step") or None
