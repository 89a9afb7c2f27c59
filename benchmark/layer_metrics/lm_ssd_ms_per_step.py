"""Step program (nemotron_h decoder): device time a step under the phase ssm_mixer (a Mamba-2 block from its pre-norm to its residual add: in_proj, the chains, the scan, out_proj), forward and backward, the four blocks together. Read from
the device trace by lm_ssd_phase_table.py (lm_ssd_phases.json); None where
the trace carries no such phase. Moves train_img_per_s_chip."""

import lm_ssd_phase_table


def read(run):
    # (no operation under the phase sums to 0: nothing to read)
    return lm_ssd_phase_table.metric(run, "lm_ssd_ms_per_step") or None
