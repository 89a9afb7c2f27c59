"""Step program (nemotron_h decoder): device time a step under the scope ssm_chain inside ssm_mixer (everything between a Mamba-2 block's two matmuls that is not the scan: the width-4 convolution with its bias and SiLU over the 6144 joined channels, softplus(dt + dt_bias), the D term, the gate and the grouped norm), forward and backward. Read from
the device trace by lm_ssd_phase_table.py (lm_ssd_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_ssd_phase_table


def read(run):
    # (no operation under the scope sums to 0: nothing to read)
    return lm_ssd_phase_table.metric(run, "lm_ssd_chain_ms_per_step") or None
