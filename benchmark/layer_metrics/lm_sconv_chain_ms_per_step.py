"""Kernels (lfm2_moe decoder): device time a step under the scope sconv_chain of the four conv mixers (ops/mixer_chains.py gated_short_conv alone: the kernel pair gated_short_conv_fwd / gated_short_conv_bwd on a TPU, the plain chain elsewhere), forward and backward. Read from
the device trace by lm_sconv_phase_table.py (lm_sconv_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_sconv_phase_table


def read(run):
    return lm_sconv_phase_table.metric(run, "lm_sconv_chain_ms_per_step")
