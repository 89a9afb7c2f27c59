"""Step program (keye_vl2 decoder): device time a step under the five sparse-attention layers' mixers (dsa_mixer: pre-norm, the q, k, v and indexer projections, head norms, rotary, the index score planes, the selection, the attention core under it, the index loss, output projection, residual add), forward and backward. Read from
the device trace by lm_dsa_phase_table.py (lm_dsa_phases.json); None where
the trace carries no such phase. Moves train_img_per_s_chip."""

import lm_dsa_phase_table


def read(run):
    return lm_dsa_phase_table.metric(run, "lm_dsa_ms_per_step")
