"""Step program (qwen3_next decoder): device time a step under the gated attention layer's mixer (gated_attn_mixer: pre-norm, the q-with-gate, k and v projections, the q and k norms, the rotary turn of a quarter of each head, the causal core, the output gate, output projection, residual add), forward and backward. Read from
the device trace by lm_gdn_phase_table.py (lm_gdn_phases.json); None where
the trace carries no such phase. Moves train_img_per_s_chip."""

import lm_gdn_phase_table


def read(run):
    return lm_gdn_phase_table.metric(run, "lm_gated_attn_ms_per_step")
