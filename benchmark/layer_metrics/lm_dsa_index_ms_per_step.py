"""Step program (keye_vl2 decoder): device time a step under the scopes dsa_index (the indexer's projections and its [queries, keys] score planes, by strips of 512 queries: once for the selection, once more where the selected plane is made from the thresholds) and dsa_index_loss (the loss's own score planes, the target from the main attention's q and k over the selected keys, the KL and its closed-form gradient) inside dsa_mixer, forward and backward. Read from
the device trace by lm_dsa_phase_table.py (lm_dsa_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_dsa_phase_table


def read(run):
    parts = [lm_dsa_phase_table.metric(run, name) for name in
             ("lm_dsa_index_scores_part", "lm_dsa_index_loss_part")]
    return None if any(p is None for p in parts) else sum(parts)
