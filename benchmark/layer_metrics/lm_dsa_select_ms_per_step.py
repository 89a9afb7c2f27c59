"""Step program (keye_vl2 decoder): device time a step under the scope dsa_select inside dsa_mixer (ops/sparse_index.py select_rows: the exact 2,048th-largest index score of every query by 32 counting passes over the ordered-integer image of its row, and the cut among tied scores where a row needs one; plain XLA, no kernel), forward and backward (a rematerialised layer selects again in its backward). Read from
the device trace by lm_dsa_phase_table.py (lm_dsa_phases.json); None where
the trace carries no such scope. Moves train_img_per_s_chip."""

import lm_dsa_phase_table


def read(run):
    return lm_dsa_phase_table.metric(run, "lm_dsa_select_ms_per_step")
