"""Step program: device time a step under update: clip, AdamW and the
teacher's EMA, fused or optax, with the engine's collective scopes inside
it (ROADMAP A2's quantity). Read from the device trace by phase_reduce.py;
None where the trace carries no phase. Moves train_img_per_s_chip."""

import phase_reduce


def read(run):
    return phase_reduce.metric(run, "train_update_ms_per_step")
