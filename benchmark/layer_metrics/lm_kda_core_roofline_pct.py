"""Kernels (decoder): the delta rule's share of its roofline. The least
time the chip could take for the KDA layers' kda_core a step, forward and
backward — the larger of its operations over the bf16 peak and its HBM
bytes over the HBM rate (lm_flops.kda_core_train, peaks.json) — over the
device time measured under the scope (lm_kda_core_ms_per_step). Moves
train_img_per_s_chip."""

import lm_flops
import lm_phase_table


def read(run):
    ms = lm_phase_table.metric(run, "lm_kda_core_ms_per_step")
    shape = run.config.get("flops")
    batch = run.counters.get("train_batch")
    if not ms or shape is None or not batch:
        return None
    layers = sum(1 for mixer, _ in shape["layers"] if mixer == "kda")
    d = shape["kda_head_dim"]
    ops, nbytes = lm_flops.kda_core_train(  # one chip's tokens
        batch * shape["seq_len"] // run.chips, shape["kda_num_heads"], d, d,
        shape["kda_chunk"])
    least_s = layers * max(ops / run.peaks["bf16_flops_per_s"],
                           nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
