"""Kernels (qwen3_next decoder): the scalar-gate delta rule's share of its
roofline. The least time the chip could take for the Gated DeltaNet
layers' gdn_core a step, forward and backward — the larger of its
operations over the bf16 peak and its HBM bytes over the HBM rate
(lm_gdn_flops.gdn_core_train: what ONE decay a head requires, not what the
per-channel kernels execute; peaks.json) — over the device time measured
under the scope (lm_gdn_core_ms_per_step). Moves train_img_per_s_chip."""

import lm_gdn_flops
import lm_gdn_phase_table


def read(run):
    ms = lm_gdn_phase_table.metric(run, "lm_gdn_core_ms_per_step")
    shape = run.config.get("flops")
    batch = run.counters.get("train_batch")
    if not ms or shape is None or not batch or "linear_num_key_heads" not in shape:
        return None
    layers = sum(1 for mixer, _ in shape["layers"] if mixer == "gdn")
    ops, nbytes = lm_gdn_flops.gdn_core_train(  # one chip's tokens
        batch * shape["seq_len"] // run.chips, shape["linear_num_key_heads"],
        shape["linear_num_value_heads"], shape["linear_key_head_dim"],
        shape["linear_value_head_dim"], shape["gdn_chunk"])
    least_s = layers * max(ops / run.peaks["bf16_flops_per_s"],
                           nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
