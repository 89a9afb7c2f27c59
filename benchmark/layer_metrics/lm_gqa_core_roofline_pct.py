"""Kernels (smallthinker decoder): the attention core's share of its
roofline. The least time the chip could take for the four layers'
gqa_core a step, forward and backward — for each layer the larger of its
operations over the bf16 peak and its HBM bytes over the HBM rate
(lm_gqa_flops.gqa_core_train: the pairs inside the band, peaks.json) —
over the device time measured under the scope (lm_gqa_core_ms_per_step).
Moves train_img_per_s_chip."""

import lm_gqa_flops
import lm_gqa_phase_table


def read(run):
    ms = lm_gqa_phase_table.gqa_core_ms(run)
    shape = run.config.get("flops")
    batch = run.counters.get("train_batch")
    if not ms or shape is None or not batch or "sliding_window_size" not in shape:
        return None
    least_s = 0.0
    for mixer, _ in shape["layers"]:
        ops, nbytes = lm_gqa_flops.gqa_core_train(
            shape["seq_len"], lm_gqa_flops.window_of(shape, mixer),
            shape["num_attention_heads"], shape["num_key_value_heads"],
            shape["head_dim"])
        least_s += max(ops / run.peaks["bf16_flops_per_s"],
                       nbytes / run.peaks["hbm_bytes_per_s"])
    # one chip's sequences
    return 100.0 * least_s * (batch // run.chips) / (ms * 1e-3)
