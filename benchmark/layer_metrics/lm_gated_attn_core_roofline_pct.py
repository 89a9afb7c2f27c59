"""Kernels (qwen3_next decoder): the gated attention core's share of its
roofline. The least time the chip could take for the layer's gqa_core a
step, forward and backward — the larger of its operations over the bf16
peak and its HBM bytes over the HBM rate
(lm_gqa_flops.gqa_core_train without a window: every causal pair of 16
heads on 2 of 256 + 256, peaks.json) — over the device time measured under the scope
gqa_core inside gated_attn_mixer. Moves train_img_per_s_chip."""

import lm_gdn_phase_table
import lm_gqa_flops


def read(run):
    ms = lm_gdn_phase_table.metric(run, "lm_gated_attn_core_ms_per_step")
    shape = run.config.get("flops")
    batch = run.counters.get("train_batch")
    if not ms or shape is None or not batch or "linear_num_key_heads" not in shape:
        return None
    layers = sum(1 for mixer, _ in shape["layers"] if mixer == "gated_attn")
    ops, nbytes = lm_gqa_flops.gqa_core_train(
        shape["seq_len"], None, shape["num_attention_heads"],
        shape["num_key_value_heads"], shape["head_dim"])
    least_s = layers * max(ops / run.peaks["bf16_flops_per_s"],
                           nbytes / run.peaks["hbm_bytes_per_s"])
    # one chip's sequences
    return 100.0 * least_s * (batch // run.chips) / (ms * 1e-3)
