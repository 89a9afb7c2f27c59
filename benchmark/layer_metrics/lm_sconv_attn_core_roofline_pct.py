"""Kernels (lfm2_moe decoder): the 64-wide attention core's share of its
roofline. The least time the chip could take for the layer's gqa_core a
step, forward and backward — every causal pair of 32 heads, scores and
values 64 wide, x 3 (lm_sconv_flops.attn_core_train_ops), over the bf16
peak (peaks.json) — over the device time measured under the scope gqa_core
inside full_attn_mixer. A 128 x 128 MXU fed 64-deep contractions (the
scores) and 64-wide results (the values) runs at half its rate at most, so
this share cannot pass 50 %; the layer's remat runs the forward kernel a
second time, which the operations do not count. Moves
train_img_per_s_chip."""

import lm_sconv_flops
import lm_sconv_phase_table


def read(run):
    ms = lm_sconv_phase_table.metric(run, "lm_sconv_attn_core_ms_per_step")
    shape = run.config.get("flops")
    batch = run.counters.get("train_batch")
    if not ms or shape is None or not batch or "conv_L_cache" not in shape:
        return None
    layers = sum(1 for mixer, _ in shape["layers"] if mixer == "full_attn")
    heads = shape["num_attention_heads"]
    ops = lm_sconv_flops.attn_core_train_ops(
        shape["seq_len"], heads, shape["hidden_size"] // heads)
    least_s = layers * ops / run.peaks["bf16_flops_per_s"]
    # one chip's sequences
    return 100.0 * least_s * (batch // run.chips) / (ms * 1e-3)
