"""Kernels (nemotron_h decoder): the attention block's core's share of its
roofline. The least time the chip could take for gqa_core a step, forward
and backward — every causal pair of 32 query heads, scores 128 deep and
values 128 wide, x 3 (lm_ssd_flops.attn_core_train_ops), over the bf16
peak (peaks.json) — over the device time measured under the scope gqa_core
inside full_attn_mixer (lm_ssd_attn_core_ms_per_step). What the number
cannot pass: a rematerialised block runs the forward kernel a second time,
which the operations do not count (4 passes' work for 3 counted: 75 %),
and the kernels compute whole tiles on the diagonal where half the pairs
are counted. Moves train_img_per_s_chip."""

import lm_ssd_flops
import lm_ssd_phase_table


def read(run):
    ms = lm_ssd_phase_table.metric(run, "lm_ssd_attn_core_ms_per_step")
    shape = run.config.get("flops")
    batch = run.counters.get("train_batch")
    if not ms or shape is None or not batch or "ssm_state_size" not in shape:
        return None
    blocks = sum(1 for mixer, _ in shape["layers"] if mixer == "full_attn")
    ops = lm_ssd_flops.attn_core_train_ops(
        shape["seq_len"], shape["num_attention_heads"], shape["head_dim"])
    least_s = blocks * ops / run.peaks["bf16_flops_per_s"]
    # one chip's sequences
    return 100.0 * least_s * (batch // run.chips) / (ms * 1e-3)
