"""Kernels (deepseek_v3 decoder): the latent attention core's share of its
roofline. The least time the chip could take for the layers' mla_core a
step, forward and backward — every causal pair of 32 heads, scores 192
deep (128 + the shared 64) and values 128 wide, x 3
(lm_mla_flops.mla_core_train_ops), over the bf16 peak (peaks.json) — over
the device time measured under the scope mla_core inside mla_mixer
(lm_mla_core_ms_per_step). What the number cannot pass: a 192-deep
contraction fills two 128-deep passes of the MXU as a 256-deep one does,
so the kernels' padded operands do (256 + 128) for every (192 + 128)
counted: 83 %; and the layer's remat runs the forward kernel a second
time, which the operations do not count (4 passes' work for 3 counted: 62 %
with both). The scope also holds the pad and the repeat of the shared key.
Moves train_img_per_s_chip."""

import lm_mla_flops
import lm_mla_phase_table


def read(run):
    ms = lm_mla_phase_table.metric(run, "lm_mla_core_ms_per_step")
    shape = run.config.get("flops")
    batch = run.counters.get("train_batch")
    if not ms or shape is None or not batch or "kv_lora_rank" not in shape \
            or "n_routed_experts" not in shape:
        return None
    ops = lm_mla_flops.mla_core_train_ops(
        shape["seq_len"], shape["num_attention_heads"],
        shape["qk_nope_head_dim"] + shape["qk_rope_head_dim"],
        shape["v_head_dim"])
    least_s = len(shape["layers"]) * ops / run.peaks["bf16_flops_per_s"]
    # one chip's sequences
    return 100.0 * least_s * (batch // run.chips) / (ms * 1e-3)
