"""Kernels (keye_vl2 decoder): the sparse attention core's share of its
roofline. The least time the chip could take for the five layers'
dsa_core a step, forward and backward — the larger of its operations over
the bf16 peak and its HBM bytes over the HBM rate
(lm_dsa_flops.dsa_core_train: the SELECTED pairs alone, min(t + 1, 2048) a
query of 32 heads on 4 of 128 + 128, peaks.json) — over the device time
measured under the scope dsa_core inside dsa_mixer. A masked pass over
every causal tile can read at most selected / causal pairs (23.4 % at
16,384 tokens) of what the same kernels reach on a band. Moves
train_img_per_s_chip."""

import lm_dsa_flops
import lm_dsa_phase_table


def read(run):
    ms = lm_dsa_phase_table.metric(run, "lm_dsa_core_ms_per_step")
    shape = run.config.get("flops")
    batch = run.counters.get("train_batch")
    if not ms or shape is None or not batch or "index_topk" not in shape:
        return None
    ops, nbytes = lm_dsa_flops.dsa_core_train(
        shape["seq_len"], shape["index_topk"], shape["num_attention_heads"],
        shape["num_key_value_heads"], shape["head_dim"])
    least_s = len(shape["layers"]) * max(ops / run.peaks["bf16_flops_per_s"],
                                         nbytes / run.peaks["hbm_bytes_per_s"])
    # one chip's sequences
    return 100.0 * least_s * (batch // run.chips) / (ms * 1e-3)
