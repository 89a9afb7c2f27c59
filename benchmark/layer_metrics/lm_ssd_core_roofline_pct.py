"""Kernels (nemotron_h decoder): the state-space scan's share of its
roofline. The least time the chip could take for the Mamba-2 blocks'
ssd_core a step, forward and backward, is the LARGER of its operations
over the bf16 peak and its HBM bytes over the HBM rate (peaks.json) —
lm_ssd_flops.ssd_scan_train_ops: the scan stated at the PUBLISHED
chunk_size 128 whatever chunk the kernels take (C B^T a group and the
masked product with u a head over (128 + 1) / 2 causal pairs a token, C S
and the state's write), x 3; lm_ssd_flops.ssd_scan_train_bytes: the joined
plane [u | B | C], dt and y forward, those and the cotangents backward —
over the device time measured under the scope ssd_core inside ssm_mixer
(lm_ssd_core_ms_per_step). At these sizes the bytes decide (1.1 ms a block
against 0.7 of operations). What the number cannot pass: a rematerialised
block runs the forward kernel a second time, which neither count holds (4
passes' work for 3 counted: 75 %), and the states a chunk that the forward
rule writes for the backward are not counted either. Moves
train_img_per_s_chip."""

import lm_ssd_flops
import lm_ssd_phase_table


def read(run):
    ms = lm_ssd_phase_table.metric(run, "lm_ssd_core_ms_per_step")
    shape = run.config.get("flops")
    batch = run.counters.get("train_batch")
    if not ms or shape is None or not batch or "ssm_state_size" not in shape:
        return None
    blocks = sum(1 for mixer, _ in shape["layers"] if mixer == "ssm")
    tokens = batch * shape["seq_len"] // run.chips   # one chip's tokens
    least_s = blocks * max(
        lm_ssd_flops.ssd_scan_train_ops(tokens, shape)
        / run.peaks["bf16_flops_per_s"],
        lm_ssd_flops.ssd_scan_train_bytes(tokens, shape)
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
