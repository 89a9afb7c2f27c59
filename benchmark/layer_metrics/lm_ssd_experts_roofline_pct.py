"""Kernels (nemotron_h decoder): the un-gated experts' kernels' share of
their roofline. The least time the chip could take for the routed blocks'
two grouped products a step, forward and backward — the rows routed to
this shard's experts (the counter lm_ssd_moe_rows_a_block of
drivers/lm_ssd_train_steps.py: the ring's moe_rows_fill, the FULLEST
routed block's, times the buffer's rows, mean over the window's steps — an
upper estimate by the spread between blocks; the even share, top_k * held
/ experts a token, where the counter is missing) through W1 [2688, 1856]
and W2 [1856, 2688], x 3 (lm_ssd_flops.experts_train_ops), over the bf16
peak (peaks.json) — over the device time of the five kernels
moe_experts_up / _down / _back / _dw12 / _dw3 ALONE: the time under
moe_experts inside moe_ffn (lm_moe_experts_ms_per_step, lm_phases.json)
less the time under moe_rows inside it (the dispatch and combine of
ops/routed_rows.py: lm_ssd_phases.json names that scope where lm_phases.json
names moe_experts). What the number cannot pass: a rematerialised block
runs the two forward kernels a second time (8 products' work for 6
counted: 75 %), and a row tile two experts share is computed once an
expert. Moves train_img_per_s_chip."""

import lm_phase_table
import lm_ssd_flops
import lm_ssd_phase_table


def read(run):
    experts_ms = lm_phase_table.metric(run, "lm_moe_experts_ms_per_step")
    rows_ms = lm_ssd_phase_table.metric(run, "lm_ssd_moe_rows_part")
    shape = run.config.get("flops")
    batch = run.counters.get("train_batch")
    if not experts_ms or rows_ms is None or shape is None or not batch \
            or "ssm_state_size" not in shape:
        return None
    ms = experts_ms - rows_ms
    if ms <= 0:
        return None
    blocks = sum(1 for _, ffn in shape["layers"] if ffn == "moe")
    rows = run.counters.get("lm_ssd_moe_rows_a_block")
    if rows is None:
        rows = (batch // run.chips * shape["seq_len"]
                * shape["num_experts_per_tok"] * shape["experts_held"]
                / shape["n_routed_experts"])
    least_s = blocks * lm_ssd_flops.experts_train_ops(rows, shape) \
        / run.peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
