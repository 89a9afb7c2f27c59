"""Kernels (lfm2_moe decoder): the gated short convolution chain's share of
its roofline. The chain is memory-bound (a dozen multiplies and adds an
element moved): the least time the chip could take for the conv layers'
sconv_chain a step, forward and backward, is its HBM bytes over the HBM
rate (lm_sconv_flops.sconv_chain_train_bytes: 4 planes of [tokens, 2048]
bf16 forward, 7 backward; peaks.json) — over the device time measured
under the scope (lm_sconv_chain_ms_per_step). A rematerialised layer runs
the forward kernel a second time, which the bytes do not count: the share
cannot pass 11 / 15 while it does. Moves train_img_per_s_chip."""

import lm_sconv_flops
import lm_sconv_phase_table


def read(run):
    ms = lm_sconv_phase_table.metric(run, "lm_sconv_chain_ms_per_step")
    shape = run.config.get("flops")
    batch = run.counters.get("train_batch")
    if not ms or shape is None or not batch or "conv_L_cache" not in shape:
        return None
    layers = sum(1 for mixer, _ in shape["layers"] if mixer == "conv")
    nbytes = lm_sconv_flops.sconv_chain_train_bytes(  # one chip's tokens
        batch * shape["seq_len"] // run.chips, shape["hidden_size"])
    return 100.0 * layers * nbytes / run.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
