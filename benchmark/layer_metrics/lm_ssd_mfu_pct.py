"""Step program (nemotron_h decoder): required FLOPs a token
(lm_ssd_flops.py: forward and backward, the Mamba-2 blocks' two
projections and their scan at the published chunk, the attention block's
projections and every causal pair of its core, the router, the shared
un-gated MLP, the routed experts as held, the head, no recomputation)
times the window's tokens a second a chip, over the chip's bf16 peak
(peaks.json): the share of the WHOLE step. Moves train_img_per_s_chip."""

import lm_ssd_flops


def read(run):
    rate = run.counters.get("train_img_per_s_chip")
    shape = run.config.get("flops")
    if rate is None or shape is None or "ssm_state_size" not in shape:
        return None
    per_token = lm_ssd_flops.train_flops_per_token(shape)
    return 100.0 * per_token * rate * shape["seq_len"] / run.peaks["bf16_flops_per_s"]
