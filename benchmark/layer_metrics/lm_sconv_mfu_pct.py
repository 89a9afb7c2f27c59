"""Step program (lfm2_moe decoder): required FLOPs a token
(lm_sconv_flops.py: forward and backward, the conv mixers' two projections
and chain, every causal pair of the attention layer, the dense FFN, the
experts as held, the tied head, no recomputation) times the window's tokens
a second a chip, over the chip's bf16 peak (peaks.json): the share of the
whole step. Moves train_img_per_s_chip."""

import lm_sconv_flops


def read(run):
    rate = run.counters.get("train_img_per_s_chip")
    shape = run.config.get("flops")
    if rate is None or shape is None or "conv_L_cache" not in shape:
        return None
    per_token = lm_sconv_flops.train_flops_per_token(shape)
    return 100.0 * per_token * rate * shape["seq_len"] / run.peaks["bf16_flops_per_s"]
