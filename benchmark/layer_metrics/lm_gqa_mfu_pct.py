"""Step program (smallthinker decoder): required FLOPs a token
(lm_gqa_flops.py: forward and backward, the pairs inside the band, the
experts as held, no recomputation) times the window's tokens a second a
chip, over the chip's bf16 peak (peaks.json). Moves train_img_per_s_chip."""

import lm_gqa_flops


def read(run):
    rate = run.counters.get("train_img_per_s_chip")
    shape = run.config.get("flops")
    if rate is None or shape is None or "sliding_window_size" not in shape:
        return None
    per_token = lm_gqa_flops.train_flops_per_token(shape)
    return 100.0 * per_token * rate * shape["seq_len"] / run.peaks["bf16_flops_per_s"]
