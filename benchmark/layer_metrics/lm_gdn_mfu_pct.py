"""Step program (qwen3_next decoder): required FLOPs a token
(lm_gdn_flops.py: forward and backward, the delta rule at the recurrence's
count, every causal pair, the experts as held, no recomputation) times the
window's tokens a second a chip, over the chip's bf16 peak (peaks.json).
Moves train_img_per_s_chip."""

import lm_gdn_flops


def read(run):
    rate = run.counters.get("train_img_per_s_chip")
    shape = run.config.get("flops")
    if rate is None or shape is None or "linear_num_key_heads" not in shape:
        return None
    per_token = lm_gdn_flops.train_flops_per_token(shape)
    return 100.0 * per_token * rate * shape["seq_len"] / run.peaks["bf16_flops_per_s"]
