"""Step program (keye_vl2 decoder): required FLOPs a token
(lm_dsa_flops.py: forward and backward, the attention core at the SELECTED
pairs, the index scores over every causal pair forward and the selected
pairs backward, the experts as held, no recomputation and no masked pair)
times the window's tokens a second a chip, over the chip's bf16 peak
(peaks.json): the share of the whole step. Moves train_img_per_s_chip."""

import lm_dsa_flops


def read(run):
    rate = run.counters.get("train_img_per_s_chip")
    shape = run.config.get("flops")
    if rate is None or shape is None or "index_topk" not in shape:
        return None
    per_token = lm_dsa_flops.train_flops_per_token(shape)
    return 100.0 * per_token * rate * shape["seq_len"] / run.peaks["bf16_flops_per_s"]
