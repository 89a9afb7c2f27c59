"""Step program (deepseek_v3 decoder): required FLOPs a token
(lm_mla_flops.py: forward and backward, the mixers' four projections,
every causal pair of every layer's latent core at 192 + 128, the dense
FFN, the shared experts, the routed experts as held, the head, no
recomputation and no padded lane) times the window's tokens a second a
chip, over the chip's bf16 peak (peaks.json): the share of the whole
step. Moves train_img_per_s_chip."""

import lm_mla_flops


def read(run):
    rate = run.counters.get("train_img_per_s_chip")
    shape = run.config.get("flops")
    if rate is None or shape is None or "n_routed_experts" not in shape:
        return None
    per_token = lm_mla_flops.train_flops_per_token(shape)
    return 100.0 * per_token * rate * shape["seq_len"] / run.peaks["bf16_flops_per_s"]
