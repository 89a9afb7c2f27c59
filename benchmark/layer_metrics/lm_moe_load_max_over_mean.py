"""Routed experts (decoder): the largest held expert's rows over the
held experts' mean, worst routed layer, mean over the window's steps
(the step's own ring column moe_load_max_over_mean). Moves
train_img_per_s_chip."""


def read(run):
    return run.counters.get("lm_moe_load_max_over_mean")
