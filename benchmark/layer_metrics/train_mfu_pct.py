"""Step program: required FLOPs per image (benchmark/flops.py) times the
window's images per second per chip, over the chip's bf16 peak
(benchmark/peaks.json). Moves train_img_per_s_chip."""

import flops


def read(run):
    rate = run.counters.get("train_img_per_s_chip")
    shape = run.config.get("flops")
    if rate is None or shape is None:
        return None
    per_image = flops.pretrain_flops_per_image(shape)
    return 100.0 * per_image * rate / run.peaks["bf16_flops_per_s"]
