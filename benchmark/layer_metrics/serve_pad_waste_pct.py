"""Serving, packer: share of the packs' token budget left as padding over
the window (engine.mean_pad_waste after reset_pad_stats at its start).
Moves serve_img_per_s."""


def read(run):
    waste = run.counters.get("serve_mean_pad_waste")
    return None if waste is None else 100.0 * waste
