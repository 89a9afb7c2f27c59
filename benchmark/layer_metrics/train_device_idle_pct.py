"""Device: share of the traced stretch in which no operation ran on the
device. Moves train_img_per_s_chip."""


def read(run):
    if "train_steps_traced" not in run.counters or run.trace is None \
            or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
