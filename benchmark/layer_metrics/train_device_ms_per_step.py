"""Step program: device-busy time (union of device operations) per step
of the traced stretch. Moves train_img_per_s_chip."""


def read(run):
    steps = run.counters.get("train_steps_traced")
    if not steps or run.trace is None or run.trace.busy_s <= 0:
        return None
    return run.trace.busy_s / steps * 1e3
