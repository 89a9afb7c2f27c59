"""Host loop: host time per step in dispatch + h2d, over the traced
stretch (it starts from a fence, so the host is not yet held back by the
device's queue and the spans show the host's own cost). Moves
train_img_per_s_chip."""


def read(run):
    steps = run.counters.get("train_steps_traced")
    if not steps:
        return None
    spans = [s for s in run.spans
             if s.phase == "traced" and s.name in ("dispatch", "h2d")]
    if not spans:
        return None
    return sum(s.ms for s in spans) / steps
