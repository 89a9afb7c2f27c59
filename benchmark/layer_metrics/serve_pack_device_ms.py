"""Packed forward: device-busy time per pack of the traced stretch. Moves
serve_img_per_s."""


def read(run):
    packs = run.counters.get("serve_packs_traced")
    if not packs or run.trace is None or run.trace.busy_s <= 0:
        return None
    return run.trace.busy_s / packs * 1e3
