"""Serving: mean wall time of one engine.flush() in the window (placement,
plane fill, dispatch, fetch, extract). Moves serve_img_per_s."""


def read(run):
    return run.counters.get("serve_flush_wall_ms_mean")
