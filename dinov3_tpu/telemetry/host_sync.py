"""The single device->host fetch funnel, with blocking-time accounting.

Every blocking device->host transfer the hot loop issues — the oracle
path's per-step metrics fetch, the async path's once-per-K ring flush,
the ``--benchmark`` fence — goes through ``blocking_fetch``, which
counts calls and the wall time the host spent blocked in them. That
makes the committed host-sync accounting (scripts/cost_host_sync.py ->
COST_HSYNC_r11.json) a measurement of the real loop rather than an
estimate: both arms are counted by the same instrument, and the
acceptance claim ("<= 1 blocking fetch per ``telemetry.flush_every``
steps") is read straight off the counter.

A fetch is BLOCKING in a way ``block_until_ready`` is not: it waits for
the value to arrive on the host (bench.py's warmup sync uses a value
fetch for the same reason). The blocked time therefore
includes any not-yet-executed device work the fetched value depends on
— which is the point: it is the dispatch-fencing cost the async ring
removes from the per-step path.
"""

from __future__ import annotations

import time

_STATS = {"fetches": 0, "blocked_s": 0.0}


def blocking_fetch(tree):
    """Fetch a pytree of device arrays to host (one blocking call),
    counting the call and the host-blocked wall time. Returns the tree
    with arrays as numpy/host values (``jax.device_get`` semantics)."""
    import jax

    t0 = time.perf_counter()
    out = jax.device_get(tree)
    _STATS["fetches"] += 1
    _STATS["blocked_s"] += time.perf_counter() - t0
    return out


def host_sync_stats(reset: bool = False) -> dict:
    """{"fetches": n, "blocked_ms": total host-blocked wall ms} since the
    last reset. ``reset=True`` zeroes the counters after reading (arm
    boundaries in cost_host_sync.py / bench.py)."""
    out = {
        "fetches": _STATS["fetches"],
        "blocked_ms": round(_STATS["blocked_s"] * 1e3, 3),
    }
    if reset:
        _STATS["fetches"] = 0
        _STATS["blocked_s"] = 0.0
    return out
