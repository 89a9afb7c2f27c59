"""Async telemetry engine: on-device metrics ring, host phase-span
tracer, memory accounting.

Three coupled pieces (the observability PR, ISSUE 6):

- **async metrics path** (``ring.py`` + train/train_step.py
  ``make_telemetry_step``): the jitted step writes its scalar metrics
  into a donated on-device ``[K, M]`` ring buffer — one
  dynamic-update-slice per step, no host sync — and the host flushes
  the ring once per ``telemetry.flush_every`` steps with a single
  fetch. A device-side finite-flag scalar (consecutive non-finite
  ``total_loss`` streak) replaces the per-step NaN check, so the
  3-strike abort survives with flush-granularity latency. The per-step
  ``float(v)`` fetch path stays as the default-off oracle behind
  ``telemetry.async_metrics=false`` (repo convention: every engine
  keeps its legacy path as a test oracle).
- **phase-span tracer** (``spans.py``): a monotonic-clock span
  recorder wrapping data-wait, h2d ``put_batch``, step dispatch,
  metrics flush, gram refresh, eval, and checkpoint save, emitting
  JSONL spans plus a per-process heartbeat file (mtime = liveness —
  the stall primitive elastic/preemption work needs), with the
  ``--profile-steps`` jax.profiler trace window folded in.
- **memory accounting** (``memory.py``): ``device.memory_stats()``
  (bytes-in-use / peak) sampled at each flush and at setup/compile
  boundaries, emitted into the telemetry JSONL and summarized into the
  committed ``MEM_r11.json`` artifact (scripts/cost_host_sync.py).

``host_sync.py`` is the single device->host fetch funnel both arms
route through, so the committed ``COST_HSYNC_r11.json`` counts blocking
fetches and host-blocked wall time per arm from the same instrument.

The serving observability plane (ISSUE 11) extends the same discipline
to the PR-10 serve engines: ``serve_obs.py`` (per-request spans, SLO
histograms, live-mix envelope re-derivation), ``hist.py`` (fixed-memory
log-bucketed histograms + the shared nearest-rank quantile helper), and
``watchdog.py`` (role-namespaced heartbeats, staleness scan, flush-
window stall spans) — one span stream and one fetch funnel cover both
worlds.

The step-anatomy trace plane (ISSUE 13) closes the loop from static
claims to measured time: ``trace.py`` reads the ``--profile-steps`` /
``bench.py --trace`` profiler window (trace.json.gz) into per-device
timelines, and ``anatomy.py`` turns it into a per-step ledger — device
time by op category, collective time attributed to the repo's named
scopes via the compiled HLO's ``op_name`` metadata, measured
exposed/overlapped collective ms (the dynamic twin of the
``by_placement`` census), and a cross-host fleet report (straggler
z-scores, input/comm/compute-bound verdict) over the span streams.
"""

from dinov3_tpu.telemetry.anatomy import (
    anatomy_ledger,
    build_op_index,
    categorize,
    emit_step_anatomy,
    fleet_report,
    ledger_summary,
    load_span_streams,
)
from dinov3_tpu.telemetry.hist import LogHistogram, quantile_nearest_rank
from dinov3_tpu.telemetry.host_sync import blocking_fetch, host_sync_stats
from dinov3_tpu.telemetry.memory import per_device_state_bytes, sample_memory
from dinov3_tpu.telemetry.ring import RingReader, RingState, make_ring, write_row
from dinov3_tpu.telemetry.serve_obs import (
    LiveMixTracker,
    ServeObserver,
    recommended_serve_envelope,
)
from dinov3_tpu.telemetry.spans import SERVE_PHASES, SpanTracer, StepTimer
from dinov3_tpu.telemetry.trace import Trace, TraceEvent, find_trace_file, load_trace
from dinov3_tpu.telemetry.watchdog import (
    PREEMPT_CHAIN,
    Watchdog,
    emit_preempt_chain,
    heartbeat_path,
    last_preempt_record,
    read_heartbeat,
    scan_heartbeats,
)


def telemetry_wished(cfg) -> bool:
    """Whether the config ASKS for the async metrics ring
    (``telemetry.async_metrics``, auto/true = on — the default engine;
    false = the per-step-fetch oracle)."""
    t = (cfg.get("telemetry") or {}).get("async_metrics", "auto")
    if isinstance(t, str):
        return t.lower() in ("auto", "true", "on")
    return bool(t)


__all__ = [
    "RingReader", "RingState", "make_ring", "write_row",
    "SERVE_PHASES", "SpanTracer", "StepTimer",
    "LogHistogram", "quantile_nearest_rank",
    "LiveMixTracker", "ServeObserver", "recommended_serve_envelope",
    "Watchdog", "heartbeat_path", "read_heartbeat", "scan_heartbeats",
    "PREEMPT_CHAIN", "emit_preempt_chain", "last_preempt_record",
    "blocking_fetch", "host_sync_stats",
    "per_device_state_bytes", "sample_memory",
    "telemetry_wished",
    "Trace", "TraceEvent", "find_trace_file", "load_trace",
    "anatomy_ledger", "build_op_index", "categorize", "emit_step_anatomy",
    "fleet_report", "ledger_summary", "load_span_streams",
]
