"""Host phase-span tracer + per-process heartbeat + benchmark fence.

``SpanTracer`` records monotonic-clock spans around the hot loop's host
phases — data-wait, h2d ``put_batch``, step dispatch, metrics flush,
gram refresh, eval, checkpoint save — as JSON lines in
``<output-dir>/telemetry/spans[.rankN].jsonl``:

    {"name": "dispatch", "iteration": 17, "t": <epoch s at start>,
     "t_mono": <perf_counter s at start>, "dur_ms": 1.84}

Durations come from ``time.perf_counter`` (monotonic); ``t`` is wall
epoch time for cross-process alignment only. ``t_mono`` is the same
monotonic clock's reading at the span's start: with the ``profile_stop``
record's ``fence_mono`` (the moment the host saw the traced window's
last device operation end) it lays a process's spans on the device
trace's clock — ``device_ns = last_device_event_end_ns + (t_mono -
fence_mono) * 1e9`` — the way ``benchmark/trace_reduce.py`` lays the
benchmark's own. Memory samples ride the same stream as ``{"name":
"memory", "point": "flush", ...}`` records (telemetry/memory.py).

The heartbeat file (``<output-dir>/telemetry/heartbeat[.rankN]``) is
rewritten at most once per ``heartbeat_every`` iterations with the last
iteration + wall time; its MTIME is the liveness primitive — a stalled
process (data-loader deadlock, dead collective, hung compile) stops
advancing it, which is the stall signal the elastic/preemption work
(ROADMAP item 4) polls for without parsing anything.

The ``--profile-steps`` jax.profiler trace window is folded in
(``profile_step_begin``/``profile_step_end``), so the span stream and
the profiler trace cover the same iterations when both are on.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import time

logger = logging.getLogger("dinov3")

# the hot-loop phase names train/train.py emits — one vocabulary, shared
# with tests (schema validation) and docs/OBSERVABILITY.md
PHASES = (
    "data_wait", "h2d", "dispatch", "metrics_fetch", "metrics_flush",
    "gram_refresh", "eval", "checkpoint_save",
)

# the serve-side phase names (telemetry/serve_obs.py emits them through
# the SAME tracer/JSONL schema, so one stream covers both worlds —
# docs/OBSERVABILITY.md span taxonomy). Ordered as a request experiences
# them: queue wait, FFD placement + plane fill, compiled-call dispatch,
# device compute fenced by the ring fetch, response extraction.
SERVE_PHASES = (
    "serve_enqueue", "serve_pack_placement", "serve_dispatch",
    "serve_device", "serve_fetch", "serve_extract",
)

# the current span-record schema version, stamped on EVERY record so
# readers (scripts/obs_report.py, the elastic-resume tooling) can gate
# on it instead of sniffing fields
SPAN_SCHEMA_V = 1


class SpanTracer:
    """JSONL span recorder + heartbeat. ``enabled=False`` turns every
    method into a no-op (the oracle arms and non-traced tools pay
    nothing)."""

    def __init__(self, output_dir: str | None, rank: int = 0,
                 enabled: bool = True, heartbeat_every: int = 1,
                 profile_steps: tuple[int, int] | None = None,
                 profile_dir: str | None = None, role: str = "train",
                 flush_every_emits: int = 32):
        self.enabled = bool(enabled and output_dir)
        self.heartbeat_every = max(1, int(heartbeat_every))
        self.role = str(role)
        # bounded auto-flush: a crash between beats loses at most
        # flush_every_emits - 1 trailing spans (0 = only beat()/close()
        # flush, the pre-PR-11 behavior)
        self.flush_every_emits = max(0, int(flush_every_emits))
        self._emits_since_flush = 0
        self._profile = profile_steps
        self._profile_dir = profile_dir
        self._profiling = False
        self._f = None
        self.spans_path = self.heartbeat_path = None
        if not self.enabled:
            return
        tdir = os.path.join(output_dir, "telemetry")
        os.makedirs(tdir, exist_ok=True)
        suffix = "" if rank == 0 else f".rank{rank}"
        # one logical stream, role-split files: the train role keeps the
        # pre-PR-11 paths; other roles (serve) write spans.<role>.jsonl
        # beside them so a trainer and a serve engine sharing an output
        # dir never interleave writes mid-line. Every record carries
        # "role", and readers (scripts/obs_report.py) fold spans*.jsonl
        # back into the one stream. Heartbeats are ALWAYS role-
        # namespaced (heartbeat.<role>[.rankN]) — the un-namespaced
        # legacy name let the two roles overwrite each other's liveness
        # signal; telemetry/watchdog.py keeps the back-compat read path.
        rpart = "" if self.role == "train" else f".{self.role}"
        self.spans_path = os.path.join(tdir, f"spans{rpart}{suffix}.jsonl")
        self.heartbeat_path = os.path.join(
            tdir, f"heartbeat.{self.role}{suffix}")
        self._f = open(self.spans_path, "a")

    # ---- spans ----

    @contextlib.contextmanager
    def span(self, name: str, iteration: int | None = None, **fields):
        """Time a block as one span record; ``fields`` ride the record
        (serve spans attach request/pack ids this way)."""
        if not self.enabled:
            yield
            return
        t_wall = time.time()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.emit({
                "name": name,
                "iteration": None if iteration is None else int(iteration),
                "t": round(t_wall, 6),
                "t_mono": round(t0, 6),
                "dur_ms": round((time.perf_counter() - t0) * 1e3, 4),
                **fields,
            })

    def emit(self, record: dict) -> None:
        """Append one JSONL record, stamped with the schema version and
        this tracer's role. Buffered; flushed by ``beat``/``close`` and
        by the bounded auto-flush every ``flush_every_emits`` records,
        so a crash that never reaches ``close`` still leaves all but the
        last flush_every_emits - 1 spans readable."""
        if self._f is None:
            return
        record.setdefault("v", SPAN_SCHEMA_V)
        record.setdefault("role", self.role)
        self._f.write(json.dumps(record) + "\n")
        if self.flush_every_emits:
            self._emits_since_flush += 1
            if self._emits_since_flush >= self.flush_every_emits:
                self._f.flush()
                self._emits_since_flush = 0

    def wrap_iter(self, iterable, name: str = "data_wait",
                  start_iteration: int = 0):
        """Time each ``next()`` of ``iterable`` as a span — the
        data-wait phase, traced without restructuring the driving
        ``MetricLogger.log_every`` loop."""
        if not self.enabled:
            yield from iterable
            return
        it = iter(iterable)
        i = int(start_iteration)
        while True:
            with self.span(name, i):
                try:
                    obj = next(it)
                except StopIteration:
                    return
            yield obj
            i += 1

    # ---- heartbeat ----

    def beat(self, iteration: int) -> None:
        """Advance the heartbeat file's mtime (at most once per
        ``heartbeat_every`` iterations) and flush buffered spans."""
        if not self.enabled or iteration % self.heartbeat_every:
            return
        self._f.flush()
        self._emits_since_flush = 0
        with open(self.heartbeat_path, "w") as hb:
            hb.write(json.dumps(
                {"iteration": int(iteration), "t": round(time.time(), 6)}))

    # ---- memory samples (ride the span stream) ----

    def emit_memory(self, point: str, iteration: int | None = None) -> None:
        if not self.enabled:
            return
        from dinov3_tpu.telemetry.memory import sample_memory

        self.emit({
            "name": "memory",
            "point": point,
            "iteration": None if iteration is None else int(iteration),
            "t": round(time.time(), 6),
            **sample_memory(),
        })

    # ---- jax.profiler trace window (--profile-steps) ----

    def profile_step_begin(self, iteration: int) -> None:
        if self._profile and iteration == self._profile[0]:
            import jax

            options = jax.profiler.ProfileOptions()
            if jax.devices()[0].platform == "tpu":
                # with the host tracer on (any level) the TPU runtime
                # stalls the traced program for hundreds of ms at a time
                # (benchmark/run.py HOST_TRACER_LEVEL, chip runs of PR 24):
                # the window holds device events only, and the spans are
                # laid against it through ``fence_mono``. On the CPU the
                # host tracer is what records ops, so it stays on.
                options.host_tracer_level = 0
                options.python_tracer_level = 0
            jax.profiler.start_trace(self._profile_dir,
                                     profiler_options=options)
            self._profiling = True
            self.emit({"name": "profile_start", "iteration": int(iteration),
                       "t": round(time.time(), 6),
                       "t_mono": round(time.perf_counter(), 6)})

    def profile_step_end(self, iteration: int, state=None) -> None:
        if self._profile and self._profiling \
                and iteration == self._profile[1]:
            import jax

            if state is not None:
                jax.tree.leaves(state.params)[0].block_until_ready()
            # the fence: the host has just seen the window's last device
            # operation end (None without a state to wait on)
            fence = None if state is None else round(time.perf_counter(), 6)
            jax.profiler.stop_trace()
            self._profiling = False
            self.emit({"name": "profile_stop", "iteration": int(iteration),
                       "t": round(time.time(), 6), "fence_mono": fence})

    def close(self) -> None:
        if self._f is not None:
            self._f.flush()
            self._f.close()
            self._f = None


class StepTimer:
    """Steady-state ``--benchmark`` timer with an EXPLICIT fence.

    The old timing free-rode on the per-step metrics fetch ("the
    metrics fetch above synced, so the step has completed") — which the
    async ring removes, leaving nothing between timestamp and dispatch.
    ``mark(state)`` fences with one tiny value fetch (``state.step``,
    4 bytes, through the counted ``blocking_fetch`` funnel, so the
    fence is counted like every other host sync) and then timestamps, so both telemetry arms time
    completed steps. On the oracle arm the fence lands after the
    metrics fetch already synced and costs ~nothing — the two timing
    methods agree there (pinned in tests/test_telemetry.py).
    """

    def __init__(self, n_steps: int, total_iters: int):
        self.n = max(0, int(n_steps))
        self.total = int(total_iters)
        self.times: list[float] = []

    def active(self, iteration: int) -> bool:
        """One extra leading mark gives N measured intervals (the
        original windowing)."""
        return bool(self.n) and iteration >= self.total - self.n - 1

    def mark(self, state=None) -> None:
        if state is not None:
            from dinov3_tpu.telemetry.host_sync import blocking_fetch

            blocking_fetch(state.step)
        self.times.append(time.perf_counter())

    @property
    def n_intervals(self) -> int:
        return max(0, len(self.times) - 1)

    def img_per_sec(self, global_batch: int) -> float | None:
        if self.n_intervals < 1:
            return None
        dt = (self.times[-1] - self.times[0]) / self.n_intervals
        return global_batch / dt

    def ms_per_step(self) -> float | None:
        if self.n_intervals < 1:
            return None
        return (self.times[-1] - self.times[0]) / self.n_intervals * 1e3
