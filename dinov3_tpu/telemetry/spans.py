"""Host phase-span tracer + per-process heartbeat + benchmark fence.

``SpanTracer`` records monotonic-clock spans around the hot loop's host
phases — data-wait, h2d ``put_batch``, step dispatch, metrics flush,
gram refresh, eval, checkpoint save — as JSON lines in
``<output-dir>/telemetry/spans[.rankN].jsonl``:

    {"name": "dispatch", "id": 412, "parent": null, "proc": "<pid>.<s>",
     "iteration": 17, "t": <epoch s at start>,
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

Set-up happens before a tracer can exist (the output directory, the
rank and the resume point are set-up's own results), so its spans go to
``LOG``, the process's ``SpanLog``: in memory from the first import, on
the same clock, nested by ``id`` / ``parent``. ``listen_to_jax`` adds
JAX's own trace / lower / compile events to it by program name. Every
``SpanTracer`` span is a span of a ``SpanLog``; the trainer's tracer is
given ``LOG``, writes what that holds so far at the head of its stream
and every later record as it closes.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import logging
import os
import threading
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


class SpanLog:
    """Spans of this process, in memory until a stream takes them.

    A record is ``{"name", "id", "parent", "proc", "t", "t_mono",
    "dur_ms", **fields}``: ``parent`` is the id of the span that was open
    on this thread when the record's span started (None at the top),
    ``proc`` is the one identifier every record of this process shares
    (its start-up is the one "request"), ``t_mono`` is ``SpanTracer``'s
    clock. A record exists when its span closes, so a child comes before
    its parent. While a stream is attached a record goes to it and is not
    kept; otherwise at most ``cap`` are kept and the rest counted in
    ``dropped``."""

    def __init__(self, cap: int = 4096):
        self.cap = int(cap)
        self.records: list[dict] = []
        self.dropped = 0
        self.proc = f"{os.getpid()}.{int(time.time())}"
        # counted where it happens (listen_to_jax): programs built or
        # loaded, the persistent cache's hits and writes, the compile
        # seconds its hits saved (``setup_done`` puts them in a record,
        # chip_smoke.py in its log lines), and the programs a loop phase
        # built or loaded when it ran AGAIN (the metric log's column)
        self.counters = {"programs_compiled": 0, "cache_hits": 0,
                         "cache_misses": 0, "compile_time_saved_s": 0.0,
                         "recompiles": 0}
        # the loop phases (``phase``) that have run once in this set-up's
        # loop: each is steady from then on
        self.ran: set[str] = set()
        self._ids = itertools.count(1)
        self._open = threading.local()
        self._sinks: list = []

    def _stack(self) -> list:
        try:
            return self._open.stack
        except AttributeError:
            self._open.stack = []
            return self._open.stack

    def open(self, name: str, **fields) -> dict:
        stack = self._stack()
        rec = {"name": name, "id": next(self._ids),
               "parent": stack[-1]["id"] if stack else None,
               "proc": self.proc, "t": round(time.time(), 6),
               "t_mono": time.perf_counter(), **fields}
        stack.append(rec)
        return rec

    def close(self, rec: dict) -> None:
        t0 = rec["t_mono"]
        rec["dur_ms"] = round((time.perf_counter() - t0) * 1e3, 4)
        rec["t_mono"] = round(t0, 6)
        self._stack().remove(rec)
        if rec["parent"] is None and rec.get("iteration") is not None:
            self.ran.add(rec["name"])
        self._put(rec)

    def _put(self, rec: dict) -> None:
        if self._sinks:
            for sink in self._sinks:
                sink(dict(rec))
        elif len(self.records) < self.cap:
            self.records.append(rec)
        else:
            self.dropped += 1

    @contextlib.contextmanager
    def span(self, name: str, **fields):
        rec = self.open(name, **fields)
        try:
            yield rec
        finally:
            self.close(rec)

    def innermost(self, *names: str) -> dict | None:
        """The innermost span open on this thread under one of ``names``."""
        for rec in reversed(self._stack()):
            if rec["name"] in names:
                return rec
        return None

    def phase(self) -> dict | None:
        """The loop phase open on this thread: its outermost span, where
        that names an iteration (``dispatch``, ``metrics_flush``,
        ``checkpoint_save``, ``eval`` ... of ``do_train``)."""
        stack = self._stack()
        if stack and stack[0].get("iteration") is not None:
            return stack[0]
        return None

    def begin_setup(self) -> int:
        """A set-up starts (a second incarnation in one process has one
        of its own): no phase has run, and only spans with an id above
        the one returned are this set-up's."""
        self.ran.clear()
        return next(self._ids)

    def seconds(self, name: str, since: int = 0) -> float:
        """How long the newest kept span under ``name`` opened after
        ``since`` (``begin_setup``) took; NaN where none was kept."""
        for rec in reversed(self.records):
            if rec["name"] == name and rec["id"] > since:
                return rec["dur_ms"] / 1e3
        return float("nan")

    def setup_done(self, iteration: int | None = None) -> dict:
        """Set-up is over (the loop's first dispatch has returned): one
        ``setup.compiled`` record says what the process has built or
        loaded so far — all hits is a warm start, and ``cache_misses``
        programs were written for the next one."""
        c = self.counters
        with self.span(
                "setup.compiled", iteration=iteration,
                programs_compiled=c["programs_compiled"],
                cache_hits=c["cache_hits"], cache_misses=c["cache_misses"],
                compile_time_saved_s=round(c["compile_time_saved_s"], 3),
        ) as rec:
            pass
        return rec

    def attach(self, sink) -> None:
        """Hand ``sink`` the records kept so far (they are its now: a
        second incarnation in this process does not get the first one's
        set-up again), then every record as it closes."""
        kept, self.records = self.records, []
        for rec in kept:
            sink(rec)
        self._sinks.append(sink)

    def detach(self, sink) -> None:
        self._sinks.remove(sink)


LOG = SpanLog()


# JAX's monitoring events from its compile path (jax/_src/dispatch.py
# log_elapsed_time: a scalar event at the start, a duration event at the
# end, both with ``fun_name``); the dispatch fast path emits none
_JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_LOAD_S = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED_S = "/jax/compilation_cache/compile_time_saved_sec"
_listening = False


def _jax_started(event: str, _value, fun_name=None, **_) -> None:
    name = _JAX_SPANS.get(event)
    if name is None:
        return
    if name == "jit.trace":
        # a function traced inside another's trace or lowering (thousands
        # a step: every jnp helper is one) is no span of its own: the
        # span it lies in tallies it by name when it ends
        outer = LOG.innermost("jit.trace", "jit.lower")
        if outer is not None:
            outer["nested_open"] += 1
            return
    LOG.open(name, program=fun_name, nested={}, nested_open=0)


def _jax_duration(event: str, duration: float, fun_name=None, **_) -> None:
    log = LOG
    name = _JAX_SPANS.get(event)
    if name is None:
        # the cache's own events carry no name: they fire inside the
        # ``jit.compile`` span of the program that was looked up
        rec = log.innermost("jit.compile")
        if event == _CACHE_SAVED_S:
            log.counters["compile_time_saved_s"] += duration
            if rec is not None:
                rec["saved_s"] = round(duration, 6)
        elif event == _CACHE_LOAD_S and rec is not None:
            rec["load_s"] = round(duration, 6)
        return
    if name == "jit.trace":
        rec = log.innermost("jit.trace", "jit.lower")
        if rec is not None and rec["nested_open"]:
            rec["nested_open"] -= 1
            tally = rec["nested"].setdefault(fun_name, [0, 0.0])
            tally[0] += 1
            tally[1] = round(tally[1] + duration, 6)
            return
    else:
        rec = log.innermost(name)
    if rec is None or rec["name"] != name or rec["program"] != fun_name:
        return  # its start was not heard
    del rec["nested_open"]
    if not rec["nested"]:
        del rec["nested"]
    if name == "jit.compile":
        # backend_compile_duration wraps compile_or_get_cached: it fires
        # for a program built and for one loaded from the cache alike
        log.counters["programs_compiled"] += 1
        phase = log.phase()
        if phase is not None:
            # a loop phase compiles what it needs the first time it runs
            # (the step in the first dispatch, small programs in the
            # first flush and the first save); in a later run of that
            # phase it is the stall an operator looks for
            rec["iteration"] = phase["iteration"]
            if phase["name"] in log.ran:
                rec["recompile"] = True
                log.counters["recompiles"] += 1
                logger.warning(
                    "recompile at iteration %s: %s, %.1fs (%s)",
                    rec["iteration"], fun_name, duration,
                    "loaded from the cache" if rec.get("cached")
                    else "built")
    log.close(rec)


def _jax_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        LOG.counters["cache_hits"] += 1
        rec = LOG.innermost("jit.compile")
        if rec is not None:
            rec["cached"] = True
    elif event == _CACHE_MISS:
        LOG.counters["cache_misses"] += 1


def listen_to_jax() -> None:
    """Register the listeners that turn JAX's trace / lower / compile and
    compilation-cache events into ``jit.*`` spans and counters of
    ``LOG``; once a process, whoever calls."""
    global _listening
    if _listening:
        return
    import jax

    jax.monitoring.register_scalar_listener(_jax_started)
    jax.monitoring.register_event_duration_secs_listener(_jax_duration)
    jax.monitoring.register_event_listener(_jax_event)
    _listening = True


class SpanTracer:
    """JSONL span recorder + heartbeat. ``enabled=False`` turns every
    method into a no-op (the oracle arms and non-traced tools pay
    nothing). Every span goes through a ``SpanLog`` (``id``, ``parent``);
    given the process's ``log`` the stream carries that one's records —
    those it holds so far first, later ones as they close."""

    def __init__(self, output_dir: str | None, rank: int = 0,
                 enabled: bool = True, heartbeat_every: int = 1,
                 profile_steps: tuple[int, int] | None = None,
                 profile_dir: str | None = None, role: str = "train",
                 flush_every_emits: int = 32, log: SpanLog | None = None):
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
        self._log = None
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
        self._log = log if log is not None else SpanLog()
        self._log.attach(self.emit)

    # ---- spans ----

    @contextlib.contextmanager
    def span(self, name: str, iteration: int | None = None, **fields):
        """Time a block as one span record; ``fields`` ride the record
        (serve spans attach request/pack ids this way)."""
        if not self.enabled:
            yield
            return
        with self._log.span(
                name,
                iteration=None if iteration is None else int(iteration),
                **fields):
            yield

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
        if self._log is not None:
            self._log.detach(self.emit)
            self._log = None
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
