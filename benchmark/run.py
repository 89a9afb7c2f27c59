"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on the machine it is started on. It looks the cell up in
``BENCHMARK.json``, loads ``configs/<config>.json`` and
``traffic/<traffic>.json``, imports the driver the traffic file names
from ``drivers/<driver>.py`` and, for ``--trace 1``, one reader per
per-layer metric of the cell from ``layer_metrics/<metric>.py``. A later
PR adds a cell by adding such files and ``BENCHMARK.json`` entries; this
file knows no cell, configuration or metric by name.

Everything printed before the last line is information. The last line of
stdout is the result object of the contract (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python lets us

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# where the data files live; benchmark/tests replaces CONFIG_DIR and
# require_devices to rehearse a run on the CPU at a test width — a switch
# of the test, not an option of the harness
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")
CONFIG_DIR = os.path.join(HERE, "configs")
TRAFFIC_DIR = os.path.join(HERE, "traffic")
DRIVER_DIR = os.path.join(HERE, "drivers")
READER_DIR = os.path.join(HERE, "layer_metrics")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_START:7.2f}s] {msg}", flush=True)


def load_module(directory: str, name: str):
    """The module ``<directory>/<name>.py``, imported by path (a metric's
    name may hold ``.`` and ``-``, which no import statement takes)."""
    path = os.path.join(directory, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + "".join(c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def require_devices(chips: int) -> list:
    """The ``chips`` TPU devices of this run; anything else ends it with
    a non-zero exit and no result line."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: no TPU (jax.devices()[0].platform == "
            f"{devices[0].platform!r}); a benchmark number comes only from the chip")
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell asks for {chips} chips, JAX reports {len(devices)}")
    return devices[:chips]


def configure_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
    every program in it, the small ones too: the second run of a cell in
    a checkout compiles nothing. A checkout without the program under
    test ends here (ImportError)."""
    import jax

    from dinov3_tpu.utils import configure_compile_cache

    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CompileWatch:
    """Counts programs built or loaded (backend compiles and persistent-
    cache hits alike) through JAX's monitoring events. A driver reads
    ``count`` at both ends of its window: a difference ends the run."""

    def __init__(self):
        import jax

        self.count = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.compile_s += duration

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    step: int
    phase: str  # "window" | "trace_lead" | "traced"

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e3


class SpanRecorder:
    """The benchmark's own spans around the calls into each layer, on the
    host's clock, kept in memory."""

    def __init__(self):
        self.spans: list = []
        self.phase = "window"

    @contextlib.contextmanager
    def span(self, name: str, step: int = -1):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append(Span(name, t0, time.perf_counter(), step, self.phase))


# Host events in the profiler's trace: 0 on the chip. With the host tracer
# on (any level) the TPU runtime stalls the traced program for hundreds of
# milliseconds at a time (a 16-row serve pack took 1.24 s traced against
# 0.66 s untraced, a pretrain stretch of 8 steps lost 0.4-1.4 s; with it off
# the traced stretch runs at the untraced speed: my chip runs, PR 24). So
# the trace holds device events only, and the benchmark's spans are laid
# against it by the clock alignment of ``trace_reduce``. benchmark/tests
# turns it on, because on the CPU the host tracer is what records ops.
HOST_TRACER_LEVEL = 0


class Tracer:
    """The profiler around one short steady stretch (``--trace 1``).

    ``with tracer:`` starts and stops the profiler; inside it the driver
    first runs a few turns of its loop that are not counted and fences,
    then ``with tracer.window():`` around the stretch that is, which it
    ends with a fence: the window's end on the host's clock is then the
    end of the last device operation, which is how the two clocks are
    aligned."""

    def __init__(self, directory: str, recorder: SpanRecorder):
        self.directory = directory
        self.recorder = recorder
        self.t0 = self.t1 = self.fence = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def __enter__(self):
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = HOST_TRACER_LEVEL
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.recorder.phase = "trace_lead"
        return self

    def __exit__(self, *exc):
        import jax

        self.recorder.phase = "window"
        jax.profiler.stop_trace()
        return False

    def mark_fence(self) -> None:
        """The host has just seen the device finish its last operation so
        far. The last mark inside the window aligns the clocks; without
        one the window's end does."""
        self.fence = time.perf_counter()

    @contextlib.contextmanager
    def window(self):
        self.recorder.phase = "traced"
        self.t0 = time.perf_counter()
        yield
        self.t1 = time.perf_counter()
        if self.fence is None or self.fence < self.t0:
            self.fence = self.t1
        self.recorder.phase = "trace_lead"


@dataclasses.dataclass
class Context:
    """What a driver is given."""

    cell: dict          # the BENCHMARK.json workloads entry
    config: dict        # configs/<config>.json
    traffic: dict       # traffic/<traffic>.json
    seed: int
    seconds: float
    trace: bool
    devices: list
    spans: SpanRecorder
    compiles: CompileWatch
    t_start: float = T_START
    tracer: Tracer | None = None
    memory_peak: int | None = None

    def snapshot_memory(self) -> None:
        """The program's peak so far. A driver calls it before a reference
        that runs on the device, so that ``memory_peak_bytes`` stays the
        program's."""
        self.memory_peak = device_record(self.devices)["memory_peak_bytes"]


@dataclasses.dataclass
class DriverResult:
    """What a driver hands back. ``metrics``: the end-to-end metrics it
    computes (``setup_s`` among them). ``checks``: every number compared,
    ``{"name", "value", "limit", "ok"}``. ``counters``: what the readers
    of per-layer metrics may read."""

    metrics: dict
    attempted: int
    failed: int
    checks: list
    counters: dict


@dataclasses.dataclass
class LayerRun:
    """What a per-layer metric's reader is given."""

    spans: list
    counters: dict
    trace: object       # trace_reduce.Reduction or None
    config: dict
    traffic: dict
    peaks: dict         # the peaks.json row of this device kind
    chips: int


def device_record(devices: list) -> dict:
    """The device as JAX reports it. ``memory_peak_bytes``: on this
    runtime the allocator's ``peak_bytes_in_use`` leaves out what a
    running program reserves for its temporaries (``peak_bytes_reserved``:
    8.8 GB of a ViT-S step's 11 GB, my chip run, PR 24); a step holds both
    at once, so the peak on the fullest chip is their sum."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise SystemExit(f"benchmark: device kind {kind!r} is not in peaks.json")
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    bench = load_json(BENCHMARK_JSON)
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"benchmark: no cell {args.workload!r} in BENCHMARK.json")
    config = load_json(os.path.join(CONFIG_DIR, cell["config"] + ".json"))
    traffic = load_json(os.path.join(TRAFFIC_DIR, cell["traffic"] + ".json"))
    driver = load_module(DRIVER_DIR, traffic["driver"])
    layer_names = [m["name"] for m in bench["per_layer"]
                   if "workloads" not in m or cell["name"] in m["workloads"]]
    e2e_names = [m["name"] for m in bench["end_to_end"]
                 if "workloads" not in m or cell["name"] in m["workloads"]]
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}

    import jax

    cache_dir = configure_cache()
    devices = require_devices(int(cell["chips"]))
    peaks = peaks_for(devices[0].device_kind)
    log(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']}, driver {traffic['driver']}, seed {args.seed}, "
        f"{args.seconds}s, trace {args.trace}; device {devices[0].device_kind} "
        f"x{len(devices)}; jax {jax.__version__}; compile cache {cache_dir}")

    spans = SpanRecorder()
    ctx = Context(cell=cell, config=config, traffic=traffic, seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace), devices=devices,
                  spans=spans, compiles=CompileWatch())
    if ctx.trace:
        ctx.tracer = Tracer(os.path.join(TRACE_DIR, cell["name"]), spans)
    result = driver.run(ctx)

    for c in result.checks:
        log(f"check {c['name']}: {c['value']!r} against limit {c['limit']!r} "
            f"-> {'ok' if c['ok'] else 'NOT OK'}")
    correct = bool(result.checks) and all(c["ok"] for c in result.checks)
    device = device_record(devices)
    if ctx.memory_peak is not None:
        device["memory_peak_bytes"] = ctx.memory_peak
    log(f"memory_stats device 0: {devices[0].memory_stats()}")

    out = {"correct": correct, "attempted": int(result.attempted),
           "failed": int(result.failed)}
    if not ctx.trace:
        missing = [n for n in e2e_names if n not in result.metrics]
        if missing:
            raise SystemExit(f"benchmark: driver reported no {missing}")
        out["metrics"] = {n: {"value": float(result.metrics[n]), "unit": units[n]}
                          for n in e2e_names}
    else:
        import trace_reduce

        reduction = trace_reduce.reduce_dir(
            ctx.tracer.directory, ctx.tracer.t0, ctx.tracer.t1, ctx.tracer.fence,
            [(s.name, s.t0, s.t1) for s in spans.spans if s.phase == "traced"])
        log(f"trace: {reduction.summary()}")
        run = LayerRun(spans=spans.spans, counters=result.counters,
                       trace=reduction, config=config, traffic=traffic,
                       peaks=peaks, chips=len(devices))
        metrics = {}
        for name in layer_names:
            value = load_module(READER_DIR, name).read(run)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": units[name]}
        out["metrics"] = metrics
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        out["breakdown"] = {"device_ops": reduction.top_ops(10),
                            "idle_gaps": reduction.top_gaps(10)}
    out["device"] = device
    log(f"end-to-end (information in a traced run): "
        f"{ {k: round(float(v), 4) for k, v in result.metrics.items()} }")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # the drivers import this file as ``run``: give them this module, not
    # a second copy with a clock of its own
    sys.modules.setdefault("run", sys.modules[__name__])
    sys.exit(main())
