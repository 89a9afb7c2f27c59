"""Set-up traced inside the program (telemetry/spans.py ``SpanLog``, JAX's
compile events by program name, ``utils.configure_compile_cache``, the
``setup.*`` spans of train/setup.py) and the benchmark's readers of it
(benchmark/setup_parts.py, the seven ``setup_*`` metrics).

Every test here works on a ``SpanLog`` of its own put in ``spans.LOG``'s
place: JAX's listeners, once registered, stay for the life of the worker
and write to whatever ``spans.LOG`` is at the time.
"""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from dinov3_tpu.telemetry import spans
from dinov3_tpu.telemetry.spans import SPAN_SCHEMA_V, SpanLog, SpanTracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CELLS = ["vitl16-pretrain", "vits16-pretrain", "kimi-linear-ep32-pretrain-8k",
         "smallthinker-ep4-pretrain-16k", "qwen3-next-ep16-pretrain-8k",
         "keye-vl2-ep8-pretrain-16k", "lfm2-ep8-pretrain-8k",
         "kanana2-ep8-pretrain-16k", "nemotron3-nano-ep16-pretrain-8k"]
METRICS = ["setup_import_s", "setup_build_s", "setup_plan_trace_s",
           "setup_jit_trace_s", "setup_lower_s", "setup_compile_load_s",
           "setup_programs"]


@pytest.fixture
def log(monkeypatch):
    fresh = SpanLog()
    monkeypatch.setattr(spans, "LOG", fresh)
    return fresh


@pytest.fixture
def jax_log(log):
    spans.listen_to_jax()
    return log


def _stream(tracer):
    return [json.loads(ln) for ln in open(tracer.spans_path)]


# ---------------- the log ----------------

def test_log_nests_by_parent_ids(log):
    with spans.LOG.span("outer", program="p") as outer:
        with log.span("inner"):
            with log.span("innermost"):
                pass
        with log.span("sibling"):
            pass
    with log.span("next"):
        pass
    by = {r["name"]: r for r in log.records}
    # a record is appended when its span closes: children first
    assert [r["name"] for r in log.records] == [
        "innermost", "inner", "sibling", "outer", "next"]
    assert by["outer"]["parent"] is None and by["next"]["parent"] is None
    assert by["inner"]["parent"] == by["sibling"]["parent"] == outer["id"]
    assert by["innermost"]["parent"] == by["inner"]["id"]
    assert len({r["id"] for r in log.records}) == 5
    assert {r["proc"] for r in log.records} == {log.proc}
    assert by["outer"]["program"] == "p"
    for r in log.records:
        assert r["dur_ms"] >= 0 and r["t_mono"] > 0 and r["t"] > 0
    # a child lies inside its parent on the one clock
    o, i = by["outer"], by["inner"]
    assert o["t_mono"] <= i["t_mono"]
    assert i["t_mono"] + i["dur_ms"] / 1e3 <= o["t_mono"] + o["dur_ms"] / 1e3 + 1e-5
    assert log.seconds("outer") == by["outer"]["dur_ms"] / 1e3
    assert log.seconds("never") != log.seconds("never")  # NaN
    # a later set-up does not read an earlier one's span
    began = log.begin_setup()
    assert log.seconds("outer", since=began) != log.seconds("outer", since=began)
    with log.span("outer"):
        pass
    assert log.seconds("outer", since=began) == log.records[-1]["dur_ms"] / 1e3


def test_log_is_bounded_and_counts_its_drops():
    small = SpanLog(cap=3)
    for i in range(5):
        with small.span("s", i=i):
            pass
    assert [r["i"] for r in small.records] == [0, 1, 2]
    assert small.dropped == 2
    assert small.seconds("s") == small.records[2]["dur_ms"] / 1e3
    # a stream takes what is kept and then every record: nothing is kept
    # or dropped while it is attached
    got = []
    small.attach(got.append)
    for i in range(5, 10):
        with small.span("s", i=i):
            pass
    assert [r["i"] for r in got] == [0, 1, 2, 5, 6, 7, 8, 9]
    assert small.records == [] and small.dropped == 2
    small.detach(got.append)
    with small.span("s", i=10):
        pass
    assert [r["i"] for r in small.records] == [10] and len(got) == 8


def test_span_closes_when_its_block_raises(log):
    with pytest.raises(RuntimeError):
        with log.span("outer"):
            with log.span("inner"):
                raise RuntimeError("boom")
    assert [r["name"] for r in log.records] == ["inner", "outer"]
    with log.span("after"):
        pass
    assert log.records[-1]["parent"] is None


# ---------------- the tracer streams it ----------------

def test_tracer_writes_the_log_so_far_first_and_later_records_as_they_come(
        tmp_path, log):
    for name in ("setup.a", "setup.b", "setup.c"):
        with log.span(name):
            pass
    tracer = SpanTracer(str(tmp_path), log=log, flush_every_emits=1)
    with tracer.span("dispatch", 0, first=True) as _:
        with log.span("jit.compile", program="step"):
            pass
    with log.span("jit.compile", program="late"):
        pass
    got = _stream(tracer)
    assert [r["name"] for r in got] == [
        "setup.a", "setup.b", "setup.c", "jit.compile", "dispatch",
        "jit.compile"]
    # the tracer's spans are spans of the log: one id space, one nesting
    assert got[4]["first"] is True and got[4]["iteration"] == 0
    assert got[3]["parent"] == got[4]["id"] and got[5]["parent"] is None
    assert len({r["id"] for r in got}) == 6
    tracer.close()
    with log.span("after_close"):
        pass
    got = _stream(tracer)
    assert len(got) == 6
    for r in got:
        assert r["v"] == SPAN_SCHEMA_V and r["role"] == "train"
    # what was streamed is the stream's; what came after the close is kept
    assert [r["name"] for r in log.records] == ["after_close"]
    assert "role" not in log.records[0]


def test_a_tracer_given_no_log_nests_its_spans_in_one_of_its_own(
        tmp_path, log):
    tracer = SpanTracer(str(tmp_path), role="serve", flush_every_emits=1)
    with tracer.span("serve_dispatch", pack=3):
        with tracer.span("serve_fetch"):
            pass
    tracer.close()
    inner, outer = _stream(tracer)
    assert inner["parent"] == outer["id"] and outer["parent"] is None
    assert outer["pack"] == 3 and outer["iteration"] is None
    assert inner["role"] == "serve" and inner["dur_ms"] >= 0
    assert log.records == []          # the process's log heard nothing


def test_disabled_tracer_writes_nothing_and_the_log_stays_readable(
        tmp_path, log):
    with log.span("setup.a"):
        pass
    tracer = SpanTracer(str(tmp_path), enabled=False, log=log)
    with log.span("setup.b"):
        pass
    tracer.close()
    assert not os.path.exists(tmp_path / "telemetry")
    assert [r["name"] for r in log.records] == ["setup.a", "setup.b"]


def test_second_incarnation_gets_only_its_own_records(tmp_path, log):
    with log.span("setup.first"):
        pass
    one = SpanTracer(str(tmp_path / "one"), log=log)
    with log.span("during_one"):
        pass
    one.close()
    with log.span("setup.second"):
        pass
    two = SpanTracer(str(tmp_path / "two"), log=log)
    two.close()
    assert [r["name"] for r in _stream(one)] == ["setup.first", "during_one"]
    assert [r["name"] for r in _stream(two)] == ["setup.second"]


def test_obs_report_folds_a_stream_that_holds_the_new_records(tmp_path, log):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import obs_report

    with log.span("setup.build"):
        with log.span("jit.trace", program="step", nested={"add": [3, 0.1]}):
            pass
    tracer = SpanTracer(str(tmp_path), log=log)
    with tracer.span("dispatch", 0, first=True):
        pass
    log.setup_done(0)
    with tracer.span("dispatch", 1):
        pass
    tracer.close()
    records, census = obs_report.load_spans(tracer.spans_path)
    assert census["lines"] == 5
    assert census["by_name"] == {"jit.trace": 1, "setup.build": 1,
                                 "dispatch": 2, "setup.compiled": 1}
    first = next(r for r in records if r.get("first"))
    assert first["iteration"] == 0 and first["parent"] is None


# ---------------- JAX's events, by program ----------------

def _by_name(log, program):
    return {r["name"]: r for r in log.records
            if r.get("program") in (program, f"jit({program})")}


def test_a_jitted_function_leaves_trace_lower_compile_under_its_name(jax_log):
    @jax.jit
    def tiny_program_a(x):
        return jnp.sin(x) * 2 + jnp.cos(x)

    x = jnp.ones((3,), jnp.float32)
    x.block_until_ready()
    with jax_log.span("caller") as caller:
        tiny_program_a(x).block_until_ready()
    got = _by_name(jax_log, "tiny_program_a")
    assert set(got) == {"jit.trace", "jit.lower", "jit.compile"}
    for r in got.values():
        assert r["parent"] == caller["id"] and r["dur_ms"] > 0
        assert "nested_open" not in r
    # jnp's own jitted helpers traced inside it are tallied, not recorded
    assert sum(r["name"] == "jit.trace" for r in jax_log.records
               if r["parent"] == caller["id"]) == 1
    assert not any(r.get("recompile") for r in jax_log.records)
    n = len(jax_log.records)
    compiled = jax_log.counters["programs_compiled"]
    assert compiled >= 1 and jax_log.counters["recompiles"] == 0
    # a second call at the same shape is the dispatch fast path: nothing
    tiny_program_a(x).block_until_ready()
    assert len(jax_log.records) == n
    assert jax_log.counters["programs_compiled"] == compiled


def test_nested_traces_are_tallied_by_name_in_the_outer_one(jax_log):
    @jax.jit
    def inner_program(x):
        return x * 3

    @jax.jit
    def outer_program(x):
        return inner_program(x) + inner_program(x + 1)

    x = jnp.ones((5,), jnp.float32)
    x.block_until_ready()
    made = len(jax_log.records)
    outer_program(x).block_until_ready()
    traces = [r for r in jax_log.records[made:] if r["name"] == "jit.trace"]
    assert [r["program"] for r in traces] == ["outer_program"]
    n, seconds = traces[0]["nested"]["inner_program"]
    assert n >= 1 and 0 < seconds <= traces[0]["dur_ms"] / 1e3


def test_a_new_shape_in_a_phase_that_has_run_is_a_recompile(jax_log, caplog):
    @jax.jit
    def tiny_program_b(x):
        return x + 1

    # what the calls need besides (the ones) is made outside the phases
    inputs = [jnp.ones((n,), jnp.float32) for n in (3, 2, 4, 5)]
    jax.block_until_ready(inputs)
    jax_log.begin_setup()
    with jax_log.span("dispatch", iteration=0, first=True):
        tiny_program_b(inputs[0]).block_until_ready()
    made = jax_log.setup_done(0)
    assert made["name"] == "setup.compiled" and jax_log.records[-1] is made
    assert made["programs_compiled"] == jax_log.counters[
        "programs_compiled"] >= 1
    assert set(made) >= {"cache_hits", "cache_misses", "compile_time_saved_s",
                         "iteration", "t_mono", "id", "proc"}
    assert jax_log.counters["recompiles"] == 0
    # the phase has run once: a program it compiles now is a recompile
    with caplog.at_level("WARNING", logger="dinov3"):
        with jax_log.span("dispatch", iteration=5000):
            tiny_program_b(inputs[1]).block_until_ready()
    assert jax_log.counters["recompiles"] == 1
    assert "recompile at iteration 5000: jit(tiny_program_b)" in caplog.text
    # ... another phase's first run compiles what it needs, and says when
    with jax_log.span("checkpoint_save", iteration=5000):
        tiny_program_b(inputs[2]).block_until_ready()
    # ... and outside every phase nothing is said
    tiny_program_b(inputs[3]).block_until_ready()
    assert jax_log.counters["recompiles"] == 1
    first, again, save, outside = [
        r for r in jax_log.records if r["name"] == "jit.compile"
        and r["program"] == "jit(tiny_program_b)"]
    assert first["iteration"] == 0 and "recompile" not in first
    assert again["recompile"] is True and again["iteration"] == 5000
    assert again["dur_ms"] > 0
    assert save["iteration"] == 5000 and "recompile" not in save
    assert "iteration" not in outside and "recompile" not in outside
    # a second incarnation's phases start over
    jax_log.begin_setup()
    assert jax_log.ran == set()


def test_a_persistent_cache_hit_is_a_load_and_says_so(jax_log, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    @jax.jit
    def tiny_program_c(x):
        return jnp.tanh(x) - 3

    keys = {"jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": str(tmp_path / "cache"),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in keys.items():
            jax.config.update(k, v)
        cc.reset_cache()
        x = jnp.ones((7,), jnp.float32)
        tiny_program_c(x).block_until_ready()
        built = _by_name(jax_log, "tiny_program_c")["jit.compile"]
        assert "cached" not in built
        assert jax_log.counters["cache_misses"] >= 1
        hits = jax_log.counters["cache_hits"]
        jax.clear_caches()
        tiny_program_c(x).block_until_ready()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
    loads = [r for r in jax_log.records if r["name"] == "jit.compile"
             and r["program"] == "jit(tiny_program_c)" and r.get("cached")]
    assert len(loads) == 1
    assert loads[0]["load_s"] > 0 and "saved_s" in loads[0]
    assert jax_log.counters["cache_hits"] == hits + 1
    # built and loaded both count as programs (the benchmark's CompileWatch
    # counts both too)
    assert sum(r["name"] == "jit.compile" and r["program"] ==
               "jit(tiny_program_c)" for r in jax_log.records) == 2


def test_configure_compile_cache_registers_once(log):
    from jax._src import monitoring

    from dinov3_tpu.utils import configure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        configure_compile_cache()
        n = (len(monitoring._scalar_listeners),
             len(monitoring._event_duration_secs_listeners),
             len(monitoring._event_listeners))
        configure_compile_cache()
        assert n == (len(monitoring._scalar_listeners),
                     len(monitoring._event_duration_secs_listeners),
                     len(monitoring._event_listeners))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert [r["name"] for r in log.records] == ["setup.compile_cache"] * 2
    assert monitoring._scalar_listeners.count(spans._jax_started) == 1


# ---------------- the spans of set-up ----------------

def test_build_train_setup_leaves_build_with_children_and_the_plan(jax_log):
    from test_telemetry import TINY_TRAIN

    from dinov3_tpu.configs import load_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    cfg = load_config(None, overrides=TINY_TRAIN)
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, 2, seed=0).items()}
    setup = build_train_setup(cfg, batch, devices=jax.devices()[:1],
                              init_state=False)
    build = next(r for r in jax_log.records if r["name"] == "setup.build")
    assert build["parent"] is None
    children = [r["name"] for r in jax_log.records
                if r["parent"] == build["id"] and r["name"].startswith("setup.")]
    # the stages a chip run reads near a second or more (the jitted init,
    # ``setup.init_state``, is not made at ``init_state=False``)
    assert children == ["setup.abstract_params", "setup.abstract_state"]
    assert not any(r["name"] == "setup.telemetry_plan" for r in jax_log.records)
    setup.telemetry()
    plan = jax_log.records[-1]
    assert plan["name"] == "setup.telemetry_plan" and plan["parent"] is None
    # the eval_shape of the raw step is a trace, under the plan's span
    traced = [r for r in jax_log.records if r["name"] == "jit.trace"
              and r["parent"] == plan["id"]]
    assert [r["program"] for r in traced] == ["step"]
    setup.telemetry()  # memoised: no second span
    assert jax_log.records[-1] is plan


# ---------------- benchmark/setup_parts.py on made-up records ----------------

def _rec(name, t0, seconds, rid=None, parent=None, **fields):
    return {"name": name, "id": rid, "parent": parent, "t_mono": t0,
            "dur_ms": seconds * 1e3, **fields}


MADE_UP = [
    _rec("setup.compile_cache", 101.5, 0.0005, 1),
    _rec("setup.build", 103.0, 10.0, 2),                     # 103 .. 113
    _rec("jit.trace", 104.0, 4.0, 3, 2, program="init"),     # inside build
    _rec("jit.compile", 105.0, 1.0, 4, 3, program="jit(c)"),  # inside the trace
    _rec("jit.lower", 108.0, 2.0, 5, 2, program="jit(init)"),
    _rec("jit.compile", 110.0, 1.5, 6, 2, program="jit(init)", cached=True),
    _rec("setup.telemetry_plan", 113.0, 5.0, 7),             # 113 .. 118
    _rec("jit.trace", 113.5, 4.0, 8, 7, program="step"),      # inside the plan
    _rec("jit.trace", 120.0, 6.0, 9, program="telemetry_step"),
    _rec("jit.trace", 121.0, 2.0, 10, 9, program="twice"),    # counted once
    _rec("jit.lower", 126.0, 3.0, 11, program="jit(telemetry_step)"),
    _rec("jit.compile", 129.0, 4.0, 12, program="jit(telemetry_step)"),  # straddles 131
    _rec("jit.compile", 140.0, 1.0, 13, program="jit(in_the_window)"),
]


def test_parts_count_nested_intervals_once_and_are_disjoint_by_precedence():
    import setup_parts

    got = setup_parts.split(MADE_UP, 100.0, 131.0)
    p = got["parts"]
    assert p["setup_import_s"] == pytest.approx(1.5)
    assert p["setup_compile_load_s"] == pytest.approx(1.0 + 1.5 + 2.0)
    assert p["setup_lower_s"] == pytest.approx(2.0 + 3.0)
    assert p["setup_plan_trace_s"] == pytest.approx(5.0)
    # the trace inside the plan belongs to the plan, the compile inside a
    # trace to compiling, and a trace nested in a trace counts once
    assert p["setup_jit_trace_s"] == pytest.approx((4.0 - 1.0) + 6.0)
    assert p["setup_build_s"] == pytest.approx(10.0 - 4.0 - 2.0 - 1.5)
    assert got["programs"] == 3


def test_a_span_that_straddles_the_end_is_cut_at_it():
    import setup_parts

    got = setup_parts.split(MADE_UP, 100.0, 131.0)
    last = [r for r in MADE_UP if r["t_mono"] == 129.0][0]
    assert setup_parts.interval(last, 100.0, 131.0) == (129.0, 131.0)
    # ... and what starts after the end is not set-up's at all
    assert got["programs"] == 3
    later = setup_parts.split(MADE_UP, 100.0, 150.0)
    assert later["programs"] == 4
    assert later["parts"]["setup_compile_load_s"] == pytest.approx(
        got["parts"]["setup_compile_load_s"] + 2.0 + 1.0)


@pytest.mark.parametrize("t_end", [131.0, 117.25, 150.0, 103.5])
def test_parts_and_remainder_add_up_to_the_wall(t_end):
    import setup_parts

    got = setup_parts.split(MADE_UP, 100.0, t_end)
    assert got["wall_s"] == t_end - 100.0
    assert abs(sum(got["parts"].values()) + got["remainder_s"]
               - got["wall_s"]) < 1e-9
    assert got["remainder_s"] == pytest.approx(
        sum(b - a for a, b in got["gaps"]))
    for a, b in got["gaps"]:
        assert 100.0 <= a < b <= t_end


def _run(warmup_steps=3, spans_=()):
    """What a reader is given, as far as ``setup_parts`` reads it."""
    return types.SimpleNamespace(
        traffic={"warmup_steps": warmup_steps},
        spans=[types.SimpleNamespace(name=n, t0=t0, t1=t1, step=step,
                                     phase="window")
               for n, t0, t1, step in spans_])


def test_the_end_is_the_dispatch_span_of_step_warmup_steps():
    import setup_parts

    run = _run(3, [("dispatch", 119.0, 129.5, 0), ("h2d", 129.5, 129.6, 0),
                   ("dispatch", 129.7, 129.8, 2), ("h2d", 130.9, 131.0, 3),
                   ("dispatch", 131.0, 131.1, 3), ("dispatch", 131.2, 131.3, 4)])
    assert setup_parts.setup_end(run) == 131.0
    assert setup_parts.setup_end(_run(3, [("dispatch", 1.0, 2.0, 0)])) is None


def test_no_log_or_an_empty_one_gives_no_reading(monkeypatch, log):
    import setup_parts

    assert setup_parts.split([], 100.0, 131.0) is None
    fake_run = types.ModuleType("run")
    fake_run.T_START, fake_run.log = 100.0, lambda msg: None
    monkeypatch.setitem(sys.modules, "run", fake_run)
    run = _run(3, [("dispatch", 131.0, 131.1, 3)])
    assert setup_parts.read(run, "setup_build_s") is None      # empty log
    # a program from before this PR keeps no log at all
    monkeypatch.delattr(spans, "LOG")
    other = _run(3, [("dispatch", 131.0, 131.1, 3)])
    assert setup_parts.read(other, "setup_programs") is None


def test_readers_read_the_log_through_the_program_and_log_one_table(
        monkeypatch, log):
    import setup_parts

    lines = []
    fake_run = types.ModuleType("run")
    fake_run.T_START, fake_run.log = 100.0, lines.append
    monkeypatch.setitem(sys.modules, "run", fake_run)
    log.records.extend(MADE_UP)
    run = _run(3, [("dispatch", 119.0, 129.5, 0), ("dispatch", 131.0, 131.1, 3)])
    want = setup_parts.split(MADE_UP, 100.0, 131.0)
    for metric in METRICS:
        reader = importlib_reader(metric)
        value = reader.read(run)
        assert value == (want["programs"] if metric == "setup_programs"
                         else want["parts"][metric])
    table = "\n".join(lines)
    assert table.count("set-up 31.00s") == 1          # computed and logged once
    assert "remainder" in table and "programs built or loaded: 3" in table
    # the 1.5 s between the cache helper and build are named by their
    # neighbours
    assert "after setup.compile_cache, before setup.build" in table
    assert "program telemetry_step: 13.000s" in table
    assert "load 1 x 1.500s" in table
    # the log's counters have a line of it
    assert "cache hits 0, misses 0" in table


def importlib_reader(metric):
    import importlib.util

    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + metric, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_new_metric_has_a_reader_the_five_cells_and_moves_setup_s():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert [w["name"] for w in bench["workloads"]] == CELLS
    new = [m for m in bench["per_layer"] if m["layer"] == "set-up"]
    assert [m["name"] for m in new] == METRICS
    first = bench["per_layer"].index(new[0])              # appended together
    assert bench["per_layer"][first:first + len(METRICS)] == new
    for m in new:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py"))
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["workloads"] == CELLS
        assert m["source"] == ("program_counter" if m["name"] ==
                               "setup_programs" else "program_span")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # nothing else moves setup_s yet, and setup_s is in every cell
    assert [m["name"] for m in bench["per_layer"]
            if m["moves"] == "setup_s"] == METRICS
