"""Step-anatomy trace plane (telemetry/trace.py + telemetry/anatomy.py):
the measured-overlap ledger, per-scope device-time attribution, fleet
straggler report, and the perf-regression gate (scripts/perf_gate.py).

Layers under test:

- ``categorize`` — the shared op classifier, including the two bugs the
  old scripts/profile_step.py classifier carried (fusion-absorbs-matmul
  undercount; ``convert_element_type`` miscounted as a convolution);
- interval arithmetic + step-window splitting, exact on synthetic data;
- a synthetic Chrome-trace ledger whose exposed/overlapped collective
  milliseconds are computed by hand;
- ``build_op_index`` round-trips on REAL compiled programs of the
  bucketed and zero3 stream twins (named scopes + backward stamps);
- the bucketed twin executed under the profiler: trace -> ledger with
  the compiled HLO joined, zero unattributed collective time;
- fleet straggler math and the bound-verdict policy on synthetic spans;
- the ``warn_exposed_comm`` guardrail (fire/no-fire/tolerance checks);
- scripts/perf_gate.py: identity pass, synthetic step-time and
  exposed-comm regressions fail, noise-aware tolerance clamps;
- committed-artifact pins: ANATOMY_r17.json acceptance (all four arms,
  zero unattributed, measured in-backward bucket-RS time) and the
  PROFILE_r17.json equivalence pin re-derived from the committed trace.
"""

import glob
import gzip
import importlib.util
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import pytest

from dinov3_tpu.telemetry.anatomy import (
    CATEGORIES,
    anatomy_ledger,
    build_op_index,
    categorize,
    emit_step_anatomy,
    fleet_report,
    intersect_length,
    ledger_summary,
    load_span_streams,
    merge_intervals,
    round_floats,
    step_windows,
)
from dinov3_tpu.telemetry.trace import (
    Trace,
    TraceEvent,
    find_trace_file,
    load_trace,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------- categorize ----------------


def test_categorize_buckets():
    assert categorize("all-reduce.17") == "collective"
    assert categorize("reduce-scatter.3") == "collective"
    assert categorize("collective-permute.1") == "collective"
    assert categorize("dot.42") == "matmul/conv"
    assert categorize("loop_convolution_fusion.2") == "matmul/conv"
    assert categorize("softmax_fusion") == "softmax/exp"
    assert categorize("exponential.1") == "softmax/exp"
    assert categorize("layer_norm_fusion") == "norm/reduce"
    assert categorize("multiply_reduce_fusion") == "norm/reduce"
    assert categorize("copy.1") == "copy/layout"
    assert categorize("transpose.9") == "copy/layout"
    assert categorize("loop_add_fusion.3") == "fusion/elementwise"
    assert categorize("custom-call.3") == "other"
    for name in ("dot.1", "fusion.2", "all-reduce.1", "whatever"):
        assert categorize(name) in CATEGORIES


def test_categorize_fixes_old_profile_step_bugs():
    # bug 1 (undercount): a fusion kind-name carrying a dot/conv token
    # was binned fusion/elementwise by the old flat classifier
    assert categorize("convolution_add_fusion.1") == "matmul/conv"
    # ...and a fusion whose BODY contains a dot (kind-name hides it)
    # is forced to matmul/conv via the HLO op index's fusion_dotty
    assert categorize("loop_add_fusion.1", fusion_dotty=True) \
        == "matmul/conv"
    # bug 2 (miscount): bare '"conv" in name' claimed every
    # convert_element_type as a convolution
    assert categorize("convert_element_type.5") == "copy/layout"
    assert categorize("convert.2") == "copy/layout"


# ---------------- interval arithmetic ----------------


def test_merge_intervals():
    assert merge_intervals([(5, 15), (0, 10), (20, 30), (30, 40),
                            (50, 50)]) == [(0, 15), (20, 40)]
    assert merge_intervals([]) == []
    assert merge_intervals([(3, 1)]) == []


def test_intersect_length_exact():
    merged = merge_intervals([(0, 15), (20, 40)])
    assert intersect_length(3, 25, merged) == (15 - 3) + (25 - 20)
    assert intersect_length(40, 60, merged) == 0.0
    assert intersect_length(-5, 0, merged) == 0.0
    assert intersect_length(0, 100, merged) == 15 + 20
    assert intersect_length(10, 10, merged) == 0.0


def _ev(name, ts, dur, pid=1, tid=0, **kw):
    return TraceEvent(name=name, pid=pid, tid=tid, ts=float(ts),
                      dur=float(dur), **kw)


def test_step_windows_largest_gaps():
    evs = [_ev("a", 0, 10), _ev("b", 12, 10), _ev("c", 1000, 10),
           _ev("d", 1015, 10), _ev("e", 2000, 10)]
    wins = step_windows(evs, 3)
    assert len(wins) == 3
    # each cluster lands whole in its own window
    for cluster, (w0, w1) in zip(([0, 12], [1000, 1015], [2000]), wins):
        for t in cluster:
            assert w0 <= t < w1
    # no n_steps, or too few events to split: one window
    assert len(step_windows(evs, None)) == 1
    assert len(step_windows(evs[:2], 3)) == 1
    assert step_windows([], 4) == []


# ---------------- synthetic-trace ledger: exact math ----------------


def _synthetic_trace():
    """One device pid, two steps. Step 0: a 100 ms collective
    (0..100 ms) half-covered by a 100 ms compute fusion (50..150 ms) ->
    50 ms overlapped, 50 ms exposed. Step 1 (after a long gap): a
    100 ms collective with no concurrent compute -> fully exposed."""
    events = [
        _ev("all-reduce.1", 0, 100_000),
        _ev("loop_add_fusion.1", 50_000, 100_000),
        _ev("all-reduce.2", 1_000_000, 100_000),
    ]
    return Trace(events=events, process_names={1: "/device:TPU:0"},
                 thread_names={}, path="synthetic")


def test_synthetic_ledger_exact_overlap_math():
    ledger = anatomy_ledger(_synthetic_trace(), n_steps=2)
    assert ledger["schema"] == "anatomy/v1"
    assert ledger["n_steps"] == 2 and ledger["n_timelines"] == 1
    assert ledger["hlo_joined"] is False
    s0, s1 = ledger["steps"]
    c0 = s0["collectives"]["unscoped"]  # no HLO index -> "unscoped"
    assert c0["ms"] == pytest.approx(100.0)
    assert c0["overlapped_ms"] == pytest.approx(50.0)
    assert c0["exposed_ms"] == pytest.approx(50.0)
    assert c0["overlap_frac"] == pytest.approx(0.5)
    assert s0["device_busy_ms"] == pytest.approx(200.0)
    assert s0["exposed_comm_frac"] == pytest.approx(50.0 / 200.0)
    assert s0["device_ms"]["fusion/elementwise"] == pytest.approx(100.0)
    c1 = s1["collectives"]["unscoped"]
    assert c1["exposed_ms"] == pytest.approx(100.0)
    assert c1["overlapped_ms"] == pytest.approx(0.0)
    assert s1["exposed_comm_frac"] == pytest.approx(1.0)
    # no index at all -> nothing can be "unattributed"
    assert ledger["unattributed_collective_ms"] == 0.0

    summary = ledger_summary(ledger)
    assert summary["schema"] == "anatomy-summary/v1"
    agg = summary["collectives"]["unscoped"]
    assert agg["ms_per_step"] == pytest.approx(100.0)
    assert agg["exposed_ms_per_step"] == pytest.approx(75.0)
    assert agg["overlap_frac"] == pytest.approx(50.0 / 200.0)
    assert summary["exposed_comm_frac"] == pytest.approx(150.0 / 300.0)
    assert summary["step_wall_ms"]["mean"] == pytest.approx(
        (150.0 + 100.0) / 2)


# the compiled text behind ``_synthetic_trace``: the first collective
# belongs to the update, the fusion to the student's backward pass, the
# second collective to a loss; a while that holds nothing but names a phase
_SYNTHETIC_HLO = """
HloModule jit_step

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %all-reduce.1 = f32[8]{0} all-reduce(%p), to_apply=%add, metadata={op_name="jit(step)/update/bucket_pack/psum"}
  %loop_add_fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(student_backbone))/blocks_0/add_any"}
  %all-reduce.2 = f32[8]{0} all-reduce(%p), to_apply=%add, metadata={op_name="jit(step)/jvp(losses)/dino_loss/psum"}
  %while.3 = f32[8]{0} while(%p), condition=%c, body=%b, metadata={op_name="jit(step)/jvp(losses)/ibot_loss/while"}
  ROOT %copy.4 = f32[8]{0} copy(%p)
}
"""


def test_synthetic_ledger_phase_table():
    """One classification, two readers: the op index carries
    ``utils.classify_step_phase`` of each instruction's op_name, and the
    ledger sums leaf device time by it."""
    index = build_op_index(_SYNTHETIC_HLO)
    assert (index["all-reduce.1"]["phase"],
            index["all-reduce.1"]["direction"]) == ("update", "fwd")
    assert (index["loop_add_fusion.1"]["phase"],
            index["loop_add_fusion.1"]["direction"]) \
        == ("student_backbone", "bwd")
    assert index["copy.4"]["phase"] is None

    trace = _synthetic_trace()
    # a while over the whole first step and a nameless copy in the second
    trace.events.extend([_ev("while.3", 0, 150_000),
                         _ev("copy.4", 1_100_000, 10_000)])
    ledger = anatomy_ledger(trace, hlo_text=_SYNTHETIC_HLO, n_steps=2)
    s0, s1 = ledger["steps"]
    # the container is not summed: its body's events are on the timeline
    assert s0["phases"] == {
        "update/fwd": pytest.approx(100.0),
        "student_backbone/bwd": pytest.approx(100.0)}
    assert s1["phases"] == {"losses/fwd": pytest.approx(100.0),
                            "unattributed": pytest.approx(10.0)}
    summary = ledger_summary(ledger)
    assert summary["phases_ms_per_step"] == {
        "update/fwd": pytest.approx(50.0),
        "student_backbone/bwd": pytest.approx(50.0),
        "losses/fwd": pytest.approx(50.0),
        "unattributed": pytest.approx(5.0)}
    # without the compiled text there is no phase to read
    bare = anatomy_ledger(_synthetic_trace(), n_steps=2)
    assert all(s["phases"] == {} for s in bare["steps"])
    assert "phases_ms_per_step" not in ledger_summary(bare)


def test_trace_reader_roundtrip(tmp_path):
    """Write a Chrome-trace JSON the way jax lays it out; find + load
    it back; .pb paths raise the pointed no-TF-protos error."""
    raw = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 7,
         "args": {"name": "/host:CPU"}},
        {"name": "thread_name", "ph": "M", "pid": 7, "tid": 3,
         "args": {"name": "tf_XLATfrtCpuClient_0"}},
        {"name": "fusion.1", "ph": "X", "pid": 7, "tid": 3, "ts": 10.0,
         "dur": 5.0, "args": {"hlo_op": "fusion.1",
                              "hlo_module": "jit_step"}},
        {"name": "zero-dur", "ph": "X", "pid": 7, "tid": 3, "ts": 1.0,
         "dur": 0.0},
        {"name": "counter", "ph": "C", "pid": 7, "tid": 3, "ts": 2.0},
    ]}
    d = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump(raw, f)
    found = find_trace_file(str(tmp_path / "trace"))
    assert found and found.endswith(".trace.json.gz")
    tr = load_trace(found)
    assert len(tr.events) == 1  # ph=="X" with dur>0 only
    assert tr.events[0].op_key == "fusion.1"
    assert tr.modules() == {"jit_step": 5.0}
    assert list(tr.timelines(tr.op_events())) \
        == ["/host:CPU/tf_XLATfrtCpuClient_03"]
    with pytest.raises(ValueError, match="xplane.pb"):
        load_trace("some/xplane.pb")


def test_emit_step_anatomy_writes_ledger_and_span(tmp_path):
    raw = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"name": "all-reduce.1", "ph": "X", "pid": 1, "tid": 0,
         "ts": 0.0, "dur": 100.0},
        {"name": "dot.1", "ph": "X", "pid": 1, "tid": 0,
         "ts": 0.0, "dur": 100.0},
    ]}
    d = tmp_path / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    with gzip.open(d / "vm.trace.json.gz", "wt") as f:
        json.dump(raw, f)

    emitted = []

    class FakeTracer:
        def emit(self, rec):
            emitted.append(rec)

    summary = emit_step_anatomy(str(tmp_path), n_steps=1,
                                tracer=FakeTracer(), iteration=12)
    assert summary is not None
    assert (tmp_path / "anatomy.json").exists()
    with open(tmp_path / "anatomy.json") as f:
        assert json.load(f)["schema"] == "anatomy/v1"
    assert len(emitted) == 1 and emitted[0]["name"] == "anatomy"
    assert emitted[0]["iteration"] == 12
    assert emitted[0]["summary"]["collectives"]
    # empty dir -> None, no artifacts
    assert emit_step_anatomy(str(tmp_path / "nothing")) is None


# ---------------- op-index round-trip on real compiled twins ----------


@pytest.fixture(scope="module")
def mesh8():
    from dinov3_tpu.parallel.context import (
        get_current_mesh,
        set_current_mesh,
    )
    from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh

    prev = get_current_mesh()
    mesh = build_mesh(MeshSpec(data=8))
    set_current_mesh(mesh)
    yield mesh
    set_current_mesh(prev)


def _bucketed_twin_compiled(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.models.streaming import (
        bucketed_stream_scan,
        pack_stream_buckets,
    )
    from dinov3_tpu.parallel.sharding import UPDATE_SHARD_AXES

    n_blocks, n_buckets, dp = 8, 4, 8
    stack = {"attn": {"qkv": {"kernel": jnp.zeros(
        (n_blocks, 16, 48), jnp.bfloat16)}},
        "mlp": {"fc1": {"kernel": jnp.zeros(
            (n_blocks, 16, 64), jnp.bfloat16)}}}
    shards = jax.eval_shape(
        lambda s: pack_stream_buckets(s, n_buckets, dp), stack)
    x = jax.ShapeDtypeStruct((dp * 4,), jnp.float32)
    axes = tuple(a for a in UPDATE_SHARD_AXES if a in mesh.shape)

    def loss(shards, x):
        return jnp.sum(bucketed_stream_scan(
            shards, x, mesh=mesh, prefetch=True))

    with mesh:
        compiled = jax.jit(
            jax.grad(loss),
            in_shardings=(NamedSharding(mesh, P(None, axes)),
                          NamedSharding(mesh, P())),
            out_shardings=NamedSharding(mesh, P(None, axes)),
        ).lower(shards, x).compile()
    in_shardings = (NamedSharding(mesh, P(None, axes)),
                    NamedSharding(mesh, P()))
    args = (jax.device_put(jnp.zeros(shards.shape, shards.dtype),
                           in_shardings[0]),
            jax.device_put(jnp.zeros(x.shape, x.dtype), in_shardings[1]))
    return compiled, args


def test_op_index_roundtrip_bucketed_traced(mesh8):
    """The full dynamic round-trip on the bucketed overlap twin:
    execute the compiled grad under the profiler, join the ledger
    against the compiled HLO — every collective event must land in a
    named scope (zero unattributed), bucket scopes among them, and the
    measured backward interval must contain bucket-scoped collective
    time (the dynamic twin of COST_BUCKET_r13's in-backward-loop
    placement)."""
    compiled, args = _bucketed_twin_compiled(mesh8)
    hlo = compiled.as_text()

    idx = build_op_index(hlo)
    colls = {n: i for n, i in idx.items() if i["category"] == "collective"}
    assert colls, "compiled twin lost its collectives"
    assert any((i["scope"] or "").startswith("bucket")
               for i in colls.values()), sorted(
        {i["scope"] for i in colls.values()})
    assert any(i["backward"] for i in idx.values())

    jax.block_until_ready(compiled(*args))  # warmup outside the window
    tdir = tempfile.mkdtemp(prefix="anat_test_", dir="/tmp")
    jax.profiler.start_trace(tdir)
    for _ in range(2):
        jax.block_until_ready(compiled(*args))
    jax.profiler.stop_trace()

    ledger = anatomy_ledger(tdir, hlo_text=hlo, n_steps=2)
    assert ledger["hlo_joined"] is True
    assert ledger["n_steps"] == 2
    assert ledger["unattributed_collective_ms"] == 0.0
    summary = ledger_summary(ledger)
    scopes = set(summary["collectives"])
    assert any(s.startswith("bucket") for s in scopes), scopes
    total_coll = sum(c["ms_per_step"]
                     for c in summary["collectives"].values())
    assert total_coll > 0
    import shutil

    shutil.rmtree(tdir, ignore_errors=True)


def test_op_index_roundtrip_zero3_compiled(mesh8):
    """zero3 stream twin (streamed_block_scan grad): the double-buffer
    gathers index with zero3_* scopes; their transposed reduce-scatters
    carry the backward stamp."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.models.streaming import streamed_block_scan
    from dinov3_tpu.parallel.sharding import zero3_leaf_spec

    L, D = 4, 16
    stack = {"w": jax.ShapeDtypeStruct((L, D, D), jnp.bfloat16)}

    def apply(p, x):
        return x @ p["w"].astype(x.dtype)

    def loss(stack, x):
        y = streamed_block_scan(apply, stack, x, L, mesh8)
        return jnp.sum(y.astype(jnp.float32))

    def stack_sharding(p):
        spec = zero3_leaf_spec(
            p.shape, ("layers",) + (None,) * (len(p.shape) - 1), mesh8)
        return NamedSharding(mesh8, spec if spec is not None else P())

    x = jax.ShapeDtypeStruct((8, D), jnp.bfloat16)
    with mesh8:
        compiled = jax.jit(
            jax.grad(loss),
            in_shardings=(jax.tree.map(stack_sharding, stack),
                          NamedSharding(mesh8, P("data"))),
        ).lower(stack, x).compile()
    idx = build_op_index(compiled.as_text())
    colls = {n: i for n, i in idx.items() if i["category"] == "collective"}
    assert colls
    scopes = {i["scope"] for i in colls.values()}
    assert any((s or "").startswith("zero3") for s in scopes), scopes
    assert any(i["backward"] for i in colls.values()), colls


# ---------------- fleet report ----------------


def _dispatch_stream(step_s, n=6, t0=0.0):
    return [{"name": "dispatch", "iteration": i, "t": t0 + i * step_s}
            for i in range(n)]


def test_fleet_straggler_math():
    streams = {f"rank{i}": _dispatch_stream(0.100) for i in range(5)}
    streams["rank5"] = _dispatch_stream(0.400)  # the straggler
    rep = fleet_report(streams)
    assert rep["schema"] == "fleet/v1" and rep["n_hosts"] == 6
    assert rep["hosts"]["rank0"]["step_ms"]["mean"] == pytest.approx(100.0)
    assert rep["hosts"]["rank5"]["step_ms"]["mean"] == pytest.approx(400.0)
    # 5 hosts at 100 ms + 1 at 400: mean 150, std sqrt(12500) -> z 2.236
    assert rep["fleet_step_ms"]["mean"] == pytest.approx(150.0)
    assert rep["hosts"]["rank5"]["straggler_z"] == pytest.approx(
        2.2360679, rel=1e-5)
    assert rep["stragglers"] == ["rank5"]
    assert all(rep["hosts"][f"rank{i}"]["straggler_z"] < 0
               for i in range(5))


def test_fleet_single_host_z_and_verdicts():
    one = {"rank0": _dispatch_stream(0.100)}
    rep = fleet_report(one)
    assert rep["hosts"]["rank0"]["straggler_z"] == 0.0
    assert rep["verdict"] == "compute-bound"
    # measured exposed comm above tolerance -> comm-bound
    rep = fleet_report(one, anatomy={"exposed_comm_frac": 0.6})
    assert rep["verdict"] == "comm-bound"
    # data-wait dominating the pitch wins over comm: input-bound
    hungry = {"rank0": _dispatch_stream(0.100)
              + [{"name": "data_wait", "dur_ms": 60.0}] * 5}
    rep = fleet_report(hungry, anatomy={"exposed_comm_frac": 0.6})
    assert rep["verdict"] == "input-bound"
    assert rep["max_data_wait_frac"] == pytest.approx(0.6)


def test_load_span_streams_ranks_roles_torn_lines(tmp_path):
    tdir = tmp_path / "telemetry"
    tdir.mkdir()
    with open(tdir / "spans.jsonl", "w") as f:
        f.write(json.dumps({"v": 1, "name": "dispatch", "iteration": 0,
                            "t": 0.0}) + "\n")
        f.write(json.dumps({"v": 2, "name": "dispatch"}) + "\n")  # wrong v
        f.write('{"torn": ')  # live-writer tail
    with open(tdir / "spans.rank1.jsonl", "w") as f:
        f.write(json.dumps({"v": 1, "name": "dispatch", "iteration": 0,
                            "t": 0.0, "role": "train"}) + "\n")
        f.write(json.dumps({"v": 1, "name": "dispatch", "iteration": 1,
                            "t": 0.1, "role": "serve"}) + "\n")
    streams = load_span_streams(str(tmp_path))
    assert sorted(streams) == ["rank0", "rank1"]
    assert len(streams["rank0"]) == 1
    assert len(streams["rank1"]) == 1  # serve-role record filtered


# ---------------- warn_exposed_comm guardrail ----------------


def test_warn_exposed_comm_fire_and_quiet(recwarn):
    from dinov3_tpu.configs import get_default_config
    from dinov3_tpu.configs.config import warn_exposed_comm

    cfg = get_default_config()  # exposed_comm_tol: 0.25
    summary = {
        "exposed_comm_frac": 0.60,
        "collectives": {
            "bucket_pack": {"exposed_ms_per_step": 9.0, "overlap_frac": 0.1},
            "other": {"exposed_ms_per_step": 2.0, "overlap_frac": 0.0},
        },
    }
    msg = warn_exposed_comm(cfg, summary)
    assert msg and "bucket_pack" in msg and "0.25" in msg
    assert any("exposed comm" in str(w.message) for w in recwarn.list)
    # within tolerance: silent
    assert warn_exposed_comm(cfg, {"exposed_comm_frac": 0.1,
                                   "collectives": {}}) is None
    # anatomy plane off: never fires, even over tolerance
    cfg.telemetry.anatomy = False
    assert warn_exposed_comm(cfg, summary) is None


def test_warn_exposed_comm_tol_validation(recwarn):
    from dinov3_tpu.configs import get_default_config
    from dinov3_tpu.configs.config import warn_exposed_comm

    cfg = get_default_config()
    assert warn_exposed_comm(cfg) is None  # default tol is sane
    cfg.telemetry.exposed_comm_tol = 1.5
    msg = warn_exposed_comm(cfg)
    assert msg and "exposed_comm_tol" in msg


# ---------------- perf gate ----------------


def _gate_baseline(mean=100.0, std=1.0, n=4, exposed=0.2):
    return {"arms": {"a": {"anatomy": {
        "schema": "anatomy-summary/v1", "n_steps": n,
        "step_wall_ms": {"mean": mean, "std": std},
        "exposed_comm_frac": exposed}}}}


def test_perf_gate_pass_and_regressions():
    pg = _load_script("perf_gate")
    base = _gate_baseline()
    assert pg.gate(base, base)["passed"] is True
    # within the 3% floor: passes
    assert pg.gate(base, _gate_baseline(mean=102.0))["passed"] is True
    # a 10% step-time regression ALWAYS fails (tolerance cap 8%)
    r = pg.gate(base, _gate_baseline(mean=110.0))
    assert r["passed"] is False
    assert any("step time regressed" in c["status"] for c in r["checks"])
    # exposed-comm drift beyond the absolute tolerance fails
    r = pg.gate(base, _gate_baseline(exposed=0.2 + 0.10))
    assert r["passed"] is False
    assert any("exposed-comm" in c["status"] for c in r["checks"])
    # ...but small drift within it passes
    assert pg.gate(base, _gate_baseline(exposed=0.24))["passed"] is True
    # an arm missing from the fresh record is skipped, not failed
    r = pg.gate(base, {"arms": {}})
    assert r["passed"] is True and "skipped" in r["checks"][0]["status"]


def test_perf_gate_noise_aware_tolerance():
    pg = _load_script("perf_gate")
    quiet = {"n_steps": 4, "step_wall_ms": {"mean": 100.0, "std": 0.0}}
    assert pg.step_time_tolerance(quiet) == pytest.approx(0.03)
    noisy = {"n_steps": 4, "step_wall_ms": {"mean": 100.0, "std": 40.0}}
    assert pg.step_time_tolerance(noisy) == pytest.approx(0.08)  # capped
    mid = {"n_steps": 4, "step_wall_ms": {"mean": 100.0, "std": 4.0}}
    # 3 * 0.04 / sqrt(4) = 0.06: between floor and cap
    assert pg.step_time_tolerance(mid) == pytest.approx(0.06)


def test_perf_gate_self_check_on_committed_baseline(capsys):
    pg = _load_script("perf_gate")
    with open(os.path.join(REPO, "ANATOMY_r17.json")) as f:
        baseline = json.load(f)
    assert pg.self_check(baseline) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["self_check"] == "ok" and out["n_arms"] >= 4


# ---------------- committed-artifact pins ----------------


def test_anatomy_r17_acceptance():
    """ANATOMY_r17.json: measured ledgers for all four arms, every
    collective attributed to a scope (zero unattributed ms), and the
    bucketed overlap twin's reduce-scatter time measured INSIDE the
    backward interval — consistent with the static COST_BUCKET_r13
    census."""
    with open(os.path.join(REPO, "ANATOMY_r17.json")) as f:
        rec = json.load(f)
    arms = rec["arms"]
    assert set(arms) >= {"replicated", "flat", "bucketed", "zero3"}
    for name, arm in arms.items():
        a = arm["anatomy"]
        assert a["schema"] == "anatomy-summary/v1", name
        assert a["hlo_joined"] is True, name
        assert a["unattributed_collective_ms"] == 0.0, name
        assert a["collectives"], name
        assert a["step_wall_ms"]["mean"] > 0, name
    # coalescing story in measured events: per-leaf arm carries far
    # more collective launches than the bucketed arm
    flat_n = sum(c["n_events"]
                 for c in arms["flat"]["anatomy"]["collectives"].values())
    bk_n = sum(c["n_events"]
               for c in arms["bucketed"]["anatomy"]["collectives"].values())
    assert flat_n > 3 * bk_n, (flat_n, bk_n)
    assert any(s.startswith("bucket")
               for s in arms["bucketed"]["anatomy"]["collectives"])
    assert any(s.startswith("zero3")
               for s in arms["zero3"]["anatomy"]["collectives"])
    # the measured-overlap column: bucket-scoped RS inside the measured
    # backward interval, matching the static in-backward-loop placement
    cons = rec["consistency"]
    assert cons["bucketed_rs_inside_backward_ms"] > 0
    assert cons["cost_bucket_r13_in_backward_loop_ops"] >= 1
    # the real-trainer dryrun wiring banked too
    assert rec["dryrun"]["anatomy"]["n_steps"] == 3
    assert rec["dryrun"]["fleet"]["verdict"] in (
        "input-bound", "comm-bound", "compute-bound")


def test_profile_r17_equivalence_pin():
    """The committed PROFILE_r17.json re-derives byte-identically from
    the committed trace through the shared parser (name-only path: no
    HLO join, so the derivation depends on nothing but the trace and
    the parser) — the pin that freezes parser semantics."""
    ps = _load_script("profile_step")
    trace = os.path.join(REPO, "docs", "profiles",
                         "PROFILE_r17_trace.json.gz")
    rec = ps.breakdown(trace, 3, None)
    with open(os.path.join(REPO, "PROFILE_r17.json")) as f:
        committed = json.load(f)
    assert rec == committed
    assert committed["schema"] == "profile/v2"
    assert committed["n_steps"] == 3
    # the trace is a real vit_test dp=8 train window: it must carry
    # collective + matmul device time
    cats = committed["by_category_ms_per_step"]
    assert cats.get("collective", 0) > 0
    assert cats.get("matmul/conv", 0) > 0


def test_round_floats():
    assert round_floats({"a": [1.23456789, {"b": (2.0000001,)}],
                         "c": "s", "d": 3}) \
        == {"a": [1.2346, {"b": [2.0]}], "c": "s", "d": 3}
