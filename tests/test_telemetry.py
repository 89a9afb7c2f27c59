"""Async telemetry engine (dinov3_tpu/telemetry/): on-device metrics
ring, host phase-span tracer, memory accounting.

The async metrics path is the default (``telemetry.async_metrics``
auto=on); the per-step ``float(v)`` fetch stays as the oracle behind
=false. These tests pin:
- ring wraparound + the RingReader's exact-window replay (iteration
  stamps verified per slot; cursor drift and too-wide windows raise);
- oracle-vs-ring BITWISE metric equality over a multi-step dryrun on
  the 8-device mesh (same seeded program, per-step ``float(v)`` values
  vs flushed rows);
- the device-side finite-flag: consecutive non-finite ``total_loss``
  streak counts across steps AND across flush boundaries (the 3-strike
  abort's flush-granularity latency can delay the abort, never miss
  it);
- copy census of the exact compiled telemetry step: the ring write is
  attributed to the "telemetry" named-scope category
  (utils.classify_copy) and the ceiling is pinned a small delta over
  the oracle step — no copy-census regression, no new "large" class;
- span JSONL schema + heartbeat mtime advance, from both the unit
  tracer and a short CPU dryrun of train/train.py (the acceptance
  artifact: spans + heartbeat + memory records + exact recorded
  losses + --benchmark under async metrics);
- resume mid-ring determinism: a run killed mid flush-window resumes
  from the checkpoint and records the same per-iteration losses as the
  uninterrupted run;
- the --benchmark explicit fence (StepTimer) agreeing with the old
  free-ride-on-the-metrics-fetch timing on the oracle path, where both
  exist;
- the ``warn_telemetry_flush_period`` config guardrail;
- the blocking-fetch funnel (host_sync) and the memory instruments.
"""

import json
import math
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.configs import apply_dot_overrides, get_default_config
from dinov3_tpu.telemetry import (
    RingReader,
    SpanTracer,
    StepTimer,
    blocking_fetch,
    host_sync_stats,
    make_ring,
    per_device_state_bytes,
    sample_memory,
    telemetry_wished,
    write_row,
)
from test_fused_update import smol_cfg

TINY_TRAIN = [
    "student.arch=vit_test", "student.patch_size=4",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2", "train.batch_size_per_device=2",
    "optim.scaling_rule=none", "data.backend=synthetic",
    "optim.epochs=1", "optim.warmup_epochs=0",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
    "dino.head_bottleneck_dim=16",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
    "ibot.head_bottleneck_dim=16",
]


def _setup(extra, batch_size=8, devices=None):
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    cfg = smol_cfg(extra)
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, batch_size, seed=0).items()}
    return build_train_setup(cfg, batch, devices=devices), batch


# ---------------- ring unit behavior ----------------

def _mk(loss, aux=None):
    return {"total_loss": jnp.float32(loss),
            "aux": jnp.float32(loss * 2 if aux is None else aux)}


NAMES = ["aux", "total_loss"]  # sorted metric-name order


def test_ring_wraparound_and_reader_windows():
    """10 writes through a K=4 ring, flushed in full + partial windows:
    every row comes back exact, in iteration order, stamps verified."""
    K = 4
    ring = jax.device_put(make_ring(len(NAMES), K))
    step = jax.jit(
        lambda r, it, v: write_row(r, it, _mk(v), NAMES))
    reader = RingReader(NAMES, K)
    got_its, got_loss = [], []
    for it in range(10):
        ring = step(ring, jnp.int32(it), jnp.float32(it + 0.5))
        if it in (3, 7, 9):  # two full windows + one partial
            its, rows, streak = reader.flush(ring, it + 1)
            assert streak == 0
            got_its += its.tolist()
            got_loss += rows[:, NAMES.index("total_loss")].tolist()
            np.testing.assert_array_equal(
                rows[:, NAMES.index("aux")],
                2.0 * np.asarray(its, np.float32) + 1.0)
    assert got_its == list(range(10))
    np.testing.assert_array_equal(
        got_loss, np.arange(10, dtype=np.float32) + 0.5)
    assert reader.cursor == 10


def test_ring_reader_rejects_bad_windows():
    K = 4
    ring = jax.device_put(make_ring(len(NAMES), K))
    step = jax.jit(lambda r, it: write_row(r, it, _mk(1.0), NAMES))
    for it in range(6):
        ring = step(ring, jnp.int32(it))
    # window wider than the ring: a missed flush, structural
    with pytest.raises(RuntimeError, match="does not fit the ring"):
        RingReader(NAMES, K).flush(ring, 6)
    # cursor drift: slots 0,1 were overwritten by iterations 4,5
    with pytest.raises(RuntimeError, match="stamp mismatch"):
        RingReader(NAMES, K, start_iteration=0).flush(ring, 2)
    # the aligned reader is fine
    its, rows, _ = RingReader(NAMES, K, start_iteration=4).flush(ring, 6)
    assert its.tolist() == [4, 5]


def test_finite_flag_streak_counts_across_flushes():
    """The device-side non-finite streak: grows on consecutive
    non-finite total_loss, resets on finite, and counts ACROSS flush
    boundaries (flushing reads, never resets)."""
    K = 3
    ring = jax.device_put(make_ring(len(NAMES), K))
    step = jax.jit(
        lambda r, it, v: write_row(r, it, _mk(v, aux=0.0), NAMES))
    seq = [1.0, float("nan"), float("inf"), 1.0, float("nan"),
           float("nan")]
    want_streak = [0, 1, 2, 0, 1, 2]
    for it, (v, want) in enumerate(zip(seq, want_streak)):
        ring = step(ring, jnp.int32(it), jnp.float32(v))
        assert int(jax.device_get(ring.nonfinite_streak)) == want
    # a flush mid-streak surfaces the streak without resetting it...
    reader = RingReader(NAMES, K, start_iteration=3)
    its, rows, streak = reader.flush(ring, 6)  # window [3, 6)
    assert streak == 2
    assert np.isnan(rows[-1, NAMES.index("total_loss")])
    # ...and the device streak keeps counting across the flush boundary:
    # a third consecutive non-finite step crosses the 3-strike threshold
    # even though a flush intervened
    ring = step(ring, jnp.int32(6), jnp.float32(float("nan")))
    assert int(jax.device_get(ring.nonfinite_streak)) == 3


def test_ring_scalar_only_guard():
    ring = jax.device_put(make_ring(1, 2))
    with pytest.raises(ValueError, match="scalar metrics only"):
        jax.jit(lambda r: write_row(
            r, jnp.int32(0), {"total_loss": jnp.zeros((2,))},
            ["total_loss"]))(ring)


# ---------------- full-step: equality, census, wiring ----------------

def test_oracle_vs_ring_bitwise_metric_equality(eight_devices):
    """Same seeded program, 5 steps on the 8-device mesh: the flushed
    ring rows equal the oracle's per-step float(v) fetches BITWISE."""
    from dinov3_tpu.train import put_batch

    extra = ["parallel.data=-1", "telemetry.flush_every=3"]
    setup_o, batch = _setup(extra, 8, eight_devices)
    d = put_batch(batch, setup_o.batch_shardings)
    oracle = {}
    state = setup_o.state
    for it in range(5):
        state, metrics = setup_o.step_fn(
            state, d, setup_o.scalars(it), jax.random.key(1))
        oracle[it] = {k: float(v) for k, v in metrics.items()}

    setup_r, _ = _setup(extra, 8, eight_devices)
    plan = setup_r.telemetry()
    assert plan is not None and plan.ring_len == 3
    assert plan.metric_names == sorted(oracle[0])
    ring = plan.init_ring()
    reader = plan.reader()
    state = setup_r.state
    flushed: dict = {}
    for it in range(5):
        state, ring = plan.step_fn(
            state, ring, d, setup_r.scalars(it), jax.random.key(1))
        if it in (2, 4):
            its, rows, streak = reader.flush(ring, it + 1)
            assert streak == 0
            for j, row_it in enumerate(its):
                flushed[int(row_it)] = dict(zip(plan.metric_names, rows[j]))
    assert set(flushed) == set(oracle)
    for it in oracle:
        for k, want in oracle[it].items():
            assert float(flushed[it][k]) == want, (it, k)


def test_classify_collective_attribution():
    from dinov3_tpu.utils import classify_collective

    ent = "ENTRY %main.1 (p0: f32[8]) -> f32[8] {\n"
    cases = {
        "  %ar = f32[128]{0} all-reduce(f32[128]{0} %x), replica_groups={}":
            "all_reduce",
        "  %ars = (f32[128], f32[128]) all-reduce-start(f32[128] %x)":
            "all_reduce",
        "  %rs = f32[16]{0} reduce-scatter(f32[128]{0} %x), dimensions={0}":
            "reduce_scatter",
        "  %ag = f32[128]{0} all-gather(f32[16]{0} %x), dimensions={0}":
            "all_gather",
        "  %cp = f32[16]{0} collective-permute(f32[16]{0} %x)": "ppermute",
        "  %aa = f32[16]{0} all-to-all(f32[16]{0} %x)": "all_to_all",
        "  %cb = f32[16]{0} collective-broadcast(f32[16]{0} %x)":
            "unattributed",
        # -done halves and non-collectives don't count
        "  %ard = f32[128]{0} all-reduce-done((f32[128], f32[128]) %ars)":
            None,
        "  %f = f32[128]{0} fusion(f32[128]{0} %x), kind=kLoop": None,
        "  %red = f32[] reduce(f32[128]{0} %x, f32[] %c)": None,
    }
    for line, want in cases.items():
        assert classify_collective(line) == want, line
    # whole-module census over the same lines
    from dinov3_tpu.utils import hlo_collective_census

    census = hlo_collective_census(ent + "\n".join(cases) + "\n}")
    assert census["by_class"]["all_reduce"]["ops"] == 2
    assert census["by_class"]["reduce_scatter"]["ops"] == 1
    assert census["by_class"]["reduce_scatter"]["bytes"] == 16 * 4
    assert census["unattributed"] == 1


def test_telemetry_step_census_pinned(eight_devices):
    """Copy census of the EXACT compiled telemetry step: the ring
    writes carry the "telemetry" named-scope attribution, the total is
    a small bounded delta over the oracle step, and no new "large"
    copies appear (donation keeps the ring write in place)."""
    from dinov3_tpu.train import put_batch
    from dinov3_tpu.utils import classify_copy, hlo_copy_census

    assert classify_copy(
        ' %dynamic-update-slice.1 = f32[4,6]{1,0} dynamic-update-slice('
        '...), metadata={op_name="jit(step)/telemetry_ring/dus"}'
    ) == "telemetry"

    setup, batch = _setup(["parallel.data=-1", "telemetry.flush_every=4"],
                          8, eight_devices)
    d = put_batch(batch, setup.batch_shardings)
    args_o = (setup.state, d, setup.scalars(0), jax.random.key(0))
    text_o = setup.step_fn.lower(*args_o).compile().as_text()
    plan = setup.telemetry()
    ring = plan.init_ring()
    text_t = plan.step_fn.lower(
        setup.state, ring, d, setup.scalars(0),
        jax.random.key(0)).compile().as_text()

    # the ring write is IN the compiled program under its named scope...
    assert "telemetry_ring" in text_t
    assert "telemetry_ring" not in text_o
    census_o = hlo_copy_census(text_o)
    census_t = hlo_copy_census(text_t)
    # ...and costs at most a handful of attributed copy ops: this
    # backend FUSES the two dynamic-update-slices ([1, M] row + [1]
    # stamp) into the step's fusions (0 standalone copy ops — free);
    # a backend that materializes them must land them in the
    # "telemetry" category (classify_copy above), never in
    # small/large/donation
    tele = census_t["by_category"].get("telemetry", {"ops": 0, "bytes": 0})
    assert tele["ops"] <= 8, census_t["by_category"]
    # census ceiling: no copy regression vs the oracle step beyond the
    # attributed telemetry writes and a few scheduling copies
    assert census_t["hlo_copy_total"] <= census_o["hlo_copy_total"] + 12, (
        census_o, census_t)
    large_o = census_o["by_category"].get("large", {"ops": 0})["ops"]
    large_t = census_t["by_category"].get("large", {"ops": 0})["ops"]
    assert large_t <= large_o, (census_o, census_t)


def test_setup_wiring_and_toggle(eight_devices):
    """auto-on: TrainSetup carries a lazy telemetry builder; =false
    selects the oracle (no builder); the plan memoizes."""
    setup, _ = _setup(["telemetry.flush_every=7"], 8, eight_devices)
    assert setup.telemetry_builder is not None
    plan = setup.telemetry()
    assert plan.ring_len == 7 and plan is setup.telemetry()
    assert "total_loss" in plan.metric_names
    off, _ = _setup(["telemetry.async_metrics=false"], 8, eight_devices)
    assert off.telemetry_builder is None and off.telemetry() is None
    cfg = smol_cfg()
    assert telemetry_wished(cfg)
    cfg.telemetry.async_metrics = False
    assert not telemetry_wished(cfg)


# ---------------- the short CPU dryrun of train/train.py ----------------

@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """One 6-iteration dryrun of the real trainer under async metrics
    (flush_every=4 -> one full + one partial flush), shared by the
    span/heartbeat/benchmark/loss assertions below."""
    from dinov3_tpu.train.train import main as train_main

    out = tmp_path_factory.mktemp("tele_run")
    result = train_main([
        "--output-dir", str(out), "--no-resume",
        "--record-losses", str(out / "losses.jsonl"),
        "--benchmark", "2",
    ] + TINY_TRAIN + [
        "train.OFFICIAL_EPOCH_LENGTH=6", "checkpointing.period=4",
        "telemetry.flush_every=4",
    ])
    return out, result


def test_dryrun_records_every_iteration(tiny_run):
    out, result = tiny_run
    assert result["iterations"] == 6
    assert math.isfinite(result["final_loss"])
    rows = [json.loads(l) for l in open(out / "losses.jsonl")]
    assert [r["iteration"] for r in rows] == list(range(6))
    assert all(math.isfinite(r["total_loss"]) for r in rows)
    # --benchmark produced a number through the explicit fence
    assert result.get("img_per_sec", 0) > 0


def test_dryrun_span_jsonl_schema(tiny_run):
    out, _ = tiny_run
    from dinov3_tpu.telemetry.spans import PHASES

    spans = [json.loads(l)
             for l in open(out / "telemetry" / "spans.jsonl")]
    assert spans, "dryrun must emit spans"
    names = {s["name"] for s in spans}
    # every hot-loop phase that ran appears with the shared vocabulary
    for want in ("data_wait", "h2d", "dispatch", "metrics_flush",
                 "checkpoint_save"):
        assert want in names, names
    for s in spans:
        assert isinstance(s["name"], str) and s["t"] > 0
        if s["name"] in PHASES:
            assert s["dur_ms"] >= 0
            assert s["iteration"] is None or isinstance(s["iteration"], int)
            # the monotonic start, beside the wall-clock one (additive
            # field, same schema version)
            assert isinstance(s["t_mono"], float) and s["t_mono"] > 0
            assert s["v"] == 1
    # one process, one monotonic clock: a span that started later in wall
    # time started later on it too
    timed = sorted((s for s in spans if s["name"] in PHASES),
                   key=lambda s: s["t_mono"])
    assert all(a["t"] <= b["t"] + 0.05 for a, b in zip(timed, timed[1:]))
    # memory samples ride the same stream, at setup/compile + flushes
    mem_points = [s["point"] for s in spans if s["name"] == "memory"]
    assert "setup" in mem_points and "compile" in mem_points
    assert mem_points.count("flush") >= 2
    for s in spans:
        if s["name"] == "memory":
            assert all(d["bytes_in_use"] >= 0 for d in s["devices"])


def test_dryrun_stream_starts_with_set_up(tiny_run):
    """The stream's head is the process's set-up: ``setup.*`` spans and
    JAX's ``jit.*`` by program, all started before the first dispatch,
    which is marked and is the parent of the step's own trace, lowering
    and compile; then one record of what set-up compiled, and no program
    compiled a second time once the loop was steady."""
    out, _ = tiny_run
    spans = [json.loads(l)
             for l in open(out / "telemetry" / "spans.jsonl")]
    dispatches = [s for s in spans if s["name"] == "dispatch"]
    first = dispatches[0]
    assert first["first"] is True and first["iteration"] == 0
    assert not any("first" in s for s in dispatches[1:])
    # one span path: the loop's records carry ids like set-up's
    assert len({s["id"] for s in spans if "id" in s}) == sum(
        "id" in s for s in spans) >= len(dispatches)
    head = spans[:spans.index(first)]
    names = {s["name"] for s in head}
    assert {"setup.compile_cache", "setup.data_iterator", "setup.build",
            "setup.telemetry_plan", "setup.init_ring", "jit.trace",
            "jit.lower", "jit.compile"} <= names
    for s in head:
        # the hot loop's first data_wait and the set-up memory sample lie
        # between set-up and the first dispatch too
        assert s["name"].startswith(("setup.", "jit.")) or s["name"] in (
            "data_wait", "memory", "dispatch")
        if "t_mono" in s:
            assert s["t_mono"] <= first["t_mono"] + first["dur_ms"] / 1e3
    for s in head:
        if s["name"].startswith("setup."):
            assert s["t_mono"] < first["t_mono"]
            assert s["proc"] == first["proc"] and s["v"] == 1
    step = {s["name"]: s for s in head
            if s.get("program") in ("telemetry_step", "jit(telemetry_step)")}
    assert set(step) == {"jit.trace", "jit.lower", "jit.compile"}
    assert all(s["parent"] == first["id"] for s in step.values())
    # the steady mark: what the process had built or loaded by then
    [made] = [s for s in spans if s["name"] == "setup.compiled"]
    assert made["iteration"] == 0 and made["t_mono"] > first["t_mono"]
    before = spans[:spans.index(made)]
    assert made["programs_compiled"] >= sum(
        s["name"] == "jit.compile" for s in before) >= 2
    assert made["cache_hits"] >= 0 and made["compile_time_saved_s"] >= 0
    # a program first compiled after it (a flush's or a save's small one)
    # says at which iteration; none was compiled AGAIN, so a healthy run
    # counts no recompile and its metric log has no such column
    for s in spans[spans.index(made):]:
        if s["name"] == "jit.compile":
            assert s["iteration"] is not None and "recompile" not in s
    assert not any("recompiles" in json.loads(l)
                   for l in open(out / "training_metrics.json"))


def test_dryrun_heartbeat(tiny_run):
    out, _ = tiny_run
    # role-namespaced since PR 11 (telemetry/watchdog.py keeps the
    # legacy un-namespaced read path for pre-PR-11 output dirs)
    hb = out / "telemetry" / "heartbeat.train"
    assert hb.exists()
    beat = json.loads(hb.read_text())
    assert beat["iteration"] >= 4 and beat["t"] > 0


def test_heartbeat_mtime_advances(tmp_path):
    tracer = SpanTracer(str(tmp_path), heartbeat_every=1)
    tracer.beat(0)
    m0 = os.stat(tracer.heartbeat_path).st_mtime_ns
    time.sleep(0.05)
    tracer.beat(1)
    m1 = os.stat(tracer.heartbeat_path).st_mtime_ns
    assert m1 > m0
    # heartbeat_every gates the touch
    tracer2 = SpanTracer(str(tmp_path / "b"), heartbeat_every=4)
    tracer2.beat(1)
    assert not os.path.exists(tracer2.heartbeat_path)
    tracer2.beat(4)
    assert os.path.exists(tracer2.heartbeat_path)
    tracer.close()
    tracer2.close()


def test_resume_mid_ring_determinism(tmp_path):
    """Kill a run mid flush-window, resume from the checkpoint: the
    resumed run records the same per-iteration losses as the
    uninterrupted one (ring re-anchors at the restored iteration)."""
    from dinov3_tpu.train.train import main as train_main

    common = TINY_TRAIN + [
        "train.OFFICIAL_EPOCH_LENGTH=5", "checkpointing.period=3",
        "telemetry.flush_every=2",
        # --record-losses pins probs_dtype=fp32; the interrupted leg
        # records nothing, so pin it everywhere or the legs would train
        # different programs (the ADVICE-r2 golden-trace rule)
        "compute_precision.probs_dtype=fp32",
    ]

    def losses(path):
        with open(path) as f:
            return {json.loads(l)["iteration"]: json.loads(l)["total_loss"]
                    for l in f if l.strip()}

    a, b = tmp_path / "a", tmp_path / "b"
    train_main(["--output-dir", str(a), "--no-resume",
                "--record-losses", str(a / "l.jsonl")] + common)
    train_main(["--output-dir", str(b), "--no-resume",
                "--max-iterations", "3"] + common)
    out = train_main(["--output-dir", str(b),
                      "--record-losses", str(b / "l.jsonl")] + common)
    assert out["iterations"] == 5
    la, lb = losses(a / "l.jsonl"), losses(b / "l.jsonl")
    assert set(la) == set(range(5))
    assert set(lb) == {3, 4}, "resume must start at the restored step"
    for it in (3, 4):
        assert la[it] == pytest.approx(lb[it], rel=1e-6), (
            f"iteration {it}: uninterrupted {la[it]} != resumed {lb[it]}")


# ---------------- --benchmark explicit fence ----------------

def test_step_timer_window():
    t = StepTimer(3, 10)
    assert [it for it in range(10) if t.active(it)] == [6, 7, 8, 9]
    assert not StepTimer(0, 10).active(9)


def test_bench_fence_agrees_with_freeride_on_oracle(eight_devices):
    """On the oracle path (per-step metrics fetch still present) the
    explicit tiny-fetch fence and the old free-ride-on-the-fetch timing
    measure the same intervals: the fence lands after the fetch already
    synced the step, so the two timestamp streams differ by ~the cost
    of one 4-byte fetch."""
    from dinov3_tpu.train import put_batch

    setup, batch = _setup(["telemetry.async_metrics=false"], 8,
                          eight_devices)
    assert setup.telemetry() is None
    d = put_batch(batch, setup.batch_shardings)
    state = setup.state
    timer = StepTimer(2, 4)
    freeride = []
    for it in range(4):
        state, metrics = setup.step_fn(
            state, d, setup.scalars(it), jax.random.key(0))
        float(metrics["total_loss"])  # the oracle's per-step sync
        if timer.active(it):
            freeride.append(time.perf_counter())
            timer.mark(state)
    assert timer.n_intervals == len(freeride) - 1 == 2
    for j in range(timer.n_intervals):
        fence_iv = timer.times[j + 1] - timer.times[j]
        free_iv = freeride[j + 1] - freeride[j]
        assert abs(fence_iv - free_iv) < 0.10 * max(fence_iv, free_iv) \
            + 0.01, (fence_iv, free_iv)


# ---------------- guardrail ----------------

def test_warn_telemetry_flush_period():
    from dinov3_tpu.configs.config import warn_telemetry_flush_period

    cfg = smol_cfg(["checkpointing.period=100",
                    "evaluation.eval_period_iterations=200"])
    cfg.telemetry.flush_every = 50
    assert warn_telemetry_flush_period(cfg) is None
    cfg.telemetry.flush_every = 150
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        msg = warn_telemetry_flush_period(cfg)
    assert msg and "checkpointing.period=100" in msg
    assert "eval" not in msg.split("exceeds")[1].split("—")[0]
    assert any("telemetry flush window" in str(w.message) for w in caught)
    cfg.telemetry.flush_every = 250
    msg = warn_telemetry_flush_period(cfg)
    assert "checkpointing.period=100" in msg \
        and "eval_period_iterations=200" in msg
    # oracle arm holds no rows on device: no warning
    cfg.telemetry.async_metrics = False
    assert warn_telemetry_flush_period(cfg) is None


# ---------------- instruments ----------------

def test_blocking_fetch_counter():
    host_sync_stats(reset=True)
    x = jnp.arange(8.0)
    out = blocking_fetch({"a": x, "b": x * 2})
    np.testing.assert_array_equal(out["a"], np.arange(8.0))
    s = host_sync_stats(reset=True)
    assert s["fetches"] == 1 and s["blocked_ms"] >= 0
    assert host_sync_stats()["fetches"] == 0


def test_memory_instruments(eight_devices):
    sm = sample_memory(eight_devices)
    assert len(sm["devices"]) == 8
    for d in sm["devices"]:
        assert d["source"] in ("memory_stats", "live_arrays")
        assert d["bytes_in_use"] >= 0
    x = jax.device_put(np.zeros((4, 4), np.float32), eight_devices[0])
    rec = per_device_state_bytes({"x": x})
    assert rec["max_per_device"] == 64 and rec["total"] == 64


def test_loss_tools_consume_flushed_batches(tmp_path):
    from dinov3_tpu.logging_utils import MetricLogger
    from dinov3_tpu.utils import LossComparator, LossRecorder

    names = ["aux", "total_loss"]
    its = np.array([3, 4, 5])
    rows = np.array([[0.5, 1.5], [0.25, 1.25], [0.125, 1.125]], np.float32)
    path = tmp_path / "rec.jsonl"
    rec = LossRecorder(str(path))
    rec.record_batch(its, names, rows)
    rec.close()
    got = [json.loads(l) for l in open(path)]
    assert [g["iteration"] for g in got] == [3, 4, 5]
    assert got[1]["total_loss"] == 1.25

    comp = LossComparator(str(path))
    assert comp.check_batch(its, names, rows)
    assert comp.n_diverged == 0
    bad = rows.copy()
    bad[2, 1] = 9.0
    assert not comp.check_batch(its, names, bad)
    assert comp.n_diverged == 1

    ml = MetricLogger()
    ml.consume_flush(names, its, rows,
                     scheds=lambda i: {"lr": 0.1 * i})
    assert ml.meters["total_loss"].count == 3
    assert ml.meters["total_loss"].value == pytest.approx(1.125)
    assert ml.meters["lr"].value == pytest.approx(0.5)


# ---------------- preemption chain + heartbeat scan (ISSUE 19) ----------------

def test_scan_heartbeats_mixed_legacy_and_namespaced(tmp_path):
    """A dir holding BOTH pre-PR-11 un-namespaced heartbeats and
    namespaced ones: legacy files report role "train" with
    ``legacy=True``, a namespaced beat shadows the legacy file for the
    same (role, rank), and staleness is judged per file."""
    from dinov3_tpu.telemetry import scan_heartbeats

    tdir = tmp_path / "telemetry"
    os.makedirs(tdir)
    now = time.time()
    for name, age in [
        ("heartbeat", 100.0),          # legacy (train, 0) — shadowed
        ("heartbeat.train", 1.0),      # namespaced (train, 0) — fresh
        ("heartbeat.rank1", 50.0),     # legacy (train, 1) — survives
        ("heartbeat.serve.rank2", 2.0),
    ]:
        p = tdir / name
        p.write_text("beat\n")
        os.utime(p, (now - age, now - age))

    rows = scan_heartbeats(str(tmp_path), stale_after_s=10.0, now=now)
    by_key = {(r["role"], r["rank"]): r for r in rows}
    assert set(by_key) == {("serve", 2), ("train", 0), ("train", 1)}
    t0 = by_key[("train", 0)]
    assert not t0["legacy"] and not t0["stalled"]  # namespaced shadows
    assert t0["path"].endswith("heartbeat.train")
    t1 = by_key[("train", 1)]
    assert t1["legacy"] and t1["stalled"]
    assert not by_key[("serve", 2)]["stalled"]


def test_preempt_chain_spans_roundtrip(tmp_path):
    """preempt_notice -> preempt_save -> resume_restore: each link
    emitted through the tracer lands in the span JSONL with the chain
    schema, and ``last_preempt_record`` recovers the newest save record
    across streams even past a torn trailing line (the usual state of a
    preempted writer's file)."""
    from dinov3_tpu.telemetry import (
        PREEMPT_CHAIN,
        SpanTracer,
        emit_preempt_chain,
        last_preempt_record,
    )

    assert PREEMPT_CHAIN == (
        "preempt_notice", "preempt_save", "resume_restore")

    tracer = SpanTracer(str(tmp_path), flush_every_emits=1)
    emit_preempt_chain(tracer, "preempt_notice", 7, signal="SIGTERM",
                       dur_ms=3.5)
    emit_preempt_chain(tracer, "preempt_save", 7, step=8, dur_ms=42.0)
    tracer.close()

    # a second (serve-role) stream with an older save + a torn line
    serve = SpanTracer(str(tmp_path), role="serve", flush_every_emits=1)
    rec = emit_preempt_chain(serve, "preempt_save", 3, step=4)
    serve.close()
    with open(serve.spans_path, "a") as f:
        f.write('{"name": "preempt_save", "t": 9')  # torn mid-record

    # hand the older record an earlier clock so "newest" is meaningful
    lines = [json.loads(l) for l in open(serve.spans_path).readlines()[:-1]]
    lines[0]["t"] = rec["t"] - 60.0
    with open(serve.spans_path, "w") as f:
        for l in lines:
            f.write(json.dumps(l) + "\n")
        f.write('{"name": "preempt_save", "t": 9')

    got = last_preempt_record(str(tmp_path))
    assert got["name"] == "preempt_save" and got["step"] == 8
    assert got["iteration"] == 7 and got["role"] == "train"
    notice = last_preempt_record(str(tmp_path), "preempt_notice")
    assert notice["signal"] == "SIGTERM"
    assert last_preempt_record(str(tmp_path), "resume_restore") is None

    # tracer=None (spans disabled): record still built for the caller
    off = emit_preempt_chain(None, "resume_restore", 0, path="disk")
    assert off["path"] == "disk" and "t" in off
    with pytest.raises(AssertionError):
        emit_preempt_chain(None, "not_a_link", 0)


def test_preemption_handler_manual_notice():
    """PreemptionHandler.notice() — the programmatic path chaos
    harnesses use — trips the same stop + first-notice clock the signal
    path records."""
    from dinov3_tpu.run.preemption import PreemptionHandler

    h = PreemptionHandler()  # signal hooks only install in __enter__
    assert not h.should_stop() and h.notice_time is None
    t0 = time.time()
    h.notice("chaos_kill")
    assert h.should_stop() and h.notice_signal == "chaos_kill"
    assert h.notice_time is not None and h.notice_time >= t0
    first = h.notice_time
    h.notice("second")  # later notices keep the FIRST clock
    assert h.notice_time == first and h.notice_signal == "chaos_kill"
