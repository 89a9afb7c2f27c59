"""Headline benchmark: DINOv3 pretrain throughput, images/sec/chip.

Runs the full fused training step (teacher fwd + student fwd/bwd on
2 global + 8 local crops + Sinkhorn + AdamW + EMA) for ViT-L/16 on the
available device(s) with synthetic data, and prints ONE JSON line on
stdout:

    {"metric": "...", "value": N, "unit": "img/s/chip", "vs_baseline": N}

Baseline: the reference codebase publishes no JAX numbers (SURVEY.md §6);
its configs record Meta's PyTorch run at 0.57 s/iter for global batch 2048
on 32 A100-class GPUs = 112 img/s/GPU (vitl_im1k_lin834.yaml:3-4).
``vs_baseline`` is img/s/chip divided by that 112 img/s/GPU anchor.

One process, one backend init (``utils.require_accelerator``): the default
backend must be ``tpu`` — a run that finds no chip FAILS; only an
explicit ``JAX_PLATFORMS=cpu`` lets it run on the CPU, and every record
names ``platform`` / ``device_kind`` / ``device_count`` so a CPU number
can never be read as a device metric. It starts no child process (a
chip belongs to one process at a time), substitutes no other step
program, and a census or trace that was asked for and fails fails the
run. The persistent compile cache is placed by
``utils.configure_compile_cache`` (``JAX_COMPILATION_CACHE_DIR`` when
set, else ``<repo>/.jax_cache``).

- every phase (init/build/compile/warmup/measure) logs start/end to
  stderr, and a heartbeat thread prints the current phase every 60 s;
- env switches bisect the step program: BENCH_PROBS=fp32|bf16
  (attention-probability storage), DINOV3_FUSED_LN=1 (Pallas layernorm),
  BENCH_OVERRIDES=comma-separated extra dot-overrides (e.g.
  optim.fused_update=false for the update-engine A/B).
- every run measures a fixed seconds-long calibration rung (chained
  1024x1024 bf16 matmuls) right after backend init and records it in
  the final JSON line ("calib");
- a batch-tiling guardrail warns (and records "batch_tiling_warning")
  when BENCH_BATCH pads >20% on the sublane axis — the B=10 cliff
  (24.22 vs 58.56 img/s/chip at B=12; round 5, before PR 1, one v5e
  chip).

Env knobs: BENCH_ARCH (vit_large), BENCH_BATCH (per-chip, 12 — the
round-5 on-chip sweep's peak for the subset drop-path program:
58.56 img/s/chip at B=12 vs 54.46 at B=8 and a pathological 24.22 at
B=10; round 5, before PR 1, one v5e chip),
BENCH_STEPS (10), BENCH_WARMUP (3), BENCH_RES (high-res crop px),
BENCH_CENSUS=1 (or ``--census``; embed a copy census AND a collective
census of the exact compiled step — counts/bytes/attribution,
utils.hlo_copy_census / utils.hlo_collective_census — in the record, so
copy and collective regressions surface in the same JSONL artifact as
throughput; the sharded-update A/B reads the
all-reduce-vs-reduce-scatter grad-sync story straight from
``collective_census.by_class``).

BENCH_TRACE=1 (or ``--trace``): after the measured loop, capture a
jax.profiler window over BENCH_TRACE_STEPS (4) extra steps of the SAME
compiled program and embed the step-anatomy summary
(telemetry/anatomy.py — per-scope collective ms, measured
exposed/overlapped fraction, straggler spread across device timelines)
in the record next to the copy/collective censuses; the
warn_exposed_comm guardrail fires against the measurement and lands in
the record as "exposed_comm_warning". The window is deliberately
OUTSIDE the timed loop so profiling overhead never pollutes the
headline img/s number. BENCH_TRACE_DIR pins the trace output dir
(default: a fresh directory under TMPDIR, path recorded).

The benched step is the DEFAULT program, which under async telemetry
(telemetry.async_metrics auto=on) is the telemetry step — metrics row
into a donated on-device ring, no per-step host sync. Every record
embeds a "telemetry" summary: the arm, the measure loop's blocking
device->host fetch count + host-blocked ms (telemetry/host_sync.py —
the COST_HSYNC_r11.json instrument), and device memory samples at the
setup/compile/measure boundaries. An A/B pins
BENCH_OVERRIDES=telemetry.async_metrics=false as the control arm.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_IMG_S_PER_CHIP = 112.0  # Meta PyTorch ViT-L run, per A100

_T0 = time.time()
_PHASE = {"name": "startup", "since": _T0}


def _log(msg: str) -> None:
    print(f"[bench +{time.time() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def _phase(name: str) -> None:
    _PHASE["name"], _PHASE["since"] = name, time.time()
    _log(f"phase={name}")


def _watchdog(period: float = 60.0) -> None:
    """Heartbeat thread: names the current phase on stderr every
    ``period`` seconds, so a slow compile in the captured tail is
    attributable to a phase. It only logs — it starts no process."""

    def run():
        while True:
            time.sleep(period)
            _log(
                f"heartbeat: in phase={_PHASE['name']} "
                f"for {time.time() - _PHASE['since']:.0f}s"
            )

    threading.Thread(target=run, daemon=True).start()


def _measure_calibration(jax, jnp) -> dict:
    """Fixed calibration rung: a seconds-long, session-independent
    program (chained 1024x1024 bf16 matmuls, fetch-synced) measured
    right after backend init and recorded in the final JSON line of
    EVERY bench run — so every phases-JSONL row a queue harness emits
    carries a measured session-health factor. Cross-session throughput
    comparisons can then divide out slow-session drift instead of the
    documented ~15% shrug (r5: the same mask program measured 41.61 on
    one host and 47.6-48.1 on another; docs/PERFORMANCE.md "Session
    calibration")."""
    n, iters = 1024, 10
    x = (jnp.arange(n * n, dtype=jnp.float32).reshape(n, n)
         / jnp.float32(n * n)).astype(jnp.bfloat16)
    f = jax.jit(lambda a: a @ a)
    y = x
    for _ in range(3):
        y = f(y)
    float(jnp.sum(y.astype(jnp.float32)))  # fetch-sync (not block_until_ready)
    t0 = time.perf_counter()
    y = x
    for _ in range(iters):
        y = f(y)
    float(jnp.sum(y.astype(jnp.float32)))
    dt = (time.perf_counter() - t0) / iters
    return {
        "program": "matmul1024_bf16_chain_x10",
        "ms_per_matmul": round(dt * 1e3, 4),
        "tflops": round(2 * n ** 3 / dt / 1e12, 2),
    }


def _split_overrides(s: str) -> list[str]:
    """Split BENCH_OVERRIDES on commas *outside* brackets, so list-valued
    entries (crops.global_crops_size=[512,768]) survive intact."""
    out, buf, depth = [], [], 0
    for ch in s:
        if ch in "[(":
            depth += 1
        elif ch in "])":
            depth -= 1
        if ch == "," and depth == 0:
            if buf:
                out.append("".join(buf))
            buf = []
        else:
            buf.append(ch)
    if buf:
        out.append("".join(buf))
    return out


def build_step_overrides(arch: str, res: int, *,
                         drop_path_mode: str | None = None,
                         probs: str | None = None,
                         extra=()) -> list[str]:
    """The exact dot-override list that defines the bench step program.

    Single source of truth shared with scripts/count_flops.py so the
    counted-FLOP ceilings are always ceilings OF THE BENCHED PROGRAM —
    the r3 13.31-vs-13.68 discrepancy came from a drifted ad-hoc copy
    of this list."""
    overrides = [
        f"student.arch={arch}",
        "student.n_storage_tokens=4",
        "student.drop_path_rate=0.3",
        "optim.scaling_rule=none",
        "parallel.data=-1",
        # the recipe's ``param_dtype: bf16`` (vitl_im1k_lin834.yaml) is the
        # torch-FSDP compute-copy dtype; training masters are always fp32
        # (ssl_meta_arch.py) and compute runs in compute_dtype=bf16, so the
        # override is kept only for recipe-key parity
        "compute_precision.param_dtype=bf16",
    ]
    if drop_path_mode:
        overrides.append(f"student.drop_path_mode={drop_path_mode}")
    if res:
        overrides += [f"crops.global_crops_size={res}",
                      f"crops.local_crops_size={max(96, res // 4)}"]
    if probs:
        overrides.append(f"compute_precision.probs_dtype={probs}")
    return overrides + list(extra)


def _zero3_summary(setup, coll_census) -> dict:
    """The record's "zero3" block: arm, per-device master/state bytes
    from the assigned NamedShardings, and (census runs only) the
    engine-scoped all-gather counts of the benched program."""
    from dinov3_tpu.telemetry.memory import layout_split

    masters = layout_split(setup.state.params, setup.state_shardings.params)
    state = layout_split(setup.state, setup.state_shardings)
    out = {
        "arm": bool(setup.zero3),
        "master_bytes_per_device": masters["per_device_bytes"],
        "master_replicated_fraction": round(
            masters["replicated_fraction"], 4),
        "state_bytes_per_device": state["per_device_bytes"],
    }
    if coll_census and "by_scope" in coll_census:
        out["gathers_by_scope"] = {
            k: v for k, v in coll_census["by_scope"].items()
            if k.startswith("zero3")}
        out["prefetch_overlap"] = coll_census.get("prefetch_overlap")
    return out


def _lowp_summary(setup, coll_census) -> dict:
    """The record's "low_precision" block: which precision arm was
    benched (train.low_precision.arm), the setup drift probe's
    per-kernel-site relative Frobenius drift (ops/lowp.py
    lowp_drift_probe — None on the bf16 arm, which quantizes nothing),
    and (census runs only) the streamed-collective story: the
    ``zero3_stream`` scope the 1-byte weight gathers ride plus the
    ``lowp_amax``/``lowp_dequant`` epilogue scopes — the phQ A/B reads
    the bytes-vs-counts story straight from here."""
    drift = getattr(setup, "lowp_drift", None)
    out = {
        "arm": getattr(setup, "lowp_arm", "bf16"),
        "drift_max": drift.get("max") if drift else None,
        "drift_by_site": ({k: v for k, v in drift.items() if k != "max"}
                          if drift else None),
    }
    if coll_census and "by_scope" in coll_census:
        out["collectives_by_scope"] = {
            k: v for k, v in coll_census["by_scope"].items()
            if k in ("zero3_stream", "lowp_amax", "lowp_dequant")}
    return out


def _bucket_summary(setup, coll_census) -> dict:
    """The record's "buckets" block: arm, plan shape (bucket count /
    payload / zero-pad fraction from BucketPlan.padding_stats) and
    (census runs only) the bucket-scoped collective counts plus the
    program-wide message-size histogram and issue-site placement of the
    benched program — the phB A/B reads the coalescing story straight
    from here."""
    plan = getattr(setup, "bucket_plan", None)
    out = {"arm": bool(getattr(setup, "bucketed", False))}
    if plan is not None:
        rows = plan.padding_stats()
        payload = sum(r["bytes"] for r in rows)
        pad = sum(r["pad_elems"] * (r["bytes"] // max(r["elems"], 1))
                  for r in rows)
        out.update({
            "n_buckets": len(rows),
            "n_leaves": sum(r["n_leaves"] for r in rows),
            "payload_bytes": int(payload),
            "pad_fraction": round(pad / max(payload, 1), 4),
            "target_bytes": int(plan.target_bytes),
        })
    if coll_census and "by_scope" in coll_census:
        out["collectives_by_scope"] = {
            k: v for k, v in coll_census["by_scope"].items()
            if k.startswith("bucket")}
        out["size_histogram"] = coll_census.get("size_histogram")
        out["by_placement"] = coll_census.get("by_placement")
    return out


def _serve_summary(engine, copy_census=None) -> dict:
    """The record's "serve" block: arm, token-budget shape, measured
    pad waste (mean over all packs since the arm's last
    ``reset_pad_stats``, plus the last pack's — usually a partial
    trailing pack), and the blocking_fetch funnel counters (fetch count
    + host-blocked ms) since the last arm boundary.
    scripts/bench_serve.py embeds one per (arm, mix) record in
    SERVE_r14.json; (census runs only) the serve-scoped copy counts of
    the packed program land alongside."""
    from dinov3_tpu.telemetry.host_sync import host_sync_stats

    L = engine.layout
    mean_waste = getattr(engine, "mean_pad_waste", None)
    out = {
        "arm": engine.arm,
        "rows": L.rows,
        "row_tokens": L.row_tokens,
        "token_budget": L.token_budget,
        "pad_waste": (round(mean_waste, 4)
                      if mean_waste is not None else None),
        "pad_waste_last_pack": (round(engine.last_pad_waste, 4)
                                if engine.last_pad_waste is not None
                                else None),
        "compile_count": engine.compile_count,
        "host_sync": host_sync_stats(reset=True),
    }
    if copy_census and "by_category" in copy_census:
        by_cat = copy_census["by_category"]
        out["serve_copies"] = by_cat.get("serve", {}).get("ops", 0)
        out["unattributed_copies"] = by_cat.get(
            "unattributed", {}).get("ops", 0)
    obs = getattr(engine, "observer", None)
    if obs is not None:
        # observability-plane sidecar: packs/requests/windows seen, the
        # per-SLO streaming-histogram summaries, the live-mix EWMA pad
        # waste and the re-derived envelope (telemetry/serve_obs.py) —
        # finalize() also serializes the full instruments into the span
        # stream for scripts/obs_report.py
        out["obs"] = obs.finalize()
    return out


def _distill_summary(setup, coll_census) -> dict:
    """The record's "distill" block: whether the benched step distills
    from a frozen teacher, which teacher arm feeds it (in_step = the
    teacher forwards inside the compiled step; serve = the host-shared
    packed engine's precomputed batch planes), and — when this process
    built shared TeacherServers (multidistillation.shared_teacher_server)
    — each server's forward-dedup/cache/compile counters, the numbers
    COST_DISTILL_r22.json pins. Census runs add the ``distill_fanout``
    scope counts of the exact benched program."""
    meta = getattr(setup, "meta", None)
    out = {
        "arm": bool(getattr(meta, "distillation", False)),
        "teacher_source": getattr(meta, "teacher_source", "in_step"),
        "teacher_embed_dim": (getattr(meta, "teacher_embed_dim", None)
                              if getattr(meta, "distillation", False)
                              else None),
    }
    try:
        from dinov3_tpu.train.multidistillation import _SHARED_TEACHERS

        if _SHARED_TEACHERS:
            out["teacher_servers"] = [s.stats()
                                      for s in _SHARED_TEACHERS.values()]
    except ImportError:
        pass
    if coll_census and "by_scope" in coll_census:
        out["collectives_by_scope"] = {
            k: v for k, v in coll_census["by_scope"].items()
            if k.startswith("distill")}
    return out


def _fleet_summary(router) -> dict:
    """The record's "fleet" block (serve/fleet.py FleetRouter): one
    entry per pool engine — arm, weights dtype, token-budget shape,
    SLO contract, quantized-kernel byte accounting, per-engine compile
    count and measured pad waste — plus the admission layer's route
    counts per (engine, SLO), the content-addressed cache counters
    (hit rate, evictions — serve/cache.py), and the total compile
    count the n_engines pin in SERVE_r16.json / the CI fleet smoke
    reads. Embedded in every fleet bench record the way the
    "serve"/"telemetry" blocks are."""
    from dinov3_tpu.serve.quant import quant_summary

    engines = {}
    for spec in router.specs:
        e = spec.engine
        L = e.layout
        mean_waste = getattr(e, "mean_pad_waste", None)
        engines[spec.name] = {
            "arm": e.arm,
            "dtype": getattr(e, "weights_dtype", "bf16"),
            "rows": L.rows,
            "row_tokens": L.row_tokens,
            "token_budget": L.token_budget,
            "max_segments_per_row": L.max_segments_per_row,
            "slo_classes": (None if spec.slo_classes is None
                            else list(spec.slo_classes)),
            "weights_fingerprint": spec.fingerprint,
            "quant": quant_summary(e.params),
            "compile_count": e.compile_count,
            "packs_run": e.packs_run,
            "pad_waste": (round(mean_waste, 4)
                          if mean_waste is not None else None),
        }
    return {
        "n_engines": len(router.specs),
        "engines": engines,
        "compile_count_total": router.compile_count,
        "route_counts": {f"{en}/{slo}": c for (en, slo), c
                         in sorted(router.route_counts.items())},
        "cache": (router.cache.stats()
                  if router.cache is not None else None),
    }


def main():
    import jax

    from dinov3_tpu.utils import configure_compile_cache, require_accelerator

    configure_compile_cache()
    # before anything else runs (or any thread starts): no TPU, no bench
    device = require_accelerator()
    n = device["count"]
    _watchdog()
    _phase("init")
    _log(f"backend={device['platform']} kind={device['device_kind']} "
         f"devices={n}")
    import jax.numpy as jnp

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup, put_batch

    arch = os.environ.get("BENCH_ARCH", "vit_large")
    per_chip = int(os.environ.get("BENCH_BATCH", "12"))
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    res = int(os.environ.get("BENCH_RES", "0"))  # >0: global crop px
    # (e.g. BENCH_RES=512 BENCH_BATCH=2 exercises the >=1024-token flash-
    # attention regime of the high-res recipes)

    _phase("calibrate")
    calib = _measure_calibration(jax, jnp)
    _log(f"calibration: {calib}")

    _phase("build")
    from dinov3_tpu.configs.config import (
        warn_bad_batch_tiling,
        warn_student_row_tiling,
    )

    tiling_warning = warn_bad_batch_tiling(per_chip)
    cfg = get_default_config()
    overrides = build_step_overrides(
        arch, res,
        probs=os.environ.get("BENCH_PROBS") or None,
        extra=_split_overrides(os.environ.get("BENCH_OVERRIDES", "")),
    )
    apply_dot_overrides(cfg, overrides)
    # same guardrail over the benched program's other student row axes
    # (local-crop rows / packed row count) — recorded with the batch one
    row_warnings = warn_student_row_tiling(cfg, per_chip)
    if row_warnings:
        tiling_warning = "; ".join(
            ([tiling_warning] if tiling_warning else []) + row_warnings)
    B = per_chip * n
    batch_np = make_synthetic_batch(cfg, B, seed=0)
    batch = {k: jnp.asarray(v) for k, v in batch_np.items()}

    # the layout guardrails fire inside build_train_setup, where the
    # param shapes and the mesh first coexist — capture them into the
    # record like the tiling warnings above: the zero3 layout guardrail
    # (configs/config.py warn_zero3_padding)
    import warnings as _bwarnings

    with _bwarnings.catch_warnings(record=True) as _bcaught:
        _bwarnings.simplefilter("always")
        setup = build_train_setup(cfg, batch)
    zero3_warnings = [str(w.message) for w in _bcaught
                      if "zero3 master layout" in str(w.message)]
    # ... and the bucket-plan guardrail (configs/config.py
    # warn_bucket_padding: zero-pad fraction + straggler buckets)
    bucket_warnings = [str(w.message) for w in _bcaught
                       if "bucket flat axis" in str(w.message)
                       or "bucket size axis" in str(w.message)]
    # ... and the accumulation tiling guardrail (configs/config.py
    # warn_accum_batch_tiling: divisibility + per-chip microbatch cliff)
    accum_warnings = [str(w.message) for w in _bcaught
                      if "optim.accum_steps axis" in str(w.message)
                      or "per-chip microbatch" in str(w.message)]
    # ... and the seq-padding guardrail (configs/config.py
    # warn_seq_padding: crop token counts that pad badly against
    # parallel.seq — every padded position costs real ring FLOPs)
    seq_pad_warnings = [str(w.message) for w in _bcaught
                        if "seq-padding axis" in str(w.message)]
    # ... and the low-precision drift guardrail (configs/config.py
    # warn_lowp_divergence: setup drift probe vs divergence_tol)
    lowp_warnings = [str(w.message) for w in _bcaught
                     if "lowp divergence axis" in str(w.message)]
    dbatch = put_batch(batch, setup.batch_shardings)
    rng = jax.random.key(0)
    state = setup.state
    scalars = setup.scalars(0)

    # the benched step is the DEFAULT program: under async telemetry
    # (telemetry.async_metrics auto=on) that is the telemetry step —
    # metrics row into the donated device ring, no per-step host sync —
    # so the phO A/B (BENCH_OVERRIDES=telemetry.async_metrics=false
    # control) measures the ring write's real cost
    from dinov3_tpu.telemetry import blocking_fetch, host_sync_stats
    from dinov3_tpu.telemetry.memory import sample_memory

    plan = setup.telemetry()
    ring = plan.init_ring() if plan is not None else None
    mem_setup = sample_memory()

    _phase("compile")
    import warnings as _warnings

    # the block emits a one-time warning at trace time when a configured
    # drop_path_mode=subset degrades to mask semantics (tiny or
    # indivisible per-shard batch) — surface that in the record so an
    # A/B labeled "subset" can never silently be the mask program
    with _warnings.catch_warnings(record=True) as _caught:
        _warnings.simplefilter("always")
        if plan is not None:
            compiled = plan.step_fn.lower(
                state, ring, dbatch, scalars, rng).compile()
        else:
            compiled = setup.step_fn.lower(
                state, dbatch, scalars, rng).compile()
    degraded = [str(w.message) for w in _caught
                if "degraded to mask semantics" in str(w.message)]
    mem_compile = sample_memory()
    _log("compile done")

    census = None
    coll_census = None
    if os.environ.get("BENCH_CENSUS") == "1" or "--census" in sys.argv:
        # copy + collective census of the EXACT program being benched
        # (same compiled HLO, no recompile), so copy/collective
        # regressions surface in the same JSONL artifact as the
        # throughput they cost — attribution categories are
        # utils.classify_copy's (rng / donation_async / update_shard /
        # small / large) and utils.classify_collective's (all_reduce /
        # reduce_scatter / all_gather / ppermute / all_to_all /
        # unattributed; an update-arm A/B reads the grad-sync story
        # straight from by_class)
        from dinov3_tpu.utils import hlo_collective_census, hlo_copy_census

        hlo_text = compiled.as_text()
        census = hlo_copy_census(hlo_text)
        _log(f"copy census: total={census['hlo_copy_total']} "
             f"by_category={census['by_category']}")
        coll_census = hlo_collective_census(hlo_text)
        _log(f"collective census: "
             f"total={coll_census['hlo_collective_total']} "
             f"by_class={coll_census['by_class']}")

    steps = max(1, steps)
    _phase("warmup")
    # synchronize via a value fetch (the telemetry arm fetches the
    # ring's streak scalar — 4 bytes — since its step has no metrics
    # output; both fetches go through the counted telemetry funnel)
    if plan is not None:
        for _ in range(warmup):
            state, ring = compiled(state, ring, dbatch, scalars, rng)
        if warmup:
            blocking_fetch(ring.nonfinite_streak)
    else:
        for _ in range(warmup):
            state, metrics = compiled(state, dbatch, scalars, rng)
        if warmup:
            blocking_fetch(metrics["total_loss"])

    _phase("measure")
    host_sync_stats(reset=True)
    t0 = time.perf_counter()
    if plan is not None:
        for _ in range(steps):
            state, ring = compiled(state, ring, dbatch, scalars, rng)
        blocking_fetch(ring.nonfinite_streak)
    else:
        for _ in range(steps):
            state, metrics = compiled(state, dbatch, scalars, rng)
        blocking_fetch(metrics["total_loss"])
    dt = (time.perf_counter() - t0) / steps
    hsync = host_sync_stats()
    mem_measure = sample_memory()

    anatomy_summary = None
    anatomy_warn = None
    trace_on = os.environ.get("BENCH_TRACE") == "1" or "--trace" in sys.argv
    if trace_on:
        # anatomy trace window (telemetry/anatomy.py): a few extra steps
        # of the SAME compiled program under the profiler, AFTER the
        # timed loop — profiling overhead must never pollute the
        # headline number. The ledger joins the trace against the
        # compiled HLO so collective time lands in named scopes.
        _phase("trace")
        import tempfile

        from dinov3_tpu.configs.config import warn_exposed_comm
        from dinov3_tpu.telemetry import (
            anatomy_ledger,
            find_trace_file,
            ledger_summary,
            load_trace,
        )
        from dinov3_tpu.telemetry.anatomy import round_floats

        tdir = os.environ.get("BENCH_TRACE_DIR") or tempfile.mkdtemp(
            prefix="bench_trace_")
        n_trace = max(1, min(steps,
                             int(os.environ.get("BENCH_TRACE_STEPS", "4"))))
        jax.profiler.start_trace(tdir)
        try:
            if plan is not None:
                for _ in range(n_trace):
                    state, ring = compiled(state, ring, dbatch, scalars, rng)
                blocking_fetch(ring.nonfinite_streak)
            else:
                for _ in range(n_trace):
                    state, metrics = compiled(state, dbatch, scalars, rng)
                blocking_fetch(metrics["total_loss"])
        finally:
            jax.profiler.stop_trace()
        # a traced run that was asked for and failed fails the run
        led = anatomy_ledger(
            load_trace(find_trace_file(tdir)),
            hlo_text=compiled.as_text(), n_steps=n_trace)
        anatomy_summary = round_floats(ledger_summary(led))
        anatomy_summary["trace_dir"] = tdir
        anatomy_warn = warn_exposed_comm(cfg, anatomy_summary)
        _log(f"anatomy: {anatomy_summary['step_wall_ms']['mean']:.2f} "
             f"ms/step wall, exposed-comm "
             f"{anatomy_summary['exposed_comm_frac']:.1%}, scopes="
             f"{sorted(anatomy_summary['collectives'])}")
    _phase("report")

    img_s_chip = B / dt / n
    tag = f"{arch}_{res}px" if res else arch
    rec = {
        "metric": f"dinov3_pretrain_{tag}_imgs_per_sec_per_chip",
        "value": round(img_s_chip, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(img_s_chip / BASELINE_IMG_S_PER_CHIP, 3),
        # the device the number was measured on, as JAX reports it
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "device_count": device["count"],
        # session-health factor: every phases-JSONL row that embeds this
        # record carries the fixed calibration rung (see docs/PERFORMANCE.md
        # "Session calibration")
        "calib": calib,
        # telemetry summary: which metrics arm was benched, the measure
        # loop's blocking-fetch count + host-blocked wall time (the
        # COST_HSYNC_r11.json instrument), and memory samples at the
        # setup/compile/measure boundaries (telemetry/memory.py)
        "telemetry": {
            "async_metrics": plan is not None,
            "ring_len": plan.ring_len if plan is not None else None,
            "n_metrics": len(plan.metric_names) if plan is not None else None,
            "host_sync_measure": {**hsync, "steps": steps},
            "memory": {"setup": mem_setup, "compile": mem_compile,
                       "measure": mem_measure},
        },
        # zero3 summary: which master-layout arm was benched, its
        # per-device state footprint from the assigned shardings
        # (telemetry/memory.layout_split — the phW A/B reads the
        # masters story straight from here), and — when the census ran —
        # the engine-scoped gather counts of the exact benched program
        "zero3": _zero3_summary(setup, coll_census),
        # bucketed-collectives summary: which grad-sync arm was benched,
        # the plan's bucket count / payload / pad fraction, and — when
        # the census ran — the bucket-scoped collective counts plus the
        # message-size histogram and issue-site placement
        "buckets": _bucket_summary(setup, coll_census),
        # low-precision summary: which fp8/int8 arm was benched, the
        # setup drift probe's per-site quantization drift, and — when
        # the census ran — the streamed-gather + dequant-epilogue scope
        # counts of the exact benched program (the phQ A/B instrument)
        "low_precision": _lowp_summary(setup, coll_census),
        # distillation summary: whether the step distills and through
        # which teacher arm (in_step vs the serve-backed fan-out), any
        # process-level TeacherServer dedup/cache counters, and — when
        # the census ran — the distill_fanout scope counts
        "distill": _distill_summary(setup, coll_census),
    }
    if anatomy_summary is not None:
        # measured step anatomy next to the static censuses: per-scope
        # collective ms with the exposed/overlapped split — the dynamic
        # twin of collective_census.by_placement
        rec["anatomy"] = anatomy_summary
    if anatomy_warn:
        rec["exposed_comm_warning"] = anatomy_warn
    if census is not None:
        rec["copy_census"] = census
    if coll_census is not None:
        rec["collective_census"] = coll_census
    if tiling_warning:
        rec["batch_tiling_warning"] = tiling_warning
    if zero3_warnings:
        rec["zero3_padding_warning"] = "; ".join(zero3_warnings)
    if bucket_warnings:
        rec["bucket_padding_warning"] = "; ".join(bucket_warnings)
    if lowp_warnings:
        rec["lowp_divergence_warning"] = "; ".join(lowp_warnings)
    if accum_warnings:
        rec["accum_tiling_warning"] = "; ".join(accum_warnings)
    if seq_pad_warnings:
        rec["seq_padding_warning"] = "; ".join(seq_pad_warnings)
    if degraded:
        # distinct reasons can fire for the global- and local-crop
        # batches of the same program — keep them all
        rec["drop_path_degraded"] = "; ".join(degraded)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
