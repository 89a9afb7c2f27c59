"""Serving A/B for the continuous-packing engine (serve/): the
committed evidence behind SERVE_r14.json.

Methodology (the PR-1..5 discipline — measure the exact shipped code
paths, stated precisely because this is the committed evidence in
docs/PERFORMANCE.md):

- **Three traffic mixes**, each a seeded draw of [H, W, 3] requests:
  ``uniform_224`` (every request the same square resolution — the mix
  rectangular batching is built for, kept as the oracle's home turf),
  ``mixed_ragged`` (H and W drawn INDEPENDENTLY on the 16px grid
  across banded 96..512px resolutions, small-skewed the way embedding
  traffic is — the shape space is hundreds of (H, W) pairs, so
  shape-polymorphic serving can never stay warm), and ``heavy_tail``
  (90% small 96..160px crops, 10% near-max 448..512px).
- **Three arms over identical traffic**: the packed engine
  (serve.continuous_packing, ONE ahead-of-time compile at build) and
  the two naive oracles (``oracle_rectangular``: group by exact shape,
  pad each group's batch to the next power of two; ``oracle_per_image``:
  one dispatch per request). All arms serve the SAME bf16 weight tree
  through the same admission/flush-deadline batcher policy.
- **Warmup protocol**: each arm first serves a DISJOINT warmup draw
  from the same mix distribution. That fully warms the packed arm (its
  one program is shape-independent) and warms the oracles exactly as
  much as a real deployment could (they cannot pre-trace traffic
  shapes they have not seen; the per-arm record reports how many
  measured shapes were novel after warmup). Oracle recompiles during
  measurement are part of the measured serving cost — that is the
  pathology under test — and are reported separately as
  ``compile_growth_during_measurement``.
- **Throughput (sustained drain)**: all measured requests arrive at
  t=0; img/s = N / wall-seconds of the drain. The stream is long
  enough (several full token budgets) that the packed arm's last
  partial pack amortizes.
- **Latency (virtual-clock rated replay)**: Poisson arrivals at 0.7x
  the PACKED arm's measured sustained rate — the same trace for every
  arm, so an arm slower than the offered rate visibly queues. The
  clock advances by each flush's measured wall time (plus waits to the
  next arrival/deadline), so percentiles don't require real sleeps;
  p50/p99 are over per-request ``done_s - arrival_s``.
- **Accounting**: per (arm, mix) record embeds bench.py's
  ``_serve_summary`` (token budget, measured pad waste, the
  blocking_fetch funnel counters) and re-fires the
  ``warn_serve_pad_waste`` guardrail against the MEASURED mix waste;
  the packed arm's one program carries the full copy + collective
  census (utils.hlo_copy_census / hlo_collective_census) with the
  serve-scoped traffic attributed and zero unattributed collectives
  pinned (tests/test_serve.py reads these from the committed record).

Layout for the full run: rows=4 x row_tokens=1025 (one max-envelope
image per row; dense segment-masked attention is O(row_tokens^2) per
row, so the smallest row that fits the 512px request minimizes the
fixed pack cost) and max_segments_per_row=28 (a row of 96px requests
holds 27 — anything lower slot-caps small traffic into pure padding).

Observability (ISSUE 11): every measured (arm, mix) window runs behind
a ``ServeObserver`` (telemetry/serve_obs.py) writing per-request phase
spans and per-SLO streaming latency histograms into one serve-role
span stream (``--obs-dir``); ``scripts/obs_report.py`` folds that
stream plus this record into the committed OBS artifact. Latency
percentiles go through the shared nearest-rank quantile helper
(telemetry/hist.py) — exact overall and per SLO class.

Writes one JSON document (default ./SERVE_r14.json) and prints it.

Usage: JAX_PLATFORMS=cpu python scripts/bench_serve.py \
           [--smoke] [--out SERVE_r14.json] [--seed 0] [--n N] \
           [--obs-dir DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


# ---------------- traffic mixes ----------------
#
# Each mix is banded: (probability, (min_px, max_px)); H and W are
# drawn independently on the patch-size grid inside the band (square
# only when the band is a single value). Small-skewed bands reflect
# embedding-serving reality (thumbnails and crops dominate; full-res
# is the tail) — and raggedness is the point: the (H, W) space of the
# mixed bands is ~300 shapes, so per-shape jit caches never converge.

MIXES_FULL = {
    "uniform_224": [(1.0, (224, 224))],
    "mixed_ragged": [(0.70, (96, 256)), (0.20, (208, 320)),
                     (0.10, (336, 512))],
    "heavy_tail": [(0.90, (96, 160)), (0.10, (448, 512))],
}

MIXES_SMOKE = {
    "uniform_224": [(1.0, (16, 16))],
    "mixed_ragged": [(0.70, (8, 16)), (0.20, (20, 24)), (0.10, (28, 32))],
    "heavy_tail": [(0.90, (8, 12)), (0.10, (28, 32))],
}


def make_mix(rng: np.random.Generator, bands, n: int, grid: int) -> list:
    """n seeded [H, W, 3] float32 images from the banded distribution."""
    probs = np.array([p for p, _ in bands])
    out = []
    for b in rng.choice(len(bands), size=n, p=probs / probs.sum()):
        lo, hi = bands[int(b)][1]
        sizes = np.arange(lo, hi + 1, grid)
        h, w = rng.choice(sizes), rng.choice(sizes)
        out.append(rng.standard_normal((int(h), int(w), 3))
                   .astype(np.float32))
    return out


def slo_class(image, layout) -> str:
    """Deterministic SLO class per request: small crops (both sides at
    or below the envelope midpoint) are ``interactive`` — the
    thumbnail/crop traffic a frontend waits on — larger requests are
    ``batch``. Size-derived (not random) so every arm serves the same
    class per request and the per-class percentiles compare across
    arms."""
    cut = (layout.min_px + layout.max_px) / 2
    return ("interactive"
            if max(image.shape[0], image.shape[1]) <= cut else "batch")


# ---------------- replays ----------------


def drain_all(engine, images) -> tuple[float, list]:
    """All arrivals at t=0; wall-seconds and responses of the drain."""
    for i, im in enumerate(images):
        engine.submit(im, request_id=i, arrival_s=0.0,
                      slo=slo_class(im, engine.layout))
    t0 = time.perf_counter()
    responses = []
    while engine.queue_len:
        responses.extend(engine.flush())
    wall = time.perf_counter() - t0
    assert len(responses) == len(images)
    return wall, responses


def _lat_summary(latencies_s: list) -> dict:
    """Exact nearest-rank percentiles of a latency sample — the shared
    quantile helper (telemetry/hist.py), replacing the ad-hoc indexing
    this script used to hand-roll (p50 as ``lats[len//2]`` — the UPPER
    median on even n — and a hand-clamped p99 index)."""
    from dinov3_tpu.telemetry.hist import quantile_nearest_rank

    lats = sorted(latencies_s)
    return {
        "n": len(lats),
        "p50_ms": round(1e3 * quantile_nearest_rank(lats, 0.50), 3),
        "p99_ms": round(1e3 * quantile_nearest_rank(lats, 0.99), 3),
        "mean_ms": round(1e3 * sum(lats) / len(lats), 3),
    }


def rated_replay(engine, trace) -> dict:
    """Virtual-clock discrete-event replay of a timed arrival trace.

    ``trace``: [(arrival_s, image)] sorted by arrival. The clock
    advances by (a) jumps to the next arrival / flush deadline while
    idle and (b) each flush's MEASURED wall time while serving — so a
    too-slow arm accumulates queueing delay exactly as a real frontend
    would, without wall-clock sleeps between arrivals.
    """
    now, i = 0.0, 0
    responses = []
    obs = getattr(engine, "observer", None)
    while i < len(trace) or engine.queue_len:
        while i < len(trace) and trace[i][0] <= now:
            engine.submit(trace[i][1], request_id=i, arrival_s=trace[i][0],
                          slo=slo_class(trace[i][1], engine.layout))
            i += 1
        if engine.should_flush(now) or (i >= len(trace) and engine.queue_len):
            t0 = time.perf_counter()
            out = engine.flush()
            now += time.perf_counter() - t0
            for r in out:
                r.done_s = now
                if obs is not None:
                    # end-to-end latency on the replay's VIRTUAL clock,
                    # so the streaming histograms estimate the same
                    # quantity as the exact-sample percentiles below
                    obs.observe_latency(r.slo, r.latency_s, r.request_id)
            responses.extend(out)
            continue
        nxt = []
        if i < len(trace):
            nxt.append(trace[i][0])
        deadline = engine.flush_deadline()
        if deadline is not None:
            nxt.append(deadline)
        if not nxt:
            break
        # always advance: should_flush reuses flush_deadline's exact
        # arithmetic (serve/batcher.py) so jumping TO the deadline
        # fires it, but a stalled clock here would spin forever
        target = max(now, min(nxt))
        now = target if target > now else now + 1e-6
    out = _lat_summary([r.latency_s for r in responses])
    by_slo: dict = {}
    for r in responses:
        by_slo.setdefault(r.slo, []).append(r.latency_s)
    # exact per-class percentiles — the reference the streaming
    # histograms (serve.obs.slo in the same record) are judged against
    # in scripts/obs_report.py, one bucket width apart at most
    out["by_slo"] = {slo: _lat_summary(v)
                     for slo, v in sorted(by_slo.items())}
    return out


# ---------------- per-arm measurement ----------------


def measure_arm(engine, warm_images, meas_images, trace,
                serve_summary, warn_fn, observer=None) -> tuple[dict, list]:
    """Disjoint warmup draw, sustained drain, rated replay, summary.

    The observer attaches AFTER warmup, beside the host_sync reset, so
    its pack/request counters cover exactly the measured window — that
    alignment is what lets obs_report.py pin fetches-per-pack == 1
    (zero blocking syncs added by the observability plane)."""
    from dinov3_tpu.telemetry.host_sync import host_sync_stats

    drain_all(engine, warm_images)
    compiles_after_warmup = engine.compile_count

    host_sync_stats(reset=True)
    engine.reset_pad_stats()
    engine.observer = observer
    wall, responses = drain_all(engine, meas_images)
    lat = rated_replay(engine, trace)
    warm_shapes = {im.shape for im in warm_images}
    rec = {
        "throughput": {
            "images_per_s": round(len(meas_images) / wall, 3),
            "wall_s": round(wall, 4),
        },
        "latency": lat,
        "compile_count_after_warmup": compiles_after_warmup,
        "compile_growth_during_measurement": (
            engine.compile_count - compiles_after_warmup),
        "novel_shapes_after_warmup": len(
            {im.shape for im in meas_images} - warm_shapes),
        "serve": serve_summary(engine),
        "pad_waste_warning": warn_fn(engine.mean_pad_waste or 0.0),
    }
    engine.observer = None
    return rec, responses


def feature_agreement(a, b) -> dict:
    """Max |diff| between two arms' responses, matched by request id."""
    bb = {r.request_id: r for r in b}
    cls = max(float(np.abs(r.cls_feature - bb[r.request_id].cls_feature).max())
              for r in a)
    pooled = max(float(np.abs(r.pooled_patch_feature
                              - bb[r.request_id].pooled_patch_feature).max())
                 for r in a)
    return {"cls_max_abs_diff": cls, "pooled_max_abs_diff": pooled}


# ---------------- the fleet benchmark (SERVE_r16) ----------------
#
# ISSUE 12 acceptance: a multi-class rated replay (>= 2 SLO classes x
# >= 2 engines x cache hit-rate sweep {0, 0.5, 0.9}) with per-(engine,
# SLO) p50/p99, an int8-vs-bf16 single-engine A/B on the same mix
# (throughput + CLS drift under serve.quant.drift_tol), cache-hit
# responses bitwise-equal to their miss, and exactly n_engines total
# compiles across the whole replay. The fleet: an int8 fast lane whose
# envelope is DERIVED from the measured interactive mix
# (LiveMixTracker.recommended_serve_envelope — the PR-11 telemetry the
# admission layer was built for) next to the full bf16 row, with the
# content-addressed cache (serve/cache.py) in front.


def repeat_trace(rng, fresh_images, n_req, hit_rate):
    """A request sequence with repeated content at ~``hit_rate``: each
    position repeats a uniformly chosen EARLIER position's image object
    with probability hit_rate, else takes the next fresh image.
    Repeats reuse the same array object, so the content hash — and the
    route (same shape -> same engine) — are identical by construction.
    The measured hit rate trails the target slightly when a repeat
    lands while its original is still in flight (a miss that computes
    twice — reported honestly per sweep)."""
    seq = []
    fresh_i = 0
    for _ in range(int(n_req)):
        if seq and rng.random() < hit_rate:
            seq.append(seq[int(rng.integers(len(seq)))])
        else:
            seq.append(fresh_images[fresh_i % len(fresh_images)])
            fresh_i += 1
    return seq


def fleet_drain(router, images, layout) -> tuple[float, list]:
    """Sustained drain through the admission layer (all arrivals t=0)."""
    for i, im in enumerate(images):
        router.submit(im, request_id=i, arrival_s=0.0,
                      slo=slo_class(im, layout))
    t0 = time.perf_counter()
    responses = []
    while router.queue_len:
        responses.extend(router.flush())
    wall = time.perf_counter() - t0
    assert len(responses) == len(images)
    return wall, responses


def fleet_rated_replay(router, trace, layout) -> tuple[list, dict]:
    """The virtual-clock rated replay (see ``rated_replay``) through a
    ``FleetRouter``, auditing the cache as it goes: every hit response
    is compared BITWISE against the latest preceding computed (miss)
    response for the same image — the frozen-weights memoization claim,
    checked on the live replay rather than assumed. ``flush(now)``
    flushes only due engines mid-trace; the drain tail flushes all."""
    now, i = 0.0, 0
    responses: list = []
    obs = router.observer
    last_miss: dict = {}
    audit = {"hits": 0, "bitwise_failures": 0}
    while i < len(trace) or router.queue_len:
        while i < len(trace) and trace[i][0] <= now:
            router.submit(trace[i][1], request_id=i, arrival_s=trace[i][0],
                          slo=slo_class(trace[i][1], layout))
            i += 1
        if router.should_flush(now) or (i >= len(trace) and router.queue_len):
            t0 = time.perf_counter()
            out = router.flush(now if i < len(trace) else None)
            now += time.perf_counter() - t0
            for r in out:
                r.done_s = now
                img = trace[r.request_id][1]
                if r.cache_hit:
                    audit["hits"] += 1
                    ref = last_miss.get(id(img))
                    if ref is None or not (
                            np.array_equal(r.cls_feature, ref.cls_feature)
                            and np.array_equal(r.pooled_patch_feature,
                                               ref.pooled_patch_feature)):
                        audit["bitwise_failures"] += 1
                else:
                    last_miss[id(img)] = r
                if obs is not None:
                    # per-(engine, SLO) streaming histograms: the key
                    # the fleet's latency plane aggregates on
                    obs.observe_latency(f"{r.engine}/{r.slo}",
                                        r.latency_s, r.request_id)
            responses.extend(out)
            continue
        nxt = []
        if i < len(trace):
            nxt.append(trace[i][0])
        deadline = router.flush_deadline()
        if deadline is not None:
            nxt.append(deadline)
        if not nxt:
            break
        target = max(now, min(nxt))
        now = target if target > now else now + 1e-6
    return responses, audit


def run_fleet(args, cfg, mixes, tracer) -> dict:
    """The SERVE_r16 record: quant A/B + derived-envelope fleet +
    cache hit-rate sweep. Returns the record dict (main() writes it)."""
    import bench
    from dinov3_tpu.configs.config import (
        serve_obs_kwargs,
        warn_quant_drift,
    )
    from dinov3_tpu.serve import (
        PackedServeEngine,
        build_serve_fleet,
        load_serving_model,
        quant_feature_drift,
        quant_summary,
        quantize_serving_tree,
        serve_layout_from_cfg,
    )
    from dinov3_tpu.telemetry import LiveMixTracker, ServeObserver

    n = args.n or (12 if args.smoke else 64)
    qcfg = cfg.serve.get("quant") or {}
    tol = float(qcfg.get("drift_tol", 0.05) or 0.05)

    t0 = time.perf_counter()
    model, params = load_serving_model(cfg)
    layout = serve_layout_from_cfg(cfg)
    print(f"[bench_serve] fleet: {cfg.student.arch} base rows="
          f"{layout.rows}x{layout.row_tokens} envelope={layout.min_px}.."
          f"{layout.max_px}px build {time.perf_counter() - t0:.1f}s",
          flush=True)

    bands = mixes["mixed_ragged"]
    rng = np.random.default_rng(args.seed)
    warm_images = make_mix(rng, bands, n, layout.patch_size)
    meas_images = make_mix(rng, bands, n, layout.patch_size)

    # ---- (a) int8 quantization: drift probe + single-engine A/B ----
    qtree = quantize_serving_tree(params)
    probe_px = int(qcfg.get("probe_px", 0) or 0)
    if probe_px <= 0:
        p = layout.patch_size
        probe_px = max(p, (min(layout.max_px, 224) // p) * p)
    drift = quant_feature_drift(model, params, qtree, px=probe_px,
                                seed=args.seed)
    drift_warning = warn_quant_drift(
        drift["cls_max_abs_diff"], tol=tol,
        axis=f"int8 serving tree, {probe_px}px CLS probe")
    print(f"[bench_serve] quant drift: {drift} (tol {tol})", flush=True)

    eng = {"bf16": PackedServeEngine(model, params, layout, warn=False),
           "int8": PackedServeEngine(model, qtree, layout, warn=False)}
    for e in eng.values():
        drain_all(e, warm_images)
    reps = 2 if args.smoke else 3
    best = {}
    ab_responses = {}
    for _ in range(reps):
        # alternate arms within each rep so drift in machine load hits
        # both symmetrically; keep the best (least-perturbed) drain
        for name, e in eng.items():
            wall, rs = drain_all(e, meas_images)
            rate = len(meas_images) / wall
            if rate > best.get(name, 0.0):
                best[name] = rate
            ab_responses[name] = rs
    agreement = feature_agreement(ab_responses["bf16"],
                                  ab_responses["int8"])
    quant_rec = {
        "drift_probe": drift,
        "drift_tol": tol,
        "drift_warning": drift_warning,
        "summary": quant_summary(qtree),
        "throughput": {
            "reps_best_of": reps,
            "bf16_images_per_s": round(best["bf16"], 3),
            "int8_images_per_s": round(best["int8"], 3),
            "int8_over_bf16": round(best["int8"] / best["bf16"], 4),
        },
        "packed_feature_agreement": agreement,
    }
    print(f"[bench_serve] quant A/B: bf16 {best['bf16']:.3f} img/s, "
          f"int8 {best['int8']:.3f} img/s "
          f"(x{best['int8'] / best['bf16']:.3f})", flush=True)

    # ---- (b) the fleet: derived int8 fast lane + full bf16 row ----
    tracker = LiveMixTracker(layout)
    for im in warm_images:
        if slo_class(im, layout) == "interactive":
            tracker.observe_request(
                layout.seq_len(im.shape[0], im.shape[1]),
                im.shape[0], im.shape[1])
    tracker.roll()
    env = tracker.recommended_serve_envelope(threshold=0.15)
    assert env is not None, "no interactive traffic in the warm draw"
    cfg.serve.fleet.engines = [
        {"name": "fast_int8", "slo": "interactive", "quant": True,
         "rows": env["rows"], "row_tokens": env["row_tokens"],
         "max_segments_per_row": env["max_segments_per_row"],
         "min_px": env.get("min_px"), "max_px": env.get("max_px")},
        {"name": "full_bf16"},
    ]
    router = build_serve_fleet(cfg, params=params, warn=False)
    n_engines = len(router.specs)
    compiles_at_build = router.compile_count
    fleet_obs = ServeObserver(tracer, layout, slo_classes=(),
                              **serve_obs_kwargs(cfg))
    fleet_obs.set_labels(mix="fleet")
    router.observer = fleet_obs
    for spec in router.specs:
        o = ServeObserver(tracer, spec.engine.layout,
                          slo_classes=("interactive", "batch"),
                          **serve_obs_kwargs(cfg))
        o.set_labels(arm=spec.engine.arm, mix="fleet", engine=spec.name)
        spec.engine.observer = o
    print(f"[bench_serve] fleet engines: "
          + ", ".join(f"{s.name}({s.engine.arm} "
                      f"{s.engine.layout.rows}x{s.engine.layout.row_tokens})"
                      for s in router.specs)
          + f", {compiles_at_build} compiles", flush=True)

    # cold-cache sustained rate sets the offered rate for every sweep
    wall, _ = fleet_drain(router, warm_images, layout)
    rate = 0.7 * (n / wall)

    sweeps = {}
    for hit_rate in (0.0, 0.5, 0.9):
        router.cache.clear(reset_counters=True)
        seq = repeat_trace(rng, meas_images, n, hit_rate)
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
        trace = [(float(a), im) for a, im in zip(arrivals, seq)]
        responses, audit = fleet_rated_replay(router, trace, layout)
        assert len(responses) == n
        by_key: dict = {}
        by_slo: dict = {}
        for r in responses:
            by_key.setdefault(f"{r.engine}/{r.slo}", []).append(r.latency_s)
            by_slo.setdefault(r.slo, []).append(r.latency_s)
        stats = router.cache.stats()
        sweeps[f"hit_{hit_rate}"] = {
            "target_hit_rate": hit_rate,
            "measured_hit_rate": stats["hit_rate"],
            "n_responses": len(responses),
            "cache": stats,
            "cache_hits_bitwise_equal": audit["bitwise_failures"] == 0,
            "cache_hit_responses": audit["hits"],
            "latency": _lat_summary([r.latency_s for r in responses]),
            "by_engine_slo": {k: _lat_summary(v)
                              for k, v in sorted(by_key.items())},
            "by_slo": {k: _lat_summary(v)
                       for k, v in sorted(by_slo.items())},
            "compile_count": router.compile_count,
            "compile_growth": router.compile_count - compiles_at_build,
        }
        print(f"[bench_serve] fleet hit={hit_rate}: measured "
              f"{stats['hit_rate']} p99 "
              f"{sweeps[f'hit_{hit_rate}']['latency']['p99_ms']}ms "
              f"routes {dict(router.route_counts)}", flush=True)

    # forced hit: same image twice, back to back — the CI smoke's
    # bitwise claim in its smallest reproducible form
    probe_img = meas_images[0]
    router.cache.clear(reset_counters=True)
    router.submit(probe_img, request_id=900001, arrival_s=0.0,
                  slo=slo_class(probe_img, layout))
    miss = []
    while router.queue_len:
        miss.extend(router.flush())
    router.submit(probe_img, request_id=900002, arrival_s=0.0,
                  slo=slo_class(probe_img, layout))
    hit = []
    while router.queue_len:
        hit.extend(router.flush())
    forced_ok = (len(miss) == 1 and len(hit) == 1 and hit[0].cache_hit
                 and not miss[0].cache_hit
                 and np.array_equal(miss[0].cls_feature,
                                    hit[0].cls_feature)
                 and np.array_equal(miss[0].pooled_patch_feature,
                                    hit[0].pooled_patch_feature))

    fleet_rec = {
        "derived_fast_envelope": env,
        "offered_rate_images_per_s": round(rate, 3),
        "sweeps": sweeps,
        "forced_hit_bitwise": bool(forced_ok),
        "drift_check": router.check_drift(warn=False),
        "summary": bench._fleet_summary(router),
        "observer": fleet_obs.finalize(),
    }
    router.finalize()

    return {
        "what": ("quantized multi-tenant serving fleet: int8-vs-bf16 "
                 "single-engine A/B (drift probe + best-of-k sustained "
                 "drains on the same mixed-ragged draw), then a 2-engine "
                 "fleet — an int8 fast lane whose envelope is derived "
                 "from the measured interactive mix next to the full "
                 "bf16 row — behind one SLO/shape admission layer with "
                 "the content-addressed feature cache in front, rated-"
                 "replayed at cache hit rates {0, 0.5, 0.9} with "
                 "per-(engine, SLO) p50/p99, every cache hit audited "
                 "bitwise against its miss, and total compiles pinned "
                 "at n_engines"),
        "arch": cfg.student.arch,
        "smoke": bool(args.smoke),
        "seed": args.seed,
        "n_per_sweep": n,
        "backend": __import__("jax").default_backend(),
        "layout": {
            "rows": layout.rows, "row_tokens": layout.row_tokens,
            "token_budget": layout.token_budget,
            "n_prefix": layout.n_prefix,
            "patch_size": layout.patch_size,
            "min_px": layout.min_px, "max_px": layout.max_px,
            "max_segments_per_row": layout.max_segments_per_row,
        },
        "quant": quant_rec,
        "fleet": fleet_rec,
        "n_engines": n_engines,
        "compile_count_total": router.compile_count,
        "compile_growth_total": router.compile_count - compiles_at_build,
    }


# ---------------- main ----------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="vit_test + tiny envelope (CI tier-1 step)")
    ap.add_argument("--fleet", action="store_true",
                    help="the SERVE_r16 fleet benchmark: int8-vs-bf16 "
                         "A/B + 2-engine SLO-routed fleet + cache "
                         "hit-rate sweep (default --out SERVE_r16.json)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=None,
                    help="images per mix (default: 64 full / 12 smoke)")
    ap.add_argument("--obs-dir", default=None,
                    help="output dir for the serve span stream "
                         "(telemetry/serve_obs.py; scripts/obs_report.py "
                         "folds it into the OBS artifact). Default: a "
                         "temp dir.")
    args = ap.parse_args()
    if args.out is None:
        args.out = "SERVE_r16.json" if args.fleet else "SERVE_r14.json"

    import jax

    import bench
    from dinov3_tpu.utils import configure_compile_cache, require_accelerator

    configure_compile_cache()
    # no CPU default: without the chip this fails unless the CPU is
    # asked for explicitly (JAX_PLATFORMS=cpu, as CI's --smoke step does)
    device = require_accelerator()
    from dinov3_tpu.configs.config import (
        apply_dot_overrides,
        get_default_config,
        serve_obs_kwargs,
        serve_pad_waste_floor,
        warn_serve_pad_waste,
    )
    from dinov3_tpu.serve import (
        OracleServeEngine,
        PackedServeEngine,
        load_serving_model,
        serve_layout_from_cfg,
    )
    from dinov3_tpu.telemetry import ServeObserver, SpanTracer
    from dinov3_tpu.utils import hlo_collective_census, hlo_copy_census

    n = args.n or (12 if args.smoke else 64)
    cfg = get_default_config()
    if args.smoke:
        apply_dot_overrides(cfg, [
            "student.arch=vit_test", "student.patch_size=4",
            "serve.min_px=8", "serve.max_px=32", "serve.rows=4",
            "serve.row_tokens=65", "serve.max_segments_per_row=12",
            "train.scan_layers=true",
        ])
        mixes = MIXES_SMOKE
    else:
        apply_dot_overrides(cfg, [
            "student.arch=vit_small", "train.scan_layers=true",
            # one max-envelope image per row (min fixed pack cost: the
            # dense segment-masked attention is O(row_tokens^2)/row),
            # slots sized so a row of 96px requests (27 fit) isn't
            # slot-capped into padding
            "serve.rows=4", "serve.row_tokens=1025",
            "serve.max_segments_per_row=28",
        ])
        mixes = MIXES_FULL

    obs_dir = args.obs_dir
    if obs_dir is None:
        import tempfile

        obs_dir = tempfile.mkdtemp(prefix="bench_serve_obs_")
    # ONE serve-role tracer for the whole run: every (mix, arm)
    # observer writes into the same spans.serve.jsonl stream, labelled,
    # the way a deployment's engine pool would share one stream
    tracer = SpanTracer(obs_dir, role="serve")
    print(f"[bench_serve] serve span stream: {tracer.spans_path}",
          flush=True)

    if args.fleet:
        record = run_fleet(args, cfg, mixes, tracer)
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"[bench_serve] wrote {args.out}")
        return 0

    t0 = time.perf_counter()
    model, params = load_serving_model(cfg)
    layout = serve_layout_from_cfg(cfg)
    floor = serve_pad_waste_floor(
        layout.row_tokens, layout.patch_size, layout.n_prefix,
        layout.min_px, layout.max_px)
    print(f"[bench_serve] {cfg.student.arch} rows={layout.rows} "
          f"row_tokens={layout.row_tokens} budget={layout.token_budget} "
          f"envelope={layout.min_px}..{layout.max_px}px "
          f"floor(mean)={floor['mean_waste']:.3f} "
          f"build {time.perf_counter() - t0:.1f}s", flush=True)

    def build_engine(arm):
        if arm == "packed":
            return PackedServeEngine(model, params, layout, warn=False)
        return OracleServeEngine(model, params, layout,
                                 mode=arm.removeprefix("oracle_"))

    record = {
        "what": ("continuous-packing serve engine vs naive oracles: "
                 "sustained img/s + rated p50/p99 over three traffic "
                 "mixes, identical bf16 weights and batcher policy; "
                 "oracle arms warm on a disjoint draw, so their "
                 "recompiles on novel traffic shapes are measured "
                 "serving cost"),
        "arch": cfg.student.arch,
        "smoke": bool(args.smoke),
        "seed": args.seed,
        "n_per_mix": n,
        "backend": jax.default_backend(),
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "device_count": device["count"],
        "layout": {
            "rows": layout.rows, "row_tokens": layout.row_tokens,
            "token_budget": layout.token_budget,
            "n_prefix": layout.n_prefix,
            "patch_size": layout.patch_size,
            "min_px": layout.min_px, "max_px": layout.max_px,
            "max_segments_per_row": layout.max_segments_per_row,
        },
        "pad_waste_floor": {k: round(v, 4) if isinstance(v, float) else v
                            for k, v in floor.items()},
        "mixes": {},
    }

    arms = ("packed", "oracle_rectangular", "oracle_per_image")
    engines = {arm: build_engine(arm) for arm in arms}

    # the one packed program's census, from its optimized HLO
    hlo = engines["packed"].compiled_text()
    copies = hlo_copy_census(hlo)
    colls = hlo_collective_census(hlo)
    record["packed_census"] = {
        "compile_s": round(engines["packed"].compile_s, 3),
        "copy_total": copies["hlo_copy_total"],
        "copy_by_category": {k: v["ops"]
                             for k, v in copies["by_category"].items()},
        "collective_total": colls["hlo_collective_total"],
        "collective_unattributed": colls["unattributed"],
    }

    for mix_name, bands in mixes.items():
        rng = np.random.default_rng(args.seed)
        warm_images = make_mix(rng, bands, n, layout.patch_size)
        meas_images = make_mix(rng, bands, n, layout.patch_size)
        tokens = sum(layout.seq_len(im.shape[0], im.shape[1])
                     for im in meas_images)
        mix_rec = {
            "n": n,
            "measured_tokens": tokens,
            "distinct_shapes_measured": len(
                {im.shape for im in meas_images}),
        }
        responses = {}

        # packed first: its sustained rate sets the rated-replay
        # arrival trace every arm then replays
        trace = None
        for arm in arms:
            eng = engines[arm]
            print(f"[bench_serve] {mix_name}/{arm} ...", flush=True)
            if trace is None:
                # probe the packed sustained rate on the warmup draw
                # (its own warmup: the AOT program needs one execution
                # for allocator/runtime steady state)
                drain_all(eng, warm_images)
                wall, _ = drain_all(eng, warm_images)
                rate = 0.7 * (n / wall)
                arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
                trace = [(float(a), im)
                         for a, im in zip(arrivals, meas_images)]
                mix_rec["offered_rate_images_per_s"] = round(rate, 3)
            observer = ServeObserver(tracer, layout,
                                     slo_classes=("interactive", "batch"),
                                     **serve_obs_kwargs(cfg))
            observer.set_labels(arm=arm, mix=mix_name)
            arm_rec, resp = measure_arm(
                eng, warm_images, meas_images, trace,
                lambda e: bench._serve_summary(
                    e, copies if e.arm == "packed" else None),
                lambda w, a=arm: warn_serve_pad_waste(
                    w, stacklevel=3,
                    axis=f"measured {mix_name} mix, {a} arm"),
                observer=observer,
            )
            mix_rec[arm] = arm_rec
            responses[arm] = resp

        for arm in ("oracle_rectangular", "oracle_per_image"):
            mix_rec[f"features_vs_{arm}"] = feature_agreement(
                responses["packed"], responses[arm])
        mix_rec["speedup_vs_rectangular"] = round(
            mix_rec["packed"]["throughput"]["images_per_s"]
            / mix_rec["oracle_rectangular"]["throughput"]["images_per_s"], 3)
        mix_rec["speedup_vs_per_image"] = round(
            mix_rec["packed"]["throughput"]["images_per_s"]
            / mix_rec["oracle_per_image"]["throughput"]["images_per_s"], 3)
        record["mixes"][mix_name] = mix_rec
        print(f"[bench_serve] {mix_name}: packed "
              f"{mix_rec['packed']['throughput']['images_per_s']} img/s, "
              f"rect x{mix_rec['speedup_vs_rectangular']}, "
              f"per-image x{mix_rec['speedup_vs_per_image']}", flush=True)

    record["packed_compile_count"] = engines["packed"].compile_count
    tracer.close()
    from dinov3_tpu.telemetry.spans import SPAN_SCHEMA_V

    record["obs"] = {"spans_path": os.path.abspath(tracer.spans_path),
                     "schema_v": SPAN_SCHEMA_V}

    out = json.dumps(record, indent=1)
    with open(args.out, "w") as f:
        f.write(out + "\n")
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
