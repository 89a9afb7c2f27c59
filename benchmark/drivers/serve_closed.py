"""Driver ``serve_closed``: a closed loop of callers on one serve engine.

One engine (``build_serve_engine``, the config-level entry) in the run's
own process, on weights made from ``--seed`` (``weights.fill``). The queue
is topped up to ``callers`` outstanding requests, ``engine.flush()`` runs
one pack, each answered request is replaced at once by the next of the
seeded stream (``traffic_gen``). Warm-up runs the same loop on a disjoint
draw. With ``--trace 1`` a stretch of ``traced_packs`` packs of the same
loop follows the window under the profiler.

End-to-end metrics computed here: ``setup_s`` and ``serve_img_per_s``
(requests answered in the window / wall). The latencies (answer time -
submit time) are multiples of the pack time in this loop, so their tail
jumps by a whole pack from seed to seed; it is handed to the per-layer
readers as a counter and carries no bound.

After the window a seeded sample of the requests it answered, the one
with most tokens among them, is compared with the plain reference.
"""

from __future__ import annotations

import time

import numpy as np

import output_check
import traffic_gen
import weights
from run import DriverResult, log


class PhaseLog:
    """The engine's observer hook, used in traced runs only, for
    information: mean host phase times of a pack (placement, dispatch,
    fetch = device wait, extract) in the window and under the profiler."""

    def __init__(self, spans):
        self.spans, self.rows = spans, []

    def on_admit(self, *a, **kw) -> None:
        pass

    def on_pack(self, summary, phases, **kw) -> None:
        self.rows.append((self.spans.phase, dict(phases)))

    def means(self, phase: str) -> dict:
        rows = [r for p, r in self.rows if p == phase]
        keys = ("placement", "dispatch", "fetch", "extract")
        return {k: round(float(np.mean([r[k] or 0.0 for r in rows])), 2)
                for k in keys} if rows else {}


class ClosedLoop:
    """``callers`` outstanding requests on ``engine``, one pack a turn."""

    def __init__(self, engine, images: list, order, callers: int, spans):
        self.engine, self.images, self.order = engine, images, order
        self.callers, self.spans = callers, spans
        self.next_id = 0
        self.submitted: dict = {}   # request id -> (submit time, pool index)
        self.answered: list = []    # (request id, latency s, response)
        self.packs = 0
        self.after_flush = None     # called when a flush has returned

    def top_up(self) -> None:
        while len(self.submitted) < self.callers:
            idx = next(self.order)
            now = time.perf_counter()
            self.engine.submit(self.images[idx], request_id=self.next_id,
                               arrival_s=now)
            self.submitted[self.next_id] = (now, idx)
            self.next_id += 1

    def turn(self) -> None:
        with self.spans.span("pack", self.packs):
            responses = self.engine.flush()
        now = time.perf_counter()
        if self.after_flush is not None:
            self.after_flush()
        self.packs += 1
        for r in responses:
            t_submit, idx = self.submitted.pop(r.request_id)
            self.answered.append((r.request_id, now - t_submit, idx, r))
        self.top_up()

    def drain(self) -> None:
        while self.engine.queue_len:
            self.callers = 0
            self.turn()


def pick_checked(done: list, tokens: list, n: int, seed: int) -> list:
    """Indices into ``done`` of the answered requests to compare: the one
    with most tokens, and one answer, drawn from the seed, of each of the
    first ``n - 1`` images of the pool. The pool's sizes are the same for
    every seed, so the reference compiles its programs for these shapes
    once in a checkout and finds them in the cache afterwards."""
    rng = np.random.default_rng([int(seed) % (1 << 62), 31])
    picks = {int(np.argmax(tokens))}
    for pool_index in range(n - 1):
        answers = [i for i, d in enumerate(done) if d[2] == pool_index]
        if answers:
            picks.add(answers[int(rng.integers(len(answers)))])
    return sorted(picks)


def served_and_inputs(done: list, images: list, picks: list):
    """([(cls [1, D], pooled [1, D]) as served], [image [1, H, W, 3]])."""
    import jax.numpy as jnp

    got = [(done[i][3].cls_feature[None], done[i][3].pooled_patch_feature[None])
           for i in picks]
    return got, [jnp.asarray(images[done[i][2]][None]) for i in picks]


def build(conf: dict, mix: dict, seed: int):
    """(engine, weights tree, image pool) of one run."""
    import jax.numpy as jnp

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.models import build_backbone
    from dinov3_tpu.serve import build_serve_engine
    from dinov3_tpu.serve.weights import serving_config

    cfg = get_default_config()
    apply_dot_overrides(cfg, list(conf["overrides"]))
    images = traffic_gen.image_pool(mix, seed)
    model = build_backbone(serving_config(cfg), teacher=True)
    abstract = output_check.abstract_backbone(model, jnp.asarray(images[0][None]))
    tree = weights.fill(abstract, seed, jnp.bfloat16)
    engine = build_serve_engine(cfg, params=tree, warn=False)
    return engine, tree, images, abstract


def run(ctx) -> DriverResult:
    conf, mix = ctx.config, ctx.traffic
    engine, tree, images, _ = build(conf, mix, ctx.seed)
    warm_images = traffic_gen.image_pool(
        {**mix, "pool_images": mix["callers"]}, ctx.seed, stream=1)
    layout = engine.layout
    log(f"engine built: rows={layout.rows} row_tokens="
        f"{layout.row_tokens} segments<={layout.max_segments_per_row} envelope "
        f"{layout.min_px}..{layout.max_px}px ring_depth={engine.ring_depth}, "
        f"compile {engine.compile_s:.2f}s; pool {len(images)} images, mean "
        f"{np.mean([layout.seq_len(*im.shape[:2]) for im in images]):.1f} tokens")

    warm = ClosedLoop(engine, warm_images,
                      traffic_gen.request_order(len(warm_images), ctx.seed + 1),
                      int(mix["callers"]), ctx.spans)
    warm.top_up()
    for _ in range(int(mix["warmup_packs"])):
        warm.turn()
    warm.drain()
    n_warm_spans = len(ctx.spans.spans)
    loop = ClosedLoop(engine, images, traffic_gen.request_order(len(images), ctx.seed),
                      int(mix["callers"]), ctx.spans)
    loop.top_up()
    engine.reset_pad_stats()
    phases = PhaseLog(ctx.spans) if ctx.tracer is not None else None
    engine.observer = phases
    setup_s = time.perf_counter() - ctx.t_start
    log(f"set-up {setup_s:.2f}s: backend compiles {ctx.compiles.count} "
        f"({ctx.compiles.compile_s:.1f}s), cache hits {ctx.compiles.cache_hits}")

    # ---- the window
    compiles_before = ctx.compiles.count + ctx.compiles.cache_hits
    t0 = time.perf_counter()
    while time.perf_counter() < t0 + ctx.seconds:
        loop.turn()
    wall = time.perf_counter() - t0
    compiled_in_window = ctx.compiles.count + ctx.compiles.cache_hits - compiles_before
    if compiled_in_window:
        raise SystemExit(f"benchmark: {compiled_in_window} program(s) compiled "
                         "or loaded inside the measured window")
    attempted = loop.next_id
    done = list(loop.answered)
    packs = loop.packs
    pad_waste = engine.mean_pad_waste
    lat_ms = np.array([lat for _, lat, _, _ in done]) * 1e3
    img_per_s = len(done) / wall
    p95 = float(np.percentile(lat_ms, 95))
    log(f"window: {len(done)} of {attempted} requests answered in {packs} packs, "
        f"{wall:.3f}s = {img_per_s:.2f} img/s; latency ms p50 "
        f"{np.percentile(lat_ms, 50):.1f} p95 {p95:.1f} max {lat_ms.max():.1f} "
        f"({len(lat_ms)} samples); mean pad waste {pad_waste:.4f}")

    counters = {"serve_packs": packs, "serve_mean_pad_waste": pad_waste,
                "serve_requests_answered": len(done), "serve_latency_p95_ms": p95}
    window_spans = ctx.spans.spans[n_warm_spans:]
    counters["serve_flush_wall_ms_mean"] = float(
        np.mean([s.ms for s in window_spans if s.name == "pack"]))
    if ctx.tracer is not None:
        n = int(mix["traced_packs"])
        with ctx.tracer:
            for _ in range(int(mix["trace_lead_packs"])):
                loop.turn()
            loop.after_flush = ctx.tracer.mark_fence  # a flush ends in its fetch
            with ctx.tracer.window():
                for _ in range(n):
                    loop.turn()
            loop.after_flush = None
        counters["serve_packs_traced"] = n
        log(f"traced stretch: {n} packs in {ctx.tracer.window_s:.3f}s; mean host "
            f"phases of a pack, ms: window {phases.means('window')}, traced "
            f"{phases.means('traced')}")
    loop.drain()
    unanswered = len(loop.submitted)
    answered_ids = {rid for rid, _, _, _ in loop.answered}
    nonfinite = sum(1 for rid, _, _, r in loop.answered if rid < attempted and not (
        np.isfinite(r.cls_feature).all() and np.isfinite(r.pooled_patch_feature).all()))
    failed = sum(1 for rid in range(attempted) if rid not in answered_ids) + nonfinite

    # ---- the output check on what the window answered
    t0 = time.perf_counter()
    tokens = [layout.seq_len(*images[idx].shape[:2]) for _, _, idx, _ in done]
    picks = pick_checked(done, tokens, int(conf["check"]["requests"]), ctx.seed)
    got, batches = served_and_inputs(done, images, picks)
    want = output_check.reference_features(tree, batches, conf["reference"])
    checks = output_check.checks_from_gaps(
        output_check.gaps(got, want), conf["check"], "served")
    checks.append(output_check.check(
        "engine_compile_count", engine.compile_count, 1, engine.compile_count == 1))
    checks.append(output_check.check(
        "requests_failed", failed, 0, failed == 0 and unanswered == 0))
    log(f"output check: {len(picks)} requests, token counts "
        f"{[tokens[i] for i in picks]}, took {time.perf_counter() - t0:.2f}s "
        "(not in setup_s)")
    return DriverResult(
        metrics={"setup_s": setup_s, "serve_img_per_s": img_per_s},
        attempted=attempted, failed=failed, checks=checks, counters=counters)
