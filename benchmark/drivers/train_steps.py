"""Driver ``train_steps``: the trainer's own hot loop, timed.

Set-up builds ONE object (``Rig``) — the compiled default (async-telemetry)
step of ``build_train_setup`` with a state the benchmark makes on the
device from ``--seed`` (``weights.py``: N(0, 0.02), LayerScale and norm
scales 1, teacher = student, fresh moments, iteration
``start_iteration`` of the recipe's schedules) — drives it through its
first ``warmup_steps`` steps, and hands the same state, ring and step
function to the window. The window is ``do_train``'s loop
(``dinov3_tpu/train/train.py``): dispatch step ``i``, then ``put_batch``
(h2d) of batch ``i+1``, the metrics ring flushed every
``telemetry.flush_every`` steps, nothing fenced in between. Every step
takes a different host batch, cycled from a pool of ``pool_batches``
batches made in set-up: a loader that keeps up.

The clock runs from a ``block_until_ready`` after warm-up to a
``block_until_ready`` on the state of the last dispatched step;
dispatching stops at ``--seconds``. With ``--trace 1`` a short fenced
stretch of the same loop follows the window under the profiler.

``correct`` (``step_check.py``): those first steps — their losses, the
first gradient as the optimizer got it, the parameters' change — against
``reference/ssl_step_fp32.py`` on the same weights, batches and
stochastic-depth draws, once the window has closed and the state is freed.

End-to-end metrics computed here: ``setup_s``, ``train_img_per_s_chip``.
"""

from __future__ import annotations

import gc
import math
import os
import time

import numpy as np

import output_check
import step_check
import weights
from reference import ssl_step_fp32
from run import ROOT, DriverResult, log

LOSS_TERMS = ("total_loss", "dino_global_crops_loss", "dino_local_crops_loss",
              "koleo_loss", "ibot_loss")


def host_pool(cfg, batch: int, seed: int, n: int) -> list:
    from dinov3_tpu.data import make_synthetic_batch

    return [make_synthetic_batch(cfg, batch, seed=(seed % (1 << 62), 11, i))
            for i in range(n)]


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


class Rig:
    """The compiled step of one configuration, and — after ``start(seed)``
    — its state, ring and pool; ``loop`` is the window's call and feed."""

    def __init__(self, conf: dict, mix: dict, devices: list, seed: int, spans,
                 extra_overrides=()):
        import jax.numpy as jnp

        from dinov3_tpu.configs import load_config
        from dinov3_tpu.train import build_train_setup

        self.conf, self.mix, self.spans = conf, mix, spans
        self.chips = len(devices)
        self.cfg = load_config(os.path.join(ROOT, conf["recipe"]), overrides=[
            *conf["overrides"], *extra_overrides, f"train.seed={seed % (1 << 31)}"])
        self.batch = int(self.cfg.train.batch_size_per_device) * self.chips
        self.start_it = int(mix["start_iteration"])
        example = host_pool(self.cfg, self.batch, seed, 1)[0]
        self.setup = build_train_setup(
            self.cfg, {k: jnp.asarray(v) for k, v in example.items()},
            devices=devices, init_state=False)
        self.plan = self.setup.telemetry()
        self.recipe = ssl_step_fp32.Recipe.from_config(
            {**conf["reference"], "global_batch": self.batch})
        self.state = self.ring = self.pending = None

    # ---- the state, from the seed

    def _fresh_state(self, seed: int):
        """The whole train state in one jitted call: student from the seed,
        teacher = student, everything else zero, both counters at the
        start iteration."""
        import jax
        import jax.numpy as jnp

        abstract = self.setup.state
        student = abstract.params["student"]

        def make(key):
            state = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract)
            filled = weights.fill_leaves(student, key, jnp.float32)
            params = dict(state.params, student=filled,
                          teacher=jax.tree.map(lambda x: x + 0.0, filled))
            start = jnp.asarray(self.start_it, jnp.int32)
            return state._replace(
                params=params, step=start,
                opt_state=state.opt_state._replace(count=start))

        with self.setup.mesh:
            return jax.jit(make, out_shardings=self.setup.state_shardings)(
                weights.seed_key(seed, weights.FILL_STREAM))

    def start(self, seed: int) -> None:
        import jax

        from dinov3_tpu.train import put_batch

        self.seed = seed
        self.pool = host_pool(self.cfg, self.batch, seed, int(self.mix["pool_batches"]))
        self.state, self.ring = self._fresh_state(seed), self.plan.init_ring()
        self.reader = self.plan.reader(self.start_it)
        self.rng = jax.random.key(seed % (1 << 31) + 1)
        self.rows: list = []
        self.steps = 0
        self.pending = put_batch(self.pool[0], self.setup.batch_shardings)
        self.first_grad = None

    def free(self) -> None:
        self.state = self.ring = self.pending = None
        gc.collect()

    # ---- the loop

    def flush(self) -> None:
        with self.spans.span("metrics_flush", self.steps - 1):
            _, got, _ = self.reader.flush(self.ring, self.start_it + self.steps)
        self.rows.extend(dict(zip(self.plan.metric_names, (float(x) for x in r)))
                         for r in got)

    def loop(self, stop_at_step=None, stop_at_time=None) -> None:
        """do_train's order: dispatch i, h2d of i+1, flush when due."""
        from dinov3_tpu.train import put_batch

        setup, step_fn, pool = self.setup, self.plan.step_fn, self.pool
        while True:
            it, dbatch = self.steps, self.pending
            with self.spans.span("dispatch", it):
                self.state, self.ring = step_fn(
                    self.state, self.ring, dbatch,
                    setup.scalars(self.start_it + it), self.rng)
            with self.spans.span("h2d", it):
                self.pending = put_batch(pool[(it + 1) % len(pool)],
                                         setup.batch_shardings)
            self.steps += 1
            if self.start_it + self.steps - self.reader.cursor >= self.plan.ring_len:
                self.flush()
            if stop_at_step is not None and self.steps >= stop_at_step:
                return
            if stop_at_time is not None and time.perf_counter() >= stop_at_time:
                return

    # ---- the first steps, and what the check reads from them

    def first_steps(self) -> dict:
        """Drive the state through its first ``warmup_steps`` steps by the
        window's own call and feed; what the program side of the check
        needs, as small arrays: per-step losses, per-leaf norms of the
        first gradient (first moment after one step / (1 - beta1)) and of
        the student's change, the teacher's change."""
        import jax
        import jax.numpy as jnp

        n = int(self.mix["warmup_steps"])
        t0 = time.perf_counter()
        self.loop(stop_at_step=1)
        first_moment = jax.jit(_leaf_norms)(self.state.opt_state.adam.mu)
        jax.block_until_ready(self.state.step)
        self.first_step_s = time.perf_counter() - t0
        if n > 1:
            self.loop(stop_at_step=n)
        self.flush()  # warms the flush path too; the window starts with an empty ring
        abstract = self.setup.state.params["student"]

        def changes(params, key):
            # the seed's weights again, leaf by leaf inside this one program:
            # no second copy of the student is ever held
            old = weights.fill_leaves(abstract, key, jnp.float32)
            sub = lambda a, b: a - b  # noqa: E731
            return (_leaf_norms(jax.tree.map(sub, params["student"], old)),
                    jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(
                        jax.tree.map(sub, params["teacher"], old)))))

        change, teacher_change = jax.jit(changes)(
            self.state.params, weights.seed_key(self.seed, weights.FILL_STREAM))
        jax.block_until_ready((self.state.step, self.pending))
        scale = 1.0 / (1.0 - self.recipe.beta1)
        return {
            "losses": [{k: row[k] for k in LOSS_TERMS} for row in self.rows[:n]],
            "grad_norms": weights.step_weights(jax.tree.map(
                lambda x: np.float64(x) * scale, first_moment)),
            "change_norms": weights.step_weights(jax.tree.map(np.float64, change)),
            "teacher_change": float(teacher_change),
        }

    def drop_path_scales(self, n: int) -> list:
        """The first ``n`` steps' stochastic-depth draws, as the residual
        factors of every crop and block: the program's own plan of kept
        rows, mapped from its packed rows (global crops one a row, local
        crops k a row in order) to crops."""
        import jax
        import jax.numpy as jnp

        meta = self.setup.meta
        if not (meta.rng_plan and meta.crop_packing):
            raise SystemExit("benchmark: the check reads the draws of the "
                             "crop-packed RNG plan; this configuration has another")
        shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for k, v in self.pool[0].items()}
        layout = meta._packed_layout(shapes)
        if layout.groups != 1:
            raise SystemExit("benchmark: packed rows grouped by data shard are not read")
        n_g, n_l, rows = layout.n_global_rows, layout.n_local, layout.rows_total
        row_of_local = n_g + np.arange(n_l) // layout.k
        build = jax.jit(lambda key: meta.build_rng_plan(key, shapes)
                        ["packed"]["drop_path"]["idx"])
        out = []
        for i in range(n):
            idx = np.asarray(build(jax.random.fold_in(self.rng, self.start_it + i)))
            kept = np.zeros(idx.shape[:2] + (rows,), np.float32)
            np.put_along_axis(kept, idx, rows / idx.shape[-1], axis=-1)
            out.append({"global": jnp.asarray(kept[..., :n_g]),
                        "local": jnp.asarray(kept[..., row_of_local])})
        return out

    def reference(self, precision: str = "fp32") -> dict:
        """The reference's readings of the same first steps (in another
        ``precision``: a control's). Call it with the state freed: it needs
        the chip's memory."""
        import jax.numpy as jnp

        n = int(self.mix["warmup_steps"])
        keys = ("global_crops", "local_crops", "masks", "mask_indices",
                "mask_weights", "mask_valid")
        batches = [{k: jnp.asarray(self.pool[i % len(self.pool)][k]) for k in keys}
                   for i in range(n)]
        student = weights.step_weights(weights.fill(
            self.setup.state.params["student"], self.seed, jnp.float32))
        return ssl_step_fp32.first_steps(
            student, batches, self.drop_path_scales(n), self.recipe, self.start_it,
            precision)


def run(ctx) -> DriverResult:
    import jax

    rig = Rig(ctx.config, ctx.traffic, ctx.devices, ctx.seed, ctx.spans)
    rig.start(ctx.seed)
    batch, chips = rig.batch, rig.chips
    log(f"state built: batch {batch} ({rig.cfg.student.arch}), ring "
        f"{rig.plan.ring_len}, compiles so far {ctx.compiles.count}")

    # ---- set-up: the first steps of this state, through the window's call
    program = rig.first_steps()
    warm_steps = rig.steps
    n_warm_spans = len(ctx.spans.spans)
    setup_s = time.perf_counter() - ctx.t_start
    log(f"set-up {setup_s:.2f}s: first step (trace + compile or cache load + "
        f"step 0) {rig.first_step_s:.2f}s, backend compiles {ctx.compiles.count} "
        f"({ctx.compiles.compile_s:.1f}s), cache hits {ctx.compiles.cache_hits}")

    # ---- the window
    compiles_before = ctx.compiles.count + ctx.compiles.cache_hits
    t0 = time.perf_counter()
    rig.loop(stop_at_time=t0 + ctx.seconds)
    t_stop = time.perf_counter()
    jax.block_until_ready(rig.state.step)
    wall = time.perf_counter() - t0
    steps = rig.steps - warm_steps
    compiled_in_window = ctx.compiles.count + ctx.compiles.cache_hits - compiles_before
    img_per_s_chip = steps * batch / wall / chips
    log(f"window: {steps} steps of {batch} images in {wall:.3f}s (dispatching "
        f"stopped at {t_stop - t0:.3f}s) = {img_per_s_chip:.3f} img/s/chip")
    if compiled_in_window:
        raise SystemExit(f"benchmark: {compiled_in_window} program(s) compiled "
                         "or loaded inside the measured window")
    window_spans = ctx.spans.spans[n_warm_spans:]
    host_ms = sum(s.ms for s in window_spans if s.name in ("dispatch", "h2d"))
    log(f"window host time in dispatch + h2d: {host_ms / max(steps, 1):.2f} ms/step "
        "(includes back-pressure once the host runs ahead of the device)")

    counters = {"train_steps": steps, "train_batch": batch,
                "train_img_per_s_chip": img_per_s_chip}
    if ctx.tracer is not None:
        n = int(ctx.traffic["traced_steps"])
        rig.flush()  # no flush falls due inside the short traced stretch
        jax.block_until_ready((rig.state.step, rig.pending))
        with ctx.tracer:
            rig.loop(stop_at_step=rig.steps + int(ctx.traffic["trace_lead_steps"]))
            jax.block_until_ready((rig.state.step, rig.pending))
            with ctx.tracer.window():
                rig.loop(stop_at_step=rig.steps + n)
                jax.block_until_ready(rig.state.step)
        counters["train_steps_traced"] = n
        log(f"traced stretch: {n} steps in {ctx.tracer.window_s:.3f}s")
    rig.flush()
    final_step = int(rig.state.step)
    ctx.snapshot_memory()  # the program's peak, before the reference runs

    # ---- the output check, after the window, with the state freed
    rig.free()
    rows = rig.rows
    window_rows = rows[warm_steps:warm_steps + steps]
    failed = sum(1 for r in window_rows if not math.isfinite(r["total_loss"]))
    want_step = rig.start_it + rig.steps
    checks = [
        output_check.check("steps_applied", final_step, want_step,
                           final_step == want_step),
        output_check.check("nonfinite_loss_rows", failed, 0, failed == 0),
    ]
    log(f"losses: step 0 {rows[0]['total_loss']:.4f}, last {rows[-1]['total_loss']:.4f}")
    t0 = time.perf_counter()
    reference = rig.reference()
    checks.extend(step_check.checks_from_gaps(
        step_check.gaps(program, reference), ctx.config["check"]))
    log(f"reference of {warm_steps} steps took {time.perf_counter() - t0:.2f}s "
        "(not in setup_s)")
    return DriverResult(
        metrics={"setup_s": setup_s, "train_img_per_s_chip": img_per_s_chip},
        attempted=steps, failed=failed, checks=checks, counters=counters)
