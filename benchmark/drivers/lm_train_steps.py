"""Driver ``lm_train_steps``: the trainer's hot loop on a decoder's
next-token step, timed.

The loop, its spans (``dispatch``, ``h2d``, ``metrics_flush``) and its
counters are ``train_steps.Rig``'s, unchanged: do_train's order, a pool of
host batches cycled, the metrics ring flushed when due, nothing fenced in
between. What differs is what a decoder's step lacks and has: no teacher
and no stochastic depth; a state made from ``--seed`` by ``lm_weights.py``
(the decays' initial values are the released code's); tokens for crops;
and the check.

``correct`` (``lm_step_check.py``): the first ``warmup_steps`` steps of
the timed state — each step's loss, the first gradient as the optimizer
got it (first moment after one step / (1 - beta1)), every leaf's change —
against ``reference/kimi_linear_fp32.py`` on the same weights and tokens
and the program's own expert choices of those steps (the meta-arch's
``routing`` on each step's weights and batch before the step is
dispatched: the step itself keeps no such thing), once the window has
closed and the state is freed; a finite loss in every window row and no
overflow of the routed layers' row buffers.

One sequence counts as one image: ``train_img_per_s_chip`` is the
benchmark's one training rate (x 8,192 = tokens/s/chip).

End-to-end metrics computed here: ``setup_s``, ``train_img_per_s_chip``.
"""

from __future__ import annotations

import math
import time

import numpy as np

import lm_step_check
import lm_weights
import output_check
import weights
from reference import kimi_linear_fp32
from run import DRIVER_DIR, DriverResult, load_module, log

train_steps = load_module(DRIVER_DIR, "train_steps")


class Rig(train_steps.Rig):
    """``train_steps.Rig`` for a meta-arch with a student and no teacher."""

    def __init__(self, conf, mix, devices, seed, spans, extra_overrides=()):
        super().__init__(conf, mix, devices, seed, spans, extra_overrides)
        self.recipe = kimi_linear_fp32.Recipe.from_config(conf["reference"])
        self.shape = kimi_linear_fp32.Shape.from_config(conf["shape"])

    def _fresh_state(self, seed: int):
        """The whole train state in one jitted call: the student from the
        seed, fresh moments, both counters at the start iteration."""
        import jax
        import jax.numpy as jnp

        abstract = self.setup.state
        student = abstract.params["student"]

        def make(key):
            state = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract)
            start = jnp.asarray(self.start_it, jnp.int32)
            return state._replace(
                params={"student": lm_weights.fill_leaves(student, key)},
                step=start, opt_state=state.opt_state._replace(count=start))

        with self.setup.mesh:
            return jax.jit(make, out_shardings=self.setup.state_shardings)(
                weights.seed_key(seed, weights.FILL_STREAM))

    def first_steps(self) -> dict:
        """Drive the state through its first ``warmup_steps`` steps by the
        window's own call and feed. What the check needs of them: each
        step's expert choices (the program's routing on the weights and
        the batch the step is about to take) and, after the first, the
        first moment (host copies: the window needs the chip's memory),
        per-step losses and per-leaf norms of the student's change."""
        import jax

        n = int(self.mix["warmup_steps"])
        t0 = time.perf_counter()
        self.choices = []
        with self.setup.mesh:
            routing = jax.jit(self.setup.meta.routing)
        for i in range(n):
            with self.setup.mesh:
                self.choices.append(np.asarray(routing(
                    self.state.params["student"], self.pending)))
            self.loop(stop_at_step=i + 1)
            if i == 0:
                self.first_step_s = time.perf_counter() - t0
                self.first_moment = jax.device_get(
                    self.state.opt_state.adam.mu["backbone"])
        self.flush()  # warms the flush path too; the window starts with an empty ring
        abstract = self.setup.state.params["student"]

        def changes(student, key):
            # the seed's weights again, leaf by leaf inside this one
            # program: no second copy of the student is ever held
            old = lm_weights.fill_leaves(abstract, key)
            return train_steps._leaf_norms(
                jax.tree.map(lambda a, b: a - b, student, old))

        change = jax.jit(changes)(
            self.state.params["student"],
            weights.seed_key(self.seed, weights.FILL_STREAM))
        jax.block_until_ready((self.state.step, self.pending))
        return {
            "losses": [row["total_loss"] for row in self.rows[:n]],
            "change_norms": lm_weights.reference_tree(
                jax.tree.map(np.float64, change)["backbone"]),
        }

    def reference(self, variant: str = "fp32", against=None,
                  keep_host: bool = False) -> dict:
        """The reference's readings of the same first steps (``variant``:
        a control's), with the per-leaf norms of the difference between
        its first gradient and ``against`` (a host tree in the reference's
        layout; default: the program's own, from its first moment).
        ``keep_host`` adds that gradient as ``gradient_host``, for a
        control to be laid against. Call it with the state freed: it
        needs the chip's memory."""
        import jax
        import jax.numpy as jnp

        n = int(self.mix["warmup_steps"])
        w = lm_weights.reference_tree(lm_weights.fill(
            self.setup.state.params["student"], self.seed)["backbone"])
        batches = [jnp.asarray(self.pool[i % len(self.pool)]["tokens"])
                   for i in range(n)]
        scale = np.float32(1.0)
        if against is None:
            against = lm_weights.reference_tree(self.first_moment)
            scale = np.float32(1.0 / (1.0 - self.recipe.beta1))
        diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a * scale - b))))
        out = {}

        def keep(g):  # leaf by leaf: one leaf of the other side's at a time
            out["grad_diff_norms"] = jax.tree.map(
                lambda a, b: np.float64(diff(a, b)), against, g)
            out["other_grad_norms"] = jax.tree.map(
                lambda a: np.float64(np.linalg.norm(a)) * scale, against)
            if keep_host:
                out["gradient_host"] = jax.device_get(g)

        out.update(kimi_linear_fp32.first_steps(
            w, batches, [jnp.asarray(c) for c in self.choices], self.shape,
            self.recipe, self.start_it, variant, keep_gradient=keep))
        return out


def run(ctx) -> DriverResult:
    import jax

    rig = Rig(ctx.config, ctx.traffic, ctx.devices, ctx.seed, ctx.spans)
    rig.start(ctx.seed)
    batch, chips = rig.batch, rig.chips
    log(f"state built: batch {batch} x {rig.cfg.lm.seq_len} tokens "
        f"({rig.cfg.student.arch}), ring {rig.plan.ring_len}, compiles so far "
        f"{ctx.compiles.count}")

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
    seq_per_s_chip = steps * batch / wall / chips
    log(f"window: {steps} steps of {batch} sequences in {wall:.3f}s (dispatching "
        f"stopped at {t_stop - t0:.3f}s) = {seq_per_s_chip:.4f} sequences/s/chip, "
        f"{seq_per_s_chip * int(rig.cfg.lm.seq_len):.0f} tokens/s/chip")
    if compiled_in_window:
        raise SystemExit(f"benchmark: {compiled_in_window} program(s) compiled "
                         "or loaded inside the measured window")
    window_spans = ctx.spans.spans[n_warm_spans:]
    host_ms = sum(s.ms for s in window_spans if s.name in ("dispatch", "h2d"))
    log(f"window host time in dispatch + h2d: {host_ms / max(steps, 1):.2f} ms/step "
        "(includes back-pressure once the host runs ahead of the device)")

    counters = {"train_steps": steps, "train_batch": batch,
                "train_img_per_s_chip": seq_per_s_chip}
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
    overflow = sum(r["moe_rows_overflow"] for r in rows)
    if window_rows:
        counters["lm_moe_load_max_over_mean"] = float(np.mean(
            [r["moe_load_max_over_mean"] for r in window_rows]))
    want_step = rig.start_it + rig.steps
    checks = [
        output_check.check("steps_applied", final_step, want_step,
                           final_step == want_step),
        output_check.check("nonfinite_loss_rows", failed, 0, failed == 0),
        output_check.check("moe_rows_overflow", overflow, 0, overflow == 0),
    ]
    log(f"losses: step 0 {rows[0]['total_loss']:.4f}, last {rows[-1]['total_loss']:.4f}; "
        f"moe_rows_fill {max(r['moe_rows_fill'] for r in rows):.4f} at most")
    t0 = time.perf_counter()
    reference = rig.reference()
    checks.extend(lm_step_check.checks_from_gaps(
        lm_step_check.gaps(program, reference), ctx.config["check"]))
    for path, grad, change in lm_step_check.worst_leaves(program, reference):
        log(f"worst leaves: {path:<64s} grad_diff {grad:.4f}  param_change {change:.4f}")
    log(f"reference of {warm_steps} steps took {time.perf_counter() - t0:.2f}s "
        "(not in setup_s)")
    return DriverResult(
        metrics={"setup_s": setup_s, "train_img_per_s_chip": seq_per_s_chip},
        attempted=steps, failed=failed, checks=checks, counters=counters)
