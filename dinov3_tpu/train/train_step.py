"""The fused DINOv3 training step.

One jitted program per step (reference split it across three separate
jit+shard_map closures — train, EMA, metrics — train/train.py:588-604,
412-419): forward (teacher + student) -> backward -> per-submodel grad clip
-> scheduled-AdamW update -> teacher-EMA from the *updated* student params.
Fusing the EMA both fixes the reference's frozen-teacher bug by construction
(SURVEY.md §2.9.1) and lets XLA overlap the EMA's elementwise work with the
optimizer update.

The update phase itself has three implementations:
- the optax reference chain (clip -> scale_by_adam -> apply -> EMA, four
  sequential tree passes) — the test oracle, selected by
  ``optim.fused_update=false``;
- the single-pass fused engine (train/fused_update.py): one tree.map
  reading each fp32 master/moment/teacher leaf once and writing it once,
  attacking the ~12 ms/step weight-shaped HBM floor the r5 profile put
  inside the 28.5% norm/reduce bucket (PROFILE_r05.json,
  docs/PERFORMANCE.md);
- the cross-replica SHARDED form of that engine (the bucketed engine,
  default on a pure data-parallel mesh, ``optim.bucketed_collectives``):
  the grads are reduce-scattered a bucket at a time, each replica runs
  the same single pass over 1/dp of every leaf (moments stored sharded
  — ZeRO-1), and the updated student/teacher are all-gathered back into
  model layout. Both fused forms plug in through the same
  ``fused_update`` callable below — the step body cannot tell them
  apart.

Step randomness likewise has two implementations (the copy/small-op
sink, 14.8% of the r5 profile): the step-wide RNG plan (rng/plan.py,
default — a few large fused draws consumed as static slices) and the
legacy per-consumer fold_in chains behind ``rng.plan=false`` (the test
oracle). Both derive from ``fold_in(base, iteration)``, so draws at
iteration k are identical on resume either way.

Metrics delivery has two implementations too (telemetry/, PR 6): the
async path wraps this step with ``make_telemetry_step`` — the metrics
row lands in a donated on-device ring via one dynamic-update-slice,
nothing crosses to the host per step — while the oracle
(``telemetry.async_metrics=false``) returns the metrics dict for the
hot loop's per-step ``float(v)`` fetch, exactly as before.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from dinov3_tpu.parallel.sharding import constrain_batch_dim
from dinov3_tpu.train.optimizer import clip_by_per_submodel_norm
from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch
from dinov3_tpu.utils import step_phase


class TrainState(NamedTuple):
    params: Any        # {"student": .., ["teacher": ..], ["gram": ..]}
    opt_state: Any
    center_state: Any  # the meta-arch's non-param state (SSL: the
                       # softmax-centering EMA centers)
    step: jnp.ndarray
    # fp8/int8 delayed-scaling amax-history rings (ops/lowp.py):
    # {"student": tree, "teacher": tree} of f32 [H] (or [L, H] scanned)
    # leaves at the castable-kernel scale sites, advanced once per step
    # AFTER the optimizer/EMA update. None on the bf16 arm — the default
    # path carries no extra state and stays bitwise-identical.
    lowp: Any = None


def split_microbatches(batch: dict, accum_steps: int) -> dict:
    """Reshape a crop-major collated batch into ``accum_steps`` stacked
    microbatches for ``lax.scan``.

    Every array leaf is ``[k*B, ...]`` where B is the image batch and k
    the per-leaf crop multiplicity (2 for global-crop leaves, n_local
    for local crops, 1 for offsets/labels), stacked CROP-major
    (collate.py: crop 0 of all images, then crop 1 of all images, ...).
    A plain leading-dim split would therefore hand microbatch 0 only
    the first crops of everything. Instead each leaf regroups
    semantically — ``(k, accum, B/accum, ...)`` -> move the accum axis
    out front -> ``(accum, k*(B/accum), ...)`` — so microbatch j holds
    ALL crops of image subset j and is itself a valid crop-major batch
    (the loss couples crops of one image; the across-image reshuffle
    bytes this costs are negligible next to the param collectives the
    accumulation amortizes).

    Scalar leaves broadcast unchanged. Raises when ``accum_steps`` does
    not divide B (``configs.config.warn_accum_batch_tiling`` warns at
    config build; this is the traced-shape backstop).
    """
    if accum_steps <= 1:
        return batch
    b_global = batch["global_crops"].shape[0] // 2

    def _split(x):
        if getattr(x, "ndim", 0) == 0:
            return x
        n = x.shape[0]
        if n % b_global or b_global % accum_steps:
            raise ValueError(
                f"optim.accum_steps={accum_steps} cannot tile a batch "
                f"leaf of leading dim {n} (image batch {b_global}); "
                f"pick accum_steps dividing the per-step image batch."
            )
        k = n // b_global
        x = x.reshape((k, accum_steps, b_global // accum_steps)
                      + x.shape[1:])
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((accum_steps, k * (b_global // accum_steps))
                         + x.shape[3:])

    return {k: _split(v) for k, v in batch.items()}


def make_train_step(
    meta: SSLMetaArch,
    optimizer: optax.GradientTransformation,
    clip_grad: float | None = 3.0,
    monitor_grad_norm: bool = False,
    fused_update: Callable | None = None,
    accum_steps: int = 1,
    lowp: dict | None = None,
) -> Callable:
    """Returns step(state, batch, scalars, rng) -> (state, metrics).

    scalars: {"teacher_temp": f32, "momentum": f32} traced per-step values
    (indexed from the schedule arrays by the caller or in-graph).

    ``fused_update``: the single-pass clip+AdamW+EMA engine
    (train/fused_update.build_fused_update). When given, it replaces the
    clip -> optimizer.update -> apply_updates -> update_ema sequence; it
    must have been built with the same clip_grad/betas/multipliers as
    ``optimizer`` (build_train_setup guarantees this — both are wired
    from the same cfg and schedules).

    ``accum_steps`` (``optim.accum_steps``): microbatched gradient
    accumulation. The fwd/bwd runs as a ``lax.scan`` over
    ``split_microbatches(batch)``, rematerialized per microbatch
    (``jax.checkpoint``), with the zero3 param gathers HOISTED outside
    the scan as scan constants — the scan-constant transpose sums the
    per-microbatch cotangents inside the backward scan, so the grad
    reduce-scatter (the gather's transpose, bucketed under the unified
    engine) fires ONCE per optimizer step on the summed gradient, not
    once per microbatch. Loss/metrics/centers are microbatch means, so
    the optimizer consumes exactly the monolithic batch-mean gradient
    (up to reduction order) while peak activation memory drops by
    ~accum_steps. ``accum_steps=1`` is byte-for-byte the monolithic
    path.

    ``lowp`` (``configs.config.lowp_cfg``): the fp8/int8 delayed-scaling
    arm config. On a quantized arm the step computes this step's scales
    from the carried amax-history rings BEFORE the forward
    (``ops.lowp.lowp_scales`` — pure elementwise math on tiny f32
    leaves), threads them through ``meta.forward`` as the read-only
    "lowp" collection, and advances the rings from the UPDATED masters
    after the optimizer/EMA update (``lowp_amax`` named scope — the amax
    over a zero3-sharded master is a scalar all-reduce-max). bf16 arm:
    no scales, no ring advance, bitwise-identical step.
    """
    if accum_steps < 1:
        raise ValueError(
            f"optim.accum_steps must be >= 1, got {accum_steps}")
    if accum_steps > 1 and not meta.supports_accum:
        raise ValueError(
            "optim.accum_steps > 1 splits a crop-major SSL batch "
            f"(split_microbatches); {type(meta).__name__} takes 1")
    lowp_arm = (lowp or {}).get("arm", "bf16")

    def step(state: TrainState, batch: dict, scalars: dict, rng: jax.Array):
        it = state.step
        # counter-based step key: a pure function of (base key, iteration),
        # so draws at iteration k are identical whether the run reached k
        # uninterrupted or restarted from a checkpoint (both rng paths)
        rng = jax.random.fold_in(rng, it)
        frozen = {k: v for k, v in state.params.items() if k != "student"}

        fwd_lowp = None
        if lowp_arm != "bf16" and state.lowp is not None:
            from dinov3_tpu.ops.lowp import lowp_scales

            fwd_lowp = {
                k: lowp_scales(h, lowp_arm, lowp["scale_margin"])
                for k, h in state.lowp.items()
            }

        if accum_steps == 1:
            rngs = rng_plan = None
            if meta.rng_plan:
                # step-wide RNG plan (rng/plan.py): a handful of large
                # fused draws replace the per-consumer fold_in chains
                # below — the copy/small-op dispatch sink the r5 profile
                # priced at 14.8%
                with step_phase("rng_plan"):
                    rng_plan = meta.build_rng_plan(rng, batch)
            else:
                rngs = {
                    "drop_path": jax.random.fold_in(rng, 0),
                    "rope": jax.random.fold_in(rng, 1),
                    "dropout": jax.random.fold_in(rng, 2),
                }

            def loss_fn(student_params):
                return meta.forward(
                    student_params, frozen, batch,
                    teacher_temp=scalars["teacher_temp"],
                    state=state.center_state,
                    iteration=it,
                    rngs=rngs,
                    rng_plan=rng_plan,
                    lowp=fwd_lowp,
                )

        else:
            micro = split_microbatches(batch, accum_steps)

            def loss_fn(student_params):
                # gather ONCE, outside the microbatch scan: the gathered
                # trees enter the scan as constants, so autodiff's
                # scan-constant transpose SUMS the per-microbatch
                # cotangents inside the backward scan and the gather's
                # transposed reduce-scatter (one staged RS per bucket
                # under the unified engine) runs once on the summed
                # gradient per optimizer step
                student_g = meta._zero3_gather_params(student_params)
                frozen_g = meta._zero3_gather_params(frozen)

                def one_micro(sp, fz, mb, rj):
                    # pin the sliced microbatch back onto the canonical
                    # batch-dim layout (the put_batch rule): after the
                    # scan's dynamic-slice the partitioner is free to
                    # pick any layout for mb, and the forward's
                    # shard_map islands are reduction-order-sensitive
                    # to it — unconstrained, the accum arm computes on
                    # a DIFFERENT layout than the monolithic oracle
                    # (~1e-2 loss drift at bf16; ~3e-3 activations even
                    # at fp32 on the 2x4 dryrun mesh)
                    mb = {
                        k: constrain_batch_dim(v, 0)
                        if getattr(v, "ndim", 0) > 0 else v
                        for k, v in mb.items()
                    }
                    rngs_j = plan_j = None
                    if meta.rng_plan:
                        with step_phase("rng_plan"):
                            plan_j = meta.build_rng_plan(rj, mb)
                    else:
                        rngs_j = {
                            "drop_path": jax.random.fold_in(rj, 0),
                            "rope": jax.random.fold_in(rj, 1),
                            "dropout": jax.random.fold_in(rj, 2),
                        }
                    loss_j, (ld_j, nc_j) = meta.forward(
                        sp, fz, mb,
                        teacher_temp=scalars["teacher_temp"],
                        state=state.center_state,
                        iteration=it,
                        rngs=rngs_j,
                        rng_plan=plan_j,
                        gather_params=False,
                        lowp=fwd_lowp,
                        n_micro=accum_steps,
                    )
                    return loss_j, ld_j, nc_j

                # rematerialize per microbatch: live activations are one
                # microbatch deep, the point of accumulating at all
                one_micro = jax.checkpoint(one_micro)

                def body(carry, xs):
                    j, mb = xs
                    rj = jax.random.fold_in(rng, j)
                    loss_j, ld_j, nc_j = one_micro(
                        student_g, frozen_g, mb, rj)
                    return carry + loss_j, (ld_j, nc_j)

                total, (ld_stack, nc_stack) = jax.lax.scan(
                    body, jnp.zeros((), jnp.float32),
                    (jnp.arange(accum_steps), micro),
                )
                # microbatch means == monolithic batch means (equal
                # microbatch sizes; centering EMAs likewise average to
                # the monolithic update since every microbatch centers
                # with the same incoming state)
                mean0 = lambda x: jnp.mean(x, axis=0)  # noqa: E731
                return total / accum_steps, (
                    jax.tree.map(mean0, ld_stack),
                    jax.tree.map(mean0, nc_stack),
                )

        (loss, (loss_dict, new_centers)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params["student"])

        metrics = dict(loss_dict)
        # a meta-arch with a student and no teacher (lm_meta_arch.py): no
        # ``teacher`` entry in the params, no EMA leg in the update
        teacher = state.params.get("teacher")
        with step_phase("update"):
            if fused_update is not None:
                # single pass over every weight-shaped leaf: clip scales
                # from one up-front batched reduction, AdamW + EMA folded
                # into one tree.map (train/fused_update.py)
                new_student, new_teacher, new_opt_state, norms = fused_update(
                    grads, state.params["student"], teacher,
                    state.opt_state, scalars["momentum"],
                )
            else:
                norms = {}
                if clip_grad is not None and clip_grad > 0:
                    grads, norms = clip_by_per_submodel_norm(grads, clip_grad)
                updates, new_opt_state = optimizer.update(
                    grads, state.opt_state, state.params["student"]
                )
                new_student = optax.apply_updates(
                    state.params["student"], updates)
                new_teacher = teacher if teacher is None else meta.update_ema(
                    teacher, new_student, scalars["momentum"])
            new_lowp = state.lowp
            if fwd_lowp is not None:
                # delayed scaling: the rings observe the UPDATED masters
                # as part of the update epilogue (train/fused_update.py)
                from dinov3_tpu.train.fused_update import lowp_state_step

                new_lowp = lowp_state_step(
                    state.lowp, new_student, new_teacher)
        if monitor_grad_norm:
            for k, v in norms.items():
                metrics[f"grad_norm/{k}"] = v
        new_params = dict(state.params)
        new_params["student"] = new_student
        if teacher is not None:
            new_params["teacher"] = new_teacher

        new_state = TrainState(
            params=new_params,
            opt_state=new_opt_state,
            center_state=new_centers,
            step=it + 1,
            lowp=new_lowp,
        )
        return new_state, metrics

    return step


def make_telemetry_step(step: Callable, metric_names) -> Callable:
    """Wrap a ``step(state, batch, scalars, rng) -> (state, metrics)``
    into the async-telemetry form ``(state, ring, batch, scalars, rng)
    -> (state, ring)``.

    The metrics dict never becomes a program output: its scalars are
    stacked into one f32 row and written into the donated ring at slot
    ``state.step % K`` (telemetry/ring.py write_row — one
    dynamic-update-slice under the ``telemetry_ring`` named scope, so
    the copy census attributes it), and the device-side non-finite
    streak scalar is advanced from ``total_loss``. ``metric_names``
    fixes the column order (the host reader interprets columns by it);
    setup derives it from an ``eval_shape`` of the raw step so the two
    can never drift.
    """
    from dinov3_tpu.telemetry.ring import write_row

    names = list(metric_names)

    def telemetry_step(state: TrainState, ring, batch: dict, scalars: dict,
                       rng: jax.Array):
        it = state.step  # pre-increment iteration stamps the row
        new_state, metrics = step(state, batch, scalars, rng)
        return new_state, write_row(ring, it, metrics, names)

    return telemetry_step
