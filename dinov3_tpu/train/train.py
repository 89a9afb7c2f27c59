"""DINOv3 pretraining entry point.

Usage (reference-compatible surface, dinov3_jax/train/train.py:51-72):

    python -m dinov3_tpu.train.train \
        --config-file configs/train/vitl_smoke.yaml \
        --output-dir /tmp/run \
        optim.epochs=1 train.batch_size_per_device=8

Differences from the reference loop (all SURVEY.md §7.1 by design):
- one fused jitted step (fwd+bwd+clip+adamw+EMA) instead of three
  jit(shard_map) closures; the teacher EMA actually feeds back (§2.9.1);
- multi-axis GSPMD mesh instead of the hand-rolled FSDP interceptor;
- schedules indexed in-graph; only teacher_temp/momentum cross the host
  boundary per step (as replicated scalars);
- async orbax checkpointing with working retention (§2.9.3);
- NaN watchdog preserved (>2 consecutive non-finite losses aborts; under
  async metrics the streak counts on device and the abort lands at the
  next flush — flush-granularity latency, never a missed abort);
- optional jax.profiler trace window (the reference stopped a trace it
  never started, §5.1), folded into the phase-span tracer.

Metrics delivery (telemetry/, PR 6): by default the jitted step writes
its scalar metrics into a donated on-device ring and the host issues ONE
blocking device->host fetch per ``telemetry.flush_every`` steps; the
pre-PR-6 per-step ``float(v)`` fetch — which fenced dispatch every step
— stays as the oracle behind ``telemetry.async_metrics=false``. The
hot loop's host phases (data-wait, h2d, dispatch, flush, gram, eval,
checkpoint) are span-traced to JSONL with a per-process heartbeat file
(mtime = liveness), and per-device memory is sampled at flushes and
setup/compile boundaries (COST_HSYNC_r11.json / MEM_r11.json are the
committed accounting).
"""

from __future__ import annotations

import argparse
import logging
import math
import sys
import time

import jax
import jax.numpy as jnp

from dinov3_tpu.checkpoint import Checkpointer
from dinov3_tpu.configs import load_config, setup_job
from dinov3_tpu.logging_utils import MetricLogger, setup_logging
from dinov3_tpu.parallel import initialize_distributed, is_main_process
from dinov3_tpu.telemetry import spans
from dinov3_tpu.train.setup import build_train_setup, put_batch

logger = logging.getLogger("dinov3")


def get_args_parser():
    p = argparse.ArgumentParser("DINOv3 TPU pretraining")
    p.add_argument("--config-file", default="", help="run recipe YAML")
    p.add_argument("--output-dir", default=".", help="logs + checkpoints")
    p.add_argument("--no-resume", action="store_true",
                   help="do not resume from the latest checkpoint")
    p.add_argument("--profile-steps", default="",
                   help="'start,stop' step range to capture a jax profiler "
                        "trace into <output-dir>/trace")
    p.add_argument("--max-iterations", type=int, default=-1,
                   help="hard cap on iterations (smoke runs)")
    p.add_argument("--record-losses", default="",
                   help="write per-iteration losses to this JSON-lines file "
                        "(numerical-parity recording)")
    p.add_argument("--ref-losses", default="",
                   help="compare per-iteration losses against a recorded "
                        "file; divergences are logged and summarized")
    p.add_argument("--dump-weights", default="",
                   help="after training, dump final params to this .npz")
    p.add_argument("--benchmark", type=int, default=0, metavar="N",
                   help="measure steady-state step time over the last N "
                        "iterations and log img/s")
    p.add_argument("--self-check", action="store_true",
                   help="run two diagnostic steps on one batch (losses "
                        "finite, every submodule trains, teacher EMA "
                        "tracks) and exit")
    p.add_argument("--tensorboard", action="store_true",
                   help="mirror metrics to <output-dir>/tb tensorboard "
                        "events in addition to training_metrics.json")
    p.add_argument("--debug-nans", action="store_true",
                   help="enable jax_debug_nans: the first op producing a "
                        "NaN raises with its location (slower; de-fuses "
                        "the step for op-level blame)")
    p.add_argument("--resume-topology", default="auto",
                   choices=("auto", "memory", "disk"),
                   help="topology-elastic resume path when the run "
                        "resumes under a different (mesh, arm) than the "
                        "one that saved: 'memory' reshards a still-live "
                        "train state in place (parallel/reshard.py — a "
                        "resize without preemption, no disk round-trip), "
                        "'disk' always restores through the checkpoint "
                        "adapter, 'auto' picks memory whenever a live "
                        "state is supplied and its mesh is still "
                        "reachable")
    p.add_argument("opts", nargs="*", default=[],
                   help="key.path=value config overrides")
    return p


def build_data_iterator(cfg, global_batch_size: int, rank: int = 0,
                        world_size: int = 1, start_iter: int = 0):
    """Host-side data iterator yielding collated numpy batches.

    Each host yields only its ``global/world`` shard (the reference striped
    by rank in EpochSampler, dinov3_jax/data/samplers.py:49-60), and
    ``start_iter`` resumes the data stream mid-run instead of replaying it
    from batch 0 (reference intent: dinov3_jax/train/train.py:840).
    """
    if global_batch_size % max(1, world_size):
        raise ValueError(
            f"global batch {global_batch_size} not divisible by "
            f"{world_size} hosts"
        )
    backend = cfg.data.backend
    if backend == "synthetic":
        from dinov3_tpu.data import SyntheticDataset
        from dinov3_tpu.data.multires import (
            CombineDataLoader,
            multires_subconfigs,
            split_advance,
        )

        local = global_batch_size // max(1, world_size)
        subs = multires_subconfigs(cfg)
        if subs is None:
            return iter(SyntheticDataset(
                cfg, local, seed=cfg.train.seed, rank=rank,
                world_size=world_size, advance=start_iter,
            ))
        # multi-resolution recipes (crop-size lists) get one synthetic
        # stream per resolution, combined exactly like the real pipeline
        ratios = [r for _, r in subs]
        counts = split_advance(cfg.train.seed, ratios, start_iter)
        loaders = [
            iter(SyntheticDataset(
                sub, local, seed=cfg.train.seed + 7919 * j, rank=rank,
                world_size=world_size, advance=int(counts[j]),
            ))
            for j, (sub, _) in enumerate(subs)
        ]
        combined = CombineDataLoader(loaders, ratios, seed=cfg.train.seed)
        if start_iter:
            combined.advance(start_iter)
        return iter(combined)
    if backend in ("folder", "imagenet"):
        from dinov3_tpu.data.pipeline import make_multires_train_pipeline

        # routes to the single-resolution pipeline unless the recipe
        # declares crop-size lists (vit7b16_high_res_adapt.yaml)
        return make_multires_train_pipeline(
            cfg, global_batch_size, rank=rank, world_size=world_size,
            sampler_advance_batches=start_iter,
        )
    raise ValueError(f"unknown data backend {backend!r}")


def do_train(cfg, args, *, devices=None, data_rank=None, data_world=None,
             process_group=None, group_name=None, live_state=None,
             live_topology=None) -> dict:
    """Train one model. With the keyword arguments a multidistillation
    subgroup trains its student on a device-subset mesh: ``devices`` are
    the group's devices, ``data_rank``/``data_world`` its host-shard
    coordinates, ``process_group`` its process indices (checkpoint barrier
    scope).

    ``live_state``/``live_topology``: a still-live ``TrainState`` and its
    ``TopologyDesc`` from a previous incarnation in THIS process (an
    elastic supervisor resizing without preemption — scripts/
    cost_reshard.py drives exactly this). Under ``--resume-topology
    auto|memory`` the resume reshards it in memory
    (``parallel/reshard.py``) instead of round-tripping through disk;
    a real preemption (process death) leaves them None and the
    checkpoint path restores across the topology change instead."""
    from dinov3_tpu.configs import global_batch_size
    from dinov3_tpu.parallel import process_count, process_index

    n_devices = len(devices) if devices is not None else jax.device_count()
    B = global_batch_size(cfg, n_devices)
    rank = data_rank if data_rank is not None else process_index()
    world = data_world if data_world is not None else process_count()

    ckpt = Checkpointer(
        f"{cfg.train.output_dir}/ckpt",
        max_to_keep=cfg.checkpointing.max_to_keep,
        keep_every=cfg.checkpointing.get("keep_every"),
        process_group=process_group,
        sync_prefix=group_name,
    )
    # the resume point decides where the data stream starts, so it must be
    # known before the iterator is built. A live in-memory state (elastic
    # resize without preemption) resumes even with no checkpoint on disk.
    start_iter = 0
    resuming = not args.no_resume and (
        ckpt.latest_step() is not None or live_state is not None)
    if resuming:
        start_iter = (int(live_state.step) if live_state is not None
                      else int(ckpt.latest_step()))

    # set-up goes to the process's span log (telemetry/spans.py): the
    # tracer that will stream it needs this function's own results. A
    # second incarnation in one process starts a set-up of its own
    log = spans.LOG
    setup_began = log.begin_setup()
    with log.span("setup.data_iterator"):
        data_iter = build_data_iterator(
            cfg, B, rank=rank, world_size=world, start_iter=start_iter)
        first = next(data_iter)
    # serve-backed teacher (distillation.teacher_source=serve): the
    # frozen teacher forwards OUTSIDE the step — a host-shared packed
    # AOT engine + content-addressed cache (train/distillation.py
    # TeacherServer) computes CLS+patch planes once per image and the
    # step consumes them as teacher_cls/teacher_patches batch inputs
    from dinov3_tpu.configs.config import distill_teacher_source

    serve_teacher = (cfg.distillation.enabled
                     and distill_teacher_source(cfg) == "serve")
    # setup traces with *global* shapes; the example's values never reach
    # the trained params (init depends only on the rng), so a zeros batch
    # keeps the traced constant identical across hosts
    if world > 1:
        example = {
            k: jnp.zeros((v.shape[0] * world,) + v.shape[1:], v.dtype)
            for k, v in first.items()
        }
    else:
        example = {k: jnp.asarray(v) for k, v in first.items()}
    if serve_teacher:
        from dinov3_tpu.train.distillation import teacher_feature_example

        example.update({
            k: jnp.asarray(v) for k, v in teacher_feature_example(
                cfg, int(example["global_crops"].shape[0])).items()
        })
    # one collate call (one ``sample_ibot_masks``) a host batch: the
    # loader's call pattern sizes the step's compact iBOT buffer
    setup = build_train_setup(cfg, example, devices=devices,
                              mask_sampler_calls=world)
    mask_rows_limit = setup.mask_rows_limit(first)
    # the bucketed collective engine keeps adam moments in the bucket
    # layout; the checkpointer needs the plan to convert to/from the
    # per-leaf on-disk layout (checkpoint.py)
    ckpt.bucket_plan = getattr(setup, "bucket_plan", None)
    # the (mesh, arm) sidecar every save carries — an elastic resume (or
    # scripts/cost_reshard.py) reads it to know which transition it is
    # about to cross
    from dinov3_tpu.parallel.reshard import describe_topology, topology_of

    run_topology = describe_topology(topology_of(setup))
    logger.info(
        "mesh %s | global batch %d | %d devices x %d hosts | setup %.1fs",
        dict(setup.mesh.shape), B, n_devices, world,
        log.seconds("setup.build", since=setup_began),
    )

    if args.self_check:
        from dinov3_tpu.train.self_check import run_self_check

        check_batch = first
        if serve_teacher:
            # self-check runs pre-restore (random teacher weights):
            # zero teacher planes exercise the mechanics without
            # building a server around weights nobody will train with
            from dinov3_tpu.train.distillation import (
                teacher_feature_example,
            )

            check_batch = {**first, **teacher_feature_example(
                cfg, int(first["global_crops"].shape[0]))}
        results = run_self_check(
            setup, put_batch(check_batch, setup.batch_shardings),
            jax.random.key(cfg.train.seed + 1),
        )
        return {"self_check_failures": sum(not v for v in results.values()),
                **{f"check/{k}": v for k, v in results.items()}}

    total_iters = cfg.optim.epochs * cfg.train.OFFICIAL_EPOCH_LENGTH
    if args.max_iterations > 0:
        total_iters = min(total_iters, args.max_iterations)

    state = setup.state
    if resuming:
        from dinov3_tpu.train.setup import elastic_resume

        with log.span("setup.restore") as restore:
            state, resume_info = elastic_resume(
                setup, ckpt,
                live_state=live_state, live_topology=live_topology,
                policy=getattr(args, "resume_topology", "auto") or "auto",
            )
            restore["path"] = resume_info["path"]
        logger.info("elastic resume via %s path (%.2fs)",
                    restore["path"], restore["dur_ms"] / 1e3)
        # the freshly initialised state was only the restore's template:
        # free its buffers now, or the run holds TWO full states on the
        # device for its whole life (ViT-L at B=12 on one 16 GB chip:
        # the first resumed step failed with RESOURCE_EXHAUSTED)
        kept = {id(x) for x in jax.tree.leaves(state)}
        for leaf in jax.tree.leaves(setup.state):
            if id(leaf) not in kept and not leaf.is_deleted():
                leaf.delete()
        setup.state = state
        if int(state.step) != start_iter:
            # a partially-committed async save can be cleaned up between
            # latest_step() and restore(); realign the data stream with
            # the step actually restored instead of training on a stream
            # advanced by the stale announced value (ADVICE r2)
            logger.warning(
                "restored step %d != announced latest %d; rebuilding the "
                "data iterator at the restored step",
                int(state.step), start_iter,
            )
            start_iter = int(state.step)
            if hasattr(data_iter, "close"):
                # wind down the abandoned pipeline's prefetch threads
                # (generator close propagates to the loader's finally)
                data_iter.close()
            data_iter = build_data_iterator(
                cfg, B, rank=rank, world_size=world, start_iter=start_iter
            )
            first = next(data_iter)
        else:
            start_iter = int(state.step)
        logger.info("resumed at iteration %d", start_iter)
    elif cfg.distillation.enabled and cfg.distillation.checkpoint_path:
        from dinov3_tpu.train.distillation import load_teacher_params

        state = load_teacher_params(cfg, state, setup.state_shardings)
    elif cfg.hrft.enabled and cfg.hrft.checkpoint_path:
        hrft_ckpt = Checkpointer(cfg.hrft.checkpoint_path)
        state = hrft_ckpt.restore_params_only(state)
        hrft_ckpt.close()
        logger.info("hrft: params loaded from %s", cfg.hrft.checkpoint_path)
    elif (cfg.student.get("pretrained_weights")
          or cfg.student.get("resume_from_teacher_chkpt")):
        from dinov3_tpu.train.pretrained import load_pretrained_weights

        state = load_pretrained_weights(cfg, state, setup.state_shardings)
    if start_iter == 0 and cfg.gram.get("ckpt"):
        # fresh run with an external gram anchor (gram-anchor phase):
        # the frozen gram backbone comes from a prior run's EMA teacher
        from dinov3_tpu.train.gram_refresh import load_gram_teacher

        state = load_gram_teacher(cfg, state, setup.state_shardings)

    teacher_server = None
    if serve_teacher:
        # process-level shared server (multidistillation.py): co-hosted
        # student subgroups with the same teacher get ONE engine + ONE
        # cache — one teacher forward per image per host, k students or
        # not. From a checkpoint the server restores host-side (each
        # host replicates the serving tree — no cross-host gather);
        # otherwise it serves the state's restored teacher backbone.
        from dinov3_tpu.train.multidistillation import shared_teacher_server

        if cfg.distillation.checkpoint_path:
            teacher_server = shared_teacher_server(
                cfg, ckpt_dir=cfg.distillation.checkpoint_path)
        else:
            teacher_server = shared_teacher_server(
                cfg, teacher_params=jax.device_get(
                    state.params["teacher"]["backbone"]))
        logger.info("distillation: serve-backed teacher %s",
                    teacher_server.stats())

    prof = None
    if args.profile_steps:
        a, b = (int(x) for x in args.profile_steps.split(","))
        prof = (a, b)

    from dinov3_tpu.telemetry import (
        SpanTracer,
        StepTimer,
        Watchdog,
        blocking_fetch,
    )
    from dinov3_tpu.utils import (
        LossComparator,
        LossRecorder,
        count_parameters,
        format_parameter_counts,
    )

    logger.info("parameters:\n%s", format_parameter_counts(
        count_parameters(state.params)))
    # metrics are cross-device means, identical on every host of this
    # (sub)group: record and compare only on the group's primary host
    # (global rank 0 normally; the lowest group rank under
    # multidistillation, where each student owns its output dir)
    main_here = rank == 0
    recorder = (LossRecorder(args.record_losses)
                if args.record_losses and main_here else None)
    comparator = (LossComparator(args.ref_losses)
                  if args.ref_losses and main_here else None)
    bench_n = max(0, int(args.benchmark))

    metric_logger = MetricLogger(
        output_file=f"{cfg.train.output_dir}/training_metrics.json"
        if main_here else None,
        tensorboard_dir=f"{cfg.train.output_dir}/tb"
        if (args.tensorboard and main_here) else None,
    )

    # telemetry engine (telemetry/): async metrics ring (None = the
    # per-step-fetch oracle behind telemetry.async_metrics=false),
    # phase-span tracer + per-process heartbeat, memory sampling
    tele_cfg = cfg.get("telemetry") or {}
    from dinov3_tpu.configs.config import anatomy_wished

    anatomy_on = anatomy_wished(cfg)
    plan = setup.telemetry()
    tracer = SpanTracer(
        cfg.train.output_dir, rank=rank,
        enabled=bool(tele_cfg.get("spans", True)),
        heartbeat_every=int(tele_cfg.get("heartbeat_every", 1)),
        profile_steps=prof, profile_dir=f"{cfg.train.output_dir}/trace",
        role="train",
        flush_every_emits=int(tele_cfg.get("span_autoflush_every", 32)),
        log=log,
    )
    # unified watchdog (telemetry/watchdog.py): a metrics-flush window
    # whose wall time exceeds the deadline emits a stall span into the
    # same stream the phase spans live in (0 = disabled)
    watchdog = Watchdog(tracer, deadline_s=float(
        tele_cfg.get("flush_deadline_s", 0.0) or 0.0))
    from dinov3_tpu.telemetry import emit_preempt_chain, last_preempt_record

    if resuming and tracer.enabled:
        # third link of the preemption span chain, with the seconds of
        # the ``setup.restore`` span; joining against the dead
        # incarnation's preempt_save record on the same stream yields
        # the preemption-to-resume latency
        prev_save = last_preempt_record(cfg.train.output_dir,
                                        "preempt_save")
        rec = {"dur_ms": restore["dur_ms"], "path": restore["path"]}
        if prev_save is not None:
            rec["since_preempt_s"] = round(
                time.time() - float(prev_save["t"]), 3)
        emit_preempt_chain(tracer, "resume_restore", start_iter, **rec)
    memory_on = bool(tele_cfg.get("memory", True)) and tracer.enabled
    if memory_on:
        tracer.emit_memory("setup")

    rng = jax.random.key(cfg.train.seed + 1)
    nan_streak = 0
    last_loss = math.nan
    # total_loss of every step this call ran, in order (returned to the
    # caller; chip_smoke.py checks them one by one)
    loss_history: list[float] = []
    header = "Train"

    from dinov3_tpu.train.gram_refresh import (
        gram_updates_before,
        refresh_gram,
        should_refresh_gram,
    )

    n_gram_updates = gram_updates_before(cfg, start_iter)

    from dinov3_tpu.run.preemption import PreemptionHandler

    preemption = PreemptionHandler().__enter__()

    with log.span("setup.init_ring"):
        ring = plan.init_ring() if plan is not None else None
    reader = plan.reader(start_iteration=start_iter) if plan is not None \
        else None
    timer = StepTimer(bench_n, total_iters)
    # the first dispatch traces, lowers and compiles (or loads) the step:
    # it is set-up's last span and those ``jit.*`` spans' parent
    first_dispatch = {"first": True}

    def _sched_row(i: int) -> dict:
        s = setup.schedules.at(i)
        return {"lr": s["lr"], "wd": s["weight_decay"],
                "mom": s["momentum"], "teacher_temp": s["teacher_temp"]}

    def flush_ring(upto: int) -> None:
        """One blocking fetch of the ring; replay the rows into every
        per-step consumer (meters, recorder, comparator), then enforce
        the 3-strike non-finite abort from the device-side streak."""
        nonlocal last_loss
        with watchdog.window("metrics_flush", iteration=upto - 1), \
                tracer.span("metrics_flush", upto - 1):
            its_arr, rows, streak = reader.flush(ring, upto)
        if not len(its_arr):
            return
        loss_col = plan.metric_names.index("total_loss")
        for j, row_it in enumerate(its_arr):
            if not math.isfinite(rows[j][loss_col]):
                logger.warning("non-finite loss at iteration %d", row_it)
        if recorder is not None:
            recorder.record_batch(its_arr, plan.metric_names, rows)
        if comparator is not None:
            comparator.check_batch(its_arr, plan.metric_names, rows)
        metric_logger.consume_flush(
            plan.metric_names, its_arr, rows, scheds=_sched_row)
        if log.counters["recompiles"]:
            metric_logger.update(recompiles=log.counters["recompiles"])
        last_loss = float(rows[-1][loss_col])
        loss_history.extend(float(r[loss_col]) for r in rows)
        if memory_on:
            tracer.emit_memory("flush", int(its_arr[-1]))
        if streak > 2:
            ckpt.close()
            tracer.close()
            raise RuntimeError(
                f"aborting: {streak} consecutive non-finite losses"
            )

    if teacher_server is not None:
        first = teacher_server.annotate(first)
    pending = put_batch(first, setup.batch_shardings, mask_rows_limit)
    for it, raw in metric_logger.log_every(
        tracer.wrap_iter(data_iter, start_iteration=start_iter),
        print_freq=10, header=header,
        n_iterations=total_iters, start_iteration=start_iter,
    ):
        batch = pending
        tracer.profile_step_begin(it)
        with tracer.span("dispatch", it, **first_dispatch):
            if plan is not None:
                # async path: metrics land in the donated device ring,
                # nothing crosses to the host — dispatch never fences
                state, ring = plan.step_fn(
                    state, ring, batch, setup.scalars(it), rng)
            else:
                state, metrics = setup.step_fn(
                    state, batch, setup.scalars(it), rng)
        if teacher_server is not None:
            # the shared teacher's serve pass for the NEXT batch runs
            # while this step computes on device — cache hits are O(µs)
            # host lookups, misses one packed AOT dispatch; the span
            # makes the overlap (or lack of it) measurable
            with tracer.span("teacher_serve", it):
                raw = teacher_server.annotate(raw)
        with tracer.span("h2d", it):
            # overlap next batch's host->device transfer with this step
            pending = put_batch(raw, setup.batch_shardings, mask_rows_limit)
        if first_dispatch:
            # the first dispatch returned, so the step has compiled
            first_dispatch = {}
            made = log.setup_done(it)
            logger.info(
                "set-up compiled %d programs (%d loaded from the cache, "
                "which saved %.1fs of compiling; %d written to it)",
                made["programs_compiled"], made["cache_hits"],
                made["compile_time_saved_s"], made["cache_misses"])
            if memory_on:
                tracer.emit_memory("compile", it)

        if plan is None:
            # oracle path (telemetry.async_metrics=false): ONE blocking
            # device->host fetch of the metrics dict per step, shared by
            # every consumer below — this fences dispatch every step,
            # which is exactly what COST_HSYNC_r11.json prices
            sched = setup.schedules.at(it)
            with tracer.span("metrics_fetch", it):
                host_metrics = {
                    k: float(v)
                    for k, v in blocking_fetch(metrics).items()
                }
            last_loss = host_metrics["total_loss"]
            loss_history.append(last_loss)
            if recorder is not None:
                recorder.record(it, host_metrics)
            if comparator is not None:
                comparator.check(it, host_metrics)
            if not math.isfinite(last_loss):
                nan_streak += 1
                logger.warning("non-finite loss at iteration %d", it)
                if nan_streak > 2:
                    ckpt.close()
                    tracer.close()
                    raise RuntimeError(
                        f"aborting: {nan_streak} consecutive non-finite "
                        "losses"
                    )
            else:
                nan_streak = 0
            metric_logger.update(
                lr=sched["lr"], wd=sched["weight_decay"],
                mom=sched["momentum"], teacher_temp=sched["teacher_temp"],
                **host_metrics,
            )
            if log.counters["recompiles"]:
                metric_logger.update(recompiles=log.counters["recompiles"])
        if timer.active(it):
            # --benchmark fences EXPLICITLY (one tiny value fetch per
            # timed step) instead of free-riding on the per-step metrics
            # fetch the async path removes; one extra leading mark gives
            # N measured intervals (telemetry/spans.py StepTimer)
            timer.mark(state)
        tracer.profile_step_end(it, state)
        if prof is not None and it == prof[1] and anatomy_on:
            # the profiler window just closed: parse the trace into the
            # per-step anatomy ledger (telemetry/anatomy.py), joined
            # against the compiled step's HLO so collective time lands
            # in named scopes. Lowering the already-jitted step again is
            # one extra (cache-friendly) compile — acceptable inside an
            # explicit --profile-steps run, and gated off by
            # telemetry.anatomy=false.
            from dinov3_tpu.telemetry import emit_step_anatomy

            try:
                if plan is not None:
                    hlo = plan.step_fn.lower(
                        state, ring, batch, setup.scalars(it), rng,
                    ).compile().as_text()
                else:
                    hlo = setup.step_fn.lower(
                        state, batch, setup.scalars(it), rng,
                    ).compile().as_text()
            except Exception:  # pragma: no cover - backend-specific
                hlo = None
            try:
                summary = emit_step_anatomy(
                    f"{cfg.train.output_dir}/trace", hlo_text=hlo,
                    n_steps=prof[1] - prof[0] + 1, tracer=tracer,
                    cfg=cfg, iteration=it)
                if summary is not None:
                    logger.info(
                        "step anatomy: %.2f ms/step wall, exposed-comm "
                        "%.1f%% of device-busy (ledger: %s/trace/"
                        "anatomy.json)", summary["step_wall_ms"]["mean"],
                        100 * summary["exposed_comm_frac"],
                        cfg.train.output_dir)
            except Exception:
                logger.exception("step-anatomy parse failed (trace kept)")
        if "gram" in state.params and should_refresh_gram(
            cfg, it, n_gram_updates
        ):
            with tracer.span("gram_refresh", it):
                state = refresh_gram(state)
            n_gram_updates += 1
        eval_period = cfg.evaluation.get("eval_period_iterations", 0)
        if eval_period and (it + 1) % eval_period == 0:
            from dinov3_tpu.evals import do_eval

            with tracer.span("eval", it):
                results = do_eval(
                    cfg, setup.meta.teacher_backbone,
                    state.params["teacher"]["backbone"],
                    # subgroup-safe: shard eval data by the group's rank
                    # span and gather features over the group's devices
                    # only (ADVICE r2 — a global collective here
                    # deadlocks multidistillation groups with different
                    # schedules)
                    data_rank=rank, data_world=world, mesh=setup.mesh,
                )
            metric_logger.update(**results)
            if rank == 0:
                # one clean record per eval (the meter JSONL smooths
                # repeated values into running medians — useless for
                # accuracy-trajectory artifacts)
                import json as _json

                with open(f"{cfg.train.output_dir}/evals.json", "a") as f:
                    f.write(_json.dumps(
                        {"iteration": it + 1, **results}) + "\n")
        stopping = preemption.should_stop()
        if stopping:
            # first link of the chain: dur_ms = signal -> step boundary
            notice_t = preemption.notice_time or time.time()
            emit_preempt_chain(
                tracer, "preempt_notice", it,
                signal=preemption.notice_signal or "unknown",
                dur_ms=round((time.time() - notice_t) * 1e3, 4))
        if plan is not None and (
            it + 1 - reader.cursor >= plan.ring_len
            or it + 1 >= total_iters
            or stopping
        ):
            # flush BEFORE the checkpoint/exit decision so the recorded
            # metrics are durable when a preemption (or the abort) ends
            # the run here
            flush_ring(it + 1)
        if (
            (it + 1) % cfg.checkpointing.period == 0
            or it + 1 == total_iters
            or stopping
        ):
            t_save = time.time()
            with tracer.span("checkpoint_save", it):
                ckpt.save(it + 1, state, topology=run_topology)
            if stopping:
                # second link: the final atomic save must be DURABLE
                # (finalize marker written) before the process dies —
                # dur_ms covers the save dispatch + finalization wait
                ckpt.wait_until_finished()
                emit_preempt_chain(
                    tracer, "preempt_save", it, step=it + 1,
                    dur_ms=round((time.time() - t_save) * 1e3, 4))
        if stopping:
            logger.warning("preempted: checkpointed at iteration %d, "
                           "exiting for requeue", it + 1)
            break
        if it + 1 >= total_iters:
            break
        tracer.beat(it)

    preemption.__exit__()
    metric_logger.close()
    tracer.close()
    ckpt.close()
    result = {"final_loss": last_loss, "iterations": int(state.step),
              "losses": loss_history}
    if getattr(args, "keep_state", False):
        # elastic-supervisor handle (scripts/cost_reshard.py): the live
        # state and its TopologyDesc outlive the incarnation so the next
        # one can reshard in memory instead of round-tripping disk
        result["state"] = state
        result["topology"] = topology_of(setup)
    if teacher_server is not None:
        result["teacher_serve"] = teacher_server.stats()
        logger.info("serve-backed teacher: %s", result["teacher_serve"])
    if recorder is not None:
        recorder.close()
        logger.info("recorded losses to %s", args.record_losses)
    if comparator is not None:
        logger.info("loss comparison: %s", comparator.summary())
        result["loss_divergences"] = comparator.n_diverged
    if timer.n_intervals >= 1:
        img_s = timer.img_per_sec(B)
        logger.info("benchmark: %.1f ms/step, %.1f img/s (%d devices)",
                    timer.ms_per_step(), img_s, n_devices)
        result["img_per_sec"] = img_s
        # the fenced intervals themselves (each ends in a value fetch)
        result["step_ms"] = [
            (b - a) * 1e3 for a, b in zip(timer.times, timer.times[1:])]
    if args.dump_weights:
        from dinov3_tpu.utils import dump_weights

        # every process participates (the shard gather is a collective);
        # only the main process writes the file
        dump_weights(args.dump_weights, state.params)
    logger.info("training done at iteration %d, final loss %.4f",
                int(state.step), result["final_loss"])
    return result


def main(argv=None):
    from dinov3_tpu.utils import configure_compile_cache

    configure_compile_cache()
    args = get_args_parser().parse_args(argv)
    if args.debug_nans:
        # SURVEY.md §5.2: the reference had no sanitizer story beyond
        # check_vma=False escapes; this is the TPU-native one — XLA re-runs
        # the step op-by-op on the first non-finite value and raises at
        # the producing op.
        jax.config.update("jax_debug_nans", True)
    cfg = load_config(args.config_file or None, overrides=list(args.opts))
    if (args.ref_losses or args.record_losses) \
            and cfg.compute_precision.get("probs_dtype") != "fp32":
        # golden traces are recorded AND compared at fp32 probability
        # storage: the recipe default bf16 would shift values past the
        # comparator tolerance for reasons that are not bugs, and a
        # recording must use the same program its comparison will
        # (ADVICE r2)
        logger.warning(
            "--record-losses/--ref-losses: pinning "
            "compute_precision.probs_dtype=fp32 (was %s) so golden "
            "traces are recorded and compared on the same fp32 program",
            cfg.compute_precision.get("probs_dtype"),
        )
        cfg.compute_precision.probs_dtype = "fp32"
    initialize_distributed()
    # MODEL.DEVICE (default tpu): finding no chip is an error unless the
    # CPU was asked for explicitly (utils.require_accelerator)
    from dinov3_tpu.utils import require_accelerator

    device = require_accelerator((cfg.get("MODEL") or {}).get("DEVICE"))
    logger.info("device: %s", device)
    cfg.train.output_dir = args.output_dir
    if cfg.multidistillation.enabled:
        return do_train_multidistillation(cfg, args)
    setup_job(cfg)
    setup_logging(args.output_dir)
    logger.info("config:\n%s", cfg)
    return do_train(cfg, args)


def do_train_multidistillation(cfg, args) -> dict:
    """Route this host into its student's rank-span subgroup and train the
    student on the subgroup's device mesh — one independent SPMD program
    per group, no cross-group collectives (the teacher is frozen).

    (reference spec: dinov3_jax/models/temp.py:109-170 +
    configs/train/dinov3_vitl16_lvd1689m_distilled.yaml:158-176; its
    meta-arch and setup bodies were stubs — SURVEY.md §2.5.)
    """
    from dinov3_tpu.parallel import process_count, process_index
    from dinov3_tpu.train.multidistillation import setup_multidistillation

    assignment = setup_multidistillation(
        cfg, process_index(), process_count(), args.output_dir,
        extra_overrides=[o for o in args.opts if "=" in o],
    )
    scfg = assignment.cfg
    setup_job(scfg)
    setup_logging(assignment.output_dir)
    logger.info("multidistillation student %r config:\n%s",
                assignment.name, scfg)
    group = set(assignment.group_ranks)
    devices = [d for d in jax.devices() if d.process_index in group]
    if not devices:
        raise RuntimeError(
            f"no devices for group ranks {sorted(group)} "
            f"(process {process_index()} of {process_count()})"
        )
    return do_train(
        scfg, args,
        devices=devices,
        data_rank=assignment.group_rank,
        data_world=len(assignment.group_ranks),
        process_group=tuple(sorted(group)),
        group_name=assignment.name,
    )


if __name__ == "__main__":
    result = main(sys.argv[1:])
    # CI gating: `--self-check && launch` must fail on a failing model
    if result and result.get("self_check_failures"):
        sys.exit(1)
