"""Sharded training setup: mesh + born-sharded state + jitted step.

The reference's equivalent was ~200 lines of `do_train` plumbing building
three separate jit(shard_map(...)) closures with hand-derived partition
specs (dinov3_jax/train/train.py:319-604). Here:

- one multi-axis mesh (parallel/mesh.py),
- ``jax.eval_shape`` over the *boxed* init gives every leaf's logical axes
  (params AND optimizer state in one pass),
- the init is jitted with those ``NamedSharding``s as out_shardings, so
  each device materializes only its own shard (no replicate-then-slice),
- the train step is jitted with donated state and explicit in/out
  shardings; XLA's SPMD partitioner inserts all collectives,
- which update engine a mesh gets is decided in ONE place,
  ``resolve_update_arm`` below,
- under the bucketed engine (a pure data-parallel mesh), the adam
  moments are born in the flat "bucket" layout — each replica stores and
  updates 1/dp of every master/moment/teacher leaf
  (train/fused_update.py),
- under the ZeRO-3 weight-streaming engine (parallel.zero3, auto = on
  at fsdp > 1), the fp32 masters, EMA teacher AND adam moments are ALL
  born sharded over the data axes in their model shapes
  (parallel/sharding.py zero3_*): compute weights re-materialize at use
  (per block inside the block scan, ops/block.py), the update runs
  shard-local, and the step's out_shardings keep the masters sharded —
  no trailing all-gather.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp

from dinov3_tpu.configs import ConfigNode
from dinov3_tpu.parallel import (
    DEFAULT_LOGICAL_RULES,
    batch_specs,
    build_mesh,
    replicated,
    state_shardings_from_abstract,
)
from dinov3_tpu.parallel.mesh import MeshSpec
from dinov3_tpu.telemetry import spans
from dinov3_tpu.train.optimizer import build_optimizer
from dinov3_tpu.train.schedules import Schedules, build_schedules
from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch
from dinov3_tpu.train.train_step import TrainState, make_train_step


@dataclasses.dataclass
class TelemetryPlan:
    """The async-metrics engine for one training setup: the jitted
    telemetry step (metrics row -> donated on-device ring, no host
    sync), the host-side column order, and the ring constructor.

    Built LAZILY (``TrainSetup.telemetry()``) because deriving the
    metric column order costs one extra ``eval_shape`` trace of the
    step — the hot loop and bench pay it once; setups whose callers
    only use ``step_fn`` (most tests) pay nothing.
    """

    step_fn: Callable      # (state, ring, batch, scalars, rng) -> (state, ring)
    metric_names: list     # ring column order (sorted metric keys)
    ring_len: int          # K = telemetry.flush_every
    ring_shardings: Any    # replicated NamedShardings for the RingState

    def init_ring(self):
        """Fresh zeroed device ring (donated to the step thereafter)."""
        from dinov3_tpu.telemetry.ring import make_ring

        return jax.device_put(
            make_ring(len(self.metric_names), self.ring_len),
            self.ring_shardings,
        )

    def reader(self, start_iteration: int = 0):
        from dinov3_tpu.telemetry.ring import RingReader

        return RingReader(self.metric_names, self.ring_len,
                          start_iteration=start_iteration)


@dataclasses.dataclass
class TrainSetup:
    cfg: ConfigNode
    meta: Any  # SSLMetaArch | LMMetaArch
    mesh: Any
    schedules: Schedules
    optimizer: Any
    state: TrainState
    state_shardings: TrainState
    step_fn: Callable  # step_fn(state, batch, scalars, rng) -> (state, metrics)
    batch_shardings: dict
    fused_update: Callable | None = None  # single-pass engine, None = optax chain
    arm: str = "replicated"  # resolve_update_arm's answer
    bucket_plan: Any = None  # the leaf->bucket assignment (BucketPlan)
    zero3_bucket_plan: Any = None  # Zero3GatherPlan (student tree)
    accum_steps: int = 1  # microbatched gradient accumulation
    # train.low_precision (ops/lowp.py): resolved arm + the setup-time
    # quantization drift probe ({site: rel-Frobenius, "max": worst}),
    # None on the bf16 arm / compile-only setups
    lowp_arm: str = "bf16"
    lowp_drift: dict | None = None
    # lazy TelemetryPlan builder; None = telemetry.async_metrics=false
    # (the per-step-fetch oracle path is then the only metrics path)
    telemetry_builder: Callable | None = None
    _telemetry_cache: Any = dataclasses.field(default=None, repr=False)

    @property
    def bucketed(self) -> bool:
        """Coalesced bucket form of the cross-replica sharded update."""
        return self.arm == "bucketed"

    @property
    def zero3(self) -> bool:
        """ZeRO-3 weight-streaming layout (masters sharded)."""
        return self.arm in ("zero3", "unified")

    @property
    def zero3_buckets(self) -> bool:
        """Unified engine (zero3 x buckets): the non-block zero3 gathers
        and their grad reduce-scatters run as hierarchy-aware flat
        buckets."""
        return self.arm == "unified"

    def mask_rows_limit(self, host_batch: dict) -> int | None:
        """The masked tokens ONE ``sample_ibot_masks`` call of
        ``host_batch``'s shape carries under this config — what
        ``put_batch`` holds each batch of a loader that collates once a
        host batch to (the step's compact buffer is this count times
        ``mask_sampler_calls``, rounded up)."""
        from dinov3_tpu.data.masking import ibot_mask_targets

        if "mask_indices" not in host_batch:
            return None  # a batch of tokens: nothing is masked
        n_img, capacity = host_batch["mask_indices"].shape
        return sum(ibot_mask_targets(
            n_img, host_batch["masks"].shape[1], capacity,
            tuple(self.cfg.ibot.mask_ratio_min_max),
            self.cfg.ibot.mask_sample_probability))

    def scalars(self, iteration: int) -> dict:
        s = self.schedules.at(iteration)
        return {
            "teacher_temp": jnp.asarray(s["teacher_temp"], jnp.float32),
            "momentum": jnp.asarray(s["momentum"], jnp.float32),
        }

    def telemetry(self) -> TelemetryPlan | None:
        """The async-metrics engine (built on first use), or None when
        the config selects the per-step-fetch oracle."""
        if self.telemetry_builder is None:
            return None
        if self._telemetry_cache is None:
            self._telemetry_cache = self.telemetry_builder()
        return self._telemetry_cache


def resolve_update_arm(cfg: ConfigNode, mesh, zero3_gather: bool) -> str:
    """Which update engine this config gets on this mesh: the one place
    that chooses, and where every conflict between the switches raises.
    Reads the config and the mesh's shape, nothing else.

    - ``"zero3"`` / ``"unified"``: ``parallel.zero3`` wished (auto = on
      at ``parallel.fsdp > 1``) on a data-axis product > 1. Masters,
      teacher and moments are born sharded over the data axes in their
      MODEL shapes (parallel/sharding.py zero3_*) and the update runs
      shard-local through the plain engine, so nothing is left for
      update buckets to coalesce; ``optim.bucketed_collectives`` (auto =
      on) then chooses ``"unified"``, where the forward's non-block
      gathers and their transposed grad reduce-scatters run as
      hierarchy-aware gather buckets (``gather_zero3_bucketed``) —
      wherever the meta-arch gathers at all (``zero3_gather``: not on a
      model-parallel mesh, not a decoder). No update-engine
      requirements there, so no raises.
    - ``"bucketed"``: any other mesh with a data-axis product > 1, under
      ``optim.bucketed_collectives`` (auto = on) and
      ``optim.fused_update``: one reduce-scatter + one all-gather per
      ~128 MiB flat bucket around the fused single pass over 1/dp
      shards (``make_bucketed_update``).
    - ``"replicated"``: one device, or either switch off: every replica
      updates whole leaves behind GSPMD's all-reduce — the oracle the
      sharded arms are tested against.

    Whether the math is the fused single pass or the optax chain is
    ``optim.fused_update``'s alone (``TrainSetup.fused_update``)."""
    from dinov3_tpu.configs.config import (
        bucketed_collectives_wished,
        zero3_wished,
    )
    from dinov3_tpu.parallel.sharding import update_shard_size

    dp = update_shard_size(mesh)
    fused = bool(cfg.optim.get("fused_update", True))
    bucketed = bucketed_collectives_wished(cfg)
    if zero3_wished(cfg) and dp > 1:
        return "unified" if bucketed and zero3_gather else "zero3"
    asked = str(cfg.optim.get("bucketed_collectives", "auto")).lower()
    if bucketed and asked != "auto" and not fused:
        raise ValueError(
            "optim.bucketed_collectives=true requires "
            "optim.fused_update=true on non-zero3 meshes (the "
            "bucketed engine is the fused single-pass math over "
            "bucket shards; only the unified zero3 gather-bucket "
            "arm — parallel.zero3 on an fsdp>1 mesh — works without "
            "it); re-enable fused_update or set "
            "bucketed_collectives=false"
        )
    return "bucketed" if bucketed and fused and dp > 1 else "replicated"


def build_train_setup(
    cfg: ConfigNode,
    example_batch: dict,
    rng: jax.Array | None = None,
    devices=None,
    mesh=None,
    init_state: bool = True,
    mask_sampler_calls: int = 1,
) -> TrainSetup:
    """See ``_build_train_setup``; this wrapper restores the ambient
    current-mesh when setup raises (the config-validation raises fire
    AFTER the mesh context is installed — without the restore a failed
    setup leaves later traces resolving against the wrong mesh)."""
    from dinov3_tpu.parallel.context import get_current_mesh, set_current_mesh

    prev = get_current_mesh()
    try:
        with spans.LOG.span("setup.build"):
            return _build_train_setup(
                cfg, example_batch, rng, devices, mesh, init_state,
                mask_sampler_calls)
    except BaseException:
        set_current_mesh(prev)
        raise


def _build_train_setup(
    cfg: ConfigNode,
    example_batch: dict,
    rng: jax.Array | None = None,
    devices=None,
    mesh=None,
    init_state: bool = True,
    mask_sampler_calls: int = 1,
) -> TrainSetup:
    """Build everything needed to train, with state born sharded.

    ``mask_sampler_calls``: how many equal ``sample_ibot_masks`` calls
    make up one global batch of the loader this setup will be fed from
    (train.py: one collate call a host). The step sizes its compact iBOT
    buffer from it (``SSLMetaArch.masked_rows``); the caller owns the
    loader, so the caller says.

    ``init_state=False`` returns the setup with ``state`` as UNBOXED
    ``ShapeDtypeStruct``s instead of materialized device arrays — the
    compile-only form the memory-accounting dryrun uses
    (scripts/cost_host_sync.py lowers the jitted step from the abstract
    state at ViT-L dp=8 without holding 8 replicated ViT-L trees in
    host RAM). Such a setup can ``.lower(...).compile()`` but not
    execute."""
    rng = rng if rng is not None else jax.random.key(cfg.train.seed)
    mesh = mesh if mesh is not None else build_mesh(
        MeshSpec.from_cfg(cfg.parallel), devices=devices
    )
    from dinov3_tpu.parallel.context import set_current_mesh

    set_current_mesh(mesh)
    if int(mesh.shape.get("seq", 1)) > 1 and bool(
            cfg.train.get("scan_layers", False)):
        # flax nn.scan's broadcast partial-eval poisons cached jaxprs of
        # the ring attention custom_vjp with stale tracers on this jax
        # release (UnexpectedTracerError at the first grad trace, even
        # without a lower()-then-call retrace). Fall back loudly rather
        # than let the step die deep inside the trace; the unscanned
        # block stack is numerically identical, it only compiles O(depth)
        # slower. tests/test_ring_attention.py exercises the seq mesh on
        # the unscanned path.
        warnings.warn(
            "train.scan_layers=true is incompatible with ring attention "
            "on a parallel.seq>1 mesh under this jax version (nn.scan x "
            "custom_vjp tracer leak); disabling scan_layers for this "
            "run.",
            stacklevel=2,
        )
        cfg.train.scan_layers = False
    # train.low_precision (ops/lowp.py): fp8/int8 delayed-scaling block
    # matmuls on the zero3 stream. Arm conflicts raise here (setup is the
    # first place every interacting knob is resolved together):
    from dinov3_tpu.configs.config import lowp_cfg

    lp = lowp_cfg(cfg)
    if lp["arm"] != "bf16":
        if bool(cfg.student.get("fp8_enabled", False)):
            raise ValueError(
                f"train.low_precision.arm={lp['arm']!r} conflicts with "
                "student.fp8_enabled=true: both would quantize the same "
                "block matmuls (the legacy fp8 hook uses current "
                "per-tensor scaling, the lowp arms delayed scaling). "
                "Pick one — arm=fp8 supersedes fp8_enabled."
            )
        if str(cfg.student.get("ffn_layer", "mlp")) == "moe":
            raise ValueError(
                f"train.low_precision.arm={lp['arm']!r} does not support "
                "student.ffn_layer=moe: the expert einsums are not "
                "stream-castable Dense kernels (ops/block.py "
                "stream_castable_path excludes router/expert leaves)."
            )
        if int((cfg.get("parallel") or {}).get("pipe", 1) or 1) > 1:
            raise ValueError(
                f"train.low_precision.arm={lp['arm']!r} is not supported "
                "under pipeline parallelism (parallel.pipe>1): the "
                "pipelined block stack bypasses the per-block zero3 "
                "stream the quantized gathers ride."
            )
    # what kind of step a recipe runs follows from its architecture: a
    # token decoder has a student and a next-token loss, and no teacher
    from dinov3_tpu.configs.config import is_lm_arch

    if is_lm_arch(cfg):
        from dinov3_tpu.train.lm_meta_arch import LMMetaArch

        meta = LMMetaArch(cfg)
    else:
        meta = SSLMetaArch(cfg, mask_sampler_calls=mask_sampler_calls)
    if meta.teacher_source == "serve" and "teacher_cls" not in example_batch:
        # the serve-backed teacher arm changes the STEP SIGNATURE: the
        # precomputed teacher planes are batch inputs (batch-sharded by
        # batch_specs below), so the trace batch must carry them —
        # train.py composes the example with teacher_feature_example
        # zeros; fail at setup, not at the first dispatch
        raise ValueError(
            "distillation.teacher_source=serve: example_batch must carry "
            "teacher_cls/teacher_patches planes "
            "(train/distillation.py teacher_feature_example)")
    schedules = build_schedules(cfg)

    # Optimizer multiplier trees need only the param paths/shapes: derive
    # them abstractly (no FLOPs, no memory).
    with spans.LOG.span("setup.abstract_params"):
        abstract_params = jax.eval_shape(
            lambda r: meta.init_params(r, example_batch), rng
        )
    optimizer = build_optimizer(cfg, abstract_params["student"], schedules)
    from dinov3_tpu.parallel.sharding import update_shard_size

    dp = update_shard_size(mesh)
    arm = resolve_update_arm(cfg, mesh, meta.zero3_gather)
    use_zero3 = arm in ("zero3", "unified")
    # meta computed the same wish from cfg alone; setup has the final
    # word (dp gate)
    meta.zero3_buckets = arm == "unified"
    zero3_bucket_plan = None
    if arm == "unified":
        from dinov3_tpu.train.fused_update import make_zero3_bucket_plan

        zero3_bucket_plan = make_zero3_bucket_plan(
            abstract_params["student"], mesh)
    # default math: the single-pass fused clip+AdamW+EMA engine (state
    # pytree identical to the optax chain's, so init/sharding/checkpoints
    # below are path-independent); optim.fused_update=false selects the
    # optax oracle chain (fused stays None)
    fused = None
    bucket_plan = None
    if arm == "bucketed":
        # the leaf -> bucket assignment, built ONCE per setup from
        # the abstract params (the TelemetryPlan convention) and
        # shared by the engine, the opt-state init, the checkpoint
        # adapter and the census scripts
        from dinov3_tpu.configs.config import warn_bucket_padding
        from dinov3_tpu.train.fused_update import (
            build_bucketed_update,
            make_bucket_plan,
        )
        from dinov3_tpu.train.param_groups import build_multiplier_trees

        _, _, is_last = build_multiplier_trees(
            abstract_params["student"],
            layerwise_decay=cfg.optim.layerwise_decay,
            patch_embed_lr_mult=cfg.optim.patch_embed_lr_mult,
            dino_head_wd_multiplier=cfg.optim.dino_head_wd_multiplier,
        )
        bucket_plan = make_bucket_plan(
            abstract_params["student"], dp, is_last_layer=is_last,
        )
        warn_bucket_padding(
            bucket_plan.padding_stats(), bucket_plan.target_bytes)
        fused = build_bucketed_update(
            cfg, abstract_params["student"], schedules, mesh,
            bucket_plan, ema=meta.ema_teacher,
        )
    elif bool(cfg.optim.get("fused_update", True)):
        from dinov3_tpu.train.fused_update import build_fused_update

        fused = build_fused_update(
            cfg, abstract_params["student"], schedules,
            ema=meta.ema_teacher,
        )

    def boxed_init(r):
        params = meta.init_params(r, example_batch, unbox=False)
        # optax descends into nn.Partitioned pytree nodes, so the adam
        # mu/nu trees inherit the logical-axis boxes — one eval_shape
        # covers params and optimizer state.
        opt_state = optimizer.init(params["student"])
        if bucket_plan is not None:
            # the bucketed engine's moments are BORN in the bucket
            # layout ({bucket_name: flat [S_b]}, 1/dp per replica via
            # the "bucket" logical rule) — same ScheduledAdamWState
            # pytree, bucket-dict mu/nu
            import optax

            from dinov3_tpu.train.fused_update import bucketed_adam_zeros

            opt_state = opt_state._replace(
                adam=optax.ScaleByAdamState(
                    count=opt_state.adam.count,
                    mu=bucketed_adam_zeros(bucket_plan),
                    nu=bucketed_adam_zeros(bucket_plan),
                )
            )
        lowp_state = None
        if lp["arm"] != "bf16":
            # amax-history rings seeded with the CURRENT master amax in
            # every slot (zero-filled rings would scale the first H steps
            # by 1.0 — instant divergence on ~0.02-std kernels); tiny f32
            # leaves at the castable-kernel scale sites only
            import flax.linen as nn

            from dinov3_tpu.ops.lowp import lowp_history_init

            lowp_state = {
                "student": lowp_history_init(
                    nn.meta.unbox(params["student"]["backbone"]),
                    lp["amax_history_len"]),
                "teacher": lowp_history_init(
                    nn.meta.unbox(params["teacher"]["backbone"]),
                    lp["amax_history_len"]),
            }
        return TrainState(
            params=params,
            opt_state=opt_state,
            center_state=meta.init_state(),
            step=jnp.zeros((), jnp.int32),
            lowp=lowp_state,
        )

    with spans.LOG.span("setup.abstract_state"):
        abstract = jax.eval_shape(boxed_init, rng)
    state_shardings = state_shardings_from_abstract(
        abstract, mesh, DEFAULT_LOGICAL_RULES
    )
    if use_zero3:
        # masters, EMA teacher AND adam moments born zero3-sharded: the
        # logical-rules shardings of the params/mu/nu subtrees are
        # overridden with the zero3 placement (one dividing dim per
        # leaf over the data axes, model shapes kept); everything else
        # (centers, counters, step) stays as derived
        from dinov3_tpu.parallel.sharding import (
            zero3_replicated_waste,
            zero3_shardings_from_abstract,
        )

        state_shardings = state_shardings._replace(
            params=zero3_shardings_from_abstract(abstract.params, mesh),
            opt_state=state_shardings.opt_state._replace(
                adam=state_shardings.opt_state.adam._replace(
                    mu=zero3_shardings_from_abstract(
                        abstract.opt_state.adam.mu, mesh),
                    nu=zero3_shardings_from_abstract(
                        abstract.opt_state.adam.nu, mesh),
                )
            ),
        )
        # layout guardrail: warn when > 1% of the master elements have
        # no dividing dim and stay replicated on every device
        import flax.linen as nn_meta

        from dinov3_tpu.configs.config import warn_zero3_padding

        pairs = [
            (l.value.shape, l.names) if isinstance(l, nn_meta.Partitioned)
            else (l.shape, (None,) * len(l.shape))
            for l in jax.tree.leaves(
                abstract.params,
                is_leaf=lambda x: isinstance(x, nn_meta.Partitioned))
        ]
        warn_zero3_padding(zero3_replicated_waste(pairs, mesh), dp)

    if abstract.lowp is not None:
        # amax-history rings pinned replicated explicitly (tiny f32
        # leaves; every device derives the same scales at quantize time)
        from dinov3_tpu.parallel.sharding import lowp_scale_specs

        state_shardings = state_shardings._replace(
            lowp=lowp_scale_specs(abstract.lowp, mesh))

    import flax.linen as nn

    if init_state:
        init_jit = jax.jit(
            lambda r: nn.meta.unbox(boxed_init(r)),
            out_shardings=state_shardings,
        )
        with mesh, spans.LOG.span("setup.init_state"):
            state = init_jit(rng)
    else:
        state = nn.meta.unbox(abstract)

    # quantization-drift guardrail (configs.config.warn_lowp_divergence):
    # a device-side per-layer probe compares the quantized lowp matmul
    # against the bf16 shadow on the sampled layer of every castable
    # kernel at the INITIAL masters/scales — a mis-tuned arm (margin,
    # ring length, int8 on an unsuited recipe) fires here at setup build
    # instead of surfacing as a silent loss divergence hours in. bench
    # captures the warning into its records (the warn_* convention).
    lowp_drift = None
    if lp["arm"] != "bf16" and init_state:
        from dinov3_tpu.configs.config import warn_lowp_divergence
        from dinov3_tpu.ops.lowp import lowp_drift_probe

        with mesh:
            probe = lowp_drift_probe(
                state.params["student"]["backbone"], state.lowp["student"],
                lp["arm"], lp["scale_margin"])
        lowp_drift = {k: float(v) for k, v in probe.items()}
        warn_lowp_divergence(
            lowp_drift["max"], tol=lp["divergence_tol"],
            axis=f"lowp train matmuls ({lp['arm']})")

    b_shardings = batch_specs(mesh, example_batch)
    # microbatched gradient accumulation (optim.accum_steps): the step
    # scans the fwd/bwd over accum_steps microbatches with ONE bucketed
    # grad-RS per optimizer step (train_step.py). Tiling guardrail fires
    # here too (load_config already warned once at build).
    accum_steps = int((cfg.get("optim") or {}).get("accum_steps", 1) or 1)
    if accum_steps > 1:
        from dinov3_tpu.configs.config import warn_accum_batch_tiling

        warn_accum_batch_tiling(cfg, mesh=mesh)
    # seq-padding guardrail: under sequence parallelism each crop's
    # token count (CLS + registers + patches) pads to a multiple of the
    # seq axis inside ring attention; warn per crop size when that
    # padding wastes > 2% of every attention pass. Only passes the
    # per-pass dispatch actually rings (N >= RING_MIN_SEQ) are checked
    # — short local crops run dense with no seq padding.
    seq_axis = int(mesh.shape.get("seq", 1))
    if seq_axis > 1 and not str(cfg.student.arch).startswith("convnext"):
        from dinov3_tpu.configs.config import warn_seq_padding
        from dinov3_tpu.ops import attention

        n_prefix = 1 + int(cfg.student.get("n_storage_tokens", 0) or 0)
        patch = int(cfg.student.patch_size)
        crops = cfg.get("crops") or {}
        sizes = {
            "global crops": crops.get("global_crops_size", 0),
            "local crops": crops.get("local_crops_size", 0),
            "gram teacher crops": crops.get("gram_teacher_crops_size", 0),
        }
        for label, px in sizes.items():
            px = int(px or 0)
            if px <= 0 or px % patch:
                continue
            n = n_prefix + (px // patch) ** 2
            if n >= attention.RING_MIN_SEQ:
                warn_seq_padding(
                    n, seq_axis, axis=f"{label} ({px}px)", stacklevel=2)
    raw_step = make_train_step(
        meta, optimizer,
        clip_grad=cfg.optim.clip_grad,
        monitor_grad_norm=cfg.train.monitor_gradient_norm,
        fused_update=fused,
        accum_steps=accum_steps,
        lowp=lp,
    )
    rep = replicated(mesh)
    scalar_shardings = {"teacher_temp": rep, "momentum": rep}
    step_fn = jax.jit(
        raw_step,
        in_shardings=(state_shardings, b_shardings, scalar_shardings, rep),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )

    # async metrics ring (telemetry/, auto=on; the per-step-fetch oracle
    # stays behind telemetry.async_metrics=false). Lazy: the builder
    # traces the raw step once (eval_shape) to fix the ring's column
    # order, so only callers that USE the engine (the hot loop, bench,
    # the telemetry tests) pay the extra trace.
    from dinov3_tpu.telemetry import telemetry_wished

    telemetry_builder = None
    if telemetry_wished(cfg):
        tele_cfg = cfg.get("telemetry") or {}

        def _build_telemetry() -> TelemetryPlan:
            from dinov3_tpu.telemetry.ring import make_ring
            from dinov3_tpu.train.train_step import make_telemetry_step

            abstract_scalars = {
                "teacher_temp": jax.ShapeDtypeStruct((), jnp.float32),
                "momentum": jax.ShapeDtypeStruct((), jnp.float32),
            }
            # the whole raw step traced once for its metric names; the
            # jitted step's first call traces it again
            with spans.LOG.span("setup.telemetry_plan"):
                abs_metrics = jax.eval_shape(
                    raw_step, nn.meta.unbox(abstract), example_batch,
                    abstract_scalars, jax.random.key(0),
                )[1]
            names = sorted(abs_metrics)
            ring_len = int(tele_cfg.get("flush_every", 50))
            ring_shardings = jax.tree.map(
                lambda _: rep, make_ring(len(names), ring_len))
            t_step = jax.jit(
                make_telemetry_step(raw_step, names),
                in_shardings=(state_shardings, ring_shardings, b_shardings,
                              scalar_shardings, rep),
                out_shardings=(state_shardings, ring_shardings),
                # state AND ring donated: the ring write is in-place
                donate_argnums=(0, 1),
            )
            return TelemetryPlan(
                step_fn=t_step, metric_names=names, ring_len=ring_len,
                ring_shardings=ring_shardings,
            )

        telemetry_builder = _build_telemetry

    return TrainSetup(
        cfg=cfg, meta=meta, mesh=mesh, schedules=schedules,
        optimizer=optimizer, state=state, state_shardings=state_shardings,
        step_fn=step_fn, batch_shardings=b_shardings, fused_update=fused,
        arm=arm, bucket_plan=bucket_plan,
        zero3_bucket_plan=zero3_bucket_plan,
        accum_steps=accum_steps,
        lowp_arm=lp["arm"],
        lowp_drift=lowp_drift,
        telemetry_builder=telemetry_builder,
    )


def elastic_resume(setup, ckpt, *, live_state=None, live_topology=None,
                   policy: str = "auto", tracer=None):
    """Topology-elastic resume into a freshly built ``setup``.

    Two paths produce bitwise-identical states (tests/test_reshard.py):

    - **memory** — a still-live ``TrainState`` from a previous
      incarnation in this process (an elastic resize without preemption)
      is resharded in place by ``parallel.reshard.reshard_state``: one
      scoped collective program per leaf-group, no disk round-trip.
      Requires ``live_state``/``live_topology`` and, under ``auto``,
      every device of the OLD mesh still visible to this process.
    - **disk** — ``ckpt.restore`` through the arm-adapting checkpoint
      path (a real preemption: the old process and its arrays are gone).

    Returns ``(state, info)``; ``info["path"]`` says which path ran, and
    the memory path attaches the full per-group reshard ``report``
    (censuses, wall times) for the span stream / cost harness.
    """
    from dinov3_tpu.parallel.reshard import reshard_state, topology_of

    if policy not in ("auto", "memory", "disk"):
        raise ValueError(f"unknown resume-topology policy {policy!r}")
    live_ok = live_state is not None and live_topology is not None
    if policy == "memory" and not live_ok:
        raise ValueError(
            "--resume-topology memory needs a live state from the "
            "previous incarnation; after a real preemption use "
            "auto/disk (checkpoint path)")
    reachable = live_ok and {
        d.id for d in live_topology.mesh.devices.flat
    } <= {d.id for d in jax.devices()}
    if policy == "memory" or (policy == "auto" and live_ok and reachable):
        state, report = reshard_state(
            live_state, live_topology, topology_of(setup), tracer=tracer)
        return state, {"path": "memory", "report": report}
    return ckpt.restore(setup.state), {"path": "disk"}


def put_batch(batch: dict, batch_shardings: dict,
              mask_rows_limit: int | None = None) -> dict:
    """Host batch -> sharded device arrays (each host feeds its shard).

    ``mask_rows_limit`` (``TrainSetup.mask_rows_limit``): the most valid
    masked tokens this host's batch may carry. A mask source that
    outgrows the sampler's rule is refused here, on the host and with the
    numbers; past this check the step's compact iBOT buffer would
    overflow and the run would stop on a NaN ``ibot_loss`` instead
    (``ibot_rows_overflow``, the backstop for callers that pass none).

    Single process: plain ``device_put`` of the (global == local) batch.
    Multi-host: each host passes only its local shard and the global array
    is assembled with ``make_array_from_process_local_data`` — no host ever
    materializes (or decodes) the full global batch (the reference striped
    sample indices by rank for the same reason, data/samplers.py:49-60).
    """
    import numpy as np

    if mask_rows_limit is not None:
        n_valid = int(np.count_nonzero(batch["mask_valid"]))
        if n_valid > mask_rows_limit:
            raise ValueError(
                f"this host's batch masks {n_valid} tokens; "
                f"sample_ibot_masks makes {mask_rows_limit} for its "
                f"{batch['mask_valid'].shape[0]} mask rows under "
                "ibot.mask_ratio_min_max / mask_sample_probability, and "
                "the step's compact iBOT buffer is sized for that "
                "(data/masking.py masked_rows_bound)")
    if jax.process_count() == 1:
        return jax.tree.map(
            lambda x, s: jax.device_put(x, s), dict(batch), batch_shardings
        )

    return {
        k: jax.make_array_from_process_local_data(
            batch_shardings[k], np.asarray(v)
        )
        for k, v in dict(batch).items()
    }
