"""DINOv3 SSL meta-architecture, functional style.

(reference: dinov3_jax/train/ssl_meta_arch.py — a Flax module holding
student/teacher/gram backbones + heads whose params lived in one variable
tree wrapped by the FSDP interceptor. Redesigned:

- ``SSLMetaArch`` is a plain Python object holding *module definitions* and
  config; parameters are an explicit pytree
  ``{"student": {backbone, dino_head, ibot_head}, "teacher": {...},
  ["gram": {...}]}`` threaded through pure functions — the natural shape for
  GSPMD sharding, donation, and a fused teacher-EMA update (the reference's
  EMA never fed back into the teacher used by the forward, SURVEY.md §2.9.1);
- the batch carries the masked tokens in per-image fixed-capacity buffers
  ([2B, M_img] indices into each image's own tokens: TPU-static shapes,
  SURVEY.md §7.3), sized for the most ONE image may mask; the step
  compacts them on the device into ONE batch-wide buffer of ``M_c`` rows
  (``masked_rows``) before the two iBOT heads, so the heads, Sinkhorn and
  the iBOT loss run over the tokens the batch has masked (30 % of the
  per-image buffers' rows at the recipe's ratios) and not over padding.
  ``M_c`` is the sampler's own count (data/masking.py
  ``masked_rows_bound``), derived from ``cfg`` and the batch's static
  shape; more valid tokens than that make ``ibot_loss`` non-finite, never
  a smaller batch. The row gather is the one place a token crosses data
  shards: [M_c, D] rows, which then split evenly over the data axes;
- teacher forward runs under ``stop_gradient`` on params the loss never
  differentiates, no separate "ema module" copies.)

Batch contract (produced by dinov3_tpu/data/collate.py):
    global_crops [2B, S, S, 3], local_crops [n_l*B, s, s, 3],
    masks [2B, T] bool, mask_indices [2B, M] int32 (per-image token index,
    0-padded), mask_weights [2B, M] f32 (1/n_masked(img), 0 for padding),
    mask_valid [2B, M] bool. A valid (image, slot) names a token once.
"""

from __future__ import annotations

from math import prod as math_prod
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from dinov3_tpu.configs import ConfigNode
from dinov3_tpu.losses import (
    gram_loss,
    koleo_loss,
    pair_ce_to_loss,
    sinkhorn_knopp,
    softmax_center_teacher,
    update_center,
)
from dinov3_tpu.models import build_backbone
from dinov3_tpu.ops import DINOHead, Policy
from dinov3_tpu.utils import step_phase


class MaskedRows(NamedTuple):
    """The batch's masked tokens, compacted (``SSLMetaArch.masked_rows``).

    indices: the batch's [2B, M_img] ``mask_indices``; slots: [M_c] int32
    flat positions in those per-image buffers, the valid ones first and
    in order, then the last position (in range; weighted out like any
    padding); weights: [M_c] f32 ``mask_weights`` of the same slots, 0 on
    padding; valid: [M_c] bool; fill / overflow: f32 scalars, valid
    tokens over M_c and the count of valid tokens that did not fit (0
    with the repo's sampler).
    """

    indices: jax.Array
    slots: jax.Array
    weights: jax.Array
    valid: jax.Array
    fill: jax.Array
    overflow: jax.Array


class SSLMetaArch:
    def __init__(self, cfg: ConfigNode, mask_sampler_calls: int = 1):
        if cfg.crops.local_crops_number <= 0:
            raise ValueError("DINOv3 needs local crops (crops.local_crops_number > 0)")
        if not cfg.ibot.separate_head:
            raise ValueError("only ibot.separate_head=true is supported")
        lo, hi = cfg.ibot.mask_ratio_min_max
        if not (0 <= lo < hi <= 1):
            raise ValueError("provide a valid ibot.mask_ratio_min_max")
        self.cfg = cfg
        # how many equal ``sample_ibot_masks`` calls make up the batch the
        # step is fed (``masked_rows``): the data layer's to say, so
        # whoever builds the loader passes it (train.py: one collate call
        # a host). The step program never guesses it from the mesh.
        self.mask_sampler_calls = int(mask_sampler_calls)
        # Training masters are ALWAYS fp32, whatever compute_precision.
        # param_dtype says: the reference recipe's ``param_dtype: bf16`` is
        # torch-FSDP MixedPrecision's *compute copy* dtype — its masters
        # (and initializer samples) stay fp32 (SURVEY.md §2.5). bf16
        # masters would freeze both EMAs by rounding: the teacher update
        # (1-m)(s-t) and Adam's second-moment increment (1-b2)g² both fall
        # below the bf16 half-ulp of their accumulators in steady state.
        # Modules cast to ``compute_dtype`` (bf16) at apply time, so the
        # MXU path is unaffected; ``param_dtype`` keeps its configured
        # value for eval/inference builds (models/__init__.py), where
        # low-precision storage is safe.
        import dataclasses as _dc

        self.policy = _dc.replace(
            Policy.from_cfg(cfg.compute_precision), param_dtype=jnp.float32
        )
        self.student_backbone = build_backbone(
            cfg, teacher=False, param_dtype=self.policy.param_dtype)
        # Distillation: the teacher is a different (frozen, pretrained)
        # architecture resolved from its own config
        # (reference: ssl_meta_arch.py _setup_distillation:257-286).
        self.distillation = bool(cfg.distillation.enabled)
        # the update's EMA leg: a frozen pretrained teacher has none
        self.ema_teacher = not self.distillation
        # a crop-major batch splits into microbatches (optim.accum_steps)
        self.supports_accum = True
        teacher_cfg = cfg
        if self.distillation:
            from dinov3_tpu.train.distillation import resolve_distillation_cfg

            teacher_cfg = resolve_distillation_cfg(cfg)
        self.teacher_cfg = teacher_cfg
        self.teacher_backbone = build_backbone(
            teacher_cfg, teacher=True, param_dtype=self.policy.param_dtype)
        self.embed_dim = self.student_backbone.embed_dim
        self.teacher_embed_dim = self.teacher_backbone.embed_dim
        # Teacher feature source (configs/config.py
        # distill_teacher_source): "in_step" (default) keeps the frozen
        # teacher's backbone forward inside the compiled step — the
        # bitwise oracle; "serve" consumes teacher_cls/teacher_patches
        # batch planes precomputed ONCE per image by the host-shared
        # packed teacher engine (train/distillation.py TeacherServer,
        # ``distill_fanout`` scope). Only meaningful under distillation
        # — the EMA teacher changes every step and cannot be served.
        from dinov3_tpu.configs.config import distill_teacher_source

        self.teacher_source = (
            distill_teacher_source(cfg) if self.distillation else "in_step")

        head_kw = dict(
            dtype=self.policy.compute_dtype,
            param_dtype=self.policy.param_dtype,
            reduce_dtype=self.policy.reduce_dtype,
        )
        self.dino_head = DINOHead(
            out_dim=cfg.dino.head_n_prototypes,
            hidden_dim=cfg.dino.head_hidden_dim,
            bottleneck_dim=cfg.dino.head_bottleneck_dim,
            nlayers=cfg.dino.head_nlayers,
            norm_last_layer=cfg.dino.head_norm_last_layer,
            **head_kw,
        )
        self.ibot_head = DINOHead(
            out_dim=cfg.ibot.head_n_prototypes,
            hidden_dim=cfg.ibot.head_hidden_dim,
            bottleneck_dim=cfg.ibot.head_bottleneck_dim,
            nlayers=cfg.ibot.head_nlayers,
            norm_last_layer=cfg.ibot.head_norm_last_layer,
            **head_kw,
        )
        if self.distillation:
            # teacher heads may use different widths; prototype counts are
            # asserted equal by resolve_distillation_cfg
            self.teacher_dino_head = DINOHead(
                out_dim=cfg.dino.head_n_prototypes,
                hidden_dim=teacher_cfg.dino.head_hidden_dim,
                bottleneck_dim=teacher_cfg.dino.head_bottleneck_dim,
                nlayers=teacher_cfg.dino.head_nlayers,
                norm_last_layer=teacher_cfg.dino.head_norm_last_layer,
                **head_kw,
            )
            self.teacher_ibot_head = DINOHead(
                out_dim=cfg.ibot.head_n_prototypes,
                hidden_dim=teacher_cfg.ibot.head_hidden_dim,
                bottleneck_dim=teacher_cfg.ibot.head_bottleneck_dim,
                nlayers=teacher_cfg.ibot.head_nlayers,
                norm_last_layer=teacher_cfg.ibot.head_norm_last_layer,
                **head_kw,
            )
        else:
            self.teacher_dino_head = self.dino_head
            self.teacher_ibot_head = self.ibot_head
        self.n_local_crops = cfg.crops.local_crops_number
        self.centering = cfg.train.centering
        # Streaming prototype-axis target/CE engine (losses/streaming.py):
        # the [*, K] teacher-target buffer is never materialized — the CE
        # consumes K-tiles of the raw logits (softmax-center) or of the
        # Sinkhorn log-domain factors. "auto"/true = streaming (default);
        # false = the materialized oracle path (the test reference, and
        # the bitwise-reference numerics).
        loss_cfg = cfg.get("loss") or {}
        st = loss_cfg.get("streaming_targets", "auto")
        if isinstance(st, str):
            low = st.lower()
            if low not in ("auto", "true", "false", "on", "off"):
                raise ValueError(
                    f"loss.streaming_targets must be auto/true/false, "
                    f"got {st!r}")
            self.streaming_targets = low in ("auto", "true", "on")
        else:
            self.streaming_targets = bool(st)
        self.loss_k_tile = int(loss_cfg.get("k_tile") or 8192)
        # Step-wide RNG-plan engine (rng/plan.py): one counter-based
        # derivation per step turns (seed, iteration) into a handful of
        # large fused draws (drop-path indices/bits, RoPE jitter) that
        # the forward consumes as static slices — no per-block fold_in
        # chains. "auto"/true = plan (default); false = the legacy
        # make_rng path (the test oracle and bitwise-legacy draws).
        rng_cfg = cfg.get("rng") or {}
        rp = rng_cfg.get("plan", "auto")
        if isinstance(rp, str):
            low = rp.lower()
            if low not in ("auto", "true", "false", "on", "off"):
                raise ValueError(
                    f"rng.plan must be auto/true/false, got {rp!r}")
            self.rng_plan = low in ("auto", "true", "on")
        else:
            self.rng_plan = bool(rp)
        if self.rng_plan and str(cfg.student.arch).startswith("convnext"):
            # ConvNeXt backbones consume drop-path through their own
            # per-stage DropPath modules (models/convnext.py) — plan
            # wiring is ViT-only; keep the legacy path there
            self.rng_plan = False
        pipe = int((cfg.get("parallel") or {}).get("pipe", 1) or 1)
        if self.rng_plan and pipe > 1:
            # the stage-stacked pipeline scan owns its rng threading
            # (parallel/pipeline.py) — fall back loudly, never silently
            import warnings

            warnings.warn(
                "rng.plan is not supported under pipeline parallelism "
                f"(parallel.pipe={pipe}); falling back to the legacy "
                "fold_in rng path for this run")
            self.rng_plan = False
        # Crop-packed single-pass student engine (ops/packing.py +
        # models/vision_transformer.py _packed_forward): pack the local
        # crop sequences k-per-row into global-length rows and run ONE
        # backbone apply for global+local — one block scan, the weight
        # stack streamed once per direction instead of twice, ~44
        # well-tiled rows instead of 120 at ViT-L B=12. "auto"/true =
        # packed (default); false = the two-pass oracle (the test
        # reference; tests/test_crop_packing.py pins equivalence).
        model_cfg = cfg.get("model") or {}
        cp = model_cfg.get("crop_packing", "auto")
        if isinstance(cp, str):
            low = cp.lower()
            if low not in ("auto", "true", "false", "on", "off"):
                raise ValueError(
                    f"model.crop_packing must be auto/true/false, "
                    f"got {cp!r}")
            self.crop_packing = low in ("auto", "true", "on")
        else:
            self.crop_packing = bool(cp)
        if self.crop_packing:
            self.crop_packing = self._resolve_crop_packing(cfg, pipe)
        # ZeRO-3 weight streaming (parallel.zero3, train/setup.py): the
        # forward materializes the NON-block master subtrees (heads,
        # patch embed, final norms) once per step under the
        # ``zero3_gather`` scope; the block stacks are excluded — their
        # weights gather per block inside the stack (the
        # ``zero3_stream`` wrapper the backbones carry). Same
        # model-parallel-free gate as the stream; inert without a mesh.
        from dinov3_tpu.configs.config import zero3_stream_wished

        self.zero3_gather = zero3_stream_wished(cfg)
        # Unified engine (train/setup.py decides the final arm and syncs
        # this flag): coalesce the non-block zero3 gathers + their grad
        # reduce-scatters into hierarchy-aware flat buckets
        # (train/fused_update.py gather_zero3_bucketed). The per-leaf
        # walk below stays the =false oracle.
        from dinov3_tpu.configs.config import bucketed_collectives_wished

        self.zero3_buckets = (
            self.zero3_gather and bucketed_collectives_wished(cfg)
        )
        self.gram_enabled = bool(cfg.gram.use_loss)
        self.gram_uses_ema_teacher = bool(cfg.gram.ema_teacher)
        # per-iteration loss-weight ramps (host numpy; moved in-graph by the
        # train step as constants)
        self.dino_local_weight_schedule = None
        if cfg.dino.reweight_dino_local_loss:
            from dinov3_tpu.train.schedules import linear_warmup_cosine_decay

            s = cfg.dino.local_loss_weight_schedule
            L = cfg.train.OFFICIAL_EPOCH_LENGTH
            self.dino_local_weight_schedule = linear_warmup_cosine_decay(
                start=s["start"], peak=s["peak"], end=s["end"],
                warmup_iterations=int(s.get("warmup_epochs", 0) * L),
                total_iterations=L * cfg.optim.epochs,
            )
        self.gram_weight_schedule = None
        if self.gram_enabled and cfg.gram.get("loss_weight_schedule"):
            from dinov3_tpu.train.schedules import linear_warmup_cosine_decay

            s = cfg.gram.loss_weight_schedule
            L = cfg.train.OFFICIAL_EPOCH_LENGTH
            self.gram_weight_schedule = linear_warmup_cosine_decay(
                start=s["start"], peak=s["peak"], end=s["end"],
                warmup_iterations=int(s.get("warmup_epochs", 0) * L),
                total_iterations=L * cfg.optim.epochs,
            )

    def _resolve_crop_packing(self, cfg: ConfigNode, pipe: int) -> bool:
        """Auto-fallback gate for the crop-packed engine (the pipeline/
        convnext convention the rng plan established): returns whether
        packing stays on, warning on every loud fallback."""
        import warnings

        if str(cfg.student.arch).startswith("convnext"):
            # packing is a token-sequence layout; ConvNeXt has no token
            # stack to pack (silent structural fallback, like rng.plan)
            return False
        if pipe > 1:
            warnings.warn(
                "model.crop_packing is not supported under pipeline "
                f"parallelism (parallel.pipe={pipe}); falling back to "
                "the two-pass student forward for this run")
            return False
        # seq parallelism no longer forfeits packing: ring attention
        # threads the packed segment ids through its rotating K/V chunks
        # (parallel/ring_attention.py), so the block-diagonal mask holds
        # on the seq-sharded path too (tests/test_ring_attention.py pins
        # the packed+seq composition).
        from dinov3_tpu.ops.packing import layout_from_cfg

        layout = layout_from_cfg(cfg, int(cfg.train.batch_size_per_device))
        if layout is None or layout.k < 2:
            k = None if layout is None else layout.k
            warnings.warn(
                "model.crop_packing: local sequences do not pack into "
                f"global rows (k={k}; need >= 2 per row); falling back "
                "to the two-pass student forward for this run")
            return False
        return True

    # ---------------- init ----------------

    def init_params(self, rng: jax.Array, batch: dict, unbox: bool = True) -> dict:
        """Initialize {"student", "teacher"[, "gram"]} with teacher == student.

        ``unbox=False`` keeps the ``nn.Partitioned`` logical-axis metadata on
        every leaf — the sharded-init path (parallel/sharding.py) needs it to
        derive ``NamedSharding``s before materializing anything.
        """
        import flax.linen as nn

        maybe_unbox = nn.meta.unbox if unbox else (lambda t: t)
        r_bb, r_dino, r_ibot = jax.random.split(rng, 3)
        g = batch["global_crops"][:1]
        bb = maybe_unbox(self.student_backbone.init(r_bb, g))["params"]
        cls = jnp.zeros((1, self.embed_dim), self.policy.compute_dtype)
        dino = maybe_unbox(self.dino_head.init(r_dino, cls))["params"]
        ibot = maybe_unbox(self.ibot_head.init(r_ibot, cls))["params"]
        student = {"backbone": bb, "dino_head": dino, "ibot_head": ibot}
        if self.distillation:
            r_tb, r_td, r_ti = jax.random.split(jax.random.fold_in(rng, 7), 3)
            tbb = maybe_unbox(self.teacher_backbone.init(r_tb, g))["params"]
            tcls = jnp.zeros(
                (1, self.teacher_embed_dim), self.policy.compute_dtype
            )
            teacher = {
                "backbone": tbb,
                "dino_head": maybe_unbox(
                    self.teacher_dino_head.init(r_td, tcls))["params"],
                "ibot_head": maybe_unbox(
                    self.teacher_ibot_head.init(r_ti, tcls))["params"],
            }
        else:
            teacher = jax.tree.map(jnp.copy, student)
        params = {"student": student, "teacher": teacher}
        if self.gram_enabled and not self.gram_uses_ema_teacher:
            params["gram"] = jax.tree.map(jnp.copy, {"backbone": bb})

        # Belt-and-braces for the fp32-master contract (the policy above
        # already initializes in fp32): catches any module that hardcodes
        # its own param dtype.
        def _master(x):
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(jnp.float32)
            return x

        return jax.tree.map(_master, params)

    def init_state(self) -> dict:
        """Non-param training state (softmax-centering EMA centers)."""
        return {
            "dino_center": jnp.zeros((1, self.cfg.dino.head_n_prototypes),
                                     self.policy.reduce_dtype),
            "ibot_center": jnp.zeros((1, self.cfg.ibot.head_n_prototypes),
                                     self.policy.reduce_dtype),
        }

    # ---------------- forwards ----------------

    def build_rng_plan(self, rng: jax.Array, batch: dict) -> dict:
        """The step's randomness plan from the counter-derived step key.

        One ``split`` fans the key per student pass; each pass's spec is
        derived from the student backbone's own static attributes
        (rng/plan.spec_from_module), so the plan and its consumers
        cannot disagree on shapes or modes. Built inside the jitted
        step — the arrays are born sharded along the batch axis
        (parallel/sharding.constrain_batch_dim).
        """
        import dataclasses

        from dinov3_tpu.parallel.context import get_current_mesh
        from dinov3_tpu.rng.plan import (
            build_step_plan,
            packed_pass_plan,
            spec_from_module,
        )

        mesh = get_current_mesh()
        specs = {
            "global": spec_from_module(
                self.student_backbone, batch["global_crops"].shape[0]),
            "local": spec_from_module(
                self.student_backbone, batch["local_crops"].shape[0]),
        }
        if not self.crop_packing:
            return build_step_plan(rng, specs, mesh)
        # packed engine: the global/local lanes keep their key positions
        # (so the RoPE factors are bitwise the two-pass oracle's) but
        # skip the drop-path draws the packed pass never consumes; the
        # packed drop-path lane is drawn at packed-row granularity over
        # 2B + P mixed rows from its own fold
        plan = build_step_plan(
            rng,
            {k: dataclasses.replace(s, drop_path_rate=0.0)
             for k, s in specs.items()},
            mesh,
        )
        rows = self._packed_layout(batch).rows_total
        plan["packed"] = packed_pass_plan(
            rng, spec_from_module(self.student_backbone, rows), plan, mesh)
        return plan

    def _packed_layout(self, batch):
        """The packed row layout for this batch's shapes (static)."""
        from dinov3_tpu.ops.packing import make_packed_layout

        p = self.cfg.student.patch_size
        n_prefix = 1 + int(self.cfg.student.get("n_storage_tokens", 0) or 0)
        g, l = batch["global_crops"], batch["local_crops"]
        return make_packed_layout(
            n_global_rows=g.shape[0], n_local=l.shape[0],
            seq_global=n_prefix + (g.shape[1] // p) * (g.shape[2] // p),
            seq_local=n_prefix + (l.shape[1] // p) * (l.shape[2] // p),
            n_prefix=n_prefix,
        )

    def _apply_backbone(self, module, params, x, masks=None, *, crop_kind,
                        train, rngs=None, rng_plan=None, local_crops=None,
                        lowp=None):
        # rng_plan is a ViT-only kwarg (ConvNeXt backbones keep the
        # legacy rng path — meta init never enables the plan for them);
        # local_crops likewise (the crop-packed single-pass engine).
        # ``lowp``: read-only delayed-scaling collection for the fp8/int8
        # train.low_precision arms (ops/lowp.py) — when absent the
        # modules' has_variable guard keeps the plain bf16 matmuls.
        variables = {"params": params}
        if lowp is not None:
            variables["lowp"] = lowp
        plan_kw = {} if rng_plan is None else {"rng_plan": rng_plan}
        if local_crops is not None:
            plan_kw["local_crops"] = local_crops
        if train and getattr(module, "ffn_layer", "") == "moe":
            # MoE blocks sow their Switch-style load-balance terms into the
            # "losses" collection; collect them for compute_losses
            out, aux_vars = module.apply(
                variables, x, masks, crop_kind=crop_kind,
                deterministic=not train, rngs=rngs, mutable=["losses"],
                **plan_kw,
            )
            flat = jax.tree_util.tree_flatten_with_path(
                aux_vars.get("losses", {})
            )[0]
            terms = []
            for keypath, leaf in flat:
                in_pipe = any(
                    getattr(k, "key", None) == "pipeline" for k in keypath
                )
                if in_pipe and leaf.ndim >= 2:
                    # pipeline-stacked [T(icks), S(tages), blocks/stage]:
                    # stage s runs a real microbatch only at ticks
                    # s..s+M-1 (M = T-S+1); bubble slots carry routing
                    # stats of zero/stale buffers and must not count
                    T, S = leaf.shape[0], leaf.shape[1]
                    M = T - S + 1
                    t = jnp.arange(T)[:, None]
                    s = jnp.arange(S)[None, :]
                    valid = (t >= s) & (t - s <= M - 1)
                    shape = (T, S) + (1,) * (leaf.ndim - 2)
                    w = valid.astype(leaf.dtype).reshape(shape)
                    terms.append(
                        jnp.sum(leaf * w)
                        / (jnp.sum(w) * math_prod(leaf.shape[2:]))
                    )
                else:
                    terms.append(jnp.mean(leaf))
            if terms:
                out["moe_aux_loss"] = sum(terms) / len(terms)
            return out
        return module.apply(
            variables, x, masks, crop_kind=crop_kind,
            deterministic=not train, rngs=rngs, **plan_kw,
        )

    def masked_rows(self, batch, n_micro: int = 1) -> MaskedRows:
        """Compact the per-image [2B, M_img] mask buffers into the
        batch-wide [M_c] rows the iBOT heads and losses run over.

        ``M_c`` = ``masked_rows_bound`` of the sampler's rule for this
        batch's static shape: the mask rows were made by
        ``self.mask_sampler_calls`` equal sampler calls, and with
        ``n_micro`` > 1 this batch is one of that many equal microbatches
        of them (train_step.split_microbatches). ``forward`` calls this
        once and hands the rows to teacher, student and loss; anything
        that calls those by itself does the same. The j-th valid flat
        position is found by binary search in the running count of valid
        slots (sorted and unique by construction; no scatter, no loop on
        the device, O(M_c log 2B*M_img)).
        """
        from dinov3_tpu.data.masking import masked_rows_bound
        from dinov3_tpu.parallel.context import get_current_mesh
        from dinov3_tpu.parallel.sharding import constrain_replicated

        mesh = get_current_mesh()
        valid, weights = (
            batch[k].reshape(-1) for k in ("mask_valid", "mask_weights"))
        if mesh is not None and mesh.size > 1:
            # the two [2B * M_img] vectors are small: every device holds
            # them whole, and the search below needs no collective
            valid, weights = (
                constrain_replicated(x, mesh) for x in (valid, weights))
        n_img, m_img = batch["mask_indices"].shape
        n_tok = batch["masks"].shape[1]
        m_c = masked_rows_bound(
            n_img * n_micro, n_tok, m_img,
            tuple(self.cfg.ibot.mask_ratio_min_max),
            self.cfg.ibot.mask_sample_probability,
            n_calls=self.mask_sampler_calls, n_seen=n_img)
        count = jnp.cumsum(valid, dtype=jnp.int32)
        n_valid = count[-1]
        nth = jnp.arange(m_c, dtype=jnp.int32)
        slots = jnp.minimum(
            jnp.searchsorted(count, nth + 1, side="left",
                             method="scan_unrolled"),
            count.size - 1).astype(jnp.int32)
        keep = nth < n_valid
        return MaskedRows(
            indices=batch["mask_indices"],
            slots=slots,
            weights=jnp.where(keep, weights[slots], 0.0),
            valid=keep,
            fill=n_valid.astype(jnp.float32) / m_c,
            overflow=jnp.maximum(n_valid - m_c, 0).astype(jnp.float32),
        )

    def _gather_masked(self, patch_tokens, masked: MaskedRows):
        """[2B, T, D] -> the batch's compact masked rows [M_c, D].

        Two static-shape gathers: each image's own masked tokens
        ([2B, M_img, D], local to the batch shard), then the compact rows
        out of those — the one gather that crosses data shards, [M_c, D]
        rows, pinned back onto the data axes so the heads' [M_c, K]
        planes split evenly. Keep the two stages: ONE gather of
        ``image * T + token`` rows out of the flat [2B*T, D] tokens ran
        on the v5e by itself and inside the ViT-S step, and hung the
        ViT-L step there — with its padding rows out of range under
        ``mode="fill"``, with every index in range, and under plain
        indexing alike (bisected on the chip, PERF.md section 6, PR 26)."""
        from dinov3_tpu.parallel.sharding import constrain_batch_dim

        per_image = jnp.take_along_axis(
            patch_tokens, masked.indices[..., None], axis=1)
        flat = per_image.reshape(-1, patch_tokens.shape[-1])
        return constrain_batch_dim(flat[masked.slots], 0)

    def teacher_backbone_features(self, teacher_params, batch, lowp=None):
        """The frozen teacher's backbone forward over the global crops:
        (cls [2B, D_t], patches [2B, T, D_t]), both in compute dtype.
        This is the piece the serve-backed teacher arm computes OUTSIDE
        the step (once per image, fanned out to every student subgroup);
        everything downstream of it — heads, centering, target specs —
        is shared with the in-step oracle via
        ``teacher_targets_from_features``, which is what makes the two
        arms bitwise-comparable."""
        out = self._apply_backbone(
            self.teacher_backbone, teacher_params["backbone"],
            batch["global_crops"], crop_kind="global", train=False,
            lowp=lowp,
        )
        return out["x_norm_clstoken"], out["x_norm_patchtokens"]

    def get_teacher_output(
        self, teacher_params, batch, teacher_temp, state, update_centers=True,
        lowp=None, *, masked: MaskedRows,
    ):
        if self.teacher_source == "serve":
            if "teacher_cls" not in batch or "teacher_patches" not in batch:
                raise ValueError(
                    "distillation.teacher_source=serve needs teacher_cls/"
                    "teacher_patches batch planes (train/distillation.py "
                    "TeacherServer.annotate; teacher_feature_example for "
                    "the trace batch)")
            # precomputed-targets arm: features were computed ONCE by
            # the host-shared packed teacher engine and ride the batch
            # as f32 planes; cast back to the compute dtype the in-step
            # backbone emits (f32 storage of bf16 values round-trips
            # exactly, so feeding the oracle's own features through
            # here is bitwise — COST_DISTILL_r22.json's equivalence pin)
            with jax.named_scope("distill_fanout"):
                dt = self.policy.compute_dtype
                cls = batch["teacher_cls"].astype(dt)
                patches = batch["teacher_patches"].astype(dt)
        else:
            with step_phase("teacher_backbone"):
                cls, patches = self.teacher_backbone_features(
                    teacher_params, batch, lowp=lowp)
        with step_phase("teacher_targets"):
            return self.teacher_targets_from_features(
                teacher_params, cls, patches, batch, teacher_temp, state,
                update_centers, masked=masked,
            )

    def teacher_targets_from_features(
        self, teacher_params, cls, patches, batch, teacher_temp, state,
        update_centers=True, *, masked: MaskedRows,
    ):
        """Teacher targets from already-computed backbone features —
        the shared tail of both teacher arms (heads -> centering ->
        target specs). ``cls`` [2B, D_t], ``patches`` [2B, T, D_t];
        ``masked``: the batch's ``masked_rows``."""
        n_g = 2
        B = cls.shape[0] // n_g
        cls_logits = self.teacher_dino_head.apply(
            {"params": teacher_params["dino_head"]}, cls
        )  # [2B, K]
        masked_logits = self.teacher_ibot_head.apply(
            {"params": teacher_params["ibot_head"]},
            self._gather_masked(patches, masked),
        )  # [M_c, K']
        valid = masked.valid

        new_state = dict(state)
        # Teacher-target storage dtype: bf16 halves the HBM footprint of
        # the [*, 65536] target buffers (10.2% of the r5 on-chip step
        # profile was fp32 passes over them); reductions stay fp32. Under
        # the streaming engine the softmax-center path stores NO target
        # buffer at all, and the Sinkhorn path stores only the log-domain
        # iterate ``xs`` (target_dtype-typed) — the materialized q never
        # exists (losses/streaming.py).
        tgt = self.policy.target_dtype
        stream = self.streaming_targets
        if self.centering == "sinkhorn_knopp":
            cls_t = sinkhorn_knopp(
                cls_logits, teacher_temp, storage_dtype=tgt,
                return_factors=stream)
            masked_t = sinkhorn_knopp(
                masked_logits, teacher_temp,
                row_weights=valid.astype(self.policy.reduce_dtype),
                storage_dtype=tgt, return_factors=stream,
            )
            if stream:
                cls_target = {"kind": "sinkhorn", "factors": cls_t}
                masked_target = {"kind": "sinkhorn", "factors": masked_t}
            else:
                cls_target = {"kind": "probs",
                              "probs": cls_t.reshape(n_g, B, -1)}
                masked_target = {"kind": "probs", "probs": masked_t}
        elif self.centering == "softmax_center":
            if stream:
                K = cls_logits.shape[-1]
                cls_target = {
                    "kind": "softmax_center",
                    "logits": cls_logits.reshape(n_g, B, K),
                    "center": state["dino_center"], "temp": teacher_temp,
                }
                # padding rows (valid == 0) are weighted out by
                # mask_weights in the loss, matching the materialized
                # path's explicit q zeroing
                masked_target = {
                    "kind": "softmax_center", "logits": masked_logits,
                    "center": state["ibot_center"], "temp": teacher_temp,
                }
            else:
                cls_centered = softmax_center_teacher(
                    cls_logits, state["dino_center"], teacher_temp,
                    storage_dtype=tgt,
                )
                masked_centered = softmax_center_teacher(
                    masked_logits, state["ibot_center"], teacher_temp,
                    storage_dtype=tgt,
                ) * valid[:, None].astype(tgt or masked_logits.dtype)
                cls_target = {"kind": "probs",
                              "probs": cls_centered.reshape(n_g, B, -1)}
                masked_target = {"kind": "probs", "probs": masked_centered}
            if update_centers:
                # bit-identical fp32 EMA accumulation on BOTH paths: the
                # center update always reads the raw logits buffer
                new_state["dino_center"] = update_center(
                    state["dino_center"], cls_logits
                )
                w = valid.astype(self.policy.reduce_dtype)[:, None]
                masked_mean = jnp.sum(masked_logits * w, axis=0, keepdims=True)
                masked_mean = masked_mean / jnp.maximum(jnp.sum(w), 1.0)
                new_state["ibot_center"] = (
                    state["ibot_center"] * 0.9 + masked_mean * 0.1
                )
        else:
            raise ValueError(f"unknown centering {self.centering!r}")

        return {
            "cls_pre_head": cls.reshape(n_g, B, -1),
            "patch_pre_head": patches,
            # teacher-target specs (losses/streaming.py pair_ce_from_spec /
            # ibot_loss_from_spec): "probs" = materialized oracle buffers,
            # "softmax_center"/"sinkhorn" = streaming (no [*, K] target
            # buffer). masked rows are the compact [M_c, K'].
            "cls_target": cls_target,
            "masked_target": masked_target,
        }, new_state

    def get_student_output(self, student_params, batch, rngs, rng_plan=None,
                           lowp=None, *, masked: MaskedRows):
        g = batch["global_crops"]
        l = batch["local_crops"]
        n_g, n_l = 2, self.n_local_crops
        B = g.shape[0] // n_g
        masks = None if self.cfg.distillation.enabled else batch["masks"]
        moe_aux = None
        if self.crop_packing:
            # crop-packed single-pass engine: ONE backbone apply over
            # [2B + P, N_g] rows (globals + k-packed locals) under
            # segment-masked attention — the weight stack streams once
            # per direction instead of twice (ops/packing.py; oracle =
            # the two-pass branch below, model.crop_packing=false)
            with step_phase("student_backbone"):
                out = self._apply_backbone(
                    self.student_backbone, student_params["backbone"], g,
                    masks, crop_kind="global", train=True, rngs=rngs,
                    rng_plan=(None if rng_plan is None
                              else rng_plan["packed"]),
                    local_crops=l, lowp=lowp,
                )
            g_cls, g_patch = out["x_norm_clstoken"], out["x_norm_patchtokens"]
            l_cls = out["local_cls"]
            if "moe_aux_loss" in out:
                # one pass covers every token (the oracle averages its
                # two per-pass load-balance terms)
                moe_aux = out["moe_aux_loss"]
        elif rng_plan is not None:
            # plan path: each pass consumes its own precomputed lane —
            # no per-pass fold_in, no make_rng anywhere in the forward
            with step_phase("student_backbone"):
                g_out = self._apply_backbone(
                    self.student_backbone, student_params["backbone"], g,
                    masks, crop_kind="global", train=True,
                    rng_plan=rng_plan["global"], lowp=lowp,
                )
                l_out = self._apply_backbone(
                    self.student_backbone, student_params["backbone"], l,
                    None, crop_kind="local", train=True,
                    rng_plan=rng_plan["local"], lowp=lowp,
                )
        else:
            with step_phase("student_backbone"):
                g_out = self._apply_backbone(
                    self.student_backbone, student_params["backbone"], g,
                    masks, crop_kind="global", train=True, rngs=rngs,
                    lowp=lowp,
                )
                l_out = self._apply_backbone(
                    self.student_backbone, student_params["backbone"], l,
                    None, crop_kind="local", train=True,
                    rngs={k: jax.random.fold_in(v, 1)
                          for k, v in rngs.items()},
                    lowp=lowp,
                )
        if not self.crop_packing:
            g_cls, g_patch = (g_out["x_norm_clstoken"],
                              g_out["x_norm_patchtokens"])
            l_cls = l_out["x_norm_clstoken"]
            if "moe_aux_loss" in g_out or "moe_aux_loss" in l_out:
                moe_aux = (g_out.get("moe_aux_loss", 0.0)
                           + l_out.get("moe_aux_loss", 0.0)) / 2.0

        with step_phase("student_heads"):
            masked_logits = self.ibot_head.apply(
                {"params": student_params["ibot_head"]},
                self._gather_masked(g_patch, masked),
            )  # [M_c, K']
            # one fused DINO-head call for global+local CLS
            cls_cat = jnp.concatenate([g_cls, l_cls], axis=0)
            cls_logits = self.dino_head.apply(
                {"params": student_params["dino_head"]}, cls_cat
            )
        K = cls_logits.shape[-1]
        g_logits = cls_logits[: n_g * B].reshape(n_g, B, K)
        l_logits = cls_logits[n_g * B:].reshape(n_l, B, K)

        global_out = {
            "cls_pre_head": g_cls.reshape(n_g, B, -1),
            "patch_pre_head": g_patch,
            "cls_after_head": g_logits,
            "masked_patch_after_head": masked_logits,
        }
        if moe_aux is not None:
            global_out["moe_aux_loss"] = moe_aux
        local_out = {
            "cls_pre_head": l_cls.reshape(n_l, B, -1),
            "cls_after_head": l_logits,
        }
        return global_out, local_out

    def get_gram_teacher_output(self, params, batch, teacher_patches):
        """Patch features anchoring the Gram loss.

        Uses the dedicated frozen gram backbone on ``gram_teacher_crops``
        when configured, else the EMA teacher's patches; resizes the patch
        grid to the student's when resolutions differ
        (reference: ssl_meta_arch.py get_gram_teacher_output + config
        gram.global_teacher_resize_method).
        """
        if not self.gram_uses_ema_teacher and "gram" in params:
            crops = batch.get("gram_teacher_crops")
            if crops is None:
                crops = batch["global_crops"]
            out = self._apply_backbone(
                self.teacher_backbone, params["gram"]["backbone"], crops,
                crop_kind="global", train=False,
            )
            feats = out["x_norm_patchtokens"]
        else:
            feats = teacher_patches
        feats = jax.lax.stop_gradient(feats)
        # resize the gram teacher's patch grid onto the student grid
        T_t = feats.shape[1]
        p = self.cfg.student.patch_size
        hs = ws = self.cfg.crops.global_crops_size // p
        if T_t != hs * ws:
            ht = wt = int(round(T_t ** 0.5))
            grid = feats.reshape(feats.shape[0], ht, wt, feats.shape[-1])
            grid = jax.image.resize(
                grid, (feats.shape[0], hs, ws, feats.shape[-1]),
                method=self.cfg.gram.global_teacher_resize_method,
                antialias=self.cfg.gram.global_teacher_resize_antialias,
            )
            feats = grid.reshape(feats.shape[0], hs * ws, feats.shape[-1])
        return feats

    # ---------------- loss ----------------

    def compute_losses(
        self, teacher_global, student_global, student_local, gram_feats,
        batch, iteration, masked: MaskedRows,
    ):
        cfg = self.cfg
        n_g = 2
        n_l = self.n_local_crops
        ignore_diag = bool(cfg.dino.global_ignore_diagonal)
        loss_dict = {}
        total = jnp.zeros((), self.policy.reduce_dtype)

        # crop-pair scales (reference compute_losses:480-489)
        g_terms = n_g * (n_g - 1) if ignore_diag else n_g * n_g
        l_terms = n_g * n_l
        g_scale = g_terms / (g_terms + l_terms)
        l_scale = l_terms / (g_terms + l_terms)

        local_w = 1.0
        if self.dino_local_weight_schedule is not None:
            sched = jnp.asarray(self.dino_local_weight_schedule, jnp.float32)
            local_w = sched[jnp.minimum(iteration, sched.shape[0] - 1)]

        # One pair-CE over ALL student crops (global + local) against the
        # teacher-target spec: on the streaming path this is a single
        # K-tiled pass over the teacher logits for BOTH dino losses (the
        # materialized path reads its q buffer once instead of twice).
        from dinov3_tpu.losses import pair_ce_from_spec

        g_rows = student_global["cls_after_head"]          # [n_g, B, K]
        l_rows = student_local["cls_after_head"]           # [n_l, B, K]
        B = g_rows.shape[1]
        with jax.named_scope("dino_loss"):
            pair = pair_ce_from_spec(
                jnp.concatenate([g_rows, l_rows], axis=0),
                teacher_global["cls_target"], k_tile=self.loss_k_tile,
            )                                               # [n_g+n_l, n_g]
            dino_local = pair_ce_to_loss(pair[n_g:], B)
            dino_global = pair_ce_to_loss(pair[:n_g], B,
                                          ignore_diagonal=ignore_diag)
        loss_dict["dino_local_crops_loss"] = dino_local
        total = total + cfg.dino.loss_weight * l_scale * local_w * dino_local
        loss_dict["dino_global_crops_loss"] = dino_global
        total = total + cfg.dino.loss_weight * g_scale * dino_global

        # KoLeo per global crop over the batch (reference:519)
        group = (cfg.dino.koleo_distributed_loss_group_size
                 if cfg.dino.koleo_loss_distributed else None)
        topk = cfg.dino.koleo_topk if cfg.dino.koleo_loss_distributed else 1
        with jax.named_scope("koleo_loss"):
            kol = sum(
                koleo_loss(teacher_cls, topk=topk, group_size=group)
                for teacher_cls in student_global["cls_pre_head"]
            ) / n_g
        loss_dict["koleo_loss"] = kol
        total = total + cfg.dino.koleo_loss_weight * n_g * kol

        # iBOT on masked tokens
        from dinov3_tpu.losses import ibot_loss_from_spec

        n_images = batch["masks"].shape[0]
        with jax.named_scope("ibot_loss"):
            ibot = ibot_loss_from_spec(
                student_global["masked_patch_after_head"],
                teacher_global["masked_target"],
                masked.weights, n_images=n_images, k_tile=self.loss_k_tile,
            )
            # a mask source that outgrows the sampler's bound must stop
            # the run, not train on fewer tokens: NaN loss AND gradient
            ibot = ibot * jnp.where(masked.overflow > 0, jnp.nan, 1.0)
        loss_dict["ibot_loss"] = ibot
        loss_dict["ibot_rows_fill"] = masked.fill
        loss_dict["ibot_rows_overflow"] = masked.overflow
        total = total + cfg.ibot.loss_weight * ibot

        if self.gram_enabled and gram_feats is not None:
            gram_w = cfg.gram.loss_weight
            if self.gram_weight_schedule is not None:
                sched = jnp.asarray(self.gram_weight_schedule, jnp.float32)
                gram_w = sched[jnp.minimum(iteration, sched.shape[0] - 1)]
            gram_kw = dict(
                normalize=cfg.gram.normalized,
                remove_neg=cfg.gram.remove_neg,
                remove_only_teacher_neg=cfg.gram.remove_only_teacher_neg,
            )
            # gram.tokens_used: all | masked | unmasked (reference
            # ssl_meta_arch.py:221-222; masked variants force token level)
            tokens_used = str(cfg.gram.get("tokens_used", "all") or "all")
            tok_mask = None
            if tokens_used == "masked":
                tok_mask = batch["masks"]
            elif tokens_used == "unmasked":
                tok_mask = ~batch["masks"]
            elif tokens_used != "all":
                raise ValueError(f"unknown gram.tokens_used {tokens_used!r}")
            with jax.named_scope("gram_loss"):
                g_loss = gram_loss(
                    student_global["patch_pre_head"], gram_feats,
                    img_level=(cfg.gram.img_level and tok_mask is None),
                    token_mask=tok_mask,
                    **gram_kw,
                )
            loss_dict["gram_loss"] = g_loss
            loss_dict["gram_loss_weight"] = jnp.asarray(gram_w, jnp.float32)
            total = total + gram_w * g_loss
            if cfg.gram.get("compute_stats", False):
                # stats-only masked/unmasked views (reference:543-556);
                # reported, never added to the total
                for name, m in (("masked", batch["masks"]),
                                ("unmasked", ~batch["masks"])):
                    loss_dict[f"stats_only/{name}_gram_loss"] = (
                        jax.lax.stop_gradient(gram_loss(
                            student_global["patch_pre_head"], gram_feats,
                            img_level=False, token_mask=m, **gram_kw,
                        ))
                    )

        if "moe_aux_loss" in student_global:
            aux_w = float(cfg.student.get("moe_aux_loss_weight", 0.01) or 0.0)
            aux = student_global["moe_aux_loss"]
            loss_dict["moe_aux_loss"] = aux
            total = total + aux_w * aux

        loss_dict["total_loss"] = total
        return total, loss_dict

    # ---------------- full forward ----------------

    def forward(
        self,
        student_params,
        frozen_params,
        batch,
        *,
        teacher_temp,
        state,
        iteration,
        rngs=None,
        rng_plan=None,
        update_centers=True,
        gather_params=True,
        lowp=None,
        n_micro=1,
    ):
        """Loss for one batch. ``frozen_params`` = {"teacher": ..,
        ["gram": ..]} under stop_gradient; gradients flow only through
        ``student_params``. Student randomness comes from EITHER ``rngs``
        (legacy fold_in streams) or ``rng_plan`` (the step-wide plan,
        ``build_rng_plan``); the teacher/gram passes are deterministic
        and consume neither. ``gather_params=False`` skips the zero3
        gathers — the microbatched accumulation path hoists them outside
        its scan (one gather + one grad-RS per OPTIMIZER step, not per
        microbatch) and passes already-replicated trees.

        ``lowp``: ``{"student": scales, "teacher": scales}`` read-only
        delayed-scaling trees for the fp8/int8 ``train.low_precision``
        arms (ops/lowp.py ``lowp_scales``) — both backbones forward
        through the quantized matmuls; the gram teacher never receives
        the collection (its anchoring features stay bf16).

        ``n_micro``: this batch is one of that many equal microbatches of
        the collated batch (``masked_rows`` sizes its buffer for the
        most-masked one)."""
        lowp = lowp or {}
        frozen = jax.lax.stop_gradient(frozen_params)
        # ZeRO-3: replicate the non-streamed master subtrees for this
        # step's compute (heads/patch-embed/norms; the block stacks stay
        # sharded and gather per block inside the scan). Differentiated
        # for the student — the constraint's transpose is the grad
        # reduce-scatter back to the sharded master layout.
        if gather_params:
            student_params = self._zero3_gather_params(student_params)
            frozen = self._zero3_gather_params(frozen)
        with step_phase("teacher_targets"):
            masked = self.masked_rows(batch, n_micro)
        teacher_global, new_state = self.get_teacher_output(
            frozen["teacher"], batch, teacher_temp, state, update_centers,
            lowp=lowp.get("teacher"), masked=masked,
        )
        student_global, student_local = self.get_student_output(
            student_params, batch, rngs, rng_plan=rng_plan,
            lowp=lowp.get("student"), masked=masked,
        )
        gram_feats = None
        if self.gram_enabled:
            with step_phase("gram_teacher"):
                gram_feats = self.get_gram_teacher_output(
                    frozen, batch, teacher_global["patch_pre_head"]
                )
        with step_phase("losses"):
            total, loss_dict = self.compute_losses(
                teacher_global, student_global, student_local, gram_feats,
                batch, iteration, masked=masked,
            )
        return total, (loss_dict, new_state)

    def _zero3_gather_params(self, tree):
        """Materialize (replicate) every master leaf of a zero3-sharded
        param tree for compute, EXCEPT the block-stack subtrees
        (``blocks`` / ``blocks_i`` / ``pipeline``) — those stream per
        block inside the stack. No-op when zero3 gathering is off or no
        mesh is active, and shape-preserving always (zero3 never changes
        leaf shapes), so both engine arms share this code path
        structurally.

        Two arms: the unified engine (``self.zero3_buckets``) coalesces
        the shardable leaves into hierarchy-aware flat buckets — one
        staged all-gather per bucket, one staged grad reduce-scatter per
        bucket in the transpose (``gather_zero3_bucketed``); the
        per-leaf walk below is the ``optim.bucketed_collectives=false``
        oracle (one collective per leaf)."""
        if not self.zero3_gather:
            return tree
        from dinov3_tpu.parallel.context import get_current_mesh
        from dinov3_tpu.parallel.sharding import (
            constrain_replicated,
            update_shard_size,
        )

        mesh = get_current_mesh()
        if mesh is None:
            return tree
        if self.zero3_buckets and update_shard_size(mesh) > 1:
            from dinov3_tpu.train.fused_update import gather_zero3_bucketed

            return gather_zero3_bucketed(tree, mesh)

        def walk(sub):
            if not isinstance(sub, dict):
                return constrain_replicated(sub, mesh)
            return {
                k: (v if k == "blocks" or k.startswith("blocks_")
                    or k == "pipeline" else walk(v))
                for k, v in sub.items()
            }

        with jax.named_scope("zero3_gather"):
            return walk(tree)

    def update_ema(self, teacher_params, student_params, momentum):
        """teacher <- m * teacher + (1 - m) * student.

        The reference updated a detached copy that never fed back
        (SURVEY.md §2.9.1); here the result IS the teacher used next step.
        Under distillation the teacher is a frozen pretrained model and is
        returned unchanged.

        The arithmetic runs in fp32 and the result is cast back to the
        teacher's storage dtype — fp32 by construction (``init_params``
        forces fp32 masters), so the cast is an identity there; it guards
        the signature for restored checkpoints in other dtypes. Without
        it, ``t * momentum`` (bf16 × fp32 scalar array) silently promoted
        a bf16 teacher to fp32 after the first step — changing the step
        signature (a second full XLA compile on step 2).

        The per-leaf rule lives in ``train/fused_update.ema_leaf`` — the
        fused single-pass engine (default path) applies the same
        expression inside its one tree.map, so the two step programs
        cannot drift apart.
        """
        if self.distillation:
            return teacher_params
        from dinov3_tpu.train.fused_update import ema_leaf

        return jax.tree.map(
            lambda t, s: ema_leaf(t, s, momentum),
            teacher_params, student_params,
        )
