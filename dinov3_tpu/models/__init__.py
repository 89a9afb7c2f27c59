"""Model factories from config (reference: dinov3_jax/models/__init__.py).

``build_backbone`` maps the ``student``/``teacher`` config sections onto
``DinoVisionTransformer`` kwargs; the teacher variant drops stochastic depth
(reference:41-49). ConvNeXt lives in ``dinov3_tpu/models/convnext.py``.
"""

from __future__ import annotations

from dinov3_tpu.configs import ConfigNode
from dinov3_tpu.models.convnext import (
    CONVNEXT_SIZES,
    ConvNeXt,
    get_convnext_arch,
)
from dinov3_tpu.configs.config import LM_ARCHS
from dinov3_tpu.models.decoder import DecoderConfig, LMDecoder
from dinov3_tpu.models.vision_transformer import (
    ARCHS,
    DinoVisionTransformer,
    vit_7b,
    vit_base,
    vit_giant2,
    vit_huge2,
    vit_large,
    vit_small,
    vit_so400m,
    vit_test,
)
from dinov3_tpu.ops.common import Policy


def _validated_drop_path_mode(s) -> str:
    mode = str(s.get("drop_path_mode", "subset") or "subset")
    if mode not in ("subset", "mask"):
        raise ValueError(
            f"student.drop_path_mode={mode!r}: expected subset|mask"
        )
    return mode


def backbone_kwargs_from_cfg(cfg: ConfigNode, *, teacher: bool = False) -> dict:
    s = cfg.student
    kw = dict(
        patch_size=s.patch_size,
        drop_path_rate=0.0 if teacher else s.drop_path_rate,
        drop_path_mode=_validated_drop_path_mode(s),
        layerscale_init=s.layerscale,
        ffn_layer=s.ffn_layer,
        moe_num_experts=int(s.get("moe_num_experts", 8) or 8),
        moe_top_k=int(s.get("moe_top_k", 2) or 2),
        ffn_ratio=s.ffn_ratio,
        qkv_bias=s.qkv_bias,
        proj_bias=s.proj_bias,
        ffn_bias=s.ffn_bias,
        norm_layer=s.norm_layer,
        n_storage_tokens=s.n_storage_tokens,
        mask_k_bias=s.mask_k_bias,
        untie_cls_and_patch_norms=s.untie_cls_and_patch_norms,
        untie_global_and_local_cls_norm=s.untie_global_and_local_cls_norm,
        in_chans=s.in_chans,
        pos_embed_type=s.pos_embed_type,
        pos_embed_rope_base=s.pos_embed_rope_base,
        pos_embed_rope_min_period=s.pos_embed_rope_min_period,
        pos_embed_rope_max_period=s.pos_embed_rope_max_period,
        pos_embed_rope_normalize_coords=s.pos_embed_rope_normalize_coords,
        pos_embed_rope_shift_coords=None if teacher else s.pos_embed_rope_shift_coords,
        pos_embed_rope_jitter_coords=None if teacher else s.pos_embed_rope_jitter_coords,
        pos_embed_rope_rescale_coords=None if teacher else s.pos_embed_rope_rescale_coords,
        pos_embed_rope_dtype=s.pos_embed_rope_dtype,
    )
    # execution options
    train = cfg.train
    kw["remat"] = {False: "none", True: "blocks"}.get(train.get("checkpointing", False), "none")
    if train.get("checkpointing_full", False):
        kw["remat"] = "full"
    # parallel.remat: non-none values override the train.checkpointing
    # mapping (the merged config cannot distinguish an explicit "none"
    # from the schema default)
    pr = str((cfg.get("parallel") or {}).get("remat", "none") or "none")
    if pr not in ("none", "attn", "blocks", "full"):
        raise ValueError(
            f"parallel.remat={pr!r}: expected none|attn|blocks|full"
        )
    if pr != "none":
        kw["remat"] = pr
    kernels = cfg.get("kernels") or {}
    kw["attn_impl"] = kernels.get("flash_attention", "auto")
    kw["flash_block_q"] = int(kernels.get("flash_block_q", 512) or 512)
    kw["flash_block_kv"] = int(kernels.get("flash_block_kv", 512) or 512)
    from dinov3_tpu.configs.config import resolve_flash_min_seq

    kw["flash_min_seq"] = resolve_flash_min_seq(
        kernels.get("flash_min_seq", "auto")
    )
    parallel = cfg.get("parallel") or {}
    kw["seq_parallel"] = int(parallel.get("seq", 1) or 1) > 1
    if kw["remat"] == "attn" and kw["seq_parallel"]:
        import logging

        logging.getLogger("dinov3").warning(
            "remat=attn has no effect under seq parallelism: ring "
            "attention never materializes the [N, N] softmax state "
            "(same for the pallas flash kernel at >=%d tokens)",
            1024,
        )
    kw["pipeline_stages"] = int(parallel.get("pipe", 1) or 1)
    kw["pipeline_microbatches"] = int(parallel.get("pipe_microbatches", 0) or 0)
    kw["scan_layers"] = bool(train.get("scan_layers", False))
    # ZeRO-3 per-block weight stream (ops/block.py): gather each block's
    # sharded weights inside the block stack under the ``zero3_stream``
    # named scope, the matmul weights cast to compute dtype BEFORE the
    # gather (halves the streamed bytes; bitwise-identical because the
    # modules cast at use anyway). Engages only for model-parallel-free
    # zero3 configs (the materialization constraint would undo a
    # tensor/expert split), and never pre-casts under fp8 (the fp8
    # quantizer must see the original fp32 weights).
    from dinov3_tpu.configs.config import zero3_stream_wished

    kw["zero3_stream"] = zero3_stream_wished(cfg)
    # train.low_precision: fp8/int8 delayed-scaling block matmuls
    # (ops/lowp.py). BOTH student and teacher forward through the
    # quantized matmuls (the EMA STORAGE stays fp32 — only the teacher's
    # forward compute is quantized, the same way it already runs bf16);
    # eval builds and the gram teacher never receive a scale collection,
    # so the attr is inert there (the has_variable guard).
    from dinov3_tpu.configs.config import lowp_cfg

    kw["lowp_arm"] = lowp_cfg(cfg)["arm"]
    # fp8 projections inside blocks when the filter regex matches "blocks"
    # (reference config surface: student.fp8_enabled / fp8_filter,
    # ssl_default_config.yaml:121-122). Student only: the EMA teacher's
    # distillation targets stay full precision, like the other
    # student-only training knobs above (drop path, rope augmentation).
    if bool(s.get("fp8_enabled", False)) and not teacher:
        import re

        filt = str(s.get("fp8_filter", "blocks") or "")
        kw["fp8"] = bool(re.search(filt, "blocks")) if filt else True
        if not kw["fp8"]:
            import logging

            logging.getLogger("dinov3").warning(
                "student.fp8_enabled=true but fp8_filter=%r does not match "
                "'blocks' (the supported granularity is the whole block "
                "stack) — fp8 is OFF", filt,
            )

    policy = Policy.from_cfg(cfg.compute_precision)
    kw["dtype"] = policy.compute_dtype
    kw["param_dtype"] = policy.param_dtype
    kw["reduce_dtype"] = policy.reduce_dtype
    kw["probs_dtype"] = policy.probs_dtype
    return kw


def build_backbone(cfg: ConfigNode, *, teacher: bool = False,
                   param_dtype=None):
    """``param_dtype`` overrides the config policy's parameter dtype —
    the training path passes fp32 so masters (and initializer samples)
    never round through bf16 (ssl_meta_arch.py), while eval builds keep
    the recipe's storage dtype."""
    arch = cfg.student.arch
    if arch in LM_ARCHS:
        # a token decoder: one student, no teacher variant to build
        return LMDecoder(DecoderConfig.from_cfg(cfg, param_dtype=param_dtype))
    if arch.startswith("convnext"):
        from dinov3_tpu.configs.config import lowp_cfg

        if lowp_cfg(cfg)["arm"] != "bf16":
            raise ValueError(
                f"train.low_precision.arm={lowp_cfg(cfg)['arm']!r} requires "
                "a ViT backbone (the quantized matmuls live in the "
                "attn/mlp block kernels); student.arch=" + arch)
        from dinov3_tpu.models.convnext import (
            convnext_kwargs_from_cfg,
            get_convnext_arch,
        )

        kw = convnext_kwargs_from_cfg(cfg, teacher=teacher)
        if param_dtype is not None:
            kw["param_dtype"] = param_dtype
        return get_convnext_arch(arch)(**kw)
    if arch not in ARCHS:
        raise ValueError(f"unknown arch {arch!r}; available: {sorted(ARCHS)}")
    kw = backbone_kwargs_from_cfg(cfg, teacher=teacher)
    if param_dtype is not None:
        kw["param_dtype"] = param_dtype
    return ARCHS[arch](**kw)


def build_model_from_cfg(cfg: ConfigNode, only_teacher: bool = False):
    """(student, teacher, embed_dim) — mirrors reference build_model_from_cfg."""
    teacher_model = build_backbone(cfg, teacher=True)
    if only_teacher:
        return teacher_model, teacher_model.embed_dim
    student_model = build_backbone(cfg, teacher=False)
    return student_model, teacher_model, student_model.embed_dim


def build_model_for_eval(cfg: ConfigNode, ckpt_dir: str | None = None):
    """(model, params) for feature extraction / evals.

    Loads the EMA teacher's backbone from a framework checkpoint directory
    (the reference's equivalent imported nonexistent ``dinov3.*`` modules,
    models/__init__.py:81-93 — SURVEY.md §2.2).
    """
    import jax
    import jax.numpy as jnp

    model = build_backbone(cfg, teacher=True)
    S = cfg.crops.global_crops_size
    if isinstance(S, (list, tuple)):
        S = int(S[0])
    example = jnp.zeros((1, S, S, cfg.student.in_chans), jnp.float32)
    import flax.linen as nn

    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.key(0), example)
    )["params"]
    if ckpt_dir:
        import orbax.checkpoint as ocp

        from ..checkpoint import pytree_restore_args

        with ocp.CheckpointManager(ckpt_dir) as manager:
            step = manager.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
            abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, params)
            restored = manager.restore(
                step,
                args=ocp.args.Composite(
                    state=pytree_restore_args(
                        {"params": {"teacher": {"backbone": abstract}}}
                    )
                ),
            )
        params = restored["state"]["params"]["teacher"]["backbone"]
    return model, params


__all__ = [
    "ARCHS", "DinoVisionTransformer", "LM_ARCHS", "LMDecoder",
    "DecoderConfig", "backbone_kwargs_from_cfg",
    "build_backbone", "build_model_from_cfg", "vit_small", "vit_base",
    "vit_large", "vit_so400m", "vit_huge2", "vit_giant2", "vit_7b", "vit_test",
]
