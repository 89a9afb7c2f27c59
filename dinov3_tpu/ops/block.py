"""Pre-norm transformer block with LayerScale and stochastic depth.

(reference: dinov3_jax/layers/block.py — its list-forward is replaced by
model-level batching of same-resolution crops; its stochastic-depth batch
subsetting is kept as ``drop_path_mode="subset"``, made TPU-static via a
fixed ``floor(B*(1-rate))`` keep count — see ops/drop_path.py.)
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from dinov3_tpu.ops.attention import SelfAttention
from dinov3_tpu.ops.drop_path import (
    _SUBSET_FALLBACK_WARNED,  # noqa: F401 - re-export (tests reset it here)
    DropPath,
    mask_residual_planned,
    resolve_drop_path,
    subset_residual,
    subset_residual_planned,
)
from dinov3_tpu.ops.ffn import make_ffn_layer
from dinov3_tpu.ops.layer_scale import LayerScale
from dinov3_tpu.ops.norms import make_norm_layer


class SelfAttentionBlock(nn.Module):
    dim: int
    num_heads: int
    ffn_ratio: float = 4.0
    ffn_layer: str = "mlp"
    norm_layer: str = "layernorm"
    qkv_bias: bool = True
    proj_bias: bool = True
    ffn_bias: bool = True
    drop_path_rate: float = 0.0
    drop_path_mode: str = "subset"  # subset (reference semantics) | mask
    layerscale_init: float | None = 1e-5
    mask_k_bias: bool = False
    attn_impl: str = "auto"
    seq_parallel: bool = False
    fp8: bool = False
    causal: bool = False
    moe_num_experts: int = 8   # only used when ffn_layer == "moe"
    moe_top_k: int = 2
    flash_block_q: int = 512
    flash_block_kv: int = 512
    flash_min_seq: int = 0
    # train.low_precision.arm: fp8/int8 quantized matmuls over the
    # castable kernels (ops/lowp.py); "bf16" = the unchanged path
    lowp_arm: str = "bf16"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    reduce_dtype: Any = jnp.float32
    probs_dtype: Any = None

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        rope: tuple[jnp.ndarray, jnp.ndarray] | None = None,
        deterministic: bool = True,
        dp_plan: dict | None = None,
        seg: jnp.ndarray | None = None,
    ) -> jnp.ndarray:
        """``dp_plan``: this block's slice of the step-wide RNG plan
        (rng/plan.py) — {"idx": [2, keep]} (subset kept rows) or
        {"keep": [2, B]} (mask bits), one entry per residual branch.
        When given, the block consumes precomputed randomness and calls
        ``make_rng`` for NOTHING; when None, the legacy per-branch
        fold_in path runs (the rng.plan=false oracle).

        ``seg``: [B, N] segment ids of the crop-packed batch
        (ops/packing.py) — attention becomes block-diagonal, and the
        rope tables are per-row [B, N, head_dim]. Both are per-ROW
        arrays, so the subset drop-path gather must carry them along
        with the kept rows (the ``aux`` threading below)."""
        norm_kw = dict(param_dtype=self.param_dtype, reduce_dtype=self.reduce_dtype)
        ls = (
            (lambda name: LayerScale(self.layerscale_init, self.param_dtype, name=name))
            if self.layerscale_init is not None
            else (lambda name: (lambda y: y))
        )
        norm1 = make_norm_layer(self.norm_layer, name="norm1", **norm_kw)
        norm2 = make_norm_layer(self.norm_layer, name="norm2", **norm_kw)
        attn = SelfAttention(
            dim=self.dim, num_heads=self.num_heads, qkv_bias=self.qkv_bias,
            proj_bias=self.proj_bias, mask_k_bias=self.mask_k_bias,
            attn_impl=self.attn_impl, seq_parallel=self.seq_parallel,
            fp8=self.fp8, causal=self.causal,
            flash_block_q=self.flash_block_q,
            flash_block_kv=self.flash_block_kv,
            flash_min_seq=self.flash_min_seq,
            lowp_arm=self.lowp_arm, dtype=self.dtype,
            param_dtype=self.param_dtype, reduce_dtype=self.reduce_dtype,
            probs_dtype=self.probs_dtype,
            name="attn",
        )
        mlp = make_ffn_layer(
            self.ffn_layer, int(self.dim * self.ffn_ratio),
            moe_num_experts=self.moe_num_experts, moe_top_k=self.moe_top_k,
            use_bias=self.ffn_bias, fp8=self.fp8, lowp_arm=self.lowp_arm,
            dtype=self.dtype, param_dtype=self.param_dtype, name="mlp",
        )

        # per-row context (crop packing): the subset gather must carry
        # the rows' own rope tables / segment ids next to the rows
        aux = {"rope": rope, "seg": seg} if seg is not None else None

        def attn_branch(t, a=None):
            r = a["rope"] if a is not None else rope
            s = a["seg"] if a is not None else seg
            return ls("ls1")(attn(norm1(t), rope=r,
                                  deterministic=deterministic, seg=s))

        def mlp_branch(t, a=None):
            return ls("ls2")(mlp(norm2(t), deterministic=deterministic))

        dropping = self.drop_path_rate > 0.0 and not deterministic
        if dp_plan is not None and dropping:
            # step-wide RNG plan (rng/plan.py): the subset/mask decision
            # was made at plan build through the SAME resolve_drop_path,
            # so the key present in the slice is the decision
            if "idx" in dp_plan:
                x = subset_residual_planned(x, attn_branch, dp_plan["idx"][0],
                                            aux=aux)
                x = subset_residual_planned(x, mlp_branch, dp_plan["idx"][1],
                                            aux=aux)
            else:
                x = mask_residual_planned(
                    x, attn_branch(x), dp_plan["keep"][0],
                    self.drop_path_rate)
                x = mask_residual_planned(
                    x, mlp_branch(x), dp_plan["keep"][1],
                    self.drop_path_rate)
            return x
        mode = self.drop_path_mode
        if dropping:
            # stratify by the data-shard count: per-span sampling matches
            # the torch reference's per-rank subsetting and keeps the
            # sampled rows inside each shard's span (subset_residual doc)
            from dinov3_tpu.parallel.context import get_current_mesh

            mode, groups = resolve_drop_path(
                x.shape[0], self.drop_path_rate, self.drop_path_mode,
                get_current_mesh())
        elif mode not in ("subset", "mask"):
            raise ValueError(
                f"unknown drop_path_mode {mode!r}; expected subset|mask"
            )
        if dropping and mode == "subset":
            # reference semantics (block.py:94-117): the branch runs on a
            # random floor(B*(1-rate)) subset — dropped samples skip the
            # compute, not just the residual
            x = subset_residual(x, attn_branch,
                                self.make_rng("drop_path"),
                                self.drop_path_rate, groups=groups,
                                aux=aux)
            x = subset_residual(x, mlp_branch,
                                self.make_rng("drop_path"),
                                self.drop_path_rate, groups=groups,
                                aux=aux)
        else:
            dp = DropPath(self.drop_path_rate)
            x = x + dp(attn_branch(x), deterministic=deterministic)
            x = x + dp(mlp_branch(x), deterministic=deterministic)
        return x

def stream_castable_path(path) -> bool:
    """Whether the param leaf at ``path`` may be cast to the compute
    dtype BEFORE the ZeRO-3 gather without changing numerics: the
    attn/mlp matmul weights and biases — their modules consume them
    through ``.astype(compute_dtype)`` at use (ops/attention.py,
    ops/ffn.py), so an earlier cast is bitwise-neutral. Excluded: norm
    scales/biases and layerscale gammas (consumed in ``reduce_dtype``)
    and the MoE router (fp32 routing logits by design). Shared by the
    in-model stream wrapper and the explicit schedule twin
    (models/streaming.py), so the two programs cast the same leaf set."""
    keys = {str(getattr(k, "key", getattr(k, "idx", k))) for k in path}
    return bool({"attn", "mlp"} & keys) and "router" not in keys


def stream_bucket_leaves(stack_params):
    """The streamable leaves of a stacked [L, ...] block-param tree, as
    ordered ``(path, leaf)`` pairs — the exact ``stream_castable_path``
    set the ZeRO-3 bf16 stream gathers per block. The bucketed forward
    gather twin (models/streaming.py ``pack_stream_buckets``) coalesces
    this set into block-group buckets; keeping the selection rule here,
    next to the in-model stream wrapper, guarantees the two programs
    stream the same leaf set."""
    import jax.tree_util as jtu

    return [
        (path, leaf)
        for path, leaf in jtu.tree_flatten_with_path(stack_params)[0]
        if hasattr(leaf, "dtype") and stream_castable_path(path)
    ]


def _zero3_stream_trans_in(stream_dtype, constrain: bool = True,
                           lowp_kernels: bool = False):
    """``nn.map_variables`` trans_in_fn for the ZeRO-3 weight stream.

    Materializes ONE block's sharded weights for compute, inside the
    block stack (so under ``nn.scan`` the all-gather sits inside the
    compiled while body, per iteration — the weight stream), under the
    ``zero3_stream`` named scope the collective census attributes. The
    matmul weights (attn/mlp leaves; the modules consume them through
    ``.astype(compute_dtype)`` anyway, so this is bitwise-neutral) are
    cast to ``stream_dtype`` BEFORE the gather — the bf16 stream, half
    the gathered bytes of the fp32 masters. fp32-consumed leaves (norm
    scales/biases, layerscale gammas, the MoE router) gather in their
    storage dtype. ``stream_dtype=None`` disables the pre-cast (fp8:
    the quantizer must see the original fp32 weights).

    ``constrain=False`` applies only the cast (no materialization) —
    kept for callers that want the stream dtype without forcing a
    placement.

    ``lowp_kernels=True`` (a fp8/int8 ``train.low_precision`` arm): the
    castable matmul KERNELS (``lowp_kernel_path``, ops/lowp.py) get the
    cast + the master-placement pin but NOT the replicated constraint —
    they stay sharded, and the quantized-matmul ``custom_vjp``
    (``lowp_matmul``) gathers their 1-byte codes under the same
    ``zero3_stream`` scope instead. Biases keep the full bf16 stream.

    No-op (constraint-wise) without an active mesh, so the wrapped block
    stays usable in unsharded tests/eval.
    """
    import jax
    import jax.tree_util as jtu

    def trans(variables):
        from dinov3_tpu.parallel.context import get_current_mesh
        from dinov3_tpu.parallel.sharding import constrain_replicated

        mesh = get_current_mesh()

        def leaf(path, p):
            if not hasattr(p, "dtype"):
                return p
            if (stream_dtype is not None
                    and stream_castable_path(path)
                    and jnp.issubdtype(p.dtype, jnp.floating)
                    and p.dtype != stream_dtype):
                # the cast output inherits the master's (sharded)
                # placement by propagation, so the replicated constraint
                # below puts the all-gather AFTER the convert: the
                # gather moves the bf16 stream, not fp32 master bytes,
                # and carries this scope in its op_name
                p = p.astype(stream_dtype)
            if lowp_kernels:
                from dinov3_tpu.ops.lowp import lowp_kernel_path

                if lowp_kernel_path(path):
                    # quantized arm: leave the kernel SHARDED — the
                    # lowp_matmul custom_vjp gathers its int8/fp8 codes
                    return p
            if not constrain:
                return p
            return constrain_replicated(p, mesh) if mesh is not None else p

        with jax.named_scope("zero3_stream"):
            return jtu.tree_map_with_path(leaf, variables)

    return trans


def remat_block_cls(remat: str, zero3_stream: bool = False,
                    stream_dtype=None, stream_init: bool = False,
                    lowp_arm: str = "bf16"):
    """SelfAttentionBlock, optionally wrapped for rematerialization and
    the ZeRO-3 weight stream.

    Remat modes: "none"; "attn" (save everything except the named fp32
    softmax state — recomputed in backward, big HBM saving at long N);
    "blocks" (save only weight matmuls); "full" (save nothing).

    "attn" only has an effect on the dense XLA attention path — the pallas
    flash kernel and ring attention never materialize the [N, N] probs in
    the first place (models/__init__.py warns on that combination).

    ``zero3_stream``: wrap the block in ``nn.map_variables`` so its
    (sharded) weights are materialized at use under the ``zero3_stream``
    scope (``_zero3_stream_trans_in``). The map sits INSIDE the remat
    wrapper, so under remat the gathered weights are never saved as
    residuals — the backward re-gathers them (the FSDP discipline:
    gather twice, store 1/dp). ``stream_init`` must be the module's
    ``is_initializing()``: during init the wrapper is NOT installed —
    flax's ``map_variables(init=True)`` stores the *transformed*
    variables, which would silently round the fp32 masters through the
    bf16 stream cast at birth (caught by the bitwise equivalence spike);
    the raw block creates the identical param tree, so init and apply
    stay structurally interchangeable."""
    import jax

    if remat not in ("none", "attn", "blocks", "full"):
        raise ValueError(
            f"unknown remat mode {remat!r}; expected none|attn|blocks|full"
        )
    base = SelfAttentionBlock
    if zero3_stream and not stream_init:
        base = nn.map_variables(
            SelfAttentionBlock, "params",
            trans_in_fn=_zero3_stream_trans_in(
                stream_dtype, lowp_kernels=(lowp_arm != "bf16")),
        )
    if remat == "attn":
        return nn.remat(
            base,
            static_argnums=(3,),
            policy=jax.checkpoint_policies.save_anything_except_these_names(
                "attn_probs"
            ),
        )
    if remat in ("blocks", "full"):
        return nn.remat(
            base,
            static_argnums=(3,),
            policy=(None if remat == "full"
                    else jax.checkpoint_policies.dots_with_no_batch_dims_saveable),
        )
    return base


class ScanBlockAdapter(nn.Module):
    """(carry, ys) scan contract for SelfAttentionBlock, shared by the
    scan-over-blocks model path (models/vision_transformer.py) and the
    pipeline stages (dinov3_tpu/parallel/pipeline.py).

    ``dp_plan`` is this layer's slice of the step-wide RNG plan (scanned
    with ``in_axes=0`` over the stacked [L, ...] plan arrays) or None on
    the legacy rng path / pipeline stages.

    ``zero3_stream``/``stream_dtype``: the ZeRO-3 weight stream
    (``remat_block_cls``) — this layer's sharded weight slice is
    materialized inside the scan body."""

    block_kwargs: dict
    remat: str = "none"
    zero3_stream: bool = False
    stream_dtype: Any = None

    @nn.compact
    def __call__(self, x, dp_plan, rope, deterministic: bool, seg=None):
        x = remat_block_cls(
            self.remat, self.zero3_stream, self.stream_dtype,
            stream_init=self.is_initializing(),
            lowp_arm=self.block_kwargs.get("lowp_arm", "bf16"),
        )(
            **self.block_kwargs, name="block"
        )(x, rope, deterministic, dp_plan, seg)
        return x, None


class CausalSelfAttentionBlock(SelfAttentionBlock):
    """Pre-norm block with causal attention (reference:
    dinov3_jax/layers/block.py CausalSelfAttentionBlock — unused by the ViT
    path, kept for parity)."""

    causal: bool = True
