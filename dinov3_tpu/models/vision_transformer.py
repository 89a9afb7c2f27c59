"""DINOv3 Vision Transformer, TPU-first.

Capabilities match the reference model
(dinov3_jax/models/vision_transformer.py:56-408): patch embed -> [CLS +
storage/register tokens + patches] -> N RoPE-attention blocks -> norm(s),
with masked-token replacement, untied CLS/patch and global/local-CLS norms,
intermediate-layer extraction, and the vit_small..vit_7b size ladder.

Redesigned rather than ported:
- crops are *batched per resolution* ([n_crops*B, H, W, 3]) instead of
  python lists of arrays, so one jitted forward per resolution serves any
  number of crops (the reference's list-forward could not jit across shapes,
  SURVEY.md §7.3);
- one RoPE table per forward, shared by all blocks (the reference recomputed
  it per block per crop, reference:212-217);
- optional ``nn.scan`` over the layer stack for O(1) compile time at depth
  40, and ``nn.remat`` for activation rematerialization;
- stochastic depth keeps the reference's batch-subset semantics (dropped
  samples skip branch compute) via a static keep count — see
  ops/drop_path.py; a per-sample mask variant remains as
  ``drop_path_mode="mask"``.
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from dinov3_tpu.ops.block import (
    ScanBlockAdapter,
    SelfAttentionBlock,
    remat_block_cls,
)
from dinov3_tpu.ops.common import canonical_dtype, part
from dinov3_tpu.ops.norms import make_norm_layer
from dinov3_tpu.ops.patch_embed import PatchEmbed
from dinov3_tpu.ops.rope import (
    rope_periods,
    rope_sincos,
    rope_with_identity_prefix,
)


class _CollectScanBlock(nn.Module):
    """Scan adapter that also fills a [K, B, N, D] buffer with the outputs
    of the requested layers (carry = (x, buffer); ``i`` is the layer index
    scanned over). Only K requested layers are kept — stacking all L
    outputs as scan ys would cost L/K more activation memory at eval time.
    Param path matches ScanBlockAdapter ("blocks"/"block"), so the same
    trained params serve both applies. ``dp_plan`` as in
    ScanBlockAdapter (None on the collect path, which is eval-only)."""

    block_kwargs: dict
    collect_idx: tuple  # static, sorted
    remat: str = "none"
    zero3_stream: bool = False
    stream_dtype: Any = None

    @nn.compact
    def __call__(self, carry, i, dp_plan, rope, deterministic: bool):
        x, buf = carry
        x = remat_block_cls(
            self.remat, self.zero3_stream, self.stream_dtype,
            stream_init=self.is_initializing(),
            lowp_arm=self.block_kwargs.get("lowp_arm", "bf16"),
        )(
            **self.block_kwargs, name="block"
        )(x, rope, deterministic, dp_plan)
        hit = (jnp.asarray(self.collect_idx) == i)[:, None, None, None]
        buf = jnp.where(hit, x[None].astype(buf.dtype), buf)
        return (x, buf), None


class DinoVisionTransformer(nn.Module):
    patch_size: int = 16
    in_chans: int = 3
    embed_dim: int = 768
    n_blocks: int = 12
    num_heads: int = 12
    ffn_ratio: float = 4.0
    qkv_bias: bool = True
    proj_bias: bool = True
    ffn_bias: bool = True
    drop_path_rate: float = 0.0
    drop_path_mode: str = "subset"  # subset (reference semantics) | mask
    layerscale_init: float | None = None
    norm_layer: str = "layernorm"
    ffn_layer: str = "mlp"
    n_storage_tokens: int = 0
    mask_k_bias: bool = False
    untie_cls_and_patch_norms: bool = False
    untie_global_and_local_cls_norm: bool = False
    # RoPE
    pos_embed_type: str = "rope"
    pos_embed_rope_base: float | None = 100.0
    pos_embed_rope_min_period: float | None = None
    pos_embed_rope_max_period: float | None = None
    pos_embed_rope_normalize_coords: str = "separate"
    pos_embed_rope_shift_coords: float | None = None
    pos_embed_rope_jitter_coords: float | None = None
    pos_embed_rope_rescale_coords: float | None = None
    pos_embed_rope_dtype: str = "fp32"
    # execution
    attn_impl: str = "auto"
    flash_block_q: int = 512   # kernels.flash_block_q/kv caps
    flash_block_kv: int = 512
    flash_min_seq: int = 0     # kernels.flash_min_seq; 0 = ops default
    seq_parallel: bool = False
    scan_layers: bool = False
    pipeline_stages: int = 1       # >1: GPipe pipeline over the pipe axis
    pipeline_microbatches: int = 0  # 0 = pipeline_stages
    fp8: bool = False              # fp8 projections inside blocks
    moe_num_experts: int = 8       # only used when ffn_layer == "moe"
    moe_top_k: int = 2
    # ZeRO-3 per-block weight stream (ops/block.py remat_block_cls):
    # materialize each block's sharded weights inside the block stack —
    # under nn.scan the all-gather sits inside the compiled while body,
    # matmul weights cast to compute dtype BEFORE the gather. Set from
    # parallel.zero3 by build_backbone (models/__init__.py); inert
    # without a sharded mesh.
    zero3_stream: bool = False
    # train.low_precision.arm: fp8/int8 delayed-scaling block matmuls
    # (ops/lowp.py); scales arrive as the read-only "lowp" variable
    # collection and the bf16 arm is today's bitwise-unchanged path
    lowp_arm: str = "bf16"
    remat: str = "none"  # none | blocks | full
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    reduce_dtype: Any = jnp.float32
    probs_dtype: Any = None  # attention-probability storage (None = fp32)

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    # ---------------- token preparation ----------------

    def _token_embedder(self):
        """Create the patch-embed module + token params ONCE per apply —
        the packed forward embeds the global and local crops with the
        same instances (a second creation would collide on names)."""
        patch_embed = PatchEmbed(
            embed_dim=self.embed_dim, patch_size=self.patch_size,
            in_chans=self.in_chans, dtype=self.dtype,
            param_dtype=self.param_dtype, name="patch_embed",
        )
        mask_token = self.param(
            "mask_token", part(nn.initializers.zeros, ("embed",)),
            (self.embed_dim,), self.param_dtype,
        )
        cls_token = self.param(
            "cls_token", part(nn.initializers.normal(0.02), (None, None, "embed")),
            (1, 1, self.embed_dim), self.param_dtype,
        )
        storage = None
        if self.n_storage_tokens > 0:
            storage = self.param(
                "storage_tokens",
                part(nn.initializers.normal(0.02), (None, None, "embed")),
                (1, self.n_storage_tokens, self.embed_dim), self.param_dtype,
            )
        return patch_embed, mask_token, cls_token, storage

    def _embed_tokens(self, embedder, x, masks):
        """[B, H, W, C] -> ([B, 1+S+T, D], (h, w)). masks: [B, T] bool."""
        patch_embed, mask_token, cls_token, storage = embedder
        B = x.shape[0]
        h, w = x.shape[1] // self.patch_size, x.shape[2] // self.patch_size
        tokens = patch_embed(x)
        if masks is not None:
            tokens = jnp.where(
                masks[..., None], mask_token.astype(tokens.dtype), tokens
            )
        parts = [jnp.broadcast_to(cls_token.astype(tokens.dtype),
                                  (B, 1, self.embed_dim))]
        if storage is not None:
            parts.append(jnp.broadcast_to(storage.astype(tokens.dtype),
                                          (B, self.n_storage_tokens, self.embed_dim)))
        parts.append(tokens)
        return jnp.concatenate(parts, axis=1), (h, w)

    def _prepare_tokens(self, x, masks):
        return self._embed_tokens(self._token_embedder(), x, masks)

    def _rope_table(self, h: int, w: int, deterministic: bool,
                    aug: dict | None = None):
        if self.pos_embed_type != "rope":
            return None
        periods = rope_periods(
            self.head_dim,
            base=self.pos_embed_rope_base,
            min_period=self.pos_embed_rope_min_period,
            max_period=self.pos_embed_rope_max_period,
        )
        rng = None
        augmenting = any(
            a is not None for a in (
                self.pos_embed_rope_shift_coords,
                self.pos_embed_rope_jitter_coords,
                self.pos_embed_rope_rescale_coords,
            )
        )
        if not deterministic and augmenting and aug is None:
            rng = self.make_rng("rope")
        sin, cos = rope_sincos(
            h, w, periods,
            normalize=self.pos_embed_rope_normalize_coords,
            rng=rng,
            shift=self.pos_embed_rope_shift_coords,
            jitter=self.pos_embed_rope_jitter_coords,
            rescale=self.pos_embed_rope_rescale_coords,
            dtype=canonical_dtype(self.pos_embed_rope_dtype),
            aug=aug if not deterministic else None,
        )
        # full-length table (identity rows for CLS/storage tokens): the
        # per-block apply becomes one fused fma, no token slice/concat
        return rope_with_identity_prefix(sin, cos, 1 + self.n_storage_tokens)

    # ---------------- layer stack ----------------

    def _block_kwargs(self):
        return dict(
            dim=self.embed_dim, num_heads=self.num_heads,
            ffn_ratio=self.ffn_ratio, ffn_layer=self.ffn_layer,
            norm_layer=self.norm_layer, qkv_bias=self.qkv_bias,
            proj_bias=self.proj_bias, ffn_bias=self.ffn_bias,
            drop_path_rate=self.drop_path_rate,
            drop_path_mode=self.drop_path_mode,
            layerscale_init=self.layerscale_init,
            mask_k_bias=self.mask_k_bias, attn_impl=self.attn_impl,
            flash_block_q=self.flash_block_q,
            flash_block_kv=self.flash_block_kv,
            flash_min_seq=self.flash_min_seq,
            seq_parallel=self.seq_parallel, fp8=self.fp8,
            lowp_arm=self.lowp_arm,
            moe_num_experts=self.moe_num_experts, moe_top_k=self.moe_top_k,
            dtype=self.dtype, param_dtype=self.param_dtype,
            reduce_dtype=self.reduce_dtype, probs_dtype=self.probs_dtype,
        )

    def _run_blocks(self, x, rope, deterministic, collect: Sequence[int] = (),
                    plan: dict | None = None, seg=None):
        """Run the stack; optionally collect outputs of the listed layers.

        Every path composes with every other feature: MoE aux losses ride
        the "losses" collection through scan/vmap (``variable_axes``), and
        the pipeline collects intermediate layers through per-stage
        buffers (parallel/pipeline.py).

        ``plan``: the pass's stacked drop-path plan ({"idx": [L, 2, keep]}
        or {"keep": [L, 2, B]}, rng/plan.py). The scanned stack consumes
        it as per-layer scan inputs (``in_axes=0`` — a dynamic-slice of
        the carried stack, not a folded key); the unrolled stack as
        static slices. The pipeline path keeps the legacy per-stage rng
        threading (the meta-arch never hands it a plan).

        ``seg``: [B, N] segment ids of the crop-packed batch — broadcast
        to every block like rope (not supported on the pipeline path;
        the meta arch falls back to two passes there)."""
        collected = {}
        # ZeRO-3 stream: bf16 pre-cast for the matmul weights, unless
        # fp8 owns the cast point (the quantizer reads the fp32 masters)
        stream_dtype = None if self.fp8 else self.dtype
        if self.pipeline_stages > 1:
            from dinov3_tpu.parallel.pipeline import PipelinedBlocks

            if self.lowp_arm != "bf16":
                raise ValueError(
                    "train.low_precision is not supported under pipeline "
                    "parallelism (per-stage scale plumbing is not wired); "
                    "set train.low_precision.arm=bf16")
            if seg is not None:
                raise ValueError(
                    "crop packing is not supported under pipeline "
                    "parallelism (the meta arch falls back to the "
                    "two-pass student forward there)")
            x, collected = PipelinedBlocks(
                block_kwargs=self._block_kwargs(),
                n_blocks=self.n_blocks,
                n_stages=self.pipeline_stages,
                n_microbatches=self.pipeline_microbatches,
                remat=self.remat,
                name="pipeline",
            )(x, rope, deterministic, collect=tuple(sorted(collect)))
        elif self.scan_layers and not collect:
            scanned = nn.scan(
                ScanBlockAdapter,
                # "lowp": per-layer delayed scales ([L] per kernel) ride
                # the scan like the stacked params — each iteration sees
                # its own layer's scalar scale (ops/lowp.py)
                variable_axes={"params": 0, "losses": 0, "lowp": 0},
                split_rngs={"params": True, "drop_path": True, "dropout": True},
                in_axes=(0 if plan is not None else nn.broadcast,
                         nn.broadcast, nn.broadcast, nn.broadcast),
                length=self.n_blocks,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block_kwargs=self._block_kwargs(), remat=self.remat,
              zero3_stream=self.zero3_stream, stream_dtype=stream_dtype,
              name="blocks")
            x, _ = scanned(x, plan, rope, deterministic, seg)
        elif self.scan_layers:
            take = tuple(sorted(collect))
            scanned = nn.scan(
                _CollectScanBlock,
                variable_axes={"params": 0, "losses": 0, "lowp": 0},
                split_rngs={"params": True, "drop_path": True, "dropout": True},
                in_axes=(0, 0 if plan is not None else nn.broadcast,
                         nn.broadcast, nn.broadcast),
                length=self.n_blocks,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block_kwargs=self._block_kwargs(), collect_idx=take,
              remat=self.remat, zero3_stream=self.zero3_stream,
              stream_dtype=stream_dtype, name="blocks")
            buf0 = jnp.zeros((len(take),) + x.shape, x.dtype)
            (x, buf), _ = scanned(
                (x, buf0), jnp.arange(self.n_blocks), plan, rope,
                deterministic
            )
            collected = {i: buf[k] for k, i in enumerate(take)}
        else:
            from dinov3_tpu.rng.plan import plan_layer_slice

            for i in range(self.n_blocks):
                x = remat_block_cls(
                    self.remat, self.zero3_stream, stream_dtype,
                    stream_init=self.is_initializing(),
                    lowp_arm=self.lowp_arm,
                )(
                    **self._block_kwargs(), name=f"blocks_{i}"
                )(x, rope, deterministic, plan_layer_slice(plan, i), seg)
                if i in collect:
                    collected[i] = x
        return x, collected

    # ---------------- heads/norms ----------------

    def _make_norms(self):
        """Create final-norm modules once; during init, touch the untied ones
        on a dummy so their params exist for later train-mode applies."""
        norm_kw = dict(param_dtype=self.param_dtype, reduce_dtype=self.reduce_dtype)
        norms = {"norm": make_norm_layer(self.norm_layer, name="norm", **norm_kw)}
        if self.untie_cls_and_patch_norms:
            norms["cls_norm"] = make_norm_layer(
                self.norm_layer, name="cls_norm", **norm_kw
            )
        if self.untie_global_and_local_cls_norm:
            norms["local_cls_norm"] = make_norm_layer(
                self.norm_layer, name="local_cls_norm", **norm_kw
            )
        if self.is_initializing():
            dummy = jnp.zeros((1, 1, self.embed_dim), self.dtype)
            for n in norms.values():
                n(dummy)
        return norms

    def _final_norms(self, x, norms, *, crop_kind: str, deterministic: bool):
        n_prefix = 1 + self.n_storage_tokens
        norm = norms["norm"]
        if self.untie_cls_and_patch_norms or self.untie_global_and_local_cls_norm:
            if (
                self.untie_global_and_local_cls_norm
                and not deterministic
                and crop_kind == "local"
            ):
                cls_norm = norms["local_cls_norm"]
            elif self.untie_cls_and_patch_norms:
                cls_norm = norms["cls_norm"]
            else:
                cls_norm = norm
            x_cls_reg = cls_norm(x[:, :n_prefix])
            x_patch = norm(x[:, n_prefix:])
        else:
            xn = norm(x)
            x_cls_reg, x_patch = xn[:, :n_prefix], xn[:, n_prefix:]
        return x_cls_reg, x_patch

    # ---------------- public API ----------------

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        masks: jnp.ndarray | None = None,
        *,
        crop_kind: str = "global",
        deterministic: bool = True,
        rng_plan: dict | None = None,
        local_crops: jnp.ndarray | None = None,
    ) -> dict:
        """Forward a batch of same-resolution crops.

        x: [B, H, W, C]; masks: optional [B, T] bool (T = H*W/p^2).
        ``rng_plan``: this pass's precomputed randomness
        ({"drop_path": ..., "rope": ...}, rng/plan.py) — when given, the
        forward consumes plan slices and never calls ``make_rng``.
        Returns the reference's feature dict (vision_transformer.py:236-243):
        x_norm_clstoken [B, D], x_storage_tokens [B, S, D],
        x_norm_patchtokens [B, T, D], x_prenorm, masks.

        ``local_crops``: optional [n_l*B, h, w, C] — the crop-packed
        single-pass engine (ops/packing.py, model.crop_packing): local
        sequences are packed k-per-row into global-length rows and run
        through ONE block stack with the globals, under segment-masked
        attention and per-segment RoPE. The returned dict then also
        carries "local_cls" [n_l*B, D] (and "local_storage_tokens");
        ``rng_plan["rope"]`` is the nested {"global": ..., "local": ...}
        per-table form there.
        """
        rng_plan = rng_plan or {}
        norms = self._make_norms()
        if local_crops is not None:
            return self._packed_forward(
                x, masks, local_crops, norms, deterministic, rng_plan)
        tokens, (h, w) = self._prepare_tokens(x, masks)
        rope = self._rope_table(h, w, deterministic,
                                aug=rng_plan.get("rope"))
        out, _ = self._run_blocks(tokens, rope, deterministic,
                                  plan=rng_plan.get("drop_path"))
        x_cls_reg, x_patch = self._final_norms(
            out, norms, crop_kind=crop_kind, deterministic=deterministic
        )
        return {
            "x_norm_clstoken": x_cls_reg[:, 0],
            "x_storage_tokens": x_cls_reg[:, 1:],
            "x_norm_patchtokens": x_patch,
            "x_prenorm": out,
            "masks": masks,
        }

    def _packed_forward(self, x, masks, local_crops, norms, deterministic,
                        rng_plan):
        """Crop-packed single-pass student forward (ops/packing.py).

        One block scan over [2B + P, N_g] rows — the ViT-L weight stack
        streams from HBM once per direction instead of twice, and the
        ~37-token local rows disappear into well-tiled global-length
        rows (the ISSUE-4 engine; oracle = the two-pass path behind
        ``model.crop_packing=false``). Per-token math is identical to
        the two-pass oracle: packing only changes which rows share an
        attention call, and segments are attention-isolated, so
        packed-vs-oracle equivalence holds to float reassociation
        (pinned in tests/test_crop_packing.py).
        """
        from dinov3_tpu.ops.packing import (
            assemble_packed_batch,
            make_packed_layout,
            pack_local_rows,
            packed_segment_ids,
            split_packed_output,
        )
        from dinov3_tpu.parallel.sharding import (
            constrain_packed_rows,
            packed_row_groups,
        )

        embedder = self._token_embedder()
        g_tokens, (hg, wg) = self._embed_tokens(embedder, x, masks)
        l_tokens, (hl, wl) = self._embed_tokens(embedder, local_crops, None)
        n_prefix = 1 + self.n_storage_tokens
        layout = make_packed_layout(
            n_global_rows=g_tokens.shape[0], n_local=l_tokens.shape[0],
            seq_global=g_tokens.shape[1], seq_local=l_tokens.shape[1],
            n_prefix=n_prefix, groups=packed_row_groups(),
        )
        if layout.k < 2:
            raise ValueError(
                f"crop packing needs k >= 2 local sequences per global "
                f"row (N_g={layout.seq_global}, N_l={layout.seq_local}); "
                "the meta arch guards this and falls back to two passes")
        with jax.named_scope("crop_pack"):
            packed = pack_local_rows(l_tokens, layout)
            tokens = constrain_packed_rows(
                assemble_packed_batch(g_tokens, packed, layout))
        seg = jnp.asarray(packed_segment_ids(layout))
        rope = self._packed_rope(layout, (hg, wg), (hl, wl), deterministic,
                                 rng_plan.get("rope"))
        out, _ = self._run_blocks(tokens, rope, deterministic,
                                  plan=rng_plan.get("drop_path"), seg=seg)
        with jax.named_scope("crop_unpack"):
            g_rows, p_rows = split_packed_output(out, layout)
            l_tok = p_rows[:, : layout.k * layout.seq_local, :]
            l_prefix = l_tok.reshape(
                layout.n_packed_rows * layout.k, layout.seq_local, -1
            )[: layout.n_local, :n_prefix]
        x_cls_reg, x_patch = self._final_norms(
            g_rows, norms, crop_kind="global", deterministic=deterministic
        )
        # the local-CLS norm choice _final_norms would make for
        # crop_kind="local" (norms are per-token, so norm-after-extract
        # == the oracle's extract-after-norm)
        if self.untie_global_and_local_cls_norm and not deterministic:
            local_norm = norms["local_cls_norm"]
        elif self.untie_cls_and_patch_norms:
            local_norm = norms["cls_norm"]
        else:
            local_norm = norms["norm"]
        l_cls_reg = local_norm(l_prefix)
        return {
            "x_norm_clstoken": x_cls_reg[:, 0],
            "x_storage_tokens": x_cls_reg[:, 1:],
            "x_norm_patchtokens": x_patch,
            "x_prenorm": out,
            "masks": masks,
            "local_cls": l_cls_reg[:, 0],
            "local_storage_tokens": l_cls_reg[:, 1:],
        }

    def _packed_rope(self, layout, global_hw, local_hw, deterministic,
                     rope_plan):
        """Per-row (sin, cos) tables for the packed batch, or None.

        ``rope_plan``: the packed pass's nested aug-factor dict
        ({"global": ..., "local": ...}, rng/plan.py) — each sub-table
        consumes its own lane, bitwise-identical to the factors the
        two-pass oracle's global/local passes would consume. On the
        legacy rng path each ``_rope_table`` call draws its own
        ``make_rng`` fold, mirroring the oracle's two per-pass draws.
        """
        if self.pos_embed_type != "rope":
            return None
        rope_plan = rope_plan or {}
        from dinov3_tpu.ops.rope import rope_packed_rows

        g_table = self._rope_table(*global_hw, deterministic,
                                   aug=rope_plan.get("global"))
        l_table = self._rope_table(*local_hw, deterministic,
                                   aug=rope_plan.get("local"))
        return rope_packed_rows(g_table, l_table, layout)

    @nn.compact
    def packed_feature_forward(self, patches, coords, prefix_idx, seg):
        """Serving-time forward over host-packed multi-image planes.

        The continuous-packing serve engine (serve/engine.py) admits
        variable-resolution images into fixed token-budget rows on the
        host; this method is the ONE fixed-shape device program those
        rows run through — deterministic (no student rng plan, no
        drop-path, no RoPE augmentation), segment-masked like the
        crop-packed trainer (``_packed_forward``), with per-TOKEN RoPE
        computed in-program from a host coordinate plane because packed
        segments carry arbitrary (h, w) patch grids rather than the
        trainer's two static crop resolutions.

        patches: [R, N, p, p, C] host-patchified pixels (zeros at
          prefix/pad slots) — each [p, p, C] patch keeps PatchEmbed's
          row-major inner layout, so embedding them as R*N single-patch
          images through the SAME PatchEmbed module reproduces the
          full-image unfold+matmul bitwise (ops/patch_embed.py).
        coords: [R, N, 2] f32 patch-center coordinates in [-1, 1]
          (ops/rope.py patch_coords math per segment); zeros at
          prefix/pad slots — angle 0 is sin 0 / cos 1, the identity
          rotation ``rope_with_identity_prefix`` gives prefix tokens.
        prefix_idx: [R, N] int32 — 0 = the slot holds the CLS token,
          s in [1, S] = storage token s-1, -1 = patch or pad slot.
        seg: [R, N] int32 segment ids, -1 = pad (ops/packing.py
          conventions: pads attend only among themselves).

        Returns {"cls_rows": [R, N, D], "patch_rows": [R, N, D]} — the
        block-stack output normed with the CLS norm and the patch norm
        respectively (the ``_final_norms`` crop_kind="global"
        deterministic selection; norms are per-token, so norming the
        full plane and extracting per segment afterwards equals the
        oracle's extract-then-norm). Per-segment CLS/pooled-patch
        extraction happens engine-side (serve_extract named scope).
        """
        patch_embed, _, cls_token, storage = self._token_embedder()
        norms = self._make_norms()
        R, N = seg.shape
        p, C = self.patch_size, self.in_chans
        with jax.named_scope("serve_pack"):
            tok = patch_embed(patches.reshape(R * N, p, p, C))
            tok = tok.reshape(R, N, self.embed_dim)
            # zero the pad slots (PatchEmbed of a zero patch is the
            # bias vector, not zero) and inject the prefix params
            is_prefix = prefix_idx >= 0
            tok = jnp.where((seg >= 0)[..., None] & ~is_prefix[..., None],
                            tok, jnp.zeros((), tok.dtype))
            table = cls_token[0]
            if storage is not None:
                table = jnp.concatenate([table, storage[0]], axis=0)
            pre = jnp.take(table.astype(tok.dtype),
                           jnp.clip(prefix_idx, 0, table.shape[0] - 1),
                           axis=0)
            tok = jnp.where(is_prefix[..., None], pre, tok)
        rope = self._serve_rope(coords)
        out, _ = self._run_blocks(tok, rope, True, seg=seg)
        cls_norm = (norms["cls_norm"] if self.untie_cls_and_patch_norms
                    else norms["norm"])
        return {"cls_rows": cls_norm(out), "patch_rows": norms["norm"](out)}

    def _serve_rope(self, coords):
        """Per-token (sin, cos) tables ([R, N, head_dim] x2) from a host
        coordinate plane — the same angle math as ``rope_sincos``
        (elementwise over the same f32 values, so real patch
        coordinates reproduce the oracle's table bitwise and zero
        coordinates reproduce the identity prefix rows bitwise),
        consumed by ``rope_apply_full``'s 3-D per-row path."""
        if self.pos_embed_type != "rope":
            return None
        import math

        periods = rope_periods(
            self.head_dim,
            base=self.pos_embed_rope_base,
            min_period=self.pos_embed_rope_min_period,
            max_period=self.pos_embed_rope_max_period,
        )
        angles = (2.0 * math.pi * coords[..., None]
                  / periods[None, None, None, :])
        angles = angles.reshape(*coords.shape[:2], -1)
        angles = jnp.concatenate([angles, angles], axis=-1)
        dtype = canonical_dtype(self.pos_embed_rope_dtype)
        return jnp.sin(angles).astype(dtype), jnp.cos(angles).astype(dtype)

    @nn.compact
    def get_intermediate_layers(
        self,
        x: jnp.ndarray,
        n: int | Sequence[int] = 1,
        *,
        reshape: bool = False,
        return_class_token: bool = False,
        return_extra_tokens: bool = False,
        norm: bool = True,
    ):
        """Eval-time feature extraction (reference:280-312, with its reshape
        and index typos fixed). Works on every block-stack layout,
        including the pipelined one (stage-owned collect buffers,
        parallel/pipeline.py)."""
        tokens, (h, w) = self._prepare_tokens(x, None)
        rope = self._rope_table(h, w, True)
        take = (
            list(range(self.n_blocks - n, self.n_blocks))
            if isinstance(n, int) else list(n)
        )
        bad = [i for i in take if not 0 <= i < self.n_blocks]
        if bad:
            raise ValueError(
                f"layer indices {bad} out of range for {self.n_blocks} "
                "blocks"
            )
        _, collected = self._run_blocks(tokens, rope, True, collect=take)
        outputs = [collected[i] for i in take]
        n_prefix = 1 + self.n_storage_tokens
        if norm:
            normed = []
            norm_kw = dict(param_dtype=self.param_dtype, reduce_dtype=self.reduce_dtype)
            norm_l = make_norm_layer(self.norm_layer, name="norm", **norm_kw)
            cls_l = (
                make_norm_layer(self.norm_layer, name="cls_norm", **norm_kw)
                if self.untie_cls_and_patch_norms else None
            )
            for out in outputs:
                if cls_l is not None:
                    normed.append(jnp.concatenate(
                        [cls_l(out[:, :n_prefix]), norm_l(out[:, n_prefix:])], axis=1
                    ))
                else:
                    normed.append(norm_l(out))
            outputs = normed
        class_tokens = [o[:, 0] for o in outputs]
        extra = [o[:, 1:n_prefix] for o in outputs]
        patches = [o[:, n_prefix:] for o in outputs]
        if reshape:
            B = x.shape[0]
            patches = [
                p.reshape(B, h, w, -1).transpose(0, 3, 1, 2) for p in patches
            ]
        if not return_class_token and not return_extra_tokens:
            return tuple(patches)
        if return_class_token and not return_extra_tokens:
            return tuple(zip(patches, class_tokens))
        if return_extra_tokens and not return_class_token:
            return tuple(zip(patches, extra))
        return tuple(zip(patches, class_tokens, extra))


# ---------------- size ladder (reference:325-408) ----------------

def _ctor(embed_dim, n_blocks, num_heads, ffn_ratio):
    def build(patch_size: int = 16, **kwargs) -> DinoVisionTransformer:
        if kwargs.get("ffn_ratio") is None:  # None defers to the ladder ratio
            kwargs.pop("ffn_ratio", None)
        args = dict(
            patch_size=patch_size, embed_dim=embed_dim, n_blocks=n_blocks,
            num_heads=num_heads, ffn_ratio=ffn_ratio,
        )
        args.update(kwargs)
        return DinoVisionTransformer(**args)

    return build


vit_small = _ctor(384, 12, 6, 4.0)
vit_base = _ctor(768, 12, 12, 4.0)
vit_large = _ctor(1024, 24, 16, 4.0)
vit_so400m = _ctor(1152, 27, 18, 3.777777778)
vit_huge2 = _ctor(1280, 32, 20, 4.0)
vit_giant2 = _ctor(1536, 40, 24, 4.0)
vit_7b = _ctor(4096, 40, 32, 3.0)
# tiny configs for tests/smoke runs (not in the reference ladder);
# vit_test_big is a distinct-width "teacher" for distillation tests,
# vit_test4 a 4-block stack for 4-stage pipeline validation,
# vit_test40 the 7B *shape* skeleton (40 blocks, ffn_ratio 3.0 — same
# depth/topology as vit_7b at test width) for stress dryruns
vit_test = _ctor(64, 2, 2, 2.0)
vit_test_big = _ctor(96, 3, 2, 2.0)
vit_test4 = _ctor(64, 4, 2, 2.0)
# same depth as vit_test4 at 2x width / 4 heads: the capacity axis of
# the loss-factorial ablations with depth held fixed
vit_test_wide = _ctor(128, 4, 4, 2.0)
vit_test40 = _ctor(64, 40, 2, 3.0)

ARCHS = {
    "vit_small": vit_small, "vit_base": vit_base, "vit_large": vit_large,
    "vit_so400m": vit_so400m, "vit_huge2": vit_huge2,
    "vit_giant2": vit_giant2, "vit_7b": vit_7b, "vit_test": vit_test,
    "vit_test_big": vit_test_big, "vit_test4": vit_test4,
    "vit_test_wide": vit_test_wide, "vit_test40": vit_test40,
}
