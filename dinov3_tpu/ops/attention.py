"""Multi-head self-attention with RoPE and pluggable kernels.

(reference: dinov3_jax/layers/attention.py — which used
``flax.linen.dot_product_attention`` with no fused kernel and a NaN-filled
"bias mask" for ``mask_k_bias``, SURVEY.md §2.9.)

TPU-first choices:
- one fused qkv matmul, head reshape after (single MXU call);
- softmax logits accumulate in ``reduce_dtype`` (fp32);
- ``mask_k_bias`` zeroes the k third of the qkv bias with a *constant* 0/1
  mask (softmax is shift-invariant in k-bias, so zeroing it is the intended
  semantic; the reference multiplied by NaNs);
- kernel dispatch: "pallas" selects the flash-attention kernel
  (dinov3_tpu/ops/flash_attention.py) on TPU, "xla" the unfused einsum
  path; "auto" picks per-backend.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from dinov3_tpu.ops.common import (
    constrain,
    fp8_matmul,
    part,
    trunc_normal_init,
)
from dinov3_tpu.ops.rope import rope_apply_full, rope_apply_with_prefix


import functools as _functools
import itertools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _softmax_lowp(logits, out_dtype):
    """Softmax with fp32 statistics but low-precision output AND residual.

    Autodiff of a plain ``softmax(logits).astype(bf16)`` saves the fp32
    probabilities for the backward — at ViT-L's 224px global crops that
    is a [16, 16, 201, 201] fp32 array per layer whose save/transpose
    copies are pure HBM traffic. Storing the residual in ``out_dtype``
    (bf16) halves that traffic; the backward (dL = p * (g - sum(g*p)))
    accumulates in fp32. Committed A/B on the fp32-master program:
    47.58 -> 48.07 img/s/chip (BENCH_r03_phases.jsonl, bf16 vs fp32
    probs storage); the r5 on-chip profile (PROFILE_r05.json) confirms
    the residual copies survive as the f32 `[11,16,201,201]` copy ops
    (~1% of step) — the bf16 residual is what keeps them there and not
    at 2x that.
    """
    return jax.nn.softmax(logits, axis=-1).astype(out_dtype)


def _softmax_lowp_fwd(logits, out_dtype):
    p = _softmax_lowp(logits, out_dtype)
    return p, p


def _softmax_lowp_bwd(out_dtype, p, g):
    pf = p.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    s = jnp.sum(gf * pf, axis=-1, keepdims=True)
    return (pf * (gf - s),)


_softmax_lowp.defvjp(_softmax_lowp_fwd, _softmax_lowp_bwd)


def xla_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    reduce_dtype=jnp.float32,
    causal: bool = False,
    probs_dtype=None,
    seg: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Unfused attention: [B, N, h, d] inputs, softmax in reduce_dtype.

    ``probs_dtype``: storage dtype of the probabilities (fp32 statistics
    either way). bf16 halves the [B, h, N, N] HBM traffic — the recipe
    default via ``compute_precision.probs_dtype`` — while ``None`` keeps
    full-precision residuals (module default; bitwise-stable tests).

    ``seg``: optional [B, N] int32 segment ids (crop packing,
    ops/packing.py): token q attends token k iff seg[b,q] == seg[b,k] —
    block-diagonal attention, so packed crops never see each other.
    Masked logits get a large finite negative (the flash kernel's
    NEG_INF convention): their exp underflows to exactly 0 after the
    row-max shift (every token matches itself, so the max is always a
    real logit), which keeps packed-vs-unpacked softmax sums bitwise
    clean and — unlike -inf — cannot produce NaN for any row."""
    d = q.shape[-1]
    scale = d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=reduce_dtype)
    logits = (logits * scale).astype(reduce_dtype)
    if seg is not None:
        same = seg[:, None, :, None] == seg[:, None, None, :]
        logits = jnp.where(same, logits, jnp.asarray(-1e30, logits.dtype))
    if causal:
        N = q.shape[1]
        row = jax.lax.broadcasted_iota(jnp.int32, (1, 1, N, N), 2)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, N, N), 3)
        logits = jnp.where(col <= row, logits, jnp.asarray(-jnp.inf, logits.dtype))
    if probs_dtype is not None and probs_dtype != logits.dtype:
        probs = _softmax_lowp(logits, probs_dtype)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    # named for the "attn" remat policy (ops/block.py remat_block_cls):
    # the [B, h, N, N] softmax state dominates saved activations at
    # long N; recomputing it in the backward trades cheap FLOPs for HBM
    from jax.ad_checkpoint import checkpoint_name

    probs = checkpoint_name(probs, "attn_probs")
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def causal_blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    block_q: int = 512,
    block_kv: int = 1024,
    reduce_dtype=jnp.float32,
    window: int | None = None,
    selection: jnp.ndarray | None = None,
) -> jnp.ndarray | tuple:
    """Causal attention block by block: [B, N, h, dqk] q, [B, N, hk, dqk]
    k, [B, N, hk, dv] v (the value width may differ from the q/k width:
    latent attention's 192 beside 128), statistics in reduce_dtype.

    Query head i reads key/value head ``i // (h // hk)`` (grouped heads;
    ``hk == h`` is plain multi-head attention).

    ``window``: token t sees the keys ``t - window < j <= t`` (``window``
    keys with its own); None: every key up to its own.

    ``selection``: [B, N, N] int8, 1 where query t keeps key s (the same
    keys for every head; no pair above the diagonal and at least one key
    a query): token t sees the keys it keeps and no others. It takes no
    gradient, and goes with no window. The call then returns a PAIR: the
    output and the rows' log-sum-exp over their kept keys, [B, h, N]
    float32, which the kernels have in hand (no gradient goes back
    through it; ``ops/sparse_index.py index_loss`` makes its target with
    it), or None where the plain tiles ran.

    A block of ``block_q`` queries meets the key tiles of its band and no
    others, ``block_kv`` keys at a time under a running maximum and sum
    (the online softmax), the diagonal's tile last. Two paths make the
    same tiles, and ``ops/causal_attention.py causal_attention_path``
    chooses between them from what the call can observe (backend, types,
    widths, the length against the blocks), no option or variable: the
    Pallas KERNELS ``causal_attn_fwd`` and ``causal_attn_bwd``, which keep
    a tile's float32 score plane in VMEM, on a TPU; ``causal_tiles``
    below, plain XLA, everywhere else."""
    h, hk = q.shape[2], k.shape[2]
    if h % hk or v.shape[2] != hk:
        raise ValueError(
            f"{h} query heads over {hk} key and {v.shape[2]} value heads")
    if window is not None and window < 1:
        raise ValueError(f"window {window}: at least the query's own key")
    if selection is not None and window is not None:
        raise ValueError("a selection goes with no window")
    from dinov3_tpu.ops import causal_attention as kernels

    if q.dtype == k.dtype == v.dtype and kernels.causal_attention_path(
            (q.shape, k.shape, v.shape), window, None, block_q, block_kv,
            q.dtype, reduce_dtype)[0] == "kernel":
        if selection is not None:
            return kernels.kernel_attention_selected(
                q, k, v, selection, q.shape[-1] ** -0.5, block_q, block_kv,
                False)
        return kernels.kernel_attention(
            q, k, v, q.shape[-1] ** -0.5, window, block_q, block_kv, False)
    if selection is not None:
        return causal_tiles(q, k, v, block_q, block_kv, reduce_dtype, window,
                            jax.lax.stop_gradient(selection)), None
    return causal_tiles(q, k, v, block_q, block_kv, reduce_dtype, window)


def causal_tiles(q, k, v, block_q, block_kv, reduce_dtype, window,
                 selection=None):
    """``causal_blockwise_attention``'s plain path.

    The ``h // hk`` query heads of a group go into the ROWS of the
    group's tiles, token-major: one ``[g * block_q, d] x [d, block_kv]``
    product a tile in place of g, and k and v are never written out g
    times.

    A block's keys run from its first key (key 0, or with a window the
    last multiple of 128 at or before the first query's first key): the
    slices are static, so the tiles above the
    diagonal and the tiles wholly below the window's lower edge are never
    computed, only the tiles an edge crosses are masked, and no plane
    wider than [B, hk, g * block_q, block_kv] exists. (A row can find a
    whole tile masked before its window begins: what that adds to its
    sum is multiplied by exp(-1e30 - max) = 0 when its first real key
    comes, and the diagonal tile, which holds its own key, comes last.)
    Each query block is rematerialised: the backward pass holds its tiles
    and no others, where the dense causal path of ``xla_attention`` holds
    [B, h, N, N] (8.6 GB a sequence at 32 heads and 8,192 tokens). On a
    v5e the whole row of keys at once — one [B, h, 512, 8192] float32
    softmax a block — ran 5.4 times slower than these tiles at the same
    block_q (1,056 against 196 ms forward and backward at 2 x 8,192 x 32
    x 192/128, my chip run, PR 27).

    A block's program depends on where its queries stand among ITS keys
    and on how many keys it has, not on where it stands in the sequence:
    past the window every block of a window layer has the same geometry,
    and such a run of blocks is traced and compiled ONCE, under
    ``lax.map``, its keys cut out at a dynamic offset (24 of the 32
    blocks at 16,384 tokens and a window of 4,096; without a window no
    two blocks are alike and each is its own program, as before).

    Under a ``selection`` a block also takes its ``[B, block_q, keys]``
    rows of it, and every tile is masked by them."""
    b, n, h, _ = q.shape
    hk = k.shape[2]
    g = h // hk
    scale = q.shape[-1] ** -0.5
    # heads beside the batch: one leading batch axis for the matmuls
    lead = lambda x: jnp.swapaxes(x, 1, 2).reshape((b * hk, n, x.shape[-1]))  # noqa: E731
    if g > 1:  # [B, N, hk * g, d] -> [B * hk, N * g, d], token-major rows
        q = q.reshape(b, n, hk, g, -1).transpose(0, 2, 1, 3, 4).reshape(
            b * hk, n * g, -1)
    else:
        q = lead(q)
    k, v = lead(k), lead(v)

    def block(qb, kb, vb, start, sb=None):
        """Queries from token ``start`` of the keys' own numbering."""
        rows = qb.shape[:2]
        end = start + rows[1] // g
        top = jnp.full(rows, -1e30, reduce_dtype)
        total = jnp.zeros(rows, reduce_dtype)
        acc = jnp.zeros(rows + (vb.shape[-1],), reduce_dtype)
        for lo in range(0, kb.shape[1], block_kv):
            hi = min(lo + block_kv, kb.shape[1])
            z = jnp.einsum("zqd,zkd->zqk", qb, kb[:, lo:hi],
                           preferred_element_type=reduce_dtype) * scale
            above = hi > start + 1  # the diagonal crosses this tile
            below = window is not None and lo <= end - 1 - window
            if above or below:
                row = jax.lax.broadcasted_iota(jnp.int32, z.shape[-2:], 0)
                row = start + (row // g if g > 1 else row)
                col = lo + jax.lax.broadcasted_iota(jnp.int32, z.shape[-2:], 1)
                seen = col <= row
                if below:
                    seen = (seen & (col > row - window)) if above \
                        else col > row - window
                z = jnp.where(seen, z, jnp.asarray(-1e30, z.dtype))
            if sb is not None:
                z = jnp.where(sb[:, :, lo:hi], z, jnp.asarray(-1e30, z.dtype))
            new_top = jnp.maximum(top, jnp.max(z, axis=-1))
            shrink = jnp.exp(top - new_top)
            p = jnp.exp(z - new_top[..., None])
            total = total * shrink + jnp.sum(p, axis=-1)
            acc = acc * shrink[..., None] + jnp.einsum(
                "zqk,zkd->zqd", p.astype(vb.dtype), vb[:, lo:hi],
                preferred_element_type=reduce_dtype)
            top = new_top
        return (acc / total[..., None]).astype(vb.dtype)

    block = jax.checkpoint(block, static_argnums=(3,))
    spans = []  # (start, end, first key) of every block of queries
    align = min(block_kv, 128)
    for start in range(0, n, block_q):
        first = 0 if window is None else \
            max(start - window + 1, 0) // align * align
        spans.append((start, min(start + block_q, n), first))
    outs = []
    # consecutive blocks of one geometry: (queries' place among the keys,
    # keys, queries)
    for (at, keys, size), run in itertools.groupby(
            spans, key=lambda s: (s[0] - s[2], s[1] - s[2], s[1] - s[0])):
        run = list(run)
        start, _, first = run[0]
        if len(run) == 1:
            chosen = ()
            if selection is not None:
                # the block's rows of it, a row for each head of the group
                # and a copy for each key/value head, as q's rows lie
                sb = selection[:, start:start + size, first:first + keys] != 0
                chosen = (jnp.repeat(jnp.repeat(sb, g, axis=1), hk, axis=0),)
            outs.append(block(q[:, start * g:(start + size) * g],
                              k[:, first:first + keys], v[:, first:first + keys],
                              at, *chosen))
            continue

        def one(xs):
            qb, first = xs
            cut = lambda x: jax.lax.dynamic_slice_in_dim(x, first, keys, axis=1)  # noqa: E731
            return block(qb, cut(k), cut(v), at)

        qs = q[:, start * g:(start + len(run) * size) * g].reshape(
            b * hk, len(run), size * g, -1)
        o = jax.lax.map(one, (jnp.moveaxis(qs, 1, 0),
                              jnp.asarray([s[2] for s in run], jnp.int32)))
        outs.append(jnp.moveaxis(o, 0, 1).reshape(b * hk, len(run) * size * g, -1))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    if g > 1:
        return out.reshape(b, hk, n, g, -1).transpose(0, 2, 1, 3, 4).reshape(
            b, n, h, -1)
    return jnp.swapaxes(out.reshape(b, h, n, out.shape[-1]), 1, 2)


# Below this many tokens the dense-softmax XLA path wins on TPU: the whole
# [N, N] fits in VMEM, XLA fuses RoPE/scale/softmax into the matmuls, and
# the flash kernel's custom_vjp would block those fusions. Measured
# full-train-step evidence (v5e): dense wins at N=201 (~1.45x, r1) AND at
# N=1029 — the 512px ViT-L step runs 9.99 img/s dense vs 7.65 flash
# (round 5, before PR 1, one v5e chip), so the old 1024 threshold flipped
# to the slower path at its first live decision point. 2048 keeps every measured
# regime on dense while leaving flash reachable where its O(N) memory is
# the point (768px -> 2309 tokens, ViT-7B long-context).
#
# The SOURCE OF TRUTH for module-built models is the config knob
# ``kernels.flash_min_seq`` (ssl_default_config.yaml, default "auto") —
# "auto" resolves against the committed op-level crossover artifact
# CROSSOVER_r19.json via scripts/crossover_attention.py's
# ``recommended_flash_min_seq`` (configs/config.py
# ``resolve_flash_min_seq``; the artifact-pin test is
# tests/test_crossover_attention.py). Re-derive the threshold by
# re-running the crossover harness on TPU and committing the artifact,
# not by editing this file. This constant is only the fallback for
# direct dispatch_attention calls that pass flash_min_seq=0.
FLASH_MIN_SEQ = 2048

# Below this many tokens ring attention is not worth the rotation: the
# point of the ring is sharding the O(N) K/V state and the O(N^2)
# logits-block traffic over the seq axis, and at short N (the 98-201
# token local crops) the whole dense call is cheaper than size-1 chunks
# ppermuting around the mesh. Dispatch is per-PASS (q.shape[1]): under
# one dp x seq mesh the 1029-token 512px globals ring while the locals
# run dense with seq-replicated activations — the crossover is a memory
# argument (O(N/s) per device vs O(N)), unlike flash_min_seq's measured
# time crossover. ``SelfAttention`` reads it when a pass is traced, so
# the in-step ring tests patch it to 1.
RING_MIN_SEQ = 1024


def dispatch_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    impl: str = "auto", reduce_dtype=jnp.float32,
    flash_block_q: int = 512, flash_block_kv: int = 512,
    probs_dtype=None, flash_min_seq: int = 0,
    seg: jnp.ndarray | None = None,
    causal: bool = False,
    window: int | None = None,
    selection: jnp.ndarray | None = None,
) -> jnp.ndarray | tuple:
    if (window is not None or selection is not None) and not causal:
        raise ValueError("a window or a selection is the causal path's")
    if causal:
        # the Pallas kernel is non-causal (flash_attention.py) and the
        # dense causal path holds the whole [N, N] plane: a decoder's
        # attention goes block by block, on every backend
        if seg is not None:
            raise ValueError("causal attention takes no segment ids")
        if selection is not None:
            return causal_blockwise_attention(
                q, k, v, reduce_dtype=reduce_dtype, window=window,
                selection=selection)
        return causal_blockwise_attention(q, k, v, reduce_dtype=reduce_dtype,
                                          window=window)
    if impl == "auto":
        # 0/None = built-in default, matching kernels.flash_min_seq's
        # documented sentinel (one convention for module and direct calls)
        min_seq = flash_min_seq or FLASH_MIN_SEQ
        impl = (
            "pallas"
            if jax.default_backend() == "tpu" and q.shape[1] >= min_seq
            else "xla"
        )
    if impl in ("xla", "reference"):
        return xla_attention(q, k, v, reduce_dtype, probs_dtype=probs_dtype,
                             seg=seg)
    if impl == "pallas":
        from dinov3_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, block_q=flash_block_q,
                               block_kv=flash_block_kv, seg=seg)
    raise ValueError(f"unknown attention impl {impl!r}")


class SelfAttention(nn.Module):
    dim: int
    num_heads: int = 8
    qkv_bias: bool = True
    proj_bias: bool = True
    proj_drop: float = 0.0
    mask_k_bias: bool = False
    attn_impl: str = "auto"
    seq_parallel: bool = False
    fp8: bool = False  # current-scaling fp8 projections (ops/common.py)
    # train.low_precision.arm: delayed-scaling fp8/int8 matmuls
    # (ops/lowp.py) — engaged only when the "lowp" scale collection is
    # present (training applies), so init/eval stay on the bf16 path
    lowp_arm: str = "bf16"
    causal: bool = False  # triangular mask (dense XLA path only)
    flash_block_q: int = 512   # kernels.flash_block_q/kv caps
    flash_block_kv: int = 512
    flash_min_seq: int = 0     # kernels.flash_min_seq; 0 = FLASH_MIN_SEQ
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    reduce_dtype: Any = jnp.float32
    probs_dtype: Any = None  # probability storage; None = reduce_dtype

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        rope: tuple[jnp.ndarray, jnp.ndarray] | None = None,
        deterministic: bool = True,
        seg: jnp.ndarray | None = None,
    ) -> jnp.ndarray:
        """``seg``: optional [B, N] segment ids for block-diagonal
        (crop-packed) attention; ``rope`` tables may then be per-row
        [B, N, head_dim] (global vs packed coordinate grids)."""
        B, N, _ = x.shape
        h, d = self.num_heads, self.dim // self.num_heads

        qkv_kernel = self.param(
            "qkv_kernel", part(trunc_normal_init(), ("embed", "heads")),
            (self.dim, 3 * self.dim), self.param_dtype,
        )
        mm = fp8_matmul if self.fp8 else (lambda a, b: a @ b)

        def lowp_mm(name):
            """Quantized-arm matmul for the kernel whose delayed scale
            is at ``("lowp", name)`` — falls back to ``mm`` when the
            arm is bf16 or no scale collection rode this apply (init,
            eval, the gram teacher)."""
            if self.lowp_arm == "bf16" or not self.has_variable("lowp", name):
                return mm
            from dinov3_tpu.ops.lowp import lowp_matmul

            scale = self.get_variable("lowp", name)
            return lambda a, b: lowp_matmul(self.lowp_arm, a, b, scale)

        qkv = lowp_mm("qkv_kernel")(
            x.astype(self.dtype), qkv_kernel.astype(self.dtype))
        if self.qkv_bias:
            qkv_b = self.param(
                "qkv_bias", part(nn.initializers.zeros, ("heads",)),
                (3 * self.dim,), self.param_dtype,
            )
            if self.mask_k_bias:
                # zero the k third: softmax(q.(k+b)) is invariant to a shared
                # k shift only for the rotary-free part, so DINOv3 masks it
                # outright (reference: LinearKMaskedBias, attention.py:23-46).
                mask = jnp.concatenate([
                    jnp.ones((self.dim,), self.param_dtype),
                    jnp.zeros((self.dim,), self.param_dtype),
                    jnp.ones((self.dim,), self.param_dtype),
                ])
                qkv_b = qkv_b * mask
            qkv = qkv + qkv_b.astype(self.dtype)

        # contiguous last-dim thirds (same column order as
        # reshape(B,N,3,h,d) + moveaxis, which forced a full strided copy
        # of qkv — round-2 profile: ~6 ms/step on the moveaxis alone)
        q = qkv[..., : self.dim].reshape(B, N, h, d)
        k = qkv[..., self.dim: 2 * self.dim].reshape(B, N, h, d)
        v = qkv[..., 2 * self.dim:].reshape(B, N, h, d)
        if rope is not None:
            sin, cos = rope
            if sin.shape[-2] == N:
                # full-length table (identity prefix rows): fused fma path
                q, k = rope_apply_full(q, k, sin, cos)
            else:
                q, k = rope_apply_with_prefix(
                    q, k, sin, cos, dtype=self.reduce_dtype
                )

        out = None
        out_token_axis = None  # "seq_tokens" when the ring path engages
        if self.causal:
            # causal runs the dense path (ViT's SSL path never uses it;
            # reference kept a CausalSelfAttention for generative probes)
            out = xla_attention(q, k, v, self.reduce_dtype, causal=True,
                                probs_dtype=self.probs_dtype)
        if out is None and self.seq_parallel \
                and N >= RING_MIN_SEQ:
            # per-pass dispatch: only passes long enough to pay for the
            # rotation ring (RING_MIN_SEQ) — under one dp x seq mesh the
            # high-res globals ring while short local crops run dense
            # with seq-replicated activations. Crop-packed rows ride
            # along: the segment ids thread through the rotating chunks
            # (parallel/ring_attention.py), same block-diagonal
            # semantics as the dense/flash seg mask.
            from dinov3_tpu.parallel.context import get_current_mesh

            mesh = get_current_mesh()
            if mesh is not None and int(mesh.shape.get("seq", 1)) > 1:
                from dinov3_tpu.parallel.ring_attention import ring_attention

                out = ring_attention(q, k, v, mesh, seg=seg,
                                     reduce_dtype=self.reduce_dtype)
                # keep the ring's output seq-sharded ("seq_tokens" rule,
                # parallel/sharding.py) so the MLP half of the block runs
                # on N/s tokens per device instead of re-gathering N
                out_token_axis = "seq_tokens"
        if out is None:
            out = dispatch_attention(
                q, k, v, self.attn_impl, self.reduce_dtype,
                flash_block_q=self.flash_block_q,
                flash_block_kv=self.flash_block_kv,
                probs_dtype=self.probs_dtype,
                flash_min_seq=self.flash_min_seq,
                seg=seg,
            )
        out = constrain(out.reshape(B, N, self.dim),
                        ("batch", out_token_axis, "embed_act"))

        proj_kernel = self.param(
            "proj_kernel", part(trunc_normal_init(), ("heads", "embed")),
            (self.dim, self.dim), self.param_dtype,
        )
        y = lowp_mm("proj_kernel")(
            out.astype(self.dtype), proj_kernel.astype(self.dtype))
        if self.proj_bias:
            proj_b = self.param(
                "proj_bias", part(nn.initializers.zeros, ("embed",)),
                (self.dim,), self.param_dtype,
            )
            y = y + proj_b.astype(self.dtype)
        if self.proj_drop > 0.0:
            y = nn.Dropout(self.proj_drop)(y, deterministic=deterministic)
        return y


class CausalSelfAttention(SelfAttention):
    """Causally-masked variant (reference: dinov3_jax/layers/attention.py
    CausalSelfAttention:135 — present in the reference inventory but unused
    by the ViT SSL path; kept for generative/probing heads)."""

    causal: bool = True
