"""2-D rotary position embeddings for ViT patch grids, as pure functions
(and ``token_rope_sincos``: a decoder's 1-D table over token positions;
``token_rope_pair_sincos`` / ``rope_apply_interleaved``: the same turn on
NEIGHBOURING channel pairs, latent attention's).

Math parity with the reference module (dinov3_jax/layers/rope_position_encoding.py):
- period spectrum from ``base ** (2j / (D_head/2))`` for j in [0, D_head/4)
  or geometric between ``min_period`` and ``max_period``;
- patch-center coordinates normalized to [-1, 1] per the ``min|max|separate``
  mode (the reference's "min" mode used max(H, W) — a bug we fix, SURVEY.md
  §2.9.8);
- optional train-time coordinate augmentation: global shift, per-axis
  log-uniform jitter, isotropic log-uniform rescale;
- output ``(sin, cos)`` of shape [H*W, D_head] consumed by ``rope_apply``
  with rotate-half pairing.

Pure functions (not a Flax module): the tables depend only on static config
+ (H, W) + an rng, so the ViT computes one table per crop resolution per
step and passes it to all blocks — no per-block recompute as in the
reference (dinov3_jax/models/vision_transformer.py:212-217).
"""

from __future__ import annotations

import math
from typing import Literal

import jax
import jax.numpy as jnp


def rope_periods(
    head_dim: int,
    base: float | None = 100.0,
    min_period: float | None = None,
    max_period: float | None = None,
    dtype=jnp.float32,
) -> jnp.ndarray:
    """[head_dim // 4] period spectrum."""
    if head_dim % 4 != 0:
        raise ValueError(f"head_dim must be divisible by 4, got {head_dim}")
    both = min_period is not None and max_period is not None
    if (base is None) == (not both):
        raise ValueError("provide either `base` or `min_period`+`max_period`")
    n = head_dim // 4
    if base is not None:
        return jnp.asarray(base, dtype) ** (
            2.0 * jnp.arange(n, dtype=dtype) / (head_dim / 2.0)
        )
    ratio = max_period / min_period
    exponents = jnp.linspace(0.0, 1.0, n, dtype=dtype)
    return (ratio**exponents) * (max_period / ratio)


def patch_coords(
    H: int,
    W: int,
    normalize: Literal["min", "max", "separate"] = "separate",
    dtype=jnp.float32,
) -> jnp.ndarray:
    """[H*W, 2] patch-center coordinates in [-1, 1] (row-major, ij order)."""
    if normalize == "max":
        denom_h = denom_w = max(H, W)
    elif normalize == "min":
        denom_h = denom_w = min(H, W)
    elif normalize == "separate":
        denom_h, denom_w = H, W
    else:
        raise ValueError(f"unknown normalize mode {normalize!r}")
    ch = (jnp.arange(H, dtype=dtype) + 0.5) / denom_h
    cw = (jnp.arange(W, dtype=dtype) + 0.5) / denom_w
    coords = jnp.stack(jnp.meshgrid(ch, cw, indexing="ij"), axis=-1).reshape(-1, 2)
    return 2.0 * coords - 1.0


def augment_coords(
    coords: jnp.ndarray,
    rng: jax.Array,
    shift: float | None = None,
    jitter: float | None = None,
    rescale: float | None = None,
) -> jnp.ndarray:
    """Train-time coordinate augmentation (jittable; factors of 1 when off)."""
    rng_shift, rng_jitter, rng_rescale = jax.random.split(rng, 3)
    d = coords.dtype
    if shift is not None:
        coords = coords + jax.random.uniform(
            rng_shift, (2,), minval=-shift, maxval=shift, dtype=d
        )
    if jitter is not None:
        j = math.log(jitter)
        coords = coords * jnp.exp(
            jax.random.uniform(rng_jitter, (2,), minval=-j, maxval=j, dtype=d)
        )
    if rescale is not None:
        r = math.log(rescale)
        coords = coords * jnp.exp(
            jax.random.uniform(rng_rescale, (1,), minval=-r, maxval=r, dtype=d)
        )
    return coords


def rope_aug_values(
    u: jnp.ndarray,
    shift: float | None = None,
    jitter: float | None = None,
    rescale: float | None = None,
) -> dict:
    """[5] uniforms in [0, 1) -> the concrete augmentation factors.

    Same marginal distributions as ``augment_coords``'s three separate
    draws (shift ~ U[-s, s] per axis; jitter/rescale ~ log-uniform over
    [1/j, j]), derived from ONE fused uniform draw so the step-wide RNG
    plan (rng/plan.py) spends a single threefry op per forward pass on
    coordinate augmentation instead of a split + three draws.
    """
    out = {}
    if shift is not None:
        out["shift"] = (2.0 * u[0:2] - 1.0) * shift
    if jitter is not None:
        out["jitter"] = jnp.exp((2.0 * u[2:4] - 1.0) * math.log(jitter))
    if rescale is not None:
        out["rescale"] = jnp.exp((2.0 * u[4:5] - 1.0) * math.log(rescale))
    return out


def augment_coords_planned(coords: jnp.ndarray, aug: dict) -> jnp.ndarray:
    """Apply precomputed augmentation factors (``rope_aug_values``)."""
    d = coords.dtype
    if "shift" in aug:
        coords = coords + aug["shift"].astype(d)
    if "jitter" in aug:
        coords = coords * aug["jitter"].astype(d)
    if "rescale" in aug:
        coords = coords * aug["rescale"].astype(d)
    return coords


def rope_sincos(
    H: int,
    W: int,
    periods: jnp.ndarray,
    normalize: Literal["min", "max", "separate"] = "separate",
    rng: jax.Array | None = None,
    shift: float | None = None,
    jitter: float | None = None,
    rescale: float | None = None,
    dtype=jnp.float32,
    aug: dict | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(sin, cos), each [H*W, 4*len(periods)] == [H*W, head_dim].

    Coordinate augmentation comes from EITHER ``rng`` (legacy in-place
    draws) OR ``aug`` (precomputed factors from the step-wide RNG plan);
    passing both is a wiring error.
    """
    if rng is not None and aug is not None:
        raise ValueError("pass either rng or aug (plan), not both")
    coords = patch_coords(H, W, normalize, dtype=jnp.float32)
    if aug is not None:
        coords = augment_coords_planned(coords, aug)
    elif rng is not None and (shift or jitter or rescale):
        coords = augment_coords(coords, rng, shift, jitter, rescale)
    # [HW, 2, 1] / [P] -> [HW, 2, P] -> [HW, 2P] -> duplicated rotate-half halves
    angles = 2.0 * math.pi * coords[:, :, None] / periods[None, None, :]
    angles = angles.reshape(angles.shape[0], -1)
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.sin(angles).astype(dtype), jnp.cos(angles).astype(dtype)


def token_rope_sincos(
    n_tokens: int, head_dim: int, theta: float, dtype=jnp.float32,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(sin, cos), each [n_tokens, head_dim], of a decoder's rotary
    embedding over token positions 0..n_tokens-1: channel pair
    (j, j + head_dim/2) of token t turns by t * theta^(-2j / head_dim)
    (rotate-half pairing, the halves duplicated as ``rope_sincos`` has
    them, so ``rope_apply_full`` serves both)."""
    if head_dim % 2:
        raise ValueError(f"head_dim must be even, got {head_dim}")
    rates = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    angles = jnp.arange(n_tokens, dtype=jnp.float32)[:, None] * rates[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.sin(angles).astype(dtype), jnp.cos(angles).astype(dtype)


def rope_rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope_apply(x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray) -> jnp.ndarray:
    """Rotate the trailing head_dim of x ([..., N, head_dim]) by the table."""
    return x * cos + rope_rotate_half(x) * sin


def rope_with_identity_prefix(
    sin: jnp.ndarray, cos: jnp.ndarray, n_prefix: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Prepend identity rotations (sin=0, cos=1) for prefix tokens.

    Lets the per-block apply be one full-sequence fma with no token-axis
    slice/concat: CLS + storage tokens rotate by the identity instead of
    being carved out and re-concatenated in every block (the fusion-breaking
    pattern the reference had, dinov3_jax/layers/attention.py:77-87)."""
    if n_prefix == 0:
        return sin, cos
    pad_sin = jnp.zeros((n_prefix, sin.shape[-1]), sin.dtype)
    pad_cos = jnp.ones((n_prefix, cos.shape[-1]), cos.dtype)
    return (jnp.concatenate([pad_sin, sin], axis=0),
            jnp.concatenate([pad_cos, cos], axis=0))


def rope_apply_full(
    q: jnp.ndarray,
    k: jnp.ndarray,
    sin: jnp.ndarray,
    cos: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rotate q/k ([B, N, heads, head_dim]) by a full-length table
    ([N, head_dim] shared by every row, or [B, N, head_dim] per-row —
    the crop-packed batch, where global and packed rows carry different
    coordinate grids; identity rows for prefix/pad tokens either way).

    Half-pair formulation (out1 = x1*c - x2*s; out2 = x2*c + x1*s) — the
    same math as ``rope_apply``'s rotate-half but with no negation pass,
    computed in the table's dtype (fp32 tables upcast q/k transiently;
    bf16 tables keep the whole chain in bf16)."""
    compute = jnp.promote_types(q.dtype, sin.dtype)
    half = sin.shape[-1] // 2
    # tables duplicate their halves ([ang, ang]); one half suffices
    if sin.ndim == 3:
        s = sin[:, :, None, :half].astype(compute)
        c = cos[:, :, None, :half].astype(compute)
    else:
        s = sin[None, :, None, :half].astype(compute)
        c = cos[None, :, None, :half].astype(compute)

    def rot(t):
        x = t.astype(compute)
        x1, x2 = x[..., :half], x[..., half:]
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        return out.astype(t.dtype)

    return rot(q), rot(k)


def rope_apply_leading(
    q: jnp.ndarray,
    k: jnp.ndarray,
    sin: jnp.ndarray,
    cos: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``rope_apply_full`` on the leading ``sin.shape[-1]`` channels of
    every head (a partial rotary: rotate-half INSIDE those channels); the
    channels past them carry no position and pass as they are. A table as
    wide as the head is ``rope_apply_full`` itself."""
    width = sin.shape[-1]
    if width == q.shape[-1]:
        return rope_apply_full(q, k, sin, cos)
    rq, rk = rope_apply_full(q[..., :width], k[..., :width], sin, cos)
    return (jnp.concatenate([rq, q[..., width:]], axis=-1),
            jnp.concatenate([rk, k[..., width:]], axis=-1))


def token_rope_pair_sincos(
    n_tokens: int, width: int, theta: float, dtype=jnp.float32,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(sin, cos), each [n_tokens, width / 2], of a rotary embedding over
    token positions 0..n_tokens-1 on ``width`` channels: pair i of token t
    turns by t * theta^(-2i / width). ``token_rope_sincos``'s angles, once
    each (which two channels make pair i is the caller's)."""
    if width % 2:
        raise ValueError(f"width must be even, got {width}")
    rates = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angles = jnp.arange(n_tokens, dtype=jnp.float32)[:, None] * rates[None, :]
    return jnp.sin(angles).astype(dtype), jnp.cos(angles).astype(dtype)


def rope_apply_interleaved(
    x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray,
) -> jnp.ndarray:
    """Turn the TRAILING ``2 * sin.shape[-1]`` channels of every head of
    x ([B, N, heads, width]; one head is a shared key) by the table
    ([N, pairs]: ``token_rope_pair_sincos``), NEIGHBOURING channels a
    pair (``rope_interleave``): (u, w) = channels (2i, 2i + 1) of the
    slice -> (u cos - w sin, u sin + w cos). The leading channels carry
    no position and pass as they are.

    The turned slice comes back with every pair's first result before
    every pair's second, [u' for all i ; w' for all i] (the order the
    public latent-attention code leaves it in): a query and a key turned
    by this function meet channel for channel, so their products are the
    published ones. Computed in the table's type (float32 tables upcast
    x transiently), returned in x's."""
    pairs = sin.shape[-1]
    lead = x.shape[-1] - 2 * pairs
    if lead < 0:
        raise ValueError(f"{2 * pairs} turned channels on heads of {x.shape[-1]}")
    compute = jnp.promote_types(x.dtype, sin.dtype)
    s = sin[None, :, None, :].astype(compute)
    c = cos[None, :, None, :].astype(compute)
    z = x[..., lead:].astype(compute).reshape(x.shape[:-1] + (pairs, 2))
    u, w = z[..., 0], z[..., 1]
    turned = jnp.concatenate([u * c - w * s, u * s + w * c], axis=-1)
    turned = turned.astype(x.dtype)
    if not lead:
        return turned
    return jnp.concatenate([x[..., :lead], turned], axis=-1)


def rope_apply_pairs(
    x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray,
) -> jnp.ndarray:
    """``rope_apply_interleaved`` of x [B, N, 2 * pairs] (one head, every
    channel turned) with each result left WHERE ITS CHANNEL WAS: channel
    2i -> u cos - w sin, 2i + 1 -> u sin + w cos. A permutation of that
    function's turned slice: the product of two vectors turned by this
    function is the same sum in another order
    (``ops/causal_attention.py latent_attention`` turns its queries so)."""
    compute = jnp.promote_types(x.dtype, sin.dtype)
    z = x.astype(compute).reshape(x.shape[:-1] + (sin.shape[-1], 2))
    u, w = z[..., 0], z[..., 1]
    s, c = sin.astype(compute), cos.astype(compute)
    return jnp.stack([u * c - w * s, u * s + w * c], axis=-1).reshape(
        x.shape).astype(x.dtype)


def rope_packed_rows(
    global_table: tuple[jnp.ndarray, jnp.ndarray],
    local_table: tuple[jnp.ndarray, jnp.ndarray],
    layout,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row RoPE tables for a crop-packed batch: ([R, N_g, d], x2).

    ``global_table``/``local_table`` are full-length (sin, cos) tables
    with their identity prefix rows already prepended
    (``rope_with_identity_prefix``), [N_g, d] and [N_l, d]. The packed
    rows tile the LOCAL table k times — each packed segment keeps its
    own local patch grid (its own CLS identity row included) — and pad
    the row tail with identity rotations; pad rotations are irrelevant
    (pad tokens are segment-masked) but identity keeps them inert.
    ``layout``: ops/packing.PackedLayout; row order follows its
    shard-grouped convention (packing.assemble_packed_batch).
    """
    sin_g, cos_g = global_table
    sin_l, cos_l = local_table
    d = sin_g.shape[-1]
    pad = layout.pad_tokens_per_row
    sin_p = jnp.concatenate(
        [jnp.tile(sin_l, (layout.k, 1)),
         jnp.zeros((pad, d), sin_l.dtype)], axis=0)
    cos_p = jnp.concatenate(
        [jnp.tile(cos_l, (layout.k, 1)),
         jnp.ones((pad, d), cos_l.dtype)], axis=0)
    g, R = layout.groups, layout.rows_total
    rows_g = jnp.broadcast_to(
        sin_g[None], (layout.n_global_rows,) + sin_g.shape)
    rows_gc = jnp.broadcast_to(
        cos_g[None], (layout.n_global_rows,) + cos_g.shape)
    rows_p = jnp.broadcast_to(
        sin_p[None], (layout.n_packed_rows,) + sin_p.shape)
    rows_pc = jnp.broadcast_to(
        cos_p[None], (layout.n_packed_rows,) + cos_p.shape)
    if g <= 1:
        return (jnp.concatenate([rows_g, rows_p], axis=0),
                jnp.concatenate([rows_gc, rows_pc], axis=0))
    gb = layout.n_global_rows // g
    pb = layout.n_packed_rows // g
    tail = sin_g.shape

    def grouped(a, b):
        mixed = jnp.concatenate(
            [a.reshape((g, gb) + tail), b.reshape((g, pb) + tail)], axis=1)
        return mixed.reshape((R,) + tail)

    return grouped(rows_g, rows_p), grouped(rows_gc, rows_pc)


def rope_apply_with_prefix(
    q: jnp.ndarray,
    k: jnp.ndarray,
    sin: jnp.ndarray,
    cos: jnp.ndarray,
    dtype=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Apply RoPE to the trailing-patch part of q/k, skipping prefix tokens.

    q, k: [B, N, heads, head_dim]; sin/cos: [P, head_dim] with P <= N.
    The first N - P tokens (CLS + storage/register tokens) pass through
    unrotated (reference: dinov3_jax/layers/attention.py:77-87).
    """
    n_prefix = q.shape[-3] - sin.shape[-2]
    if n_prefix < 0:
        raise ValueError(
            f"rope table covers {sin.shape[-2]} tokens but sequence has {q.shape[-3]}"
        )
    compute = dtype or q.dtype
    sin = sin[:, None, :].astype(compute)  # [P, 1, head_dim] broadcasting over heads
    cos = cos[:, None, :].astype(compute)

    def rot(t):
        patch = rope_apply(t[..., n_prefix:, :, :].astype(compute), sin, cos)
        return jnp.concatenate(
            [t[..., :n_prefix, :, :], patch.astype(t.dtype)], axis=-3
        )

    return rot(q), rot(k)
