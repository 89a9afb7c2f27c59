"""Plain reference of the DINOv3 ViT forward: ``jax.numpy``, float32,
matmul precision "highest". No kernels, no packing, no scan, nothing
imported from the program.

Written from the published architecture (pre-norm blocks, CLS + storage
tokens, axial RoPE on the patch tokens of q and k with the prefix left
alone, LayerScale, erf GELU, LayerNorm eps 1e-6; the layout of
``tests/torch_dinov3_oracle.py``). One image (or a batch of one
resolution) at a time: ``embed`` -> ``block`` x depth -> ``head``, each a
small jitted function, so a new resolution compiles three small programs
and the weights of one block are all that is upcast at a time.

``precision`` selects what the matmuls of the linear layers compute in:

- ``"fp32"``: the reference proper.
- ``"bf16"``: inputs rounded to bfloat16, float32 accumulation — what
  the configurations state. Read for information (the rounding floor).
- ``"int8"`` and ``"fp8"``: the CONTROLS of the output check, the
  nearest precisions below the stated one and the step that would tempt
  a later PR; the check's limit has to refuse the one the
  configuration's file names. int8: weights quantised per output channel
  and activations per token to symmetric int8; fp8: both scaled per
  tensor and rounded to float8_e4m3fn. The products of the quantised values
  accumulate in float32; the rest is as ``"bf16"``. Roundings are
  straight-through (``_ste``), so a control can be differentiated
  (``ssl_step_fp32.py``): its forward pass runs in the lower precision.

Weights (``Weights`` below) are plain dicts of arrays of any float type;
they are upcast here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
PRECISIONS = ("fp32", "bf16", "int8", "fp8")


def _ste(x, q):
    """``q`` (``x`` rounded) in the forward pass, the identity in the
    backward pass: a control's forward runs in its precision and its
    gradient still reaches the weights."""
    return x + jax.lax.stop_gradient(q - x)


def _linear(x, w, b, precision: str):
    """x [..., K] @ w [K, N] + b, in the named precision; float32 out."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp32":
        y = jnp.matmul(x, w, precision="highest")
    elif precision == "bf16":
        y = jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    elif precision == "int8":
        sx = jax.lax.stop_gradient(
            jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127.0)
        sw = jax.lax.stop_gradient(
            jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 127.0)
        y = jnp.matmul(_ste(x, jnp.round(x / sx) * sx), _ste(w, jnp.round(w / sw) * sw),
                       precision="highest")
    elif precision == "fp8":
        sx = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
        sw = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 448.0)
        f8 = lambda t, s: (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s  # noqa: E731
        y = jnp.matmul(_ste(x, f8(x, sx)), _ste(w, f8(w, sw)), precision="highest")
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return y + b.astype(jnp.float32)


def _round(x, precision: str):
    """Activations between layers: float32 in the reference, rounded to
    bfloat16 where the lower precisions would store them."""
    if precision == "fp32":
        return x
    return _ste(x, x.astype(jnp.bfloat16).astype(jnp.float32))


def _layernorm(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + LN_EPS)
    return y * scale.astype(jnp.float32) + bias.astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def rope_tables(hp: int, wp: int, head_dim: int, base: float):
    """(sin, cos), each [hp*wp, head_dim]: patch centres in [-1, 1] per
    axis ("separate" normalisation), periods base**(2j / (head_dim/2)),
    angles laid out [h-axis periods, w-axis periods] and duplicated for
    the rotate-half pairing."""
    n = head_dim // 4
    periods = base ** (2.0 * jnp.arange(n, dtype=jnp.float32) / (head_dim / 2.0))
    ch = 2.0 * (jnp.arange(hp, dtype=jnp.float32) + 0.5) / hp - 1.0
    cw = 2.0 * (jnp.arange(wp, dtype=jnp.float32) + 0.5) / wp - 1.0
    gh, gw = jnp.meshgrid(ch, cw, indexing="ij")
    coords = jnp.stack([gh, gw], axis=-1).reshape(-1, 2)
    ang = 2.0 * math.pi * coords[:, :, None] / periods[None, None, :]
    ang = ang.reshape(ang.shape[0], -1)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.sin(ang), jnp.cos(ang)


@functools.partial(jax.jit, static_argnames=("patch", "precision"))
def embed(w, images, *, patch: int, precision: str = "fp32"):
    """[B, H, W, C] -> [B, 1 + S + hp*wp, D] tokens (CLS, storage, patches)."""
    B, H, W, C = images.shape
    hp, wp = H // patch, W // patch
    x = images.astype(jnp.float32).reshape(B, hp, patch, wp, patch, C)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, hp * wp, patch * patch * C)
    k = w["patch_kernel"]
    t = _linear(x, k.reshape(patch * patch * C, k.shape[-1]), w["patch_bias"],
                precision)
    D = t.shape[-1]
    parts = [jnp.broadcast_to(w["cls_token"].astype(jnp.float32).reshape(1, 1, D),
                              (B, 1, D))]
    if w.get("storage_tokens") is not None:
        s = w["storage_tokens"].astype(jnp.float32).reshape(1, -1, D)
        parts.append(jnp.broadcast_to(s, (B, s.shape[1], D)))
    parts.append(t)
    return _round(jnp.concatenate(parts, axis=1), precision)


@functools.partial(jax.jit, static_argnames=("heads", "n_prefix", "precision"))
def block(b, x, sin, cos, scale=None, *, heads: int, n_prefix: int,
          precision: str = "fp32"):
    """One pre-norm block on [B, N, D] float32 tokens. ``scale``: None, or
    [2, B] factors on the two residual branches of each row (stochastic
    depth by batch subset: 0 for a dropped row, rows / kept for a kept one)."""
    B, N, D = x.shape
    s1, s2 = (1.0, 1.0) if scale is None else (
        scale[0][:, None, None], scale[1][:, None, None])
    d = D // heads
    h = _layernorm(x, b["norm1_scale"], b["norm1_bias"])
    qkv = _round(_linear(_round(h, precision), b["qkv_kernel"], b["qkv_bias"],
                         precision), precision)
    q, k, v = (qkv[..., i * D:(i + 1) * D].reshape(B, N, heads, d)
               for i in range(3))

    def rope(t):
        p = t[:, n_prefix:]
        x1, x2 = p[..., : d // 2], p[..., d // 2:]
        rot = jnp.concatenate([-x2, x1], axis=-1)
        p = p * cos[None, :, None, :] + rot * sin[None, :, None, :]
        return jnp.concatenate([t[:, :n_prefix], p], axis=1)

    q, k = rope(q), rope(k)
    if precision != "fp32":
        q, k, v = (_round(t, precision) for t in (q, k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / math.sqrt(d)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round(probs, precision), v,
                   precision="highest").reshape(B, N, D)
    a = _linear(_round(o, precision), b["proj_kernel"], b["proj_bias"], precision)
    x = x + s1 * _round(a, precision) * b["ls1"].astype(jnp.float32)
    h = _layernorm(x, b["norm2_scale"], b["norm2_bias"])
    f = _linear(_round(h, precision), b["fc1_kernel"], b["fc1_bias"], precision)
    f = jax.nn.gelu(_round(f, precision), approximate=False)
    f = _linear(_round(f, precision), b["fc2_kernel"], b["fc2_bias"], precision)
    x = x + s2 * _round(f, precision) * b["ls2"].astype(jnp.float32)
    return _round(x, precision)


@functools.partial(jax.jit, static_argnames=("n_prefix",))
def head(w, x, *, n_prefix: int):
    """Final norm; (CLS [B, D], mean-pooled patch features [B, D])."""
    y = _layernorm(x, w["norm_scale"], w["norm_bias"])
    return y[:, 0], jnp.mean(y[:, n_prefix:], axis=1)


def features(w: dict, images, *, patch: int, heads: int, rope_base: float,
             precision: str = "fp32"):
    """Features of a batch of one resolution: (cls, pooled), float32.

    ``w``: {"patch_kernel" [p, p, C, D], "patch_bias", "cls_token",
    "storage_tokens" or None, "norm_scale", "norm_bias", "blocks": list
    of {"norm1_scale", "norm1_bias", "qkv_kernel" [D, 3D] (q, k, v
    thirds), "qkv_bias", "proj_kernel", "proj_bias", "ls1", "norm2_scale",
    "norm2_bias", "fc1_kernel", "fc1_bias", "fc2_kernel", "fc2_bias",
    "ls2"}}."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    H, W = images.shape[1], images.shape[2]
    x = embed(w, images, patch=patch, precision=precision)
    D = x.shape[-1]
    n_prefix = x.shape[1] - (H // patch) * (W // patch)
    sin, cos = rope_tables(H // patch, W // patch, D // heads, rope_base)
    with jax.default_matmul_precision("highest"):
        for b in w["blocks"]:
            x = block(b, x, sin, cos, heads=heads, n_prefix=n_prefix,
                      precision=precision)
        return head(w, x, n_prefix=n_prefix)
