"""Normalization layers with fp32 statistic accumulation.

(reference: dinov3_jax/layers/rms_norm.py — which accumulated in fp32 but had
a ``jnp.float`` typo; and plain ``nn.LayerNorm`` used throughout the ViT.)
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from dinov3_tpu.ops.common import part


class LayerNorm(nn.Module):
    """LayerNorm: fp32 stats, params in param_dtype, output in input dtype.

    On TPU the forward/backward run as the fused Pallas kernel
    (ops/fused_norm.py) — one read, in-register fp32 statistics, one write —
    when the width is lane-aligned; elsewhere the identical math goes
    through plain XLA ops."""

    epsilon: float = 1e-6
    param_dtype: Any = jnp.float32
    reduce_dtype: Any = jnp.float32
    fused: bool = True

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        dim = x.shape[-1]
        scale = self.param("scale", part(nn.initializers.ones, ("embed",)), (dim,),
                           self.param_dtype)
        bias = self.param("bias", part(nn.initializers.zeros, ("embed",)), (dim,),
                          self.param_dtype)
        if self.fused and self.reduce_dtype == jnp.float32:
            from dinov3_tpu.ops.fused_norm import fused_layernorm

            return fused_layernorm(x, scale, bias, self.epsilon)
        xf = x.astype(self.reduce_dtype)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.epsilon)
        y = y * scale.astype(self.reduce_dtype) + bias.astype(self.reduce_dtype)
        return y.astype(x.dtype)


class RMSNorm(nn.Module):
    """RMSNorm: fp32 mean-square, learned scale. ``zero_centered``: the
    scale is 1 + w, w from zeros (weight decay then pulls towards 1)."""

    epsilon: float = 1e-6
    param_dtype: Any = jnp.float32
    reduce_dtype: Any = jnp.float32
    zero_centered: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        dim = x.shape[-1]
        init = nn.initializers.zeros if self.zero_centered else nn.initializers.ones
        scale = self.param("scale", part(init, ("embed",)), (dim,),
                           self.param_dtype)
        xf = x.astype(self.reduce_dtype)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + self.epsilon)
        scale = scale.astype(self.reduce_dtype)
        y = y * (1.0 + scale if self.zero_centered else scale)
        return y.astype(x.dtype)


def make_norm_layer(kind: str, **kwargs) -> nn.Module:
    # "layernormbf16" (7B recipes) selected a bf16-computed LN in the
    # PyTorch original; statistics stay fp32 here — strictly more accurate
    # and free on TPU (the VPU upcasts anyway).
    if kind in ("layernorm", "layer_norm", "ln", "layernormbf16"):
        return LayerNorm(**kwargs)
    if kind in ("rmsnorm", "rms_norm", "rms"):
        return RMSNorm(**kwargs)
    raise ValueError(f"unknown norm layer {kind!r}")
