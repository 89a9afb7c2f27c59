"""Feed-forward layers: standard ViT MLP and SwiGLU.

(reference: dinov3_jax/layers/ffn_layers.py. The reference's ``Mlp`` applied
activation+dropout after the *second* Dense too — a deviation from the
standard ViT MLP and from Meta's PyTorch DINOv3; we use the standard form,
SURVEY.md §2.3. SwiGLU hidden sizing matches: ``int(2/3 * hidden)`` rounded
up to ``align_to``.)
"""

from __future__ import annotations

import math
from typing import Any, Callable

import flax.linen as nn
import jax.numpy as jnp

from dinov3_tpu.ops.common import fp8_dot_general, part, trunc_normal_init


def _dense_kwargs(fp8: bool) -> dict:
    return {"dot_general": fp8_dot_general} if fp8 else {}


def _lowp_dense_kwargs(module: nn.Module, kernel: str) -> dict:
    """Per-Dense ``dot_general`` override for a fp8/int8
    ``train.low_precision`` arm: the OWNING module reads the kernel's
    delayed scale from the read-only ``"lowp"`` collection (a Dense
    submodule cannot see sibling collections — scales live at the FFN
    module as ``fc1_kernel``-style names, ops/lowp.py
    ``lowp_scale_site``) and closes it over ``lowp_matmul``. Falls back
    to the legacy fp8 hook / plain dot when the arm is bf16 or no scale
    collection rode this apply (init, eval, the gram teacher)."""
    arm = getattr(module, "lowp_arm", "bf16")
    if arm == "bf16" or not module.has_variable("lowp", kernel):
        return _dense_kwargs(module.fp8)
    from dinov3_tpu.ops.lowp import make_lowp_dot_general

    return {"dot_general": make_lowp_dot_general(
        module.get_variable("lowp", kernel), arm)}


def exact_gelu(x):
    """erf-based GELU — what torch ``nn.GELU()`` (and hence Meta's DINOv3)
    computes; flax's ``nn.gelu`` defaults to the tanh approximation, which
    diverges from the released weights' semantics by up to ~1e-3."""
    import jax

    return jax.nn.gelu(x, approximate=False)


class Mlp(nn.Module):
    hidden_dim: int
    out_dim: int | None = None
    act: Callable = exact_gelu
    use_bias: bool = True
    dropout_rate: float = 0.0
    fp8: bool = False
    lowp_arm: str = "bf16"  # train.low_precision.arm (ops/lowp.py)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        out_dim = self.out_dim or x.shape[-1]
        x = nn.Dense(
            self.hidden_dim, use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=part(trunc_normal_init(), ("embed", "mlp")),
            bias_init=part(nn.initializers.zeros, ("mlp",)),
            name="fc1", **_lowp_dense_kwargs(self, "fc1_kernel"),
        )(x)
        x = self.act(x)
        x = nn.Dropout(self.dropout_rate)(x, deterministic=deterministic)
        x = nn.Dense(
            out_dim, use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=part(trunc_normal_init(), ("mlp", "embed")),
            bias_init=part(nn.initializers.zeros, ("embed",)),
            name="fc2", **_lowp_dense_kwargs(self, "fc2_kernel"),
        )(x)
        x = nn.Dropout(self.dropout_rate)(x, deterministic=deterministic)
        return x


def swiglu_hidden_dim(hidden_dim: int, align_to: int = 8) -> int:
    """2/3 rule rounded up to a lane-friendly multiple."""
    d = int(hidden_dim * 2 / 3)
    return (d + align_to - 1) // align_to * align_to


class SwiGLUFFN(nn.Module):
    hidden_dim: int
    out_dim: int | None = None
    use_bias: bool = True
    align_to: int = 64  # keep the hidden dim MXU/lane aligned on TPU
    fp8: bool = False
    lowp_arm: str = "bf16"  # train.low_precision.arm (ops/lowp.py)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        out_dim = self.out_dim or x.shape[-1]
        d = swiglu_hidden_dim(self.hidden_dim, self.align_to)
        # fused [gate | value] projection: one big MXU matmul
        w12 = nn.Dense(
            2 * d, use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=part(trunc_normal_init(), ("embed", "mlp")),
            bias_init=part(nn.initializers.zeros, ("mlp",)),
            name="w12", **_lowp_dense_kwargs(self, "w12_kernel"),
        )(x)
        gate, value = jnp.split(w12, 2, axis=-1)
        x = nn.silu(gate) * value
        return nn.Dense(
            out_dim, use_bias=self.use_bias, dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=part(trunc_normal_init(), ("mlp", "embed")),
            bias_init=part(nn.initializers.zeros, ("embed",)),
            name="w3", **_lowp_dense_kwargs(self, "w3_kernel"),
        )(x)


class MoEFFN(nn.Module):
    """Mixture-of-experts FFN with expert parallelism (beyond the
    reference, which has no MoE — SURVEY.md §2.5 "EP — absent").

    Dense (dropless) formulation: a linear router picks top-k experts per
    token; every expert computes every token and outputs combine weighted
    by the (renormalized) router probabilities, zero for non-selected
    experts. FLOPs are ``num_experts`` times a dense MLP of the same
    hidden size (``num_experts/top_k`` times a sparse top-k dispatch) —
    the right trade below ~16 experts, where the alternative
    (gather/scatter token dispatch) costs an all-to-all and ragged matmuls
    that XLA cannot tile well. Expert params are stacked [E, ...] with the "experts" logical
    axis -> ``expert`` mesh axis: each expert-parallel device computes its
    own experts and XLA inserts one activation-sized all-reduce for the
    combine.

    An auxiliary load-balancing loss (Switch-style: E * sum_e f_e * p_e)
    is stored in the "losses" collection under "moe_aux_loss".
    """

    hidden_dim: int
    num_experts: int = 8
    top_k: int = 2
    out_dim: int | None = None
    act: Callable = exact_gelu
    use_bias: bool = True
    fp8: bool = False  # accepted for make_ffn_layer symmetry; dense path only
    lowp_arm: str = "bf16"  # symmetry only (setup raises on lowp + moe)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        import jax

        D = x.shape[-1]
        out_dim = self.out_dim or D
        E, H, K = self.num_experts, self.hidden_dim, self.top_k
        if not 1 <= K <= E:
            raise ValueError(f"top_k={K} must be in [1, {E}]")

        router = nn.Dense(
            E, use_bias=False, dtype=jnp.float32,
            param_dtype=self.param_dtype,
            kernel_init=part(trunc_normal_init(), ("embed", None)),
            name="router",
        )
        w1 = self.param(
            "w1", part(trunc_normal_init(), ("experts", "embed", "mlp")),
            (E, D, H), self.param_dtype,
        )
        w2 = self.param(
            "w2", part(trunc_normal_init(), ("experts", "mlp", None)),
            (E, H, out_dim), self.param_dtype,
        )
        b1 = b2 = None
        if self.use_bias:
            b1 = self.param("b1", part(nn.initializers.zeros, ("experts", "mlp")),
                            (E, H), self.param_dtype)
            b2 = self.param("b2", part(nn.initializers.zeros, ("experts", None)),
                            (E, out_dim), self.param_dtype)

        probs = jax.nn.softmax(router(x.astype(jnp.float32)), axis=-1)  # [..., E]
        top_p, top_idx = jax.lax.top_k(probs, K)
        # renormalize over the selected experts; scatter back to dense [E]
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        gate = jnp.sum(
            jax.nn.one_hot(top_idx, E, dtype=probs.dtype) * top_p[..., None],
            axis=-2,
        )  # [..., E], zero for unselected experts

        # Switch-style load-balance aux loss over all tokens in the batch
        flat_gate = gate.reshape(-1, E)
        frac_tokens = jnp.mean((flat_gate > 0).astype(jnp.float32), axis=0)
        frac_probs = jnp.mean(probs.reshape(-1, E), axis=0)
        self.sow("losses", "moe_aux_loss",
                 E * jnp.sum(frac_tokens * frac_probs))

        xc = x.astype(self.dtype)
        h = jnp.einsum("...d,edh->e...h", xc, w1.astype(self.dtype))
        if b1 is not None:
            h = h + b1.astype(self.dtype).reshape((E,) + (1,) * (x.ndim - 1) + (H,))
        h = self.act(h)
        y = jnp.einsum("e...h,eho->e...o", h, w2.astype(self.dtype))
        if b2 is not None:
            y = y + b2.astype(self.dtype).reshape((E,) + (1,) * (x.ndim - 1) + (out_dim,))
        # combine: weighted sum over experts (all-reduce over the expert
        # mesh axis under GSPMD)
        gate_e = jnp.moveaxis(gate, -1, 0).astype(self.dtype)  # [E, ...]
        return jnp.sum(y * gate_e[..., None], axis=0)


# The compact row buffer of ``RoutedExpertsFFN`` holds this many times
# the rows its experts get under an even router. A CAPACITY, not a bound:
# the true bound is every choice there is (``n_tokens * min(top_k, held)``,
# 16 times the even share at 8 of 256 experts and top-8), which no chip
# has the memory for. What a capacity-factor layer usually does with the
# pairs past it is drop them silently; this one counts them and the
# meta-arch makes the loss non-finite, so a run stops instead of training
# on tokens that lost experts.
ROWS_CAPACITY_FACTOR = 2.0


def routed_rows_capacity(n_tokens: int, top_k: int, num_experts: int,
                         held: int, factor: float = ROWS_CAPACITY_FACTOR) -> int:
    """Rows of the compact buffer of ``RoutedExpertsFFN``: ``factor``
    times the rows its ``held`` experts get when the router spreads
    ``n_tokens * top_k`` choices evenly over ``num_experts``, rounded up
    to a multiple of 128 and never more than every choice there is
    (``n_tokens * min(top_k, held)``: past that nothing can overflow)."""
    expected = n_tokens * top_k * held / num_experts
    rows = -(-int(math.ceil(factor * expected)) // 128) * 128
    return max(1, min(rows, n_tokens * min(top_k, held)))


class RoutedExpertsFFN(nn.Module):
    """The routed experts of one expert-parallel shard: a router over
    all ``num_experts``, the gated experts ``[first, first + held)`` of
    them held here (``shard`` of ``shards`` equal ones), and the part of
    the layer's result that those give.

        router "sigmoid": s = sigmoid(W_r x_r) (float32); the top_k
            largest of s + bias; w = scale * s_sel / (sum(s_sel) +
            ``norm_eps``) (0 where a family's normaliser has none);
        router "softmax": r = W_r x_r (float32); the top_k largest of r;
            w = scale * softmax(r_sel) (= the softmax over all experts,
            renormalised over the chosen ones); no bias exists;
        y = sum over the selected experts e held here of
            w_e W3_e (act(g) * u), [g ; u] = W12_e x, act = ``gate``
            ("silu": SwiGLU, "relu": ReGLU); ``gate`` "relu2" is an
            UN-GATED expert of two matrices, w_e W2_e relu(W1_e x)^2,
            held as ``w1`` [held, D, H] and ``w2`` [held, H, D]

    ``x_r`` is ``x`` unless the caller hands in ``router_input`` (same
    leading shape): a layer whose router reads another tensor than its
    experts do.

    What the absent experts would add is another shard's to compute and
    an all-to-all's to bring; on one shard the layer runs without that
    exchange. The (token, choice) pairs routed to a held expert are
    gathered, sorted by expert, into a compact ``[rows, D]`` buffer, two
    grouped matmuls run over the rows each expert was sent and no others
    (``ops/grouped_matmul.py``: its kernels where ``grouped_matmul_path``
    takes the shape, ``lax.ragged_dot`` elsewhere), and the weighted rows
    are added back to their tokens. ``rows`` is
    ``routed_rows_capacity``: no token is dropped while the held experts
    draw at most ``rows_factor`` times their even share; beyond it
    ``overflow`` counts the pairs left out, for the caller to make the
    loss non-finite with.

    The selection bias takes no gradient (it is the balancing signal of
    an aux-loss-free router, moved by its own rule and not by the loss).

    Returns ``(y, aux)``: ``choice`` [N, top_k] int32 (the experts each
    token chose, over all of them), ``rows`` (pairs routed here),
    ``capacity``, ``overflow`` and ``load_max_over_mean`` (largest held
    expert's rows over the held experts' mean), float32 scalars.
    """

    hidden_dim: int
    num_experts: int
    top_k: int
    shards: int = 1
    shard: int = 0
    scale: float = 1.0
    rows_factor: float = ROWS_CAPACITY_FACTOR
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    router: str = "sigmoid"   # | "softmax"
    gate: str = "silu"        # | "relu" | "relu2" (un-gated)
    norm_eps: float = 0.0     # "sigmoid": added to the chosen scores' sum
    # ``grouped_matmul_path``'s: None = this backend's path, True = the
    # kernels interpreted (a test's way onto the kernel path off the chip)
    interpret: bool | None = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, router_input: jnp.ndarray | None = None):
        import jax

        from dinov3_tpu.ops.grouped_matmul import (
            experts_block, grouped_matmul_path, ragged_experts_block, row_tile)
        from dinov3_tpu.ops.routed_rows import (
            combine_form, combine_rows, dispatch_rows, row_lists)

        E, K, H = self.num_experts, self.top_k, self.hidden_dim
        if E % self.shards or not 0 <= self.shard < self.shards:
            raise ValueError(
                f"shard {self.shard} of {self.shards} over {E} experts")
        if self.router not in ("sigmoid", "softmax") \
                or self.gate not in ("silu", "relu", "relu2"):
            raise ValueError(f"router {self.router!r}, gate {self.gate!r}")
        held = E // self.shards
        first = self.shard * held
        D = x.shape[-1]
        x2 = x.reshape(-1, D)
        N = x2.shape[0]
        cap = routed_rows_capacity(N, K, E, held, self.rows_factor)

        xr = x2 if router_input is None else router_input.reshape(-1, D)
        router = self.param(
            "router", part(trunc_normal_init(), ("embed", None)),
            (D, E), self.param_dtype)
        if self.router == "sigmoid":
            bias = self.param(
                "router_bias", part(nn.initializers.zeros, (None,)),
                (E,), self.param_dtype)
        gated = self.gate != "relu2"
        w12 = self.param(
            "w12" if gated else "w1",
            part(trunc_normal_init(), ("experts", "embed", "mlp")),
            (held, D, 2 * H if gated else H), self.param_dtype)
        w3 = self.param(
            "w3" if gated else "w2",
            part(trunc_normal_init(), ("experts", "mlp", "embed")),
            (held, H, D), self.param_dtype)

        with jax.named_scope("moe_route"):
            s = jnp.dot(
                xr.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            if self.router == "sigmoid":
                s = jax.nn.sigmoid(s)
                _, choice = jax.lax.top_k(
                    s + jax.lax.stop_gradient(bias.astype(jnp.float32)), K)
                s_sel = jnp.take_along_axis(s, choice, axis=-1)
                if self.norm_eps:
                    w = self.scale * s_sel / (
                        jnp.sum(s_sel, axis=-1, keepdims=True) + self.norm_eps)
                else:
                    w = self.scale * s_sel / jnp.sum(s_sel, axis=-1, keepdims=True)
            else:
                _, choice = jax.lax.top_k(s, K)
                w = self.scale * jax.nn.softmax(
                    jnp.take_along_axis(s, choice, axis=-1), axis=-1)
            # the pairs of held experts, expert by expert, first
            local = (choice - first).reshape(-1)
            here = (local >= 0) & (local < held)
            key = jnp.where(here, local, held)
            order = jnp.argsort(key, stable=True)[:cap]
            counts = jnp.sum(
                key[:, None] == jnp.arange(held, dtype=key.dtype)[None],
                axis=0, dtype=jnp.int32)
            n_here = jnp.sum(counts)
            before = jnp.cumsum(counts) - counts
            sizes = jnp.clip(cap - before, 0, counts)
            kept = jnp.arange(cap) < jnp.minimum(n_here, cap)
            w_rows = jnp.where(kept, w.reshape(-1).at[order].get(
                mode="promise_in_bounds", unique_indices=True), 0.0)
            # the index lists of both row movements, integers only, once
            form = combine_form(N, cap, D, self.interpret)
            lists = row_lists(order, kept, N, K, form)

        with jax.named_scope("moe_experts"):
            # both forms answer for the rows past the last group, values
            # and gradients alike: the kernels never read them, and
            # ``ragged_experts_block`` masks them at both ends
            # (``ragged_dot``'s need: PR 27's fault)
            rows = dispatch_rows(x2.astype(self.dtype), lists, self.interpret)
            path, _ = grouped_matmul_path(cap, D, H, self.dtype,
                                          self.interpret, self.gate)
            if path == "kernel":
                out = experts_block(rows, w12, w3, w_rows, sizes, self.gate,
                                    row_tile(cap), bool(self.interpret))
            else:
                out = ragged_experts_block(rows, w12, w3, w_rows, sizes, kept,
                                           self.gate)
            y = combine_rows(out, lists, N, self.dtype, self.interpret)

        f32 = lambda v: jnp.asarray(v, jnp.float32)  # noqa: E731
        aux = {
            "choice": choice.astype(jnp.int32),
            "rows": f32(n_here),
            "capacity": f32(cap),
            "overflow": f32(jnp.maximum(n_here - cap, 0)),
            "load_max_over_mean": f32(jnp.max(counts)) * held
            / jnp.maximum(f32(n_here), 1.0),
        }
        return y.reshape(x.shape), aux


def make_ffn_layer(kind: str, hidden_dim: int, *, moe_num_experts: int = 8,
                   moe_top_k: int = 2, **kwargs) -> nn.Module:
    if kind == "mlp":
        return Mlp(hidden_dim=hidden_dim, **kwargs)
    if kind in ("swiglu", "swiglu64", "swiglu128"):
        align = {"swiglu": 8, "swiglu64": 64, "swiglu128": 128}[kind]
        return SwiGLUFFN(hidden_dim=hidden_dim, align_to=align, **kwargs)
    if kind == "moe":
        return MoEFFN(hidden_dim=hidden_dim, num_experts=moe_num_experts,
                      top_k=moe_top_k, **kwargs)
    raise ValueError(f"unknown ffn layer {kind!r}")
