"""Low-precision training arms: fp8/int8 block matmuls on the ZeRO-3
stream (ROADMAP item 3; the training-side extension of the PR-12 serving
quantization discipline).

One switch — ``train.low_precision.arm`` (bf16 | fp8 | int8), bf16
default = today's bitwise-unchanged path — quantizes exactly the
``stream_castable_path`` attn/mlp matmul KERNELS (``lowp_kernel_path``,
the same leaf rule the int8 serving engine uses) for the block matmuls:

- **Per-tensor delayed scaling.** Each castable kernel carries an amax
  history ring in the train state (``TrainState.lowp``: f32 [H] per
  kernel, [L, H] under the block scan); the step's weight scale is
  ``scale_margin * max(history) / qmax`` — one step behind the masters,
  so the scale is a compile-time-free constant of the forward and no
  amax sync sits on the critical matmul path (the FP8-LM / Transformer
  Engine recipe). Histories advance AFTER the optimizer update from the
  new masters under the ``lowp_amax`` named scope (the amax over a
  zero3-sharded master is a tiny all-reduce-max the census attributes).
  Activations use current per-tensor scaling (one amax per tensor,
  stop-gradient), matching ``fp8_dot_general``'s convention.
- **The cast rides the bf16-before-gather hook.** Under the zero3
  stream (``ops/block.py _zero3_stream_trans_in``) the castable KERNEL
  leaves skip the bf16 gather; ``lowp_matmul`` quantizes the sharded
  bf16 view shard-locally and gathers the 1-byte codes under the SAME
  ``zero3_stream`` named scope — identical collective counts, ~2x fewer
  streamed bytes (COST_LP_r21.json). Biases/norms/gammas keep the plain
  bf16/f32 stream; masters, Adam moments, and the EMA teacher's
  STORAGE are untouched (the teacher's forward runs the same quantized
  matmuls — its fp32 EMA state never sees a quantizer).
- **Real quantized dots.** ``jax.lax.dot_general`` on the quantized
  operands with ``preferred_element_type`` (int32 accum for int8, f32
  for fp8), dequantized by ``s_x * s_w`` in a ``lowp_dequant`` named
  scope the PR-13 anatomy ledger attributes. The backward is a
  module-level ``jax.custom_vjp`` (the ``_softmax_lowp`` idiom —
  defined ONCE, config static, or flax re-wraps per call and nn.scan
  trips the tracer leak): straight-through wrt the quantization, dx
  from the RE-GATHERED dequantized codes (the backward never gathers
  fp32/bf16 masters — the FSDP gather-twice discipline at 1-byte
  rates), full dw back to the masters.

Scales reach the modules as a read-only ``"lowp"`` flax variable
collection mirroring the module tree (``module.apply({"params": p,
"lowp": scales}, ...)``), sliced per layer by ``nn.scan`` via
``variable_axes={"lowp": 0}``; a module only engages its lowp path when
``lowp_arm != "bf16"`` AND the scale variable exists, so init, eval,
and the gram teacher (never handed a collection) stay on the bf16 path
with zero signature changes.

CPU-harness honesty (docs/PERFORMANCE.md): XLA:CPU emulates the fp8/int8
dots by upconversion, so the CPU tier pins numerics and the streamed
collective-bytes census; the speed claim is banked by the phQ on-chip
A/B (not yet run).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

LOWP_ARMS = ("bf16", "fp8", "int8")


class QSpec(NamedTuple):
    """One quantized arm: storage dtype, symmetric max code, accumulator
    dtype for ``preferred_element_type``."""

    qdtype: Any
    qmax: float
    acc_dtype: Any


_QSPECS = {
    # float8_e4m3 finite max (ops/common.py _F8_MAX); fp8 dots accumulate f32
    "fp8": QSpec(jnp.float8_e4m3fn, 448.0, jnp.float32),
    # symmetric int8 ([-127, 127], -128 unused — serve/quant.py convention);
    # int8 dots accumulate exactly in int32
    "int8": QSpec(jnp.int8, 127.0, jnp.int32),
}


def qspec(arm: str) -> QSpec:
    if arm not in _QSPECS:
        raise ValueError(
            f"unknown low-precision arm {arm!r}; expected one of {LOWP_ARMS}"
        )
    return _QSPECS[arm]


# ---------------------------------------------------------------------
# scale math — ONE implementation shared with the int8 serving engine
# (serve/quant.py quantize_leaf delegates here with xp=numpy, so the
# training and serving quantizers can never drift apart numerically)
# ---------------------------------------------------------------------

def symmetric_scale(amax, qmax, xp=jnp):
    """``amax / qmax`` with zero-amax channels pinned to scale 1.0 (the
    divide stays exact and dequant returns exact zeros — serve/quant.py
    convention). Works on numpy (host serving quantizer) and jnp
    (traced training quantizer) alike."""
    return xp.where(
        amax > 0, amax / xp.float32(qmax), xp.float32(1.0)
    ).astype(xp.float32)


def symmetric_quantize(w, scale, qmax, qdtype, xp=jnp):
    """Symmetric quantization of ``w`` by a precomputed ``scale``:
    integer arms round half-to-even (``rint``, the serving convention)
    and clip to [-qmax, qmax]; float arms (fp8) clip to the finite range
    and let the dtype cast do the rounding."""
    w32 = w.astype(xp.float32) / scale
    if xp.issubdtype(xp.dtype(qdtype), xp.integer):
        w32 = xp.rint(w32)
    return xp.clip(w32, -qmax, qmax).astype(qdtype)


def scale_from_history(hist, qmax: float, margin: float):
    """Delayed-scaling weight scale from one amax history ring:
    ``margin * max(history) / qmax`` over the ring axis (last), zero-safe
    (an all-zero history — a dead kernel — scales by 1.0)."""
    amax = jnp.max(hist.astype(jnp.float32), axis=-1)
    return symmetric_scale(jnp.float32(margin) * amax, qmax)


def current_scale(x, qmax: float):
    """Current (per-tensor, stop-gradient) activation scale — the
    ``fp8_dot_general`` convention (ops/common.py): amax floored at
    1e-12 so a zero tensor quantizes to zeros with a finite scale."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    return jax.lax.stop_gradient(
        jnp.maximum(amax, 1e-12) / jnp.float32(qmax))


# ---------------------------------------------------------------------
# the quantized-kernel leaf rule (shared with serve/quant.py)
# ---------------------------------------------------------------------

def lowp_kernel_path(path) -> bool:
    """Whether the param leaf at ``path`` runs the low-precision matmul:
    an attn/mlp matmul KERNEL by the stream-castable rule (ops/block.py
    ``stream_castable_path``) narrowed to ``*kernel`` leaves — exactly
    the set the int8 serving engine quantizes (serve/quant.py
    ``quantizable_path`` delegates here). Biases stay on the bf16
    stream; norm scales, layerscale gammas, and the MoE router were
    never castable at all."""
    from dinov3_tpu.ops.block import stream_castable_path

    if not path or not stream_castable_path(path):
        return False
    last = str(getattr(path[-1], "key", getattr(path[-1], "idx", path[-1])))
    return "kernel" in last


def _path_keys(path) -> list[str]:
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


def lowp_scale_site(path) -> tuple[tuple[str, ...], str]:
    """Where a kernel's scale lives in the ``"lowp"`` collection: flax
    ``nn.Dense`` kernels (params path ``(..., "fc1", "kernel")``) fold
    into their parent module as ``fc1_kernel`` — the Dense submodule
    cannot read sibling collections, so the owning FFN module reads the
    scale and passes a closure; attention kernels (``qkv_kernel`` /
    ``proj_kernel``) are direct params of the attn module and keep
    their name in place."""
    keys = _path_keys(path)
    if keys[-1] == "kernel":
        return tuple(keys[:-2]), f"{keys[-2]}_kernel"
    return tuple(keys[:-1]), keys[-1]


# ---------------------------------------------------------------------
# delayed-scaling state: amax history rings in TrainState.lowp
# ---------------------------------------------------------------------

def lowp_amax_tree(backbone_params) -> dict:
    """Per-kernel amax of a backbone param tree, placed at each
    kernel's ``lowp_scale_site`` — the collection-shaped tree every
    history/scale helper below maps over. Scanned stacks (any exact
    ``blocks`` path component — ``blocks_i`` is the unrolled arm)
    reduce over the non-layer axes to [L]; unrolled kernels reduce to a
    scalar. The amax of a zero3-SHARDED master is a cross-shard max
    (one tiny all-reduce, ``lowp_amax`` scope at the call sites)."""
    out: dict = {}
    for path, leaf in jtu.tree_flatten_with_path(backbone_params)[0]:
        if not hasattr(leaf, "dtype") or not lowp_kernel_path(path):
            continue
        keys = _path_keys(path)
        axes = tuple(range(1, leaf.ndim)) if "blocks" in keys else None
        amax = jnp.max(jnp.abs(leaf.astype(jnp.float32)), axis=axes)
        parent, name = lowp_scale_site(path)
        node = out
        for k in parent:
            node = node.setdefault(k, {})
        node[name] = amax
    return out


def lowp_history_init(backbone_params, history_len: int) -> dict:
    """Fresh amax history rings, every slot filled with the CURRENT
    masters' amax (not zeros: a zero history would scale the first
    ``history_len`` steps by 1.0 — wildly wrong for ~0.02-std kernels
    — and delayed scaling would start from a divergence)."""
    amax = lowp_amax_tree(backbone_params)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(
            a[..., None], a.shape + (int(history_len),)
        ).astype(jnp.float32),
        amax,
    )


def lowp_history_step(hist_tree, backbone_params):
    """Advance every history ring one step: drop the oldest amax, append
    the NEW masters' (post-update) amax. Runs after the optimizer /
    EMA update under the ``lowp_amax`` named scope (train/fused_update
    ``lowp_state_step``) so next step's scales see this step's
    weights."""
    with jax.named_scope("lowp_amax"):
        new = lowp_amax_tree(backbone_params)
        return jax.tree.map(
            lambda h, a: jnp.concatenate(
                [h[..., 1:], a[..., None].astype(jnp.float32)], axis=-1),
            hist_tree, new,
        )


def lowp_scales(hist_tree, arm: str, margin: float):
    """History rings -> the ``"lowp"`` variable collection of per-kernel
    delayed scales ([L] per scanned kernel, scalar unrolled)."""
    spec = qspec(arm)
    return jax.tree.map(
        lambda h: scale_from_history(h, spec.qmax, margin), hist_tree)


# ---------------------------------------------------------------------
# the quantized matmul (module-level custom_vjp; arm static)
# ---------------------------------------------------------------------

def _gather_codes(q):
    """Materialize (replicate) quantized codes for the dot under the
    ``zero3_stream`` scope — the SAME scope (and so the same census
    attribution and identical collective count) as the bf16 stream this
    replaces, at 1-byte rates. The codes inherit the sharded master's
    placement through the elementwise quantizer, so the gather moves
    the codes, not the wide operand. No-op without a mesh."""
    from dinov3_tpu.parallel.context import get_current_mesh
    from dinov3_tpu.parallel.sharding import constrain_replicated

    mesh = get_current_mesh()
    with jax.named_scope("zero3_stream"):
        return constrain_replicated(q, mesh) if mesh is not None else q


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def lowp_matmul(arm: str, x, w, scale):
    """``x @ w`` through the quantized arm: w by its delayed per-tensor
    ``scale`` (quantized SHARD-LOCAL, codes gathered under
    ``zero3_stream``), x by current scaling, ``lax.dot_general`` on the
    codes with the arm's accumulator ``preferred_element_type``, dequant
    epilogue under ``lowp_dequant``. x: [..., K] (compute dtype),
    w: [K, N] (the bf16 stream view of the master), scale: f32 scalar."""
    out, _ = _lowp_matmul_fwd(arm, x, w, scale)
    return out


def _lowp_matmul_fwd(arm, x, w, scale):
    spec = qspec(arm)
    scale = jax.lax.stop_gradient(scale.astype(jnp.float32))
    q_w = symmetric_quantize(w, scale, spec.qmax, spec.qdtype)
    q_w_rep = _gather_codes(q_w)
    with jax.named_scope("lowp_amax"):
        s_x = current_scale(x, spec.qmax)
    q_x = symmetric_quantize(x, s_x, spec.qmax, spec.qdtype)
    acc = jax.lax.dot_general(
        q_x, q_w_rep, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=spec.acc_dtype,
    )
    with jax.named_scope("lowp_dequant"):
        out = (acc.astype(jnp.float32) * (s_x * scale)).astype(x.dtype)
    return out, (q_x, s_x, q_w, scale)


def _lowp_matmul_bwd(arm, res, g):
    """Straight-through backward on the DEQUANTIZED codes: dx re-gathers
    the saved 1-byte weight codes (never the wide masters) under the
    same ``zero3_stream`` scope; dw contracts the quantized-activation
    view with the cotangent — the STE wrt both quantizers (scales carry
    stop_gradient, zero cotangent)."""
    q_x, s_x, q_w, scale = res
    q_w_rep = _gather_codes(q_w)
    w_hat = (q_w_rep.astype(jnp.float32) * scale).astype(g.dtype)
    x_hat = (q_x.astype(jnp.float32) * s_x).astype(g.dtype)
    dx = jax.lax.dot_general(
        g, w_hat, (((g.ndim - 1,), (1,)), ((), ())))
    batch = tuple(range(g.ndim - 1))
    dw = jax.lax.dot_general(x_hat, g, ((batch, batch), ((), ())))
    return dx, dw, jnp.zeros_like(scale)


lowp_matmul.defvjp(_lowp_matmul_fwd, _lowp_matmul_bwd)


def make_lowp_dot_general(scale, arm: str):
    """Drop-in ``dot_general`` for ``nn.Dense`` routing through
    ``lowp_matmul`` (the ``_dense_kwargs`` hook, ops/ffn.py). Dense
    always contracts its input's last dim with kernel dim 0 — anything
    else is a wiring bug this raises on."""

    def dg(lhs, rhs, dimension_numbers, precision=None,
           preferred_element_type=None):
        expected = (((lhs.ndim - 1,), (0,)), ((), ()))
        if dimension_numbers != expected:
            raise NotImplementedError(
                f"lowp dot_general only supports the Dense contraction "
                f"{expected}, got {dimension_numbers}")
        return lowp_matmul(arm, lhs, rhs, scale)

    return dg


# ---------------------------------------------------------------------
# drift probe (warn_lowp_divergence, configs/config.py)
# ---------------------------------------------------------------------

def lowp_drift_probe(backbone_params, hist_tree, arm: str, margin: float,
                     seed: int = 0) -> dict:
    """Device-side per-kernel drift of the lowp matmul vs its bf16
    shadow on a SAMPLED layer (layer 0 of each scanned stack; every
    unrolled ``blocks_0`` kernel): relative Frobenius error of
    ``lowp_matmul(x, w)`` against ``x @ w`` in bf16 on a fixed normal
    probe batch. Returns ``{"<site>": drift}`` plus ``"max"`` — the
    number ``warn_lowp_divergence`` gates on at setup build and bench
    embeds per record."""
    scales = lowp_scales(hist_tree, arm, margin)
    drifts: dict = {}
    for path, leaf in jtu.tree_flatten_with_path(backbone_params)[0]:
        if not hasattr(leaf, "dtype") or not lowp_kernel_path(path):
            continue
        keys = _path_keys(path)
        if any(k.startswith("blocks_") and k != "blocks_0" for k in keys):
            continue  # sampled layer: the unrolled arm probes block 0
        parent, name = lowp_scale_site(path)
        node = scales
        for k in parent:
            node = node[k]
        s = node[name]
        w = leaf
        if "blocks" in keys:  # scanned [L, K, N]: probe layer 0
            w, s = w[0], s[0]
        w = w.astype(jnp.bfloat16)
        x = jax.random.normal(
            jax.random.key(seed), (8, w.shape[0]), jnp.bfloat16)
        ref = (x @ w).astype(jnp.float32)
        got = lowp_matmul(arm, x, w, s).astype(jnp.float32)
        denom = jnp.maximum(jnp.linalg.norm(ref), 1e-12)
        site = "/".join(parent + (name,))
        drifts[site] = float(jnp.linalg.norm(got - ref) / denom)
    drifts["max"] = max(
        [v for k, v in drifts.items() if k != "max"], default=0.0)
    return drifts
