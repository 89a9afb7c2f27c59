"""The elementwise chains between a delta-rule mixer's matmuls, each a
Pallas kernel pair: bfloat16 planes in, float32 in VMEM, out what the
next consumer takes, so that no float32 ``[tokens, heads * width]`` plane
crosses HBM and none is re-laid between the projections' ``[B, T, H * d]``
(8 tokens x 128 channels a tile) and the delta rule's ``[B, T, H, d]``
(``ops/kda.py``: 8 HEADS x 128 channels a tile, rows token-major and
head-minor). The relayout happens in VMEM: a head is one 128-lane group
of a ``[B, T, C]`` block and every H-th row of a ``[B, T * H, d]`` block,
read and written by strided loads and stores (bfloat16 a pair of heads a
32-bit word, ``ops/kda.py _pair_planes`` / ``_store_pair``).

Three chains shared by ``models/decoder.py KDAMixer`` and ``GDNMixer``
(which differ in what a kernel is GIVEN: which lane group of the input
holds which head, which outputs are normalised, eps, the gate's
activation, the convolution's width). A kernel's body is ONE pair of
heads inside a ``fori_loop`` over the pairs (lane offsets that are the
loop's index, hinted as multiples of the head width): unrolled over 16
pairs the bodies cost 15 s of a warm set-up's tracing (``PERF.md``, PR 38):

- ``conv_silu_norm``: y = SiLU(causal depthwise convolution of width W)
  and, where asked, y / sqrt(sum_d y^2 + eps^2) a head
  (``ops/common.py l2_normalize``), out of one ``[B, T, C]`` plane into
  one ``[B, T, H, d]`` plane an output. A grid step is a block of tokens
  of every head; the W - 1 tokens before it come from the previous
  block's last rows (a second, 16-row view of the same plane).
- ``gated_rms_norm``: o / sqrt(mean_d o^2 + eps) * scale * act(gate),
  o float32 ``[B, T, H, d]`` (the delta rule's output as it lies), gate a
  lane group a head of a bfloat16 ``[B, T, C]`` plane, out bfloat16
  ``[B, T, H * d]`` for the output projection.
- ``log_decay``: g = -exp(A_log) softplus(f + dt_bias), bfloat16
  ``[B, T, H * d]`` to the float32 ``[B, T, H, d]`` the delta rule reads.

and a fourth, ``ShortConvMixer``'s whole sequence mixing, with no heads
and no relayout (planes ``[B, T, channels]`` in and out), on the same
block / tail / taps-gradient machinery:

- ``gated_short_conv``: y = C * conv(B * u), the causal depthwise
  convolution of width W between two multiplicative gates and no
  activation, [B ; C ; u] the three lane groups of ONE ``[B, T, 3 C]``
  plane read where they lie; the backward writes [dB ; dC ; du] as one
  such plane.

and the two chains of ``Mamba2Mixer`` around its scan, flat planes too
(``ops/ssd.py`` takes and returns ``[B, T, channels]``), their grid
(sequence, LANE block, time block): the channels are independent, or
independent a group, so a block of lanes is a parallel axis:

- ``ssm_conv_silu``: y = SiLU(conv(x) + bias), the causal depthwise
  convolution of width W WITH a bias and an activation and no gates, over
  the ``joined`` channels [u | B | C] that start at lane ``first`` of
  in_proj's plane, cut from the plane where they lie. The backward sums
  the taps' and the bias's gradients ([W, C] and [C], rows of one
  ``[8, C]`` float32 block a sequence) and carries ds like the others.
- ``ssm_gate_norm``: out = v / sqrt(mean_group v^2 + eps) * scale with
  v = (y + D u) * SiLU(z): the skip, the gate BEFORE the norm, the norm
  over a GROUP's channels (``inner / groups``, whole lane tiles); y the
  scan's output, u the first ``inner`` lanes of the convolution's plane,
  z the first ``inner`` lanes of in_proj's, D a lane vector (a head's
  value over its channels, repeated outside). The backward writes dy, du
  and dz and sums the gradients of D and of the scale a lane (two rows
  of one ``[8, inner]`` float32 block a sequence; D's is folded to a
  value a head outside, by JAX's transposition of the repeat).

Each is a ``jax.custom_vjp`` whose residuals are the chain's INPUTS and
nothing else (what the plain chains' ``jax.checkpoint`` keeps); the
backward kernel makes the float32 intermediates again in VMEM and applies
the chain's derivative in closed form. The gradients of the small
parameters (the ``[W, C]`` taps, the norm's scale, the decay's two
vectors) are summed in float32 in an output block that stays in VMEM over
a sequence's grid steps, a sequence a row, and added up outside. The
convolution's backward runs the blocks LAST FIRST and carries the first
rows of a block's pre-activation gradient to the block before it (the
taps reach W - 1 tokens ahead).

Rounding is where the plain chains round: the inputs are the bfloat16
projection results, everything inside is float32, the outputs are rounded
once (to nearest even) or stay float32. ``mixer_chain_path`` (and
``ssm_chain_path`` for ``Mamba2Mixer``'s pair) reads the path off what it
can see; the plain chains live in the mixers.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dinov3_tpu.ops.kda import _pair_planes, _store_pair

TIME_BLOCK = 128   # tokens a grid step
LANES = 128
_HALO = 16         # rows of the previous block a step is handed (a bf16 tile)
_EDGE = 8          # of them, and of a carried gradient, the rows used
CONV_KERNEL_NAME = "conv_silu_norm_fwd"
CONV_BACKWARD_KERNEL_NAME = "conv_silu_norm_bwd"
NORM_KERNEL_NAME = "gated_rms_norm_fwd"
NORM_BACKWARD_KERNEL_NAME = "gated_rms_norm_bwd"
DECAY_KERNEL_NAME = "log_decay_fwd"
DECAY_BACKWARD_KERNEL_NAME = "log_decay_bwd"
GATED_CONV_KERNEL_NAME = "gated_short_conv_fwd"
GATED_CONV_BACKWARD_KERNEL_NAME = "gated_short_conv_bwd"
SSM_CONV_KERNEL_NAME = "ssm_conv_silu_fwd"
SSM_CONV_BACKWARD_KERNEL_NAME = "ssm_conv_silu_bwd"
SSM_NORM_KERNEL_NAME = "ssm_gate_norm_fwd"
SSM_NORM_BACKWARD_KERNEL_NAME = "ssm_gate_norm_bwd"
SSM_TIME_BLOCK = 256   # tokens a grid step of Mamba2Mixer's two chains
SSM_LANE_BLOCK = 2048  # channels a grid step of them, at most
# a step holds its blocks twice over (the pipeline's two buffers): 4 MB a
# 128 x 8192 bfloat16 plane and its gradient, past what a kernel gets unasked
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),  # sequences, time blocks
    vmem_limit_bytes=64 * 1024 * 1024)
_ACTS = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}


def mixer_chain_path(length: int, head_dims, heads, dtype,
                     block: int = TIME_BLOCK,
                     interpret: bool | None = None) -> tuple[str, str]:
    """(path, why) the chains of a mixer take: ("kernel", ...) or
    ("plain", the reason it is not the kernels). ``head_dims`` and
    ``heads``: the head widths (key, value) and the head counts of the
    ``[B, T, H, d]`` planes the chains read or write (``gated_short_conv``,
    which has no heads: its channels as one width, no head count)."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "plain", f"the planes are {jnp.dtype(dtype).name}, not bfloat16"
    if len(set(head_dims)) != 1 or head_dims[0] % LANES:
        return "plain", (f"heads {tuple(head_dims)} wide, not one multiple "
                         f"of {LANES}")
    if any(h % 2 for h in heads):
        return "plain", f"head counts {tuple(heads)}: a pair of heads a word"
    if length % block:
        return "plain", f"{length} tokens are not whole blocks of {block}"
    backend = jax.default_backend()
    if interpret is None and backend != "tpu":
        return "plain", f"the backend is {backend}, not a TPU"
    return "kernel", "interpreted" if interpret else "compiled for the TPU"


def ssm_chain_path(length: int, inner: int, joined: int, groups: int, dtype,
                   block: int = SSM_TIME_BLOCK,
                   interpret: bool | None = None) -> tuple[str, str]:
    """``mixer_chain_path`` for ``Mamba2Mixer``'s two chains: ``inner``
    channels of y, u and z in ``groups`` norm groups, ``joined`` channels
    [u | B | C] under the convolution, ``length`` tokens a sequence."""
    if joined % LANES or inner % groups or (inner // groups) % LANES:
        return "plain", (f"{joined} joined channels, {inner} in {groups} norm "
                         f"groups: not whole lane tiles of {LANES}")
    return mixer_chain_path(length, (inner // groups,), (), dtype, block,
                            interpret)


# ------------------------------------------------------------ helpers


def _tokens_spec(rows: int, width: int, reverse_of: int | None = None):
    """A block of tokens of a [B, T, C] array (``rows`` tokens of ``width``
    channels) or of a [B, T * H, d] one (``rows`` = tokens x heads: every
    head of them); with ``reverse_of`` = n grid step m is block n - 1 - m."""
    at = (lambda m: reverse_of - 1 - m) if reverse_of else (lambda m: m)
    return pl.BlockSpec((1, rows, width), lambda i, m: (i, at(m), 0),
                        memory_space=pltpu.VMEM)


def _whole_spec(shape):
    return pl.BlockSpec(shape, lambda i, m: (0,) * len(shape),
                        memory_space=pltpu.VMEM)


def _sums_spec(rows: int, c: int):
    """A sequence's row of a [B, rows, C] float32 sum: the same block at
    every time step, so it stays in VMEM until the sequence is done."""
    return pl.BlockSpec((None, rows, c), lambda i, m: (i, 0, 0),
                        memory_space=pltpu.VMEM)


def _rows_type(x_shape, heads: int, d: int, dtype, interpret: bool):
    """The type of a [B, T * H, d] output; the interpreter cannot store
    through a block's 32-bit view, so there a bfloat16 plane leaves the
    kernel as float32 and is rounded after (``ops/kda.py``)."""
    b, t = x_shape[:2]
    return jax.ShapeDtypeStruct(
        (b, t * heads, d), jnp.float32 if interpret else dtype)


def _lanes(group, d: int):
    """The ``d`` channels of lane group ``group`` (a head of a [B, T, C]
    block), static or a loop's index."""
    if isinstance(group, int):
        return slice(group * d, (group + 1) * d)
    return pl.ds(pl.multiple_of(group * d, d), d)


def _group(layout, head):
    """The lane group that holds ``head``: ``layout`` = (first, n, per)
    says head h lies at (h // n) * per + first + h % n: n heads side by
    side from ``first`` in every stretch of ``per`` groups."""
    first, n, per = layout
    return (head // n) * per + first + head % n


def _fed(layouts, heads):
    """The lane groups the heads of every output read, in order."""
    return [_group(layout, h) for layout, n in zip(layouts, heads)
            for h in range(n)]


def _each_pair(heads: int, body):
    """``body(pair)`` for every pair of heads, as ONE traced loop: the
    kernels' bodies are a pair long, not ``heads / 2`` pairs."""
    jax.lax.fori_loop(0, heads // 2, lambda pair, c: (body(pair), c)[1], 0)


def _shifted(x, edge, k: int, back: bool):
    """Row t of x [n, d] replaced by row t - k (``back``: the rows before
    x's first are ``edge``'s [8, d] last) or by row t + k (the rows past
    x's last are ``edge``'s first)."""
    n = x.shape[0]
    if k == 0:
        return x
    if back:
        return pltpu.roll(jnp.concatenate([edge, x], 0), k, 0)[_EDGE:]
    return pltpu.roll(jnp.concatenate([x, edge], 0), n + _EDGE - k, 0)[:n]


def _first_step_zeros(ref, axis: int = 1):
    @pl.when(pl.program_id(axis) == 0)
    def _():
        ref[...] = jnp.zeros_like(ref)


# ---------------------------------------- convolution + SiLU + unit norm


def _conv_pre(x_ref, halo_ref, w_ref, group, cols, d, width, starts):
    """The pre-activation s = conv(x) of one head, float32 [block, d], and
    the W shifted planes it is the weighted sum of (tap j reads the token
    W - 1 - j before). ``starts``: this block is its sequence's first and
    has nothing before it."""
    x = x_ref[0, :, _lanes(group, d)].astype(jnp.float32)
    before = halo_ref[0, :, _lanes(group, d)].astype(jnp.float32)[_EDGE:]
    before = jnp.where(starts, 0.0, before)
    planes = [_shifted(x, before, width - 1 - j, back=True)
              for j in range(width)]
    s = sum(w_ref[j:j + 1, cols] * planes[j] for j in range(width))
    return s, planes


def _conv_fwd_kernel(x_ref, halo_ref, *refs, layouts, heads, normalise, eps,
                     d, width, block):
    n = len(layouts)
    starts = pl.program_id(1) == 0     # (read outside the loops' bodies)
    for w_ref, y_ref, layout, h, unit in zip(refs[:n], refs[n:], layouts,
                                             heads, normalise):
        def one_pair(pair):        # (traced at once, inside this turn)
            out = []
            for head in (2 * pair, 2 * pair + 1):
                s, _ = _conv_pre(x_ref, halo_ref, w_ref, _group(layout, head),
                                 _lanes(head, d), d, width, starts)
                a = s * jax.nn.sigmoid(s)
                if unit:
                    a = a * jax.lax.rsqrt(
                        jnp.sum(a * a, axis=-1, keepdims=True) + eps * eps)
                out.append(a)
            _store_pair(y_ref, pair, h, *out, rows=block)

        _each_pair(h, one_pair)


def _conv_bwd_kernel(x_ref, halo_ref, *refs, layouts, heads, normalise, eps,
                     d, width, block, last_step):
    """The blocks come LAST FIRST (the wrapper's grid: a sequence's first
    block is grid step ``last_step``; ``halo_ref`` is still the rows
    BEFORE this block). ``carry_ref`` [8, channels] holds, head by head,
    the first rows of the pre-activation's gradient of the block after
    this one."""
    n = len(layouts)
    w_refs, dy_refs = refs[:n], refs[n:2 * n]
    dx_ref, dw_refs, carry_ref = refs[2 * n], refs[2 * n + 1:3 * n + 1], refs[-1]
    first = pl.program_id(1) == 0            # the sequence's LAST block
    starts = pl.program_id(1) == last_step
    if len(_fed(layouts, heads)) < x_ref.shape[-1] // d:
        dx_ref[...] = jnp.zeros_like(dx_ref)  # the groups no head reads
    for dw_ref in dw_refs:
        _first_step_zeros(dw_ref)
    at = 0
    for w_ref, dy_ref, dw_ref, layout, h, unit in zip(
            w_refs, dy_refs, dw_refs, layouts, heads, normalise):
        def one_pair(pair):
            for hd, dy in enumerate(
                    _pair_planes(dy_ref, pair, h, rows=block)):
                head = 2 * pair + hd
                group = _group(layout, head)
                cols, mine = _lanes(head, d), _lanes(at + head, d)
                s, planes = _conv_pre(x_ref, halo_ref, w_ref, group, cols, d,
                                      width, starts)
                sig = jax.nn.sigmoid(s)
                a = s * sig
                if unit:
                    r = jax.lax.rsqrt(
                        jnp.sum(a * a, axis=-1, keepdims=True) + eps * eps)
                    dy = r * (dy - a * (r * r) * jnp.sum(
                        dy * a, axis=-1, keepdims=True))
                ds = dy * sig * (1.0 + s * (1.0 - sig))
                after = jnp.where(first, 0.0, carry_ref[:, mine])
                carry_ref[:, mine] = ds[:_EDGE]
                dx = sum(w_ref[j:j + 1, cols] * _shifted(
                    ds, after, width - 1 - j, back=False)
                    for j in range(width))
                dx_ref[0, :, _lanes(group, d)] = dx.astype(dx_ref.dtype)
                for j in range(width):
                    dw_ref[j:j + 1, cols] += jnp.sum(
                        ds * planes[j], axis=0, keepdims=True)

        _each_pair(h, one_pair)
        at += h


def _conv_operands(x, block, reverse):
    """x's two views: a block of tokens, and the 16 rows before it (the
    first block is handed its own first rows, which the kernel zeroes)."""
    b, t, c = x.shape
    if block % _HALO:
        raise ValueError(f"a time block of {block} is not whole tiles of {_HALO}")
    n = t // block
    at = (lambda m: n - 1 - m) if reverse else (lambda m: m)
    per = block // _HALO
    halo = pl.BlockSpec(
        (1, _HALO, c), lambda i, m: (i, jnp.maximum(at(m) * per - 1, 0), 0),
        memory_space=pltpu.VMEM)
    return _tokens_spec(block, c, n if reverse else None), halo, n


def _head_counts(kernels, d):
    return tuple(w.shape[1] // d for w in kernels)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "layouts", "normalise", "eps", "d", "block", "interpret"))
def _conv_forward(x, kernels, layouts, normalise, eps, d, block, interpret):
    b, t, c = x.shape
    heads = _head_counts(kernels, d)
    spec, halo, n = _conv_operands(x, block, reverse=False)
    out = pl.pallas_call(
        functools.partial(_conv_fwd_kernel, layouts=layouts, heads=heads,
                          normalise=normalise, eps=eps, d=d,
                          width=kernels[0].shape[0], block=block),
        grid=(b, n),
        in_specs=[spec, halo] + [_whole_spec(w.shape) for w in kernels],
        out_specs=[_tokens_spec(block * h, d) for h in heads],
        out_shape=[_rows_type(x.shape, h, d, x.dtype, interpret)
                   for h in heads],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=CONV_KERNEL_NAME,
    )(x, x, *(w.astype(jnp.float32) for w in kernels))
    return tuple(y.astype(x.dtype).reshape(b, t, h, d)
                 for y, h in zip(out, heads))


@functools.partial(jax.jit, inline=True, static_argnames=(
    "layouts", "normalise", "eps", "d", "block", "interpret"))
def _conv_backward(x, kernels, dys, layouts, normalise, eps, d, block,
                   interpret):
    b, t, c = x.shape
    heads = _head_counts(kernels, d)
    spec, halo, n = _conv_operands(x, block, reverse=True)
    dx, *dws = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, layouts=layouts, heads=heads,
                          normalise=normalise, eps=eps, d=d,
                          width=kernels[0].shape[0], block=block,
                          last_step=n - 1),
        grid=(b, n),
        in_specs=[spec, halo] + [_whole_spec(w.shape) for w in kernels]
        + [_tokens_spec(block * h, d, n) for h in heads],
        out_specs=[spec] + [_sums_spec(*w.shape) for w in kernels],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)] + [
            jax.ShapeDtypeStruct((b,) + w.shape, jnp.float32)
            for w in kernels],
        scratch_shapes=[pltpu.VMEM((_EDGE, sum(heads) * d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=CONV_BACKWARD_KERNEL_NAME,
    )(x, x, *(w.astype(jnp.float32) for w in kernels),
      *(dy.reshape(b, t * h, d) for dy, h in zip(dys, heads)))
    return dx, tuple(jnp.sum(dw, axis=0).astype(w.dtype)
                     for dw, w in zip(dws, kernels))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _conv_chain(x, kernels, layouts, normalise, eps, d, block, interpret):
    return _conv_forward(x, kernels, layouts=layouts, normalise=normalise,
                         eps=eps, d=d, block=block, interpret=interpret)


def _conv_chain_fwd(x, kernels, *static):
    return _conv_chain(x, kernels, *static), (x, kernels)


def _conv_chain_bwd(layouts, normalise, eps, d, block, interpret, res, dys):
    return _conv_backward(*res, dys, layouts=layouts, normalise=normalise,
                          eps=eps, d=d, block=block, interpret=interpret)


_conv_chain.defvjp(_conv_chain_fwd, _conv_chain_bwd)

IN_ORDER = (0, 1, 1)   # the layout of a plane whose heads lie in order


def _checked(layouts, heads, groups: int):
    layouts = tuple(tuple(int(v) for v in layout) for layout in layouts)
    fed = _fed(layouts, heads)
    if len(set(fed)) != len(fed) or not all(0 <= g < groups for g in fed):
        raise ValueError(f"layouts {layouts} of {heads} heads: a lane group "
                         f"of the {groups} feeds two heads, or none is there")
    return layouts


def conv_silu_norm(x, kernels, layouts, normalise, head_dim: int,
                   eps: float = 1e-12, block: int = TIME_BLOCK,
                   interpret: bool | None = None):
    """One ``[B, T, H_i, head_dim]`` plane an output i, in x's type:
    head h of output i is SiLU(conv) of the lane group of x that
    ``layouts[i]`` = (first, n, per) gives it, (h // n) * per + first +
    h % n (``IN_ORDER``: group h), under the taps
    ``kernels[i][:, h * head_dim:(h + 1) * head_dim]``, divided by
    sqrt(its sum of squares + eps^2) where ``normalise[i]``.

    x [B, T, C] bfloat16, T whole blocks; kernels[i] [W, H_i * head_dim];
    no lane group feeds two heads (the backward writes a group's gradient
    once; a group that feeds none gets zeros)."""
    d = int(head_dim)
    layouts = _checked(layouts, _head_counts(kernels, d), x.shape[-1] // d)
    return _conv_chain(x, tuple(kernels), layouts, tuple(map(bool, normalise)),
                       float(eps), d, int(block), bool(interpret))


# --------------------------------------------- gated short convolution


def _gconv_parts(x_ref, halo_b_ref, halo_u_ref, g, groups, starts):
    """Of the 128 channels ``g`` of a [B ; C ; u] block, float32: B, u,
    z = B * u [block, 128] and z's rows before the block [8, 128] (zeros
    where the block ``starts`` its sequence)."""
    gate = x_ref[0, :, _lanes(g, LANES)].astype(jnp.float32)
    u = x_ref[0, :, _lanes(2 * groups + g, LANES)].astype(jnp.float32)
    before = (halo_b_ref[0, :, _lanes(g, LANES)].astype(jnp.float32)
              * halo_u_ref[0, :, _lanes(g, LANES)].astype(jnp.float32))[_EDGE:]
    return gate, u, gate * u, jnp.where(starts, 0.0, before)


def _each_group(groups: int, body):
    jax.lax.fori_loop(0, groups, lambda g, c: (body(g), c)[1], 0)


def _gconv_fwd_kernel(x_ref, halo_b_ref, halo_u_ref, w_ref, y_ref, *, groups,
                      width):
    starts = pl.program_id(1) == 0

    def one(g):
        cols = _lanes(g, LANES)
        _, _, z, before = _gconv_parts(x_ref, halo_b_ref, halo_u_ref, g, groups,
                                       starts)
        c = sum(w_ref[j:j + 1, cols] * _shifted(z, before, width - 1 - j, True)
                for j in range(width))
        out = x_ref[0, :, _lanes(groups + g, LANES)].astype(jnp.float32) * c
        y_ref[0, :, cols] = out.astype(y_ref.dtype)

    _each_group(groups, one)


def _gconv_bwd_kernel(x_ref, halo_b_ref, halo_u_ref, w_ref, dy_ref, dx_ref,
                      dw_ref, carry_ref, *, groups, width, last_step):
    """The blocks come LAST FIRST, as ``_conv_bwd_kernel``'s: ``carry_ref``
    [8, C] holds the first rows of dc = dy * C of the block after this
    one. With c = conv(z) made again:

        dC = dy * c,   dz_t = sum_j k_j dc_{t + W - 1 - j},
        dB = dz * u,   du = dz * B,   dk_j += sum_t dc_t z_{t - W + 1 + j}
    """
    first = pl.program_id(1) == 0            # the sequence's LAST block
    starts = pl.program_id(1) == last_step
    _first_step_zeros(dw_ref)

    def one(g):
        cols, mid = _lanes(g, LANES), _lanes(groups + g, LANES)
        gate, u, z, before = _gconv_parts(x_ref, halo_b_ref, halo_u_ref, g,
                                          groups, starts)
        planes = [_shifted(z, before, width - 1 - j, True) for j in range(width)]
        c = sum(w_ref[j:j + 1, cols] * planes[j] for j in range(width))
        dy = dy_ref[0, :, cols].astype(jnp.float32)
        dc = dy * x_ref[0, :, mid].astype(jnp.float32)
        dx_ref[0, :, mid] = (dy * c).astype(dx_ref.dtype)
        after = jnp.where(first, 0.0, carry_ref[:, cols])
        carry_ref[:, cols] = dc[:_EDGE]
        dz = sum(w_ref[j:j + 1, cols] * _shifted(dc, after, width - 1 - j, False)
                 for j in range(width))
        dx_ref[0, :, cols] = (dz * u).astype(dx_ref.dtype)
        dx_ref[0, :, _lanes(2 * groups + g, LANES)] = (dz * gate).astype(
            dx_ref.dtype)
        for j in range(width):
            dw_ref[j:j + 1, cols] += jnp.sum(dc * planes[j], axis=0,
                                             keepdims=True)

    _each_group(groups, one)


def _gconv_operands(x, block, reverse):
    """``_conv_operands`` for a [B ; C ; u] plane: the block of tokens, and
    the 16 rows before it of B's and of u's third alone."""
    b, t, c3 = x.shape
    if block % _HALO:
        raise ValueError(f"a time block of {block} is not whole tiles of {_HALO}")
    n = t // block
    at = (lambda m: n - 1 - m) if reverse else (lambda m: m)
    per = block // _HALO
    halo = lambda third: pl.BlockSpec(  # noqa: E731
        (1, _HALO, c3 // 3),
        lambda i, m: (i, jnp.maximum(at(m) * per - 1, 0), third),
        memory_space=pltpu.VMEM)
    return _tokens_spec(block, c3, n if reverse else None), halo(0), halo(2), n


@functools.partial(jax.jit, inline=True, static_argnames=("block", "interpret"))
def _gconv_forward(x, taps, block, interpret):
    b, t, c3 = x.shape
    c = c3 // 3
    spec, halo_b, halo_u, n = _gconv_operands(x, block, reverse=False)
    return pl.pallas_call(
        functools.partial(_gconv_fwd_kernel, groups=c // LANES,
                          width=taps.shape[0]),
        grid=(b, n),
        in_specs=[spec, halo_b, halo_u, _whole_spec(taps.shape)],
        out_specs=_tokens_spec(block, c),
        out_shape=jax.ShapeDtypeStruct((b, t, c), x.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=GATED_CONV_KERNEL_NAME,
    )(x, x, x, taps.astype(jnp.float32))


@functools.partial(jax.jit, inline=True, static_argnames=("block", "interpret"))
def _gconv_backward(x, taps, dy, block, interpret):
    b, t, c3 = x.shape
    c = c3 // 3
    spec, halo_b, halo_u, n = _gconv_operands(x, block, reverse=True)
    dx, dw = pl.pallas_call(
        functools.partial(_gconv_bwd_kernel, groups=c // LANES,
                          width=taps.shape[0], last_step=n - 1),
        grid=(b, n),
        in_specs=[spec, halo_b, halo_u, _whole_spec(taps.shape),
                  _tokens_spec(block, c, n)],
        out_specs=[spec, _sums_spec(*taps.shape)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b,) + taps.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_EDGE, c), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=GATED_CONV_BACKWARD_KERNEL_NAME,
    )(x, x, x, taps.astype(jnp.float32), dy)
    return dx, jnp.sum(dw, axis=0).astype(taps.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gconv_chain(x, taps, block, interpret):
    return _gconv_forward(x, taps, block=block, interpret=interpret)


def _gconv_chain_fwd(x, taps, block, interpret):
    return _gconv_chain(x, taps, block, interpret), (x, taps)


def _gconv_chain_bwd(block, interpret, res, dy):
    return _gconv_backward(*res, dy, block=block, interpret=interpret)


_gconv_chain.defvjp(_gconv_chain_fwd, _gconv_chain_bwd)


def gated_short_conv(x, taps, block: int = TIME_BLOCK,
                     interpret: bool | None = None):
    """y [B, T, C] in x's type: y = C * conv(B * u), [B ; C ; u] the three
    thirds of x [B, T, 3 C] bfloat16 in that order, conv the causal
    depthwise convolution under ``taps`` [W, C] (tap j reads the token
    W - 1 - j before; nothing before a sequence's first token), float32
    inside. T whole blocks, C whole lane groups of 128, W - 1 <= 8."""
    if x.shape[-1] != 3 * taps.shape[1] or taps.shape[1] % LANES \
            or taps.shape[0] - 1 > _EDGE:
        raise ValueError(f"a plane {x.shape} under taps {taps.shape}")
    return _gconv_chain(x, taps, int(block), bool(interpret))


# ------------------------------------------------------ gated RMS norm


def _normed(o, eps):
    r = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * r, r


def _norm_fwd_kernel(o_ref, gate_ref, scale_ref, y_ref, *, layout, heads, act,
                     eps, d, block):
    scale = scale_ref[...]

    def one_pair(pair):
        for hd, o in enumerate(_pair_planes(o_ref, pair, heads, rows=block)):
            head = 2 * pair + hd
            z = gate_ref[0, :, _lanes(_group(layout, head), d)]
            y_ref[0, :, _lanes(head, d)] = (
                _normed(o, eps)[0] * scale
                * _ACTS[act](z.astype(jnp.float32))).astype(y_ref.dtype)

    _each_pair(heads, one_pair)


def _norm_bwd_kernel(o_ref, gate_ref, scale_ref, dy_ref, do_ref, dgate_ref,
                     dscale_ref, *, layout, heads, act, eps, d, block):
    scale = scale_ref[...]
    _first_step_zeros(dscale_ref)
    if heads < gate_ref.shape[-1] // d:
        dgate_ref[...] = jnp.zeros_like(dgate_ref)  # the groups no head reads

    def one_pair(pair):
        grads = []
        for hd, o in enumerate(_pair_planes(o_ref, pair, heads, rows=block)):
            head = 2 * pair + hd
            lanes = _lanes(_group(layout, head), d)
            z = gate_ref[0, :, lanes].astype(jnp.float32)
            dy = dy_ref[0, :, _lanes(head, d)].astype(jnp.float32)
            normed, r = _normed(o, eps)
            sig = jax.nn.sigmoid(z)
            if act == "sigmoid":
                gated, slope = sig, sig * (1.0 - sig)
            else:
                gated, slope = z * sig, sig * (1.0 + z * (1.0 - sig))
            dscale_ref[0:1, :] += jnp.sum(dy * normed * gated, axis=0,
                                          keepdims=True)
            dgate_ref[0, :, lanes] = (
                dy * normed * scale * slope).astype(dgate_ref.dtype)
            dn = dy * scale * gated
            grads.append(r * (dn - normed * jnp.mean(
                dn * normed, axis=-1, keepdims=True)))
        _store_pair(do_ref, pair, heads, *grads, rows=block)

    _each_pair(heads, one_pair)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "layout", "act", "eps", "block", "interpret"))
def _norm_forward(o, gate, scale, layout, act, eps, block, interpret):
    b, t, h, d = o.shape
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, layout=layout, heads=h, act=act,
                          eps=eps, d=d, block=block),
        grid=(b, t // block),
        in_specs=[_tokens_spec(block * h, d),
                  _tokens_spec(block, gate.shape[-1]), _whole_spec((1, d))],
        out_specs=_tokens_spec(block, h * d),
        out_shape=jax.ShapeDtypeStruct((b, t, h * d), gate.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=NORM_KERNEL_NAME,
    )(o.reshape(b, t * h, d), gate, scale.astype(jnp.float32).reshape(1, d))


@functools.partial(jax.jit, inline=True, static_argnames=(
    "layout", "act", "eps", "block", "interpret"))
def _norm_backward(o, gate, scale, dy, layout, act, eps, block, interpret):
    b, t, h, d = o.shape
    do, dgate, dscale = pl.pallas_call(
        functools.partial(_norm_bwd_kernel, layout=layout, heads=h, act=act,
                          eps=eps, d=d, block=block),
        grid=(b, t // block),
        in_specs=[_tokens_spec(block * h, d),
                  _tokens_spec(block, gate.shape[-1]), _whole_spec((1, d)),
                  _tokens_spec(block, h * d)],
        out_specs=[_tokens_spec(block * h, d),
                   _tokens_spec(block, gate.shape[-1]), _sums_spec(_EDGE, d)],
        out_shape=[jax.ShapeDtypeStruct((b, t * h, d), jnp.float32),
                   jax.ShapeDtypeStruct(gate.shape, gate.dtype),
                   jax.ShapeDtypeStruct((b, _EDGE, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=NORM_BACKWARD_KERNEL_NAME,
    )(o.reshape(b, t * h, d), gate, scale.astype(jnp.float32).reshape(1, d), dy)
    return (do.reshape(o.shape), dgate,
            jnp.sum(dscale[:, 0], axis=0).astype(scale.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _norm_chain(o, gate, scale, layout, act, eps, block, interpret):
    return _norm_forward(o, gate, scale, layout=layout, act=act, eps=eps,
                         block=block, interpret=interpret)


def _norm_chain_fwd(o, gate, scale, *static):
    return _norm_chain(o, gate, scale, *static), (o, gate, scale)


def _norm_chain_bwd(layout, act, eps, block, interpret, res, dy):
    return _norm_backward(*res, dy, layout=layout, act=act, eps=eps,
                          block=block, interpret=interpret)


_norm_chain.defvjp(_norm_chain_fwd, _norm_chain_bwd)


def gated_rms_norm(o, gate, scale, layout=IN_ORDER, act: str = "sigmoid",
                   eps: float = 1e-6, block: int = TIME_BLOCK,
                   interpret: bool | None = None):
    """[B, T, H * d] in the gate's type: o / sqrt(mean_d o^2 + eps) *
    scale * act(gate), head by head.

    o [B, T, H, d] float32; gate [B, T, C] bfloat16, head h's gate the
    lane group ``layout`` gives it (``conv_silu_norm``'s words); scale
    [d]; ``act`` "sigmoid" or "silu". The gate's gradient is zero in the
    groups no head reads."""
    h, d = o.shape[2:]
    (layout,) = _checked((layout,), (h,), gate.shape[-1] // d)
    return _norm_chain(o.astype(jnp.float32), gate, scale, layout, act,
                       float(eps), int(block), bool(interpret))


# ------------------------------------------------------------ log decay


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _decay_fwd_kernel(f_ref, rate_ref, bias_ref, g_ref, *, heads, d, block):
    def one_pair(pair):
        out = []
        for head in (2 * pair, 2 * pair + 1):
            cols = _lanes(head, d)
            out.append(rate_ref[:, cols] * _softplus(
                f_ref[0, :, cols].astype(jnp.float32) + bias_ref[:, cols]))
        _store_pair(g_ref, pair, heads, *out, rows=block)

    _each_pair(heads, one_pair)


def _decay_bwd_kernel(f_ref, rate_ref, bias_ref, dg_ref, df_ref, drate_ref,
                      dbias_ref, *, heads, d, block):
    _first_step_zeros(drate_ref)
    _first_step_zeros(dbias_ref)

    def one_pair(pair):
        for hd, dg in enumerate(_pair_planes(dg_ref, pair, heads, rows=block)):
            cols = _lanes(2 * pair + hd, d)
            x = f_ref[0, :, cols].astype(jnp.float32) + bias_ref[:, cols]
            df = dg * rate_ref[:, cols] * jax.nn.sigmoid(x)
            df_ref[0, :, cols] = df.astype(df_ref.dtype)
            dbias_ref[0:1, cols] += jnp.sum(df, axis=0, keepdims=True)
            drate_ref[0:1, cols] += jnp.sum(dg * _softplus(x), axis=0,
                                            keepdims=True)

    _each_pair(heads, one_pair)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("heads", "block", "interpret"))
def _decay_forward(f, rate, bias, heads, block, interpret):
    b, t, c = f.shape
    d = c // heads
    return pl.pallas_call(
        functools.partial(_decay_fwd_kernel, heads=heads, d=d, block=block),
        grid=(b, t // block),
        in_specs=[_tokens_spec(block, c), _whole_spec((1, c)),
                  _whole_spec((1, c))],
        out_specs=_tokens_spec(block * heads, d),
        out_shape=jax.ShapeDtypeStruct((b, t * heads, d), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=DECAY_KERNEL_NAME,
    )(f, rate, bias).reshape(b, t, heads, d)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("heads", "block", "interpret"))
def _decay_backward(f, rate, bias, dg, heads, block, interpret):
    b, t, c = f.shape
    d = c // heads
    df, drate, dbias = pl.pallas_call(
        functools.partial(_decay_bwd_kernel, heads=heads, d=d, block=block),
        grid=(b, t // block),
        in_specs=[_tokens_spec(block, c), _whole_spec((1, c)),
                  _whole_spec((1, c)), _tokens_spec(block * heads, d)],
        out_specs=[_tokens_spec(block, c), _sums_spec(_EDGE, c),
                   _sums_spec(_EDGE, c)],
        out_shape=[jax.ShapeDtypeStruct(f.shape, f.dtype)]
        + [jax.ShapeDtypeStruct((b, _EDGE, c), jnp.float32)] * 2,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=DECAY_BACKWARD_KERNEL_NAME,
    )(f, rate, bias, dg.reshape(b, t * heads, d))
    return (df, jnp.sum(drate[:, :1], axis=0), jnp.sum(dbias[:, :1], axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _decay_chain(f, rate, bias, heads, block, interpret):
    return _decay_forward(f, rate, bias, heads=heads, block=block,
                          interpret=interpret)


def _decay_chain_fwd(f, rate, bias, *static):
    return _decay_chain(f, rate, bias, *static), (f, rate, bias)


def _decay_chain_bwd(heads, block, interpret, res, dg):
    return _decay_backward(*res, dg, heads=heads, block=block,
                           interpret=interpret)


_decay_chain.defvjp(_decay_chain_fwd, _decay_chain_bwd)


def log_decay(f, a_log, dt_bias, block: int = TIME_BLOCK,
              interpret: bool | None = None):
    """g [B, T, H, d] float32 = -exp(A_log_h) softplus(f + dt_bias): f
    [B, T, H * d] bfloat16, a_log [H], dt_bias [H * d]. The two vectors
    go in as float32 rows a channel; A_log's exponential and the sum of
    its row's gradient over a head's channels are JAX's, outside."""
    heads = a_log.shape[0]
    d = f.shape[-1] // heads
    rate = jnp.repeat(-jnp.exp(a_log.astype(jnp.float32)), d)[None]
    return _decay_chain(f, rate, dt_bias.astype(jnp.float32)[None], heads,
                        int(block), bool(interpret))


# ---------------------------------------- Mamba-2: convolution, bias, SiLU


_SSM_COMPILER_PARAMS = pltpu.CompilerParams(
    # sequences, lane blocks, time blocks
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _lane_block(width: int, unit: int = LANES) -> int:
    """The widest block of whole ``unit``s that divides ``width`` and is
    no wider than ``SSM_LANE_BLOCK`` (one unit where a unit is wider)."""
    return max(k for k in range(unit, max(SSM_LANE_BLOCK, unit) + 1, unit)
               if width % k == 0)


def _ssm_specs(b, t, block, lanes, first=0, reverse=False):
    """The block specs of a grid (sequences, lane blocks, time blocks):
    ``cut`` a block of ``block`` tokens of ``lanes`` channels from lane
    ``first`` on, ``halo`` the 16 rows before it (``_conv_operands``'
    words), ``own`` such a block of a plane of its own, ``rows`` a lane
    block of an ``[8, C]`` tile of parameters and ``sums`` a sequence's
    row of their gradient, which stays in VMEM over the time blocks; with
    ``reverse`` time step m is block ``n`` - 1 - m."""
    if block % _HALO or first % lanes:
        raise ValueError(f"a time block of {block}, {lanes} lanes a block "
                         f"from lane {first}: not whole tiles")
    n, per, off = t // block, block // _HALO, first // lanes
    at = (lambda m: n - 1 - m) if reverse else (lambda m: m)
    spec = lambda shape, index: pl.BlockSpec(  # noqa: E731
        shape, index, memory_space=pltpu.VMEM)
    return dict(
        n=n,
        cut=spec((1, block, lanes), lambda i, j, m: (i, at(m), off + j)),
        own=spec((1, block, lanes), lambda i, j, m: (i, at(m), j)),
        halo=spec((1, _HALO, lanes), lambda i, j, m: (
            i, jnp.maximum(at(m) * per - 1, 0), off + j)),
        rows=spec((_EDGE, lanes), lambda i, j, m: (0, j)),
        sums=spec((None, _EDGE, lanes), lambda i, j, m: (i, 0, j)))


def _ssm_conv_pre(x_ref, halo_ref, rows_ref, cols, width, starts):
    """s = conv(x) + bias of 128 channels, float32 [block, 128], and the W
    shifted planes (``_conv_pre``'s words); ``rows_ref`` [8, lanes]: the W
    taps, then the bias."""
    x = x_ref[0, :, cols].astype(jnp.float32)
    before = halo_ref[0, :, cols].astype(jnp.float32)[_EDGE:]
    before = jnp.where(starts, 0.0, before)
    planes = [_shifted(x, before, width - 1 - j, back=True)
              for j in range(width)]
    s = sum(rows_ref[j:j + 1, cols] * planes[j] for j in range(width))
    return s + rows_ref[width:width + 1, cols], planes


def _ssm_conv_fwd_kernel(x_ref, halo_ref, rows_ref, y_ref, *, width):
    starts = pl.program_id(2) == 0

    def one(g):
        cols = _lanes(g, LANES)
        s, _ = _ssm_conv_pre(x_ref, halo_ref, rows_ref, cols, width, starts)
        y_ref[0, :, cols] = (s * jax.nn.sigmoid(s)).astype(y_ref.dtype)

    _each_group(y_ref.shape[-1] // LANES, one)


def _ssm_conv_bwd_kernel(x_ref, halo_ref, rows_ref, dy_ref, dx_ref, sums_ref,
                         carry_ref, *, width, last_step):
    """The blocks come LAST FIRST (``_conv_bwd_kernel``'s words);
    ``carry_ref`` [8, lanes] holds the first rows of ds of the block after
    this one. With s made again and ds = dy * SiLU'(s):

        dx_t = sum_j k_j ds_{t + W - 1 - j},
        dk_j += sum_t ds_t x_{t - W + 1 + j},   dbias += sum_t ds_t
    """
    first = pl.program_id(2) == 0            # the sequence's LAST block
    starts = pl.program_id(2) == last_step
    _first_step_zeros(sums_ref, axis=2)

    def one(g):
        cols = _lanes(g, LANES)
        s, planes = _ssm_conv_pre(x_ref, halo_ref, rows_ref, cols, width,
                                  starts)
        sig = jax.nn.sigmoid(s)
        ds = dy_ref[0, :, cols].astype(jnp.float32) * sig * (
            1.0 + s * (1.0 - sig))
        after = jnp.where(first, 0.0, carry_ref[:, cols])
        carry_ref[:, cols] = ds[:_EDGE]
        dx = sum(rows_ref[j:j + 1, cols] * _shifted(
            ds, after, width - 1 - j, back=False) for j in range(width))
        dx_ref[0, :, cols] = dx.astype(dx_ref.dtype)
        for j in range(width):
            sums_ref[j:j + 1, cols] += jnp.sum(ds * planes[j], axis=0,
                                               keepdims=True)
        sums_ref[width:width + 1, cols] += jnp.sum(ds, axis=0, keepdims=True)

    _each_group(dx_ref.shape[-1] // LANES, one)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "width", "first", "block", "lanes", "interpret"))
def _ssm_conv_forward(x, rows, width, first, block, lanes, interpret):
    b, t, _ = x.shape
    c = rows.shape[1]
    sp = _ssm_specs(b, t, block, lanes, first)
    return pl.pallas_call(
        functools.partial(_ssm_conv_fwd_kernel, width=width),
        grid=(b, c // lanes, sp["n"]),
        in_specs=[sp["cut"], sp["halo"], sp["rows"]],
        out_specs=sp["own"],
        out_shape=jax.ShapeDtypeStruct((b, t, c), x.dtype),
        compiler_params=_SSM_COMPILER_PARAMS,
        interpret=interpret,
        name=SSM_CONV_KERNEL_NAME,
    )(x, x, rows)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "width", "first", "block", "lanes", "interpret"))
def _ssm_conv_backward(x, rows, dy, width, first, block, lanes, interpret):
    """(dx [B, T, C] in x's type, the rows' gradient [8, C])."""
    b, t, _ = x.shape
    c = rows.shape[1]
    sp = _ssm_specs(b, t, block, lanes, first, reverse=True)
    dx, drows = pl.pallas_call(
        functools.partial(_ssm_conv_bwd_kernel, width=width,
                          last_step=sp["n"] - 1),
        grid=(b, c // lanes, sp["n"]),
        in_specs=[sp["cut"], sp["halo"], sp["rows"], sp["own"]],
        out_specs=[sp["own"], sp["sums"]],
        out_shape=[jax.ShapeDtypeStruct((b, t, c), x.dtype),
                   jax.ShapeDtypeStruct((b, _EDGE, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_EDGE, lanes), jnp.float32)],
        compiler_params=_SSM_COMPILER_PARAMS,
        interpret=interpret,
        name=SSM_CONV_BACKWARD_KERNEL_NAME,
    )(x, x, rows, dy)
    return dx, jnp.sum(drows, axis=0)


def _lanes_from(dx, first: int, width: int):
    """dx as lanes ``first`` on of a plane ``width`` wide, zeros around."""
    return jnp.pad(dx, ((0, 0), (0, 0),
                        (first, width - first - dx.shape[-1])))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _ssm_conv_chain(x, rows, width, first, block, lanes, interpret):
    return _ssm_conv_forward(x, rows, width=width, first=first, block=block,
                             lanes=lanes, interpret=interpret)


def _ssm_conv_chain_fwd(x, rows, *static):
    return _ssm_conv_chain(x, rows, *static), (x, rows)


def _ssm_conv_chain_bwd(width, first, block, lanes, interpret, res, dy):
    x, rows = res
    dx, drows = _ssm_conv_backward(x, rows, dy, width=width, first=first,
                                   block=block, lanes=lanes,
                                   interpret=interpret)
    return _lanes_from(dx, first, x.shape[-1]), drows


_ssm_conv_chain.defvjp(_ssm_conv_chain_fwd, _ssm_conv_chain_bwd)


def _packed_rows(*vectors):
    """[8, C] float32: the vectors' rows ([n, C] or [C]) one under the
    other, zeros below (a float32 tile of parameters a kernel is handed,
    and the layout of the sums its backward returns)."""
    rows = jnp.concatenate(
        [jnp.atleast_2d(v).astype(jnp.float32) for v in vectors])
    return jnp.pad(rows, ((0, _EDGE - rows.shape[0]), (0, 0)))


def ssm_conv_silu(x, taps, bias, first: int = 0, block: int = SSM_TIME_BLOCK,
                  lanes: int | None = None, interpret: bool | None = None):
    """y [B, T, C] in x's type = SiLU(conv(x[..., first:first + C]) + bias):
    the causal depthwise convolution under ``taps`` [W, C] (tap j reads
    the token W - 1 - j before; nothing before a sequence's first token),
    float32 inside. x [B, T, >= first + C] bfloat16 is read where it lies
    (its cotangent: zeros outside the C channels); T whole blocks, C and
    ``first`` whole lane tiles, W <= 7. ``lanes``: the channels a grid
    step (the widest block that divides both, unasked)."""
    width, c = taps.shape
    if c % LANES or first % LANES or first + c > x.shape[-1] \
            or width + 1 > _EDGE or bias.shape != (c,):
        raise ValueError(f"a plane {x.shape} from lane {first} under taps "
                         f"{taps.shape} and a bias {bias.shape}")
    lanes = int(lanes or _lane_block(math.gcd(first, c)))
    return _ssm_conv_chain(x, _packed_rows(taps, bias), width, int(first),
                           int(block), lanes, bool(interpret))


# ---------------------------- Mamba-2: skip, gate, then the grouped norm


def _ssm_norm_parts(y_ref, u_ref, z_ref, rows_ref, cols, eps):
    """Of one norm group (``cols``), float32 [block, group]: u, z, the
    gate's sigmoid, a = y + D u, the normalised n = v / rms(v) of v = a *
    SiLU(z), and 1 / rms(v) [block, 1]. ``rows_ref`` [8, lanes]: D a
    lane, then the scale."""
    u = u_ref[0, :, cols].astype(jnp.float32)
    z = z_ref[0, :, cols].astype(jnp.float32)
    sig = jax.nn.sigmoid(z)
    a = y_ref[0, :, cols].astype(jnp.float32) + rows_ref[0:1, cols] * u
    v = a * (z * sig)
    r = jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return u, z, sig, a, v * r, r


def _ssm_norm_fwd_kernel(y_ref, u_ref, z_ref, rows_ref, out_ref, *, group,
                         eps):
    def one(g):
        cols = _lanes(g, group)
        normed = _ssm_norm_parts(y_ref, u_ref, z_ref, rows_ref, cols, eps)[4]
        out_ref[0, :, cols] = (normed * rows_ref[1:2, cols]).astype(
            out_ref.dtype)

    _each_group(out_ref.shape[-1] // group, one)


def _ssm_norm_bwd_kernel(y_ref, u_ref, z_ref, rows_ref, dout_ref, dy_ref,
                         du_ref, dz_ref, sums_ref, *, group, eps):
    """With a, v, n = v r made again and dn = dout * scale:

        dv = r (dn - n mean_group(dn n)),   da = dv SiLU(z) = dy,
        du = D da,   dz = dv a SiLU'(z),
        dD += sum_t da u,   dscale += sum_t dout n   (a lane each)
    """
    _first_step_zeros(sums_ref, axis=2)

    def one(g):
        cols = _lanes(g, group)
        u, z, sig, a, normed, r = _ssm_norm_parts(
            y_ref, u_ref, z_ref, rows_ref, cols, eps)
        dout = dout_ref[0, :, cols].astype(jnp.float32)
        dn = dout * rows_ref[1:2, cols]
        dv = r * (dn - normed * jnp.mean(dn * normed, axis=-1, keepdims=True))
        da = dv * (z * sig)
        dy_ref[0, :, cols] = da.astype(dy_ref.dtype)
        du_ref[0, :, cols] = (da * rows_ref[0:1, cols]).astype(du_ref.dtype)
        dz_ref[0, :, cols] = (dv * a * sig * (1.0 + z * (1.0 - sig))).astype(
            dz_ref.dtype)
        sums_ref[0:1, cols] += jnp.sum(da * u, axis=0, keepdims=True)
        sums_ref[1:2, cols] += jnp.sum(dout * normed, axis=0, keepdims=True)

    _each_group(dy_ref.shape[-1] // group, one)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "group", "eps", "block", "lanes", "interpret"))
def _ssm_norm_forward(y, xbc, plane, rows, group, eps, block, lanes,
                      interpret):
    b, t, inner = y.shape
    sp = _ssm_specs(b, t, block, lanes)
    return pl.pallas_call(
        functools.partial(_ssm_norm_fwd_kernel, group=group, eps=eps),
        grid=(b, inner // lanes, sp["n"]),
        in_specs=[sp["own"]] * 3 + [sp["rows"]],
        out_specs=sp["own"],
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_SSM_COMPILER_PARAMS,
        interpret=interpret,
        name=SSM_NORM_KERNEL_NAME,
    )(y, xbc, plane, rows)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "group", "eps", "block", "lanes", "interpret"))
def _ssm_norm_backward(y, xbc, plane, rows, dout, group, eps, block, lanes,
                       interpret):
    """(dy, du, dz, each [B, T, inner] in its operand's type, the rows'
    gradient [8, inner])."""
    b, t, inner = y.shape
    sp = _ssm_specs(b, t, block, lanes)
    dy, du, dz, drows = pl.pallas_call(
        functools.partial(_ssm_norm_bwd_kernel, group=group, eps=eps),
        grid=(b, inner // lanes, sp["n"]),
        in_specs=[sp["own"]] * 3 + [sp["rows"], sp["own"]],
        out_specs=[sp["own"]] * 3 + [sp["sums"]],
        out_shape=[jax.ShapeDtypeStruct(y.shape, x.dtype)
                   for x in (y, xbc, plane)]
        + [jax.ShapeDtypeStruct((b, _EDGE, inner), jnp.float32)],
        compiler_params=_SSM_COMPILER_PARAMS,
        interpret=interpret,
        name=SSM_NORM_BACKWARD_KERNEL_NAME,
    )(y, xbc, plane, rows, dout)
    return dy, du, dz, jnp.sum(drows, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _ssm_norm_chain(y, xbc, plane, rows, group, eps, block, lanes, interpret):
    return _ssm_norm_forward(y, xbc, plane, rows, group=group, eps=eps,
                             block=block, lanes=lanes, interpret=interpret)


def _ssm_norm_chain_fwd(y, xbc, plane, rows, *static):
    return _ssm_norm_chain(y, xbc, plane, rows, *static), (y, xbc, plane, rows)


def _ssm_norm_chain_bwd(group, eps, block, lanes, interpret, res, dout):
    y, xbc, plane, rows = res
    dy, du, dz, drows = _ssm_norm_backward(
        *res, dout, group=group, eps=eps, block=block, lanes=lanes,
        interpret=interpret)
    return (dy, _lanes_from(du, 0, xbc.shape[-1]),
            _lanes_from(dz, 0, plane.shape[-1]), drows)


_ssm_norm_chain.defvjp(_ssm_norm_chain_fwd, _ssm_norm_chain_bwd)


def ssm_gate_norm(y, xbc, plane, skip, scale, groups: int, eps: float = 1e-5,
                  block: int = SSM_TIME_BLOCK, lanes: int | None = None,
                  interpret: bool | None = None):
    """out [B, T, inner] in y's type = v / sqrt(mean_group v^2 + eps) *
    scale, v = (y + skip * u) * SiLU(z), the mean over each of ``groups``
    stretches of inner / groups channels, float32 inside.

    y [B, T, inner] bfloat16 (the scan's output); u and z the first
    ``inner`` lanes of ``xbc`` [B, T, >= inner] and of ``plane``
    [B, T, >= inner], read where they lie (their cotangents: zeros past
    them); ``skip`` and ``scale`` [inner], a value a lane. T whole blocks,
    a group whole lane tiles. ``lanes``: the channels a grid step, whole
    groups (the widest block of them that divides ``inner``, unasked)."""
    inner = y.shape[-1]
    group = inner // groups
    if inner % groups or group % LANES or skip.shape != (inner,) \
            or scale.shape != (inner,) or min(
                xbc.shape[-1], plane.shape[-1]) < inner:
        raise ValueError(f"y {y.shape} in {groups} groups, u of {xbc.shape}, "
                         f"z of {plane.shape}, skip {skip.shape}, scale "
                         f"{scale.shape}")
    lanes = int(lanes or _lane_block(inner, group))
    if lanes % group:
        raise ValueError(f"{lanes} lanes a block are not whole groups of "
                         f"{group}")
    return _ssm_norm_chain(y, xbc, plane, _packed_rows(skip, scale), group,
                           float(eps), int(block), lanes, bool(interpret))
