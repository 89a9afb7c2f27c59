"""Ring attention: exact sequence/context-parallel attention over a mesh axis.

(reference: absent — dinov3_jax computed one dense
``nn.dot_product_attention`` per device (layers/attention.py:116) with no
sequence parallelism of any kind; SURVEY.md §5.7 flags ring/all-gather-KV
attention over an ``sp`` axis as required for the 518-768 px and ViT-7B
configs. This module supplies it TPU-style: K/V chunks rotate around the
``seq`` mesh axis with ``lax.ppermute`` (riding ICI neighbor links) while
each device keeps only its own query chunk, merging partial softmax
statistics online — O(N/s) memory per device, exact to fused attention.)

The public wrapper handles the non-divisible token counts ViT produces
(CLS + register prefix): pads to a multiple of the axis size, masks padded
keys by *global* position, and slices the pad back off.

Three properties the high-res gram-anchoring stage added on top of the
original forward-only rotation:

- **segment masking** (crop packing, ops/packing.py): the per-row segment
  ids rotate around the ring NEXT TO their K/V chunks (a third ppermute
  per step), and each step masks ``row_seg != col_seg`` pairs with the
  same large-finite ``NEG_INF`` convention as the dense/flash paths — so
  the packed student forward no longer has to forfeit the seq axis.
- **a hand-written ``custom_vjp``**: autodiff through the forward scan
  would save one [B, h, C, C] probability block per ring step — O(N^2)
  residual bytes, exactly what ring attention exists to avoid. The
  backward instead re-runs the ring (a second pass of ppermutes) from the
  saved (q, k, v, out, lse) residuals, with the dk/dv accumulators
  co-rotating with their chunks so each arrives home after ``size``
  rotations carrying every query shard's contribution.
- **named scopes** ``ring_permute`` (the rotating collectives) and
  ``ring_merge`` (the island boundary + online merge), joined by the
  step-anatomy ledger (telemetry/anatomy.py) through the compiled HLO
  ``op_name`` — ring collectives attribute to their own scopes instead of
  falling into "other"/unattributed (utils.HLO_COLLECTIVE_SCOPES).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _masked_logits(qf, kc, rseg, csegc, src, n_valid, reduce_dtype):
    """[B, h, C, C] logits of the local (pre-scaled) query chunk against
    one rotating K chunk. Two masks, both large-finite (the flash
    kernel's NEG_INF convention — every real row keeps a real max, so
    exp underflows to exact 0 and no row can go NaN):

    - pad mask by *global* key position (``src`` names the shard the
      chunk originated on, so position = src * C + local offset);
    - segment mask (crop packing): query q sees key k iff their segment
      ids match — ``rseg`` is the local row chunk, ``csegc`` the column
      chunk that rotates with kc.
    """
    C = qf.shape[1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", qf, kc.astype(reduce_dtype),
        preferred_element_type=reduce_dtype,
    )
    if n_valid is not None:
        gpos = src * C + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, 1, C), 3
        )
        s = jnp.where(gpos < n_valid, s, NEG_INF)
    if rseg is not None:
        same = rseg[:, None, :, None] == csegc[:, None, None, :]
        s = jnp.where(same, s, NEG_INF)
    return s


def _ring_fwd_local(q, k, v, seg, *, axis_name, n_valid, reduce_dtype):
    """One full ring pass. Returns (out [B, C, h, d] in q.dtype,
    lse [B, h, C, 1] log-sum-exp in reduce_dtype — the backward's
    softmax residual)."""
    B, C, h, d = q.shape
    size = jax.lax.axis_size(axis_name)
    # the chunk-origin tracker feeds only the global-position pad mask;
    # left dead, its PartitionId lowering trips the SPMD partitioner on
    # the custom_vjp primal path (custom-call bodies are not inlined)
    my = (jax.lax.axis_index(axis_name) if n_valid is not None
          else jnp.zeros((), jnp.int32))
    scale = d ** -0.5
    qf = q.astype(reduce_dtype) * scale

    perm = [(i, (i + 1) % size) for i in range(size)]

    def step(carry, _):
        m, l, acc, kc, vc, sc, src = carry
        s = _masked_logits(qf, kc, seg, sc, src, n_valid, reduce_dtype)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        with jax.named_scope("ring_merge"):
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vc.astype(reduce_dtype),
                preferred_element_type=reduce_dtype,
            )
        # rotate the K/V (+ column-segment) chunk to the next device;
        # chunk held after the rotation originated on shard
        # (src - 1) mod size
        with jax.named_scope("ring_permute"):
            kc = jax.lax.ppermute(kc, axis_name, perm)
            vc = jax.lax.ppermute(vc, axis_name, perm)
            if sc is not None:
                sc = jax.lax.ppermute(sc, axis_name, perm)
        src = (src - 1) % size
        return (m_new, l_new, acc_new, kc, vc, sc, src), None

    # initial carries derived from q so they carry the same device-varying
    # manual-axes type as the loop outputs (shard_map scan vma rule)
    qz = jnp.swapaxes(qf, 1, 2) * 0.0  # [B, h, C, d], all zeros
    m0 = qz[..., :1] + NEG_INF
    l0 = qz[..., :1]
    acc0 = qz
    (m, l, acc, _, _, _, _), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v, seg, my), None, length=size
    )
    l = jnp.maximum(l, 1e-37)
    out = acc / l
    lse = m + jnp.log(l)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype), lse


def _ring_bwd_local(q, k, v, seg, out, lse, dout, *, axis_name, n_valid,
                    reduce_dtype):
    """The second ring pass: flash-style backward from the (out, lse)
    residuals. Per visiting chunk: p = exp(s - lse) reproduces the
    forward's probabilities without any saved [C, C] state; dv/dk
    contributions accumulate into buffers that CO-ROTATE with the chunk
    (same ppermute schedule), so after ``size`` rotations each chunk's
    gradient arrives back on the device that owns it, complete."""
    B, C, h, d = q.shape
    size = jax.lax.axis_size(axis_name)
    my = (jax.lax.axis_index(axis_name) if n_valid is not None
          else jnp.zeros((), jnp.int32))  # see _ring_fwd_local
    scale = d ** -0.5
    qf = q.astype(reduce_dtype) * scale
    doutf = dout.astype(reduce_dtype)
    # delta = sum_d(dout * out) per (b, h, q): the softmax-jacobian
    # correction term, computable from residuals (Dao et al.'s trick)
    delta = jnp.einsum(
        "bqhd,bqhd->bhq", doutf, out.astype(reduce_dtype),
        preferred_element_type=reduce_dtype,
    )[..., None]

    perm = [(i, (i + 1) % size) for i in range(size)]

    def step(carry, _):
        dq, kc, vc, sc, dk, dv, src = carry
        s = _masked_logits(qf, kc, seg, sc, src, n_valid, reduce_dtype)
        p = jnp.exp(s - lse)  # masked logits -> exact 0, like the fwd
        with jax.named_scope("ring_merge"):
            dv_new = dv + jnp.einsum(
                "bhqk,bqhd->bkhd", p, doutf,
                preferred_element_type=reduce_dtype,
            )
            dp = jnp.einsum(
                "bqhd,bkhd->bhqk", doutf, vc.astype(reduce_dtype),
                preferred_element_type=reduce_dtype,
            )
            ds = p * (dp - delta)
            dq = dq + jnp.einsum(
                "bhqk,bkhd->bqhd", ds, kc.astype(reduce_dtype),
                preferred_element_type=reduce_dtype,
            ) * scale
            # qf already carries the scale, so dk needs no extra factor
            dk_new = dk + jnp.einsum(
                "bhqk,bqhd->bkhd", ds, qf,
                preferred_element_type=reduce_dtype,
            )
        with jax.named_scope("ring_permute"):
            kc = jax.lax.ppermute(kc, axis_name, perm)
            vc = jax.lax.ppermute(vc, axis_name, perm)
            if sc is not None:
                sc = jax.lax.ppermute(sc, axis_name, perm)
            dk_new = jax.lax.ppermute(dk_new, axis_name, perm)
            dv_new = jax.lax.ppermute(dv_new, axis_name, perm)
        src = (src - 1) % size
        return (dq, kc, vc, sc, dk_new, dv_new, src), None

    z = q.astype(reduce_dtype) * 0.0  # [B, C, h, d] zeros, q's vma type
    (dq, _, _, _, dk, dv, _), _ = jax.lax.scan(
        step, (z, k, v, seg, z, z, my), None, length=size
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# The segment-masked ring attention with its hand-written VJP, over
# GLOBAL (inside-jit) arrays. Two structural constraints shape this:
#
# - The custom_vjp sits OUTSIDE the shard_map islands — the forward and
#   the backward each run their own island — because a custom_vjp primal
#   call does not inline under shard_map's manual lowering (its
#   axis_index lowers to a bare PartitionId the SPMD partitioner
#   rejects).
# - The custom_vjp functions are defined ONCE at module level with the
#   static configuration threaded through ``nondiff_argnums``, never
#   rebuilt per trace: jax/flax cache call jaxprs keyed on the callee's
#   identity (``nn.scan``'s body jaxpr among them), and a custom_vjp
#   object recreated inside every trace poisons those caches with the
#   previous trace's tracers (UnexpectedTracerError on the second trace
#   of the scanned block stack — the lower()-then-call pattern every
#   cost script uses).
#
# ``cfg`` is the hashable static tuple
# (mesh, seq_axis, spec, seg_spec, lse_spec, n_valid, reduce_dtype).
# The integer segment ids of the seg variant get a float0 cotangent —
# custom_vjp backward outputs must mirror the primal argument pytree,
# ints included.

def _ring_islands(cfg):
    """(fwd_sm, bwd_sm) shard_map islands for one static config —
    rebuilt per trace (cheap), closing only over ``cfg``."""
    mesh, seq_axis, spec, seg_spec, lse_spec, n_valid, reduce_dtype = cfg
    kw = dict(axis_name=seq_axis, n_valid=n_valid,
              reduce_dtype=reduce_dtype)
    has_seg = seg_spec is not None

    def fwd_island(q, k, v, seg=None):
        return _ring_fwd_local(q, k, v, seg, **kw)

    def bwd_island(q, k, v, out, lse, dout, seg=None):
        return _ring_bwd_local(q, k, v, seg, out, lse, dout, **kw)

    if has_seg:
        fwd_sm = jax.shard_map(
            lambda q, k, v, seg: fwd_island(q, k, v, seg), mesh=mesh,
            in_specs=(spec, spec, spec, seg_spec),
            out_specs=(spec, lse_spec),
        )
        bwd_sm = jax.shard_map(
            lambda q, k, v, seg, out, lse, dout: bwd_island(
                q, k, v, out, lse, dout, seg), mesh=mesh,
            in_specs=(spec, spec, spec, seg_spec, spec, lse_spec, spec),
            out_specs=(spec, spec, spec),
        )
    else:
        fwd_sm = jax.shard_map(
            lambda q, k, v: fwd_island(q, k, v), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=(spec, lse_spec),
        )
        bwd_sm = jax.shard_map(
            lambda q, k, v, out, lse, dout: bwd_island(
                q, k, v, out, lse, dout), mesh=mesh,
            in_specs=(spec, spec, spec, spec, lse_spec, spec),
            out_specs=(spec, spec, spec),
        )
    return fwd_sm, bwd_sm


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_core(cfg, q, k, v):
    return _ring_islands(cfg)[0](q, k, v)[0]


def _ring_core_fwd(cfg, q, k, v):
    out, lse = _ring_islands(cfg)[0](q, k, v)
    return out, (q, k, v, out, lse)


def _ring_core_bwd(cfg, res, dout):
    q, k, v, out, lse = res
    return _ring_islands(cfg)[1](q, k, v, out, lse, dout)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_core_seg(cfg, q, k, v, seg):
    return _ring_islands(cfg)[0](q, k, v, seg)[0]


def _ring_core_seg_fwd(cfg, q, k, v, seg):
    out, lse = _ring_islands(cfg)[0](q, k, v, seg)
    return out, (q, k, v, seg, out, lse)


def _ring_core_seg_bwd(cfg, res, dout):
    q, k, v, seg, out, lse = res
    dq, dk, dv = _ring_islands(cfg)[1](q, k, v, seg, out, lse, dout)
    return dq, dk, dv, np.zeros(seg.shape, jax.dtypes.float0)


_ring_core_seg.defvjp(_ring_core_seg_fwd, _ring_core_seg_bwd)


def ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    n_valid: int | None = None,
    reduce_dtype=jnp.float32,
    seg: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Shard-local ring attention forward. Must run inside ``shard_map``
    with ``axis_name`` bound.

    q, k, v: [B, C, h, d] — the local chunk of C = N_padded / axis_size
    tokens. Returns the local [B, C, h, d] output chunk. ``n_valid``: the
    real token count before padding (keys at global position >= n_valid
    are masked); None means no padding anywhere. ``seg``: the local
    [B, C] int32 segment-id chunk (crop packing) — it serves as both the
    row ids and the initial rotating column chunk.

    Plain autodiff here differentiates through the scan and saves one
    [B, h, C, C] probability block per ring step; the ``ring_attention``
    wrapper's custom_vjp path is the memory-bounded backward.
    """
    return _ring_fwd_local(q, k, v, seg, axis_name=axis_name,
                           n_valid=n_valid, reduce_dtype=reduce_dtype)[0]


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    seg: jnp.ndarray | None = None,
    seq_axis: str = "seq",
    batch_axes: tuple = ("dcn_data", "data", "fsdp"),
    heads_axis: str | None = "tensor",
    reduce_dtype=jnp.float32,
) -> jnp.ndarray:
    """GSPMD-callable exact attention with the token dim sharded over
    ``seq_axis``. q, k, v: [B, N, h, d] global arrays (inside jit);
    ``seg``: optional [B, N] int32 segment ids (crop packing) — same
    block-diagonal semantics as ``xla_attention(seg=...)``.
    """
    size = int(mesh.shape[seq_axis])
    if size == 1:
        from dinov3_tpu.ops.attention import xla_attention

        return xla_attention(q, k, v, reduce_dtype, seg=seg)
    B, N, h, d = q.shape
    n_padded = -(-N // size) * size
    pad = n_padded - N
    if pad:
        cfgpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(t, cfgpad) for t in (q, k, v))
        if seg is not None:
            # pad value is irrelevant: padded keys are masked by global
            # position, padded query rows are sliced off below
            seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1)
    # only shard batch/head dims that divide evenly; otherwise replicate
    # that dim inside the island (results are identical either way)
    import math

    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    b_div = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    b_axes = batch_axes if (batch_axes and B % b_div == 0) else None
    h_axis = (
        heads_axis
        if heads_axis in mesh.shape and h % int(mesh.shape[heads_axis]) == 0
        else None
    )
    spec = P(b_axes, seq_axis, h_axis, None)
    seg_spec = P(b_axes, seq_axis) if seg is not None else None
    lse_spec = P(b_axes, h_axis, seq_axis, None)
    cfg = (mesh, seq_axis, spec, seg_spec, lse_spec,
           N if pad else None, reduce_dtype)
    # the island-boundary scope: any reshard GSPMD inserts to feed the
    # islands attributes to ring_merge in the anatomy ledger
    with jax.named_scope("ring_merge"):
        out = (_ring_core_seg(cfg, q, k, v, seg) if seg is not None
               else _ring_core(cfg, q, k, v))
    if pad:
        out = out[:, :N]
    return out
