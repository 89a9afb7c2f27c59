"""Blockwise flash attention as Pallas TPU kernels, with a custom VJP.

(reference: dinov3_jax/layers/attention.py:116 used
``flax.linen.dot_product_attention`` — a dense [N, N] softmax with O(N^2)
memory and no kernel fusion; SURVEY.md §5.7 calls out the absence of any
flash/blockwise path as the gap for high-res (518-768 px) and ViT-7B runs.)

Design
------
- Non-causal bidirectional attention (ViT), shapes [B, N, heads, d].
- Forward: one Pallas kernel per (batch, head, q-block); keys/values for
  the whole row live in VMEM (N <= ~2.4k tokens for DINOv3's largest crop,
  so K+V fit comfortably); online softmax with running max/normalizer in
  fp32, matmuls on the MXU via ``preferred_element_type=float32``.
- Backward: standard two-kernel FlashAttention-2 scheme — ``delta =
  rowsum(dO * O)`` precomputed, then a dq kernel (loop over k-blocks) and a
  dk/dv kernel (loop over q-blocks), both recomputing probabilities from
  the saved logsumexp instead of materializing [N, N].
- Sequence padding: N is static under jit, so q/k/v are zero-padded to a
  lane-aligned Np and the pad columns are masked with -inf at trace time
  only when padding exists.
- Segment masking (crop packing, ops/packing.py): an optional [B, N]
  int32 segment-id array turns every kernel block-diagonal — token q
  attends token k iff their ids match, exactly the ``-inf``-style
  masking the pad columns already use. Ids are threaded twice, as
  [BH, Np, 1] rows (q side) and [BH, 1, Np] cols (k side), so neither
  kernel needs an in-VMEM transpose. Pad positions from the lane
  alignment get id -2: distinct from the packer's -1 pads, though the
  existing n_valid masking already covers them.

All kernels run in interpret mode off-TPU so the CPU test mesh exercises
the exact same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pick(n_padded: int, cap: int) -> int:
    for c in (512, 256, 128):
        if c <= cap and n_padded % c == 0:
            return c
    raise ValueError(f"n_padded={n_padded} is not a multiple of 128")


def _block_sizes(n_padded: int, block_q: int = 512,
                 block_kv: int = 512) -> tuple[int, int]:
    """Concrete q/kv block sizes: the largest 128-multiple divisor of
    n_padded within the configured caps (``kernels.flash_block_q/kv``)."""
    return (_pick(n_padded, max(128, int(block_q))),
            _pick(n_padded, max(128, int(block_kv))))


def _vmem_spec(block_shape=None, index_map=None):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


# ---------------------------------------------------------------- forward


def _fwd_kernel(*refs, scale, n_valid, bk, has_seg):
    # q_ref: [bq, d]; k_ref/v_ref: [Np, d]; o_ref: [bq, d]; lse_ref: [bq, 1]
    # with has_seg: + sq_ref [bq, 1], sk_ref [1, Np] (row/col segment ids)
    if has_seg:
        q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        sq_ref = sk_ref = None
    bq, d = q_ref.shape
    n_padded = k_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * scale
    sq = sq_ref[...] if has_seg else None  # [bq, 1]

    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * bk, bk), :]
        v = v_ref[pl.ds(j * bk, bk), :]
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if has_seg:
            sk = sk_ref[:, pl.ds(j * bk, bk)]  # [1, bk]
            s = jnp.where(sq == sk, s, NEG_INF)
        if n_padded != n_valid:
            col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(col < n_valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_padded // bk, body, (m, l, acc))
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(l)


def _flash_fwd(q, k, v, seg_rows=None, seg_cols=None, *, n_valid,
               interpret, caps=(512, 512)):
    """q, k, v: [BH, Np, d] fp32/bf16; returns (o, lse)."""
    bh, n_padded, d = q.shape
    bq, bk = _block_sizes(n_padded, *caps)
    scale = d ** -0.5
    has_seg = seg_rows is not None
    kernel = functools.partial(
        _fwd_kernel, scale=scale, n_valid=n_valid, bk=bk, has_seg=has_seg
    )
    grid = (bh, n_padded // bq)
    in_specs = [
        _vmem_spec((None, bq, d), lambda b, i: (b, i, 0)),
        _vmem_spec((None, n_padded, d), lambda b, i: (b, 0, 0)),
        _vmem_spec((None, n_padded, d), lambda b, i: (b, 0, 0)),
    ]
    args = [q, k, v]
    if has_seg:
        in_specs += [
            _vmem_spec((None, bq, 1), lambda b, i: (b, i, 0)),
            _vmem_spec((None, 1, n_padded), lambda b, i: (b, 0, 0)),
        ]
        args += [seg_rows, seg_cols]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            _vmem_spec((None, bq, d), lambda b, i: (b, i, 0)),
            _vmem_spec((None, bq, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_padded, d), q.dtype),
            jax.ShapeDtypeStruct((bh, n_padded, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return o, lse


# ---------------------------------------------------------------- backward


def _dq_kernel(*refs, scale, n_valid, bk, has_seg):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         sq_ref, sk_ref, dq_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = refs
        sq_ref = sk_ref = None
    bq, d = q_ref.shape
    n_padded = k_ref.shape[0]
    q = q_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...]      # [bq, 1]
    delta = delta_ref[...]  # [bq, 1]
    sq = sq_ref[...] if has_seg else None  # [bq, 1]
    dq = jnp.zeros((bq, d), jnp.float32)

    def body(j, dq):
        k = k_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if has_seg:
            sk = sk_ref[:, pl.ds(j * bk, bk)]  # [1, bk]
            s = jnp.where(sq == sk, s, NEG_INF)
        if n_padded != n_valid:
            col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(col < n_valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jax.lax.fori_loop(0, n_padded // bk, body, dq)
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, n_valid, bq, has_seg):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         sq_ref, sk_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref) = refs
        sq_ref = sk_ref = None
    bk, d = k_ref.shape
    n_padded = q_ref.shape[0]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    sk = sk_ref[...] if has_seg else None  # [1, bk]
    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * bq, bq), :].astype(jnp.float32)
        do = do_ref[pl.ds(i * bq, bq), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(i * bq, bq), :]      # [bq, 1]
        delta = delta_ref[pl.ds(i * bq, bq), :]  # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        if has_seg:
            sq = sq_ref[pl.ds(i * bq, bq), :]  # [bq, 1]
            s = jnp.where(sq == sk, s, NEG_INF)
        if n_padded != n_valid:
            # pad q rows: their lse is 0 -> exp(s) could blow up; mask rows
            row = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(row < n_valid, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk]
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    dk, dv = jax.lax.fori_loop(0, n_padded // bq, body, (dk, dv))
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


# ------------------------------------------------------------ public entry


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_bhnd(q, k, v, interpret, caps):
    o, _ = _fwd_pallas(q, k, v, None, interpret, caps)
    return o


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_bhnd_seg(q, k, v, seg, interpret, caps):
    o, _ = _fwd_pallas(q, k, v, seg, interpret, caps)
    return o


def _fwd_pallas(q, k, v, seg, interpret, caps=(512, 512)):
    n_valid = q.shape[1]
    n_padded = _round_up(n_valid, 128)
    pad = n_padded - n_valid
    if pad:
        padcfg = ((0, 0), (0, pad), (0, 0))
        q = jnp.pad(q, padcfg)
        k = jnp.pad(k, padcfg)
        v = jnp.pad(v, padcfg)
    seg_rows = seg_cols = None
    if seg is not None:
        if pad:
            seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-2)
        seg_rows = seg[:, :, None]
        seg_cols = seg[:, None, :]
    o, lse = _flash_fwd(q, k, v, seg_rows, seg_cols, n_valid=n_valid,
                        interpret=interpret, caps=caps)
    return o[:, :n_valid], (q, k, v, o, lse, seg, n_valid)


def _flash_bhnd_fwd(q, k, v, interpret, caps):
    o, res = _fwd_pallas(q, k, v, None, interpret, caps)
    return o, res


def _flash_bhnd_seg_fwd(q, k, v, seg, interpret, caps):
    o, res = _fwd_pallas(q, k, v, seg, interpret, caps)
    return o, res


def _bwd_pallas(interpret, caps, res, do):
    q, k, v, o, lse, seg, n_valid = res  # padded to Np
    bh, n_padded, d = q.shape
    pad = n_padded - n_valid
    if pad:
        do = jnp.pad(do, ((0, 0), (0, pad), (0, 0)))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    bq, bk = _block_sizes(n_padded, *caps)
    scale = d ** -0.5
    has_seg = seg is not None
    seg_args, dq_seg_specs, dkv_seg_specs = [], [], []
    if has_seg:
        seg_args = [seg[:, :, None], seg[:, None, :]]
        dq_seg_specs = [
            _vmem_spec((None, bq, 1), lambda b, i: (b, i, 0)),
            _vmem_spec((None, 1, n_padded), lambda b, i: (b, 0, 0)),
        ]
        dkv_seg_specs = [
            _vmem_spec((None, n_padded, 1), lambda b, j: (b, 0, 0)),
            _vmem_spec((None, 1, bk), lambda b, j: (b, 0, j)),
        ]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, n_valid=n_valid, bk=bk,
                          has_seg=has_seg),
        grid=(bh, n_padded // bq),
        in_specs=[
            _vmem_spec((None, bq, d), lambda b, i: (b, i, 0)),
            _vmem_spec((None, n_padded, d), lambda b, i: (b, 0, 0)),
            _vmem_spec((None, n_padded, d), lambda b, i: (b, 0, 0)),
            _vmem_spec((None, bq, d), lambda b, i: (b, i, 0)),
            _vmem_spec((None, bq, 1), lambda b, i: (b, i, 0)),
            _vmem_spec((None, bq, 1), lambda b, i: (b, i, 0)),
        ] + dq_seg_specs,
        out_specs=_vmem_spec((None, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n_padded, d), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta, *seg_args)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, n_valid=n_valid, bq=bq,
                          has_seg=has_seg),
        grid=(bh, n_padded // bk),
        in_specs=[
            _vmem_spec((None, n_padded, d), lambda b, j: (b, 0, 0)),
            _vmem_spec((None, bk, d), lambda b, j: (b, j, 0)),
            _vmem_spec((None, bk, d), lambda b, j: (b, j, 0)),
            _vmem_spec((None, n_padded, d), lambda b, j: (b, 0, 0)),
            _vmem_spec((None, n_padded, 1), lambda b, j: (b, 0, 0)),
            _vmem_spec((None, n_padded, 1), lambda b, j: (b, 0, 0)),
        ] + dkv_seg_specs,
        out_specs=[
            _vmem_spec((None, bk, d), lambda b, j: (b, j, 0)),
            _vmem_spec((None, bk, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_padded, d), k.dtype),
            jax.ShapeDtypeStruct((bh, n_padded, d), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta, *seg_args)

    if pad:
        dq, dk, dv = (t[:, :n_valid] for t in (dq, dk, dv))
    return dq, dk, dv


def _flash_bhnd_bwd(interpret, caps, res, do):
    return _bwd_pallas(interpret, caps, res, do)


def _flash_bhnd_seg_bwd(interpret, caps, res, do):
    dq, dk, dv = _bwd_pallas(interpret, caps, res, do)
    seg, n_valid = res[5], res[6]
    # integer segment ids have no tangent space; float0 is the formal
    # zero cotangent custom_vjp requires for them (shape of the UNPADDED
    # primal input)
    dseg = np.zeros((seg.shape[0], n_valid), dtype=jax.dtypes.float0)
    return dq, dk, dv, dseg


_flash_bhnd.defvjp(_flash_bhnd_fwd, _flash_bhnd_bwd)
_flash_bhnd_seg.defvjp(_flash_bhnd_seg_fwd, _flash_bhnd_seg_bwd)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    interpret: bool | None = None,
    block_q: int = 512,
    block_kv: int = 512,
    seg: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Fused attention. q, k, v: [B, N, heads, d] -> [B, N, heads, d].

    Softmax statistics accumulate in fp32 regardless of input dtype.
    ``interpret`` defaults to True off-TPU so CPU tests run the same code.
    ``block_q``/``block_kv`` cap the kernel block sizes
    (``kernels.flash_block_q/kv``; actual = largest divisor within cap).
    ``seg``: optional [B, N] int32 segment ids — block-diagonal attention
    for the crop-packed batch (ops/packing.py); same ``-inf`` masking
    class the kernels already apply to pad columns.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, N, h, d = q.shape
    to_bhnd = lambda t: t.transpose(0, 2, 1, 3).reshape(B * h, N, d)
    caps = (int(block_q), int(block_kv))
    if seg is None:
        o = _flash_bhnd(to_bhnd(q), to_bhnd(k), to_bhnd(v), interpret, caps)
    else:
        seg_bh = jnp.broadcast_to(
            seg.astype(jnp.int32)[:, None, :], (B, h, N)).reshape(B * h, N)
        o = _flash_bhnd_seg(to_bhnd(q), to_bhnd(k), to_bhnd(v), seg_bh,
                            interpret, caps)
    return o.reshape(B, h, N, d).transpose(0, 2, 1, 3)
