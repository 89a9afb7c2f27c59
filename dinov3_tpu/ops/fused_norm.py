"""Fused LayerNorm as a Pallas TPU kernel with a custom VJP.

Why: the round-1 profile of the ViT-L fused train step showed an ~18 ms
fp32 elementwise tail dominated by layernorm statistics (of a 136 ms step)
— XLA lowers the norm to separate reduce + apply fusions, reading the
activation twice in fp32 per norm and more in the backward. This kernel
reads the bf16 activation once, keeps mean/rstd in registers (fp32), and
writes the normalized output once; the backward recomputes the statistics
in-register instead of saving them, and accumulates dscale/dbias across
row-blocks in VMEM.

(reference: the PyTorch original uses torch.nn.LayerNorm = cuDNN fused
kernels; the JAX port used plain ``nn.LayerNorm``/fp32 math with no fusion
control — dinov3_jax/layers/rms_norm.py and nn.LayerNorm call sites.)

Dispatch contract (``fused_layernorm``):
- Pallas kernel on a TPU backend when the trailing dim is lane-aligned
  (D % 128 == 0);
- under a multi-device mesh the kernel runs inside a ``shard_map`` island
  over the row-sharded activation: LayerNorm is row-local (statistics
  reduce over D only, which is never sharded — parallel/sharding.py maps
  ``embed_act`` to None), so each device normalizes its own rows and no
  collective is needed. Without the island an opaque custom call inside a
  GSPMD program would force replication;
- identical fp32 math through plain XLA ops otherwise (CPU test meshes,
  odd widths, row counts not divisible by the mesh's data axes) — same
  values, same gradients.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK_ROWS = 256


def _vmem_spec(block_shape=None, index_map=None):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _stats(x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return xc, jax.lax.rsqrt(var + eps)


def _mask_rows(t, i, br, n_valid):
    """Zero rows beyond n_valid so garbage in the padded tail of the last
    block cannot reach the stats or the dscale/dbias accumulators."""
    row = i * br + jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
    return jnp.where(row < n_valid, t, 0.0)


def _fwd_kernel(x_ref, s_ref, b_ref, y_ref, *, eps, n_valid, br):
    x = x_ref[...].astype(jnp.float32)
    if n_valid % br:
        x = _mask_rows(x, pl.program_id(0), br, n_valid)
    xc, rstd = _stats(x, eps)
    s = s_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    y_ref[...] = (xc * rstd * s + b).astype(y_ref.dtype)


def _bwd_kernel(x_ref, s_ref, g_ref, dx_ref, ds_ref, db_ref,
                *, eps, n_valid, br):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if n_valid % br:
        x = _mask_rows(x, i, br, n_valid)
        g = _mask_rows(g, i, br, n_valid)
    xc, rstd = _stats(x, eps)
    xhat = xc * rstd
    gs = g * s_ref[...].astype(jnp.float32)
    c1 = jnp.mean(gs, axis=-1, keepdims=True)
    c2 = jnp.mean(gs * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (gs - c1 - xhat * c2)).astype(dx_ref.dtype)
    ds_ref[...] += jnp.sum(g * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(g, axis=0, keepdims=True)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln_2d(x, scale, bias, eps, interpret):
    y, _ = _ln_2d_fwd(x, scale, bias, eps, interpret)
    return y


def _pallas_shapes(R: int):
    br = min(_BLOCK_ROWS, _round_up(R, 16))
    return br, pl.cdiv(R, br)


def _ln_2d_fwd(x, scale, bias, eps, interpret):
    R, D = x.shape
    br, grid = _pallas_shapes(R)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, n_valid=R, br=br),
        grid=(grid,),
        in_specs=[
            _vmem_spec((br, D), lambda i: (i, 0)),
            _vmem_spec((1, D), lambda i: (0, 0)),
            _vmem_spec((1, D), lambda i: (0, 0)),
        ],
        out_specs=_vmem_spec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=interpret,
    )(x, scale, bias)
    return y, (x, scale)


def _ln_2d_bwd(eps, interpret, res, g):
    x, scale = res
    R, D = x.shape
    br, grid = _pallas_shapes(R)
    dx, ds, db = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, n_valid=R, br=br),
        grid=(grid,),
        in_specs=[
            _vmem_spec((br, D), lambda i: (i, 0)),
            _vmem_spec((1, D), lambda i: (0, 0)),
            _vmem_spec((br, D), lambda i: (i, 0)),
        ],
        out_specs=[
            _vmem_spec((br, D), lambda i: (i, 0)),
            _vmem_spec((1, D), lambda i: (0, 0)),
            _vmem_spec((1, D), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x.dtype),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
        ],
        interpret=interpret,
    )(x, scale, g)
    return dx, ds.astype(scale.dtype), db.astype(scale.dtype)


_ln_2d.defvjp(_ln_2d_fwd, _ln_2d_bwd)


def _xla_layernorm(x, scale, bias, eps, reduce_dtype=jnp.float32):
    xf = x.astype(reduce_dtype)
    xc, rstd = _stats(xf, eps)
    y = xc * rstd * scale.astype(reduce_dtype) + bias.astype(reduce_dtype)
    return y.astype(x.dtype)


def use_pallas_layernorm(D: int) -> bool:
    """Opt-in (DINOV3_FUSED_LN=1): measured on v5e, the ViT-L train step is
    *faster without* this kernel — XLA fuses the LN statistics directly
    into the preceding matmul fusions (the round-2 profile's
    convert_reduce_fusions run at ~86% MXU), and an opaque custom call
    breaks those fusions and adds ~240 kernel launches per step (measured
    53.7 vs 58.9 img/s). Kept for workloads where the norm is NOT adjacent
    to a matmul."""
    import os

    if os.environ.get("DINOV3_FUSED_LN", "0") != "1":
        return False
    return jax.default_backend() == "tpu" and D % 128 == 0


def _island_specs(mesh, shape):
    """PartitionSpecs for running the row-local kernel per-shard under a
    multi-device mesh: rows (dim 0) over the data axes, tokens (dim 1 of
    rank-3 activations) over ``seq``, D unsharded. Returns None when the
    shape does not divide the mesh, or under pipeline parallelism — there
    the norms run inside the stage-vmapped pipeline body whose buffers are
    sharded over ``pipe``, a layout these specs cannot express (caller
    falls back to XLA)."""
    from jax.sharding import PartitionSpec as P

    from dinov3_tpu.parallel.mesh import data_axes, data_parallel_size

    if int(mesh.shape.get("pipe", 1)) > 1:
        return None
    if shape[0] % data_parallel_size(mesh) != 0:
        return None
    mid = [None] * (len(shape) - 2)
    if len(shape) >= 3 and int(mesh.shape.get("seq", 1)) > 1:
        if shape[1] % int(mesh.shape["seq"]) != 0:
            return None
        mid[0] = "seq"
    return P(data_axes(mesh), *mid, None)


def fused_layernorm(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    eps: float = 1e-6,
    interpret: bool | None = None,
    force: bool | None = None,
) -> jnp.ndarray:
    """LayerNorm over the trailing dim: fp32 stats, output in ``x.dtype``.

    ``force=True`` runs the Pallas kernel regardless of backend (tests use
    it with ``interpret=True`` on CPU); ``force=False`` forces the XLA path.
    """
    D = x.shape[-1]
    use = use_pallas_layernorm(D) if force is None else force
    if not use:
        return _xla_layernorm(x, scale.reshape(D), bias.reshape(D), eps)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    from dinov3_tpu.parallel.context import get_current_mesh

    mesh = get_current_mesh()
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        spec = _island_specs(mesh, x.shape)
        if spec is None:
            # the kernel was chosen and cannot run on this layout: say
            # so (an explicit force is an error; the env opt-in warns)
            msg = (f"fused_layernorm: shape {tuple(x.shape)} does not map "
                   f"onto mesh {dict(mesh.shape)}; running the XLA "
                   "layernorm instead of the Pallas kernel")
            if force:
                raise ValueError(msg)
            import warnings

            warnings.warn(msg, stacklevel=2)
            return _xla_layernorm(x, scale.reshape(D), bias.reshape(D), eps)

        def _local(xs, s, b):
            return _ln_nd(xs, s, b, float(eps), interpret)

        return jax.shard_map(
            _local, mesh=mesh,
            in_specs=(spec, P(None), P(None)),
            out_specs=spec,
            # no collectives in the island (row-local math); pallas_call's
            # out_shape carries no vma so the varying-axes check must be off
            check_vma=False,
        )(x, scale.reshape(D), bias.reshape(D))

    return _ln_nd(x, scale, bias, float(eps), interpret)


def _ln_nd(x, scale, bias, eps, interpret):
    """Flatten leading dims, run the 2-D kernel, restore the shape."""
    D = x.shape[-1]
    lead = x.shape[:-1]
    R = 1
    for s in lead:
        R *= s
    y = _ln_2d(
        x.reshape(R, D), scale.reshape(1, D), bias.reshape(1, D),
        eps, interpret,
    )
    return y.reshape(*lead, D)
