"""The held experts' block of ``ops/ffn.py RoutedExpertsFFN`` — from the
gathered, expert-sorted ``rows`` to the weighted rows the combine adds
back — as grouped-matmul Pallas kernels under one ``jax.custom_vjp``:

    out[r] = w_rows[r] * (act(g) * u) W3_e,  [g ; u] = rows[r] W12_e,

(or, ``gate`` "relu2", an UN-GATED expert of two matrices: out[r] =
w_rows[r] * relu(h)^2 W2_e, h = rows[r] W1_e — the same five kernels with
the first matrix and the plane between the products H wide, not 2 H, and
no u half: ``_hidden``) for the rows r of expert e's group (``sizes[e]`` rows, the groups one
after another from row 0) and exactly 0, value and gradient, for every
row past the last group whatever the buffer holds there.

What the kernels are given is the list of VISITS, made from the group
sizes by ``_visits`` and scalar-prefetched: a visit is (expert, row tile)
for every tile an expert has rows in, in row order (an expert with no
rows is visited once, so that its weights' gradient is written), so a
tile two experts share is visited once an expert with the other's rows
masked, and a tile past the last routed row is visited by no product.
The grid is static (row tiles + experts - 1 steps: the most there can
be); the steps past the last visit fetch nothing (their input blocks are
the last visit's) and spend themselves zero-filling the output tiles no
visit reached, so the block answers for the whole buffer.

- ``_walk`` (forward: ``EXPERTS_UP`` rows x W12 -> [g ; u] rounded from
  the float32 accumulator, ``EXPERTS_DOWN`` (act(g) * u) x W3 with the
  gate in its prologue and ``* w_rows`` in float32 in its epilogue;
  backward: ``EXPERTS_BACK`` cotangent x W3^T, the gate's derivative and
  d ``w_rows``, then x W12^T, in one visit): a grid step is one visit,
  the expert's whole weight matrices are VMEM blocks that are fetched
  when the expert changes, the row tile's result is written under the
  expert's row mask.
- ``_sum_groups`` (``EXPERTS_DW12`` rows^T x d[g ; u], ``EXPERTS_DW3``
  (act(g) * u)^T x the weighted cotangent): an expert's float32 output
  block stays in VMEM over its visits, by column tiles where the whole
  matrix would not fit.

Five kernels, not more, because a step's set-up pays for each one it has
to trace, lower and load: with eight traced (a forward that kept act(g) * u
apart, two backward walks, four branches a walk) the 16k cell's warm
``setup_s`` read 4.3 s over the parent's 35, with these five 1.2 s (my chip
runs, PR 43). The primal and the forward rule are the same two calls, so
a layer's rematerialisation traces nothing new.

Between the two products, and between the two passes, crosses ONE
bfloat16 ``[rows, 2 H]`` plane, [g ; u] (beside ``rows``, the block's
input); both passes make act(g) * u of it in VMEM. No float32
``[rows, 2 H]`` plane exists anywhere, and one float32 ``[rows, D]``:
the forward combine's operand. The cotangent of ``out`` is rounded to
bfloat16 where it enters the backward (as XLA's default precision does
with a product's operand on a TPU; the layer's arrives in bfloat16, so
nothing is lost and no float32 plane is read), every accumulation is
float32, the weights' gradients leave as float32.

``grouped_matmul_path`` reads the path and the tiles off the shapes;
``ragged_experts_block`` is the ``lax.ragged_dot`` form: the fall-back,
what a CPU's compiled step takes, and the tests' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
EXPERTS_UP = "moe_experts_up"
EXPERTS_DOWN = "moe_experts_down"
EXPERTS_BACK = "moe_experts_back"
EXPERTS_DW12 = "moe_experts_dw12"
EXPERTS_DW3 = "moe_experts_dw3"
# an expert's [D, 2 H] bfloat16 matrix is ONE block (twice over: the
# pipeline's two buffers), and so is a float32 gradient block of it
_WEIGHT_BLOCK_BYTES = 16 * 1024 * 1024
_COMPILER_PARAMS = dict(vmem_limit_bytes=100 * 1024 * 1024)
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
UNGATED = "relu2"  # an expert of two matrices: W2 relu(W1 x)^2, no u half


def ragged_experts_block(rows, w12, w3, w_rows, sizes, kept, gate: str):
    """The block as two ``lax.ragged_dot`` between XLA's own elementwise
    passes. The rows past the last group belong to no expert, and
    ``ragged_dot`` does not say what it leaves there: on the CPU zeros,
    on a v5e whatever the buffer held — in the BACKWARD pass too, where
    the gradient with respect to those rows went into the tokens'
    gradient and was a million times the true one (my chip runs, PR 27).
    Both ends are masked by ``kept``, values and gradients alike."""
    dtype = rows.dtype
    rows = jnp.where(kept[:, None], rows, 0)
    h = jax.lax.ragged_dot(
        rows, w12.astype(dtype), sizes,
        preferred_element_type=jnp.float32).astype(dtype)
    if gate == UNGATED:
        hidden = jnp.square(jax.nn.relu(h))
    else:
        g, u = jnp.split(h, 2, axis=-1)
        hidden = _ACTS[gate](g) * u
    out = jax.lax.ragged_dot(
        hidden, w3.astype(dtype), sizes,
        preferred_element_type=jnp.float32)
    return jnp.where(kept[:, None], out, 0.0) * w_rows[:, None]


def row_tile(cap: int) -> int | None:
    """Rows a visit: 256, or 128 where 256 does not divide the buffer;
    None where neither does. (On a v5e, both passes of the block at the
    five cells' shapes and fills: 128 and 256 rows within 2.5 % of each
    other, 512 rows 3-10 % slower, since a tile two experts share is
    computed once an expert; ``chip_smoke.py --phases moe``, PR 43.)"""
    return next((t for t in (256, 128) if cap % t == 0), None)


def _column_tiles(rows: int, columns: int) -> int:
    """Into how many column tiles ``_sum_groups`` cuts a float32
    [rows, columns] gradient block so that one fits
    ``_WEIGHT_BLOCK_BYTES`` (whole lanes; 1 where the matrix fits)."""
    n = 1
    while (rows * (columns // n) * 4 > _WEIGHT_BLOCK_BYTES
           and columns % (2 * n * LANES) == 0):
        n *= 2
    return n


def grouped_matmul_path(cap: int, d: int, h: int, dtype,
                        interpret: bool | None = None,
                        gate: str = "silu") -> tuple[str, str]:
    """(path, why) the experts' block of a ``[cap, d]`` row buffer over
    experts of hidden width ``h`` takes on this backend:
    ("kernel", ...) or ("ragged_dot", the reason it is not the kernels).
    ``gate`` says how wide an expert's first matrix is: [d, 2 h], or
    [d, h] of an un-gated expert (``UNGATED``). The hidden width may end
    in HALF a lane tile (1856 = 14.5 tiles: the [rows, h] plane and the
    matrices are whole blocks as they lie, and Mosaic pads the last tile
    in VMEM and masks it in a contraction); the rows' width d may not,
    since the row movement's kernel takes whole tiles."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "ragged_dot", f"the rows are {jnp.dtype(dtype).name}, not bfloat16"
    if d % LANES or h % (LANES // 2):
        return "ragged_dot", (f"widths {d} and {h} are not whole tiles of "
                              f"{LANES} lanes")
    if row_tile(cap) is None:
        return "ragged_dot", f"a buffer of {cap} rows is not whole tiles of 128"
    wide = h if gate == UNGATED else 2 * h
    if d * wide * 2 > _WEIGHT_BLOCK_BYTES:
        return "ragged_dot", (
            f"an expert's [{d}, {wide}] matrix ({d * wide * 2 >> 20} MiB) is "
            f"more than a VMEM block of {_WEIGHT_BLOCK_BYTES >> 20} MiB")
    backend = jax.default_backend()
    if interpret is None and backend != "tpu":
        return "ragged_dot", f"the backend is {backend}, not a TPU"
    return "kernel", "interpreted" if interpret else "compiled for the TPU"


# ------------------------------------------------------------ the visits


@functools.partial(jax.jit, inline=True, static_argnames=("cap", "tm"))
def _visits(sizes, cap: int, tm: int):
    """The scalar-prefetched operands of every kernel, int32, one entry a
    grid step (``cap // tm + groups - 1`` of them): the expert, the row
    tile read, the row tile written, and the rows [lo, hi) of that tile
    that are the expert's. A group with no rows is visited once, at the
    tile its first row would be in, with lo = hi. A step past the last
    visit keeps the last visit's expert and input tile (nothing is
    fetched), has lo = hi too, and is handed, to zero-fill, the next
    output tile no visit wrote (the last tile again once there is none).
    (Sums over a [steps, groups] one-hot where an index would do: a gather
    costs ten times a sum to trace.)"""
    groups, tiles = sizes.shape[0], cap // tm
    sizes = sizes.astype(jnp.int32)
    ends = jax.lax.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles - 1)
    n = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 1)
    stop = jax.lax.cumsum(n)
    v = jax.lax.iota(jnp.int32, tiles + groups - 1)
    of = (v[:, None] >= (stop - n)[None]) & (v[:, None] < stop[None])
    pick = lambda x: jnp.sum(jnp.where(of, x[None], 0), axis=1)  # noqa: E731
    live = v < stop[-1]
    tile = pick(first - (stop - n)) + v
    last_tile = jnp.max(jnp.where(live, tile, 0))
    return (jnp.where(live, pick(jax.lax.iota(jnp.int32, groups)), groups - 1),
            jnp.where(live, tile, last_tile),
            jnp.where(live, tile, jnp.minimum(
                last_tile + 1 + v - stop[-1], tiles - 1)),
            jnp.clip(pick(starts) - tile * tm, 0, tm),
            jnp.clip(pick(ends) - tile * tm, 0, tm))


def _mine(meta, v, tm):
    """[tm, 1] bool: the rows of step ``v``'s tile that are its expert's."""
    r = jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (r >= meta[3][v]) & (r < meta[4][v])


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


# ------------------------------------------------------------ row walks


def _walk_kernel(*refs, body, tm, n_in):
    meta, ins, outs = refs[:5], refs[5:5 + n_in], refs[5 + n_in:]
    written, lo, hi = meta[2:]
    v = pl.program_id(0)
    new_tile = (v == 0) | (written[v] != written[jnp.maximum(v - 1, 0)])
    rows_here = hi[v] > lo[v]

    def held(o):  # a tile is zeros when it is first met
        return jnp.where(new_tile, jnp.zeros_like(o), o[...])

    def visit():  # under the expert's row mask, over what the tile holds
        mine = _mine(meta, v, tm)
        for o, value in zip(outs, body(*ins)):
            o[...] = jnp.where(mine, value.astype(o.dtype), held(o))

    def no_rows():
        for o in outs:
            o[...] = held(o)

    # (one two-way branch, not two ``pl.when``: a branch is a tenth of a
    # kernel's tracing, and a step's set-up pays for every one)
    jax.lax.cond(rows_here, visit, no_rows)


def _walk(body, name, meta, tm, tiled, weights, out_widths, out_dtypes,
          interpret):
    """One ``pallas_call`` over the visits: ``tiled`` [cap, w] operands a
    row tile a step, ``weights`` [groups, a, b] the visit's expert whole,
    outputs [cap, w] written under the expert's row mask; ``body(*refs)``
    gives a tile's values, one an output."""
    cap = tiled[0].shape[0]
    steps = meta[0].shape[0]
    tile_in = lambda w: pl.BlockSpec(  # noqa: E731
        (tm, w), lambda v, g, read, *_: (read[v], 0), memory_space=pltpu.VMEM)
    tile_out = lambda w: pl.BlockSpec(  # noqa: E731
        (tm, w), lambda v, g, read, written, *_: (written[v], 0),
        memory_space=pltpu.VMEM)
    whole = lambda a, b: pl.BlockSpec(  # noqa: E731
        (None, a, b), lambda v, g, *_: (g[v], 0, 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_walk_kernel, body=body, tm=tm,
                          n_in=len(tiled) + len(weights)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(steps,),
            in_specs=[tile_in(x.shape[1]) for x in tiled]
            + [whole(*w.shape[1:]) for w in weights],
            out_specs=[tile_out(w) for w in out_widths]),
        out_shape=[jax.ShapeDtypeStruct((cap, w), t)
                   for w, t in zip(out_widths, out_dtypes)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), **_COMPILER_PARAMS),
        interpret=interpret, name=name,
    )(*meta, *tiled, *weights)


def _gate_parts(h_ref, gate):
    """(act(g), act'(g), u) float32 of a [tm, 2 H] tile [g ; u]."""
    hid = h_ref.shape[1] // 2
    g = h_ref[:, :hid].astype(jnp.float32)
    u = h_ref[:, hid:].astype(jnp.float32)
    if gate == "relu":
        on = g > 0
        return jnp.where(on, g, 0.0), on.astype(jnp.float32), u
    s = jax.nn.sigmoid(g)
    return g * s, s * (1.0 + g * (1.0 - s)), u


def _hidden(h_ref, gate):
    """The rows the second product reads, [tm, H] float32: act(g) * u of a
    gated tile, relu(h)^2 of an un-gated one (``gate`` "relu2": the tile
    is [tm, H], there is no u half)."""
    if gate == UNGATED:
        r = jnp.maximum(h_ref[...].astype(jnp.float32), 0.0)
        return r * r
    act, _, u = _gate_parts(h_ref, gate)
    return act * u


def _up_body(rows, w12):
    return (_dot(rows[...], w12[...]),)


def _down_body(h, w_rows, w3, *, gate):
    return (_dot(_hidden(h, gate).astype(h.dtype), w3[...]) * w_rows[...],)


def _back_body(ct, w_rows, h, w3, w12, *, gate):
    da = _dot(ct[...].astype(h.dtype), w3[...], _NT)   # of w_rows * (a W3)
    if gate == UNGATED:
        r = jnp.maximum(h[...].astype(jnp.float32), 0.0)
        d_w = jnp.sum(r * r * da, axis=1, keepdims=True)
        dh = (da * w_rows[...] * (2.0 * r)).astype(h.dtype)
        return dh, d_w, _dot(dh, w12[...], _NT)
    act, slope, u = _gate_parts(h, gate)
    d_w = jnp.sum(act * u * da, axis=1, keepdims=True)
    da = da * w_rows[...]
    dh = jnp.concatenate([da * u * slope, da * act], axis=1).astype(h.dtype)
    return dh, d_w, _dot(dh, w12[...], _NT)


# ------------------------------------------------------------ group sums


def _sum_kernel(*refs, body, tm, n_in):
    meta, ins, out = refs[:5], refs[5:5 + n_in], refs[5 + n_in]
    group, _, _, lo, hi = meta
    v = pl.program_id(1)

    @pl.when((v == 0) | (group[v] != group[jnp.maximum(v - 1, 0)]))
    def _():
        out[...] = jnp.zeros_like(out)

    @pl.when(hi[v] > lo[v])
    def _():
        left, right = body(*ins, mine=_mine(meta, v, tm))
        # [tm, a]^T [tm, b]: the left operand is turned in float32, as
        # Mosaic turns planes, and rounded where the forward's was
        out[...] += _dot(left.T.astype(right.dtype), right)


def _sum_groups(body, name, meta, tm, shape, whole, cut, interpret):
    """``shape`` = [groups, a, b] float32: for each expert the sum over
    its visits of left^T right, the two [tm, a] float32 and [tm, b]
    planes ``body(*refs, mine=)`` makes of a row tile of the ``whole``
    operands and of the ``cut`` one, which like the result goes by column
    tiles; one of the two has the other experts' rows zeroed."""
    groups, a, b = shape
    pieces = _column_tiles(a, b)
    tile = lambda w: pl.BlockSpec(  # noqa: E731
        (tm, w), lambda j, v, g, read, *_: (read[v], 0),
        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_sum_kernel, body=body, tm=tm, n_in=len(whole) + 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(pieces, meta[0].shape[0]),
            in_specs=[tile(x.shape[1]) for x in whole] + [pl.BlockSpec(
                (tm, b // pieces), lambda j, v, g, read, *_: (read[v], j),
                memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(
                (None, a, b // pieces), lambda j, v, g, *_: (g[v], 0, j),
                memory_space=pltpu.VMEM)),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), **_COMPILER_PARAMS),
        interpret=interpret, name=name,
    )(*meta, *whole, cut)


def _dw12_body(rows, dh, *, mine):
    return rows[...].astype(jnp.float32), jnp.where(mine, dh[...], 0)


def _dw3_body(h, w_rows, ct, *, mine, gate):
    return (jnp.where(mine, _hidden(h, gate), 0.0),
            (ct[...].astype(jnp.float32) * w_rows[...]).astype(h.dtype))


# ------------------------------------------------------------ the block
# (inline jits, as ``ops/mixer_chains.py``'s: a model's routed layers and
# their passes trace the kernels once a shape, not once a call)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "gate", "tm", "interpret"))
def _forward(rows, w12, w3, w_rows, meta, gate, tm, interpret):
    """(out [cap, D] float32, [g ; u] [cap, 2 H] as the rows' type)."""
    d = rows.shape[1]
    h, = _walk(_up_body, EXPERTS_UP, meta, tm, [rows],
               [w12.astype(rows.dtype)], [w12.shape[2]], [rows.dtype],
               interpret)
    out, = _walk(functools.partial(_down_body, gate=gate), EXPERTS_DOWN, meta,
                 tm, [h, w_rows[:, None]], [w3.astype(rows.dtype)], [d],
                 [jnp.float32], interpret)
    return out, h


@functools.partial(jax.jit, inline=True, static_argnames=(
    "gate", "tm", "interpret"))
def _backward(rows, h, w12, w3, w_rows, meta, ct, gate, tm, interpret):
    """The cotangents of rows, w12, w3 and w_rows from what the forward
    rule kept and ``ct``, the cotangent of ``out``."""
    d = rows.shape[1]
    groups, hid = w3.shape[:2]
    # rounded HERE, where it enters the products, and not widened: the
    # layer's combine hands on a bfloat16 cotangent behind the widening
    # its rule's signature needs, and XLA folds the pair away, so the
    # kernels read a plane of half the bytes (``ops/routed_rows.py``)
    ct, w_col = ct.astype(rows.dtype), w_rows[:, None]
    dh, d_w, d_rows = _walk(
        functools.partial(_back_body, gate=gate), EXPERTS_BACK, meta, tm,
        [ct, w_col, h], [w3.astype(rows.dtype), w12.astype(rows.dtype)],
        [w12.shape[2], 1, d], [rows.dtype, jnp.float32, rows.dtype],
        interpret)
    d_w12 = _sum_groups(_dw12_body, EXPERTS_DW12, meta, tm,
                        (groups, d, w12.shape[2]), [rows], dh, interpret)
    d_w3 = _sum_groups(functools.partial(_dw3_body, gate=gate), EXPERTS_DW3,
                       meta, tm, (groups, hid, d), [h, w_col], ct, interpret)
    return (d_rows, d_w12.astype(w12.dtype), d_w3.astype(w3.dtype),
            d_w[:, 0].astype(w_rows.dtype))


def _forward_once(rows, w12, w3, w_rows, meta, gate, tm, interpret):
    # JAX traces a rule's primal with no abstract mesh set and its forward
    # rule under the empty one: two keys in ``_forward``'s cache for one
    # program, so set-up would trace and lower the forward's kernels twice.
    # Setting the mesh to what it already is makes the two keys one.
    with jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh()):
        return _forward(rows, w12, w3, w_rows, meta, gate=gate, tm=tm,
                        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _block(rows, w12, w3, w_rows, meta, gate, tm, interpret):
    return _forward_once(rows, w12, w3, w_rows, meta, gate, tm, interpret)[0]


def _block_fwd(rows, w12, w3, w_rows, meta, gate, tm, interpret):
    out, h = _forward_once(rows, w12, w3, w_rows, meta, gate, tm, interpret)
    return out, (rows, h, w12, w3, w_rows, meta)


def _block_bwd(gate, tm, interpret, res, ct):
    return _backward(*res, ct, gate=gate, tm=tm, interpret=interpret) + (None,)


_block.defvjp(_block_fwd, _block_bwd)


def experts_block(rows, w12, w3, w_rows, sizes, gate, tm, interpret):
    """The block on the kernel path: ``rows`` [cap, D] bfloat16 sorted by
    expert, ``w12`` [held, D, 2 H] and ``w3`` [held, H, D] as the module
    holds them (rounded to the rows' type here; ``gate`` "relu2": ``w12``
    is W1 [held, D, H] and ``w3`` W2), ``w_rows`` [cap] float32,
    ``sizes`` [held] int32 (their sum at most cap), ``gate`` "silu" |
    "relu" | "relu2", ``tm`` = ``row_tile(cap)``: [cap, D] float32, the rows
    past the last group exactly 0 (``grouped_matmul_path`` says which
    shapes it takes). The visits are made here, once, outside the rule:
    both passes are given them."""
    return _block(rows, w12, w3, w_rows,
                  _visits(sizes, cap=rows.shape[0], tm=tm), gate, tm, interpret)
