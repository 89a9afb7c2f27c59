"""The state-space duality (SSD) recurrence of a Mamba-2 mixer, in chunks.

Per head h of ``heads`` (``head_dim`` = P wide), reading group h //
(heads / groups)'s B_t, C_t in R^N (``state`` = N), with a step
dt_t > 0 and ONE rate a_h < 0 a head:

    S_t = exp(dt_t a_h) S_{t-1} + dt_t u_t B_t^T,   S_0 = 0 in R^{P x N}
    y_t = S_t C_t

(the D u skip is the caller's: it is an elementwise term of the chain
that follows). The delta rule of ``ops/kda.py`` cannot compute it: that
one writes b (v - S^T k), this one dt u B^T, with nothing read back from
the state before the write, so a chunk needs no triangular inverse.
Inside a chunk of L tokens, with G_t the running sum of dt a from the
chunk's first token and S the state the chunk starts from,

    y_t = e^{G_t} S C_t + sum_{s <= t} (C_t . B_s) e^{G_t - G_s} dt_s u_s
    S'  = e^{G_L} S + sum_s e^{G_L - G_s} dt_s u_s B_s^T

: ONE plane C B^T a GROUP and chunk (its heads share it), ONE decay mask
e^{G_t - G_s} dt_s a HEAD, two products with the state a head.

``ssd_chunked`` takes u, B and C where the mixer's convolution leaves
them, side by side in one ``[B, T, heads P + 2 groups N]`` plane
([u | B | C]), and has two paths that share these definitions;
``ssd_path`` chooses from shapes, types and backend, no option or
variable:

- the KERNEL PAIR (``ssd_chunk_fwd`` / ``ssd_chunk_bwd``, a
  ``pallas_call`` each under one ``custom_vjp``) on a TPU (or where a
  test asks for ``interpret``) at bfloat16 planes, heads of 64 on a
  state of 128, an even number of heads a group that fills whole lane
  tiles, and whole chunks of ``KERNEL_CHUNK`` tokens. A grid step is one
  (sequence, group, chunk), the chunks the sequential axis; the group's
  [N, heads P] float32 state (transposed, so that a PAIR of 64-wide heads
  is one 128-lane tile and nothing is ever cut at 64 lanes: a head's
  half of a pair's product is chosen by a lane mask) lives in VMEM
  scratch, zeroed at a sequence's first chunk: it never goes to HBM
  inside a sequence, unless the pass is the forward rule's, which also
  writes each chunk's STARTING state for the backward ([B, n, groups, N,
  heads P / groups] float32). The blocks of u, B and C are cut from the
  joined plane as it lies. The backward kernel walks the chunks last
  first with the state's cotangent in the scratch and transposes the
  chunk by hand; what a decay gives to G and to dt leaves as two rows a
  head and token, and JAX's own transposition of the running sum outside
  turns them into the gradients of dt and a.
- the PLAIN path everywhere else (``_chunk`` under ``lax.scan``,
  float32, each chunk's body rematerialised, its backward JAX's own): the
  CPU's, the tests' oracle next to ``ssd_recurrent``. Any chunk; a row
  that is no whole number of chunks is padded with tokens that neither
  decay nor write (dt = 0) and their outputs dropped.

Precision: dt, G, every exponential and the state are float32. In the
kernels the products with the state run at ``Precision.HIGHEST`` (a
float32 matmul is otherwise one bfloat16 pass on a TPU); C B^T is exact
in one pass (bfloat16 operands, float32 accumulation), and the masked
plane (C B^T) * mask is rounded to bfloat16 for its product with u, as a
flash-attention kernel rounds its probabilities.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PLAIN_CHUNK = 128
KERNEL_CHUNK = 128
KERNEL_NAME = "ssd_chunk_fwd"
BACKWARD_KERNEL_NAME = "ssd_chunk_bwd"
LANES, HALF = 128, 64
_NEG = -1e30
_HI = jax.lax.Precision.HIGHEST
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def split_plane(xbc, heads: int, head_dim: int, groups: int, state: int):
    """([B, T, heads, P], [B, T, groups, N], [B, T, groups, N]) out of the
    joined plane [u | B | C]."""
    b, t, _ = xbc.shape
    inner, gn = heads * head_dim, groups * state
    return (xbc[..., :inner].reshape(b, t, heads, head_dim),
            xbc[..., inner:inner + gn].reshape(b, t, groups, state),
            xbc[..., inner + gn:].reshape(b, t, groups, state))


def ssd_path(heads: int, head_dim: int, groups: int, state: int, tokens: int,
             dtype, interpret: bool | None = None) -> tuple[str, str]:
    """(path, why) ``ssd_chunked`` takes at these sizes on this backend:
    ("kernel", ...) or ("scan", the reason it is not the kernel pair)."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "scan", f"the plane is {jnp.dtype(dtype).name}, not bfloat16"
    if (head_dim, state) != (HALF, LANES):
        return "scan", (f"heads of {head_dim} on a state of {state}: the "
                        f"kernels take {HALF} on {LANES}")
    if heads % groups or (heads // groups) % 2:
        return "scan", (f"{heads} heads in {groups} groups: a pair of heads a "
                        "lane tile")
    if tokens % KERNEL_CHUNK:
        return "scan", (f"{tokens} tokens are not whole chunks of "
                        f"{KERNEL_CHUNK}")
    backend = jax.default_backend()
    if interpret is None and backend != "tpu":
        return "scan", f"the backend is {backend}, not a TPU"
    return "kernel", "interpreted" if interpret else "compiled for the TPU"


# ------------------------------------------------------------ plain path


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _chunk(s0, u, bm, cm, dt, a):
    """One chunk, float32. s0 [B, G, J, P, N] (J heads a group); u
    [B, L, G, J, P]; bm, cm [B, L, G, N]; dt [B, L, G, J]; a [G, J].
    Returns (state', y [B, L, G, J, P])."""
    big = jnp.cumsum(dt * a, axis=1)                      # G_t, <= 0
    n = u.shape[1]
    lower = jnp.arange(n)[:, None] >= jnp.arange(n)[None]
    cb = _mm("blgn,bsgn->bgls", cm, bm)
    diff = big[:, :, None] - big[:, None]                  # [B, t, s, G, J]
    mask = jnp.exp(jnp.where(lower[None, :, :, None, None], diff, _NEG)) \
        * dt[:, None]
    y = _mm("btsgj,bsgjp->btgjp", cb.transpose(0, 2, 3, 1)[..., None] * mask, u)
    y = y + jnp.exp(big)[..., None] * _mm("blgn,bgjpn->blgjp", cm, s0)
    to_end = jnp.exp(big[:, -1:] - big) * dt
    new = jnp.exp(big[:, -1])[..., None, None] * s0 \
        + _mm("bsgjp,bsgn->bgjpn", u * to_end[..., None], bm)
    return new, y


def _scan_forward(xbc, dt, a, heads, head_dim, groups, state, chunk):
    b, t, _ = xbc.shape
    pad = (-t) % chunk
    if pad:
        xbc = jnp.pad(xbc, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    n, j = (t + pad) // chunk, heads // groups
    u, bm, cm = split_plane(xbc.astype(jnp.float32), heads, head_dim, groups,
                            state)
    chunks = tuple(
        jnp.moveaxis(x.reshape((b, n, chunk) + shape), 1, 0)
        for x, shape in ((u, (groups, j, head_dim)), (bm, (groups, state)),
                         (cm, (groups, state)), (dt, (groups, j))))
    body = jax.checkpoint(
        lambda s, xs: _chunk(s, *xs, a.reshape(groups, j)))
    _, y = jax.lax.scan(
        body, jnp.zeros((b, groups, j, head_dim, state), jnp.float32), chunks)
    return jnp.moveaxis(y, 0, 1).reshape(b, t + pad, heads * head_dim)[:, :t]


def ssd_recurrent(xbc, dt, a, heads, head_dim, groups, state):
    """The recurrence itself, token by token (tests and small sizes):
    float32 [B, T, heads P]."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    u, bm, cm = split_plane(f32(xbc), heads, head_dim, groups, state)
    bm, cm = (jnp.repeat(x, heads // groups, axis=2) for x in (bm, cm))
    dt, a = f32(dt), f32(a)

    def step(s, xs):
        ut, bt, ct, dtt = xs
        s = jnp.exp(dtt * a)[..., None, None] * s + jnp.einsum(
            "bhp,bhn->bhpn", ut * dtt[..., None], bt, precision=_HI)
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct, precision=_HI)

    b, t = xbc.shape[:2]
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (u, bm, cm, dt))
    _, y = jax.lax.scan(
        step, jnp.zeros((b, heads, head_dim, state), jnp.float32), xs)
    return jnp.moveaxis(y, 0, 1).reshape(b, t, heads * head_dim)


# ------------------------------------------------------- the two kernels
#
# A grid step is one (sequence, group, chunk). Its blocks: u [L, J P]
# (the group's J heads side by side, a PAIR of heads a lane tile), B and
# C [L, N], cut from the joined plane; ``cols`` [L, 2 J] float32, a
# column a head of G (the chunk's running sum of dt a, made outside) and
# then of dt, and ``rows`` [2 J, L], the same two the other way up: a
# decay mask is an outer difference of a column and a row, and nothing is
# transposed in the kernel but B (for the state's write). The state and
# its cotangent are [N, J P] float32.


def _dot(a, b, contract, precision=jax.lax.Precision.DEFAULT):
    # (DEFAULT by name: an ambient ``default_matmul_precision`` must not
    # ask Mosaic for a float32 contraction of bfloat16 operands)
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _turned(col):
    """A column [n, 1] laid down as a row [1, n] (through the diagonal:
    no relayout)."""
    n = col.shape[0]
    return jnp.sum(jnp.where(_iota((n, n), 0) == _iota((n, n), 1), col, 0.0),
                   axis=0, keepdims=True)


def _first(rows: int):
    """[rows, 128] bool: the lanes of a pair's first head."""
    return _iota((rows, LANES), 1) < HALF


def _pair_scales(cols_ref, pair, per_group):
    """Of a pair of heads, each [L, 128] float32 with a head's value over
    its 64 lanes: e^{G_t}, e^{G_L - G_t} and dt_t; and e^{G_L} [1, 128]."""
    n = cols_ref.shape[0]
    both = lambda at: jnp.where(  # noqa: E731
        _first(n), cols_ref[:, at:at + 1], cols_ref[:, at + 1:at + 2])
    big, dt = both(2 * pair), both(per_group + 2 * pair)
    end = big[n - 1:]
    return jnp.exp(big), jnp.exp(end - big), dt, jnp.exp(end)


def _fwd_kernel(*refs, per_group, keep_states):
    if keep_states:
        u_ref, b_ref, c_ref, cols_ref, rows_ref, y_ref, s_ref, st_ref = refs
    else:
        u_ref, b_ref, c_ref, cols_ref, rows_ref, y_ref, st_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    n = u_ref.shape[0]
    bm, cm = b_ref[...], c_ref[...]
    cb = _dot(cm, bm, _NT)                               # [t, s]
    lower = _iota((n, n), 0) >= _iota((n, n), 1)
    bm32, cm32 = bm.astype(jnp.float32), cm.astype(jnp.float32)
    for pair in range(per_group // 2):
        lanes = slice(pair * LANES, (pair + 1) * LANES)
        u = u_ref[:, lanes]
        inside = []
        for head in (2 * pair, 2 * pair + 1):
            mask = jnp.exp(jnp.where(
                lower, cols_ref[:, head:head + 1] - rows_ref[head:head + 1, :],
                _NEG)) * rows_ref[per_group + head:per_group + head + 1, :]
            inside.append(_dot((cb * mask).astype(u.dtype), u, _NN))
        from_start, to_end, dt, whole = _pair_scales(cols_ref, pair, per_group)
        state = st_ref[:, lanes]
        y_ref[:, lanes] = (
            jnp.where(_first(n), *inside)
            + from_start * _dot(cm32, state, _NN, _HI)).astype(y_ref.dtype)
        if keep_states:
            s_ref[:, lanes] = state
        st_ref[:, lanes] = whole * state + _dot(
            bm32, to_end * dt * u.astype(jnp.float32), _TN, _HI)


def _bwd_kernel(u_ref, b_ref, c_ref, cols_ref, rows_ref, dy_ref, s_ref,
                du_ref, db_ref, dc_ref, dvec_ref, dst_ref, *, per_group):
    """The chunk transposed by hand. With W = dy u^T (a head's lanes), E =
    e^{G_t - G_s} (s <= t) and X = W * (C B^T) * E:

        d(C B^T) = sum over the group's heads of W * E * dt_s
        du_s = sum_t (C B^T * E dt_s)[t, s] dy_t + e^{G_L - G_s} dt_s B_s dS'
        dC = d(C B^T) B + e^{G_t} dy S^T,  dB = d(C B^T)^T C + (e^{G_L - G_s} dt_s u) dS'^T
        dS = e^{G_L} dS' + C^T (e^{G_t} dy)

    and, a row a head each in ``dvec``: what G_t collects as a ROW scale
    (sum_s X_ts dt_s + dy_t . y_inter_t, and at the chunk's last token all
    that e^{G_L} scales), and what token s gives WITHOUT its dt_s (sum_t
    X_ts + e^{G_L - G_s} (B_s dS') . u_s): times dt_s it is what G_s loses
    as a column, as it stands it is dt_s's own share."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    n = u_ref.shape[0]
    bm, cm = b_ref[...], c_ref[...]
    cb = _dot(cm, bm, _NT)                               # [t, s]
    bc = _dot(bm, cm, _NT)                               # [s, t]
    lower = _iota((n, n), 0) >= _iota((n, n), 1)
    upper = _iota((n, n), 0) <= _iota((n, n), 1)
    last = _iota((1, n), 1) == n - 1
    at_row = _iota((2 * per_group, n), 0)
    bm32, cm32 = bm.astype(jnp.float32), cm.astype(jnp.float32)
    first = _first(n)

    def of_head(x, hd):   # a head's lanes of a pair's plane, added: [rows, 1]
        mine = _first(x.shape[0]) == (hd == 0)
        return jnp.sum(jnp.where(mine, x, 0.0), axis=1, keepdims=True)

    dcb = jnp.zeros((n, n), jnp.float32)
    dc = jnp.zeros(cm.shape, jnp.float32)
    db = jnp.zeros(bm.shape, jnp.float32)
    dvec = jnp.zeros((2 * per_group, n), jnp.float32)
    for pair in range(per_group // 2):
        lanes = slice(pair * LANES, (pair + 1) * LANES)
        u, dy = u_ref[:, lanes], dy_ref[:, lanes]
        u32, dy32 = u.astype(jnp.float32), dy.astype(jnp.float32)
        from_start, to_end, dt, whole = _pair_scales(cols_ref, pair, per_group)
        state, dstate = s_ref[:, lanes], dst_ref[:, lanes]
        with_state = _dot(cm32, state, _NN, _HI)         # C S, [L, 128]
        with_dstate = _dot(bm32, dstate, _NN, _HI)       # B dS', [L, 128]
        scaled_dy = from_start * dy32
        row_share = scaled_dy * with_state               # dy . y_inter
        col_share = to_end * with_dstate * u32           # no dt_s
        inside = []
        for hd, head in enumerate((2 * pair, 2 * pair + 1)):
            col, row = cols_ref[:, head:head + 1], rows_ref[head:head + 1, :]
            dt_row = rows_ref[per_group + head:per_group + head + 1, :]
            dt_col = cols_ref[:, per_group + head:per_group + head + 1]
            decay = jnp.exp(jnp.where(lower, col - row, _NEG))      # [t, s]
            w = _dot(jnp.where(first == (hd == 0), dy, 0), u, _NT)
            wd = w * decay
            dcb = dcb + wd * dt_row
            x = wd * cb
            # the transposed plane made as it lies: [s, t]
            turned = jnp.exp(jnp.where(upper, row - col, _NEG)) * dt_col
            inside.append(_dot((bc * turned).astype(u.dtype), dy, _NN))
            mine = of_head(col_share, hd)                           # [L, 1]
            at_end = jnp.sum(mine * dt_col, axis=0, keepdims=True) \
                + whole[:, hd * HALF:hd * HALF + 1] * jnp.sum(
                    of_head(state * dstate, hd), axis=0, keepdims=True)
            g_row = _turned(jnp.sum(x * dt_row, axis=1, keepdims=True)
                            + of_head(row_share, hd)) \
                + jnp.where(last, at_end, 0.0)
            s_row = jnp.sum(x, axis=0, keepdims=True) + _turned(mine)
            dvec = jnp.where(at_row == head, g_row, dvec)
            dvec = jnp.where(at_row == per_group + head, s_row, dvec)
        du_ref[:, lanes] = (jnp.where(first, *inside)
                            + to_end * dt * with_dstate).astype(du_ref.dtype)
        dc = dc + _dot(scaled_dy, state, _NT, _HI)
        db = db + _dot(to_end * dt * u32, dstate, _NT, _HI)
        dst_ref[:, lanes] = whole * dstate + _dot(cm32, scaled_dy, _TN, _HI)
    dc_ref[...] = (dc + _dot(dcb, bm32, _NN, _HI)).astype(dc_ref.dtype)
    db_ref[...] = (db + _dot(dcb, cm32, _TN, _HI)).astype(db_ref.dtype)
    dvec_ref[...] = dvec


def _specs(b, t, heads, head_dim, groups, state, chunk, reverse):
    """The grid and the block specs both kernels share."""
    n, j = t // chunk, heads // groups
    wide = j * head_dim
    at = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    first_b = heads * head_dim // state
    plane = lambda width, offset: pl.BlockSpec(  # noqa: E731
        (None, chunk, width), lambda i, g, c: (i, at(c), offset + g),
        memory_space=pltpu.VMEM)
    return dict(
        grid=(b, groups, n),
        u=plane(wide, 0), b=plane(state, first_b),
        c=plane(state, first_b + groups), own=plane(state, 0),
        cols=pl.BlockSpec((None, None, chunk, 2 * j),
                          lambda i, g, c: (i, g, at(c), 0),
                          memory_space=pltpu.VMEM),
        rows=pl.BlockSpec((None, None, 2 * j, chunk),
                          lambda i, g, c: (i, g, 0, at(c)),
                          memory_space=pltpu.VMEM),
        states=pl.BlockSpec((None, None, None, state, wide),
                            lambda i, g, c: (i, at(c), g, 0, 0),
                            memory_space=pltpu.VMEM),
        scratch=[pltpu.VMEM((state, wide), jnp.float32)])


def _vectors(big, dt, groups):
    """``cols`` [B, G, T, 2 J] and ``rows`` [B, G, 2 J, T] of G and dt
    ([B, T, heads] float32)."""
    b, t, heads = big.shape
    by_group = lambda x: x.reshape(b, t, groups, heads // groups)  # noqa: E731
    cols = jnp.concatenate([by_group(big), by_group(dt)], axis=-1)
    cols = cols.transpose(0, 2, 1, 3)
    return cols, cols.transpose(0, 1, 3, 2)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "sizes", "keep_states", "interpret"))
def _kernel_forward(xbc, big, dt, sizes, keep_states, interpret):
    """y [B, T, heads P] in the plane's type and, for the forward rule,
    each chunk's starting states."""
    heads, head_dim, groups, state, chunk = sizes
    b, t, _ = xbc.shape
    sp = _specs(b, t, heads, head_dim, groups, state, chunk, reverse=False)
    out_specs = [sp["u"]]
    out_shape = [jax.ShapeDtypeStruct((b, t, heads * head_dim), xbc.dtype)]
    if keep_states:
        out_specs.append(sp["states"])
        out_shape.append(jax.ShapeDtypeStruct(
            (b, t // chunk, groups, state, heads // groups * head_dim),
            jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, per_group=heads // groups,
                          keep_states=keep_states),
        grid=sp["grid"],
        in_specs=[sp["u"], sp["b"], sp["c"], sp["cols"], sp["rows"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=sp["scratch"], compiler_params=_COMPILER_PARAMS,
        interpret=interpret, name=KERNEL_NAME,
    )(xbc, xbc, xbc, *_vectors(big, dt, groups))
    return out[0], (out[1] if keep_states else None)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "sizes", "interpret"))
def _kernel_backward(xbc, big, dt, states, dy, sizes, interpret):
    """The cotangents of the plane (its type), of G and of dt."""
    heads, head_dim, groups, state, chunk = sizes
    b, t, _ = xbc.shape
    j = heads // groups
    sp = _specs(b, t, heads, head_dim, groups, state, chunk, reverse=True)
    du, db, dc, dvec = pl.pallas_call(
        functools.partial(_bwd_kernel, per_group=j),
        grid=sp["grid"],
        in_specs=[sp["u"], sp["b"], sp["c"], sp["cols"], sp["rows"], sp["u"],
                  sp["states"]],
        out_specs=[sp["u"], sp["own"], sp["own"], sp["rows"]],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, heads * head_dim), xbc.dtype),
            jax.ShapeDtypeStruct((b, t, groups * state), xbc.dtype),
            jax.ShapeDtypeStruct((b, t, groups * state), xbc.dtype),
            jax.ShapeDtypeStruct((b, groups, 2 * j, t), jnp.float32)],
        scratch_shapes=sp["scratch"], compiler_params=_COMPILER_PARAMS,
        interpret=interpret, name=BACKWARD_KERNEL_NAME,
    )(xbc, xbc, xbc, *_vectors(big, dt, groups), dy.astype(xbc.dtype), states)
    dvec = dvec.reshape(b, groups, 2, j, t).transpose(2, 0, 4, 1, 3)
    to_big, no_dt = dvec.reshape(2, b, t, heads)
    return (jnp.concatenate([du, db, dc], axis=-1), to_big - no_dt * dt, no_dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_path(xbc, big, dt, sizes, interpret):
    return _kernel_forward(xbc, big, dt, sizes=sizes, keep_states=False,
                           interpret=interpret)[0]


def _kernel_path_fwd(xbc, big, dt, sizes, interpret):
    y, states = _kernel_forward(xbc, big, dt, sizes=sizes, keep_states=True,
                                interpret=interpret)
    return y, (xbc, big, dt, states)


def _kernel_path_bwd(sizes, interpret, res, dy):
    return _kernel_backward(*res, dy, sizes=sizes, interpret=interpret)


# optimize_remat: under a layer's remat the pass that keeps no residuals
# runs the primal (no state output), as ``ops/kda.py``'s pair does
_kernel_path.defvjp(_kernel_path_fwd, _kernel_path_bwd, optimize_remat=True)


def ssd_chunked(xbc, dt, a, heads: int, head_dim: int, groups: int, state: int,
                chunk: int = PLAIN_CHUNK, interpret: bool | None = None):
    """y [B, T, heads P] of the recurrence above from S_0 = 0, in the
    plane's type: ``xbc`` [B, T, heads P + 2 groups N] = [u | B | C],
    ``dt`` [B, T, heads] (> 0; float32 here whatever it comes in), ``a``
    [heads] (< 0). ``chunk`` is the plain path's (the kernels work in
    ``KERNEL_CHUNK``); which path runs, forward and backward, is
    ``ssd_path``'s answer; ``interpret`` is for tests (True: the kernels,
    interpreted, off the TPU; False: compiled, for a TPU that is
    described and not attached)."""
    if heads % groups:
        raise ValueError(f"{heads} heads in {groups} groups")
    dt, a = dt.astype(jnp.float32), a.astype(jnp.float32)
    b, t, _ = xbc.shape
    if ssd_path(heads, head_dim, groups, state, t, xbc.dtype,
                interpret)[0] != "kernel":
        return _scan_forward(xbc, dt, a, heads, head_dim, groups, state,
                             chunk).astype(xbc.dtype)
    return kernel_scan(xbc, dt, a, (heads, head_dim, groups, state,
                                    KERNEL_CHUNK), bool(interpret))


def kernel_scan(xbc, dt, a, sizes, interpret: bool):
    """The kernel pair at ``sizes`` = (heads, P, groups, N, chunk), T a
    multiple of the chunk (``ssd_chunked`` calls it at ``KERNEL_CHUNK``;
    ``chip_smoke.py`` times other chunks through it)."""
    b, t, heads = dt.shape
    chunk = sizes[-1]
    # G, the running sum of dt a inside each chunk: JAX transposes it
    big = jnp.cumsum((dt * a).reshape(b, t // chunk, chunk, heads),
                     axis=2).reshape(b, t, heads)
    return _kernel_path(xbc, big, dt, tuple(sizes), interpret)
