"""The causal tiles of ``ops/attention.py causal_blockwise_attention`` as
two Pallas kernels: banded, grouped heads in the rows, value width free;
and latent attention's pair, which takes its key in two parts.

The mathematics is the plain path's (an online softmax over key tiles,
from the band's first tile to the diagonal's, masked entries a finite
-1e30): q, k, v, the probabilities and the score cotangent enter the MXU
in the activation type with float32 accumulation; the scores, the
running maximum and sum, every exponential, the log-sum-exp kept for the
backward and every accumulator are float32. What differs is where a
tile's score plane lives: in VMEM, from the product that makes it to the
product that consumes it, where the plain path's XLA fusions write it to
HBM and read it back between the product, the maximum, the exponential
and the second product.

Both kernels cut their blocks from the arrays as they lie. ``[B, N, h,
d]`` is ``[B, N, h * d]`` for free; a block of ``g * d`` lanes at lane
block ``kv head`` is one key/value head's group of ``g`` query heads, a
static lane slice a head; k and v are blocks of ``d`` lanes at their own
head count: never written out g times, never fetched g times. The grid
is (sequence, key/value head, query block, key tile of the block's
band): its last axis is as long as the longest band, a block whose band
is shorter clamps the tile index (no new DMA) and skips the body, so a
tile above the diagonal or wholly below the window's lower edge costs
neither a product nor a DMA. Only the tiles an edge crosses build a mask
(two bodies, chosen by the grid position).

``causal_attn_fwd``: a grid step is one key tile against the ``g``
query heads of the group, a head at a time (``g * block_q`` rows against
the ONE key tile held in VMEM); the running maximum, sum and output
accumulator of every head stay in VMEM scratch until the diagonal's
tile, which comes last. With ``keep_lse`` (the forward rule of the
``custom_vjp``) it also writes each row's log-sum-exp, ``[B, hk, g, N]``
float32, a row vector a head.

``causal_attn_bwd``: ONE kernel, the same grid. Its planes are
TRANSPOSED (keys in the rows, queries in the lanes: the log-sum-exp and
``sum(o * do)`` are then row vectors, and dk, dv and the score products
take their operands as they lie; only dq's product needs its plane
turned): five products a tile and one exponential, where a dk/dv and a
dq kernel would make seven and two. dq of the block's ``g`` heads
accumulates in VMEM scratch along the band; dk and dv of the WHOLE
sequence of one key/value head accumulate in float32 VMEM scratch
(``N * (d + dv)`` floats: 16.8 MB at 16,384 tokens of 128 + 128) over
every query block and head of the group and leave once, rounded, when
the head's last tile is done. ``causal_attention_path`` refuses a length
whose dk and dv do not fit.

A per-query SELECTION besides the band (``selection`` [B, N, N] int8, 1
where query t keeps key s: which keys a query sees is then data, a
learned indexer's choice) is one more block a tile: ``[block_q,
block_kv]`` of it beside the key tile, in both kernels (the backward,
whose planes have the keys in the rows, turns the block once a tile in
VMEM), and one more ``where`` on the score plane in every tile. A tile none of
whose pairs is kept costs what any other does: the grid is still the
band's (no tile is skipped by what the data says).

Under a selection the forward kernel's log-sum-exp is an OUTPUT of the
call (``kernel_attention_selected``: ``[B, h, N]`` float32 beside o, in
the primal as in the forward rule, no gradient through it): what a
learned indexer is trained towards — the mean over ALL query heads of
each head's softmax over the keys its query keeps — needs those row
statistics and nothing else of the attention pass, so no second pass
makes them.

``index_loss_value`` / ``index_loss_grad`` (``index_loss_tiles``) are
that index loss (``ops/sparse_index.py``) by causal tiles: grid
(sequence, query block, key tile of the block's band), the planes
transposed as the backward's. A tile's target (one product and one
exponential a main head, summed in VMEM), its index scores (one product
of 64 a pair and index head, ReLU, the weighted sum over heads), the
rows' softmax statistics over their kept keys, the KL and — in the
forward rule, which walks a block's band twice: statistics first — the
closed-form gradient (dq^I along the band, da, and dk^I of the whole
sequence in float32 VMEM scratch, as dk and dv above) never leave VMEM:
no plane a head, no target plane, no [queries, keys] float32 at all in
HBM.

Latent attention (``latent_attention``: ``latent_attn_fwd`` /
``latent_attn_bwd``) is a pair of its own, because its operands differ in
KIND: a key in two arrays, 128 channels a head beside the head's values
in ``kvb`` and ONE shared 64 (``kpe``), and q 192 wide a head, which is a
lane group and a half. A score is the sum of two products, q_nope .
k_nope + q_rope . kpe, made as ONE 256-deep product (float32
accumulation): a head's q as [nope | its rope channels in its half of a
lane group, zeros in the other] against [k_nope | kpe, kpe]. Nothing a
head is written to HBM on the way in or out: q arrives a PAIR of heads a
block, 384 lanes as the projection leaves them, and the four parts are
put in place once a query block in VMEM (``pltpu.roll`` by 64, as the
heads of 64 are); a rotary turn of q's rope channels is made there too,
neighbouring lanes a pair (two rolls by one lane), so no XLA pass ever
touches the [tokens, heads, 192] plane; k_nope and v are ONE 256-lane
block of ``kvb`` as it lies and their cotangents leave the same way; the
shared key's cotangent sums over all heads of a sequence in a float32
block that stays in VMEM through the sequence's whole grid, and is
rounded once. dq of a pair is one block as q is, so the backward's grid
runs (sequence, pair, query block, head of the pair, key tile) and holds
dk and dv of BOTH heads (their blocks in ONE buffer each: they change
sixteen times a sequence). The band's arithmetic, the online softmax and
the backward's five products are the generic pair's. A 192-deep
contraction fills two 128-deep MXU passes as a 256-deep one does:
what this pair saves is copies, not products.

A q/k width that is neither a multiple of the 128-lane tile nor packed
takes the plain tiles (``causal_attention_path``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "causal_attn_fwd"
BACKWARD_KERNEL_NAME = "causal_attn_bwd"
LATENT_KERNEL_NAME = "latent_attn_fwd"
LATENT_BACKWARD_KERNEL_NAME = "latent_attn_bwd"
INDEX_LOSS_KERNEL_NAME = "index_loss_value"
INDEX_LOSS_GRAD_KERNEL_NAME = "index_loss_grad"
_LANES = 128
_HALF = 64     # the head width that goes two heads a lane group
_NEG = -1e30
# what the backward may hold of one key/value head's dk and dv: the
# float32 accumulators and the (double-buffered) blocks they leave in
_RESIDENT_BYTES = 48 * 1024 * 1024
# ... and of a PAIR of latent attention's heads beside the shared key's
# cotangent, each in ONE buffer (``latent_attention_path``)
_LATENT_RESIDENT_BYTES = 64 * 1024 * 1024
# the latent pair's (queries a block, keys a tile): its own, no plain path
# shares them
LATENT_BLOCK_Q, LATENT_BLOCK_KV = 1024, 1024
_COMPILER_PARAMS = pltpu.CompilerParams(
    # sequences, key/value heads, query blocks, a block's key tiles
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=100 * 1024 * 1024)
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


def _dot(a, b, dims=_NN):
    # one pass of the MXU in the operands' type whatever precision the
    # caller's context asks of ITS products (Mosaic refuses bfloat16
    # operands under "highest")
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _band(i, block_q, block_kv, window):
    """(first key tile, number of key tiles) of query block ``i`` (a
    Python int or a traced scalar)."""
    last = ((i + 1) * block_q - 1) // block_kv
    if window is None:
        return 0, last + 1
    below = i * block_q - window + 1
    first = (max(below, 0) if isinstance(i, int)
             else jnp.maximum(below, 0)) // block_kv
    return first, last - first + 1


def _tile_place(block_q, block_kv, window, axes=(2, 3)):
    """Of this grid step: (the tile is of the block's band, an edge
    crosses it, its first key less the block's first query, the tile's
    index, the band's length). ``axes``: the grid's axes of the query
    block and of the band's tile."""
    i, t = (pl.program_id(a) for a in axes)
    first, count = _band(i, block_q, block_kv, window)
    off = (first + t) * block_kv - i * block_q
    edge = off + block_kv > 1                       # the diagonal
    if window is not None:                          # the window's lower edge
        edge = jnp.logical_or(edge, off <= block_q - 1 - window)
    return t < count, edge, off, first + t, count


def _seen(shape, query_axis, off, window):
    """[query, key] (or [key, query]) entries inside the band."""
    rel = _iota(shape, 1 - query_axis) - _iota(shape, query_axis) + off
    seen = rel <= 0
    return seen if window is None else seen & (rel > -window)


def _both_bodies(run, edge, tile):
    pl.when(jnp.logical_and(run, edge))(functools.partial(tile, True))
    pl.when(jnp.logical_and(run, jnp.logical_not(edge)))(
        functools.partial(tile, False))


def _placed(ref, j, per_kv):
    """Head ``j`` of a block of 64-wide heads ([rows, heads * 64], two
    heads a 128-lane group), alone in a 128-lane plane: in the half its
    key/value head has in the pair's block (``j // per_kv``: lanes 0..63
    or 64..127), zeros in the other. A product with the pair's keys or
    values over all 128 lanes is then the product with that one head's."""
    x = ref[:, (j // 2) * _LANES:(j // 2 + 1) * _LANES].astype(jnp.float32)
    upper = j // per_kv == 1
    if upper != bool(j % 2):
        x = pltpu.roll(x, _HALF, 1)
    there = _iota(x.shape, 1) >= _HALF
    return jnp.where(there if upper else jnp.logical_not(there), x, 0.0).astype(
        ref.dtype)


def _unplaced(ref, planes, per_kv):
    """``_placed`` undone: ``planes(j)`` [rows, 128] float32 holds head
    j's result in its key/value head's half; two heads a lane group go
    back into ``ref`` as they lie in HBM."""
    def moved(j):
        x = planes(j)
        return x if (j // per_kv == 1) == bool(j % 2) else pltpu.roll(x, _HALF, 1)

    for m in range(ref.shape[-1] // _LANES):
        low, high = moved(2 * m), moved(2 * m + 1)
        ref[:, m * _LANES:(m + 1) * _LANES] = jnp.where(
            _iota(low.shape, 1) < _HALF, low, high).astype(ref.dtype)


def _online_softmax(s, v, j, m_scr, l_scr, acc_scr):
    """One key tile's float32 scores ``s`` [block_q, block_kv] and values
    ``v`` into head ``j``'s running maximum, sum and accumulator."""
    block_kv, dv = s.shape[1], v.shape[1]
    m_prev = m_scr[j]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - pltpu.repeat(m_next, block_kv // _LANES, axis=1))
    alpha = jnp.exp(m_prev - m_next)
    l_scr[j] = alpha * l_scr[j] + jnp.sum(p, axis=1, keepdims=True)
    m_scr[j] = m_next
    acc_scr[j] = acc_scr[j] * pltpu.repeat(
        alpha, dv // _LANES, axis=1) + _dot(p.astype(v.dtype), v)


def _fwd_kernel(*refs, scale, group, block_q, block_kv, window, keep_lse,
                selected=False, packed=False):
    """q_ref [block_q, g * d], k_ref [block_kv, d], v_ref [block_kv, dv],
    (``selected``: sel_ref [block_q, block_kv] int8,) o_ref [block_q,
    g * dv], lse_ref [g, block_q]; scratch: the group's heads stacked
    [g, block_q, d], the running maximum and sum [g, block_q, 128] (every
    lane the same) and the accumulator [g, block_q, dv].

    ``packed``: heads of 64. A grid step is then a PAIR of key/value heads
    (k_ref and v_ref [block_kv, 128]: the pair side by side, one lane
    group) and the ``group`` = 2 g query heads that read them (q_ref
    [block_q, group * 64]); the scratch planes are 128 wide, a head's q
    stands alone in its key/value head's half (``_placed``), and of a
    head's accumulator that half is its output, the other a product
    nobody reads. A tile's body is the same: a 128 x 128 MXU pass does a
    64-deep contraction at half its rate, zeros or no zeros."""
    sel_ref = None
    if selected:
        sel_ref, refs = refs[3], refs[:3] + refs[4:]
    if keep_lse:
        q_ref, k_ref, v_ref, o_ref, lse_ref, q_scr, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, q_scr, m_scr, l_scr, acc_scr = refs
    d, dv = q_scr.shape[-1], v_ref.shape[-1]
    run, edge, off, _, count = _tile_place(block_q, block_kv, window)
    t = pl.program_id(3)

    @pl.when(t == 0)
    def _():
        for j in range(group):
            q_scr[j] = (_placed(q_ref, j, group // 2) if packed
                        else q_ref[:, j * d:(j + 1) * d])
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(masked):
        k, v = k_ref[...], v_ref[...]

        def head(j, carry):
            s = _dot(q_scr[j], k, _NT) * scale
            if masked:
                s = jnp.where(_seen(s.shape, 0, off, window), s, _NEG)
            if selected:
                s = jnp.where(sel_ref[...].astype(jnp.int32) != 0, s, _NEG)
            _online_softmax(s, v, j, m_scr, l_scr, acc_scr)
            return carry

        jax.lax.fori_loop(0, group, head, 0)

    _both_bodies(run, edge, tile)

    @pl.when(t == count - 1)
    def _():
        if packed:
            _unplaced(o_ref, lambda j: acc_scr[j] / l_scr[j], group // 2)
        for j in range(group):
            total = l_scr[j]
            if not packed:
                o_ref[:, j * dv:(j + 1) * dv] = (
                    acc_scr[j] / pltpu.repeat(total, dv // _LANES, axis=1)
                ).astype(o_ref.dtype)
            if keep_lse:  # a column of [block_q, 128] laid down as a row
                lse_ref[j:j + 1, :] = (m_scr[j] + jnp.log(total)).T[:1]


def _bwd_kernel(*refs, scale, group, block_q, block_kv, window,
                selected=False, packed=False):
    """The forward's blocks, do_ref like o_ref, delta_ref (the rows'
    sum(o * do)) like lse_ref (``selected``: then sel_ref [block_q,
    block_kv] int8, the forward's own block, turned once a tile into the
    last scratch, [block_kv, block_q] float32); dq_ref like q_ref; dk_ref [N, d] and
    dv_ref [N, dv], one key/value head's whole sequence. Scratch: q and
    do stacked a head, dq [g, block_q, d], dk [N, d] and dv [N, dv], all
    three float32. ``packed`` as the forward's: q and do of a head stand
    alone in their key/value head's half of a 128-lane plane, so dk and
    dv of the pair [N, 128] take each head's part where that key/value
    head lies, and of dq's plane the same half is the head's; and o_ref
    (like do_ref) stands where delta_ref stood: the rows' sum(o * do) over
    64 of 128 lanes is made here, into one more scratch [g, block_q] (XLA
    makes it of a whole float32 plane and a copy of it).

    With s^T = k q^T (keys in the rows), p^T = exp(s^T - lse),
    dp^T = v do^T and ds^T = p^T (dp^T - delta) scale:

        dv += p^T do,    dk += ds^T q,    dq += (ds^T)^T k
    """
    sel_ref = kept_scr = None
    if selected:
        sel_ref, kept_scr, refs = refs[6], refs[-1], refs[:6] + refs[7:-1]
    if packed:  # o in delta's place; the rows' sum(o * do) is made here
        (q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, dq_ref, dk_ref, dv_ref,
         q_scr, do_scr, dq_scr, dk_scr, dv_scr, delta_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref,
         dv_ref, q_scr, do_scr, dq_scr, dk_scr, dv_scr) = refs
    d, dv = q_scr.shape[-1], v_ref.shape[-1]
    run, edge, off, at, count = _tile_place(block_q, block_kv, window)
    i, t = pl.program_id(2), pl.program_id(3)
    last_block = i == pl.num_programs(2) - 1

    @pl.when(jnp.logical_and(i == 0, t == 0))
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(t == 0)
    def _():
        for j in range(group):
            if packed:
                q_scr[j] = _placed(q_ref, j, group // 2)
                do_scr[j] = _placed(do_ref, j, group // 2)
                if j % 2 == 0:   # a lane group's two heads: sum(o * do) a row
                    lanes = slice(j // 2 * _LANES, (j // 2 + 1) * _LANES)
                    both = (o_ref[:, lanes].astype(jnp.float32)
                            * do_ref[:, lanes].astype(jnp.float32))
                    low = _iota(both.shape, 1) < _HALF
                    for at, half in ((j, low), (j + 1, jnp.logical_not(low))):
                        total = jnp.sum(jnp.where(half, both, 0.0), axis=1,
                                        keepdims=True)
                        delta_ref[at:at + 1, :] = jnp.broadcast_to(
                            total, both.shape).T[:1]
            else:
                q_scr[j] = q_ref[:, j * d:(j + 1) * d]
                do_scr[j] = do_ref[:, j * dv:(j + 1) * dv]
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def tile(masked):
        k, v = k_ref[...], v_ref[...]
        keys = pl.ds(pl.multiple_of(at * block_kv, block_kv), block_kv)
        if selected:  # keys in the rows, as this kernel's planes lie
            kept_scr[...] = sel_ref[...].astype(jnp.float32).T

        def head(j, carry):
            q, do = q_scr[j], do_scr[j]
            s = _dot(k, q, _NT) * scale                  # [block_kv, block_q]
            if masked:
                s = jnp.where(_seen(s.shape, 1, off, window), s, _NEG)
            if selected:
                s = jnp.where(kept_scr[...] != 0.0, s, _NEG)
            p = jnp.exp(s - lse_ref[pl.ds(j, 1), :])
            dv_scr[keys, :] += _dot(p.astype(do.dtype), do)
            ds = p * (_dot(v, do, _NT) - delta_ref[pl.ds(j, 1), :]) * scale
            dk_scr[keys, :] += _dot(ds.astype(q.dtype), q)
            dq_scr[j] += _dot(ds.T.astype(k.dtype), k)
            return carry

        jax.lax.fori_loop(0, group, head, 0)

    _both_bodies(run, edge, tile)

    @pl.when(t == count - 1)
    def _():
        if packed:
            _unplaced(dq_ref, lambda j: dq_scr[j], group // 2)
            return
        for j in range(group):
            dq_ref[:, j * d:(j + 1) * d] = dq_scr[j].astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(last_block, t == count - 1))
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _packs(d: int, dv: int, hk: int) -> bool:
    """Heads of 64 + 64 go two key/value heads a lane group."""
    return d == dv == _HALF and hk % 2 == 0


def _operands(q, k, block_q, block_kv, window, dv=None):
    """(the grid, the block of a [B, N, h * w] array of query heads, the
    block of a [B, N, hk * w] array of keys or values along the band, the
    block of a [B, hk, g, N] array of row statistics, the [block_q,
    block_kv] block of a [B, N, N] selection along the band, the query
    heads a grid step and how many key/value heads a step holds: 1 or 2).
    Heads of 64 + 64 (``_packs``): a grid step is a PAIR of key/value
    heads, one 128-lane block of the keys as they lie, and the 2 g query
    heads that read them; the row statistics are then [B, hk / 2, 2 g,
    N], the same bytes."""
    b, n, h, d = q.shape
    hk = k.shape[2]
    pack = 2 if _packs(d, dv, hk) else 1
    g = h // hk * pack
    blocks = n // block_q
    steps = max(_band(i, block_q, block_kv, window)[1] for i in range(blocks))

    def band_tile(s, kh, i, t):
        first, count = _band(i, block_q, block_kv, window)
        return s, first + jnp.minimum(t, count - 1), kh

    vmem = pltpu.VMEM
    rows = lambda w: pl.BlockSpec(  # noqa: E731
        (None, block_q, g * w), lambda s, kh, i, t: (s, i, kh),
        memory_space=vmem)
    keys = lambda w: pl.BlockSpec(  # noqa: E731
        (None, block_kv, w * pack), band_tile, memory_space=vmem)
    stats = pl.BlockSpec((None, None, g, block_q),
                         lambda s, kh, i, t: (s, kh, 0, i), memory_space=vmem)

    pair = pl.BlockSpec(
        (None, block_q, block_kv),
        lambda s, kh, i, t: (s, i, band_tile(s, kh, i, t)[1]), memory_space=vmem)
    return (b, hk // pack, blocks, steps), rows, keys, stats, pair, g, pack


# A ``pallas_call`` traces its kernel body every time it is called, and a
# trace of the step calls these wrappers three times an attention layer
# (the pass, the forward rule under the layer's remat, the backward),
# twice a set-up: under ``jit`` every call of one shape shares one trace
# (ops/kda.py, PR 31); ``inline`` leaves no call in the program.
@functools.partial(jax.jit, inline=True, static_argnames=(
    "scale", "window", "block_q", "block_kv", "keep_lse", "interpret"))
def _kernel_forward(q, k, v, scale, window, block_q, block_kv, keep_lse,
                    interpret, selection=None):
    """(o [B, N, h, dv], the rows' log-sum-exp [B, hk, g, N] float32 or
    None) as one ``pallas_call``."""
    b, n, h, d = q.shape
    hk, dv = v.shape[2], v.shape[3]
    grid, rows, keys, stats, pair, g, pack = _operands(
        q, k, block_q, block_kv, window, dv)
    packed, wide = pack == 2, pack * d    # (the scratch planes' width)
    flat = lambda x: x.reshape(b, n, -1)  # noqa: E731
    chosen = () if selection is None else (selection,)
    out_specs = [rows(dv)]
    out_shape = [jax.ShapeDtypeStruct((b, n, h * dv), v.dtype)]
    if keep_lse:
        out_specs.append(stats)
        out_shape.append(jax.ShapeDtypeStruct((b, grid[1], g, n), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, group=g, block_q=block_q,
                          block_kv=block_kv, window=window, keep_lse=keep_lse,
                          **({"selected": True} if chosen else {}),
                          **({"packed": True} if packed else {})),
        grid=grid,
        in_specs=[rows(d), keys(d), keys(dv)] + [pair] * len(chosen),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((g, block_q, wide), q.dtype),
            pltpu.VMEM((g, block_q, _LANES), jnp.float32),
            pltpu.VMEM((g, block_q, _LANES), jnp.float32),
            pltpu.VMEM((g, block_q, wide if packed else dv), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=KERNEL_NAME,
    )(flat(q), flat(k), flat(v), *chosen)
    return (out[0].reshape(b, n, h, dv),
            out[1].reshape(b, hk, h // hk, n) if keep_lse else None)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "scale", "window", "block_q", "block_kv", "interpret"))
def _kernel_backward(q, k, v, o, lse, do, scale, window, block_q, block_kv,
                     interpret, selection=None):
    """The cotangents of q, k, v, in their types, as one ``pallas_call``
    from what the forward rule kept."""
    b, n, h, d = q.shape
    hk, dv = v.shape[2], v.shape[3]
    packed = _packs(d, dv, hk)
    if not packed:
        # a product would round its float32 operands on the TPU: multiply, add
        delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
        delta = jnp.swapaxes(delta, 1, 2).reshape(b, hk, h // hk, n)
    grid, rows, keys, stats, pair, g, pack = _operands(
        q, k, block_q, block_kv, window, dv)
    wide = pack * d
    flat = lambda x: x.reshape(b, n, -1)  # noqa: E731
    delta_spec = stats
    if packed:  # the kernel makes the rows' sum(o * do), of o
        delta, delta_spec = flat(o), rows(dv)
    lse = lse.reshape(b, grid[1], g, n)
    chosen = () if selection is None else (selection,)
    whole = lambda w: pl.BlockSpec(  # noqa: E731
        (None, n, w * pack), lambda s, kh, i, t: (s, 0, kh),
        memory_space=pltpu.VMEM)
    dq, dk, dv_ = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, group=g, block_q=block_q,
                          block_kv=block_kv, window=window,
                          **({"selected": True} if chosen else {}),
                          **({"packed": True} if packed else {})),
        grid=grid,
        in_specs=[rows(d), keys(d), keys(dv), rows(dv), stats, delta_spec]
        + [pair] * len(chosen),
        out_specs=[rows(d), whole(d), whole(dv)],
        out_shape=[jax.ShapeDtypeStruct((b, n, h * d), q.dtype),
                   jax.ShapeDtypeStruct((b, n, hk * d), k.dtype),
                   jax.ShapeDtypeStruct((b, n, hk * dv), v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((g, block_q, wide), q.dtype),
            pltpu.VMEM((g, block_q, wide if packed else dv), do.dtype),
            pltpu.VMEM((g, block_q, wide), jnp.float32),
            pltpu.VMEM((n, wide), jnp.float32),
            pltpu.VMEM((n, wide if packed else dv), jnp.float32)]
        + [pltpu.VMEM((g, block_q), jnp.float32)] * packed
        + [pltpu.VMEM((block_kv, block_q), jnp.float32)] * len(chosen),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=BACKWARD_KERNEL_NAME,
    )(flat(q), flat(k), flat(v), flat(do), lse, delta, *chosen)
    return (dq.reshape(b, n, h, d), dk.reshape(b, n, hk, d),
            dv_.reshape(b, n, hk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def kernel_attention(q, k, v, scale, window, block_q, block_kv, interpret):
    """``causal_blockwise_attention`` on the kernel path ([B, N, h, d] q,
    [B, N, hk, d] k, [B, N, hk, dv] v; ``causal_attention_path`` says
    which shapes it takes)."""
    return _kernel_forward(q, k, v, scale=scale, window=window,
                           block_q=block_q, block_kv=block_kv,
                           keep_lse=False, interpret=interpret)[0]


def _kernel_attention_fwd(q, k, v, scale, window, block_q, block_kv, interpret):
    o, lse = _kernel_forward(q, k, v, scale=scale, window=window,
                             block_q=block_q, block_kv=block_kv,
                             keep_lse=True, interpret=interpret)
    return o, (q, k, v, o, lse)


def _kernel_attention_bwd(scale, window, block_q, block_kv, interpret, res, do):
    return _kernel_backward(*res, do, scale=scale, window=window,
                            block_q=block_q, block_kv=block_kv,
                            interpret=interpret)


# optimize_remat: under a layer's remat the pass that keeps no residuals
# runs the primal (no log-sum-exp written), not the forward rule
kernel_attention.defvjp(_kernel_attention_fwd, _kernel_attention_bwd,
                        optimize_remat=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def kernel_attention_selected(q, k, v, selection, scale, block_q, block_kv,
                              interpret):
    """``kernel_attention`` under a per-query selection ([B, N, N] int8,
    1 where query t keeps key s, no pair above the diagonal; every query
    keeps at least one key), and the rows' log-sum-exp over their kept
    keys beside it: (o, [B, h, N] float32). No window; the selection takes
    no gradient, and the log-sum-exp hands none back (its cotangent is
    dropped: a consumer reads it detached)."""
    return _kernel_attention_selected_fwd(
        q, k, v, selection, scale, block_q, block_kv, interpret)[0]


def _kernel_attention_selected_fwd(q, k, v, selection, scale, block_q,
                                   block_kv, interpret):
    o, lse = _kernel_forward(q, k, v, scale=scale, window=None,
                             block_q=block_q, block_kv=block_kv, keep_lse=True,
                             interpret=interpret, selection=selection)
    b, n, h, _ = q.shape
    return (o, lse.reshape(b, h, n)), (q, k, v, o, lse, selection)


def _kernel_attention_selected_bwd(scale, block_q, block_kv, interpret, res, ct):
    *res, selection = res
    return (*_kernel_backward(*res, ct[0], scale=scale, window=None,
                              block_q=block_q, block_kv=block_kv,
                              interpret=interpret, selection=selection), None)


# no optimize_remat: the primal IS the forward rule (the log-sum-exp is an
# output), and several outputs under it break JAX's DCE (PERF.md, PR 39)
kernel_attention_selected.defvjp(
    _kernel_attention_selected_fwd, _kernel_attention_selected_bwd)


# ---------------------------------------------------------------- latent
# Latent attention's key comes in two parts: 128 channels a head
# (``k_nope``, beside the head's v in ``kvb``) and ONE shared 64 (``kpe``).
# The pair below reads q, kvb and kpe where the projections left them and
# writes their cotangents where the projections' transposes want them.

def _swap_pairs(x):
    """Neighbouring lanes exchanged: (2i, 2i + 1) -> (2i + 1, 2i)."""
    even = _iota(x.shape, 1) % 2 == 0
    return jnp.where(even, pltpu.roll(x, x.shape[1] - 1, 1), pltpu.roll(x, 1, 1))


def _latent_q(q_ref, table, odd, dtype):
    """One head of a PAIR's block as ``q_proj`` leaves it (q_ref [block_q,
    384]: [nope | rope] of the even head, then of the odd one: lane groups
    [nope_e], [rope_e | nope_o's first half], [nope_o's second | rope_o])
    as (nope [block_q, 128], rope [block_q, 128]): the head's rope
    channels in ITS half of the lane group (the even head's the lower),
    zeros in the other, so that a product with the shared key laid twice
    side by side is the product with those 64 channels. ``table``: () or
    the block's (cos, sin) rows of ``_latent_tables``; the turn is
    float32, its ends the operands' type (``rope_apply_interleaved``'s
    arithmetic, each result left where its channel was)."""
    g = [q_ref[:, m * _LANES:(m + 1) * _LANES].astype(jnp.float32)
         for m in range(3)]
    low = _iota(g[0].shape, 1) < _HALF
    rope = jnp.where(low, g[1], g[2])                  # [rope_e | rope_o]
    if table:
        cos, sin = (ref[...] for ref in table)
        rope = rope * cos + _swap_pairs(rope) * sin
    if odd:
        nope = jnp.where(low, pltpu.roll(g[1], _HALF, 1),
                         pltpu.roll(g[2], _HALF, 1))
    else:
        nope = g[0]
    rope = jnp.where(low != odd, rope, 0.0)
    return nope.astype(dtype), rope.astype(dtype)


def _place_latent_q(q_scr, q_ref, table, head):
    """``_latent_q`` of head ``head`` (a traced scalar: its parity picks
    the body) into q_scr [block_q, 256]."""
    for odd in (False, True):
        @pl.when((head % 2 == 1) == odd)
        def _(odd=odd):
            nope, rope = _latent_q(q_ref, table, odd, q_scr.dtype)
            q_scr[:, :_LANES] = nope
            q_scr[:, _LANES:] = rope


def _latent_key(kv_ref, kpe_ref):
    """(k [block_kv, 256]: the head's k_nope beside the shared key laid
    twice, v [block_kv, 128]) of a tile: a 256-deep product with
    ``_latent_q``'s planes is q_nope . k_nope + q_rope . kpe, both
    accumulated in float32 inside the one product."""
    return (jnp.concatenate([kv_ref[:, :_LANES], kpe_ref[...]], axis=1),
            kv_ref[:, _LANES:])


def _latent_fwd_kernel(*refs, scale, block_q, block_kv, turned, keep_lse):
    """``_fwd_kernel`` of ONE head a grid step whose operands lie as latent
    attention's projections leave them: q_ref [block_q, 384] (the head's
    PAIR, ``_latent_q``), kv_ref [block_kv, 256] (the head's [k_nope | v]
    out of ``kvb``), kpe_ref [block_kv, 128] (the shared key, twice),
    (``turned``: cos_ref and sin_ref [block_q, 128] float32,) o_ref
    [block_q, 128], (lse_ref [1, block_q]); scratch: the head's q [block_q,
    256], then ``_fwd_kernel``'s. Everything after the score is
    ``_fwd_kernel``'s."""
    q_ref, kv_ref, kpe_ref, *refs = refs
    table, refs = (tuple(refs[:2]), refs[2:]) if turned else ((), refs)
    if keep_lse:
        o_ref, lse_ref, q_scr, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, q_scr, m_scr, l_scr, acc_scr = refs
    run, edge, off, _, count = _tile_place(block_q, block_kv, None)
    head, t = pl.program_id(1), pl.program_id(3)

    @pl.when(t == 0)
    def _():
        _place_latent_q(q_scr, q_ref, table, head)
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(masked):
        k, v = _latent_key(kv_ref, kpe_ref)
        s = _dot(q_scr[...], k, _NT) * scale
        if masked:
            s = jnp.where(_seen(s.shape, 0, off, None), s, _NEG)
        _online_softmax(s, v, 0, m_scr, l_scr, acc_scr)

    _both_bodies(run, edge, tile)

    @pl.when(t == count - 1)
    def _():
        total = l_scr[0]
        o_ref[...] = (acc_scr[0] / total).astype(o_ref.dtype)
        if keep_lse:
            lse_ref[...] = (m_scr[0] + jnp.log(total)).T[:1]


def _latent_bwd_kernel(*refs, scale, block_q, block_kv, turned):
    """``_bwd_kernel`` for the forward above, a PAIR of heads a step of
    the grid's second axis and the pair's two heads its fourth (sequence,
    pair, query block, head of the pair, key tile): dq of a pair is one
    block as q is, so both heads' parts have to be in VMEM when it
    leaves. The forward's blocks, do_ref and o_ref like its o_ref (the
    rows' sum(o * do) is made here, as the packed heads' is), lse_ref
    [1, block_q]; dq_ref like q_ref; dkv_ref [N, 512]: the pair's [dk_nope
    | dv | dk_nope | dv] as ``kvb`` lies, the whole sequence, float32
    accumulators [2 N, 128] each until the pair's last tile; dkpe_ref [N,
    128] FLOAT32: the shared key's cotangent summed over every head of the
    sequence where it lies (the block stays through the whole grid of a
    sequence; the even heads' sum in the lower half, the odd heads' in the
    upper: the caller adds the halves and rounds once). Scratch: the
    head's q [block_q, 256], the rows' sum(o * do) [1, block_q], dq of
    both heads [2, block_q, 256] float32, dk and dv."""
    q_ref, kv_ref, kpe_ref, *refs = refs
    table, refs = (tuple(refs[:2]), refs[2:]) if turned else ((), refs)
    (do_ref, o_ref, lse_ref, dq_ref, dkv_ref, dkpe_ref,
     q_scr, delta_scr, dq_scr, dk_scr, dv_scr) = refs
    n = dkv_ref.shape[0]
    run, edge, off, at, count = _tile_place(block_q, block_kv, None, (2, 4))
    pair, i, j, t = (pl.program_id(a) for a in (1, 2, 3, 4))
    last = jnp.logical_and(j == 1, t == count - 1)

    @pl.when(jnp.logical_and(jnp.logical_and(pair == 0, i == 0),
                             jnp.logical_and(j == 0, t == 0)))
    def _():
        dkpe_ref[...] = jnp.zeros_like(dkpe_ref)

    @pl.when(jnp.logical_and(i == 0, t == 0))
    def _():
        rows = pl.ds(pl.multiple_of(j * n, block_kv), n)
        dk_scr[rows, :] = jnp.zeros((n, _LANES), jnp.float32)
        dv_scr[rows, :] = jnp.zeros((n, _LANES), jnp.float32)

    @pl.when(t == 0)
    def _():
        _place_latent_q(q_scr, q_ref, table, j)
        both = o_ref[...].astype(jnp.float32) * do_ref[...].astype(jnp.float32)
        delta_scr[...] = jnp.broadcast_to(
            jnp.sum(both, axis=1, keepdims=True), both.shape).T[:1]
        dq_scr[j] = jnp.zeros(dq_scr.shape[1:], jnp.float32)

    def tile(masked):
        k, v = _latent_key(kv_ref, kpe_ref)
        q, do = q_scr[...], do_ref[...]
        keys = pl.ds(pl.multiple_of(at * block_kv, block_kv), block_kv)
        held = pl.ds(pl.multiple_of(j * n + at * block_kv, block_kv), block_kv)
        s = _dot(k, q, _NT) * scale                      # [block_kv, block_q]
        if masked:
            s = jnp.where(_seen(s.shape, 1, off, None), s, _NEG)
        p = jnp.exp(s - lse_ref[...])
        dv_scr[held, :] += _dot(p.astype(do.dtype), do)
        ds = p * (_dot(v, do, _NT) - delta_scr[...]) * scale
        dk = _dot(ds.astype(q.dtype), q)                 # [dk_nope | d kpe, twice]
        dk_scr[held, :] += dk[:, :_LANES]
        dkpe_ref[keys, :] += dk[:, _LANES:]
        dq_scr[j] += _dot(ds.T.astype(k.dtype), k)

    _both_bodies(run, edge, tile)

    @pl.when(last)
    def _():
        # ``_latent_q`` undone: each head's rope part is in ITS half of its
        # plane's upper lane group (the product with the key laid twice
        # wrote it into both)
        low = _iota((block_q, _LANES), 1) < _HALF
        rope = jnp.where(low, dq_scr[0][:, _LANES:], dq_scr[1][:, _LANES:])
        if table:
            cos, sin = (ref[...] for ref in table)
            rope = rope * cos + _swap_pairs(rope * sin)
        odd = pltpu.roll(dq_scr[1][:, :_LANES], _HALF, 1)
        for m, x in enumerate((dq_scr[0][:, :_LANES], jnp.where(low, rope, odd),
                               jnp.where(low, odd, rope))):
            dq_ref[:, m * _LANES:(m + 1) * _LANES] = x.astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(i == pl.num_programs(2) - 1, last))
    def _():
        for m, scr in enumerate((dk_scr, dv_scr, dk_scr, dv_scr)):
            dkv_ref[:, m * _LANES:(m + 1) * _LANES] = scr[
                (m // 2) * n:(m // 2 + 1) * n, :].astype(dkv_ref.dtype)


def _latent_tables(n: int, theta: float):
    """(cos, sin) [N, 128] float32 for ``_latent_q``'s lane group of two
    heads' rope channels, neighbours a pair: pair i of token t turns by
    t * theta^(-2i / 64); lane 2i holds (cos, -sin), lane 2i + 1 (cos,
    sin), so that x * cos + (x's neighbours exchanged) * sin is the turn."""
    from dinov3_tpu.ops.rope import token_rope_pair_sincos

    sin, cos = token_rope_pair_sincos(n, _HALF, theta)
    return (jnp.tile(jnp.repeat(cos, 2, axis=-1), (1, 2)),
            jnp.tile(jnp.stack([-sin, sin], axis=-1).reshape(n, _HALF), (1, 2)))


def _latent_operands(q, kvb, kpe, theta, block_q, block_kv, pairs):
    """(operands, their blocks) both latent kernels start with: q, kvb,
    the shared key laid twice, and with a ``theta`` the two tables.
    ``pairs``: the grid is the backward's (sequence, pair, query block,
    head of the pair, key tile), else the forward's (sequence, head, query
    block, key tile)."""
    n = q.shape[1]

    def at(index):  # an index map of either grid, written for (s, head, i, t)
        if pairs:
            return lambda s, p, i, j, t: index(s, 2 * p + j, i, t)
        return index

    def tile(i, t):
        return jnp.minimum(t, _band(i, block_q, block_kv, None)[1] - 1)

    table = () if theta is None else _latent_tables(n, theta)
    spec = lambda shape, index: pl.BlockSpec(  # noqa: E731
        shape, at(index), memory_space=pltpu.VMEM)
    return (q, kvb, jnp.concatenate([kpe, kpe], axis=-1), *table), [
        spec((None, block_q, 3 * _LANES), lambda s, kh, i, t: (s, i, kh // 2)),
        spec((None, block_kv, 2 * _LANES), lambda s, kh, i, t: (s, tile(i, t), kh)),
        spec((None, block_kv, _LANES), lambda s, kh, i, t: (s, tile(i, t), 0)),
    ] + [spec((block_q, _LANES), lambda s, kh, i, t: (i, 0))] * len(table), spec


def _latent_sizes(q, kvb, block_q, block_kv):
    b, n, _ = q.shape
    h = kvb.shape[-1] // (2 * _LANES)
    return b, n, h, (q.shape[-1] // h) ** -0.5, n // block_q, _band(
        n // block_q - 1, block_q, block_kv, None)[1]


@functools.partial(jax.jit, inline=True, static_argnames=(
    "theta", "block_q", "block_kv", "keep_lse", "interpret"))
def _latent_forward(q, kvb, kpe, theta, block_q, block_kv, keep_lse, interpret):
    """(o [B, N, h * 128], the rows' log-sum-exp [B, h, 1, N] float32 or
    None) as one ``pallas_call``."""
    b, n, h, scale, blocks, steps = _latent_sizes(q, kvb, block_q, block_kv)
    operands, in_specs, spec = _latent_operands(
        q, kvb, kpe, theta, block_q, block_kv, pairs=False)
    out_specs = [spec((None, block_q, _LANES), lambda s, kh, i, t: (s, i, kh))]
    out_shape = [jax.ShapeDtypeStruct((b, n, h * _LANES), q.dtype)]
    if keep_lse:
        out_specs.append(spec((None, None, 1, block_q),
                              lambda s, kh, i, t: (s, kh, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, 1, n), jnp.float32))
    row = pltpu.VMEM((1, block_q, _LANES), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_latent_fwd_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, turned=theta is not None,
                          keep_lse=keep_lse),
        grid=(b, h, blocks, steps),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_q, 2 * _LANES), q.dtype), row, row, row],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=LATENT_KERNEL_NAME,
    )(*operands)
    return out[0], out[1] if keep_lse else None


@functools.partial(jax.jit, inline=True, static_argnames=(
    "theta", "block_q", "block_kv", "interpret"))
def _latent_backward(q, kvb, kpe, o, lse, do, theta, block_q, block_kv,
                     interpret):
    """The cotangents of q, kvb and kpe, each laid out as its primal, as
    one ``pallas_call`` from what the forward rule kept."""
    b, n, h, scale, blocks, steps = _latent_sizes(q, kvb, block_q, block_kv)
    operands, in_specs, spec = _latent_operands(
        q, kvb, kpe, theta, block_q, block_kv, pairs=True)
    head = spec((None, block_q, _LANES), lambda s, kh, i, t: (s, i, kh))
    # the whole sequence of a pair (of every head: dkpe) stays where it
    # is until it is done: one buffer, not the pipeline's two
    whole = lambda w, index: pl.BlockSpec(  # noqa: E731
        (None, n, w), index, memory_space=pltpu.VMEM,
        pipeline_mode=pl.Buffered(1))
    dq, dkvb, dkpe = pl.pallas_call(
        functools.partial(_latent_bwd_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, turned=theta is not None),
        grid=(b, h // 2, blocks, 2, steps),
        in_specs=in_specs + [head, head, spec(
            (None, None, 1, block_q), lambda s, kh, i, t: (s, kh, 0, i))],
        out_specs=[
            pl.BlockSpec((None, block_q, 3 * _LANES),
                         lambda s, p, i, j, t: (s, i, p), memory_space=pltpu.VMEM),
            whole(4 * _LANES, lambda s, p, i, j, t: (s, 0, p)),
            whole(_LANES, lambda s, p, i, j, t: (s, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(kvb.shape, kvb.dtype),
                   jax.ShapeDtypeStruct((b, n, _LANES), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, 2 * _LANES), q.dtype),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((2, block_q, 2 * _LANES), jnp.float32),
            pltpu.VMEM((2 * n, _LANES), jnp.float32),
            pltpu.VMEM((2 * n, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # (dkpe sums over the pairs of a sequence: no axis but the
            # first may be split)
            dimension_semantics=("parallel",) + ("arbitrary",) * 4,
            vmem_limit_bytes=_COMPILER_PARAMS.vmem_limit_bytes),
        interpret=interpret,
        name=LATENT_BACKWARD_KERNEL_NAME,
    )(*operands, do, o, lse)
    # the even heads' sum and the odd heads', float32: rounded ONCE
    return dq, dkvb, (dkpe[..., :_HALF] + dkpe[..., _HALF:]).astype(kpe.dtype)


def latent_attention(q, kvb, kpe, theta=None, block_q: int = LATENT_BLOCK_Q,
                     block_kv: int = LATENT_BLOCK_KV, interpret: bool = False):
    """Causal latent attention on the kernel path, every operand as its
    projection leaves it: q [B, N, h * 192] (a head's [128 | 64]), kvb
    [B, N, h * 256] (a head's [k_nope 128 | v 128]) and the ONE shared key
    kpe [B, N, 64]; o [B, N, h * 128]. A score is (q_nope . k_nope +
    q_rope . kpe) / sqrt(192). ``theta``: None takes q's rope channels and
    kpe as they are; a number turns q's rope channels in the kernels,
    NEIGHBOURING channels a pair, each result left where its channel was
    (``rope_apply_interleaved`` lays them evens' first: the same sum in
    another order), and wants kpe turned likewise by the caller
    (``ops/rope.py rope_apply_pairs``). ``latent_attention_path`` says
    which shapes it takes."""
    return _latent_attention(q, kvb, kpe, theta, block_q, block_kv, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _latent_attention(q, kvb, kpe, theta, block_q, block_kv, interpret):
    return _latent_forward(q, kvb, kpe, theta=theta, block_q=block_q,
                           block_kv=block_kv, keep_lse=False,
                           interpret=interpret)[0]


def _latent_attention_fwd(q, kvb, kpe, theta, block_q, block_kv, interpret):
    o, lse = _latent_forward(q, kvb, kpe, theta=theta, block_q=block_q,
                             block_kv=block_kv, keep_lse=True,
                             interpret=interpret)
    return o, (q, kvb, kpe, o, lse)


def _latent_attention_bwd(theta, block_q, block_kv, interpret, res, do):
    return _latent_backward(*res, do, theta=theta, block_q=block_q,
                            block_kv=block_kv, interpret=interpret)


_latent_attention.defvjp(_latent_attention_fwd, _latent_attention_bwd,
                        optimize_remat=True)


def _index_loss_kernel(*refs, scale, norm, group, index_heads, block_q,
                       block_kv, steps, with_grad):
    """A tile of ``ops/sparse_index.py``'s index loss, every plane with the
    keys in its rows and the queries in its lanes (a row statistic or a
    head weight is then a row vector, as in the backward above).

    qit_ref [H_I * d_I, block_q] (q^I turned: a head is d_I rows), at_ref
    [H_I, block_q], ki_ref [block_kv, d_I] (``with_grad``: then
    kit_ref [d_I, block_kv], the same tile turned), sel_ref [block_q,
    block_kv] int8, q_ref [block_q, h * d], k_ref [block_kv, hk * d],
    lse_ref [h, block_q] (the main attention's, from the core);
    loss_ref [1, block_q] float32: each query's KL, not yet divided by
    their number.

    Of a tile: the target p = mean_i exp(q_i . k scale - lse_i) on the
    kept pairs (one product and one exponential a main head), the index
    scores I = sum_j a_j ReLU(k^I . q^I_j) (one product a head), and the
    rows' softmax statistics of I over their kept keys.

    Without ``with_grad`` the grid is (sequence, query block, key tile of
    the block's band) and the statistics run on (a maximum, a sum) beside
    sum p (log p - I) and sum p, closed when the band ends:
    KL = sum p (log p - I) + (m + log l) sum p.

    ``with_grad`` walks a block's band TWICE (the grid's last axis is
    ``2 * steps``): first the scores alone for (m, l), then everything,
    with log softmax_S(I) = I - m - log l in hand: the KL term by term and

        dI = (softmax_S(I) - p) norm  on the kept pairs,
        dz_j = dI a_j [z_j > 0],   da_j = sum_s dI ReLU(z_j),
        dq^I_j (turned) += k^I(turned) dz_j,    dk^I += dz_j q^I_j

    (z_j made again from the operands: no plane a head is kept); dz enters
    the MXU in the operands' type, everything else is float32. dqt_ref and
    dat_ref are laid out as qit_ref and at_ref; dk_ref [N, d_I] is the
    whole sequence's and leaves once, as dk and dv do above. Scratch: the
    main heads stacked [h, block_q, d], three planes [block_kv, block_q]
    float32 (the selection's block turned, the target, the scores and
    then dI), the row statistics, and the gradients' accumulators."""
    if with_grad:
        (qit_ref, at_ref, ki_ref, kit_ref, sel_ref, q_ref, k_ref, lse_ref,
         loss_ref, dqt_ref, dat_ref, dk_ref, q_scr, kept_scr, p_scr, i_scr,
         m_scr, l_scr, sum_scr, dqt_scr, dat_scr, dk_scr) = refs
    else:
        (qit_ref, at_ref, ki_ref, sel_ref, q_ref, k_ref, lse_ref, loss_ref,
         q_scr, kept_scr, p_scr, i_scr, m_scr, l_scr, sum_scr, mass_scr) = refs
    heads, d = q_scr.shape[0], q_scr.shape[-1]
    di = qit_ref.shape[0] // index_heads
    i, t = pl.program_id(1), pl.program_id(2)
    _, count = _band(i, block_q, block_kv, None)
    # ``with_grad``: the tile of the band's second walk (negative in the first)
    at_tile = t - steps if with_grad else t

    @pl.when(t == 0)
    def _():
        for j in range(heads):
            q_scr[j] = q_ref[:, j * d:(j + 1) * d]
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        for ref in (l_scr, sum_scr) + (
                (dqt_scr, dat_scr) if with_grad else (mass_scr,)):
            ref[...] = jnp.zeros_like(ref)

    if with_grad:
        @pl.when(jnp.logical_and(i == 0, t == 0))
        def _():
            dk_scr[...] = jnp.zeros_like(dk_scr)

    def head_rows(j):
        return pl.ds(pl.multiple_of(j * di, di), di)

    def kept():
        return kept_scr[...] != 0.0

    def scores():
        """I into ``i_scr``, the selection's block turned into ``kept_scr``."""
        kept_scr[...] = sel_ref[...].astype(jnp.float32).T
        i_scr[...] = jnp.zeros_like(i_scr)
        ki = ki_ref[...]

        def head(j, carry):
            z = _dot(ki, qit_ref[head_rows(j), :])
            i_scr[...] += jnp.maximum(z, 0.0) * at_ref[pl.ds(j, 1), :]
            return carry

        jax.lax.fori_loop(0, index_heads, head, 0)

    def statistics():
        """The running (maximum, sum) of exp(I) over the rows' kept keys
        (a row that has kept none yet stands at (-1e30, 0))."""
        scores_, m_prev, held = i_scr[...], m_scr[...], kept()
        m_next = jnp.maximum(m_prev, jnp.max(
            jnp.where(held, scores_, _NEG), axis=0, keepdims=True))
        e = jnp.where(held, jnp.exp(scores_ - m_next), 0.0)
        l_scr[...] = jnp.exp(m_prev - m_next) * l_scr[...] + jnp.sum(
            e, axis=0, keepdims=True)
        m_scr[...] = m_next

    def target():
        """p [block_kv, block_q]: 0 off the kept pairs (where a head's
        exponential may have overflowed: masked once, after the sum)."""
        p_scr[...] = jnp.zeros_like(p_scr)
        for kh in range(heads // group):
            k = k_ref[:, kh * d:(kh + 1) * d]

            def head(j, carry, k=k, kh=kh):
                at = kh * group + j
                s = _dot(k, q_scr[at], _NT) * scale
                p_scr[...] += jnp.exp(s - lse_ref[pl.ds(at, 1), :])
                return carry

            jax.lax.fori_loop(0, group, head, 0)
        return jnp.where(kept(), p_scr[...] * (1.0 / heads), 0.0)

    def cross_entropy(p, logq):
        """sum_s p (log p - logq) a query, over the pairs with p > 0."""
        live = p > 0.0
        return jnp.sum(jnp.where(
            live, p * (jnp.log(jnp.where(live, p, 1.0)) - logq), 0.0),
            axis=0, keepdims=True)

    if not with_grad:
        @pl.when(t < count)
        def _():
            scores()
            statistics()
            p = target()
            sum_scr[...] += cross_entropy(p, i_scr[...])
            mass_scr[...] += jnp.sum(p, axis=0, keepdims=True)

        @pl.when(t == count - 1)
        def _():
            loss_ref[...] = sum_scr[...] + mass_scr[...] * (
                m_scr[...] + jnp.log(l_scr[...]))
        return

    @pl.when(t < count)
    def _():
        scores()
        statistics()

    @pl.when(jnp.logical_and(at_tile >= 0, at_tile < count))
    def _():
        scores()
        p = target()
        logq = i_scr[...] - (m_scr[...] + jnp.log(l_scr[...]))
        sum_scr[...] += cross_entropy(p, logq)
        i_scr[...] = jnp.where(kept(), jnp.exp(logq) - p, 0.0) * norm     # dI
        ki, kit = ki_ref[...], kit_ref[...]
        keys = pl.ds(pl.multiple_of(at_tile * block_kv, block_kv), block_kv)

        def head(j, carry):
            rows = head_rows(j)
            qt, di_ = qit_ref[rows, :], i_scr[...]
            z = _dot(ki, qt)
            dat_scr[pl.ds(j, 1), :] += jnp.sum(
                jnp.maximum(z, 0.0) * di_, axis=0, keepdims=True)
            dz = jnp.where(z > 0.0, di_ * at_ref[pl.ds(j, 1), :], 0.0).astype(
                qt.dtype)
            dk_scr[keys, :] += _dot(dz, qt, _NT)
            dqt_scr[rows, :] += _dot(kit, dz)
            return carry

        jax.lax.fori_loop(0, index_heads, head, 0)

    @pl.when(at_tile == count - 1)
    def _():
        loss_ref[...] = sum_scr[...]
        dqt_ref[...] = dqt_scr[...].astype(dqt_ref.dtype)
        dat_ref[...] = dat_scr[...].astype(dat_ref.dtype)

    @pl.when(jnp.logical_and(i == pl.num_programs(1) - 1, at_tile == count - 1))
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)


def index_loss_fits(q_shape, index_shape) -> bool:
    """Whether ``index_loss_tiles`` takes main heads [.., h, d] beside an
    indexer [.., H_I, d_I] (given the core took the kernels): whole lane
    tiles a main head, whole sublane tiles (of a 2-byte type) an index
    head."""
    return not (q_shape[-1] % _LANES or index_shape[-1] % 16)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "with_grad", "block_q", "block_kv", "interpret"))
def index_loss_tiles(qi, ki, a, selection, q, k, lse, with_grad=False,
                     block_q=512, block_kv=1024, interpret=False):
    """``ops/sparse_index.py index_loss`` on the kernel path, ONE
    ``pallas_call``: (L, () or L's gradient on (q^I, k^I, a)). qi [B, N,
    H_I, d_I], ki [B, N, d_I], a [B, N, H_I], ``selection`` [B, N,
    N] int8, q [B, N, h, d], k [B, N, hk, d] and ``lse`` [B, h, N], the
    main attention's rows' log-sum-exp over their kept keys, as the core
    hands it on. No [.., keys] float32 plane leaves VMEM."""
    b, n, hi, di = qi.shape
    h, d, hk = q.shape[2], q.shape[3], k.shape[2]
    blocks = n // block_q
    steps = _band(blocks - 1, block_q, block_kv, None)[1]
    turned = lambda x: jnp.swapaxes(x.reshape(b, n, -1), 1, 2)  # noqa: E731

    def tile(i, t):
        return jnp.minimum(jnp.where(t >= steps, t - steps, t),
                           _band(i, block_q, block_kv, None)[1] - 1)

    vmem = pltpu.VMEM
    rows = lambda w: pl.BlockSpec(  # noqa: E731
        (None, w, block_q), lambda s, i, t: (s, 0, i), memory_space=vmem)
    keys = lambda w: pl.BlockSpec(  # noqa: E731
        (None, block_kv, w), lambda s, i, t: (s, tile(i, t), 0), memory_space=vmem)
    operands = [turned(qi), turned(a), ki, selection, q.reshape(b, n, -1),
                k.reshape(b, n, -1), lse]
    in_specs = [
        rows(hi * di), rows(hi), keys(di),
        pl.BlockSpec((None, block_q, block_kv),
                     lambda s, i, t: (s, i, tile(i, t)), memory_space=vmem),
        pl.BlockSpec((None, block_q, h * d), lambda s, i, t: (s, i, 0),
                     memory_space=vmem),
        keys(hk * d), rows(h)]
    plane = pltpu.VMEM((block_kv, block_q), jnp.float32)
    row = pltpu.VMEM((1, block_q), jnp.float32)
    out_specs, out_shape = [rows(1)], [jax.ShapeDtypeStruct((b, 1, n), jnp.float32)]
    scratch = [pltpu.VMEM((h, block_q, d), q.dtype)] + [plane] * 3 + [row] * 4
    if with_grad:
        operands.insert(3, turned(ki))
        in_specs.insert(3, pl.BlockSpec(
            (None, di, block_kv), lambda s, i, t: (s, 0, tile(i, t)),
            memory_space=vmem))
        out_specs += [rows(hi * di), rows(hi), pl.BlockSpec(
            (None, n, di), lambda s, i, t: (s, 0, 0), memory_space=vmem)]
        out_shape += [jax.ShapeDtypeStruct((b, hi * di, n), qi.dtype),
                      jax.ShapeDtypeStruct((b, hi, n), a.dtype),
                      jax.ShapeDtypeStruct((b, n, di), ki.dtype)]
        scratch = scratch[:-1] + [pltpu.VMEM((hi * di, block_q), jnp.float32),
                                  pltpu.VMEM((hi, block_q), jnp.float32),
                                  pltpu.VMEM((n, di), jnp.float32)]
    norm = 1.0 / (b * n)
    out = pl.pallas_call(
        functools.partial(
            _index_loss_kernel, scale=d ** -0.5, norm=norm, group=h // hk,
            index_heads=hi, block_q=block_q, block_kv=block_kv, steps=steps,
            with_grad=with_grad),
        grid=(b, blocks, steps * (2 if with_grad else 1)),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name=INDEX_LOSS_GRAD_KERNEL_NAME if with_grad else INDEX_LOSS_KERNEL_NAME,
    )(*operands)
    loss = jnp.sum(out[0]) * norm
    if not with_grad:
        return loss, ()
    dqt, dat, dki = out[1:]
    return loss, (jnp.swapaxes(dqt, 1, 2).reshape(qi.shape), dki,
                  jnp.swapaxes(dat, 1, 2))


def _refuses_types(dtype, reduce_dtype) -> str | None:
    if dtype not in (jnp.bfloat16, jnp.float32):
        return f"{jnp.dtype(dtype).name} is neither bfloat16 nor float32"
    if reduce_dtype != jnp.float32:
        return (f"statistics in {jnp.dtype(reduce_dtype).name}: the "
                "kernels' are float32")
    return None


def _refuses_blocks(n: int, block_q: int, block_kv: int) -> str | None:
    if block_q % _LANES or block_kv % _LANES:
        return f"blocks of {block_q} x {block_kv} are not multiples of {_LANES}"
    if n % block_q or n % block_kv:
        return (f"{n} tokens are not whole blocks of {block_q} queries and "
                f"{block_kv} keys")
    return None


def _on_backend(interpret: bool | None) -> tuple[str, str]:
    backend = jax.default_backend()
    if interpret is None and backend != "tpu":
        return "tiles", f"the backend is {backend}, not a TPU"
    return "kernel", "interpreted" if interpret else "compiled for the TPU"


def causal_attention_path(shapes, window: int | None = None,
                          interpret: bool | None = None, block_q: int = 512,
                          block_kv: int = 1024, dtype=jnp.bfloat16,
                          reduce_dtype=jnp.float32) -> tuple[str, str]:
    """(path, why) ``causal_blockwise_attention`` takes for q, k, v of
    these three ``shapes`` and this one ``dtype`` on this backend:
    ("kernel", ...) or ("tiles", the reason it is not the kernel)."""
    (_, n, _, d), (_, _, hk, _), (_, _, _, dv) = shapes
    if why := _refuses_types(dtype, reduce_dtype):
        return "tiles", why
    packed = _packs(d, dv, hk)
    if (d % _LANES or dv % _LANES) and not packed:
        return "tiles", (
            f"the widths {d} + {dv} are not multiples of {_LANES}, nor are the "
            f"heads {_HALF} + {_HALF} wide on an even number of key/value "
            f"heads ({hk})")
    if why := _refuses_blocks(n, block_q, block_kv):
        return "tiles", why
    # (heads of 64: a PAIR of key/value heads is resident, 128 + 128)
    wide, wide_v = (_LANES, _LANES) if packed else (d, dv)
    resident = n * (wide + wide_v) * (4 + 2 * jnp.dtype(dtype).itemsize)
    if resident > _RESIDENT_BYTES:
        return "tiles", (f"dk and dv of {n} tokens ({resident >> 20} MiB) "
                         "do not fit the backward's VMEM")
    return _on_backend(interpret)


def latent_attention_path(tokens: int, heads: int, widths,
                          interpret: bool | None = None,
                          block_q: int = LATENT_BLOCK_Q,
                          block_kv: int = LATENT_BLOCK_KV, dtype=jnp.bfloat16,
                          reduce_dtype=jnp.float32) -> tuple[str, str]:
    """(path, why) a latent-attention mixer takes for ``heads`` heads of
    ``widths`` (qk_nope, qk_rope, v) over ``tokens`` tokens a sequence, in
    this one ``dtype`` on this backend: ("kernel", ...) is
    ``latent_attention``, ("tiles", the reason it is not) the key
    repeated for every head and ``causal_blockwise_attention``.

    The backward holds a PAIR of heads' dk_nope and dv of the whole
    sequence in float32 and the block they leave in (one buffer), and the
    shared key's cotangent in float32: ``tokens * (512 * (4 + itemsize) +
    512)`` bytes, 56 MiB at 16,384 tokens of bfloat16 under a VMEM limit
    of 100 (the rest is tiles and planes); 20,480 tokens are refused."""
    if why := _refuses_types(dtype, reduce_dtype):
        return "tiles", why
    if tuple(widths) != (_LANES, _HALF, _LANES) or heads % 2:
        return "tiles", (
            f"{heads} heads of {' | '.join(map(str, widths))}: the kernels "
            f"take an even number of {_LANES} | {_HALF} | {_LANES}")
    if why := _refuses_blocks(tokens, block_q, block_kv):
        return "tiles", why
    resident = tokens * (4 * _LANES * (4 + jnp.dtype(dtype).itemsize)
                         + 4 * _LANES)
    if resident > _LATENT_RESIDENT_BYTES:
        return "tiles", (
            f"a pair of heads' dk and dv and the shared key's of {tokens} "
            f"tokens ({resident >> 20} MiB) do not fit the backward's VMEM")
    return _on_backend(interpret)


def index_loss_path(shapes, index_width: int, **how) -> tuple[str, str]:
    """(path, why) ``ops/sparse_index.py index_loss`` takes beside a core
    of these q, k, v ``shapes`` and an indexer of heads ``index_width``
    wide (``how``: ``causal_attention_path``'s other arguments):
    ("kernel", ...) or ("strips", the reason it is not the kernel)."""
    path, why = causal_attention_path(shapes, None, **how)
    if path != "kernel":
        return "strips", f"the core hands on no log-sum-exp: {why}"
    if not index_loss_fits(shapes[0], (index_width,)):
        return "strips", (
            f"heads of {shapes[0][-1]} and index heads of {index_width} are "
            f"not whole tiles of {_LANES} lanes and 16 sublanes")
    return "kernel", why
