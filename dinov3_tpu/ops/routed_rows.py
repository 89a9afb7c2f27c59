"""How ``ops/ffn.py RoutedExpertsFFN`` moves rows around its held experts'
block: two gathers through integer index lists that ``moe_route`` makes
once a layer, each the other's transpose under a ``jax.custom_vjp``.

    dispatch:  rows[r] = x[token[r]]                 [N, D] -> [cap, D]
    combine:   y[n]    = sum of src[r] over the buffer rows r of token n,
                         added in float32, rounded once  [cap, D] -> [N, D]

The backward of dispatch IS combine (over the rows' gradient) and the
backward of combine IS dispatch (of the layer's cotangent, in the type it
arrives in: bfloat16 in a bfloat16 model, so no float32 ``[cap, D]``
cotangent plane is made; the kernels' backward rounds what it is given to
the rows' type, and XLA folds the widening that the rule's signature
needs with that rounding). Every index is in bounds by construction and
every gather says so: no fill-mode select over a ``[cap, D]`` plane.

``RowLists`` (``row_lists``) holds ``token`` [cap], the token of every
buffer row, and what the combine's form reads besides. The combine is
ONE sum, and ``combine_form`` says which traversal runs here (as
``grouped_matmul_path`` does for the block):

- "sorted": the buffer gathered into token order (``perm``, a sort of
  ``cap`` integers) and the runs of a token's rows, which are contiguous
  now, added by ``ROWS_SORTED_SUM``, a Pallas kernel that reads the
  sorted buffer once by aligned chunks and adds a tile of tokens in VMEM
  as a one-hot product (exact: a float32 row goes as three bfloat16
  parts). It pays by the buffer, whatever ``N * top_k`` is;
- "scatter": ``zeros.at[token].add(src)`` with the in-bounds promise,
  where the kernel does not run (the CPU, a width that is not whole
  lanes): XLA sorts the ``cap`` rows and adds.

Measured and not shipped (both passes of one layer's combine alone on a
v5e at the six decoder cells' shapes, ms, ``chip_smoke.py --phases
moe_rows``; my chip run, PR 47, call 1; smallthinker, kanana2,
qwen3_next, keye_vl2, lfm2, kimi_linear: ``N * top_k / cap`` 2, 2, 4, 4,
4, 16): a gather-sum through the inverse list ``pos[N, top_k]`` (XLA
fuses ONE gather a fusion, so it is ``top_k`` passes over a float32
``[N, D]`` accumulator) read 12.33, 9.89, 16.49, 13.77, 14.53, 5.76
against "sorted" 6.22, 5.12, 4.29, 3.82, 4.91, 1.96 and "scatter" 12.44,
8.81, 7.76, 6.56, 8.51, 4.91 (the parent's forms 13.25, 9.41, 8.25, 6.98,
11.47, 4.92): it wins at no ratio, so there is no rule by
``(N, top_k, held, cap)`` to keep. A kernel that DMA-gathers single rows
out of the unsorted buffer is refused by this Mosaic (a row of a
``[cap, D]`` plane in HBM's (8, 128) tiling cannot be sliced): XLA's own
gather does the random access, the kernel the sum.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dinov3_tpu.ops.grouped_matmul import LANES

ROWS_SORTED_SUM = "moe_rows_sorted_sum"
# tokens a grid step, buffer rows a DMA (whole (16, 128) bfloat16 tiles)
_TOKEN_TILE, _ROW_CHUNK = 128, 128


class RowLists(NamedTuple):
    token: jax.Array                 # [cap] int32
    perm: jax.Array | None = None    # "sorted": [cap] int32, rows by token
    sorted_token: jax.Array | None = None  # [cap / chunk, 1, chunk] int32
    starts: jax.Array | None = None  # [N / tile + 1] int32, into the sort


def combine_form(n_tokens: int, cap: int, d: int,
                 interpret: bool | None = None) -> str:
    """"sorted" | "scatter": the combine's traversal of a ``[cap, d]``
    buffer into ``n_tokens`` tokens on this backend (``interpret``:
    ``grouped_matmul_path``'s)."""
    kernel = (d % LANES == 0 and cap % _ROW_CHUNK == 0
              and n_tokens % _TOKEN_TILE == 0
              and (interpret is not None or jax.default_backend() == "tpu"))
    return "sorted" if kernel else "scatter"


def row_lists(order, kept, n_tokens: int, top_k: int, form: str) -> RowLists:
    """The lists of one layer from ``order`` [cap] (the (token, choice)
    pair ``token * top_k + choice`` of every buffer row) and ``kept``
    [cap] (the rows that hold a pair routed here)."""
    cap = order.shape[0]
    token = order // top_k
    if form == "scatter":
        return RowLists(token)
    # a row that holds no pair sorts past every token's and is no one's
    key = jnp.where(kept, token, n_tokens)
    in_order, perm = jax.lax.sort(
        (key, jnp.arange(cap, dtype=jnp.int32)), num_keys=1)
    # (a count where a search would do: one fused pass over the keys)
    edges = jnp.arange(0, n_tokens + 1, _TOKEN_TILE, dtype=jnp.int32)
    starts = jnp.sum(key[None, :] < edges[:, None], axis=1, dtype=jnp.int32)
    return RowLists(token, perm=perm, starts=starts,
                    sorted_token=in_order.reshape(
                        cap // _ROW_CHUNK, 1, _ROW_CHUNK))


# ------------------------------------------------------ the sorted sum


def _sorted_sum_kernel(starts, tokens, src, out, acc, rows, toks, sem, *,
                       tn, chunk):
    """One tile of ``tn`` tokens: the aligned chunks of the token-sorted
    buffer that hold its rows come one DMA each (the next in flight while
    one is added), and a chunk is added as onehot[tn, chunk] x rows: a
    row of another tile's token matches no row of the one-hot."""
    i = pl.program_id(0)
    lo = starts[i] // chunk
    hi = (starts[i + 1] + chunk - 1) // chunk

    def fetch(c, slot):
        return (pltpu.make_async_copy(
            src.at[pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :],
            rows.at[slot], sem.at[slot, 0]),
            pltpu.make_async_copy(tokens.at[c], toks.at[slot], sem.at[slot, 1]))

    def start(c, slot):
        for copy in fetch(c, slot):
            copy.start()

    @pl.when(lo < hi)
    def _():
        start(lo, 0)

    acc[...] = jnp.zeros_like(acc)
    local = jax.lax.broadcasted_iota(jnp.int32, (tn, chunk), 0) + i * tn

    def add(c, carry):
        slot = (c - lo) % 2

        @pl.when(c + 1 < hi)
        def _():
            start(c + 1, 1 - slot)

        for copy in fetch(c, slot):
            copy.wait()
        onehot = (toks[slot] == local).astype(jnp.bfloat16)
        dot = lambda part: jax.lax.dot_general(  # noqa: E731
            onehot, part, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
        x = rows[slot]
        if x.dtype == jnp.float32:  # three bfloat16 parts hold it exactly
            high = x.astype(jnp.bfloat16)
            rest = x - high.astype(jnp.float32)
            mid = rest.astype(jnp.bfloat16)
            low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
            acc[...] += dot(high) + dot(mid) + dot(low)
        else:
            acc[...] += dot(x)
        return carry

    jax.lax.fori_loop(lo, hi, add, 0)
    out[...] = acc[...].astype(out.dtype)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "n_tokens", "dtype", "interpret"))
def _sorted_sum(src, lists: RowLists, n_tokens, dtype, interpret):
    d, tn, chunk = src.shape[1], _TOKEN_TILE, _ROW_CHUNK
    in_order = src.at[lists.perm].get(mode="promise_in_bounds",
                                      unique_indices=True)
    return pl.pallas_call(
        functools.partial(_sorted_sum_kernel, tn=tn, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_tokens // tn,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tn, d), lambda i, s: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((tn, d), jnp.float32),
                            pltpu.VMEM((2, chunk, d), src.dtype),
                            pltpu.VMEM((2, 1, chunk), jnp.int32),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((n_tokens, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret, name=ROWS_SORTED_SUM,
    )(lists.starts, lists.sorted_token, in_order)


# ------------------------------------------------- the two movements


def _dispatch(x, lists: RowLists):
    with jax.named_scope("moe_rows"):
        return x.at[lists.token].get(mode="promise_in_bounds")


def _combine(src, lists: RowLists, n_tokens: int, dtype, interpret):
    """``src`` [cap, D] -> [n_tokens, D] ``dtype``. A row of ``src`` that
    holds no routed pair must be zero where the form adds it ("scatter":
    the experts' block answers for that, values and gradients; "sorted"
    reads no such row)."""
    with jax.named_scope("moe_rows"):
        if lists.perm is not None:
            # (one key in the inline jit's cache for a rule's primal and
            # its forward rule: ``grouped_matmul._forward_once``)
            with jax.sharding.use_abstract_mesh(
                    jax.sharding.get_abstract_mesh()):
                return _sorted_sum(src, lists, n_tokens=n_tokens, dtype=dtype,
                                   interpret=bool(interpret))
        # in the source's type, as the transposed ``take`` added
        acc = jnp.zeros((n_tokens,) + src.shape[1:], src.dtype)
        return acc.at[lists.token].add(
            src, mode="promise_in_bounds").astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dispatch_rows(x, lists, n_tokens, interpret):
    return _dispatch(x, lists)


def _dispatch_fwd(x, lists, n_tokens, interpret):
    return _dispatch_rows(x, lists, n_tokens, interpret), lists


def _dispatch_bwd(n_tokens, interpret, lists, d_rows):
    return _combine(d_rows, lists, n_tokens, d_rows.dtype, interpret), None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


def dispatch_rows(x, lists: RowLists, interpret: bool | None = None):
    """``x`` [N, D] -> the expert-sorted buffer [cap, D], in ``x``'s type.
    The rows past the last routed pair hold some token's row, not zeros:
    the experts' block answers for them."""
    return _dispatch_rows(x, lists, x.shape[0], interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _combine_rows(src, lists, n_tokens, dtype, src_dtype, interpret):
    return _combine(src, lists, n_tokens, dtype, interpret)


def _combine_fwd(src, lists, n_tokens, dtype, src_dtype, interpret):
    return _combine(src, lists, n_tokens, dtype, interpret), lists


def _combine_bwd(n_tokens, dtype, src_dtype, interpret, lists, dy):
    return _dispatch(dy, lists).astype(src_dtype), None


_combine_rows.defvjp(_combine_fwd, _combine_bwd)


def combine_rows(src, lists: RowLists, n_tokens: int, dtype,
                 interpret: bool | None = None):
    """The buffer ``src`` [cap, D] added back into its tokens:
    [n_tokens, D] ``dtype``, rounded once."""
    return _combine_rows(src, lists, n_tokens, jnp.dtype(dtype), src.dtype,
                         interpret)
