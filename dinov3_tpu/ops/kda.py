"""Kimi Delta Attention: the gated delta rule with one decay per key
channel, in chunks.

Per head, with q_t, k_t in R^dk (k L2-normalised), v_t in R^dv, a decay
a_t = exp(g_t) in (0, 1)^dk and a write strength b_t in (0, 1):

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,  S_0 = 0
    o_t = S_t^T q_t,                                  S in R^{dk x dv}

``kda_chunked`` computes it ``chunk`` tokens at a time (the WY form of
the delta rule). Inside a chunk, with G_t the running sum of g from the
chunk's first token and S the state the chunk starts from,

    u_t = b_t (v_t - S^T (e^{G_t} k_t) - sum_{s<t} A_ts u_s),
          A_ts = sum_c k_tc k_sc e^{G_tc - G_sc}
    o_t = S^T (e^{G_t} q_t) + sum_{s<=t} P_ts u_s,
          P_ts = sum_c q_tc k_sc e^{G_tc - G_sc}
    S'  = Diag(e^{G_C}) S + sum_s (e^{G_C - G_s} k_s) u_s^T

so the chunk is a handful of matmuls: A, P, the inverse of the unit
lower-triangular I + Diag(b) A (a product of 6 factors, since its
strict part is nilpotent), and five products with the state.

What runs where. ``kda_chunked`` has two forward paths that share these
definitions and no loop, and ``kda_path`` chooses between them from
what it can see, no option or variable: the KERNEL where the backend is
a TPU (or a test asks for ``interpret``), d_k and d_v are multiples of
128 and the chunk is 64; the PLAIN path (``_chunk`` under ``lax.scan``,
each chunk's body rematerialised) everywhere else: CPU runs, narrow
test widths, other chunks. The kernel (``kda_chunk_fwd``, one
``pallas_call``) takes the chunk axis as the grid's sequential axis and
the sequence as the parallel one; a grid step is one chunk of every
head, worked through a pair of heads at a time. In VMEM it keeps that
chunk of q, k, v (the activation type), g and o (float32) and every
head's [d_v, d_k] float32 state, zeroed at a sequence's first chunk and
carried from grid step to grid step: a state never goes to HBM inside a
sequence, unless the pass is the one that a backward follows, which
also writes each chunk's STARTING state ([n, B, H, d_k, d_v], what the
scan's backward keeps). The blocks are cut from the [B, T, H, d] arrays
as they lie (a chunk is [C * H, d] rows, token-major and head-minor, of
which a head is every H-th: strided loads and stores), so no copy is
made on either side of the call. The BACKWARD of both paths is the plain
one: the ``_chunk`` body's VJP, chunk by chunk from the last, recomputed
from the saved starting states (``custom_vjp`` on the kernel path, JAX's
own transposition of the scan on the plain one). Under a layer's remat
the first pass runs the kernel without the state output.

Precision: g, G, every exponential, every product below and the state
are float32, the matmuls at ``Precision.HIGHEST`` (on the TPU a float32
matmul is otherwise one bfloat16 pass). A_ts and P_ts need e^{G_t - G_s}
for s < t, which is at most 1, but a matmul can only take it as
(k_t e^{G_t - G_r})(k_s e^{G_r - G_s}) about some reference token r, and
a factor is finite only while |G - G_r| stays under float32's 88. So the
plane is made block by block, halving (``_decayed_products``): a chunk's
later half against its earlier half about the later half's first token,
then each half's two quarters alike, down to single tokens. With
s < r <= t both exponents are sums of log decays, never positive: no
factor exceeds 1 whatever a channel's decay, and one that underflows
stands for a product that is smaller still. (About ONE reference in the
chunk's middle a factor reaches e^{32 x decay}, which float32 holds only
while a channel decays by less than 2.7 nats a token; the published
initial values reach 1.6, and learned ones are unbounded.)
G itself is a float32 running sum: a difference of two of its values is
good to |G| 2^-24, so a chunk that decays by thousands of nats resolves
the factors of its slow channels to 1e-4 and no better.
The kernel's arithmetic is the plain path's, product for product, in
float32 with full-precision products (Mosaic's ``contract_precision
<fp32>``: on the chip 8e-8 of the token recurrence, as the plain path)
and the same halving levels: no factor above 1 at any decay. It differs
in the blocking alone: a level is ONE product over the whole chunk,
masked to the level's blocks (12 times the score operations the count
in ``benchmark/lm_flops.py`` needs, at shapes the MXU takes); the score
planes are transposed; beta scales the right-hand side of T's product
and not T's columns; and two heads' [C, C] planes share a [C, 2 C]
plane against block-diagonal operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
KERNEL_NAME = "kda_chunk_fwd"
_NEG = -1e30
# a grid step holds a chunk of every head twice over (6 MB at 32 heads of
# 128, 10 with the state output) beside the states' 2: past the 16 MB a
# kernel gets unasked
_VMEM_LIMIT = 64 * 1024 * 1024
_HI = jax.lax.Precision.HIGHEST


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _unit_lower_inverse(strict):
    """(I + L)^-1 for strictly lower-triangular L [..., C, C], C a power
    of two: L^C = 0, so the inverse is the finite series sum_k (-L)^k =
    (I - L)(I + L^2)(I + L^4)...(I + L^(C/2))."""
    c = strict.shape[-1]
    eye = jnp.eye(c, dtype=strict.dtype)
    inv, power = eye - strict, strict
    for _ in range(max(c.bit_length() - 2, 0)):
        power = _mm("...ij,...jk->...ik", power, power)
        inv = _mm("...ij,...jk->...ik", inv, eye + power)
    return inv


def _diagonal(x):
    """[B, C, H] -> [B, H, C, C] with x on the diagonal."""
    x = jnp.moveaxis(x, 1, 2)
    return x[..., None] * jnp.eye(x.shape[-1], dtype=x.dtype)


def _decayed_products(rows, k, big):
    """sum_c rows_tc k_sc e^{G_tc - G_sc} for s < t (0 elsewhere):
    rows [R, B, C, H, dk] (R stacked row operands: q and k), k and the
    running log decay ``big`` [B, C, H, dk]; returns [R, B, H, C, C].

    Level by level, single tokens up to C/2: every block of 2 * half
    tokens gives its later half's rows against its earlier half's
    columns, about the later half's first token r, and is put together
    from that product (lower left) and the two blocks of the level below
    (on its diagonal). Both exponents (G_t - G_r for t >= r, G_r - G_s
    for s < r) are <= 0, so no factor overflows."""
    r, b, c, h, dk = rows.shape
    out = jnp.zeros((r, b, h, c, 1, 1), jnp.float32)
    half = 1
    while half < c:
        n = c // (2 * half)
        halves = lambda x: x.reshape(x.shape[:-3] + (n, 2, half, h, dk))  # noqa: E731
        g2 = halves(big)
        ref = g2[:, :, 1, :1]                                 # G at r
        later = halves(rows)[:, :, :, 1] * jnp.exp(g2[:, :, 1] - ref)
        earlier = halves(k)[:, :, 0] * jnp.exp(ref - g2[:, :, 0])
        lower_left = _mm("rbnthc,bnshc->rbhnts", later, earlier)
        below = out.reshape(r, b, h, n, 2, half, half)
        out = jnp.concatenate([
            jnp.concatenate([below[..., 0, :, :], jnp.zeros_like(lower_left)], -1),
            jnp.concatenate([lower_left, below[..., 1, :, :]], -1)], -2)
        half *= 2
    return out[..., 0, :, :]


def _chunk(state, q, k, v, g, beta, q_scale):
    """One chunk. state [B, H, dk, dv] float32; q, k [B, C, H, dk] and
    v [B, C, H, dv] in any float type; g [B, C, H, dk] and beta
    [B, C, H] float32. Returns (state', o), float32."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    q = q * q_scale
    big = jnp.cumsum(g, axis=1)                      # G_t, <= 0
    from_start = jnp.exp(big)                        # e^{G_t}, <= 1
    to_end = jnp.exp(big[:, -1][:, None] - big)      # e^{G_C - G_t}, <= 1
    p, a = _decayed_products(jnp.stack([q, k]), k, big)
    p = p + _diagonal(jnp.sum(q * k, axis=-1))       # s = t: no decay
    bt = jnp.moveaxis(beta, 1, 2)                    # [B, H, C]
    t = _unit_lower_inverse(bt[..., None] * a) * bt[..., None, :]
    # u = T (V - (e^G k) S)
    rhs = v - _mm("bthc,bhcd->bthd", k * from_start, state)
    u = _mm("bhts,bshd->bthd", t, rhs)
    o = _mm("bthc,bhcd->bthd", q * from_start, state) \
        + _mm("bhts,bshd->bthd", p, u)
    new = from_start[:, -1][..., None] * state \
        + _mm("bshc,bshd->bhcd", k * to_end, u)
    return new, o


def _scan_forward(q, k, v, g, beta, chunk, q_scale):
    """The plain path: ``_chunk`` under ``lax.scan``, T a multiple of
    ``chunk``. Its reverse-mode derivative is JAX's own (the body is
    rematerialised: one state a chunk is kept)."""
    b, t, h, dk = q.shape
    n = t // chunk
    # [n, B, C, ...]: the scan's leading axis is the chunk
    chunks = tuple(
        jnp.moveaxis(x.reshape((b, n, chunk) + x.shape[2:]), 1, 0)
        for x in (q, k, v, g, beta))
    body = jax.checkpoint(lambda s, xs: _chunk(s, *xs, q_scale))
    _, o = jax.lax.scan(
        body, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), chunks)
    return jnp.moveaxis(o, 0, 1).reshape(b, t, h, v.shape[-1])


# ------------------------------------------------- the forward as a kernel


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _pair_planes(ref, pair, heads):
    """Heads ``2 * pair`` and ``2 * pair + 1``'s [C, d] float32 planes out
    of a block [1, C * heads, d] whose rows run token-major, head-minor
    (the [B, T, H, d] array as it lies). A float32 block gives a head's
    rows by a strided load; a bfloat16 block packs two rows a 32-bit
    word, which here are the pair's own two heads: one strided load of
    words, the even head in the low halves."""
    at = lambda first, stride: (0, pl.ds(first, CHUNK, stride=stride))  # noqa: E731
    if ref.dtype == jnp.float32:
        return tuple(ref[at(2 * pair + hd, heads)] for hd in (0, 1))
    words = ref.bitcast(jnp.uint32)[at(pair, heads // 2)]
    return (pltpu.bitcast(words << 16, jnp.float32),
            pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32))


def _fwd_kernel(*refs, q_scale, heads, keep_states):
    """One (sequence, chunk) of the grid: every head of the chunk, a PAIR
    at a time. q/k/v/g/o refs [1, C * heads, d] (a chunk of the
    [B, T, H, d] arrays, rows token-major and head-minor), ``b_ref``
    [heads / 2, 2 * C] a row of beta a pair (the two heads' chunks side
    by side), the optional states [heads, dk, dv]; ``st_ref`` [heads, dv,
    dk] holds the states, TRANSPOSED so that the per-channel decay runs
    along the lanes.

    Everything between a pair's loads and its stores is values: the two
    heads' chains are independent until then and the scheduler
    interleaves them. The score planes are kept TRANSPOSED ([s, t]: the
    streamed operand of a level's product is then the 64 columns, not
    the 128 stacked rows) and, from the inverse on, the pair's planes
    sit side by side in one [C, 2 * C] plane, which a product takes
    against a block-diagonal [2 * C, 2 * C] operand: full MXU tiles
    where one head's [C, C] would fill a quarter."""
    if keep_states:
        q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, st_ref = refs
    else:
        q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref = refs
        s_ref = None
    c, dk = CHUNK, q_ref.shape[-1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    rows = jax.lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    srow = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    tcol, left = lane & (c - 1), lane < c
    eye = (srow == tcol).astype(jnp.float32)       # [I | I]
    row2 = jax.lax.broadcasted_iota(jnp.int32, (2 * c, 2 * c), 0)
    col2 = jax.lax.broadcasted_iota(jnp.int32, (2 * c, 2 * c), 1)
    same_half = (row2 < c) == (col2 < c)

    def blocks(x):                  # [X0 | X1] -> [[X0, 0], [0, X1]]
        return jnp.where(same_half, jnp.concatenate([x, x], 0), 0.0)

    def scores(hd, q, k, big):
        """(G_C on every row, [A^T | P^T] for head 0 of the pair and
        [P^T | A^T] for head 1: the pair's A planes then lie side by
        side with no lane moved). A and P below the diagonal, level by
        level as _decayed_products: ``end`` is G at the last token of a
        row's block of ``half`` tokens; a later half's rows decay from
        the earlier half's end to their token, the earlier half's
        columns from their token to that end. Every level is ONE product
        over the whole chunk, of which the entries inside a block of
        2 * half are kept."""
        first, second = (k, q) if hd == 0 else (q, k)
        end, out, half = big, jnp.zeros((c, 2 * c), jnp.float32), 1
        while half < c:
            later = (rows & half) != 0
            lat = jnp.exp(jnp.where(later, big - pltpu.roll(end, half, 0), _NEG))
            ear = jnp.exp(jnp.where(later, _NEG, end - big))
            prod = _dot(k * ear,
                        jnp.concatenate([first * lat, second * lat], 0), _NT)
            shift = half.bit_length()
            out = out + jnp.where(
                (srow >> shift) == (tcol >> shift), prod, 0.0)
            end = jnp.where(later, end, pltpu.roll(end, c - half, 0))
            half *= 2
        return end, out

    def one_pair(pair, carry):
        planes = []
        for hd, (q, k, v, big) in enumerate(zip(*(
                _pair_planes(ref, pair, heads)
                for ref in (q_ref, k_ref, v_ref, g_ref)))):
            q = q * q_scale
            step = 1
            while step < c:                              # G_t: running sum
                big = big + jnp.where(
                    rows >= step, pltpu.roll(big, step, 0), 0.0)
                step *= 2
            planes.append((q, k, v, big, *scores(hd, q, k, big),
                           jnp.sum(q * k, axis=-1, keepdims=True)))
        (*_, s0, qk0), (*_, s1, qk1) = planes
        a_t = jnp.where(left, s0, s1)                    # [A0^T | A1^T]
        p_t = jnp.where(left, s1, s0) + jnp.where(       # [P1^T | P0^T]
            srow == tcol, jnp.where(left, qk1, qk0), 0.0)
        # (I + A^T Diag(b))^-1 = (I - L)(I + L^2)(I + L^4)...: a stage
        # squares the power and multiplies it into the inverse in ONE
        # product, [power; inverse] against the power's blocks
        b_row = b_ref[pl.ds(pair, 1), :]                 # [1, 2 C]
        strict = a_t * b_row
        inv, power = eye - strict, _dot(strict, blocks(strict), _NN)
        for _ in range(c.bit_length() - 3):
            both = _dot(jnp.concatenate([power, inv], 0), blocks(power), _NN)
            inv, power = inv + both[c:], both[:c]
        inv = inv + _dot(inv, blocks(power), _NN)
        states, rhs, out = [], [], []
        for hd, (q, k, v, big, *_) in enumerate(planes):
            st, from_start = st_ref[2 * pair + hd], jnp.exp(big)
            with_state = _dot(jnp.concatenate(
                [k * from_start, q * from_start], 0), st, _NT)
            states.append(st)
            rhs.append(v - with_state[:c])
            out.append(with_state[c:])
        # [u0; u1] = blocks(T) [b0 r0; b1 r1] (beta stood up as a column
        # through the diagonal), then [P1 u1; P0 u0]
        b_col = jnp.sum(jnp.where(row2 == col2, b_row, 0.0), axis=1,
                        keepdims=True)
        u = _dot(blocks(inv), b_col * jnp.concatenate(rhs, 0), _TN)
        pu = _dot(blocks(p_t), jnp.concatenate([u[c:], u[:c]], 0), _TN)
        for hd, (q, k, v, big, end, *_) in enumerate(planes):
            head = 2 * pair + hd
            o_ref[0, pl.ds(head, c, stride=heads), :] = out[hd] + (
                pu[c:] if hd == 0 else pu[:c])
            if keep_states:
                s_ref[head] = states[hd].T
            st_ref[head] = jnp.exp(end[:1]) * states[hd] + _dot(
                u[hd * c:(hd + 1) * c], k * jnp.exp(end - big), _TN)
        return carry

    jax.lax.fori_loop(0, heads // 2, one_pair, 0)


def _kernel_forward(q, k, v, g, beta, q_scale, keep_states, interpret):
    """The forward pass as one ``pallas_call``; T a multiple of CHUNK,
    dk and dv multiples of 128. Returns (o, states): o [B, T, H, dv]
    float32, states [n, B, H, dk, dv] (each chunk's STARTING state) or
    None. An odd head count gains a head that neither decays nor
    writes."""
    heads = q.shape[2]
    if heads % 2:
        widths = ((0, 0), (0, 0), (0, 1), (0, 0))
        q, k, v, g = (jnp.pad(x, widths) for x in (q, k, v, g))
        beta = jnp.pad(beta, widths[:3])
    b, t, h, dk = q.shape
    dv, n = v.shape[-1], t // CHUNK
    q, k, v = (x if x.dtype == jnp.bfloat16 else x.astype(jnp.float32)
               for x in (q, k, v))
    # a chunk of [B, T, H, d] as it lies: [C * H, d], no copy where H
    # fills the sublane tiles (8 rows of float32, 16 of bfloat16)
    rows = lambda x: x.reshape(b, t * h, x.shape[-1])  # noqa: E731
    spec = lambda d: pl.BlockSpec(  # noqa: E731
        (1, CHUNK * h, d), lambda i, m: (i, m, 0), memory_space=pltpu.VMEM)
    # a pair's beta, chunk by chunk: [B, n, H / 2, 2 * C]
    b_rows = jnp.moveaxis(
        beta.reshape(b, n, CHUNK, h // 2, 2), 2, 4).reshape(
            b, n, h // 2, 2 * CHUNK)
    out_specs = [spec(dv)]
    out_shape = [jax.ShapeDtypeStruct((b, t * h, dv), jnp.float32)]
    if keep_states:
        out_specs.append(pl.BlockSpec(
            (None, None, h, dk, dv), lambda i, m: (m, i, 0, 0, 0),
            memory_space=pltpu.VMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((n, b, h, dk, dv), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, q_scale=q_scale, heads=h,
                          keep_states=keep_states),
        grid=(b, n),
        in_specs=[spec(dk), spec(dk), spec(dv), spec(dk),
                  pl.BlockSpec((None, None, h // 2, 2 * CHUNK),
                               lambda i, m: (i, m, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((h, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_NAME,
    )(rows(q), rows(k), rows(v), rows(g), b_rows)
    o = out[0].reshape(b, t, h, dv)[:, :, :heads]
    return o, (out[1][:, :, :heads] if keep_states else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel_path(q, k, v, g, beta, q_scale, interpret):
    return _kernel_forward(q, k, v, g, beta, q_scale, False, interpret)[0]


def _kernel_path_fwd(q, k, v, g, beta, q_scale, interpret):
    o, states = _kernel_forward(q, k, v, g, beta, q_scale, True, interpret)
    return o, (q, k, v, g, beta, states)


def _kernel_path_bwd(q_scale, interpret, res, do):
    """The plain path's backward: the ``_chunk`` body's VJP, chunk by
    chunk from the last, each recomputed from its saved starting state.
    The chunks are sliced out of, and the gradients written into, the
    [B, T, ...] arrays where they lie: no chunk-major copy is made."""
    *inputs, states = res

    def body(j, carry):
        dstate, grads = carry
        i = states.shape[0] - 1 - j
        piece = lambda x: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            x, i * CHUNK, CHUNK, axis=1)
        _, vjp = jax.vjp(lambda s, *a: _chunk(s, *a, q_scale),
                         states[i], *(piece(x) for x in inputs))
        dstate, *dinputs = vjp((dstate, piece(do)))
        return dstate, tuple(
            jax.lax.dynamic_update_slice_in_dim(full, d, i * CHUNK, axis=1)
            for full, d in zip(grads, dinputs))

    _, grads = jax.lax.fori_loop(
        0, states.shape[0], body,
        (jnp.zeros_like(states[0]), tuple(jnp.zeros_like(x) for x in inputs)))
    return grads


# optimize_remat: under a layer's remat the pass that keeps no residuals
# runs the primal (no state output), not the forward rule with its states
# thrown away
_kernel_path.defvjp(_kernel_path_fwd, _kernel_path_bwd, optimize_remat=True)


def kda_path(dk: int, dv: int, chunk: int = CHUNK,
             interpret: bool | None = None) -> tuple[str, str]:
    """(path, why) ``kda_chunked`` takes at these widths on this backend:
    ("kernel", ...) or ("scan", the reason it is not the kernel)."""
    if chunk != CHUNK:
        return "scan", f"chunk {chunk} is not the kernel's {CHUNK}"
    if dk % 128 or dv % 128:
        return "scan", f"dk {dk}, dv {dv} are not multiples of 128"
    backend = jax.default_backend()
    if interpret is None and backend != "tpu":
        return "scan", f"the backend is {backend}, not a TPU"
    return "kernel", "interpreted" if interpret else "compiled for the TPU"


def kda_chunked(q, k, v, g, beta, chunk: int = CHUNK, q_scale: float = 1.0,
                interpret: bool | None = None):
    """o [B, T, H, dv] float32 of the recurrence above from S_0 = 0, for
    the queries ``q * q_scale``.

    q, k [B, T, H, dk], v [B, T, H, dv] (kept in the type they come in,
    bfloat16 activations for one, until a chunk's float32 products),
    g [B, T, H, dk] (log decay, <= 0), beta [B, T, H]. ``T`` need not be
    a multiple of ``chunk``: the tail is padded with tokens that neither
    decay nor write (g = 0, beta = 0, k = 0) and their outputs are
    dropped. Which forward runs is ``kda_path``'s answer; ``interpret``
    is for tests (True: the kernel, interpreted, off the TPU; False: the
    kernel compiled, for a TPU that is described and not attached).
    """
    if chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk} must be a power of two")
    t = q.shape[1]
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    pad = (-t) % chunk
    if pad:
        widths = lambda x: ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)  # noqa: E731
        q, k, v, g, beta = (jnp.pad(x, widths(x)) for x in (q, k, v, g, beta))
    if kda_path(q.shape[-1], v.shape[-1], chunk, interpret)[0] == "kernel":
        o = _kernel_path(q, k, v, g, beta, float(q_scale), bool(interpret))
    else:
        o = _scan_forward(q, k, v, g, beta, chunk, q_scale)
    return o[:, :t]


def kda_recurrent(q, k, v, g, beta):
    """The recurrence itself, token by token (tests and small sizes)."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    q, k, v, g, beta = (f32(x) for x in (q, k, v, g, beta))
    b, _, h, dk = q.shape

    def step(s, xs):
        qt, kt, vt, gt, bt = xs                     # [B, H, .]
        s = jnp.exp(gt)[..., None] * s
        old = jnp.einsum("bhc,bhcd->bhd", kt, s, precision=_HI)
        s = s + jnp.einsum("bhc,bhd->bhcd", kt,
                           bt[..., None] * (vt - old), precision=_HI)
        return s, jnp.einsum("bhc,bhcd->bhd", qt, s, precision=_HI)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)
