"""The gated delta rule in chunks, at either form of its gate: one decay
per key channel (Kimi Delta Attention) or ONE decay a head (Gated
DeltaNet).

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

The two forms of the gate. ``kda_chunked`` reads which delta rule a
call is off the gate's RANK, an operand's shape and no option: g
[B, T, H, dk] is the per-channel gate above, with q, k, v and beta all at
H heads; g [B, T, Hv] is ONE log decay a value head and token (the same
recurrence with a_t a scalar), with q and k at their own Hk key heads,
key head j serving value heads j r .. j r + r - 1, r = Hv / Hk. With one
decay a head e^{G_t - G_s} is one number a token pair, so

    A = strict_lower(k k^T) * D,  P = lower(q k^T) * D,
    D_ts = e^{G_t - G_s} (s <= t), an outer difference of ONE vector,

k k^T and q k^T hold no decay and do not depend on the value head (one
product a KEY head serves its r value heads), the decays to and from
the state scale rows (e^{G_t} (k_t S), not (e^{G_t} k_t) S), and nothing
needs a reference token: below the diagonal every exponent is <= 0 as it
stands. So the scalar gate needs NONE of the halving levels described
under Precision, which are the per-channel gate's alone.

What runs where. ``kda_chunked`` has three paths that share these
definitions, and ``kda_path`` chooses from what it can see, no option or
variable. Where the backend is a TPU (or a test asks for ``interpret``),
d_k and d_v are multiples of 128 and the chunk is 64: the PER-CHANNEL
KERNELS (``kda_chunk_fwd`` / ``kda_chunk_bwd``) for a gate of rank 4;
the SCALAR-GATE KERNELS (``gdn_chunk_fwd`` / ``gdn_chunk_bwd``) for a
gate of rank 3 with one or two value heads a key head. Everywhere else —
CPU runs, narrow test widths, other chunks, other head groupings — the
PLAIN path (``_chunk`` under ``lax.scan``, each chunk's body
rematerialised, its backward JAX's own transposition of the scan), which
a gate of rank 3 reaches as the per-channel case it also is: q and k
repeated over a key head's value heads, the gate broadcast over the key
channels (``_per_channel``; ``kda_recurrent`` takes both forms the same
way). A path is both of its passes: no loop is shared, none chosen apart.

The scalar-gate kernels keep the per-channel kernels' grid, blocks,
state scratch, pair layout, inverse and what the forward rule keeps, and
the same float32 set; their bodies share only the helpers. A pair of
heads is a key head's two value heads (r = 2) or two key heads' (r = 1):
ONE product k [k; q]^T a key head gives both raw planes, ONE masked
exponential [C, 2 C] the pair's two D — the EXPONENT is masked, since
above the diagonal G_t - G_s is positive (63 x 21 nats at the published
initial values) and e^that times 0 is NaN. The backward needs no level
either: with X = dA A + dP P (= dD D), dG_t = sum_s X_ts - sum_s X_st
plus the row scales' shares, dq = (dP D) k and dk = (dP D)^T q +
(dA D + (dA D)^T) k, with a key head's two value heads' planes ADDED
before those products, so dq and dk leave the kernel once, at the key
heads. In units of 64 x 128 x 128 a pair and chunk the forward does 21
products and the backward 25, against the per-channel pair's 32 and 60.
The per-channel kernels, from here on:

The forward kernel (``kda_chunk_fwd``, one ``pallas_call``) takes the
chunk axis as the grid's sequential axis and the sequence as the
parallel one; a grid step is one chunk of every head, worked through a
pair of heads at a time. In VMEM it keeps that chunk of q, k, v (the
activation type), g and o (float32) and every head's [d_v, d_k] float32
state, zeroed at a sequence's first chunk and carried from grid step to
grid step: a state never goes to HBM inside a sequence, unless the pass
is the one that a backward follows (``custom_vjp``'s forward rule), which
also writes each chunk's STARTING state ([n, B, H, d_v, d_k], what the
scan's backward keeps) and the inverse it made ([B, n, H / 2, C, 2 C], a
quarter of the states' bytes: six dependent products the backward then
does not repeat). The blocks are cut from the [B, T, H, d] arrays
as they lie (a chunk is [C * H, d] rows, token-major and head-minor, of
which a head is every H-th: strided loads and stores), so no copy is
made on either side of the call. Under a layer's remat the first pass
runs the kernel without the state output.

The backward kernel (``kda_chunk_bwd``, one ``pallas_call``, the
``custom_vjp``'s backward rule) has the same grid with the chunks LAST
FIRST, the same blocks, and in place of the states their cotangent, a
[d_v, d_k] float32 scratch a head, zero at a sequence's last chunk and
carried towards its first. A grid step makes again, from the chunk's
saved starting state and inverse, what else the forward made (G, A and
P by the same levels, u) and then transposes the definitions above by
hand. The one step that is not a product's transpose is the inverse:
with M = I + Diag(b) A, r = V - (e^G k) S and u = M^-1 (b r),

    dw = M^-T du,   dM = -dw u^T,   dA = Diag(b) dM (below the diagonal),
    db_t = sum_s dM_ts A_ts + dw_t . r_t,   dr = dV = b dw

: two full-width products where the transposed six-factor chain has
twenty [C, C] ones in a dependent line. A level's product is transposed
twice (for its rows and its columns), and every decayed operand x e^E
gives (x e^E) d(x e^E) to dE: E is a difference of two values of G, so
the kernel gathers one dG per token and channel, and dg is its running
sum from the chunk's end. bfloat16 q, k, v get bfloat16 gradients,
rounded and packed in the kernel.

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
The kernels' arithmetic is the plain path's, product for product (but
for the inverse's gradient), in float32 with full-precision products
(Mosaic's ``contract_precision<fp32>``: on the chip within 1.3e-6 of the
token recurrence's output and 4e-6 of its gradients, of each one's
largest entry, as the plain path) and
the same halving levels, backward as forward: no factor above 1 at any
decay, and the state's cotangent float32 like the state. They differ
in the blocking alone: a level is ONE product over the whole chunk,
masked to the level's blocks (12 times the score operations the count
in ``benchmark/lm_flops.py`` needs, at shapes the MXU takes: the
per-channel path's cost alone, the scalar-gate kernels make each score
plane once); the score planes are transposed; beta scales the
right-hand side of T's product and not T's columns; and two heads'
[C, C] planes share a [C, 2 C] plane against block-diagonal operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
KERNEL_NAME = "kda_chunk_fwd"
BACKWARD_KERNEL_NAME = "kda_chunk_bwd"
SCALAR_KERNEL_NAME = "gdn_chunk_fwd"
SCALAR_BACKWARD_KERNEL_NAME = "gdn_chunk_bwd"
_NEG = -1e30
# a grid step holds a chunk of every head twice over (6 MB at 32 heads of
# 128, 12 with what the forward rule keeps, 17 in the backward) beside the
# 2 of the states or of their cotangents: past the 16 MB a kernel gets
# unasked
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),  # sequences, chunks
    vmem_limit_bytes=64 * 1024 * 1024)
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


# --------------------------------------------------- the two kernels
#
# Both work on one (sequence, chunk) a grid step, every head of it, a
# PAIR of heads at a time. q/k/v/g/o refs are [1, C * heads, d] (a chunk
# of the [B, T, H, d] arrays, rows token-major and head-minor); beta is
# [heads / 2, 2 * C], a row a pair (the two heads' chunks side by side);
# a state is [dv, dk]: TRANSPOSED, so that the per-channel decay runs
# along the lanes.
#
# Everything between a pair's loads and its stores is values: the two
# heads' chains are independent until then and the scheduler interleaves
# them. The score planes are kept TRANSPOSED ([s, t]: the streamed
# operand of a level's product is then the 64 columns, not the 128
# stacked rows) and, from the inverse on, the pair's planes sit side by
# side in one [C, 2 * C] plane, which a product takes against a
# block-diagonal [2 * C, 2 * C] operand: full MXU tiles where one head's
# [C, C] would fill a quarter.


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _blocks(x):
    """[X0 | X1] ([C, 2 C]) -> [[X0, 0], [0, X1]]."""
    c = x.shape[0]
    same_half = (_iota((2 * c, 2 * c), 0) < c) == (_iota((2 * c, 2 * c), 1) < c)
    return jnp.where(same_half, jnp.concatenate([x, x], 0), 0.0)


def _turned(x):
    """A row [1, n] stood up as a column [n, 1], or a column laid down
    (through the diagonal: no relayout)."""
    n = max(x.shape)
    return jnp.sum(jnp.where(_iota((n, n), 0) == _iota((n, n), 1), x, 0.0),
                   axis=x.shape.index(n), keepdims=True)


def _pair_planes(ref, pair, heads, rows=CHUNK):
    """Heads ``2 * pair`` and ``2 * pair + 1``'s [rows, d] float32 planes out
    of a block [1, rows * heads, d] whose rows run token-major, head-minor
    (the [B, T, H, d] array as it lies). A float32 block gives a head's
    rows by a strided load; a bfloat16 block packs two rows a 32-bit
    word, which here are the pair's own two heads: one strided load of
    words, the even head in the low halves."""
    at = lambda first, stride: (0, pl.ds(first, rows, stride=stride))  # noqa: E731
    if ref.dtype == jnp.float32:
        return tuple(ref[at(2 * pair + hd, heads)] for hd in (0, 1))
    words = ref.bitcast(jnp.uint32)[at(pair, heads // 2)]
    return (pltpu.bitcast(words << 16, jnp.float32),
            pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32))


def _store_pair(ref, pair, heads, even, odd, rows=CHUNK):
    """``_pair_planes`` the other way: the pair's two [rows, d] float32
    planes into a block of the gradient; into a bfloat16 block rounded to
    nearest even and packed, a word a pair of rows."""
    at = lambda first, stride: (0, pl.ds(first, rows, stride=stride))  # noqa: E731
    if ref.dtype == jnp.float32:
        ref[at(2 * pair, heads)] = even
        ref[at(2 * pair + 1, heads)] = odd
        return

    def rounded(x):                                 # bfloat16 in the high half
        bits = pltpu.bitcast(x, jnp.uint32)
        return bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))

    ref.bitcast(jnp.uint32)[at(pair, heads // 2)] = (
        rounded(even) >> 16) | (rounded(odd) & jnp.uint32(0xFFFF0000))


def _running_sum(x, reverse=False):
    """Along the chunk's tokens (rows), in log2(C) shifted adds; reversed,
    token t gets the sum from t to the chunk's end."""
    c = x.shape[0]
    rows, step = _iota(x.shape, 0), 1
    while step < c:
        x = x + (jnp.where(rows < c - step, pltpu.roll(x, c - step, 0), 0.0)
                 if reverse else
                 jnp.where(rows >= step, pltpu.roll(x, step, 0), 0.0))
        step *= 2
    return x


def _level_mask(c, half):
    """[s, t] entries of a [C, 2 C] plane inside one block of 2 * half."""
    lane, shift = _iota((c, 2 * c), 1), half.bit_length()
    return (_iota((c, 2 * c), 0) >> shift) == ((lane & (c - 1)) >> shift)


def _scores(hd, q, k, big):
    """(G_C on every row, [A^T | P^T] for head 0 of the pair and
    [P^T | A^T] for head 1: the pair's A planes then lie side by side
    with no lane moved, each level's two decay planes). A and P below the
    diagonal, level by level as _decayed_products: ``end`` is G at the
    last token of a row's block of ``half`` tokens; a later half's rows
    decay from the earlier half's end to their token (``lat``), the
    earlier half's columns from their token to that end (``ear``). Every
    level is ONE product over the whole chunk, of which the entries
    inside a block of 2 * half are kept."""
    c = q.shape[0]
    rows = _iota(q.shape, 0)
    first, second = (k, q) if hd == 0 else (q, k)
    end, out, half, decays = big, jnp.zeros((c, 2 * c), jnp.float32), 1, []
    while half < c:
        later = (rows & half) != 0
        lat = jnp.exp(jnp.where(later, big - pltpu.roll(end, half, 0), _NEG))
        ear = jnp.exp(jnp.where(later, _NEG, end - big))
        prod = _dot(k * ear,
                    jnp.concatenate([first * lat, second * lat], 0), _NT)
        out = out + jnp.where(_level_mask(c, half), prod, 0.0)
        decays.append((ear, lat))
        end = jnp.where(later, end, pltpu.roll(end, c - half, 0))
        half *= 2
    return end, out, decays


def _pair_scores(q_scale, heads, pair, *refs):
    """The pair's planes out of ``refs`` (q, k, v, g and whatever else
    comes in such blocks), q scaled and g summed along the chunk, and its
    score planes side by side: ([(q, k, v, G, *others, G_C, decays)] a
    head, [A0^T | A1^T], [P1^T | P0^T] with P's diagonal)."""
    c = CHUNK
    planes = []
    for hd, (q, k, v, g, *others) in enumerate(zip(*(
            _pair_planes(ref, pair, heads) for ref in refs))):
        q, big = q * q_scale, _running_sum(g)
        end, s, decays = _scores(hd, q, k, big)
        planes.append(((q, k, v, big, *others, end, decays), s,
                       jnp.sum(q * k, axis=-1, keepdims=True)))
    (head0, s0, qk0), (head1, s1, qk1) = planes
    srow, lane = _iota((c, 2 * c), 0), _iota((c, 2 * c), 1)
    left = lane < c
    a_t = jnp.where(left, s0, s1)                    # [A0^T | A1^T]
    p_t = jnp.where(left, s1, s0) + jnp.where(       # [P1^T | P0^T]
        srow == (lane & (c - 1)), jnp.where(left, qk1, qk0), 0.0)
    return (head0, head1), a_t, p_t


def _pair_inverse(a_t, b_row):
    """(I + A^T Diag(b))^-1 = (I - L)(I + L^2)(I + L^4)... for the pair,
    [C, 2 C]: a stage squares the power and multiplies it into the
    inverse in ONE product, [power; inverse] against the power's
    blocks."""
    c = a_t.shape[0]
    eye = (_iota((c, 2 * c), 0) == (_iota((c, 2 * c), 1) & (c - 1))
           ).astype(jnp.float32)                     # [I | I]
    strict = a_t * b_row
    inv, power = eye - strict, _dot(strict, _blocks(strict), _NN)
    for _ in range(c.bit_length() - 3):
        both = _dot(jnp.concatenate([power, inv], 0), _blocks(power), _NN)
        inv, power = inv + both[c:], both[:c]
    return inv + _dot(inv, _blocks(power), _NN)


def _fwd_kernel(*refs, q_scale, heads, keep_states):
    """``st_ref`` [heads, dv, dk] holds the states from grid step to grid
    step; with ``keep_states`` (what a backward needs of this pass)
    ``s_ref`` takes each head's state as the chunk finds it and
    ``inv_ref`` [heads / 2, C, 2 C] each pair's inverse."""
    if keep_states:
        q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, inv_ref, st_ref = refs
    else:
        q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref = refs
    c = CHUNK

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    def one_pair(pair, carry):
        planes, a_t, p_t = _pair_scores(
            q_scale, heads, pair, q_ref, k_ref, v_ref, g_ref)
        b_row = b_ref[pl.ds(pair, 1), :]                 # [1, 2 C]
        inv = _pair_inverse(a_t, b_row)
        if keep_states:
            inv_ref[pair] = inv
        states, rhs, out = [], [], []
        for hd, (q, k, v, big, *_) in enumerate(planes):
            st, from_start = st_ref[2 * pair + hd], jnp.exp(big)
            with_state = _dot(jnp.concatenate(
                [k * from_start, q * from_start], 0), st, _NT)
            states.append(st)
            rhs.append(v - with_state[:c])
            out.append(with_state[c:])
        # [u0; u1] = blocks(T) [b0 r0; b1 r1] (beta stood up as a
        # column), then [P1 u1; P0 u0]
        u = _dot(_blocks(inv), _turned(b_row) * jnp.concatenate(rhs, 0), _TN)
        pu = _dot(_blocks(p_t), jnp.concatenate([u[c:], u[:c]], 0), _TN)
        for hd, (q, k, v, big, end, _) in enumerate(planes):
            head = 2 * pair + hd
            o_ref[0, pl.ds(head, c, stride=heads), :] = out[hd] + (
                pu[c:] if hd == 0 else pu[:c])
            if keep_states:
                s_ref[head] = states[hd]
            st_ref[head] = jnp.exp(end[:1]) * states[hd] + _dot(
                u[hd * c:(hd + 1) * c], k * jnp.exp(end - big), _TN)
        return carry

    jax.lax.fori_loop(0, heads // 2, one_pair, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s_ref, inv_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dst_ref, *,
                q_scale, heads):
    """The grid's chunks come LAST FIRST. ``s_ref`` [heads, dv, dk] is
    the state each head's chunk started from and ``inv_ref`` [heads / 2,
    C, 2 C] each pair's inverse (what the forward rule wrote);
    ``dst_ref`` [heads, dv, dk] holds the cotangent of the state the
    chunk ends with, zero at a sequence's last chunk, and leaves this grid
    step as the cotangent of the state it started from.

    First the forward again, less the inverse, P u and the output: G,
    the score planes by the same levels, rhs = v - (k e^G) S and
    u = M^-1 (b rhs), M = I + Diag(b) A. Then, with dS' the cotangent in
    ``dst_ref`` and K' = k e^{G_C - G}:

        du   = P^T do + K' dS'                dP = do u^T (s <= t)
        dw   = M^-T du    dM = -dw u^T    dA = Diag(b) dM (s < t)
        drhs = b dw = dv  dbeta_t = sum_s dM_ts A_ts + dw_t . rhs_t
        [d(q e^G); d(k e^G)] = [do; -drhs] S^T,        dK' = u dS'^T
        dS   = e^{G_C} dS' + [q e^G; k e^G]^T [do; -drhs]

    (the inverse differentiated in closed form: two products where the
    transposed chain has twenty), dA and dP back through the levels, two
    transposed products a level. Every decayed operand x e^E gives
    (x e^E) d(x e^E) to dE; E is a difference of two values of G (a
    level's reference token gets the same sum with both signs: left
    out), so a dG per token and channel is gathered, G_C's share too, and
    dg is its reversed running sum."""
    c = CHUNK

    @pl.when(pl.program_id(1) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    left = _iota((c, 2 * c), 1) < c

    def one_pair(pair, carry):
        planes, a_t, p_t = _pair_scores(
            q_scale, heads, pair, q_ref, k_ref, v_ref, g_ref, do_ref)
        b_row = b_ref[pl.ds(pair, 1), :]                 # [1, 2 C]
        b_col = _turned(b_row)
        inv = _blocks(inv_ref[pair])
        do0, do1 = planes[0][4], planes[1][4]
        # [P1^T do1; P0^T do0]
        pt_do = _dot(_blocks(p_t), jnp.concatenate([do1, do0], 0), _NN)
        rhs, du, decayed = [], [], []
        for hd, (q, k, v, big, do, end, _) in enumerate(planes):
            st, dst = s_ref[2 * pair + hd], dst_ref[2 * pair + hd]
            from_start, to_end = jnp.exp(big), jnp.exp(end - big)
            q_in, k_in, k_out = q * from_start, k * from_start, k * to_end
            rhs.append(v - _dot(k_in, st, _NT))
            du.append((pt_do[c:] if hd == 0 else pt_do[:c])
                      + _dot(k_out, dst, _NT))
            decayed.append((st, dst, from_start, to_end, q_in, k_in, k_out))
        rhs = jnp.concatenate(rhs, 0)
        u = _dot(inv, b_col * rhs, _TN)                  # [u0; u1]
        dw = _dot(inv, jnp.concatenate(du, 0), _NN)      # M^-T du
        drhs = b_col * dw
        # u0 and u1 against [-dw0; do0; do1; -dw1]: rows of u0 give
        # [dM0^T | dP0^T], rows of u1 [dP1^T | dM1^T]: head by head the
        # layout of _scores' planes
        both = _dot(
            u, jnp.concatenate([-dw[:c], do0, do1, -dw[c:]], 0), _NT)
        d0, d1 = both[:c, :2 * c], both[c:, 2 * c:]
        db_ref[pl.ds(pair, 1), :] = jnp.sum(
            jnp.where(left, d0, d1) * a_t, axis=0, keepdims=True) + _turned(
                jnp.sum(dw * rhs, axis=1, keepdims=True))
        d_scores = (jnp.where(left, d0 * b_row, d0),     # [dA0^T | dP0^T]
                    jnp.where(left, d1, d1 * b_row))     # [dP1^T | dA1^T]
        grads = []
        for hd, (q, k, v, big, do, end, levels) in enumerate(planes):
            st, dst, from_start, to_end, q_in, k_in, k_out = decayed[hd]
            u_h, down = u[hd * c:(hd + 1) * c], jnp.concatenate(
                [do, -drhs[hd * c:(hd + 1) * c]], 0)
            d_in = _dot(down, st, _NN)         # [d(q e^G); d(k e^G)]
            d_out = _dot(u_h, dst, _NN)        # d(k e^{G_C - G})
            dst_ref[2 * pair + hd] = jnp.exp(end[:1]) * dst + _dot(
                down, jnp.concatenate([q_in, k_in], 0), _TN)
            # the levels: a level's product (k ear) x [first lat;
            # second lat]^T, transposed twice
            first, second = (k, q) if hd == 0 else (q, k)
            d_col = d_first = d_second = jnp.zeros_like(k)
            half = 1
            for ear, lat in levels:
                d_prod = jnp.where(_level_mask(c, half), d_scores[hd], 0.0)
                d_col = d_col + ear * _dot(d_prod, jnp.concatenate(
                    [first * lat, second * lat], 0), _NN)
                d_rows = _dot(d_prod, k * ear, _TN)
                d_first = d_first + lat * d_rows[:c]
                d_second = d_second + lat * d_rows[c:]
                half *= 2
            diag = jnp.sum(do * u_h, axis=-1, keepdims=True)   # dP_tt
            d_k, d_q = (d_first, d_second) if hd == 0 else (d_second, d_first)
            dq = d_in[:c] * from_start + diag * k + d_q
            dk = d_in[c:] * from_start + d_out * to_end + diag * q \
                + d_col + d_k
            dbig = q_in * d_in[:c] + k_in * d_in[c:] - k_out * d_out \
                + first * d_first + second * d_second - k * d_col
            at_end = jnp.sum(k_out * d_out, axis=0, keepdims=True) \
                + jnp.exp(end[:1]) * jnp.sum(st * dst, axis=0, keepdims=True)
            grads.append((dq * q_scale, dk, drhs[hd * c:(hd + 1) * c],
                          _running_sum(dbig, reverse=True) + at_end))
        for ref, even, odd in zip((dq_ref, dk_ref, dv_ref, dg_ref), *grads):
            _store_pair(ref, pair, heads, even, odd)
        return carry

    jax.lax.fori_loop(0, heads // 2, one_pair, 0)


def _even_heads(*arrays):
    """An odd head count gains a head (axis 2) of zeros: one that neither
    decays nor writes, and whose cotangents are zero."""
    if arrays[0].shape[2] % 2 == 0:
        return arrays
    return tuple(jnp.pad(x, ((0, 0), (0, 0), (0, 1)) + ((0, 0),) * (x.ndim - 3))
                 for x in arrays)


def _pair_rows(x):
    """[B, T, H] (H even) chunk by chunk with a row a pair of heads, the
    two heads' chunks side by side: [B, n, H / 2, 2 C]."""
    b, t, h = x.shape
    return jnp.moveaxis(x.reshape(b, t // CHUNK, CHUNK, h // 2, 2), 2, 4).reshape(
        b, t // CHUNK, h // 2, 2 * CHUNK)


def _token_rows(x):
    """``_pair_rows`` the other way."""
    b, n, pairs, _ = x.shape
    return jnp.moveaxis(x.reshape(b, n, pairs, 2, CHUNK), 4, 2).reshape(
        b, n * CHUNK, 2 * pairs)


def _kernel_operands(q, k, v, g, beta, reverse=False):
    """What both kernels take of the (even-headed) inputs: (the arrays as
    [B, T * H, d] views and a pair's beta chunk by chunk, the block of a
    [B, T * H, d] array, beta's block, the blocks of what the forward
    rule keeps for the backward: the states and the inverses). With
    ``reverse`` grid step m is chunk n - 1 - m."""
    b, t, h, _ = q.shape
    n = t // CHUNK
    chunk = (lambda m: n - 1 - m) if reverse else (lambda m: m)
    q, k, v = (x if x.dtype == jnp.bfloat16 else x.astype(jnp.float32)
               for x in (q, k, v))
    # a chunk of [B, T, H, d] as it lies: [C * H, d], no copy where H
    # fills the sublane tiles (8 rows of float32, 16 of bfloat16)
    rows = lambda x: x.reshape(b, t * h, x.shape[-1])  # noqa: E731
    spec = lambda d: pl.BlockSpec(  # noqa: E731
        (1, CHUNK * h, d), lambda i, m: (i, chunk(m), 0),
        memory_space=pltpu.VMEM)
    b_spec = pl.BlockSpec((None, None, h // 2, 2 * CHUNK),
                          lambda i, m: (i, chunk(m), 0, 0),
                          memory_space=pltpu.VMEM)
    kept_specs = [
        pl.BlockSpec((None, None, h, v.shape[-1], q.shape[-1]),
                     lambda i, m: (chunk(m), i, 0, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, None, h // 2, CHUNK, 2 * CHUNK),
                     lambda i, m: (i, chunk(m), 0, 0, 0),
                     memory_space=pltpu.VMEM)]
    return ((rows(q), rows(k), rows(v), rows(g), _pair_rows(beta)), spec,
            b_spec, kept_specs)


# A ``pallas_call`` traces its kernel body every time it is called, and a
# trace of the step calls these two wrappers six times a KDA layer (the
# pass, the layer's remat and its linearisation, the backward): seconds
# of host time a body at 32 heads, 28 s of a 102 s set-up (PERF.md,
# PR 31). Under ``jit`` every call of one shape shares one trace;
# ``inline`` leaves no call in the program, which is what it was.
@functools.partial(jax.jit, inline=True,
                   static_argnames=("q_scale", "keep_states", "interpret"))
def _kernel_forward(q, k, v, g, beta, q_scale, keep_states, interpret):
    """The forward pass as one ``pallas_call``; T a multiple of CHUNK,
    dk and dv multiples of 128. Returns (o, kept): o [B, T, H, dv]
    float32; kept, of the even head count H' the kernel works on, each
    chunk's STARTING states [n, B, H', dv, dk] (transposed) and inverses
    [B, n, H' / 2, C, 2 C], or None."""
    heads = q.shape[2]
    q, k, v, g, beta = _even_heads(q, k, v, g, beta)
    b, t, h, dk = q.shape
    dv, n = v.shape[-1], t // CHUNK
    operands, spec, b_spec, kept_specs = _kernel_operands(q, k, v, g, beta)
    out_specs = [spec(dv)]
    out_shape = [jax.ShapeDtypeStruct((b, t * h, dv), jnp.float32)]
    if keep_states:
        out_specs += kept_specs
        out_shape += [
            jax.ShapeDtypeStruct((n, b, h, dv, dk), jnp.float32),
            jax.ShapeDtypeStruct((b, n, h // 2, CHUNK, 2 * CHUNK), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, q_scale=q_scale, heads=h,
                          keep_states=keep_states),
        grid=(b, n),
        in_specs=[spec(dk), spec(dk), spec(dv), spec(dk), b_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((h, dv, dk), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=KERNEL_NAME,
    )(*operands)
    o = out[0].reshape(b, t, h, dv)[:, :, :heads]
    return o, (tuple(out[1:]) if keep_states else None)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("q_scale", "interpret"))
def _kernel_backward(q, k, v, g, beta, kept, do, q_scale, interpret):
    """The backward pass as one ``pallas_call`` over the chunks in
    reverse, from what the forward rule kept: the cotangents of q, k, v
    (in their types), g and beta (float32)."""
    heads, types = q.shape[2], (q.dtype, k.dtype, v.dtype)
    q, k, v, g, beta, do = _even_heads(q, k, v, g, beta, do)
    b, t, h, dk = q.shape
    dv, n = v.shape[-1], t // CHUNK
    operands, spec, b_spec, kept_specs = _kernel_operands(
        q, k, v, g, beta, reverse=True)
    # the interpreter cannot store through a block's 32-bit view: there a
    # bfloat16 gradient leaves the kernel as float32 and is rounded after
    like = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, jnp.float32 if interpret else x.dtype)
    *grads, db_rows = pl.pallas_call(
        functools.partial(_bwd_kernel, q_scale=q_scale, heads=h),
        grid=(b, n),
        in_specs=[spec(dk), spec(dk), spec(dv), spec(dk), b_spec, spec(dv),
                  *kept_specs],
        out_specs=[spec(dk), spec(dk), spec(dv), spec(dk), b_spec],
        out_shape=[like(x) for x in operands],
        scratch_shapes=[pltpu.VMEM((h, dv, dk), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=BACKWARD_KERNEL_NAME,
    )(*operands, do.reshape(b, t * h, dv), *kept)
    dq, dk_, dv_, dg = (
        x.reshape(b, t, h, x.shape[-1])[:, :, :heads] for x in grads)
    return (*(x.astype(dt) for x, dt in zip((dq, dk_, dv_), types)), dg,
            _token_rows(db_rows)[:, :, :heads])


def _kernel_pair(forward, backward):
    """``forward`` and ``backward`` (a ``pallas_call`` each) as one
    differentiable function of (q, k, v, g, beta, q_scale, interpret)."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
    def path(q, k, v, g, beta, q_scale, interpret):
        return forward(q, k, v, g, beta, q_scale=q_scale, keep_states=False,
                       interpret=interpret)[0]

    def path_fwd(q, k, v, g, beta, q_scale, interpret):
        o, kept = forward(q, k, v, g, beta, q_scale=q_scale, keep_states=True,
                          interpret=interpret)
        return o, (q, k, v, g, beta, kept)

    def path_bwd(q_scale, interpret, res, do):
        return backward(*res, do, q_scale=q_scale, interpret=interpret)

    # optimize_remat: under a layer's remat the pass that keeps no
    # residuals runs the primal (no state output), not the forward rule
    # with its states thrown away
    path.defvjp(path_fwd, path_bwd, optimize_remat=True)
    return path


_kernel_path = _kernel_pair(_kernel_forward, _kernel_backward)


# ------------------------------------- the two kernels of a scalar gate
#
# ONE log decay a value head and token (g [B, T, Hv]; q and k at Hk key
# heads, Hv = r Hk, r 1 or 2). Grid, blocks, state scratch, what the
# forward rule keeps and the pair layouts are the kernels' above. What
# differs is the decay: e^{G_t - G_s} is one number a token pair, at most
# 1 below the diagonal, so the plane D is ONE masked exponential of an
# outer difference (the EXPONENT is masked: above the diagonal it is
# positive and overflows), k k^T and q k^T are one product a KEY head with
# no decay inside, and the decays to and from the state scale rows.
# g and beta come as a row a pair of value heads ([Hv / 2, 2 C], the two
# heads' chunks side by side), and dg and dbeta leave so.
#
# A loop step is a PAIR OF KEY HEADS (one strided load of bfloat16 words,
# as a pair of heads above) with its r pairs of value heads: at r = 2 a
# key head's two value heads are a pair, at r = 1 the two key heads' own
# are. A pair's chain of products is one dependent line (scores, the
# inverse's six, u, P u: each waits for the last), so a step first loads
# what all its pairs read, then computes them as values, then stores: at
# r = 2 the scheduler has two independent lines to interleave.


def _lane_running_sum(x, reverse=False):
    """``_running_sum`` along the lanes of a pair's row [1, 2 C], inside
    each head's half."""
    n = x.shape[1]
    at, step = _iota(x.shape, 1) & (n // 2 - 1), 1
    while step < n // 2:
        x = x + (jnp.where(at < n // 2 - step, pltpu.roll(x, n - step, 1), 0.0)
                 if reverse else
                 jnp.where(at >= step, pltpu.roll(x, step, 1), 0.0))
        step *= 2
    return x


def _scalar_pairs(step, heads_k, heads, q_scale, q_ref, k_ref):
    """The pairs of value heads of loop step ``step`` (key heads
    ``2 * step`` and ``2 * step + 1``): [(pair, [(q, k)] a key head the
    pair reads, q scaled)]."""
    keys = tuple(zip((x * q_scale for x in _pair_planes(q_ref, step, heads_k)),
                     _pair_planes(k_ref, step, heads_k)))
    if heads == heads_k:
        return [(step, keys)]
    return [(2 * step + j, keys[j:j + 1]) for j in (0, 1)]


def _scalar_scores(keys, g_row):
    """What both scalar kernels make first of a pair, from its key heads'
    planes and its row of g [1, 2 C]: (G as columns [2 C, 1], head 0's
    chunk above head 1's; [A0^T | A1^T]; [P0^T | P1^T] with P's diagonal;
    D^T below the diagonal, the factor of A's raw products, and D^T with
    the diagonal, P's). ONE product a key head, k against [k; q]:
    [k k^T | k q^T], the raw planes transposed, which the pair's two
    decay planes then scale."""
    c = CHUNK
    big = _lane_running_sum(g_row)                      # [1, 2 C]: G_t
    col = _turned(big)                                  # [2 C, 1]: G_s
    srow, lane = _iota((c, 2 * c), 0), _iota((c, 2 * c), 1)
    left, t = lane < c, lane & (c - 1)
    decay = jnp.exp(jnp.where(
        srow <= t, big - jnp.where(left, col[:c], col[c:]), _NEG))
    strict = jnp.where(srow < t, decay, 0.0)
    raw = [_dot(k, jnp.concatenate([k, q], 0), _NT) for q, k in keys]
    swapped = [pltpu.roll(x, c, 1) for x in raw]        # [k q^T | k k^T]
    a_t = jnp.where(left, raw[0], swapped[-1]) * strict
    p_t = jnp.where(left, swapped[0], raw[-1]) * decay
    return col, a_t, p_t, strict, decay


def _scalar_fwd_pair(keys, g_row, b_row, values, states):
    """A pair's forward as values: (the inverse [C, 2 C], the two heads'
    outputs, their states at the chunk's end)."""
    c = CHUNK
    col, a_t, p_t, _, _ = _scalar_scores(keys, g_row)
    inv = _pair_inverse(a_t, b_row)
    from_start = jnp.exp(col)                        # e^{G_t}, a row scale
    both = [jnp.concatenate([k, q], 0) for q, k in keys]
    halves = (slice(0, c), slice(c, 2 * c))
    rhs, out = [], []
    for rows, kq, v, st in zip(halves, both * 2, values, states):
        with_state = _dot(kq, st, _NT)               # [k S; q S]
        rhs.append(v - from_start[rows] * with_state[:c])
        out.append(from_start[rows] * with_state[c:])
    u = _dot(_blocks(inv), _turned(b_row) * jnp.concatenate(rhs, 0), _TN)
    pu = _dot(_blocks(p_t), u, _TN)                  # [P0 u0; P1 u1]
    new = []
    for rows, (_, k), st in zip(halves, keys * 2, states):
        end = col[rows][c - 1:]                      # G_C, [1, 1]
        new.append(jnp.exp(end) * st + _dot(
            jnp.exp(end - col[rows]) * u[rows], k, _TN))
    return inv, [o + pu[rows] for o, rows in zip(out, halves)], new


def _scalar_fwd_kernel(*refs, q_scale, heads_k, heads, keep_states):
    """``_fwd_kernel`` at a scalar gate: ``heads`` value heads on
    ``heads_k`` key heads (as the blocks hold them: ``heads_k`` even,
    ``heads`` once or twice it)."""
    if keep_states:
        q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, inv_ref, st_ref = refs
    else:
        q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    def key_pair(step, carry):
        loaded = [
            (pair, keys, g_ref[pl.ds(pair, 1), :], b_ref[pl.ds(pair, 1), :],
             _pair_planes(v_ref, pair, heads),
             [st_ref[2 * pair + hd] for hd in (0, 1)])
            for pair, keys in _scalar_pairs(
                step, heads_k, heads, q_scale, q_ref, k_ref)]
        done = [(pair, states, _scalar_fwd_pair(keys, g_row, b_row, vs, states))
                for pair, keys, g_row, b_row, vs, states in loaded]
        for pair, states, (inv, out, new) in done:
            if keep_states:
                inv_ref[pair] = inv
            for hd in (0, 1):
                head = 2 * pair + hd
                o_ref[0, pl.ds(head, CHUNK, stride=heads), :] = out[hd]
                if keep_states:
                    s_ref[head] = states[hd]
                st_ref[head] = new[hd]
        return carry

    jax.lax.fori_loop(0, heads_k // 2, key_pair, 0)


def _scalar_bwd_pair(keys, g_row, b_row, values, dos, states, dstates, inv):
    """A pair's backward as values: ([dq] and [dk] a key head of the pair,
    q's still to be scaled; [dv0; dv1]; the rows of dg and dbeta; the two
    heads' state cotangents at the chunk's start).

    ``_bwd_kernel``'s transposition with rhs = v - e^G (k S),
    o = e^G (q S) + P u and K' = e^{G_C - G} k. The score planes are
    A = (k k^T) D below the diagonal and P = (q k^T) D with it, so with
    X = dA A + dP P (= dD D)

        dG_t = sum_s X_ts - sum_s X_st + the row scales' shares,
        dq = (dP D) k,    dk = (dP D)^T q + (dA D + (dA D)^T) k

    : no level, and a key head's two value heads' planes are ADDED before
    these products, so dq and dk leave once a key head. Every row scale s
    gives s ds (a row's dot product) to dG, G_C's share goes to every
    token, and dg is dG's reversed running sum."""
    c = CHUNK
    left = _iota((c, 2 * c), 1) < c
    halves = (slice(0, c), slice(c, 2 * c))
    half = lambda x: pltpu.roll(x, c, 1)  # noqa: E731  the halves swapped
    col, a_t, p_t, strict, decay = _scalar_scores(keys, g_row)
    b_col, inv = _turned(b_row), _blocks(inv)
    from_start = jnp.exp(col)
    # [P0^T do0; P1^T do1]
    pt_do = _dot(_blocks(p_t), jnp.concatenate(dos, 0), _NN)
    rhs, du, scales = [], [], []
    for rows, (_, k), v, st, dst in zip(halves, keys * 2, values, states,
                                        dstates):
        end = col[rows][c - 1:]                      # G_C, [1, 1]
        to_end = jnp.exp(end - col[rows])
        rhs.append(v - from_start[rows] * _dot(k, st, _NT))
        du.append(pt_do[rows] + to_end * _dot(k, dst, _NT))
        scales.append((from_start[rows], to_end, jnp.exp(end)))
    rhs = jnp.concatenate(rhs, 0)
    u = _dot(inv, b_col * rhs, _TN)                  # [u0; u1]
    dw = _dot(inv, jnp.concatenate(du, 0), _NN)      # M^-T du
    drhs = b_col * dw
    # u_h against [-dw_h; do_h]: [dM_h^T | dP_h^T], then pair by pair
    d0, d1 = (_dot(u[rows], jnp.concatenate([-dw[rows], do], 0), _NT)
              for rows, do in zip(halves, dos))
    dm_t = jnp.where(left, d0, half(d1))             # [dM0^T | dM1^T]
    dp_t = jnp.where(left, half(d0), d1)             # [dP0^T | dP1^T]
    db_row = jnp.sum(dm_t * a_t, axis=0, keepdims=True) + _turned(
        jnp.sum(dw * rhs, axis=1, keepdims=True))
    da_t = dm_t * b_row
    x_t = da_t * a_t + dp_t * p_t                    # (dD D)^T
    d_a, d_p = da_t * strict, dp_t * decay           # to the raw planes
    # a head's [(dP D)^T | (dA D)^T], added over a key head's heads
    planes = (jnp.where(left, d_p, half(d_a)), jnp.where(left, half(d_p), d_a))
    if len(keys) == 1:
        planes = (planes[0] + planes[1],)
    dq, dk = [], []
    for (q, k), z in zip(keys, planes):
        rows = _dot(z, k, _TN)                 # [(dP D) k; (dA D) k]
        dq.append(rows[:c])
        dk.append(rows[c:] + _dot(z, jnp.concatenate([q, k], 0), _NN))
    total = lambda x: jnp.sum(  # noqa: E731
        jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)
    dbig, at_end, dstarts = [], [], []
    for hd, (rows, do, st, dst, (in_, out_, whole)) in enumerate(zip(
            halves, dos, states, dstates, scales)):
        key = hd * (len(keys) - 1)
        q, k = keys[key]
        down, in_ = jnp.concatenate([do, -drhs[rows]], 0), jnp.concatenate(
            [in_, in_], 0)
        # [d(q e^G); d(k e^G)] and d(k e^{G_C - G}), with their scales
        d_in = in_ * _dot(down, st, _NN)
        d_out = out_ * _dot(u[rows], dst, _NN)
        dstarts.append(whole * dst + _dot(
            in_ * down, jnp.concatenate([q, k], 0), _TN))
        dq[key] = dq[key] + d_in[:c]
        dk[key] = dk[key] + d_in[c:] + d_out
        lanes = jnp.where(left == (hd == 0), x_t, 0.0)
        dbig.append(jnp.sum(q * d_in[:c] + k * (d_in[c:] - d_out), axis=1,
                            keepdims=True)
                    - jnp.sum(lanes, axis=1, keepdims=True))
        at_end.append(total(k * d_out) + whole * total(st * dst))
    dg = jnp.sum(x_t, axis=0, keepdims=True) + _turned(jnp.concatenate(dbig, 0))
    dg_row = _lane_running_sum(dg, reverse=True) + jnp.where(
        _iota((1, 2 * c), 1) < c, *at_end)
    return dq, dk, drhs, dg_row, db_row, dstarts


def _scalar_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s_ref,
                       inv_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                       dst_ref, *, q_scale, heads_k, heads):
    """``_bwd_kernel`` at a scalar gate (``_scalar_bwd_pair``): the
    chunks last first, ``dst_ref`` the states' cotangents; dq and dk
    leave at the key heads, a pair of them a loop step."""
    c = CHUNK

    @pl.when(pl.program_id(1) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    def key_pair(step, carry):
        heads_of = lambda ref, pair: [ref[2 * pair + hd] for hd in (0, 1)]  # noqa: E731
        loaded = [
            (pair, keys, g_ref[pl.ds(pair, 1), :], b_ref[pl.ds(pair, 1), :],
             _pair_planes(v_ref, pair, heads), _pair_planes(do_ref, pair, heads),
             heads_of(s_ref, pair), heads_of(dst_ref, pair), inv_ref[pair])
            for pair, keys in _scalar_pairs(
                step, heads_k, heads, q_scale, q_ref, k_ref)]
        done = [(pair, _scalar_bwd_pair(*operands))
                for pair, *operands in loaded]
        dq, dk = [], []
        for pair, (dq_, dk_, drhs, dg_row, db_row, dstarts) in done:
            dq, dk = dq + dq_, dk + dk_
            _store_pair(dv_ref, pair, heads, drhs[:c], drhs[c:])
            dg_ref[pl.ds(pair, 1), :] = dg_row
            db_ref[pl.ds(pair, 1), :] = db_row
            for hd in (0, 1):
                dst_ref[2 * pair + hd] = dstarts[hd]
        _store_pair(dq_ref, step, heads_k, *(x * q_scale for x in dq))
        _store_pair(dk_ref, step, heads_k, *dk)
        return carry

    jax.lax.fori_loop(0, heads_k // 2, key_pair, 0)


def _scalar_heads(q, k, *values):
    """(q, k) at an even number of key heads and ``values`` (arrays with
    the value heads on axis 2) at as many times more as they came with:
    an odd count gains a key head of zeros and its value heads (heads that
    neither decay nor write, and whose cotangents are zero)."""
    if q.shape[2] % 2 == 0:
        return (q, k), values
    more = lambda x, n: jnp.pad(  # noqa: E731
        x, ((0, 0), (0, 0), (0, n)) + ((0, 0),) * (x.ndim - 3))
    per_key = values[0].shape[2] // q.shape[2]
    return (more(q, 1), more(k, 1)), tuple(more(x, per_key) for x in values)


def _scalar_operands(q, k, v, g, beta, reverse=False):
    """``_kernel_operands`` at a scalar gate: q and k have their own head
    count, g goes as beta does."""
    b, t = q.shape[:2]
    n, hv = t // CHUNK, v.shape[2]
    chunk = (lambda m: n - 1 - m) if reverse else (lambda m: m)
    q, k, v = (x if x.dtype == jnp.bfloat16 else x.astype(jnp.float32)
               for x in (q, k, v))
    rows = lambda x: x.reshape(b, t * x.shape[2], x.shape[-1])  # noqa: E731
    spec = lambda x: pl.BlockSpec(  # noqa: E731
        (1, CHUNK * x.shape[2], x.shape[-1]), lambda i, m: (i, chunk(m), 0),
        memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((None, None, hv // 2, 2 * CHUNK),
                            lambda i, m: (i, chunk(m), 0, 0),
                            memory_space=pltpu.VMEM)
    kept_specs = [
        pl.BlockSpec((None, None, hv, v.shape[-1], q.shape[-1]),
                     lambda i, m: (chunk(m), i, 0, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, None, hv // 2, CHUNK, 2 * CHUNK),
                     lambda i, m: (i, chunk(m), 0, 0, 0),
                     memory_space=pltpu.VMEM)]
    return ((rows(q), rows(k), rows(v), _pair_rows(g), _pair_rows(beta)),
            [spec(q), spec(k), spec(v), row_spec, row_spec], spec, kept_specs)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("q_scale", "keep_states", "interpret"))
def _scalar_forward(q, k, v, g, beta, q_scale, keep_states, interpret):
    """``_kernel_forward`` at a scalar gate: q, k [B, T, Hk, dk],
    v [B, T, Hv, dv], g and beta [B, T, Hv], Hv one or two times Hk."""
    heads = v.shape[2]
    (q, k), (v, g, beta) = _scalar_heads(q, k, v, g, beta)
    b, t, h, dv = v.shape
    dk, n = q.shape[-1], t // CHUNK
    operands, in_specs, spec, kept_specs = _scalar_operands(q, k, v, g, beta)
    o = jax.ShapeDtypeStruct((b, t * h, dv), jnp.float32)
    out_specs, out_shape = [spec(v)], [o]
    if keep_states:
        out_specs += kept_specs
        out_shape += [
            jax.ShapeDtypeStruct((n, b, h, dv, dk), jnp.float32),
            jax.ShapeDtypeStruct((b, n, h // 2, CHUNK, 2 * CHUNK), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_scalar_fwd_kernel, q_scale=q_scale,
                          heads_k=q.shape[2], heads=h,
                          keep_states=keep_states),
        grid=(b, n),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((h, dv, dk), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=SCALAR_KERNEL_NAME,
    )(*operands)
    o = out[0].reshape(b, t, h, dv)[:, :, :heads]
    return o, (tuple(out[1:]) if keep_states else None)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("q_scale", "interpret"))
def _scalar_backward(q, k, v, g, beta, kept, do, q_scale, interpret):
    """``_kernel_backward`` at a scalar gate: dq and dk at the key heads,
    dg and dbeta [B, T, Hv]."""
    heads_k, heads, types = q.shape[2], v.shape[2], (q.dtype, k.dtype, v.dtype)
    (q, k), (v, g, beta, do) = _scalar_heads(q, k, v, g, beta, do)
    b, t, h, dv = v.shape
    operands, in_specs, spec, kept_specs = _scalar_operands(
        q, k, v, g, beta, reverse=True)
    # (the interpreter and a block's 32-bit view: ``_kernel_backward``)
    like = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, jnp.float32 if interpret else x.dtype)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_scalar_bwd_kernel, q_scale=q_scale,
                          heads_k=q.shape[2], heads=h),
        grid=(b, t // CHUNK),
        in_specs=[*in_specs, spec(do), *kept_specs],
        out_specs=in_specs,
        out_shape=[like(x) for x in operands],
        scratch_shapes=[pltpu.VMEM((h, dv, q.shape[-1]), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=SCALAR_BACKWARD_KERNEL_NAME,
    )(*operands, do.reshape(b, t * h, dv), *kept)
    dq, dk_, dv_ = (
        x.reshape((b, t, -1, x.shape[-1]))[:, :, :n].astype(dt)
        for x, n, dt in zip((dq, dk_, dv_), (heads_k, heads_k, heads), types))
    return (dq, dk_, dv_, *(_token_rows(x)[:, :, :heads] for x in (dg, dbeta)))


_scalar_path = _kernel_pair(_scalar_forward, _scalar_backward)


def kda_path(dk: int, dv: int, chunk: int = CHUNK,
             interpret: bool | None = None,
             gate_heads: tuple[int, int] | None = None) -> tuple[str, str]:
    """(path, why) ``kda_chunked`` takes at these widths on this backend:
    ("kernel", ...) or ("scan", the reason it is not the kernel).
    ``gate_heads`` is (key heads, value heads) of a call with ONE decay a
    value head (a gate of rank 3), None of one with a decay a channel."""
    if chunk != CHUNK:
        return "scan", f"chunk {chunk} is not the kernel's {CHUNK}"
    if dk % 128 or dv % 128:
        return "scan", f"dk {dk}, dv {dv} are not multiples of 128"
    if gate_heads is not None and gate_heads[1] not in (
            gate_heads[0], 2 * gate_heads[0]):
        return "scan", ("{1} value heads on {0} key heads: the scalar-gate "
                        "kernels take one or two a key head").format(*gate_heads)
    backend = jax.default_backend()
    if interpret is None and backend != "tpu":
        return "scan", f"the backend is {backend}, not a TPU"
    how = "interpreted" if interpret else "compiled for the TPU"
    return "kernel", how if gate_heads is None else f"scalar gate, {how}"


def _per_channel(q, k, v, g):
    """(q, k, g) as the per-channel definitions above take them: a gate of
    rank 3 (ONE decay a value head) stands for all its key channels, and
    key head j serves value heads j r .. j r + r - 1."""
    if g.ndim == 4:
        return q, k, g
    r = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(x, r, axis=2) for x in (q, k))
    return q, k, jnp.broadcast_to(g[..., None], g.shape + q.shape[-1:])


def kda_chunked(q, k, v, g, beta, chunk: int = CHUNK, q_scale: float = 1.0,
                interpret: bool | None = None):
    """o [B, T, H, dv] float32 of the recurrence above from S_0 = 0, for
    the queries ``q * q_scale``.

    The gate's rank says which delta rule: g [B, T, H, dk] is a log decay
    (<= 0) a key channel, with q, k [B, T, H, dk], v [B, T, H, dv] and
    beta [B, T, H]; g [B, T, Hv] is ONE log decay a value head (Gated
    DeltaNet), with q, k [B, T, Hk, dk] at the key heads, Hv a multiple
    of Hk, v [B, T, Hv, dv] and beta [B, T, Hv] (the gradients of q and k
    come back at the key heads, g's [B, T, Hv]). q, k, v are kept in the
    type they come in, bfloat16 activations for one, until a chunk's
    float32 products. ``T`` need not be a multiple of ``chunk``: the tail
    is padded with tokens that neither decay nor write (g = 0, beta = 0,
    k = 0) and their outputs are dropped. Which path runs, forward and
    backward (a kernel pair, or the scan and its transposition after the
    scalar gate is repeated and broadcast), is ``kda_path``'s answer;
    ``interpret`` is for tests (True: the kernels, interpreted, off the
    TPU; False: the kernels compiled, for a TPU that is described and not
    attached).
    """
    if chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk} must be a power of two")
    scalar = g.ndim == 3
    if scalar and v.shape[2] % q.shape[2]:
        raise ValueError(
            f"{v.shape[2]} value heads on {q.shape[2]} key heads")
    t = q.shape[1]
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    pad = (-t) % chunk
    if pad:
        widths = lambda x: ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)  # noqa: E731
        q, k, v, g, beta = (jnp.pad(x, widths(x)) for x in (q, k, v, g, beta))
    gate_heads = (q.shape[2], v.shape[2]) if scalar else None
    if kda_path(q.shape[-1], v.shape[-1], chunk, interpret,
                gate_heads)[0] == "kernel":
        o = (_scalar_path if scalar else _kernel_path)(
            q, k, v, g, beta, float(q_scale), bool(interpret))
    else:
        q, k, g = _per_channel(q, k, v, g)
        o = _scan_forward(q, k, v, g, beta, chunk, q_scale)
    return o[:, :t]


def kda_recurrent(q, k, v, g, beta):
    """The recurrence itself, token by token (tests and small sizes), at
    either form of the gate (``kda_chunked``'s words)."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    q, k, v, g, beta = (f32(x) for x in (q, k, v, g, beta))
    q, k, g = _per_channel(q, k, v, g)
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
