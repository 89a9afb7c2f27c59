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
strict part is nilpotent), and five products with the state. The state
is carried from chunk to chunk by ``lax.scan``; each chunk's body is
rematerialised, so the backward pass keeps one state per chunk and the
inputs, and recomputes the rest.

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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64
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


def kda_chunked(q, k, v, g, beta, chunk: int = CHUNK, q_scale: float = 1.0):
    """o [B, T, H, dv] float32 of the recurrence above from S_0 = 0, for
    the queries ``q * q_scale``.

    q, k [B, T, H, dk], v [B, T, H, dv] (kept in the type they come in,
    bfloat16 activations for one, until a chunk's float32 products),
    g [B, T, H, dk] (log decay, <= 0), beta [B, T, H]. ``T`` need not be
    a multiple of ``chunk``: the tail is padded with tokens that neither
    decay nor write (g = 0, beta = 0, k = 0) and their outputs are
    dropped.
    """
    if chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk} must be a power of two")
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    pad = (-t) % chunk
    if pad:
        widths = lambda x: ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)  # noqa: E731
        q, k, v, g, beta = (jnp.pad(x, widths(x)) for x in (q, k, v, g, beta))
    n = (t + pad) // chunk
    # [n, B, C, ...]: the scan's leading axis is the chunk
    chunks = tuple(
        jnp.moveaxis(x.reshape((b, n, chunk) + x.shape[2:]), 1, 0)
        for x in (q, k, v, g, beta))
    body = jax.checkpoint(lambda s, xs: _chunk(s, *xs, q_scale))
    _, o = jax.lax.scan(body, jnp.zeros((b, h, dk, dv), jnp.float32), chunks)
    o = jnp.moveaxis(o, 0, 1).reshape(b, n * chunk, h, dv)
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
