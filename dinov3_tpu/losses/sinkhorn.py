"""Distributed Sinkhorn-Knopp centering as global-array math.

The reference implemented this inside ``shard_map`` with explicit
``lax.psum`` over the "dp" axis and an ``init_phase`` escape hatch
(dinov3_jax/loss/dino_clstoken_loss.py:35-62, ibot_patch_loss.py:77-109).
Here the logits are a *global* jit array sharded over the data axes by
GSPMD, so every ``jnp.sum`` is already a cross-device reduction — XLA
inserts the collectives, no axis names, no init-phase special case
(SURVEY.md §7.1).

Padded rows (the tail of the step's compact masked-token buffer,
SURVEY.md §7.3) are handled by ``row_weights``: zero-weight rows
contribute nothing and receive a harmless uniform output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sinkhorn_knopp(
    logits: jnp.ndarray,
    temperature: float | jnp.ndarray,
    n_iterations: int = 3,
    row_weights: jnp.ndarray | None = None,
    reduce_dtype=jnp.float32,
    storage_dtype=None,
    return_factors: bool = False,
):
    """Sinkhorn-normalized teacher targets.

    logits: [B, K] global teacher scores (B = all crops x global batch, or
    the padded masked-token buffer for iBOT).
    row_weights: optional [B] 0/1 validity; the effective sample count is
    ``sum(row_weights)`` (the reference's ``n_masked_patches`` psum).
    storage_dtype: dtype of the materialized [B, K] buffers (the
    normalized-logit iterate and the returned targets). ``None`` keeps
    them in ``reduce_dtype``. bf16 halves the HBM traffic of the
    dominant loss-side tensors (r5 on-chip profile); every logsumexp
    still reduces in ``reduce_dtype`` — the storage read upcasts inside
    the fused reduction, so nothing fp32-sized is materialized.
    Returns [B, K] assignment probabilities (each valid row sums to 1) —
    or, with ``return_factors=True``, the log-domain
    ``SinkhornFactors(xs, r, c, log_B, valid)`` with
    ``q = exp(xs - r - c + log_B)`` left UNmaterialized, for the
    streaming CE engine (losses/streaming.py) to consume tile-by-tile.
    """
    B, K = logits.shape
    NEG = jnp.asarray(-1e30, reduce_dtype)  # "-inf" that stays NaN-free
    # Work entirely in the log domain: the iterations are algebraically
    # identical to the reference's linear-domain ones (division ==
    # logsumexp subtraction) but cannot over/underflow — the reference's
    # raw ``exp(logits/T)`` overflowed for |logits|/T > ~88 and its Q
    # underflowed to all-zero columns at low temperatures.
    #
    # Offset form: after one materialized global normalization the iterate
    # is represented as ``xs - r_i - c_j`` for per-row / per-column offset
    # vectors, so each half-iteration is a read-only reduction over ``xs``
    # instead of a read-modify-write of the [B, K] fp32 buffer — ~40% less
    # HBM traffic for the 65k–262k-prototype heads this normalizes.
    x = logits / jnp.asarray(temperature, logits.dtype)  # [B, K]
    if row_weights is not None:
        valid = row_weights.astype(reduce_dtype) > 0
        B_eff = jnp.maximum(jnp.sum(valid.astype(reduce_dtype)), 1.0)
        log_B = jnp.log(B_eff)
        row_pad = jnp.where(valid, 0.0, NEG)  # [B], -inf on padding rows
    else:
        valid = None
        log_B = jnp.log(jnp.asarray(B, reduce_dtype))
        row_pad = None

    store = storage_dtype or reduce_dtype
    xf = x.astype(reduce_dtype)
    if row_pad is not None:
        xf = xf + row_pad[:, None]
    # One materialized global normalization (brings values to small
    # magnitude, which keeps the offset subtractions below full-precision
    # ulp — iterating offsets against raw logits would re-incur
    # |logits/T|-scale rounding on every pass); everything after is
    # read-only against xs. The normalization itself runs in reduce_dtype
    # (the fp32 intermediates live only inside XLA fusions); only the
    # iterate's storage is ``store``-typed.
    xs = (xf - jax.nn.logsumexp(xf)).astype(store)
    r = jnp.zeros((B, 1), reduce_dtype)   # row offsets
    c = jnp.zeros((1, K), reduce_dtype)   # column offsets
    log_K = jnp.log(jnp.asarray(K, reduce_dtype))
    for _ in range(n_iterations):
        # prototype marginal -> uniform 1/K (reduce over samples)
        c = c + jax.nn.logsumexp(xs - r - c, axis=0, keepdims=True) + log_K
        # sample marginal -> uniform 1/B (reduce over prototypes)
        dr = jax.nn.logsumexp(xs - r - c, axis=1, keepdims=True) + log_B
        if valid is not None:
            # padding rows keep their offset, staying at ~NEG so they
            # contribute nothing to later column reductions
            dr = jnp.where(valid[:, None], dr, 0.0)
        r = r + dr
    if return_factors:
        from dinov3_tpu.losses.streaming import SinkhornFactors

        return SinkhornFactors(
            xs=xs, r=r, c=c,
            log_B=jnp.asarray(log_B, reduce_dtype), valid=valid,
        )
    log_q = xs - r - c  # promotes to reduce_dtype inside the fusion
    q = jnp.exp(log_q + log_B).astype(store)  # each valid row sums to 1
    if valid is not None:
        q = jnp.where(valid[:, None], q, jnp.zeros((), store))
    return q
