"""Streaming prototype-axis target/CE engine.

The r5 on-chip profile (``PROFILE_r05.json``, docs/PERFORMANCE.md) puts
10.2% of the ViT-L step in fp32 passes over the ``[*, 65536]`` teacher
targets: the softmax-center/Sinkhorn targets were materialized as full
``[rows, K]`` probability buffers in HBM that the DINO/iBOT
cross-entropies then re-read. GSPMD places and shards those buffers but
cannot delete them — avoiding the materialization is algorithmic, and at
the K=262144 ViT-7B recipes the fp32 target buffer alone is multi-GB.

This engine computes the CE directly from the teacher *logits* in ONE
pass over K-tiles (``lax.scan`` + ``dynamic_slice`` on the prototype
axis). Per tile it accumulates, in fp32:

- the teacher's centered-softmax statistics (online running max /
  sum-exp, flash-attention style rescaling),
- the student ``logsumexp`` statistics (same online scheme),
- the ``<q, x>`` cross-term of the logit-einsum CE, rescaled alongside
  the teacher max so the normalization divides out exactly at the end.

so the ``[rows, K]`` fp32 target buffer NEVER exists in HBM for the
softmax-center path. For the Sinkhorn path the iterate ``xs`` (stored in
``compute_precision.target_dtype``) is unavoidable — the Sinkhorn
iterations themselves need it — but the *materialized q* is not: the CE
consumes the log-domain factors ``(xs, r, c)`` (bf16/storage-typed in,
fp32 accumulators) and ``q`` is reconstructed inside the fusion that
reads it. The DINO pairs do so tile-by-tile in the scan (a ``[T*B, K]``
q would be an operand of their einsum); the iBOT rows need no tiles at
all: row-aligned ``dot``, ``qsum`` and ``lse`` are plain reductions over
the whole planes, whose fusions hold ``q`` and the student's exponentials
(PR 36: the scan's pinned tile copies cost more than they guarded, 3.04
against 2.04 ms at ``[1920, 65536]`` on a v5e).

Autodiff. Gradients flow only through the student logits (teacher
logits come from stop_gradient'ed params). The two Sinkhorn CEs (the
recipes' centering) carry a ``jax.custom_vjp``: the forward rule is the
primal and keeps ``lse``, the backward rule is the closed form
``dx = (d_dot * q + d_lse * softmax(x / tau)) / tau`` written ONCE over
the whole plane — read ``xs``, read ``x``, write ``dx``; no scan, no
``[rows, K]`` carry, no zero plane for ``xs`` (PR 36; JAX's transposition
of the checkpointed scan carried the cotangent plane through a ``while``
and wrote it a tile at a time, 3.1 x the forward's time). For the iBOT
rows neither ``q`` nor the softmax is a value of its own: ONE loop fusion
writes ``dx`` (an ``optimization_barrier`` keeps it out of the head's
backward matmuls, see ``_row_ce_sinkhorn_bwd``); for the DINO pairs the
compiler keeps the T teacher crops' ``q`` as ``[B, K]`` fp32 values
shared by the S student crops (T*B rows against the gradient's S*B: it
will not duplicate an exponential into S readers). The two softmax-center
CEs (no recipe and no benchmark cell runs them) keep the older scheme:
the scan body is wrapped in ``jax.checkpoint`` so the backward pass
REcomputes each tile's weights instead of saving them — the saved
residuals are the per-iteration carries (``[S,T,B]``-sized statistics),
not ``[rows, K]`` buffers.

Equivalence with the materialized oracle (``dino_loss`` /
``ibot_patch_loss_masked`` over ``softmax_center_teacher`` /
``sinkhorn_knopp`` outputs) is pinned by tests/test_streaming_targets.py
for both centering modes and both target dtypes; the oracle path stays
selectable with ``loss.streaming_targets=false``.

Sharding note: the whole-plane reductions and the backward rules are
elementwise and reduce ops, which GSPMD partitions over a sharded
prototype axis as it does any other; so it does the ``dynamic_slice`` of
the scans that are left: with prototype-sharded heads (tensor-axis
"vocab") the partitioner resolves the slice and correctness holds (pinned
by the 8/16-device dryrun programs); pick ``loss.k_tile`` a multiple of
``K / tensor_axis`` there so tiles stay shard-aligned.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class SinkhornFactors(NamedTuple):
    """Log-domain factorization of Sinkhorn targets:
    ``q = exp(xs - r - c + log_B)`` (zero on invalid rows).

    xs: [R, K] globally-normalized logits, storage-typed (target_dtype);
    r: [R, 1] fp32 row offsets; c: [1, K] fp32 column offsets;
    log_B: fp32 scalar (log of the effective row count);
    valid: [R] bool or None (fixed-capacity padding mask).
    """

    xs: jnp.ndarray
    r: jnp.ndarray
    c: jnp.ndarray
    log_B: jnp.ndarray
    valid: jnp.ndarray | None


def choose_k_tile(K: int, cap: int) -> int:
    """Largest divisor of K that is <= cap (the flash_block convention:
    the config value is an upper bound, the actual tile always divides)."""
    t = max(1, min(int(cap) if cap else K, K))
    while K % t:
        t -= 1
    return t


@jax.custom_vjp
def _pin(x):
    """``optimization_barrier`` with an autodiff rule (absent in older
    jax): the cotangent tile is pinned the same way, so neither the
    forward nor the backward program can hoist a full-K buffer. The
    Sinkhorn CEs differentiate by their own closed-form rules, so of
    them only the DINO pairs' FORWARD scan pins tiles; the autodiff rule
    serves the softmax-center scans."""
    return jax.lax.optimization_barrier(x)


def _pin_fwd(x):
    return _pin(x), None


def _pin_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_pin.defvjp(_pin_fwd, _pin_bwd)


def _slice_k(arr, i, tk, axis):
    """Tile ``arr`` along the prototype axis, pinned inside the loop.

    The optimization barrier blocks XLA's loop-invariant code motion
    from commuting per-tile converts with the slice
    (``convert(slice(x))`` -> ``slice(convert(x))`` + hoist), which
    would re-materialize the full [rows, K] fp32 buffer this engine
    exists to avoid (observed on XLA:CPU without the barrier: the
    hoisted f32 logits buffer rode the scan carry).
    """
    return _pin(jax.lax.dynamic_slice_in_dim(arr, i * tk, tk, axis=axis))


# ---------------- pairwise (DINO CLS: every student crop x every
# teacher crop) ----------------


def _pair_ce_softmax_stream(student_logits, t_logits, center, t_temp,
                            s_temp, tk):
    """[S,B,K] student logits x [T,B,K] teacher logits -> [S,T] pair CE,
    teacher targets = softmax((l - center)/t_temp), never materialized."""
    S, B, K = student_logits.shape
    T = t_logits.shape[0]
    f32 = jnp.float32
    n = K // tk
    c_full = center.reshape(-1).astype(f32)  # [K]

    def body(carry, i):
        m_t, s_t, dot, m_s, s_s = carry
        yt = (_slice_k(t_logits, i, tk, 2).astype(f32)
              - _slice_k(c_full, i, tk, 0)) / t_temp            # [T,B,tk]
        # mirrors the oracle: x is divided in its storage dtype
        # (dino_loss: x = student_logits / student_temp), then promoted
        # fp32 inside the reductions
        xt = _slice_k(student_logits, i, tk, 2) / jnp.asarray(
            s_temp, student_logits.dtype)                        # [S,B,tk]
        xt_f = xt.astype(f32)
        new_m_t = jnp.maximum(m_t, yt.max(-1))
        alpha = jnp.exp(m_t - new_m_t)                           # [T,B]
        w = jnp.exp(yt - new_m_t[..., None])                     # [T,B,tk]
        s_t = s_t * alpha + w.sum(-1)
        dot = dot * alpha[None] + jnp.einsum(
            "sbk,tbk->stb", xt_f, w, preferred_element_type=f32)
        new_m_s = jnp.maximum(m_s, xt_f.max(-1))
        beta = jnp.exp(m_s - new_m_s)
        s_s = s_s * beta + jnp.exp(xt_f - new_m_s[..., None]).sum(-1)
        return (new_m_t, s_t, dot, new_m_s, s_s), None

    init = (
        jnp.full((T, B), -jnp.inf, f32), jnp.zeros((T, B), f32),
        jnp.zeros((S, T, B), f32),
        jnp.full((S, B), -jnp.inf, f32), jnp.zeros((S, B), f32),
    )
    (m_t, s_t, dot, m_s, s_s), _ = jax.lax.scan(
        jax.checkpoint(body), init, jnp.arange(n))
    lse_s = m_s + jnp.log(s_s)                                   # [S,B]
    # softmax targets sum to exactly 1 per row by construction
    return lse_s.sum(-1)[:, None] - (dot / s_t[None]).sum(-1)    # [S,T]


def _sinkhorn_log_q(factors: SinkhornFactors):
    """fp32 ``log q = xs - r - c + log_B`` over the whole ``[R, K]``
    plane: an expression for a consumer's fusion, never a buffer (``xs``
    is read in its storage type and upcast inside)."""
    f32 = jnp.float32
    return (factors.xs.astype(f32) - factors.r.astype(f32)
            - factors.c.astype(f32) + factors.log_B.astype(f32))


def _scaled_f32(student_logits, s_temp):
    """``x / tau`` in fp32. Mirrors the oracle: x is divided in its
    storage dtype (dino_loss: x = student_logits / student_temp), then
    promoted fp32 inside the reductions."""
    return (student_logits / jnp.asarray(
        s_temp, student_logits.dtype)).astype(jnp.float32)


def _sinkhorn_dx(student_logits, s_temp, q_term, d_lse, lse):
    """The closed-form cotangent of the student logits for both Sinkhorn
    CEs: ``(q_term + d_lse * softmax(x / tau)) / tau``, where ``q_term``
    is the caller's ``d_dot``-weighted ``q``. ONE elementwise expression
    over the plane, fp32 until the divide by tau, which mirrors the
    forward's (storage dtype)."""
    dt = student_logits.dtype
    softmax = jnp.exp(_scaled_f32(student_logits, s_temp) - lse[..., None])
    return (q_term + d_lse[..., None] * softmax).astype(dt) / jnp.asarray(
        s_temp, dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _pair_parts_sinkhorn_stream(student_logits, factors: SinkhornFactors,
                                s_temp, tk):
    """[S,B,K] student logits x Sinkhorn factor tiles ->
    ``(dot [S,T,B], qsum [T,B], lse [S,B])``, one K-tile scan.

    ``q`` tiles are reconstructed as ``exp(xs - r - c + log_B)`` from the
    storage-typed (bf16 under target_dtype=bf16) ``xs`` tiles with fp32
    accumulation; the materialized ``[T*B, K]`` q buffer never exists.
    """
    S, B, K = student_logits.shape
    R = factors.xs.shape[0]
    T = R // B
    f32 = jnp.float32
    n = K // tk
    r = factors.r.astype(f32)
    log_B = factors.log_B.astype(f32)

    def body(carry, i):
        dot, qsum, m_s, s_s = carry
        lq = (_slice_k(factors.xs, i, tk, 1).astype(f32) - r
              - _slice_k(factors.c, i, tk, 1).astype(f32) + log_B)
        q = jnp.exp(lq).reshape(T, B, tk)
        xt_f = _scaled_f32(_slice_k(student_logits, i, tk, 2), s_temp)
        dot = dot + jnp.einsum(
            "sbk,tbk->stb", xt_f, q, preferred_element_type=f32)
        qsum = qsum + q.sum(-1)
        new_m_s = jnp.maximum(m_s, xt_f.max(-1))
        beta = jnp.exp(m_s - new_m_s)
        s_s = s_s * beta + jnp.exp(xt_f - new_m_s[..., None]).sum(-1)
        return (dot, qsum, new_m_s, s_s), None

    init = (
        jnp.zeros((S, T, B), f32), jnp.zeros((T, B), f32),
        jnp.full((S, B), -jnp.inf, f32), jnp.zeros((S, B), f32),
    )
    (dot, qsum, m_s, s_s), _ = jax.lax.scan(body, init, jnp.arange(n))
    return dot, qsum, m_s + jnp.log(s_s)


def _pair_parts_fwd(student_logits, factors, s_temp, tk):
    out = _pair_parts_sinkhorn_stream(student_logits, factors, s_temp, tk)
    return out, (student_logits, factors, out[2])


def _pair_parts_bwd(s_temp, tk, res, cts):
    """``dx_s = (sum_t d_dot[s,t] q_t + d_lse_s softmax(x_s / tau)) /
    tau`` over the whole ``[S,B,K]`` plane. ``qsum``'s cotangent reaches
    the teacher's factors alone, which take no gradient (None: no zero
    plane is written for ``xs``)."""
    student_logits, factors, lse = res
    d_dot, _, d_lse = cts
    B = student_logits.shape[1]
    T = factors.xs.shape[0] // B
    lq = _sinkhorn_log_q(factors).reshape(T, B, -1)
    # T is the number of teacher crops (2): a sum of broadcasts, which
    # stays elementwise, where an einsum would be a 2-deep contraction
    q_term = sum(d_dot[:, t, :, None] * jnp.exp(lq[t])[None]
                 for t in range(T))
    return _sinkhorn_dx(student_logits, s_temp, q_term, d_lse, lse), None


_pair_parts_sinkhorn_stream.defvjp(_pair_parts_fwd, _pair_parts_bwd)


def _pair_ce_sinkhorn_stream(student_logits, factors: SinkhornFactors,
                             s_temp, tk):
    """[S,B,K] student logits x Sinkhorn factors -> [S,T] pair CE."""
    dot, qsum, lse_s = _pair_parts_sinkhorn_stream(
        student_logits, factors, s_temp, tk)
    # truncated Sinkhorn rows sum to ~1, not exactly 1: accumulate qsum
    # like the oracle does
    corr = jnp.einsum("sb,tb->st", lse_s, qsum)
    return corr - dot.sum(-1)


def pair_ce_from_spec(student_logits, spec, student_temp: float = 0.1,
                      k_tile: int = 0):
    """[S,B,K] student logits x a teacher-target spec -> [S,T] pair CE.

    spec kinds (built by SSLMetaArch.get_teacher_output):
      {"kind": "probs", "probs": [T,B,K]}                 materialized oracle
      {"kind": "softmax_center", "logits": [T,B,K],
       "center": [1,K], "temp": scalar}                   streaming
      {"kind": "sinkhorn", "factors": SinkhornFactors}    streaming
    """
    kind = spec["kind"]
    if kind == "probs":
        from dinov3_tpu.losses.dino_loss import dino_pair_ce

        return dino_pair_ce(student_logits, spec["probs"],
                            student_temp=student_temp)
    K = student_logits.shape[-1]
    tk = choose_k_tile(K, k_tile)
    if kind == "softmax_center":
        return _pair_ce_softmax_stream(
            student_logits, spec["logits"], spec["center"], spec["temp"],
            student_temp, tk)
    if kind == "sinkhorn":
        return _pair_ce_sinkhorn_stream(
            student_logits, spec["factors"], student_temp, tk)
    raise ValueError(f"unknown teacher-target spec kind {kind!r}")


# ---------------- row-aligned (iBOT: student masked token i x teacher
# masked token i) ----------------


def _row_ce_softmax_stream(student_logits, t_logits, center, t_temp,
                           s_temp, tk):
    """[M,K] x [M,K] -> (dot, qsum, lse) per row, streaming."""
    M, K = student_logits.shape
    f32 = jnp.float32
    n = K // tk
    c_full = center.reshape(-1).astype(f32)

    def body(carry, i):
        m_t, s_t, dot, m_s, s_s = carry
        yt = (_slice_k(t_logits, i, tk, 1).astype(f32)
              - _slice_k(c_full, i, tk, 0)) / t_temp             # [M,tk]
        xt = _slice_k(student_logits, i, tk, 1) / jnp.asarray(
            s_temp, student_logits.dtype)
        xt_f = xt.astype(f32)
        new_m_t = jnp.maximum(m_t, yt.max(-1))
        alpha = jnp.exp(m_t - new_m_t)
        w = jnp.exp(yt - new_m_t[:, None])
        s_t = s_t * alpha + w.sum(-1)
        dot = dot * alpha + (xt_f * w).sum(-1)
        new_m_s = jnp.maximum(m_s, xt_f.max(-1))
        beta = jnp.exp(m_s - new_m_s)
        s_s = s_s * beta + jnp.exp(xt_f - new_m_s[:, None]).sum(-1)
        return (new_m_t, s_t, dot, new_m_s, s_s), None

    z = jnp.zeros((M,), f32)
    ninf = jnp.full((M,), -jnp.inf, f32)
    (m_t, s_t, dot, m_s, s_s), _ = jax.lax.scan(
        jax.checkpoint(body), (ninf, z, z, ninf, z), jnp.arange(n))
    return dot / s_t, jnp.ones((M,), f32), m_s + jnp.log(s_s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _row_ce_sinkhorn_stream(student_logits, factors: SinkhornFactors,
                            s_temp):
    """[M,K] x Sinkhorn factors -> (dot, qsum, lse) per row, as reductions
    over the whole plane: ``q`` and the student's exponentials live inside
    the reduce fusions (``xs`` read in its storage type, fp32 sums), so
    there is no tile to cut and nothing to pin. Differentiated by the
    closed form below."""
    xt_f = _scaled_f32(student_logits, s_temp)
    q = jnp.exp(_sinkhorn_log_q(factors))
    return ((xt_f * q).sum(-1), q.sum(-1),
            jax.scipy.special.logsumexp(xt_f, axis=-1))


def _row_ce_sinkhorn_fwd(student_logits, factors, s_temp):
    out = _row_ce_sinkhorn_stream(student_logits, factors, s_temp)
    return out, (student_logits, factors, out[2])


def _row_ce_sinkhorn_bwd(s_temp, res, cts):
    """``dx = (d_dot q + d_lse softmax(x / tau)) / tau`` over the whole
    ``[M, K]`` plane: read ``xs``, read ``x``, write ``dx``. Padding rows,
    the overflow NaN and the ``1 / n_images`` scale arrive in the
    cotangents; ``qsum``'s cotangent reaches the teacher's factors alone,
    which take no gradient (None: no zero plane is written for ``xs``).

    The barrier makes ``dx`` a value: one loop fusion under the loss's
    scope writes it (in the matmuls' bf16 where the head computes in
    bf16). Without it XLA rebuilds the expression, two exponentials an
    element, as a producer inside BOTH matmuls of the head's backward: no
    faster at 1,920 rows (67.14 against 66.97 ms a ViT-S step), and the
    ViT-L step 5.2 ms slower (175.26 against 170.06): with those matmuls
    the TPU scheduler stops hoisting the backbones' weight converts and
    their prefetches (v5e, PR 36, ``PERF.md`` section 6)."""
    student_logits, factors, lse = res
    d_dot, _, d_lse = cts
    q_term = d_dot[:, None] * jnp.exp(_sinkhorn_log_q(factors))
    return jax.lax.optimization_barrier(
        _sinkhorn_dx(student_logits, s_temp, q_term, d_lse, lse)), None


_row_ce_sinkhorn_stream.defvjp(_row_ce_sinkhorn_fwd, _row_ce_sinkhorn_bwd)


def ibot_loss_from_spec(student_logits, spec, masks_weight, n_images: int,
                        student_temp: float = 0.1, k_tile: int = 0):
    """iBOT masked-token CE against a teacher-target spec ([M,K] rows).

    Padding rows carry ``masks_weight == 0`` so their (well-defined but
    meaningless) streaming CE contributes nothing — same contract as the
    materialized path, where their q rows are zeroed instead.
    """
    from dinov3_tpu.losses.ibot_loss import ibot_patch_loss_from_parts

    kind = spec["kind"]
    if kind == "probs":
        from dinov3_tpu.losses.ibot_loss import ibot_patch_loss_masked

        return ibot_patch_loss_masked(
            student_logits, spec["probs"], masks_weight, n_images,
            student_temp=student_temp)
    if kind == "softmax_center":
        tk = choose_k_tile(student_logits.shape[-1], k_tile)
        dot, qsum, lse = _row_ce_softmax_stream(
            student_logits, spec["logits"], spec["center"], spec["temp"],
            student_temp, tk)
    elif kind == "sinkhorn":
        dot, qsum, lse = _row_ce_sinkhorn_stream(
            student_logits, spec["factors"], student_temp)
    else:
        raise ValueError(f"unknown teacher-target spec kind {kind!r}")
    return ibot_patch_loss_from_parts(dot, qsum, lse, masks_weight,
                                      n_images)
