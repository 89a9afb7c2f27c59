"""The learned indexer of a sparse-attention layer (the "lightning
indexer" of DeepSeek-V3.2-Exp's report, as ``models/decoder.py DSAMixer``
uses it): which ``topk`` keys each query keeps, and the loss that trains
the indexer towards the main attention's own distribution over them.

For one sequence, q^I [T, H_I, d_I], ONE key head k^I [T, d_I] and head
weights a [T, H_I]:

    I[t, s] = sum_j a[t, j] * ReLU(q^I[t, j] . k^I[s]),      s <= t

(the products in the operands' type with float32 accumulation; the ReLU,
the weighting and the sum over heads float32 elementwise: a product of
float32 operands would be rounded on the TPU). Exact zeros are made +0.0.

**The selection** of query t is the ``min(t + 1, topk)`` largest I[t, s]
over s <= t, a tie at the threshold going to the lower s (the order of
``lax.top_k``). It is found EXACTLY and without a sort: float32 scores
map one-to-one onto int32 keys of the same order (``ordered_key``), the
k-th largest key of a row is built bit by bit from the top — 32 passes
that each count the row's keys at or above a candidate — and a row whose
threshold is tied more often than it has places left also gets the
position of its last kept tie (``lax.cond``: rows of distinct scores pay
nothing for it). Two int32 a query, ``(threshold key, last tied key
kept)``, say the whole selection; ``selection_plane`` turns them into
the [T, T] plane with the score planes again and two compares.

**The index loss** is the KL divergence from the main attention's
distribution over the selected keys, averaged over its heads and
detached, to the indexer's softmax over the same keys:

    p[t, s] = (1 / H) sum_i P_i[t, s],   P_i[t, .] = softmax_{S_t}(q_i[t] . k_g(i)[s] / sqrt(d))
    L = (1 / T) sum_t sum_{s in S_t} p[t, s] (log p[t, s] - log softmax_{S_t}(I[t, .])[s])

Two paths make it, chosen by what the attention core hands on. Where the
core ran the kernels it returns its rows' log-sum-exp over the selected
keys beside its output, and ``index_loss`` is ONE kernel a pass
(``ops/causal_attention.py index_loss_tiles``: a causal tile's target,
index scores, KL and closed-form gradient are made in VMEM and nothing as
wide as the keys leaves it); where the core ran the plain tiles it hands
on None and the loss goes by strips in plain XLA, each strip's target made
where the strip is. ``index_loss`` is a ``custom_vjp`` of ONE output (a
rematerialised layer drops or keeps it whole): its forward rule computes
the loss AND its gradient on q^I, k^I and a (what crosses the layer's
backward is three small arrays, never a [T, T] plane); the selection and
the main attention's q, k and log-sum-exp take no gradient.

Everything in plain XLA goes by strips of ``chunk`` queries against the keys up to
the strip's last (``q_chunk_size`` / ``kv_chunk_size`` of the published
``sa_config`` read as this tile: no effect on the mathematics); strips
are grouped ``group`` at a time under one key extent, so a group is ONE
program (``lax.scan``) and the work above the diagonal that a static
extent costs is bounded by the group's height.

The step's scopes (``models/decoder.py``): ``select_thresholds`` opens
``dsa_index`` around its score planes and ``dsa_select`` around the
counting passes, ``selection_plane`` opens ``dsa_index``, ``index_loss``
opens ``dsa_index_loss``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INT_MIN = np.int32(-(2 ** 31))
CHUNK = 512   # queries a strip (sa_config.q_chunk_size)
GROUP = 8     # strips that share one key extent


def _flip_negatives(bits):
    """The low 31 bits of the negative words turned over: its own inverse."""
    return bits ^ ((bits >> 31) & np.int32(0x7FFFFFFF))


def ordered_key(x):
    """float32 -> int32 with ``key(a) < key(b)`` iff ``a < b`` (-0.0 below
    +0.0, NaNs at the ends)."""
    return _flip_negatives(
        jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32))


def key_to_float(k):
    """``ordered_key``'s inverse."""
    return jax.lax.bitcast_convert_type(
        _flip_negatives(k.astype(jnp.int32)), jnp.float32)


def kth_largest_key(keys, k):
    """The ``k[r]``-th largest of each row of int32 ``keys`` [R, K]
    (1 <= k[r] <= K), exactly: the largest v with count(keys >= v) >= k,
    built from the sign bit down in 32 counting passes."""
    def bit(i, v):
        cand = v + jax.lax.shift_left(np.int32(1), 31 - i)   # wraps at i = 0
        enough = jnp.sum((keys >= cand[:, None]).astype(jnp.int32), -1) >= k
        return jnp.where(enough, cand, v)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.full(keys.shape[:1], INT_MIN, jnp.int32))


def select_rows(keys, k):
    """``(threshold key, last tied position kept)`` [R] int32 each of the
    rows' ``k`` largest keys, ties to the lower position."""
    width = keys.shape[-1]
    v = kth_largest_key(keys, k)
    above = jnp.sum((keys > v[:, None]).astype(jnp.int32), -1)
    tied = keys == v[:, None]
    need = k - above                       # places left for the tied keys

    def cut(_):
        # the position of the ``need``-th tied key: the columns before it
        # are those whose running count of ties is short of ``need``
        run = jnp.cumsum(tied.astype(jnp.int32), axis=-1)
        return jnp.sum((run < need[:, None]).astype(jnp.int32), -1)

    return v, jax.lax.cond(
        jnp.any(jnp.sum(tied.astype(jnp.int32), -1) > need), cut,
        lambda _: jnp.full(v.shape, width, jnp.int32), None)


def selected(keys, v, last, causal):
    """The [R, K] plane of the pairs ``select_rows`` kept."""
    col = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    v, last = v[:, None], last[:, None]
    return causal & ((keys > v) | ((keys == v) & (col <= last)))


def index_scores(qi, ki, a):
    """I [R, K] float32 of q^I [R, H_I, d_I], k^I [K, d_I], a [R, H_I]."""
    z = jnp.einsum("rhd,kd->rhk", qi, ki, preferred_element_type=jnp.float32)
    s = jnp.sum(jax.nn.relu(z) * a.astype(jnp.float32)[:, :, None], axis=1)
    return jnp.where(s == 0.0, 0.0, s)


def _strip_keys(qi, ki, a, start):
    """(int32 keys with the pairs above the diagonal at INT_MIN, the
    causal plane) of the strip whose first query is ``start``."""
    scores = index_scores(qi, ki, a)
    row = start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    causal = col <= row
    return jnp.where(causal, ordered_key(scores), INT_MIN), causal


def _kept(start, rows: int, topk: int):
    """min(t + 1, topk) for the ``rows`` queries from ``start`` on."""
    return jnp.minimum(start + jnp.arange(rows) + 1, topk)


def _sequences(fn, args):
    """``fn`` over the sequences of a batch, one after the other; ONE
    sequence is called as it is (``lax.map`` would stack its planes into a
    second copy)."""
    if jax.tree.leaves(args)[0].shape[0] == 1:
        return jax.tree.map(lambda x: x[None], fn(jax.tree.map(
            lambda x: x[0], args)))
    return jax.lax.map(fn, args)


def _layout(n: int, chunk: int, group: int) -> list:
    """[(first query, strips, rows a strip, key extent), ...]: the groups
    of strips of one sequence of ``n`` tokens."""
    rows = next(r for r in range(min(chunk, n), 0, -1) if n % r == 0)
    strips = n // rows
    return [(g * rows, min(group, strips - g), rows,
             min((g + group) * rows, n)) for g in range(0, strips, group)]


def _strips(x, first, strips, rows):
    return x[first:first + strips * rows].reshape(
        (strips, rows) + x.shape[1:])


@functools.partial(jax.jit, inline=True, static_argnames=(
    "topk", "chunk", "group"))
def select_thresholds(qi, ki, a, topk: int, chunk: int = CHUNK,
                      group: int = GROUP):
    """[B, T] int32 x 2: every query's ``(threshold key, last tied
    position kept)`` over its ``min(t + 1, topk)`` best keys. No
    gradient (the operands are read detached)."""
    qi, ki, a = (jax.lax.stop_gradient(x) for x in (qi, ki, a))
    n = qi.shape[1]

    def sequence(args):
        qi, ki, a = args
        vs, lasts = [], []
        for first, strips, rows, extent in _layout(n, chunk, group):
            def strip(xs, extent=extent):
                q_s, a_s, start = xs
                with jax.named_scope("dsa_index"):
                    keys, _ = _strip_keys(q_s, ki[:extent], a_s, start)
                with jax.named_scope("dsa_select"):
                    return select_rows(keys, _kept(start, q_s.shape[0], topk))

            v, last = jax.lax.map(strip, (
                _strips(qi, first, strips, rows), _strips(a, first, strips, rows),
                first + rows * jnp.arange(strips)))
            vs.append(v.reshape(-1))
            lasts.append(last.reshape(-1))
        return jnp.concatenate(vs), jnp.concatenate(lasts)

    return _sequences(sequence, (qi, ki, a))


def _target(q, k, sel, scale):
    """p [R, K] float32: the mean over the query heads of each head's
    softmax over the selected keys. q [R, h, d], k [K, hk, d]."""
    r, h, d = q.shape
    hk = k.shape[1]
    qg = jnp.moveaxis(q.reshape(r, hk, h // hk, d), 1, 0)      # [hk, R, g, d]

    def head(total, xs):
        q_j, k_j = xs
        z = jnp.einsum("rgd,kd->grk", q_j, k_j,
                       preferred_element_type=jnp.float32) * scale
        z = jnp.where(sel[None], z, -jnp.inf)
        return total + jnp.sum(jax.nn.softmax(z, axis=-1), axis=0), None

    total, _ = jax.lax.scan(head, jnp.zeros(sel.shape, jnp.float32),
                            (qg, jnp.moveaxis(k, 1, 0)))
    return total / h


@functools.partial(jax.jit, inline=True, static_argnames=(
    "topk", "chunk", "group"))
def selection_plane(qi, ki, a, thr, last, topk: int, chunk: int = CHUNK,
                    group: int = GROUP):
    """(the selection [B, T, T] int8 — 1 where query t keeps key s —, the
    number of queries whose kept keys are not min(t + 1, topk)) from
    ``select_thresholds``'s two int32 a query: the score planes again and
    two compares, no counting pass. No gradient."""
    qi, ki, a = (jax.lax.stop_gradient(x) for x in (qi, ki, a))
    n = qi.shape[1]

    def sequence(args):
        qi, ki, a, thr, last = args
        planes, excess = [], jnp.zeros((), jnp.int32)
        for first, strips, rows, extent in _layout(n, chunk, group):
            def strip(xs, extent=extent):
                q_s, a_s, thr_s, last_s, start = xs
                keys, causal = _strip_keys(q_s, ki[:extent], a_s, start)
                sel = selected(keys, thr_s, last_s, causal)
                bad = jnp.sum(jnp.sum(sel.astype(jnp.int32), -1)
                              != _kept(start, q_s.shape[0], topk))
                return jnp.pad(sel.astype(jnp.int8),
                               ((0, 0), (0, n - extent))), bad

            cut = lambda x: _strips(x, first, strips, rows)  # noqa: E731
            sel, bad = jax.lax.map(strip, (
                cut(qi), cut(a), cut(thr), cut(last),
                first + rows * jnp.arange(strips)))
            planes.append(sel.reshape(strips * rows, n))
            excess = excess + jnp.sum(bad)
        return jnp.concatenate(planes), excess

    with jax.named_scope("dsa_index"):
        plane, excess = _sequences(sequence, (qi, ki, a, thr, last))
    return plane, jnp.sum(excess)


def _strip_loss(qi, ki, a, sel, p):
    """The sum over the strip's queries of KL(p || softmax_S(I)); ``p`` is
    read where ``sel`` is set and nowhere else (what stands elsewhere, a
    number or not, reaches neither the loss nor the gradient)."""
    scores = index_scores(qi, ki, a)
    logq = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
    kept = sel & (p > 0)
    p = jnp.where(kept, p, 1.0)
    return jnp.sum(jnp.where(kept, p * (jnp.log(p) - jnp.where(sel, logq, 0.0)),
                             0.0))


def _index_loss(qi, ki, a, plane, q, k, lse, chunk, group, interpret,
                with_grad):
    """The loss and, ``with_grad``, its gradient on (qi, ki, a): by
    ``ops/causal_attention.py index_loss_tiles`` where the core handed on
    its rows' log-sum-exp (``lse``: the core took the kernels) and the
    widths fit, else one pass over every sequence's strips in plain XLA,
    each strip's target made where the strip is and read at once. No
    [T, T] float32 plane exists on either path."""
    from dinov3_tpu.ops import causal_attention as kernels

    if lse is not None and kernels.index_loss_fits(q.shape, qi.shape):
        with jax.named_scope("dsa_index_loss"):
            return kernels.index_loss_tiles(
                qi, ki, a, plane, q, k, lse, with_grad=with_grad,
                interpret=bool(interpret))
    b, n = qi.shape[:2]
    scale, norm = q.shape[-1] ** -0.5, 1.0 / (b * n)

    def sequence(args):
        qi, ki, a, plane, q, k = args
        loss = jnp.zeros((), jnp.float32)
        dqs, das = [], []
        dki = jnp.zeros(ki.shape, jnp.float32)
        for first, strips, rows, extent in _layout(n, chunk, group):
            count = strips * rows

            def strip(carry, xs, extent=extent):
                loss, dki = carry
                q_i, a_s, sel, q_s = xs
                sel = sel[:, :extent] != 0
                p_s = jax.lax.stop_gradient(
                    _target(q_s, k[:extent], sel, scale))

                def f(q_i, k_i, a_s):
                    return norm * _strip_loss(q_i, k_i, a_s, sel, p_s)

                if not with_grad:
                    return (loss + f(q_i, ki[:extent], a_s), dki), ()
                part, (dq, dk, da) = jax.value_and_grad(f, argnums=(0, 1, 2))(
                    q_i, ki[:extent], a_s)
                return (loss + part,
                        dki.at[:extent].add(dk.astype(jnp.float32))), (dq, da)

            cut = lambda x: _strips(x, first, strips, rows)  # noqa: E731
            (loss, dki), out = jax.lax.scan(
                strip, (loss, dki), (cut(qi), cut(a), cut(plane), cut(q)))
            if with_grad:
                dqs.append(out[0].reshape((count,) + qi.shape[1:]))
                das.append(out[1].reshape((count,) + a.shape[1:]))
        grads = ((jnp.concatenate(dqs), dki.astype(ki.dtype), jnp.concatenate(das))
                 if with_grad else ())
        return loss, grads

    with jax.named_scope("dsa_index_loss"):
        loss, grads = _sequences(sequence, (qi, ki, a, plane, q, k))
    return jnp.sum(loss), grads


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def index_loss(qi, ki, a, plane, q, k, lse=None, chunk: int = CHUNK,
               group: int = GROUP, interpret: bool = False):
    """L, the mean over sequences and queries of KL(p || softmax_S(I)).

    qi [B, T, H_I, d_I], ki [B, T, d_I], a [B, T, H_I]: the indexer's, the
    only operands that take a gradient; ``plane``: ``selection_plane``'s;
    q [B, T, h, d], k [B, T, hk, d]: the main attention's, read detached;
    ``lse`` [B, h, T]: their rows' log-sum-exp over the selected keys, as
    the attention core hands it on where it ran the kernels
    (``ops/attention.py causal_blockwise_attention`` under the same
    ``plane``): the whole loss is then one kernel a pass; None: the plain
    strips."""
    return _index_loss(qi, ki, a, plane, q, k, lse, chunk, group, interpret,
                       False)[0]


def _index_loss_fwd(qi, ki, a, plane, q, k, lse, chunk, group, interpret):
    return _index_loss(qi, ki, a, plane, q, k, lse, chunk, group, interpret,
                       True)


def _index_loss_bwd(chunk, group, interpret, grads, ct):
    # the selection, q, k and their log-sum-exp take no gradient
    return (*((g * ct).astype(g.dtype) for g in grads), None, None, None, None)


# optimize_remat: under a layer's remat the pass that keeps no residuals
# runs the primal (no gradient made), not the forward rule
index_loss.defvjp(_index_loss_fwd, _index_loss_bwd, optimize_remat=True)


def pack_selection(plane):
    """[..., T, T] int8 -> [..., T, ceil(T / 8)] uint8, bit 7 first
    (``numpy.unpackbits``'s order): what a reference is handed."""
    return jnp.packbits(plane.astype(jnp.uint8), axis=-1)
