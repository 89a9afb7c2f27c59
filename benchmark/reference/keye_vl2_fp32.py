"""The plain reference of the ``keye_vl2`` decoder's training step
(Keye-VL-2.0's language model on text tokens): forward, the next-token
loss and the index loss, gradient (``jax.grad``), clip and AdamW in
float32 under ``jax.default_matmul_precision("highest")``. It imports
nothing of the program and shares no algorithm with it where the program
has one of its own: the index scores and both softmaxes go over whole rows
of keys, a block of queries at a time, with k and v repeated for every
query head of a group; the selection is ``lax.top_k`` on the row; the
rotary embedding is written out from sines and cosines; the experts are a
``lax.scan`` over the ones held.

Source: ``config.json`` of Kwai-Keye/Keye-VL-2.0-30B-A3B for every size
(``model_type`` KeyeVL2; the language model's keys are Qwen3-MoE's, and
``sa_config`` is the indexer's), Qwen3-MoE's released modelling code for
the block and DeepSeek-V3.2-Exp's report and code for the indexer and its
loss; what the config's keys do not settle is listed under ``assumed`` in
the configuration's file and marked (assumed) below.

**Symbols.** x_t in R^D is a layer's input at token t (from 0), n(x) =
RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale, eps 1e-6, the scale from
ones (not zero-centred). sg = stop_gradient. No bias but LayerNorm's.

**The layer**, for one sequence x of [T, D] (D 2048):

1.  h = n1(x). q = W_q h -> [T, 32, 128], k = W_k h, v = W_v h ->
    [T, 4, 128]; q = n_q(q), k = n_k(k): RMSNorm over the 128 channels of
    every head, one scale vector each (Qwen3-MoE's q_norm / k_norm:
    assumed, the config has no key for them); q_t, k_t rotated over the
    whole head, rot(z)_t = z * cos(t f) + [-z_2 ; z_1] * sin(t f),
    z = [z_1 ; z_2] halves of d/2, f_j = theta^(-2j/d), theta 1e7 (text
    tokens: the three M-RoPE position ids are equal and ``mrope_section``
    collapses to this). Query head i reads key/value head g(i) = i // 8.
2.  The indexer reads h' = sg(h): q^I = W^I_q h' -> [T, 16, 64];
    k^I = LayerNorm_64(W^I_k h') -> [T, 64], ONE key head (scale and bias,
    eps 1e-6: assumed); a_t = W^I_w h' * 16^-1/2 * 64^-1/2 -> [T, 16];
    q^I and k^I rotated whole, theta 1e7 (assumed: DeepSeek's code rotates
    its index heads' rotary part; here the head is 64 wide and the main
    heads rotate whole).
        I[t, s] = sum_j a[t, j] * ReLU(q^I[t, j] . k^I[s]),    s <= t
    No Hadamard turn and no fp8.
3.  S_t = the ``topk`` (2,048) largest I[t, s] over s <= t: all of them
    while t < topk; a tie at the threshold goes to the lower s
    (``lax.top_k``). ``q_chunk_size`` / ``kv_chunk_size`` (512) are read
    as a tile of step 2, with no effect on the mathematics (assumed).
4.  o[t, i] = sum_{s in S_t} P_i[t, s] v[s, g(i)], P_i[t, .] = softmax
    over S_t of q[t, i] . k[s, g(i)] / sqrt(128): one selection a query,
    shared by the 32 heads. x' = x + W_o o.
5.  The index loss (the sparse stage of DeepSeek-V3.2-Exp):
        p[t, s] = sg((1 / 32) sum_i P_i[t, s]),    s in S_t
        L^I = (1 / T) sum_t sum_{s in S_t} p[t, s] (log p[t, s]
                                    - log softmax_{S_t}(I[t, .])[s])
6.  The routed layer, Qwen3-MoE's: r = W_r g, g = n2(x'); C = the top_k
    (8) largest of r over all 128; w = softmax(r[C]) (softmax over all,
    the 8 largest, renormalised); f_e(g) = W3_e (SiLU(a) * b), [a ; b] =
    W12_e g, width 768; x'' = x' + sum over e in C held here of w_e f_e(g).
    No shared expert, no balancing loss in the step.
7.  The step minimises L_LM + sum over layers of L^I (weight 1: assumed).
    By the two stop-gradients L_LM reaches every leaf but the indexer's
    (W^I_q, W^I_k, the LayerNorm, W^I_w) and L^I reaches those alone; no
    gradient crosses the selection. Left out: the dense warm-up stage of
    DeepSeek-V3.2-Exp's recipe (main model frozen, dense attention, the
    indexer alone trained), which is a second recipe, not a second model;
    the vision tower (no sizes in the published config).

This shard holds the experts ``[first, first + held)``; what the others
would add is left out.

``choices`` hands C in from outside and ``selections`` hands S in
(packed bits, ``numpy.unpackbits``'s order): seed-made routers and
indexers put many of the k-th and (k+1)-th values within rounding of each
other, so a program in another precision picks differently for a share of
the tokens and pairs, and a reference that is to be laid against it
follows ITS choice. The share of C the reference's own router agrees with
and the share of the program's selected pairs, in rows t >= topk, that
the reference's own indexer selects too are returned beside.

**Loss.** L_LM: mean over sequences b and positions t < T-1 of
logsumexp(z_bt) - z_bt[token_{b,t+1}], z = W_head n(x) over the
vocabulary held (a slice of the published one is a smaller vocabulary).
L^I of a batch is the mean over its sequences.

**Step.** Global-norm clip of the whole gradient, then AdamW: m, v
moments with bias correction, p <- p - lr (m^ / (sqrt(v^) + eps) + wd p),
wd 0 on every norm's scale and on LayerNorm's bias.

Weights, one dict: ``embed`` [V, D], ``head`` [D, V], ``norm`` [D],
``layers``: a list of {``norm1``, ``norm2``, ``mixer``: {``wq``, ``wk``,
``wv``, ``wo``, ``q_norm``, ``k_norm`` [d]; the indexer's ``wiq`` [D,
16 * 64], ``wik`` [D, 64], ``ik_scale``, ``ik_bias`` [64], ``wiw``
[D, 16]}, ``ffn``: {``router`` [D, E], ``w12`` [held, D, 2F], ``w3``
[held, F, D]}}.

``variant`` makes the controls of the configuration's ``check``.
``"bf16"`` is the nearest precision below the one the configuration
states: the configuration runs bfloat16 matmuls and activations and keeps
a float32 set (the router, both softmaxes, the index scores past their
products, the norms' statistics, the rotary turn, both losses); the
control lowers that whole set to bfloat16 too — every layer and the head
take their input and their weights rounded to bfloat16 and compute in it;
gradients come back in float32 to float32 master weights, clip and AdamW,
as the configuration states for those. ``"no_select"`` is a planted
fault: dense causal attention, the mechanism left out (S_t = every key up
to t, in the index loss too). ``"no_index_loss"`` is another: L^I is
left out of what the step minimises (the indexer untrained).
``"drop_expert"`` is a third, the last held expert left out.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NO_DECAY = ("norm", "norm1", "norm2", "q_norm", "k_norm", "ik_scale", "ik_bias")
VARIANTS = ("fp32", "bf16", "no_select", "no_index_loss", "drop_expert")


@dataclasses.dataclass(frozen=True)
class Shape:
    """What the equations need beyond the weights' own shapes (the
    ``shape`` group of the configuration's file)."""

    layers: tuple             # (("dsa", "moe"), ...)
    heads: int
    kv_heads: int
    rope_theta: float
    index_heads: int
    index_topk: int
    top_k: int
    first_expert: int         # the experts held: [first, first + held)
    eps: float = 1e-6

    @classmethod
    def from_config(cls, group: dict) -> "Shape":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in group.items() if k in fields}
        kw["layers"] = tuple(tuple(x) for x in kw["layers"])
        return cls(**kw)


@dataclasses.dataclass(frozen=True)
class Recipe:
    """The recipe's numbers (``configs/train/keye_vl2_ep8.yaml``)."""

    base_lr: float = 3e-4
    min_lr: float = 3e-5
    warmup_epochs: int = 10
    epochs: int = 100
    epoch_length: int = 1250
    weight_decay: float = 0.1
    weight_decay_end: float = 0.1
    clip_grad: float = 1.0
    beta1: float = 0.9
    beta2: float = 0.95
    adam_eps: float = 1e-8

    @classmethod
    def from_config(cls, group: dict) -> "Recipe":
        return cls(**{k: type(getattr(cls, k))(v) for k, v in group.items()
                      if k in cls.__dataclass_fields__})

    def schedule(self, it: int) -> dict:
        """Linear warm-up then cosine for the rate, cosine for the decay."""
        total = self.epochs * self.epoch_length
        warm = self.warmup_epochs * self.epoch_length

        def cosine(start, end, i, n):
            return end + 0.5 * (start - end) * (1.0 + math.cos(math.pi * i / n))

        lr = (self.base_lr * it / (warm - 1) if it < warm
              else cosine(self.base_lr, self.min_lr, it - warm, total - warm))
        wd = cosine(self.weight_decay, self.weight_decay_end, it, total)
        return {"lr": np.float32(lr), "weight_decay": np.float32(wd)}


# ---- the layers

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rotate(z, theta: float):
    """z [B, T, H, d], token t turned by t * theta^(-2j/d) on the channel
    pair (j, j + d/2); in the type z comes in."""
    t, d = z.shape[1], z.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = (jnp.concatenate([f(angle), f(angle)], -1)[None, :, None, :]
                .astype(z.dtype) for f in (jnp.cos, jnp.sin))
    z1, z2 = jnp.split(z, 2, axis=-1)
    return z * cos + jnp.concatenate([-z2, z1], -1) * sin


def attention(x, m, s: Shape, selection, variant: str, block: int = 512):
    """(W_o o [1, T, D], L^I, the pairs of ``selection`` in rows t >= topk
    that the own indexer selects too, those pairs) for ONE sequence: x
    [1, T, D] the normed input, ``selection`` [1, T, ceil(T / 8)] uint8
    packed bits or None (the own indexer's)."""
    bsz, t, _ = x.shape
    assert bsz == 1, "one sequence a call"
    h, hk, hi = s.heads, s.kv_heads, s.index_heads
    q = (x @ m["wq"]).reshape(bsz, t, h, -1)
    k = (x @ m["wk"]).reshape(bsz, t, hk, -1)
    v = (x @ m["wv"]).reshape(bsz, t, hk, -1)
    d, g = q.shape[-1], h // hk
    q = rotate(rms_norm(q, m["q_norm"], s.eps), s.rope_theta)[0]
    k = rotate(rms_norm(k, m["k_norm"], s.eps), s.rope_theta)[0]
    v = v[0]
    hb = jax.lax.stop_gradient(x)
    qi = (hb @ m["wiq"]).reshape(bsz, t, hi, -1)
    di = qi.shape[-1]
    ki = layer_norm(hb @ m["wik"], m["ik_scale"], m["ik_bias"], s.eps)
    a = (hb @ m["wiw"])[0] * jnp.asarray((hi * di) ** -0.5, x.dtype)
    qi = rotate(qi, s.rope_theta)[0]
    ki = rotate(ki[:, :, None, :], s.rope_theta)[0, :, 0]
    keep = min(s.index_topk, t)
    key = jnp.arange(t)[None, :]

    @jax.checkpoint
    def group(qb, kj, vj, sel):
        """The g query heads [R, g, d] that read one key/value head
        [T, d], which is written out once for each of them: (their
        outputs [R, g, d], the sum of their softmaxes over the selected
        keys [R, T], detached)."""
        kj, vj = (jnp.repeat(u[:, None], g, axis=1) for u in (kj, vj))
        z = jnp.einsum("qhd,khd->hqk", qb, kj) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(sel[None], z, -jnp.inf), -1)
        return (jnp.einsum("hqk,khd->qhd", p, vj),
                jax.lax.stop_gradient(jnp.sum(p.astype(jnp.float32), 0)))

    @jax.checkpoint
    def rows(args):
        """One block of queries against the whole row of keys."""
        qb, qib, ab, first, bits = args
        # (the rows padded on past the last token stand at the last token)
        at = jnp.minimum(first + jnp.arange(qb.shape[0]), t - 1)[:, None]
        causal = key <= at
        score = jnp.sum(jax.nn.relu(jnp.einsum("qhd,kd->qhk", qib, ki))
                        * ab[:, :, None], axis=1)               # I [R, T]
        value, idx = jax.lax.top_k(
            jnp.where(causal, score, -jnp.inf).astype(jnp.float32), keep)
        own = jnp.zeros(causal.shape, bool).at[
            jnp.arange(qb.shape[0])[:, None], idx].set(value > -jnp.inf)
        if variant == "no_select":
            sel = causal
        elif bits is None:
            sel = own
        else:
            sel = jnp.unpackbits(bits, axis=-1)[:, :t].astype(bool) & causal
        real = (first + jnp.arange(qb.shape[0]) < t)[:, None]
        sel = jnp.where(real, sel, causal)
        late = sel & real & (at >= s.index_topk)
        agree = jnp.stack([jnp.sum(late & own), jnp.sum(late)])
        outs, total = [], jnp.zeros(sel.shape, jnp.float32)
        for j in range(hk):
            o, p = group(qb[:, j * g:(j + 1) * g], k[:, j], v[:, j], sel)
            outs.append(o)
            total = total + p
        p = total / h
        logq = jax.nn.log_softmax(
            jnp.where(sel, score, -jnp.inf).astype(jnp.float32), -1)
        kept = sel & (p > 0)
        kl = jnp.sum(jnp.where(kept, p * (jnp.log(jnp.where(kept, p, 1.0))
                                          - jnp.where(sel, logq, 0.0)), 0.0), -1)
        return (jnp.concatenate(outs, 1),
                jnp.sum(jnp.where(real[:, 0], kl, 0.0)), agree)

    # one block of queries after the other (``lax.map``: the compiler
    # holds one block's [heads, block, T] scores, not all of them)
    block = min(block, t)
    pad = (-t) % block
    cut = lambda u: jnp.pad(u, ((0, pad),) + ((0, 0),) * (u.ndim - 1)).reshape(  # noqa: E731
        (-1, block) + u.shape[1:])
    firsts = jnp.arange((t + pad) // block) * block
    bits = None if selection is None else cut(selection[0])
    o, kl, agree = jax.lax.map(rows, (cut(q), cut(qi), cut(a), firsts, bits))
    agree = jnp.sum(agree, 0)
    o = o.reshape(t + pad, h * d)[:t][None]
    return o @ m["wo"], (jnp.sum(kl) / t).astype(jnp.float32), agree[0], agree[1]


def swiglu(x, w12, w3):
    gate, value = jnp.split(x @ w12, 2, axis=-1)
    return (jax.nn.silu(gate) * value) @ w3


@jax.checkpoint
def expert(x, w12, w3, weight):
    """weight * W3 (SiLU(a) * b), [a ; b] = W12 x: one expert on every
    token, each token's result times its routing weight (0 where the
    token did not choose it). Rematerialised whole: the backward pass
    holds one expert's [tokens, D] result at a time, not all of them."""
    return weight[:, None] * swiglu(x, w12, w3)


def experts(x, f, s: Shape, choice, variant: str):
    """(y, share of ``choice`` this router agrees with). x [N, D];
    ``choice`` [N, top_k] int32 or None (the router's own)."""
    logits = x @ f["router"]
    _, own = jax.lax.top_k(logits, s.top_k)
    if choice is None:
        choice = own
    agree = jnp.mean(jnp.any(choice[:, :, None] == own[:, None, :], -1))
    weight = jax.nn.softmax(jnp.take_along_axis(logits, choice, axis=-1), -1)
    held = f["w12"].shape[0] - (1 if variant == "drop_expert" else 0)

    def add(y, held_expert):
        """One held expert after the other (a ``lax.scan``: the compiler
        holds one expert's [tokens, D] result at a time, in the backward
        pass too)."""
        w12, w3, e = held_expert
        w_e = jnp.sum(jnp.where(choice == s.first_expert + e, weight, 0.0), -1)
        return y + expert(x, w12, w3, w_e), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x), (
        f["w12"][:held], f["w3"][:held], jnp.arange(held)))
    return y, agree


def _lowered(variant: str, *trees):
    """The trees as the control computes on them: rounded to bfloat16
    under ``"bf16"``, as they came otherwise."""
    if variant != "bf16":
        return trees
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16), trees)


def layer(x, lw, s: Shape, choice, selection, variant: str):
    """ONE sequence's layer: (x'', the router's agreement, L^I, the
    selected pairs agreed on, those pairs). The two halves are
    rematerialised one after the other: the backward pass holds the inside
    of one half at a time."""
    stream = x.dtype
    x, lw = _lowered(variant, x, lw)
    flat = lambda a: a.reshape(-1, a.shape[-1])  # noqa: E731

    @jax.checkpoint
    def mixer_half(x, norm, m):
        y, *rest = attention(rms_norm(x, norm, s.eps), m, s, selection, variant)
        return x + y, rest

    @jax.checkpoint
    def ffn_half(x, norm, f):
        out, agree = experts(flat(rms_norm(x, norm, s.eps)), f, s, choice,
                             variant)
        return x + out.reshape(x.shape), agree

    x, (index_loss, same, pairs) = mixer_half(x, lw["norm1"], lw["mixer"])
    out, agree = ffn_half(x, lw["norm2"], lw["ffn"])
    return (out.astype(stream), agree.astype(jnp.float32),
            index_loss.astype(jnp.float32), same, pairs)


def hidden(w, tokens, s: Shape, choices=None, selections=None,
           variant: str = "fp32"):
    """(the last layer's output [B, T, D], mean router agreement, the sum
    over layers of L^I, the indexers' agreement share). ``choices``:
    [layers, B*T, top_k] or None; ``selections``: [layers, B, T, T/8]
    uint8 or None."""
    x = w["embed"][tokens]
    bsz, t = tokens.shape
    run = jax.checkpoint(layer, static_argnums=(2, 5))
    agrees, index_loss, same, pairs = [], 0.0, 0, 0
    for i, lw in enumerate(w["layers"]):
        outs = [run(x[b:b + 1], lw, s,
                    None if choices is None
                    else choices[i].reshape(bsz, t, -1)[b],
                    None if selections is None else selections[i][b:b + 1],
                    variant) for b in range(bsz)]
        x = jnp.concatenate([o[0] for o in outs], 0)
        agrees.append(jnp.mean(jnp.stack([o[1] for o in outs])))
        index_loss = index_loss + jnp.mean(jnp.stack([o[2] for o in outs]))
        same, pairs = (same + sum(o[3] for o in outs),
                       pairs + sum(o[4] for o in outs))
    return (x, jnp.mean(jnp.stack(agrees)), index_loss,
            same / jnp.maximum(pairs, 1))


def logits(w, tokens, s: Shape, choices=None, selections=None):
    with jax.default_matmul_precision("highest"):
        x = hidden(w, tokens, s, choices, selections)[0]
        return rms_norm(x, w["norm"], s.eps) @ w["head"]


def head_loss(x, norm, head, tokens, eps: float, variant: str = "fp32",
              block: int = 2048):
    """The final norm, the head and the mean next-token cross-entropy, a
    block of tokens at a time."""
    x, norm, head = _lowered(variant, x, norm, head)
    bsz, t, d = x.shape
    y = rms_norm(x, norm, eps)[:, :-1].reshape(-1, d)
    targets = tokens[:, 1:].reshape(-1)

    @jax.checkpoint
    def nll(xb, tb):
        z = xb @ head
        return jnp.sum(jax.nn.logsumexp(z, -1)
                       - jnp.take_along_axis(z, tb[:, None], -1)[:, 0])

    total = sum(nll(y[i:i + block], targets[i:i + block])
                for i in range(0, y.shape[0], block))
    return (total / (bsz * (t - 1))).astype(jnp.float32)


def loss_fn(w, tokens, s: Shape, choices=None, selections=None,
            variant: str = "fp32"):
    """(L_LM + sum of L^I (without it under ``"no_index_loss"``), (router
    agreement, sum of L^I, indexer agreement))."""
    x, agree, index_loss, share = hidden(w, tokens, s, choices, selections,
                                         variant)
    loss = head_loss(x, w["norm"], w["head"], tokens, s.eps, variant)
    if variant != "no_index_loss":
        loss = loss + index_loss
    return loss, (agree, index_loss, share)


# ---- the step

def decays(w):
    """1.0 where weight decay applies, 0.0 on norm scales, in the tree's
    shape."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: 0.0 if str(getattr(path[-1], "key", path[-1])) in NO_DECAY
        else 1.0, w)


def _sq(tree):
    return sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree.leaves(tree))


# The gradient of ``loss_fn``, layer by layer: one compiled call a layer
# and sequence, forward and then backward, each holding one sequence's
# layer in float32 and nothing else, so that it fits on the chip beside
# the weights and two moments. The tests lay it against ``jax.grad`` of
# the whole at a small size.

@functools.partial(jax.jit, static_argnames=("s", "variant"))
def layer_forward(x, lw, choice, selection, *, s: Shape, variant: str):
    return layer(x, lw, s, choice, selection, variant)


@functools.partial(jax.jit, static_argnames=("s", "variant", "index_weight"),
                   donate_argnums=(4, 5))
def layer_backward(x, lw, choice, selection, dy, acc, *, s: Shape,
                   variant: str, index_weight: float):
    """(d loss / d x, ``acc`` + d loss / d weights) of one layer on one
    sequence from d loss / d output; the layer's own L^I enters the loss
    with ``index_weight`` (1 / sequences, or 0)."""
    (_, agree, index_loss, same, pairs), vjp = jax.vjp(
        lambda x, lw: layer(x, lw, s, choice, selection, variant), x, lw)
    zero = lambda u: np.zeros(u.shape, jax.dtypes.float0)  # noqa: E731
    dx, dlw = vjp((dy, jnp.zeros_like(agree),
                   jnp.full_like(index_loss, index_weight), zero(same),
                   zero(pairs)))
    return dx, jax.tree.map(jnp.add, acc, dlw)


@functools.partial(jax.jit, static_argnames=("eps", "variant"))
def head_backward(x, norm, head, tokens, *, eps: float, variant: str):
    """(loss, d x, d norm, d head) of the final norm, head and loss."""
    loss, (dx, dnorm, dhead) = jax.value_and_grad(
        lambda *a: head_loss(*a, tokens, eps, variant), argnums=(0, 1, 2))(
            x, norm, head)
    return loss, dx, dnorm, dhead


@jax.jit
def _embed_backward(embed, tokens, dx):
    return jnp.zeros_like(embed).at[tokens].add(dx)


@functools.partial(jax.jit, static_argnames=("clip",), donate_argnums=(0,))
def _clip(g, *, clip: float):
    c = jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(_sq(g)), 1e-12))
    return jax.tree.map(lambda x: x * c, g)


def gradient(w, tokens, choices, selections, *, s: Shape, r: Recipe,
             variant: str = "fp32"):
    """(clipped gradient, the loss the step minimises, (router agreement,
    sum of L^I, indexer agreement))."""
    bsz, t = tokens.shape

    def choice_of(i, b):
        return None if choices is None else choices[i].reshape(bsz, t, -1)[b]

    def selection_of(i, b):
        return None if selections is None else selections[i][b:b + 1]

    xs, agrees, index_loss, same, pairs = [w["embed"][tokens]], [], 0.0, 0, 0
    for i, lw in enumerate(w["layers"]):
        outs = [layer_forward(xs[-1][b:b + 1], lw, choice_of(i, b),
                              selection_of(i, b), s=s, variant=variant)
                for b in range(bsz)]
        xs.append(jnp.concatenate([o[0] for o in outs], 0))
        agrees.append(jnp.mean(jnp.stack([o[1] for o in outs])))
        index_loss = index_loss + jnp.mean(jnp.stack([o[2] for o in outs]))
        same, pairs = (same + sum(o[3] for o in outs),
                       pairs + sum(o[4] for o in outs))
    loss, dx, dnorm, dhead = head_backward(xs.pop(), w["norm"], w["head"], tokens,
                                           eps=s.eps, variant=variant)
    index_weight = 0.0 if variant == "no_index_loss" else 1.0 / bsz
    g_layers = [None] * len(s.layers)
    for i in reversed(range(len(s.layers))):
        lw, x = w["layers"][i], xs.pop()
        acc, dxs = jax.tree.map(jnp.zeros_like, lw), []
        for b in range(bsz):
            dxb, acc = layer_backward(
                x[b:b + 1], lw, choice_of(i, b), selection_of(i, b),
                dx[b:b + 1], acc, s=s, variant=variant,
                index_weight=index_weight)
            dxs.append(dxb)
        dx, g_layers[i] = jnp.concatenate(dxs, 0), acc
    g = {"embed": _embed_backward(w["embed"], tokens, dx), "head": dhead,
         "norm": dnorm, "layers": g_layers}
    if variant != "no_index_loss":
        loss = loss + index_loss
    return (_clip(g, clip=r.clip_grad), loss,
            (jnp.mean(jnp.stack(agrees)), index_loss,
             same / jnp.maximum(pairs, 1)))


@functools.partial(jax.jit, static_argnames=("r",), donate_argnums=(0, 1))
def adamw(state, g, sched, *, r: Recipe):
    """``state`` = {"w", "mu", "nu", "count"} -> the next one."""
    count = state["count"] + 1
    c1 = 1.0 - r.beta1 ** count.astype(jnp.float32)
    c2 = 1.0 - r.beta2 ** count.astype(jnp.float32)

    def leaf(g, p, mu, nu, dec):
        mu = r.beta1 * mu + (1.0 - r.beta1) * g
        nu = r.beta2 * nu + (1.0 - r.beta2) * g * g
        direction = (mu / c1) / (jnp.sqrt(nu / c2) + r.adam_eps)
        return p - sched["lr"] * (direction + sched["weight_decay"] * dec * p), mu, nu

    out = jax.tree.map(leaf, g, state["w"], state["mu"], state["nu"], decays(g))
    new = jax.tree.transpose(jax.tree.structure(g), jax.tree.structure((0, 0, 0)), out)
    return {"w": new[0], "mu": new[1], "nu": new[2], "count": count}


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a - b)))


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


def first_steps(w, batches: list, choices: list, s: Shape, r: Recipe, start: int,
                variant: str = "fp32", keep_gradient=None) -> dict:
    """Follow the first ``len(batches)`` steps from the weights ``w``
    (fresh moments) at iterations ``start``, ``start + 1``, ...:
    {"losses": [per step] (what the step minimises), "index_losses": [per
    step] (the sum over layers of L^I), "router_agreement" and
    "index_agreement": the least of the steps, "grad_norms": per leaf, of
    the first clipped gradient, "change_norms": per leaf, of the weights'
    change after the steps}. An entry of ``choices`` is the step's expert
    choices or the pair (choices, selections). ``w``'s buffers are
    the optimizer's from the first step on (donated): pass a tree nothing
    else needs.
    ``keep_gradient(g)`` is called with the first clipped gradient (device
    arrays, donated to the optimizer afterwards) for a caller that lays
    it against another, leaf by leaf."""
    if variant not in VARIANTS:
        raise ValueError(variant)
    with jax.default_matmul_precision("highest"):
        # the weights as they came, on the host: the device holds one set
        # of weights, two of moments and one gradient, and a layer
        start_w = jax.tree.map(np.asarray, w)
        zeros = jax.tree.map(jnp.zeros_like, w)
        state = {"w": w, "mu": zeros, "nu": jax.tree.map(jnp.copy, zeros),
                 "count": jnp.zeros((), jnp.int32)}
        del w
        losses, index_losses, agrees, shares, grad_norms = [], [], [], [], None
        for i, (tokens, choice) in enumerate(zip(batches, choices)):
            choice, selection = (choice if isinstance(choice, (tuple, list))
                                 else (choice, None))
            g, loss, (agree, index_loss, share) = gradient(
                state["w"], tokens, choice, selection, s=s, r=r,
                variant=variant)
            losses.append(float(loss))
            index_losses.append(float(index_loss))
            agrees.append(float(agree))
            shares.append(float(share))
            if i == 0:
                grad_norms = jax.tree.map(np.asarray, leaf_norms(g))
                if keep_gradient is not None:
                    keep_gradient(g)
            state = adamw(state, g, r.schedule(start + i), r=r)
        change = jax.tree.map(
            lambda new, old: np.asarray(_diff_norm(new, old)), state["w"], start_w)
    return {"losses": losses, "index_losses": index_losses,
            "router_agreement": min(agrees), "index_agreement": min(shares),
            "grad_norms": grad_norms, "change_norms": change}
