"""The plain reference of the ``qwen3_next`` decoder's training step:
forward, next-token loss, gradient (``jax.grad``), clip and AdamW in
float32 under ``jax.default_matmul_precision("highest")``. It imports
nothing of the program and shares no algorithm with it where the program
has one of its own: the delta rule is the RECURRENCE, token by token (no
chunks, no kernel), the attention is a masked softmax over whole rows of
keys one key/value group at a time, the rotary embedding is written out
from sines and cosines, the experts are a loop over the ones held.

Source: ``config.json`` of Qwen/Qwen3-Next-80B-A3B-Instruct (``model_type``
``qwen3_next``) for every size, and the released modelling code, from
memory, for what the config's keys do not settle (the configuration's
file lists those under ``assumed``). Left out: the multi-token prediction
module (no key of ``config.json`` sizes it) and any auxiliary balancing
loss.

**Symbols.** x_t in R^D is a layer's input at token t (from 0). Every
norm but one is ZERO-CENTRED: n(x) = x / sqrt(mean(x^2) + eps) * (1 + w),
eps 1e-6, w from zeros. No bias anywhere. Layer i (from 0) is gated
attention where (i + 1) % ``full_attention_interval`` == 0, Gated
DeltaNet otherwise.

**Gated DeltaNet** (Hk key heads of d_k under Hv value heads of d_v,
r = Hv / Hk; value head j is served by key head j // r); u = n1(x):

    [q ; k ; v ; z]_h = W_qkvz u     a KEY head h: d_k, d_k, r d_v, r d_v
    [b ; a]_h = W_ba u               a key head: r, r (one a value head)
    [q ; k ; v] <- SiLU(conv([q ; k ; v]))   ONE causal depthwise
        convolution of width W over the joined channels (all q heads,
        then all k heads, then all v heads): y_t = sum_j c_j u_{t-W+1+j}
    q_t <- q_t / sqrt(sum q_t^2 + 1e-6) * d_k^-0.5, k_t likewise without
        the scale, a head at a time
    beta_t = sigmoid(b_t);  g_t = -exp(A_log_j) softplus(a_t + dt_bias_j)
        ONE number a value head and token
    S_t = (I - beta_t k_t k_t^T) e^{g_t} S_{t-1} + beta_t k_t v_t^T,  S_0 = 0
    o_t = S_t^T q_t
    y_t = W_o [ o_t / sqrt(mean(o_t^2) + eps) * w_o * SiLU(z_t) ]   this
        norm's scale is w_o itself (from ones), a value head at a time

**Gated attention** (H query heads on Hk key/value heads of d); u = n1(x):

    [q_i ; gate_i] = W_q u a head (d and d);  k = W_k u, v = W_v u
    q_i <- n_q(q_i), k <- n_k(k)    over the d channels of a head, one
                                    scale vector for all heads
    the first R channels of every q and k head rotated:
        rot(z)_t = z cos(t f) + [-z_2 ; z_1] sin(t f), z = [z_1 ; z_2]
        halves of R/2, f_j = theta^(-2j/R), j < R/2; channels R.. untouched
    query head i reads key/value head i // (H / Hk)
    o_i,t = softmax_{j <= t}(q_i,t . k_j / sqrt(d)) v_j
    y = W_o [ o * sigmoid(gate) ]

**FFN**, every layer; g = n2(x'), x' = x + mixer:

    r = W_r g in R^E;  C = the top_k largest of r;  w = softmax(r[C])
    f_e(g) = W3_e (SiLU(a) * b), [a ; b] = W12_e g
    x'' = x' + sum over e in C held here of w_e f_e(g)
             + sigmoid(w_s . g) f_shared(g)

This shard holds the experts ``[first, first + held)``; what the others
would add is left out. ``choices`` hands C in from outside: seed-made
routers put many of the k-th and (k+1)-th logits within rounding of each
other, so a program in another precision picks differently for a share
of the tokens, and a reference that is to be laid against it follows ITS
choice. The share of C the reference's own router agrees with is
returned beside it.

**Loss.** Mean over sequences b and positions t < T-1 of
logsumexp(z_bt) - z_bt[token_{b,t+1}], z = W_head n(x) over the
vocabulary held (a slice of the published one is a smaller vocabulary).

**Step.** Global-norm clip of the whole gradient, then AdamW: m, v
moments with bias correction, p <- p - lr (m^ / (sqrt(v^) + eps) + wd p),
wd 0 on every norm scale, ``A_log`` and ``dt_bias``.

Weights, one dict, every matrix in the PUBLISHED grouping of its columns:
``embed`` [V, D], ``head`` [D, V], ``norm`` [D], ``layers``: a list of
{``norm1``, ``norm2``, ``mixer``, ``ffn``}; a Gated DeltaNet ``mixer``:
{``wqkvz`` [D, Hk (2 d_k + 2 r d_v)], ``wba`` [D, Hk 2 r], ``conv``
[W, 2 Hk d_k + Hv d_v], ``A_log`` [Hv], ``dt_bias`` [Hv], ``o_norm``
[d_v], ``wo``}; an attention ``mixer``: {``wq`` [D, H 2 d], ``wk``,
``wv``, ``q_norm`` [d], ``k_norm`` [d], ``wo``}; ``ffn``: {``router``
[D, E], ``w12`` [held, D, 2F], ``w3`` [held, F, D], ``shared``: {``w12``,
``w3``}, ``shared_gate`` [D, 1]}.

``variant`` makes the controls of the configuration's ``check``.
``"bf16"`` is the nearest precision below the one the configuration
states: the configuration runs bfloat16 matmuls and activations and keeps
a float32 set (the delta rule's decays, products and state, the router,
the softmax, the norms' statistics, the rotary turn, the loss); the
control lowers that whole set to bfloat16 too — every layer and the head
take their input and their weights rounded to bfloat16 and compute in it,
the delta rule's state included; gradients come back in float32 to
float32 master weights, clip and AdamW, as the configuration states for
those. ``"no_decay"`` is a planted fault of this family's own: g = 0 in
every Gated DeltaNet layer, the delta rule without its gate.
``"drop_expert"`` is another, the last held expert left out (a 32nd of a
routed leaf at the configuration's size). ``"no_renorm"`` is the routed
layer's own: the chosen experts weighted by the softmax over ALL the
router's outputs and not renormalised over the chosen
(``norm_topk_prob`` read as false), which moves every routed leaf and
the routers.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NO_DECAY = ("norm", "norm1", "norm2", "o_norm", "q_norm", "k_norm", "A_log",
            "dt_bias")
VARIANTS = ("fp32", "bf16", "no_decay", "drop_expert", "no_renorm")


@dataclasses.dataclass(frozen=True)
class Shape:
    """What the equations need beyond the weights' own shapes (the
    ``shape`` group of the configuration's file)."""

    layers: tuple             # (("gdn" | "gated_attn", "moe"), ...)
    gdn_key_heads: int
    gdn_value_heads: int
    gdn_key_dim: int
    heads: int
    kv_heads: int
    rotary_dim: int
    rope_theta: float
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
    """The recipe's numbers (``configs/train/qwen3_next_ep16.yaml``)."""

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

def unit_rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def zc_norm(x, w, eps):
    """The zero-centred RMSNorm: the scale is 1 + w."""
    return unit_rms(x, eps) * (1.0 + w)


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


@jax.checkpoint
def swiglu(x, w12, w3):
    gate, value = jnp.split(x @ w12, 2, axis=-1)
    return (jax.nn.silu(gate) * value) @ w3


def causal_conv(u, c):
    """y_t = sum_j c[j] u_{t - W + 1 + j}; u [B, T, C], c [W, C]."""
    width, t = c.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * c[j] for j in range(width))


def delta_rule(q, k, v, a, b, block: int = 64):
    """The recurrence, token by token, in the type its inputs come in.
    q, k [B, T, H, d_k]; v [B, T, H, d_v]; a (the decay e^g, one a head)
    and b [B, T, H]. ``lax.scan`` over tokens inside a rematerialised scan
    over blocks of tokens, so the backward pass holds one state a block
    and not one a token."""
    bsz, t, h, d = q.shape
    pad = (-t) % block
    if pad:  # tokens that neither decay nor write; their outputs are cut
        grow = lambda x, fill: jnp.pad(  # noqa: E731
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2), constant_values=fill)
        q, k, v, a, b = grow(q, 0), grow(k, 0), grow(v, 0), grow(a, 1), grow(b, 0)

    def token(s, xs):
        qt, kt, vt, at, bt = xs
        s = at[..., None, None] * s
        old = jnp.einsum("bhc,bhcd->bhd", kt, s)
        s = s + jnp.einsum("bhc,bhd->bhcd", kt, bt[..., None] * (vt - old))
        return s, jnp.einsum("bhc,bhcd->bhd", qt, s)

    @jax.checkpoint
    def tokens(s, xs):
        return jax.lax.scan(token, s, xs)

    def blocks(x):  # [B, T, ...] -> [T / block, block, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((-1, block) + x.shape[1:])

    _, o = jax.lax.scan(tokens, jnp.zeros((bsz, h, d, v.shape[-1]), q.dtype),
                        tuple(blocks(x) for x in (q, k, v, a, b)))
    o = jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)
    return o[:, :t]


def gdn(x, m, s: Shape, variant: str):
    """x [B, T, D] the normed input."""
    bsz, t, _ = x.shape
    hk, hv, dk = s.gdn_key_heads, s.gdn_value_heads, s.gdn_key_dim
    r, dv = hv // hk, m["o_norm"].shape[0]

    # (each line rematerialised by itself: the backward pass then holds
    # the planes these lines end in, not the ones they pass through)
    @jax.checkpoint
    def projected(wqkvz, conv):
        mixed = (x @ wqkvz).reshape(bsz, t, hk, 2 * dk + 2 * r * dv)
        q, k, v, z = jnp.split(mixed, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
        flat = lambda u: u.reshape(bsz, t, -1)  # noqa: E731
        joined = jax.nn.silu(causal_conv(
            jnp.concatenate([flat(q), flat(k), flat(v)], -1), conv))
        q, k, v = jnp.split(joined, [hk * dk, 2 * hk * dk], axis=-1)
        q = l2_norm(q.reshape(bsz, t, hk, dk)) * dk ** -0.5
        k = l2_norm(k.reshape(bsz, t, hk, dk))
        # key head h serves value heads h r .. h r + r - 1
        q, k = (jnp.repeat(u, r, axis=2) for u in (q, k))
        return q, k, v.reshape(bsz, t, hv, dv), z.reshape(bsz, t, hv, dv)

    q, k, v, z = projected(m["wqkvz"], m["conv"])
    ba = (x @ m["wba"]).reshape(bsz, t, hk, 2 * r)
    b, a = (u.reshape(bsz, t, hv) for u in (ba[..., :r], ba[..., r:]))
    g = -jnp.exp(m["A_log"]) * jax.nn.softplus(a + m["dt_bias"])
    if variant == "no_decay":
        g = jnp.zeros_like(g)
    o = delta_rule(q, k, v, jnp.exp(g), jax.nn.sigmoid(b))
    gated = jax.checkpoint(lambda o, z, scale: (
        unit_rms(o, s.eps) * scale * jax.nn.silu(z)).reshape(bsz, t, -1))
    return gated(o, z, m["o_norm"]) @ m["wo"]


def rotate_leading(z, width: int, theta: float):
    """z [B, T, H, d]: the first ``width`` channels of every head, token t
    turned by t * theta^(-2j/width) on the channel pair (j, j + width/2);
    the channels past them as they are; in the type z comes in."""
    t = z.shape[1]
    freq = theta ** (-np.arange(0, width, 2, dtype=np.float32) / width)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = (jnp.concatenate([f(angle), f(angle)], -1)[None, :, None, :]
                .astype(z.dtype) for f in (jnp.cos, jnp.sin))
    z1, z2 = z[..., :width // 2], z[..., width // 2:width]
    turned = z[..., :width] * cos + jnp.concatenate([-z2, z1], -1) * sin
    return jnp.concatenate([turned, z[..., width:]], -1)


def attention(x, m, s: Shape, block: int = 64):
    """x [B, T, D] the normed input."""
    bsz, t, _ = x.shape
    h, hk = s.heads, s.kv_heads
    q = (x @ m["wq"]).reshape(bsz, t, h, -1)
    d = q.shape[-1] // 2
    q, gate = q[..., :d], q[..., d:]
    k = (x @ m["wk"]).reshape(bsz, t, hk, d)
    v = (x @ m["wv"]).reshape(bsz, t, hk, d)
    q = rotate_leading(zc_norm(q, m["q_norm"], s.eps), s.rotary_dim, s.rope_theta)
    k = rotate_leading(zc_norm(k, m["k_norm"], s.eps), s.rotary_dim, s.rope_theta)

    @jax.checkpoint
    def group(q, k, v):
        """The h / hk query heads [B, T, g, d] that read one key/value
        head [B, T, d], which is written out once for each of them.
        Rematerialised: the backward pass holds one group's repeated
        keys and values at a time, not all of them."""
        g = q.shape[2]
        k, v = (jnp.repeat(a[:, :, None], g, axis=2) for a in (k, v))

        @jax.checkpoint
        def rows(args):
            """Softmax over the whole row of keys, the later ones masked."""
            qb, first = args
            z = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
            # (the rows padded on past the last token stand at the last
            # token: a row that sees no key at all is NaN, in the gradient
            # too)
            at = jnp.minimum(first + jnp.arange(qb.shape[1]), t - 1)[:, None]
            z = jnp.where(jnp.arange(t)[None, :] <= at, z, -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(z, -1), v)

        # one block of queries after the other (``lax.map``: the compiler
        # holds one block's [heads, block, T] scores, not all of them)
        pad = (-t) % block
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        blocks = jnp.moveaxis(qp.reshape(bsz, -1, block, g, d), 1, 0)
        o = jax.lax.map(rows, (blocks, jnp.arange(blocks.shape[0]) * block))
        return jnp.moveaxis(o, 0, 1).reshape(bsz, t + pad, g, d)[:, :t]

    # query head i reads key/value head i // (h / hk)
    q = q.reshape(bsz, t, hk, h // hk, d)
    o = jnp.stack([group(q[:, :, j], k[:, :, j], v[:, :, j])
                   for j in range(hk)], axis=2).reshape(bsz, t, h, d)
    return (o * jax.nn.sigmoid(gate)).reshape(bsz, t, -1) @ m["wo"]


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
    if variant == "no_renorm":
        weight = jnp.take_along_axis(jax.nn.softmax(logits, -1), choice, axis=-1)
    else:
        weight = jax.nn.softmax(jnp.take_along_axis(logits, choice, axis=-1), -1)
    held = f["w12"].shape[0] - (1 if variant == "drop_expert" else 0)

    def add(y, held_expert):
        """One held expert after the other (a ``lax.scan``: the compiler
        holds one expert's [tokens, D] result at a time, in the backward
        pass too)."""
        w12, w3, e = held_expert
        w_e = jnp.sum(jnp.where(choice == s.first_expert + e, weight, 0.0), -1)
        return y + expert(x, w12, w3, w_e), None

    shared = jax.nn.sigmoid(x @ f["shared_gate"]) * swiglu(
        x, f["shared"]["w12"], f["shared"]["w3"])
    y, _ = jax.lax.scan(add, shared, (
        f["w12"][:held], f["w3"][:held], jnp.arange(held)))
    return y, agree


def _lowered(variant: str, *trees):
    """The trees as the control computes on them: rounded to bfloat16
    under ``"bf16"``, as they came otherwise."""
    if variant != "bf16":
        return trees
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16), trees)


def layer(x, lw, kinds, s: Shape, choice, variant: str):
    """The two halves are rematerialised one after the other: the
    backward pass holds the inside of one half at a time."""
    stream = x.dtype
    x, lw = _lowered(variant, x, lw)
    flat = lambda a: a.reshape(-1, a.shape[-1])  # noqa: E731

    @jax.checkpoint
    def mixer_half(x, norm, m):
        y = zc_norm(x, norm, s.eps)
        return x + (gdn(y, m, s, variant) if kinds[0] == "gdn"
                    else attention(y, m, s))

    @jax.checkpoint
    def ffn_half(x, norm, f):
        out, agree = experts(flat(zc_norm(x, norm, s.eps)), f, s, choice, variant)
        return x + out.reshape(x.shape), agree

    out, agree = ffn_half(mixer_half(x, lw["norm1"], lw["mixer"]),
                          lw["norm2"], lw["ffn"])
    return out.astype(stream), agree.astype(jnp.float32)


def hidden(w, tokens, s: Shape, choices=None, variant: str = "fp32"):
    """(the last layer's output [B, T, D], mean router agreement).
    ``choices``: [layers, B*T, top_k] or None."""
    x = w["embed"][tokens]
    run = jax.checkpoint(layer, static_argnums=(2, 3, 5))
    agrees = []
    for i, (lw, kinds) in enumerate(zip(w["layers"], s.layers)):
        x, agree = run(x, lw, kinds, s,
                       None if choices is None else choices[i], variant)
        agrees.append(agree)
    return x, jnp.mean(jnp.stack(agrees))


def logits(w, tokens, s: Shape, choices=None):
    with jax.default_matmul_precision("highest"):
        x, _ = hidden(w, tokens, s, choices)
        return zc_norm(x, w["norm"], s.eps) @ w["head"]


def head_loss(x, norm, head, tokens, eps: float, variant: str = "fp32",
              block: int = 2048):
    """The final norm, the head and the mean next-token cross-entropy, a
    block of tokens at a time."""
    x, norm, head = _lowered(variant, x, norm, head)
    bsz, t, d = x.shape
    y = zc_norm(x, norm, eps)[:, :-1].reshape(-1, d)
    targets = tokens[:, 1:].reshape(-1)

    @jax.checkpoint
    def nll(xb, tb):
        z = xb @ head
        return jnp.sum(jax.nn.logsumexp(z, -1)
                       - jnp.take_along_axis(z, tb[:, None], -1)[:, 0])

    total = sum(nll(y[i:i + block], targets[i:i + block])
                for i in range(0, y.shape[0], block))
    return (total / (bsz * (t - 1))).astype(jnp.float32)


def loss_fn(w, tokens, s: Shape, choices=None, variant: str = "fp32"):
    """(loss, router agreement)."""
    x, agree = hidden(w, tokens, s, choices, variant)
    return head_loss(x, w["norm"], w["head"], tokens, s.eps, variant), agree


# ---- the step

def decays(w):
    """1.0 where weight decay applies, 0.0 on norm scales, A_log and
    dt_bias, in the tree's shape."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: 0.0 if str(getattr(path[-1], "key", path[-1])) in NO_DECAY
        else 1.0, w)


def _sq(tree):
    return sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree.leaves(tree))


# The gradient of ``loss_fn``, layer by layer: one compiled call a layer
# and sequence, forward and then backward, each holding one sequence's
# layer in float32 and nothing else, so that it fits on the chip beside
# the weights, two moments and the gradient. The tests lay it against
# ``jax.grad`` of the whole at a small size.

@functools.partial(jax.jit, static_argnames=("kinds", "s", "variant"))
def layer_forward(x, lw, choice, *, kinds, s: Shape, variant: str):
    return layer(x, lw, kinds, s, choice, variant)


@functools.partial(jax.jit, static_argnames=("kinds", "s", "variant"),
                   donate_argnums=(3, 4))
def layer_backward(x, lw, choice, dy, acc, *, kinds, s: Shape, variant: str):
    """(d loss / d x, ``acc`` + d loss / d weights) of one layer on one
    sequence from d loss / d output."""
    (_, agree), vjp = jax.vjp(
        lambda x, lw: layer(x, lw, kinds, s, choice, variant), x, lw)
    dx, dlw = vjp((dy, jnp.zeros_like(agree)))
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


def gradient(w, tokens, choices, *, s: Shape, r: Recipe, variant: str = "fp32"):
    """(clipped gradient, loss, router agreement)."""
    bsz, t = tokens.shape

    def choice_of(i, b):
        return None if choices is None else choices[i].reshape(bsz, t, -1)[b]

    xs, agrees = [w["embed"][tokens]], []
    for i, (lw, kinds) in enumerate(zip(w["layers"], s.layers)):
        outs = [layer_forward(xs[-1][b:b + 1], lw, choice_of(i, b), kinds=kinds,
                              s=s, variant=variant) for b in range(bsz)]
        xs.append(jnp.concatenate([y for y, _ in outs], 0))
        agrees.append(jnp.mean(jnp.stack([a for _, a in outs])))
    loss, dx, dnorm, dhead = head_backward(xs.pop(), w["norm"], w["head"], tokens,
                                           eps=s.eps, variant=variant)
    g_layers = [None] * len(s.layers)
    for i in reversed(range(len(s.layers))):
        lw, kinds, x = w["layers"][i], s.layers[i], xs.pop()
        acc, dxs = jax.tree.map(jnp.zeros_like, lw), []
        for b in range(bsz):
            dxb, acc = layer_backward(x[b:b + 1], lw, choice_of(i, b),
                                      dx[b:b + 1], acc, kinds=kinds, s=s,
                                      variant=variant)
            dxs.append(dxb)
        dx, g_layers[i] = jnp.concatenate(dxs, 0), acc
    g = {"embed": _embed_backward(w["embed"], tokens, dx), "head": dhead,
         "norm": dnorm, "layers": g_layers}
    return _clip(g, clip=r.clip_grad), loss, jnp.mean(jnp.stack(agrees))


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
    {"losses": [per step], "router_agreement": the least of the steps,
    "grad_norms": per leaf, of the first clipped gradient, "change_norms":
    per leaf, of the weights' change after the steps}. ``w``'s buffers are
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
        losses, agrees, grad_norms = [], [], None
        for i, (tokens, choice) in enumerate(zip(batches, choices)):
            g, loss, agree = gradient(state["w"], tokens, choice, s=s, r=r,
                                      variant=variant)
            losses.append(float(loss))
            agrees.append(float(agree))
            if i == 0:
                grad_norms = jax.tree.map(np.asarray, leaf_norms(g))
                if keep_gradient is not None:
                    keep_gradient(g)
            state = adamw(state, g, r.schedule(start + i), r=r)
        change = jax.tree.map(
            lambda new, old: np.asarray(_diff_norm(new, old)), state["w"], start_w)
    return {"losses": losses, "router_agreement": min(agrees),
            "grad_norms": grad_norms, "change_norms": change}
