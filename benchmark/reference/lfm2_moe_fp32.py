"""The plain reference of the ``lfm2_moe`` decoder's training step:
forward, next-token loss, gradient (``jax.grad``), clip and AdamW in
float32 under ``jax.default_matmul_precision("highest")``. It imports
nothing of the program and shares no algorithm with it where the program
has one of its own: the short convolution is three shifted products (no
blocks, no kernel), the attention is a masked softmax over whole rows of
keys, a block of queries and one key/value group at a time, the rotary
embedding is written out from sines and cosines, the experts are a
``lax.scan`` over the ones held.

Source: ``config.json`` of LiquidAI/LFM2-24B-A2B (``model_type``
``lfm2_moe``) for every size, and the ``lfm2_moe`` decoder layer of the
public ``transformers`` implementation, from memory, for what the
config's keys do not settle (the configuration's file lists those under
``assumed``). Left out: any auxiliary balancing loss and the rule that
moves the router's selection bias between steps.

**The layer, for one sequence x of [T, D]** (D 2048). RMSNorm is
n(x) = x / sqrt(mean(x^2) + eps) * w, eps 1e-5, w from ones, not
zero-centred. No bias anywhere. Layer i is a ``conv`` or a
``full_attention`` layer as ``layer_types[i]`` says.

1. h = n_op(x) (``operator_norm``; here ``norm1``).
2. ``conv`` layer: [B ; C ; u] = W_in h, three blocks of D in THAT order
   (``assumed``: the public code's ``chunk(3)``); z = B * u;
   c_t = sum_{j=0..W-1} k_j * z_{t-W+1+j} (depthwise, causal, z_{<0} = 0,
   taps k of [W, D], W = ``conv_L_cache`` 3, no bias); y = W_out (C * c).
   No activation anywhere in the mixer.
3. ``full_attention`` layer (H query heads on Hk key/value heads of
   d = D / H = 64): q = W_q h -> [T, H, d], k = W_k h, v = W_v h ->
   [T, Hk, d]; q <- RMSNorm_d(q), k <- RMSNorm_d(k) (``q_layernorm`` /
   ``k_layernorm``: one scale vector each, eps as above); rotary over the
   whole head, rotate-half (``assumed``: as written here):
       rot(z)_t = z cos(t f) + [-z_2 ; z_1] sin(t f), z = [z_1 ; z_2]
       halves of d/2, f_j = theta^(-2j/d), j < d/2, positions t = 0..T-1;
   o[t, i] = softmax_{s <= t}(q_i . k_{i // (H/Hk)} / sqrt(d)) v_{i // (H/Hk)};
   y = W_o o. No bias, no gate, no window.
4. x' = x + y; g = n_ffn(x') (``ffn_norm``; here ``norm2``).
5. The first ``num_dense_layers`` layers: f = W_3 (SiLU(a) * b),
   [a ; b] = W_12 g, width ``intermediate_size``. Every later layer,
   routed: s = sigmoid(W_r g) over all E experts; C = the top_k largest
   of s + bias (``expert_bias``: no gradient, moved by no rule in the
   step); w = s[C] / (sum s[C] + 1e-6) (``assumed``: the public code's
   normaliser), times ``routed_scaling_factor`` (1);
   f = sum over e in C held here of w_e SwiGLU_e(g).
6. x'' = x' + f. After the last layer: n_out (``embedding_norm``, the
   model's FINAL norm), logits = n_out(x) E^T with E the token embedding
   (``assumed`` tied: the LFM2 family's convention).

This shard holds the experts ``[first, first + held)``; what the others
would add is left out. ``choices`` hands C in from outside: seed-made
routers put many of the k-th and (k+1)-th scores within rounding of each
other, so a program in another precision picks differently for a share
of the tokens, and a reference that is to be laid against it follows ITS
choice. The share of C the reference's own router agrees with is
returned beside it.

**Loss.** Mean over sequences b and positions t < T-1 of
logsumexp(z_bt) - z_bt[token_{b,t+1}] over the vocabulary held (a slice
of the published one is a smaller vocabulary).

**Step.** Global-norm clip of the whole gradient, then AdamW: m, v
moments with bias correction, p <- p - lr (m^ / (sqrt(v^) + eps) + wd p),
wd 0 on every norm scale and on the selection bias (whose gradient is 0:
it stays where the seed put it).

Weights, one dict: ``embed`` [V, D] (embedding AND head), ``norm`` [D],
``layers``: a list of {``norm1``, ``norm2``, ``mixer``, ``ffn``}; a conv
``mixer``: {``win`` [D, 3 D], ``conv`` [W, D], ``wout`` [D, D]}; an
attention ``mixer``: {``wq`` [D, H d], ``wk``, ``wv`` [D, Hk d],
``q_norm`` [d], ``k_norm`` [d], ``wo``}; a dense ``ffn``: {``w12``
[D, 2 F], ``w3`` [F, D]}; a routed one: {``router`` [D, E],
``router_bias`` [E], ``w12`` [held, D, 2 F'], ``w3`` [held, F', D]}.

``variant`` makes the controls of the configuration's ``check``.
``"bf16"`` is the nearest precision below the one the configuration
states: the configuration runs bfloat16 matmuls and activations and keeps
a float32 set (the convolution's chain, the router, the softmax, the
norms' statistics, the rotary turn, the loss); the control lowers that
whole set to bfloat16 too — every layer and the head take their input and
their weights rounded to bfloat16 and compute in it; gradients come back
in float32 to float32 master weights, clip and AdamW. ``"no_conv"`` is
the mechanism left out: c_t = k_{W-1} * z_t, the taps on past tokens
dropped. ``"untied_head"`` is the tying left out of the gradient: the
head as a matrix of its own, so the embedding leaf's gradient loses the
head's part. ``"drop_expert"`` leaves the last held expert out (an eighth
of a routed leaf at the configuration's size).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NO_DECAY = ("norm", "norm1", "norm2", "q_norm", "k_norm", "router_bias")
VARIANTS = ("fp32", "bf16", "no_conv", "untied_head", "drop_expert")
ROUTER_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Shape:
    """What the equations need beyond the weights' own shapes (the
    ``shape`` group of the configuration's file)."""

    layers: tuple             # (("conv" | "full_attn", "dense" | "moe"), ...)
    heads: int
    kv_heads: int
    rope_theta: float
    top_k: int
    first_expert: int         # the experts held: [first, first + held)
    routed_scaling_factor: float = 1.0
    eps: float = 1e-5

    @classmethod
    def from_config(cls, group: dict) -> "Shape":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in group.items() if k in fields}
        kw["layers"] = tuple(tuple(x) for x in kw["layers"])
        return cls(**kw)


@dataclasses.dataclass(frozen=True)
class Recipe:
    """The recipe's numbers (``configs/train/lfm2_ep8.yaml``)."""

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

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


@jax.checkpoint
def swiglu(x, w12, w3):
    gate, value = jnp.split(x @ w12, 2, axis=-1)
    return (jax.nn.silu(gate) * value) @ w3


def short_conv(x, m, variant: str):
    """x [B, T, D] the normed input: step 2, the convolution as W shifted
    products."""
    t = x.shape[1]
    gate, mid, u = jnp.split(x @ m["win"], 3, axis=-1)     # B, C, u
    z = gate * u
    width = m["conv"].shape[0]
    if variant == "no_conv":
        c = m["conv"][width - 1] * z
    else:
        padded = jnp.pad(z, ((0, 0), (width - 1, 0), (0, 0)))
        c = sum(m["conv"][j] * padded[:, j:j + t] for j in range(width))
    return (mid * c) @ m["wout"]


def rotate(z, theta: float):
    """z [B, T, H, d]: token t turned by t * theta^(-2j/d) on the channel
    pair (j, j + d/2); in the type z comes in."""
    t, d = z.shape[1], z.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = (jnp.concatenate([f(angle), f(angle)], -1)[None, :, None, :]
                .astype(z.dtype) for f in (jnp.cos, jnp.sin))
    z1, z2 = z[..., :d // 2], z[..., d // 2:]
    return z * cos + jnp.concatenate([-z2, z1], -1) * sin


def attention(x, m, s: Shape, block: int = 64):
    """x [B, T, D] the normed input: step 3."""
    bsz, t, _ = x.shape
    h, hk = s.heads, s.kv_heads
    q = (x @ m["wq"]).reshape(bsz, t, h, -1)
    d = q.shape[-1]
    k = (x @ m["wk"]).reshape(bsz, t, hk, d)
    v = (x @ m["wv"]).reshape(bsz, t, hk, d)
    q = rotate(rms_norm(q, m["q_norm"], s.eps), s.rope_theta)
    k = rotate(rms_norm(k, m["k_norm"], s.eps), s.rope_theta)

    @jax.checkpoint
    def group(q, k, v):
        """The h / hk query heads [B, T, g, d] that read one key/value
        head [B, T, d]. Rematerialised: the backward pass holds one
        group's planes at a time."""

        @jax.checkpoint
        def rows(args):
            """Softmax over the whole row of keys, the later ones masked."""
            qb, first = args
            z = jnp.einsum("bqhd,bkd->bhqk", qb, k) / math.sqrt(d)
            # (the rows padded on past the last token stand at the last
            # token: a row that sees no key at all is NaN)
            at = jnp.minimum(first + jnp.arange(qb.shape[1]), t - 1)[:, None]
            z = jnp.where(jnp.arange(t)[None, :] <= at, z, -jnp.inf)
            return jnp.einsum("bhqk,bkd->bqhd", jax.nn.softmax(z, -1), v)

        g = q.shape[2]
        pad = (-t) % block
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        blocks = jnp.moveaxis(qp.reshape(bsz, -1, block, g, d), 1, 0)
        o = jax.lax.map(rows, (blocks, jnp.arange(blocks.shape[0]) * block))
        return jnp.moveaxis(o, 0, 1).reshape(bsz, t + pad, g, d)[:, :t]

    # query head i reads key/value head i // (h / hk)
    q = q.reshape(bsz, t, hk, h // hk, d)
    o = jnp.stack([group(q[:, :, j], k[:, :, j], v[:, :, j])
                   for j in range(hk)], axis=2)
    return o.reshape(bsz, t, -1) @ m["wo"]


@jax.checkpoint
def expert(x, w12, w3, weight):
    """weight * W3 (SiLU(a) * b), [a ; b] = W12 x: one expert on every
    token, each token's result times its routing weight (0 where the
    token did not choose it)."""
    return weight[:, None] * swiglu(x, w12, w3)


def route(x, f, s: Shape, choice=None):
    """(choice [N, top_k], weight [N, top_k], agreement): step 5's rule.
    ``choice`` None: the router's own."""
    scores = jax.nn.sigmoid(x @ f["router"])
    _, own = jax.lax.top_k(
        scores + jax.lax.stop_gradient(f["router_bias"]), s.top_k)
    if choice is None:
        choice = own
    agree = jnp.mean(jnp.any(choice[:, :, None] == own[:, None, :], -1))
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    weight = s.routed_scaling_factor * picked / (
        jnp.sum(picked, -1, keepdims=True) + ROUTER_EPS)
    return choice, weight, agree


def experts(x, f, s: Shape, choice, variant: str):
    """(y, share of ``choice`` this router agrees with). x [N, D]."""
    choice, weight, agree = route(x, f, s, choice)
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


def layer(x, lw, kinds, s: Shape, choice, variant: str):
    """The two halves are rematerialised one after the other: the
    backward pass holds the inside of one half at a time."""
    stream = x.dtype
    x, lw = _lowered(variant, x, lw)
    flat = lambda a: a.reshape(-1, a.shape[-1])  # noqa: E731

    @jax.checkpoint
    def mixer_half(x, norm, m):
        y = rms_norm(x, norm, s.eps)
        return x + (short_conv(y, m, variant) if kinds[0] == "conv"
                    else attention(y, m, s))

    @jax.checkpoint
    def ffn_half(x, norm, f):
        y = rms_norm(x, norm, s.eps)
        if kinds[1] == "dense":
            return x + swiglu(y, f["w12"], f["w3"]), jnp.ones((), x.dtype)
        out, agree = experts(flat(y), f, s, choice, variant)
        return x + out.reshape(x.shape), agree

    out, agree = ffn_half(mixer_half(x, lw["norm1"], lw["mixer"]),
                          lw["norm2"], lw["ffn"])
    return out.astype(stream), agree.astype(jnp.float32)


def _routed(s: Shape) -> list:
    """For each layer its place among the routed layers, None for a dense
    one (``choices`` is stacked over the routed layers alone)."""
    at, out = 0, []
    for _, ffn in s.layers:
        out.append(at if ffn == "moe" else None)
        at += ffn == "moe"
    return out


def hidden(w, tokens, s: Shape, choices=None, variant: str = "fp32"):
    """(the last layer's output [B, T, D], mean router agreement of the
    routed layers). ``choices``: [routed layers, B*T, top_k] or None."""
    x = w["embed"][tokens]
    run = jax.checkpoint(layer, static_argnums=(2, 3, 5))
    agrees = []
    for lw, kinds, at in zip(w["layers"], s.layers, _routed(s)):
        x, agree = run(x, lw, kinds, s,
                       None if choices is None or at is None else choices[at],
                       variant)
        if at is not None:
            agrees.append(agree)
    return x, jnp.mean(jnp.stack(agrees))


def logits(w, tokens, s: Shape, choices=None):
    with jax.default_matmul_precision("highest"):
        x, _ = hidden(w, tokens, s, choices)
        return rms_norm(x, w["norm"], s.eps) @ w["embed"].T


def head_loss(x, norm, embed, tokens, eps: float, variant: str = "fp32",
              block: int = 2048):
    """The final norm, the head (the embedding table, turned) and the mean
    next-token cross-entropy, a block of tokens at a time."""
    x, norm, embed = _lowered(variant, x, norm, embed)
    bsz, t, d = x.shape
    y = rms_norm(x, norm, eps)[:, :-1].reshape(-1, d)
    targets = tokens[:, 1:].reshape(-1)

    @jax.checkpoint
    def nll(xb, tb):
        z = xb @ embed.T
        return jnp.sum(jax.nn.logsumexp(z, -1)
                       - jnp.take_along_axis(z, tb[:, None], -1)[:, 0])

    total = sum(nll(y[i:i + block], targets[i:i + block])
                for i in range(0, y.shape[0], block))
    return (total / (bsz * (t - 1))).astype(jnp.float32)


def loss_fn(w, tokens, s: Shape, choices=None, variant: str = "fp32"):
    """(loss, router agreement). Under ``"untied_head"`` the head reads a
    copy of the table that hands no gradient back."""
    x, agree = hidden(w, tokens, s, choices, variant)
    table = (jax.lax.stop_gradient(w["embed"]) if variant == "untied_head"
             else w["embed"])
    return head_loss(x, w["norm"], table, tokens, s.eps, variant), agree


# ---- the step

def decays(w):
    """1.0 where weight decay applies, 0.0 on norm scales and the
    selection bias, in the tree's shape."""
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
def head_backward(x, norm, embed, tokens, *, eps: float, variant: str):
    """(loss, d x, d norm, the HEAD's part of d embed) of the final norm,
    head and loss."""
    loss, (dx, dnorm, dembed) = jax.value_and_grad(
        lambda *a: head_loss(*a, tokens, eps, variant), argnums=(0, 1, 2))(
            x, norm, embed)
    return loss, dx, dnorm, dembed


@functools.partial(jax.jit, donate_argnums=(0,))
def _embed_backward(acc, tokens, dx):
    """``acc`` + the EMBEDDING's part of d embed."""
    return acc.at[tokens].add(dx)


@functools.partial(jax.jit, static_argnames=("clip",), donate_argnums=(0,))
def _clip(g, *, clip: float):
    c = jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(_sq(g)), 1e-12))
    return jax.tree.map(lambda x: x * c, g)


def gradient(w, tokens, choices, *, s: Shape, r: Recipe, variant: str = "fp32"):
    """(clipped gradient, loss, router agreement)."""
    bsz, t = tokens.shape
    routed = _routed(s)
    # (the tying is the head's and the embedding's: the layers' programs
    # are the sound run's)
    head_variant, variant = variant, "fp32" if variant == "untied_head" else variant

    def choice_of(i, b):
        if choices is None or routed[i] is None:
            return None
        return choices[routed[i]].reshape(bsz, t, -1)[b]

    xs, agrees = [w["embed"][tokens]], []
    for i, (lw, kinds) in enumerate(zip(w["layers"], s.layers)):
        outs = [layer_forward(xs[-1][b:b + 1], lw, choice_of(i, b), kinds=kinds,
                              s=s, variant=variant) for b in range(bsz)]
        xs.append(jnp.concatenate([y for y, _ in outs], 0))
        if routed[i] is not None:
            agrees.append(jnp.mean(jnp.stack([a for _, a in outs])))
    loss, dx, dnorm, dembed = head_backward(
        xs.pop(), w["norm"], w["embed"], tokens, eps=s.eps, variant=variant)
    if head_variant == "untied_head":
        dembed = jnp.zeros_like(dembed)
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
    # ONE leaf: the head's part + the embedding's
    g = {"embed": _embed_backward(dembed, tokens, dx), "norm": dnorm,
         "layers": g_layers}
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
