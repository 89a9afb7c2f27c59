"""The plain reference of the ``deepseek_v3`` decoder's training step as
kanana-2-30b-a3b-instruct-2601 configures it: forward, next-token loss,
gradient (``jax.grad``), clip and AdamW in float32 under
``jax.default_matmul_precision("highest")``. It imports nothing of the
program and shares no algorithm with it where the program has one of its
own: the rotary turn is written out from sines and cosines on the
PUBLISHED channel order (the turned pair goes back to channels 2i and
2i + 1), the attention is a masked softmax over whole rows of keys, a
block of queries at a time, the shared key is repeated over the heads by
broadcasting, the experts are a ``lax.scan`` over the ones held.

Source: ``config.json`` of kakaocorp/kanana-2-30b-a3b-instruct-2601
(``model_type`` ``deepseek_v3``) for every size, and the ``deepseek_v3``
decoder layer of the public ``transformers`` implementation with the
query's low-rank pair left out (``q_lora_rank`` null), from memory, for
what the config's keys do not settle (the configuration's file lists
those under ``assumed``). Left out: any balancing loss, any multi-token
head (the config has none) and the rule that moves the router's selection
bias between steps.

**The layer, for one sequence x of [T, D]** (D 2048). RMSNorm is
n(x) = x / sqrt(mean(x^2) + eps) * w, eps 1e-6, w from ones. No bias
anywhere. Every layer's mixer is latent attention.

1. h = n_in(x) (``input_layernorm``; here ``norm1``).
2. q = W_q h -> [T, H, d_nope + d_rope] (H 32, 128 + 64), a head
   [q_nope ; q_pe]. [c ; kpe] = W_kva h (D -> r + d_rope, r 512);
   c' = n_r(c) (``kv_a_layernorm``, the same eps);
   [k_nope ; v] = W_kvb c' -> [T, H, d_nope + d_v] (d_v 128).
3. Rotary, ``rope_interleave``: for i in 0..d_rope/2 - 1, angle
   a_i(t) = t * theta^(-2i / d_rope), theta 1e6, t = 0..T-1; channels
   (2i, 2i + 1) of every q_pe head and of the ONE kpe turn together:
   (u, w) -> (u cos a - w sin a, u sin a + w cos a), written back to
   channels 2i and 2i + 1. (The public code then moves evens before odds,
   in q and k alike: the scores are the same.) No scaling of positions or
   of the softmax (``rope_scaling`` null).
4. k_i = [k_nope_i ; kpe] for every head i (ONE kpe for all H);
   o[t, i] = softmax_{s <= t}(q_i[t] . k_i[s] / sqrt(d_nope + d_rope))
   v_i[s]; y = W_o o (H d_v -> D). No gate, no window.
5. x' = x + y; g = n_post(x') (``post_attention_layernorm``; ``norm2``).
6. The first ``first_k_dense_replace`` layers: f = W_3 (SiLU(a) * b),
   [a ; b] = W_12 g, width ``intermediate_size``. Every later layer,
   routed: s = sigmoid(W_r g) over all E experts; C = the top_k largest of
   s + bias (``e_score_correction_bias``: no gradient, moved by no rule in
   the step; ``n_group`` 1: the group-limited step selects every expert);
   w = ``routed_scaling_factor`` * s[C] / (sum s[C] + 1e-20) (``assumed``:
   the public code's normaliser); f = sum over e in C held here of
   w_e SwiGLU_e(g), experts of width ``moe_intermediate_size``, +
   SwiGLU_shared(g), ONE gated MLP of width ``n_shared_experts`` x that
   (``assumed``: the public code's), every token, weight 1.
7. x'' = x' + f. After the last layer: n_out, logits = W_head n_out(x)
   (untied).

This shard holds the experts ``[first, first + held)``; what the others
would add is left out, and the shared part is counted here, once.
``choices`` hands C in from outside: seed-made routers put many of the
k-th and (k+1)-th scores within rounding of each other, so a program in
another precision picks differently for a share of the tokens, and a
reference that is to be laid against it follows ITS choice. The share of
C the reference's own router agrees with is returned beside it.

**Loss.** Mean over sequences b and positions t < T-1 of
logsumexp(z_bt) - z_bt[token_{b,t+1}] over the vocabulary held (a slice
of the published one is a smaller vocabulary), float32.

**Step.** Global-norm clip of the whole gradient, then AdamW: m, v
moments with bias correction, p <- p - lr (m^ / (sqrt(v^) + eps) + wd p),
wd 0 on every norm scale and on the selection bias (whose gradient is 0:
it stays where the seed put it).

Weights, one dict: ``embed`` [V, D], ``head`` [D, V], ``norm`` [D],
``layers``: a list of {``norm1``, ``norm2``, ``mixer``, ``ffn``}; a
``mixer``: {``wq`` [D, H (d_nope + d_rope)], ``wkva`` [D, r + d_rope],
``kv_norm`` [r], ``wkvb`` [r, H (d_nope + d_v)], ``wo`` [H d_v, D]}; a
dense ``ffn``: {``w12`` [D, 2 F], ``w3`` [F, D]}; a routed one:
{``router`` [D, E], ``router_bias`` [E], ``w12`` [held, D, 2 F'], ``w3``
[held, F', D], ``shared``: {``w12`` [D, 4 F'], ``w3`` [2 F', D]}}.

``variant`` makes the controls of the configuration's ``check``.
``"bf16"`` is the nearest precision below the one the configuration
states: the configuration runs bfloat16 matmuls and activations and keeps
a float32 set (the rotary turn, the router, the softmax, the norms'
statistics, the loss); the control lowers that whole set to bfloat16 too —
every layer and the head take their input and their weights rounded to
bfloat16 and compute in it; gradients come back in float32 to float32
master weights, clip and AdamW. ``"no_rope"`` is the mechanism left out:
no channel is turned (``kimi_linear``'s latent layer under this model's
name). ``"rotate_half"`` turns channel j with j + d_rope / 2 in place of
2i with 2i + 1: a layout that would load no published checkpoint.
``"drop_expert"`` leaves the last held expert out (a sixteenth of a
routed leaf at the configuration's size).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NO_DECAY = ("norm", "norm1", "norm2", "kv_norm", "router_bias")
VARIANTS = ("fp32", "bf16", "no_rope", "rotate_half", "drop_expert")
ROUTER_EPS = 1e-20


@dataclasses.dataclass(frozen=True)
class Shape:
    """What the equations need beyond the weights' own shapes (the
    ``shape`` group of the configuration's file)."""

    layers: tuple             # (("mla", "dense" | "moe"), ...)
    heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    top_k: int
    routed_scaling_factor: float
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
    """The recipe's numbers (``configs/train/kanana2_ep8.yaml``)."""

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


def rotate(z, theta: float, variant: str):
    """z [B, T, H, d]: step 3, token t turned by t * theta^(-2i/d) on the
    channel pair (2i, 2i + 1); in the type z comes in."""
    if variant == "no_rope":
        return z
    t, d = z.shape[1], z.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = (f(angle)[None, :, None, :].astype(z.dtype)
                for f in (jnp.cos, jnp.sin))
    if variant == "rotate_half":
        u, w = z[..., :d // 2], z[..., d // 2:]
        return jnp.concatenate([u * cos - w * sin, u * sin + w * cos], -1)
    u, w = z[..., 0::2], z[..., 1::2]
    return jnp.stack([u * cos - w * sin, u * sin + w * cos], -1).reshape(z.shape)


def attention(x, m, s: Shape, variant: str, block: int = 128):
    """x [B, T, D] the normed input: steps 2-4."""
    bsz, t, _ = x.shape
    h, nope, rope = s.heads, s.qk_nope_head_dim, s.qk_rope_head_dim
    q = (x @ m["wq"]).reshape(bsz, t, h, nope + rope)
    kva = x @ m["wkva"]
    c, kpe = kva[..., :s.kv_lora_rank], kva[..., s.kv_lora_rank:]
    kvb = (rms_norm(c, m["kv_norm"], s.eps) @ m["wkvb"]).reshape(
        bsz, t, h, nope + s.v_head_dim)
    q = jnp.concatenate([
        q[..., :nope], rotate(q[..., nope:], s.rope_theta, variant)], -1)
    kpe = rotate(kpe[:, :, None, :], s.rope_theta, variant)   # ONE key head
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        kpe, (bsz, t, h, rope))], -1)
    v = kvb[..., nope:]

    @jax.checkpoint
    def rows(args):
        """Softmax over the whole row of keys, the later ones masked."""
        qb, first = args
        z = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(nope + rope)
        # (the rows padded on past the last token stand at the last
        # token: a row that sees no key at all is NaN)
        at = jnp.minimum(first + jnp.arange(qb.shape[1]), t - 1)[:, None]
        z = jnp.where(jnp.arange(t)[None, :] <= at, z, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(z, -1), v)

    # one block of queries after the other (``lax.map``: the compiler
    # holds one block's [heads, block, T] scores, not all of them)
    pad = (-t) % block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(qp.reshape(bsz, -1, block, h, nope + rope), 1, 0)
    o = jax.lax.map(rows, (blocks, jnp.arange(blocks.shape[0]) * block))
    o = jnp.moveaxis(o, 0, 1).reshape(bsz, t + pad, h, -1)[:, :t]
    return o.reshape(bsz, t, -1) @ m["wo"]


@jax.checkpoint
def expert(x, w12, w3, weight):
    """weight * W3 (SiLU(a) * b), [a ; b] = W12 x: one expert on every
    token, each token's result times its routing weight (0 where the
    token did not choose it)."""
    return weight[:, None] * swiglu(x, w12, w3)


def route(x, f, s: Shape, choice=None):
    """(choice [N, top_k], weight [N, top_k], agreement): step 6's rule.
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
    # the shared experts, ONE gated MLP, every token, weight 1
    return y + swiglu(x, f["shared"]["w12"], f["shared"]["w3"]), agree


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
        return x + attention(y, m, s, variant)

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


def loss_fn(w, tokens, s: Shape, choices=None, variant: str = "fp32"):
    """(loss, router agreement)."""
    x, agree = hidden(w, tokens, s, choices, variant)
    return head_loss(x, w["norm"], w["head"], tokens, s.eps, variant), agree


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
    routed = _routed(s)
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
    loss, dx, dnorm, dhead = head_backward(
        xs.pop(), w["norm"], w["head"], tokens, eps=s.eps, variant=variant)
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
