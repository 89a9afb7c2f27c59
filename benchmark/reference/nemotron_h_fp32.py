"""The plain reference of the ``nemotron_h`` decoder's training step:
forward, next-token loss, gradient (``jax.grad``), clip and AdamW in
float32 under ``jax.default_matmul_precision("highest")``. It imports
nothing of the program and shares no algorithm with it where the program
has one of its own: the state-space recurrence is written as it is
defined, ONE token at a time under ``lax.scan`` (no chunks: a chunking
fault in the program cannot be shared; the scan is cut into stretches of
128 tokens only so that its backward keeps one state a stretch and not
one a token), the convolution is four shifted products, the attention is
a masked softmax over whole rows of keys, a block of queries and one
key/value group at a time, the experts are a ``lax.scan`` over the ones
held.

Source: ``config.json`` of nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
(``model_type`` ``nemotron_h``) for every size, and the ``nemotron_h``
block of the public ``transformers`` implementation with Mamba-2's
recurrence, from memory, for what the config's keys do not settle (the
configuration's file lists those under ``assumed``). Left out: any
balancing loss and the rule that moves the router's selection bias
between steps; the config has no multi-token head.

**The blocks, for one sequence x of [T, D]** (D 2688). RMSNorm is
n(x) = x / sqrt(mean(x^2) + eps) * w, eps 1e-5, w from ones. No
projection has a bias. Block i is ONE of three, as
``hybrid_override_pattern[i]`` says (M, *, E), x' = x + f(n_i(x)) with its
own n_i:

1. **M, Mamba-2** (H = 64 heads of P = 64: an inner width of 4096; a
   state of N = 128; G = 8 groups). h = n(x). [z ; xBC ; dt] = W_in h,
   widths 4096 | 6144 | 64 in THAT order (``assumed``).
   xBC' = SiLU(c + sum_{j=0..3} K[j] * xBC[t - 3 + j]) (depthwise,
   causal, zeros before the sequence, taps K of [4, 6144], bias c);
   [u ; B ; C] = xBC', widths 4096 | 1024 | 1024 (``assumed``); u as 64
   heads of 64, B and C as 8 groups of 128; head i reads group i // 8.
   Delta[t, i] = softplus(dt[t, i] + dt_bias_i) (the public clamp to
   ``time_step_limit`` (0, inf) changes nothing: ``assumed``);
   a_i = -exp(A_log_i). Per head, with S_0 = 0 in R^{64 x 128}:
       S_t = exp(Delta_t a) S_{t-1} + Delta_t u_t B_t^T
       y_t = S_t C_t + D_i u_t.
   g = y * SiLU(z) (4096 wide), then normalised over each GROUP of
   4096 / 8 = 512 channels: g / sqrt(mean_512(g^2) + eps) * w — the gate
   BEFORE the norm (``assumed``); f = W_out (that).
2. **`*`, attention** (32 query heads on 2 key/value heads of 128).
   h = n(x); q = W_q h, k = W_k h, v = W_v h; query head i reads key/value
   head i // 16; o[t, i] = softmax_{s <= t}(q_i[t] . k[s] / sqrt(128)) v[s];
   f = W_o o. No bias, no window, no gate, NO turn of q or k (``assumed``:
   the public ``nemotron_h`` attention applies none; ``rope_theta`` in the
   config is read by nothing).
3. **E, routed.** g = n(x); s = sigmoid(W_r g) over all E = 128 experts;
   C = the 6 largest of s + b (b: ``e_score_correction_bias``, no
   gradient, moved by no rule in the step; ONE group, so the
   group-limited step selects everything); w = 2.5 * s[C] / (sum s[C] +
   1e-20) (``assumed``: the public code's normaliser);
   f = sum over e in C held here of w_e W2_e relu(W1_e g)^2, width 1856,
   + W2_s relu(W1_s g)^2, ONE un-gated MLP of width 3712, every token,
   weight 1.
4. After the last block: n_f, logits = W_head n_f(x) (untied).

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
wd 0 on every norm scale, on ``A_log``, ``dt_bias``, ``D``, the
convolution's bias and the selection bias (whose gradient is 0: it stays
where the seed put it).

Weights, one dict: ``embed`` [V, D], ``head`` [D, V], ``norm`` [D],
``layers``: a list of {``norm``, ``mixer``} (M, *) or {``norm``, ``ffn``}
(E); an M ``mixer``: {``win`` [D, 2 HP + 2 GN + H], ``conv``
[4, HP + 2 GN], ``conv_bias``, ``A_log`` [H], ``dt_bias`` [H], ``D`` [H],
``gnorm`` [HP], ``wout`` [HP, D]}; a * ``mixer``: {``wq`` [D, 32 x 128],
``wk``, ``wv`` [D, 2 x 128], ``wo``}; an ``ffn``: {``router`` [D, E],
``router_bias`` [E], ``w1`` [held, D, F], ``w2`` [held, F, D],
``shared``: {``w1`` [D, 2 F], ``w2`` [2 F, D]}}.

``variant`` makes the controls of the configuration's ``check``.
``"bf16"`` is the nearest precision below the one the configuration
states: the configuration runs bfloat16 matmuls and activations and keeps
a float32 set (the scan's state, decays and Delta, the softmax, the
router, the norms' statistics, the loss); the control lowers that whole
set to bfloat16 too — every block and the head take their input and their
weights rounded to bfloat16 and compute in it; gradients come back in
float32 to float32 master weights, clip and AdamW. ``"norm_then_gate"``
is the other convention Mamba-2 code has: SiLU(z) applied AFTER the
grouped norm. ``"one_group"``: every head reads group 0's B and C.
``"relu"``: the experts' activation without the square. ``"drop_expert"``
leaves the last held expert out (an eighth of a routed leaf at the
configuration's size).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NO_DECAY = ("norm", "gnorm", "router_bias", "A_log", "dt_bias", "D",
            "conv_bias")
VARIANTS = ("fp32", "bf16", "norm_then_gate", "one_group", "relu",
            "drop_expert")
ROUTER_EPS = 1e-20
STRETCH = 128  # tokens whose states the scan's backward makes again at a time


@dataclasses.dataclass(frozen=True)
class Shape:
    """What the equations need beyond the weights' own shapes (the
    ``shape`` group of the configuration's file)."""

    layers: tuple             # (("ssm" | "full_attn", None) | (None, "moe"), ...)
    heads: int
    kv_heads: int
    mamba_heads: int
    mamba_head_dim: int
    groups: int
    state: int
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
    """The recipe's numbers (``configs/train/nemotron3_nano_ep16.yaml``)."""

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


# ---- the blocks

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def recurrence(u, bm, cm, delta, a):
    """Step 1's recurrence as written, a token at a time: u
    [B, T, G, J, P] (J heads a group), bm and cm [B, T, G, N], delta
    [B, T, G, J], a [G, J] -> S_t C_t [B, T, G, J, P], in the type they
    come in."""
    bsz, t, g, j, p = u.shape

    def token(state, xs):
        ut, bt, ct, dt = xs
        state = jnp.exp(dt * a)[..., None, None] * state \
            + (dt[..., None] * ut)[..., None] * bt[:, :, None, None, :]
        return state, jnp.sum(state * ct[:, :, None, None, :], -1)

    @jax.checkpoint
    def stretch(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = (-t) % STRETCH

    def cut(x):  # [B, T, ...] -> [stretches, STRETCH, B, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((-1, STRETCH) + x.shape[1:])

    # (a padded token has delta 0: it neither decays nor writes)
    _, y = jax.lax.scan(stretch, jnp.zeros((bsz, g, j, p, bm.shape[-1]), u.dtype),
                        (cut(u), cut(bm), cut(cm), cut(delta)))
    return jnp.moveaxis(y.reshape((t + pad,) + y.shape[2:]), 0, 1)[:, :t]


def mamba2(x, m, s: "Shape", variant: str):
    """x [B, T, D] the normed input: step 1, the convolution as four
    shifted products."""
    bsz, t, _ = x.shape
    h, p, g, n = s.mamba_heads, s.mamba_head_dim, s.groups, s.state
    inner, gn = h * p, g * n
    plane = x @ m["win"]
    z, xbc, dt = (plane[..., :inner], plane[..., inner:2 * inner + 2 * gn],
                  plane[..., 2 * inner + 2 * gn:])
    width = m["conv"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    xbc = jax.nn.silu(m["conv_bias"] + sum(
        m["conv"][j] * padded[:, j:j + t] for j in range(width)))
    u = xbc[..., :inner].reshape(bsz, t, g, h // g, p)
    bm = xbc[..., inner:inner + gn].reshape(bsz, t, g, n)
    cm = xbc[..., inner + gn:].reshape(bsz, t, g, n)
    if variant == "one_group":
        bm, cm = (jnp.broadcast_to(v[:, :, :1], v.shape) for v in (bm, cm))
    delta = jax.nn.softplus(dt + m["dt_bias"]).reshape(bsz, t, g, h // g)
    a = -jnp.exp(m["A_log"]).reshape(g, h // g)
    y = recurrence(u, bm, cm, delta, a) + m["D"].reshape(g, h // g, 1) * u
    y = y.reshape(bsz, t, inner)

    def group_norm(v):
        v = v.reshape(bsz, t, g, inner // g)
        v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True) + s.eps)
        return v.reshape(bsz, t, inner) * m["gnorm"]

    if variant == "norm_then_gate":
        y = group_norm(y) * jax.nn.silu(z)
    else:
        y = group_norm(y * jax.nn.silu(z))
    return y @ m["wout"]


def attention(x, m, s: "Shape", block: int = 64):
    """x [B, T, D] the normed input: step 2."""
    bsz, t, _ = x.shape
    h, hk = s.heads, s.kv_heads
    q = (x @ m["wq"]).reshape(bsz, t, h, -1)
    d = q.shape[-1]
    k = (x @ m["wk"]).reshape(bsz, t, hk, d)
    v = (x @ m["wv"]).reshape(bsz, t, hk, d)

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


@functools.partial(jax.checkpoint, static_argnums=(3,))
def mlp(x, w1, w2, variant: str):
    """W2 relu(W1 x)^2 (``"relu"``: without the square)."""
    hidden = jax.nn.relu(x @ w1)
    return (hidden if variant == "relu" else jnp.square(hidden)) @ w2


def route(x, f, s: "Shape", choice=None):
    """(choice [N, top_k], weight [N, top_k], agreement): step 3's rule.
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


def experts(x, f, s: "Shape", choice, variant: str, with_shared: bool = True):
    """(y, share of ``choice`` this router agrees with). x [N, D]. The
    held experts' part plus (``with_shared``) the shared MLP's."""
    choice, weight, agree = route(x, f, s, choice)
    held = f["w1"].shape[0] - (1 if variant == "drop_expert" else 0)

    def add(y, held_expert):
        """One held expert after the other (a ``lax.scan``: the compiler
        holds one expert's [tokens, D] result at a time, in the backward
        pass too)."""
        w1, w2, e = held_expert
        w_e = jnp.sum(jnp.where(choice == s.first_expert + e, weight, 0.0), -1)
        return y + w_e[:, None].astype(x.dtype) * mlp(x, w1, w2, variant), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x), (
        f["w1"][:held], f["w2"][:held], jnp.arange(held)))
    if with_shared:
        y = y + mlp(x, f["shared"]["w1"], f["shared"]["w2"], variant)
    return y, agree


def _lowered(variant: str, *trees):
    """The trees as the control computes on them: rounded to bfloat16
    under ``"bf16"``, as they came otherwise."""
    if variant != "bf16":
        return trees
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16), trees)


def layer(x, lw, kinds, s: "Shape", choice, variant: str):
    """One block: x + f(n(x)), f the ONE sublayer ``kinds`` names."""
    stream = x.dtype
    x, lw = _lowered(variant, x, lw)
    y = rms_norm(x, lw["norm"], s.eps)
    agree = jnp.ones((), jnp.float32)
    if kinds[0] == "ssm":
        out = x + mamba2(y, lw["mixer"], s, variant)
    elif kinds[0] == "full_attn":
        out = x + attention(y, lw["mixer"], s)
    else:
        f, agree = experts(y.reshape(-1, y.shape[-1]), lw["ffn"], s, choice,
                           variant)
        out = x + f.reshape(x.shape)
    return out.astype(stream), agree.astype(jnp.float32)


def _routed(s: "Shape") -> list:
    """For each block its place among the routed blocks, None for a
    mixer's (``choices`` is stacked over the routed blocks alone)."""
    at, out = 0, []
    for _, ffn in s.layers:
        out.append(at if ffn == "moe" else None)
        at += ffn == "moe"
    return out


def hidden(w, tokens, s: "Shape", choices=None, variant: str = "fp32"):
    """(the last block's output [B, T, D], mean router agreement of the
    routed blocks). ``choices``: [routed blocks, B*T, top_k] or None."""
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


def logits(w, tokens, s: "Shape", choices=None):
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


def loss_fn(w, tokens, s: "Shape", choices=None, variant: str = "fp32"):
    """(loss, router agreement)."""
    x, agree = hidden(w, tokens, s, choices, variant)
    return head_loss(x, w["norm"], w["head"], tokens, s.eps, variant), agree


# ---- the step

def decays(w):
    """1.0 where weight decay applies, 0.0 on ``NO_DECAY``, in the tree's shape."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: 0.0 if str(getattr(path[-1], "key", path[-1])) in NO_DECAY
        else 1.0, w)


def _sq(tree):
    return sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree.leaves(tree))


# The gradient of ``loss_fn``, block by block: one compiled call a block
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


@functools.partial(jax.jit, static_argnames=("rows",))
def _embed_backward(tokens, dx, *, rows: int):
    return jnp.zeros((rows, dx.shape[-1]), dx.dtype).at[tokens].add(dx)


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
    g = {"embed": _embed_backward(tokens, dx, rows=w["embed"].shape[0]),
         "head": dhead, "norm": dnorm, "layers": g_layers}
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
