"""The plain reference of the ``kimi_linear`` decoder's training step:
forward, next-token loss, gradient (``jax.grad``), clip and AdamW in
float32 under ``jax.default_matmul_precision("highest")``. It imports
nothing of the program and shares no algorithm with it where the program
has one of its own: the delta rule is the RECURRENCE, token by token, the
attention is a masked softmax over whole rows of keys, the experts are a
loop over the ones held.

Source: ``config.json`` of moonshotai/Kimi-Linear-48B-A3B-Instruct
(``model_type`` ``kimi_linear``) for every size, and the model's report
and released code for what the config does not say (the configuration's
file lists those under ``assumed``).

**Symbols.** x_t in R^D is a layer's input at token t, n(x) = RMSNorm(x)
= x / sqrt(mean(x^2) + eps) * scale, eps 1e-5. Layers are pre-norm and
residual: x <- x + Mixer(n1(x)); x <- x + FFN(n2(x)).

**KDA** (Kimi Delta Attention), H heads, d_k = d_v = d; x = n1(x_t):

    q_t = L2norm(SiLU(conv(W_q x)_t)) * d^-0.5     conv: causal, depthwise,
    k_t = L2norm(SiLU(conv(W_k x)_t))              width W: y_t = sum_j c_j u_{t-W+1+j}
    v_t = SiLU(conv(W_v x)_t)                      L2norm(u) = u / sqrt(sum u^2 + 1e-24)
    a_t = exp(-exp(A_log_h) * softplus(W_f2 W_f1 x_t + dt_bias))   in (0,1)^d, per key channel
    b_t = sigmoid(w_b x_t)
    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,   S_0 = 0, S in R^{d x d}
    o_t = S_t^T q_t
    y_t = W_o [ RMSNorm_head(o_t) * sigmoid(W_g2 W_g1 x_t) ]

**MLA** without rotary (``mla_use_nope``), H heads; x = n1(x_t):

    q_t = W_q x_t in R^{H x (d_nope + d_rope)}
    [c_t ; kpe_t] = W_kva x_t          (kv_lora_rank + d_rope)
    [k_nope ; v] = W_kvb n(c_t)        (H x (d_nope + d_v))
    k = [k_nope ; kpe_t, the same for every head]
    y_t = W_o softmax_{s <= t}(q_t k_s / sqrt(d_nope + d_rope)) v_s

**FFN.** SwiGLU(x; W12, W3) = W3 (SiLU(g) * u), [g ; u] = W12 x. The
leading layers: one of width ``intermediate_size``. The others, over E
experts of which this shard holds ``[first, first + held)``:

    s = sigmoid(W_r x) in R^E
    C = the top_k largest of s + bias       (the bias takes no gradient)
    w_e = scale * s_e / sum_{c in C} s_c    for e in C
    y = sum_{e in C, e held here} w_e SwiGLU_e(x) + SwiGLU_shared(x)

``choices`` hands C in from outside: seed-made routers put many of the
k-th and (k+1)-th scores within rounding of each other, so a program in
another precision picks differently for a share of the tokens, and a
reference that is to be laid against it follows ITS choice (as the SSL
step's reference follows the program's stochastic-depth draws). The
share of C the reference's own router agrees with is returned beside it.

**Loss.** Mean over sequences b and positions t < T-1 of
logsumexp(z_bt) - z_bt[token_{b,t+1}], z = W_head n(x) over the
vocabulary held (a slice of the published one is a smaller vocabulary).

**Step.** Global-norm clip of the whole gradient, then AdamW: m, v
moments with bias correction, p <- p - lr (m^ / (sqrt(v^) + eps) + wd p),
wd 0 on every norm scale, ``A_log``, ``dt_bias`` and the router bias.

Weights, one dict: ``embed`` [V, D], ``head`` [D, V], ``norm`` [D],
``layers``: a list of {``norm1``, ``norm2``, ``mixer``, ``ffn``} with the
leaf names used below.

``variant`` makes the controls of the configuration's ``check``.
``"bf16"`` is the nearest precision below the one the configuration
states: the configuration runs bfloat16 matmuls and activations and keeps
a float32 set (KDA's decays, chunk products and state, the router, the
softmax, the norms' statistics, the loss); the control lowers that whole
set to bfloat16 too — every layer and the head take their input and
their weights rounded to bfloat16 and compute in it, the delta rule's
state included; gradients come back in float32 to float32 master weights,
clip and AdamW, as the configuration states for those. ``"drop_expert"``
is a planted fault, the last held expert left out.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NO_DECAY = ("norm", "norm1", "norm2", "o_norm", "kv_norm", "A_log",
            "dt_bias", "router_bias")
VARIANTS = ("fp32", "bf16", "drop_expert")


@dataclasses.dataclass(frozen=True)
class Shape:
    """What the equations need beyond the weights' own shapes (the
    ``shape`` group of the configuration's file)."""

    layers: tuple             # (("kda" | "mla", "dense" | "moe"), ...)
    kda_heads: int
    mla_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    top_k: int
    routed_scaling_factor: float
    first_expert: int         # the experts held: [first, first + held)
    eps: float = 1e-5

    @classmethod
    def from_config(cls, group: dict) -> "Shape":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in group.items() if k in fields}
        kw["layers"] = tuple(tuple(x) for x in kw["layers"])
        return cls(**kw)


@dataclasses.dataclass(frozen=True)
class Recipe:
    """The recipe's numbers (``configs/train/kimi_linear_ep32.yaml``)."""

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


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-24)


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
    q, k, a [B, T, H, d]; v [B, T, H, d]; b [B, T, H]. ``lax.scan`` over
    tokens inside a rematerialised scan over blocks of tokens, so the
    backward pass holds one state a block and not one a token."""
    bsz, t, h, d = q.shape
    pad = (-t) % block
    if pad:  # tokens that neither decay nor write; their outputs are cut
        grow = lambda x, fill: jnp.pad(  # noqa: E731
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2), constant_values=fill)
        q, k, v, a, b = grow(q, 0), grow(k, 0), grow(v, 0), grow(a, 1), grow(b, 0)

    def token(s, xs):
        qt, kt, vt, at, bt = xs
        s = at[..., None] * s
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


def kda(x, m, s: Shape):
    bsz, t, _ = x.shape
    h = s.kda_heads
    heads = lambda u: u.reshape(bsz, t, h, -1)  # noqa: E731
    # (each line rematerialised by itself: the backward pass then holds
    # the [tokens, heads * d] planes these lines end in, not the ones
    # they pass through)
    act = jax.checkpoint(lambda w, c: heads(jax.nn.silu(causal_conv(x @ w, c))))
    q = l2_norm(act(m["wq"], m["cq"])) * (m["cq"].shape[-1] // h) ** -0.5
    k = l2_norm(act(m["wk"], m["ck"]))
    v = act(m["wv"], m["cv"])
    a = jax.checkpoint(lambda f1, f2, bias, a_log: jnp.exp(
        -jnp.exp(a_log)[:, None] * heads(jax.nn.softplus(x @ f1 @ f2 + bias))))(
            m["wf1"], m["wf2"], m["dt_bias"], m["A_log"])
    b = jax.nn.sigmoid(x @ m["wb"])
    o = delta_rule(q, k, v, a, b)
    gated = jax.checkpoint(lambda o, g1, g2, scale: (
        rms_norm(o, scale, s.eps) * jax.nn.sigmoid(heads(x @ g1 @ g2))
    ).reshape(bsz, t, -1))
    return gated(o, m["wg1"], m["wg2"], m["o_norm"]) @ m["wo"]


def mla(x, m, s: Shape, block: int = 256):
    bsz, t, _ = x.shape
    h, nope, rope = s.mla_heads, s.qk_nope_head_dim, s.qk_rope_head_dim
    q = (x @ m["wq"]).reshape(bsz, t, h, nope + rope)
    kva = x @ m["wkva"]
    c, kpe = kva[..., :s.kv_lora_rank], kva[..., s.kv_lora_rank:]
    kvb = (rms_norm(c, m["kv_norm"], s.eps) @ m["wkvb"]).reshape(
        bsz, t, h, nope + s.v_head_dim)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        kpe[:, :, None], (bsz, t, h, rope))], -1)
    v = kvb[..., nope:]

    @jax.checkpoint
    def rows(args):
        """Softmax over the whole row of keys, the later ones masked."""
        qb, first = args
        z = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(nope + rope)
        at = first + jnp.arange(qb.shape[1])
        z = jnp.where(jnp.arange(t)[None, :] <= at[:, None], z, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(z, -1), v)

    # one block of queries after the other (``lax.map``: the compiler
    # holds one block's [heads, block, T] scores, not all of them)
    pad = (-t) % block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(qp.reshape(bsz, -1, block, h, nope + rope), 1, 0)
    o = jax.lax.map(rows, (blocks, jnp.arange(blocks.shape[0]) * block))
    o = jnp.moveaxis(o, 0, 1).reshape(bsz, t + pad, h, -1)[:, :t]
    return o.reshape(bsz, t, -1) @ m["wo"]


def experts(x, f, s: Shape, choice, variant: str):
    """(y, share of ``choice`` this router agrees with). x [N, D];
    ``choice`` [N, top_k] int32 or None (the router's own)."""
    scores = jax.nn.sigmoid(x @ f["router"])
    _, own = jax.lax.top_k(scores + jax.lax.stop_gradient(f["router_bias"]), s.top_k)
    if choice is None:
        choice = own
    agree = jnp.mean(jnp.any(choice[:, :, None] == own[:, None, :], -1))
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    weight = s.routed_scaling_factor * picked / jnp.sum(picked, -1, keepdims=True)
    held = f["w12"].shape[0] - (1 if variant == "drop_expert" else 0)
    y = swiglu(x, f["shared"]["w12"], f["shared"]["w3"])
    for e in range(held):
        w_e = jnp.sum(jnp.where(choice == s.first_expert + e, weight, 0.0), -1)
        y = y + w_e[:, None] * swiglu(x, f["w12"][e], f["w3"][e])
    return y, agree


def _lowered(variant: str, *trees):
    """The trees as the control computes on them: rounded to bfloat16
    under ``"bf16"``, as they came otherwise."""
    if variant != "bf16":
        return trees
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16), trees)


def layer(x, lw, kinds, s: Shape, choice, variant: str):
    mixer, ffn = kinds
    stream = x.dtype
    x, lw = _lowered(variant, x, lw)
    y = rms_norm(x, lw["norm1"], s.eps)
    x = x + (kda(y, lw["mixer"], s) if mixer == "kda" else mla(y, lw["mixer"], s))
    y = rms_norm(x, lw["norm2"], s.eps)
    if ffn == "dense":
        out, agree = swiglu(y, lw["ffn"]["w12"], lw["ffn"]["w3"]), jnp.float32(1.0)
    else:
        out, agree = experts(y.reshape(-1, y.shape[-1]), lw["ffn"], s, choice,
                             variant)
    return (x + out.reshape(x.shape)).astype(stream), agree.astype(jnp.float32)


def hidden(w, tokens, s: Shape, choices=None, variant: str = "fp32"):
    """(the last layer's output [B, T, D], mean router agreement).
    ``choices``: [routed layers, B*T, top_k] or None."""
    x = w["embed"][tokens]
    run = jax.checkpoint(layer, static_argnums=(2, 3, 5))
    agrees, j = [], 0
    for lw, kinds in zip(w["layers"], s.layers):
        choice = None
        if kinds[1] == "moe":
            choice = None if choices is None else choices[j]
            j += 1
        x, agree = run(x, lw, kinds, s, choice, variant)
        if kinds[1] == "moe":
            agrees.append(agree)
    agree = jnp.mean(jnp.stack(agrees)) if agrees else jnp.float32(1.0)
    return x, agree


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
    """1.0 where weight decay applies, 0.0 on norm scales, A_log, dt_bias
    and the router bias, in the tree's shape."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: 0.0 if str(getattr(path[-1], "key", path[-1])) in NO_DECAY
        else 1.0, w)


def _sq(tree):
    return sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree.leaves(tree))


# The gradient of ``loss_fn``, layer by layer: one compiled call a layer
# and sequence, forward and then backward, each holding one sequence's
# layer in float32 and nothing else. (``jax.grad(loss_fn)`` as one program
# asks the compiler for 17.5 GB at 2 x 8,192 tokens, whatever is
# rematerialised inside it: this sandbox, PR 27, compiled for a described
# v5e. The tests lay the two against each other at a small size.)

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
    routed = [k[1] == "moe" for k in s.layers]
    slot = np.cumsum(routed) - 1

    def choice_of(i, b):
        if not routed[i] or choices is None:
            return None
        return choices[slot[i]].reshape(bsz, t, -1)[b]

    xs, agrees = [w["embed"][tokens]], []
    for i, (lw, kinds) in enumerate(zip(w["layers"], s.layers)):
        outs = [layer_forward(xs[-1][b:b + 1], lw, choice_of(i, b), kinds=kinds,
                              s=s, variant=variant) for b in range(bsz)]
        xs.append(jnp.concatenate([y for y, _ in outs], 0))
        if routed[i]:
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
    agree = jnp.mean(jnp.stack(agrees)) if agrees else jnp.float32(1.0)
    return _clip(g, clip=r.clip_grad), loss, agree


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
