"""Plain reference of one DINOv3 pretraining step: ``jax.numpy``, float32,
matmul precision "highest". Nothing imported from the program, nothing the
program made: weights and batches come from the benchmark's seed, every
rule and number below from the published recipe (``Recipe``; the
configuration's file carries the numbers).

One step, as the paper and its reference implementation define it:

- teacher (no gradient): ViT on the 2 global crops of each image; DINO head
  on CLS, iBOT head on the masked patch tokens; Sinkhorn-Knopp (3 rounds)
  over all CLS rows, and over all masked tokens;
- student: ViT on the global crops with the masked patches replaced by the
  mask token, and on the local crops; stochastic depth by batch subset
  (the residual scales are an input: the step's random draws, as data);
  DINO head on every CLS, iBOT head on the masked tokens;
- loss: DINO cross-entropy over (student crop, teacher crop) pairs, same-
  crop pairs left out, global and local terms weighted by their pair
  counts; KoLeo on the student's global CLS, per crop; iBOT cross-entropy on
  the masked tokens, mean per image, mean over images;
- update: gradient clipped per sub-model (backbone, DINO head, iBOT head),
  AdamW with layer-wise learning-rate decay, a lower rate on the patch
  embedding, no weight decay on biases, norms and LayerScale, the
  prototype layers on their own (frozen-then-released) rate; the teacher is
  the EMA of the updated student.

Weights layout (all float32): ``{"backbone": {"patch_kernel" [p, p, C, D],
"patch_bias", "cls_token", "mask_token", "norm_scale", "norm_bias",
"blocks": {<name of reference/vit_fp32.py's block>: [depth, ...]}},
"dino_head": H, "ibot_head": H}``, ``H = {"w0", "b0", ..., "w<n-1>",
"b<n-1>", "prototypes"}``. No storage tokens (neither configuration has
any).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import vit_fp32


@dataclasses.dataclass(frozen=True)
class Recipe:
    """The recipe's numbers (``vitl_im1k_lin834.yaml`` and its defaults)."""

    patch_size: int = 16
    num_heads: int = 16
    rope_base: float = 100.0
    n_local_crops: int = 8
    student_temp: float = 0.1
    sinkhorn_rounds: int = 3
    dino_weight: float = 1.0
    ibot_weight: float = 1.0
    koleo_weight: float = 0.1
    clip_grad: float = 3.0
    beta1: float = 0.9
    beta2: float = 0.999
    adam_eps: float = 1e-8
    layerwise_decay: float = 0.9
    patch_embed_lr_mult: float = 0.2
    # schedules, per iteration
    global_batch: int = 12
    base_lr: float = 1e-3          # x 4 sqrt(global_batch / 1024)
    min_lr: float = 1e-6
    warmup_epochs: int = 10
    epochs: int = 100
    epoch_length: int = 1250
    weight_decay: float = 0.04
    weight_decay_end: float = 0.4
    momentum: float = 0.992
    final_momentum: float = 1.0
    warmup_teacher_temp: float = 0.04
    teacher_temp: float = 0.07
    warmup_teacher_temp_epochs: int = 30
    freeze_last_layer_epochs: int = 1

    @classmethod
    def from_config(cls, group: dict) -> "Recipe":
        return cls(**{k: type(getattr(cls, k))(v) for k, v in group.items()
                      if k in cls.__dataclass_fields__})

    def schedule(self, it: int) -> dict:
        """The step's scalars at iteration ``it`` (float32, as a trainer
        holds them): linear warm-up then cosine for the rate, cosine for
        weight decay and teacher momentum, linear warm-up then constant
        for the teacher temperature."""
        total = self.epochs * self.epoch_length
        warm = self.warmup_epochs * self.epoch_length
        peak = self.base_lr * 4.0 * math.sqrt(self.global_batch / 1024.0)

        def cosine(start, end, i, n):
            return end + 0.5 * (start - end) * (1.0 + math.cos(math.pi * i / n))

        lr = (peak * it / (warm - 1) if it < warm
              else cosine(peak, self.min_lr, it - warm, total - warm))
        warm_t = self.warmup_teacher_temp_epochs * self.epoch_length
        temp = (self.warmup_teacher_temp + (self.teacher_temp - self.warmup_teacher_temp)
                * it / (warm_t - 1) if it < warm_t else self.teacher_temp)
        frozen = it < self.freeze_last_layer_epochs * self.epoch_length
        out = {"lr": lr, "last_layer_lr": 0.0 if frozen else lr,
               "weight_decay": cosine(self.weight_decay, self.weight_decay_end, it, total),
               "momentum": cosine(self.momentum, self.final_momentum, it, total),
               "teacher_temp": temp}
        return {k: np.float32(v) for k, v in out.items()}


def _mm(x, w):
    return jnp.matmul(x, w, precision="highest")


def backbone(w, images, masks, scales, r: Recipe, precision: str = "fp32"):
    """[N, H, W, C] crops of one size -> (CLS [N, D], patch tokens
    [N, T, D]) after the final norm. ``masks`` [N, T] bool or None;
    ``scales`` [depth, 2, N] residual factors or None (no stochastic
    depth). One block's activations are kept per layer; the rest is
    recomputed in the backward pass."""
    N, H, W, C = images.shape
    p = r.patch_size
    hp, wp = H // p, W // p
    x = images.reshape(N, hp, p, wp, p, C).transpose(0, 1, 3, 2, 4, 5)
    k = w["patch_kernel"]
    t = _mm(x.reshape(N, hp * wp, p * p * C), k.reshape(p * p * C, -1)) + w["patch_bias"]
    if masks is not None:
        t = jnp.where(masks[..., None], w["mask_token"], t)
    D = t.shape[-1]
    x = jnp.concatenate(
        [jnp.broadcast_to(w["cls_token"].reshape(1, 1, D), (N, 1, D)), t], axis=1)
    sin, cos = vit_fp32.rope_tables(hp, wp, D // r.num_heads, r.rope_base)
    depth = w["blocks"]["ls1"].shape[0]
    if scales is None:
        scales = jnp.ones((depth, 2, N), jnp.float32)

    def layer(x, bs):
        b, s = bs
        return vit_fp32.block(b, x, sin, cos, s, heads=r.num_heads, n_prefix=1,
                              precision=precision), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, (w["blocks"], scales))
    y = vit_fp32._layernorm(x, w["norm_scale"], w["norm_bias"])
    return y[:, 0], y[:, 1:]


def head(h, x):
    """DINO / iBOT head: MLP (tanh GELU between layers) to the bottleneck,
    L2 normalisation, prototypes. [R, D] -> [R, K]."""
    n = sum(1 for k in h if k.startswith("w"))
    for i in range(n):
        x = _mm(x, h[f"w{i}"]) + h[f"b{i}"]
        if i < n - 1:
            x = jax.nn.gelu(x, approximate=True)
    x = x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-24)
    return _mm(x, h["prototypes"])


def sinkhorn(logits, temp, valid, rounds: int):
    """Sinkhorn-Knopp in the log domain: Q = exp(logits / temp) over the
    ``valid`` rows, normalised to total 1, then ``rounds`` times columns to
    1/K and rows to 1/B; returns B * Q (rows sum to 1; other rows 0). The
    iterate is kept as one [R, K] plane less a row and a column offset."""
    R, K = logits.shape
    x = jnp.where(valid[:, None], logits / temp, -1e30)
    log_b = jnp.log(jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0))
    xs = x - jax.nn.logsumexp(x)
    row = jnp.zeros((R, 1), jnp.float32)
    col = jnp.zeros((1, K), jnp.float32)
    for _ in range(rounds):
        col = col + jax.nn.logsumexp(xs - row - col, axis=0, keepdims=True) + math.log(K)
        d = jax.nn.logsumexp(xs - row - col, axis=1, keepdims=True) + log_b
        row = row + jnp.where(valid[:, None], d, 0.0)
    return jnp.where(valid[:, None], jnp.exp(xs - row - col + log_b), 0.0)


def koleo(x):
    """-mean log distance of each L2-normalised row to its nearest other."""
    x = x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-16)
    sims = _mm(x, x.T) - 2.0 * jnp.eye(x.shape[0], dtype=x.dtype)
    diff = x - x[jnp.argmax(sims, axis=1)]
    return -jnp.mean(jnp.log(jnp.sqrt(jnp.sum(diff * diff, axis=-1) + 1e-16) + 1e-8))


def _cross_entropy(student_logits, q, student_temp):
    """-sum_k q log softmax(student / temp), per row."""
    x = student_logits / student_temp
    return jax.nn.logsumexp(x, axis=-1) * jnp.sum(q, axis=-1) - jnp.sum(q * x, axis=-1)


def loss_fn(student, teacher, batch, scales, teacher_temp, r: Recipe,
            precision: str = "fp32"):
    g, loc = batch["global_crops"], batch["local_crops"]
    n_g, n_l = 2, r.n_local_crops
    B = g.shape[0] // n_g
    idx, valid = batch["mask_indices"], batch["mask_valid"].reshape(-1)

    def masked(patches):
        return jnp.take_along_axis(patches, idx[..., None], axis=1).reshape(
            -1, patches.shape[-1])

    # teacher targets
    t_cls, t_patch = backbone(teacher["backbone"], g, None, None, r, precision)
    q_cls = sinkhorn(head(teacher["dino_head"], t_cls), teacher_temp,
                     jnp.ones((n_g * B,), bool), r.sinkhorn_rounds)
    q_patch = sinkhorn(head(teacher["ibot_head"], masked(t_patch)), teacher_temp,
                       valid, r.sinkhorn_rounds)
    q_cls, q_patch = jax.lax.stop_gradient((q_cls, q_patch))

    # student
    s_cls, s_patch = backbone(student["backbone"], g, batch["masks"],
                              scales["global"], r, precision)
    l_cls, _ = backbone(student["backbone"], loc, None, scales["local"], r, precision)
    logits = head(student["dino_head"], jnp.concatenate([s_cls, l_cls], axis=0))
    K = logits.shape[-1]
    g_logits = logits[: n_g * B].reshape(n_g, B, K)
    l_logits = logits[n_g * B:].reshape(n_l, B, K)
    q = q_cls.reshape(n_g, B, K)

    pair = jax.vmap(lambda s: jax.vmap(
        lambda t: jnp.sum(_cross_entropy(s, t, r.student_temp)))(q))
    ce_g = pair(g_logits) * (1.0 - jnp.eye(n_g))          # same-crop pairs out
    dino_global = jnp.sum(ce_g) / (B * n_g * (n_g - 1))
    dino_local = jnp.sum(pair(l_logits)) / (B * n_l * n_g)
    pairs_g, pairs_l = n_g * (n_g - 1), n_g * n_l
    kol = sum(koleo(c) for c in s_cls.reshape(n_g, B, -1)) / n_g
    ce_patch = _cross_entropy(head(student["ibot_head"], masked(s_patch)), q_patch,
                              r.student_temp)
    ibot = jnp.sum(ce_patch * batch["mask_weights"].reshape(-1)) / (n_g * B)
    total = (r.dino_weight * (pairs_l * dino_local + pairs_g * dino_global)
             / (pairs_g + pairs_l)
             + r.koleo_weight * n_g * kol + r.ibot_weight * ibot)
    return total, {"total_loss": total, "dino_global_crops_loss": dino_global,
                   "dino_local_crops_loss": dino_local, "koleo_loss": kol,
                   "ibot_loss": ibot}


def multipliers(student, r: Recipe):
    """(lr factor, decays?, last layer?) per leaf, in the tree's shape."""
    depth = student["backbone"]["blocks"]["ls1"].shape[0]
    no_decay = ("bias", "b0", "b1", "b2", "b3", "ls1", "ls2")

    def rule(path, leaf):
        names = [str(getattr(k, "key", k)) for k in path]
        name = names[-1]
        lr = 1.0
        if "blocks" in names:  # block i of depth: decay ** (depth - i)
            lr = (r.layerwise_decay ** (depth - np.arange(depth, dtype=np.float64))
                  ).astype(np.float32).reshape((depth,) + (1,) * (leaf.ndim - 1))
        elif name in ("patch_kernel", "patch_bias", "cls_token", "mask_token"):
            lr = r.layerwise_decay ** (depth + 1)
            if name.startswith("patch"):
                lr = lr * r.patch_embed_lr_mult
        decays = not (name.endswith("bias") or "norm" in name or name in no_decay)
        return lr, float(decays), name == "prototypes"

    flat, treedef = jax.tree_util.tree_flatten_with_path(student)
    cols = zip(*(rule(p, leaf) for p, leaf in flat))
    return tuple(jax.tree_util.tree_unflatten(treedef, list(c)) for c in cols)


def _sq(tree):
    return sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree.leaves(tree))


def leaf_norms(tree):
    """L2 norm of every leaf; of every block's slice for a stacked leaf."""
    def norm(path, leaf):
        stacked = any(getattr(k, "key", None) == "blocks" for k in path)
        axes = tuple(range(1, leaf.ndim)) if stacked else None
        return jnp.sqrt(jnp.sum(jnp.square(leaf), axis=axes))
    return jax.tree_util.tree_map_with_path(norm, tree)


@functools.partial(jax.jit, static_argnames=("r", "precision"), donate_argnums=(0,))
def step(state, batch, scales, sched, *, r: Recipe, precision: str = "fp32"):
    """One step on ``state`` = {"student", "teacher", "mu", "nu", "count"}
    -> (new state, {"losses", "grad_norms"}); the gradient's norms per
    leaf are taken as the optimizer gets it, after the clip. ``precision``
    other than "fp32" makes the CONTROL: the blocks' forward pass in that
    precision of ``reference/vit_fp32.py``."""
    student, teacher = state["student"], state["teacher"]
    grads, losses = jax.grad(loss_fn, has_aux=True)(
        student, teacher, batch, scales, sched["teacher_temp"], r, precision)

    def clip(sub):
        c = jnp.minimum(1.0, r.clip_grad / jnp.maximum(jnp.sqrt(_sq(sub)), 1e-12))
        return jax.tree.map(lambda g: g * c, sub)

    grads = {k: clip(sub) for k, sub in grads.items()}
    count = state["count"] + 1
    c1 = 1.0 - r.beta1 ** count.astype(jnp.float32)
    c2 = 1.0 - r.beta2 ** count.astype(jnp.float32)
    lr_mult, decays, last = multipliers(student, r)

    def leaf(g, p, mu, nu, t, lm, dec, is_last):
        mu = r.beta1 * mu + (1.0 - r.beta1) * g
        nu = r.beta2 * nu + (1.0 - r.beta2) * g * g
        direction = (mu / c1) / (jnp.sqrt(nu / c2) + r.adam_eps)
        lr = sched["last_layer_lr"] if is_last else sched["lr"]
        p = p - lr * lm * (direction + sched["weight_decay"] * dec * p)
        return p, mu, nu, sched["momentum"] * t + (1.0 - sched["momentum"]) * p

    out = jax.tree.map(leaf, grads, student, state["mu"], state["nu"], teacher,
                       lr_mult, decays, last)
    new = jax.tree.transpose(jax.tree.structure(student),
                             jax.tree.structure((0, 0, 0, 0)), out)
    return ({"student": new[0], "mu": new[1], "nu": new[2], "teacher": new[3],
             "count": count},
            {"losses": losses, "grad_norms": leaf_norms(grads)})


@jax.jit
def change_norms(new, old):
    """Per-leaf norm of the student's change, and the teacher's as one."""
    d = jax.tree.map(jnp.subtract, new["student"], old)
    return leaf_norms(d), jnp.sqrt(_sq(jax.tree.map(jnp.subtract, new["teacher"], old)))


def first_steps(student, batches: list, scales: list, r: Recipe, start: int,
                precision: str = "fp32") -> dict:
    """Follow the first ``len(batches)`` steps from ``student`` (teacher =
    student, fresh moments) at iterations ``start``, ``start + 1``, ...:
    {"losses": [per step, by name], "grad_norms": of the first gradient,
    "change_norms": of the student after the steps, "teacher_change"}."""
    with jax.default_matmul_precision("highest"):
        zeros = jax.tree.map(jnp.zeros_like, student)
        state = {"student": jax.tree.map(jnp.copy, student),
                 "teacher": jax.tree.map(jnp.copy, student),
                 "mu": zeros, "nu": jax.tree.map(jnp.copy, zeros),
                 "count": jnp.zeros((), jnp.int32)}
        losses, grad_norms = [], None
        for i, (batch, sc) in enumerate(zip(batches, scales)):
            state, out = step(state, batch, sc, r.schedule(start + i), r=r,
                              precision=precision)
            losses.append({k: float(v) for k, v in out["losses"].items()})
            if i == 0:
                grad_norms = jax.tree.map(np.asarray, out["grad_norms"])
        change, teacher_change = change_norms(state, student)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": jax.tree.map(np.asarray, change),
            "teacher_change": float(teacher_change)}
