"""Single-pass fused clip + AdamW + teacher-EMA update engine, and its
cross-replica sharded forms.

The r5 on-chip profile (``PROFILE_r05.json``, docs/PERFORMANCE.md) puts
28.5% of the ViT-L step in norm/reduce fusions whose largest named
component is the fp32 weight-shaped elementwise traffic of the optimizer
+ teacher-EMA chain: ~12 ms/step of HBM floor over 304M fp32
masters+moments. The previous step program streamed that state through
FOUR sequential tree passes (train/train_step.py):

    1. per-submodel clip        (scale grads, write clipped grads)
    2. optax.scale_by_adam      (read g, mu, nu; write mu, nu, direction)
    3. scheduled lr/wd + apply  (read direction, params; write params)
    4. teacher EMA              (read teacher, new params; write teacher)

each a separate ``tree.map`` whose intermediates XLA does not reliably
multi-output-fuse across pass boundaries (the profile shows them as
distinct weight-shaped ``multiply_add``/``multiply_multiply`` programs).
This engine collapses them into ONE ``tree.map`` whose per-leaf function
takes ``(grad, param, mu, nu, teacher)`` and returns
``(new_param, new_mu, new_nu, new_teacher)`` — every fp32 master/moment/
teacher array is read once and written once per step. The per-submodel
clip norms are computed as one batched fused reduction up front (grads
only — the unavoidable second read of grad-shaped data), and all scalar
schedules (lr / last-layer lr / wd / momentum) stay in-graph exactly as
in the optax chain.

The math replicates the existing chain operation-for-operation
(optax.scale_by_adam's moment updates, safe int32 count increment and
bias correction; scheduled_adamw's per-leaf multipliers;
optax.apply_updates' cast; ssl_meta_arch.update_ema's fp32 blend), so
the optax chain in train/optimizer.py remains the reference
implementation and test oracle — ``tests/test_fused_update.py`` pins
leaf-for-leaf equivalence over multi-step runs. The engine reuses the
chain's ``ScheduledAdamWState`` pytree unchanged: checkpoints, sharding
derivation (train/setup.py eval_shape) and buffer donation are
identical on both paths. Toggle with ``optim.fused_update`` (default
on); the bench A/B rung has not been run on the chip.

Cross-replica SHARDED forms. Every replica of the engine above runs the
full single pass over the complete fp32 master/moment/teacher trees:
dp-way redundant compute and HBM traffic on exactly that weight-shaped
floor. Following "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" (Xu et al., 2020) a data-parallel mesh reshapes
the update phase into

    reduce-scatter(grads) -> clip+AdamW+EMA over 1/dp of every leaf ->
    all-gather(updated student + EMA'd teacher)

and which engine does so follows from the mesh (train/setup.py
``resolve_update_arm``, the one place that chooses):

- one device: ``make_fused_update`` as it stands (what every benchmark
  cell runs), and on any mesh behind ``optim.bucketed_collectives=
  false`` with GSPMD's all-reduce in front of it: the oracle the
  sharded forms are tested against;
- a pure data-parallel mesh: ``make_bucketed_update`` below. The leaves
  are coalesced into large flat buckets, ONE reduce-scatter a bucket
  for the grads and ONE all-gather a bucket for each updated tree, the
  adam moments BORN in the bucket layout so that each replica stores
  1/dp of mu/nu (ZeRO-1). A leaf enters a bucket flat and zero-padded to
  a multiple of dp (``flatten_update_leaf``; padded lanes are inert:
  g = p = mu = nu = 0 stays 0 through the update math), which is also
  the layout the moments have on disk (checkpoint.py);
- an fsdp mesh (ZeRO-3, ``parallel.zero3``): masters, teacher and
  moments are already sharded in their model shapes, the update is
  ``make_fused_update`` run shard-local, and what is coalesced is the
  forward's non-block weight gathers (``gather_zero3_bucketed`` at the
  end of this file).

GSPMD's annotations express the collectives (parallel/sharding.py
"bucket" rule). XLA:CPU lowers the grad sync as all-reduce + dynamic-
slice, which TPU XLA rewrites into the reduce-scatter they describe;
``make_bucketed_update_schedule`` is the same schedule with explicit
collectives, whose census shows the rewritten set on any backend.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from dinov3_tpu.parallel.sharding import STAGING_ORDER
from dinov3_tpu.train.optimizer import (
    ScheduledAdamWState,
    per_submodel_norms,
)
from dinov3_tpu.train.param_groups import build_multiplier_trees
from dinov3_tpu.train.schedules import Schedules


def ema_leaf(t: jnp.ndarray, s: jnp.ndarray, momentum) -> jnp.ndarray:
    """teacher <- m * teacher + (1 - m) * student, fp32 arithmetic, cast
    back to the teacher's storage dtype.

    Single source of truth for the EMA rule: ``SSLMetaArch.update_ema``
    (the unfused path) and the fused engine below both apply this exact
    expression, so the two step programs cannot drift apart.
    """
    return (
        t.astype(jnp.float32) * momentum
        + s.astype(jnp.float32) * (1.0 - momentum)
    ).astype(t.dtype)


def lowp_state_step(lowp_state: Any, new_student: Any, new_teacher: Any):
    """Advance both fp8/int8 delayed-scaling amax-history rings from the
    UPDATED masters (train.low_precision, ops/lowp.py).

    Part of the update epilogue the same way ``ema_leaf`` is: the step
    calls it right after the fused (or optax-oracle) parameter pass, so
    XLA fuses the per-kernel amax reductions into the update's tail —
    they read the freshly written masters while those are still hot, and
    under zero3 each amax over a sharded master is one scalar
    all-reduce-max under the ``lowp_amax`` named scope. Next step's
    scales therefore lag the weights by exactly one step (the standard
    delayed-scaling recipe)."""
    from dinov3_tpu.ops.lowp import lowp_history_step

    return {
        "student": lowp_history_step(
            lowp_state["student"], new_student["backbone"]),
        "teacher": lowp_history_step(
            lowp_state["teacher"], new_teacher["backbone"]),
    }


# pytree-leaf sentinel for "no clip scale" (None would be treated as an
# empty subtree and break the structure match in the fused tree.map)
_NO_CLIP = object()


def _safe_int32_increment(count: jnp.ndarray) -> jnp.ndarray:
    # optax._src.numerics.safe_int32_increment, replicated so the fused
    # engine's bias correction is bit-identical to scale_by_adam's
    max_int32 = jnp.iinfo(jnp.int32).max
    one = jnp.array(1, jnp.int32)
    return jnp.where(count < max_int32, count + one, max_int32)


def update_leaf_math(g, p, mu, nu, t, lm, wm, is_ll, scale,
                     lr_t, ll_lr_t, wd_t, bc1, bc2, b1, b2, eps,
                     momentum, ema):
    """The single-pass clip+AdamW+EMA per-leaf rule.

    Single source of truth for the update math: the replicated fused
    engine, the bucketed engine, and its explicit-collective schedule
    program all call this exact function (on full leaves, flat 1/dp
    shards, and shard_map-local shards respectively), so the three
    step programs cannot drift apart. Returns ``(new_param, new_mu,
    new_nu[, new_teacher])``.
    """
    if scale is not _NO_CLIP:
        g = (g * scale).astype(g.dtype)
    # scale_by_adam's moment updates + bias correction, verbatim
    mu_n = (1 - b1) * g + b1 * mu
    nu_n = (1 - b2) * (g ** 2) + b2 * nu
    mu_hat = mu_n / bc1.astype(mu_n.dtype)
    nu_hat = nu_n / bc2.astype(nu_n.dtype)
    direction = mu_hat / (jnp.sqrt(nu_hat) + eps)
    # scheduled_adamw's per-leaf rule, verbatim
    lr = jnp.where(is_ll, ll_lr_t, lr_t)
    d = direction + wd_t * wm * p.astype(direction.dtype)
    upd = -lr * lm * d
    # optax.apply_updates' cast, verbatim
    p_n = jnp.asarray(p + upd).astype(p.dtype)
    if ema:
        return p_n, mu_n, nu_n, ema_leaf(t, p_n, momentum)
    return p_n, mu_n, nu_n


def make_fused_update(
    schedules: Schedules,
    lr_mult: Any,
    wd_mult: Any,
    is_last_layer: Any,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    clip_grad: float | None = None,
    ema: bool = True,
) -> Callable:
    """Build the engine.

    Returns ``update(grads, params, teacher, opt_state, momentum) ->
    (new_params, new_teacher, new_opt_state, norms)`` where ``norms`` is
    the per-submodel pre-clip grad-norm dict ({} when clipping is off,
    matching the unfused path's monitoring contract). ``opt_state`` is
    the optax chain's ``ScheduledAdamWState`` — init via
    ``build_optimizer(...).init`` as before.

    ``ema=False`` (distillation: frozen pretrained teacher) passes the
    teacher through untouched, mirroring ``SSLMetaArch.update_ema``.
    """
    lr_arr = jnp.asarray(schedules.lr, jnp.float32)
    ll_lr_arr = jnp.asarray(schedules.last_layer_lr, jnp.float32)
    wd_arr = jnp.asarray(schedules.weight_decay, jnp.float32)
    do_clip = clip_grad is not None and clip_grad > 0

    def update(grads, params, teacher, opt_state, momentum):
        if not isinstance(opt_state, ScheduledAdamWState):
            raise TypeError(
                "fused update engine requires the scheduled_adamw state, "
                f"got {type(opt_state).__name__}"
            )
        i = jnp.minimum(opt_state.count, lr_arr.shape[0] - 1)
        lr_t, ll_lr_t, wd_t = lr_arr[i], ll_lr_arr[i], wd_arr[i]
        count_inc = _safe_int32_increment(opt_state.adam.count)
        # bias corrections are leaf-independent: hoist them out of the map
        bc1 = 1 - b1 ** count_inc
        bc2 = 1 - b2 ** count_inc

        norms = {}
        if do_clip:
            # one batched reduction over the raw grads, up front; the
            # scale is then folded into the single per-leaf pass below
            # instead of materializing a clipped-grads tree
            norms = per_submodel_norms(grads)
            scales = {
                k: jnp.minimum(1.0, clip_grad / jnp.maximum(n, 1e-12))
                for k, n in norms.items()
            }
            scale_tree = {
                k: jax.tree.map(lambda _, s=scales[k]: s, sub)
                for k, sub in grads.items()
            }
        else:
            scale_tree = jax.tree.map(lambda _: _NO_CLIP, grads)

        def leaf(g, p, mu, nu, t, lm, wm, is_ll, scale):
            return update_leaf_math(
                g, p, mu, nu, t, lm, wm, is_ll, scale,
                lr_t, ll_lr_t, wd_t, bc1, bc2, b1, b2, eps, momentum, ema,
            )

        n_out = 4 if ema else 3
        teacher_arg = teacher if ema else jax.tree.map(lambda _: 0.0, grads)
        fused = jax.tree.map(
            leaf, grads, params, opt_state.adam.mu, opt_state.adam.nu,
            teacher_arg, lr_mult, wd_mult, is_last_layer, scale_tree,
        )
        outs = jax.tree.transpose(
            jax.tree.structure(grads),
            jax.tree.structure(tuple(range(n_out))),
            fused,
        )
        if ema:
            new_params, new_mu, new_nu, new_teacher = outs
        else:
            new_params, new_mu, new_nu = outs
            new_teacher = teacher
        new_opt_state = ScheduledAdamWState(
            count=opt_state.count + 1,
            adam=optax.ScaleByAdamState(
                count=count_inc, mu=new_mu, nu=new_nu
            ),
        )
        return new_params, new_teacher, new_opt_state, norms

    return update


def build_fused_update(
    cfg, params: Any, schedules: Schedules, ema: bool = True
) -> Callable:
    """Wire config -> multiplier trees -> fused engine.

    Mirrors ``build_optimizer`` (same multiplier trees, same betas, same
    clip) so the engine and the optax oracle are built from identical
    inputs. ``params``: the *student* parameter pytree (unboxed or
    abstract), used only for path structure.
    """
    lr_mult, wd_mult, is_last = build_multiplier_trees(
        params,
        layerwise_decay=cfg.optim.layerwise_decay,
        patch_embed_lr_mult=cfg.optim.patch_embed_lr_mult,
        dino_head_wd_multiplier=cfg.optim.dino_head_wd_multiplier,
    )
    if cfg.optim.optimizer != "adamw":
        raise ValueError(
            f"fused update engine supports adamw only, got "
            f"{cfg.optim.optimizer!r}; set optim.fused_update=false"
        )
    return make_fused_update(
        schedules, lr_mult, wd_mult, is_last,
        b1=cfg.optim.adamw_beta1, b2=cfg.optim.adamw_beta2,
        clip_grad=cfg.optim.clip_grad, ema=ema,
    )


# ---------------- the flat per-leaf layout ----------------
#
# A leaf flat and zero-padded to a multiple of dp: what a bucket's
# members are made of, and the layout the bucketed arm's moments have
# on disk (checkpoint.py, parallel/reshard.py).

def padded_flat_size(n: int, dp: int) -> int:
    """Flat leaf size padded up to a multiple of the shard count."""
    return -(-int(n) // dp) * dp


def leaf_size(x) -> int:
    """Element count of a (possibly abstract) leaf."""
    n = 1
    for d in x.shape:
        n *= int(d)
    return n


def flatten_update_leaf(x, dp: int):
    """Leaf -> flat 1-D array zero-padded to a multiple of ``dp``.

    The zero padding is inert through ``update_leaf_math``: a padded
    lane has g = p = mu = nu = teacher = 0, so mu_n = nu_n = 0, the
    direction is 0/(sqrt(0)+eps) = 0, weight decay contributes
    wd*wm*0 = 0, and the lane stays exactly 0 forever — flatten/
    unflatten round-trips are lossless (pinned in
    tests/test_buckets.py).
    """
    flat = x.reshape(-1)
    pad = (-flat.size) % dp
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat


def unflatten_update_leaf(flat, like):
    """Flat padded array -> the original leaf shape (drop the padding)."""
    return flat[: leaf_size(like)].reshape(like.shape)


# ---------------- bucketed collective engine ----------------
#
# One reduce-scatter and two all-gathers a LEAF (357 + 714 at ViT-L) are
# small messages, latency-bound at production mesh sizes (PAPERS.md
# arxiv 2408.13356: sub-MiB collectives are dominated by per-message
# launch cost, not wire bytes). The bucketed engine
# (optim.bucketed_collectives, auto = on on a pure data-parallel mesh;
# =false is the replicated fused engine, its oracle) coalesces the
# update-phase leaves into a small fixed set of large flat BUCKETS —
# grouped by (submodel, dtype, param-group) so the per-submodel clip
# norms and the last-layer lr never mix inside a bucket — and issues ONE
# reduce-scatter per bucket for the grads and ONE all-gather per bucket
# for the updated params (plus one for the EMA'd teacher): the
# SimpleFSDP coalescing (arxiv 2411.00284).
#
# The bucket layout is SHARD-INTERLEAVED: a bucket is the row-major
# flattening of a [dp, S_b/dp] matrix whose row k holds, member by
# member in tree order, each member leaf's k-th flat shard (the member
# leaves are individually in their flatten_update_leaf padded form, so
# every member's shard is exactly padded/dp elements and every column
# range is dp-aligned). Two properties follow:
#
# * sharding the bucket over the data axes (the "bucket" rule) gives
#   each replica row k — every member leaf's k-th shard — so a bucket
#   reduce-scatter computes, segment for segment, the sums a
#   reduce-scatter of each leaf alone would: the result does not depend
#   on the plan (tests/test_buckets.py pins the moments and clip norms
#   BITWISE between the default plan and one of a leaf a bucket);
# * extracting one member from a dim-0-sharded bucket is a column slice
#   of the [dp, S_b/dp] view — shard-LOCAL, no data movement — so
#   between the bucket-granular collectives the engine runs the update
#   math leaf by leaf (scalar multipliers, per_submodel_norms,
#   update_leaf_math per leaf) on flat 1/dp shards.
#
# The adam moments are BORN in the bucket layout (bucketed_adam_zeros);
# checkpoints always persist the per-leaf flat layout and convert at the
# save/restore boundary (buckets_to_flat_tree / flat_tree_to_buckets —
# pure index permutations, bitwise lossless both ways).

import dataclasses


@dataclasses.dataclass(frozen=True)
class BucketMember:
    """One leaf's segment inside a bucket."""

    index: int       # leaf index in the student tree's flatten order
    path: str        # jax.tree_util.keystr of the leaf (diagnostics)
    shape: tuple     # original leaf shape
    size: int        # element count
    padded: int      # padded_flat_size(size, dp) — the segment length
    offset: int      # segment start (elements, dp-aligned)


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One coalesced flat bucket (layout comment above)."""

    name: str                        # dict key of the bucket arrays
    group: str                       # top-level submodel key (clip norms)
    dtype: Any                       # numpy dtype of every member
    is_last_layer: bool              # param-group bit (last-layer lr)
    members: tuple                   # tuple[BucketMember, ...]
    size: int                        # total flat elements (dp-aligned)

    @property
    def pad_elems(self) -> int:
        return sum(m.padded - m.size for m in self.members)


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """The leaf -> bucket assignment for one student tree at one shard
    count, built ONCE per training setup from the abstract params
    (train/setup.py — the TelemetryPlan convention) and shared by the
    engine, the opt-state init, the checkpoint adapter, the guardrail
    and the census scripts.

    Assembly rule (make_bucket_plan): leaves are walked in tree order,
    grouped by (top-level submodel key, dtype, is-last-layer bit) —
    submodels must not mix because the clip norms are per submodel,
    dtypes must not mix because a bucket is one array, and the
    last-layer lr schedule stays uniform per bucket — then packed
    greedily into buckets of ~``target_bytes`` payload. A single leaf
    larger than the target becomes its own bucket (leaves are never
    split); a trailing bucket smaller than 1/8 of the target is merged
    into its predecessor so greedy packing cannot strand a straggler
    (configs/config.py warn_bucket_padding checks the built plan
    anyway).
    """

    buckets: tuple                   # tuple[Bucket, ...]
    treedef: Any                     # student tree structure
    n_leaves: int
    dp: int
    target_bytes: int

    @property
    def names(self):
        return [b.name for b in self.buckets]

    def padding_stats(self):
        """Per-bucket accounting rows for the guardrail + bench."""
        return [
            {
                "name": b.name,
                "group": b.group,
                "dtype": str(jnp.dtype(b.dtype)),
                "is_last_layer": bool(b.is_last_layer),
                "n_leaves": len(b.members),
                "elems": int(b.size),
                "pad_elems": int(b.pad_elems),
                "bytes": int(b.size) * jnp.dtype(b.dtype).itemsize,
            }
            for b in self.buckets
        ]

    # ---- layout conversions ----
    #
    # All four are pure index permutations built from reshape /
    # column-slice / concatenate, so every direction is bitwise
    # lossless; the checkpoint pair works on numpy arrays too.

    def _leaves(self, tree):
        leaves = jax.tree.leaves(tree)
        if len(leaves) != self.n_leaves:
            raise ValueError(
                f"bucket plan built for {self.n_leaves} leaves, "
                f"got a tree with {len(leaves)}"
            )
        return leaves

    def _assemble(self, flat_parts, bucket):
        # per-member flat [padded] -> interleaved bucket [S_b]
        mats = [f.reshape(self.dp, -1) for f in flat_parts]
        mat = mats[0] if len(mats) == 1 else jnp.concatenate(mats, axis=1)
        return mat.reshape(-1)

    def pack_tree(self, tree, constrain_fn=None):
        """Model-layout tree -> {bucket_name: flat [S_b]} (each leaf
        through its padded-flat form, then shard-interleaved into the
        bucket). ``constrain_fn`` (e.g. ``constrain_bucket``) is applied
        to each assembled bucket — under GSPMD that constraint is where
        the ONE reduce-scatter per bucket lands."""
        leaves = self._leaves(tree)
        out = {}
        for b in self.buckets:
            flat = self._assemble(
                [flatten_update_leaf(leaves[m.index], self.dp)
                 for m in b.members], b)
            out[b.name] = constrain_fn(flat) if constrain_fn else flat
        return out

    def pack_flat_tree(self, flat_tree, constrain_fn=None):
        """Per-leaf flat padded tree -> bucket layout."""
        leaves = self._leaves(flat_tree)
        out = {}
        for b in self.buckets:
            flat = self._assemble([leaves[m.index] for m in b.members], b)
            out[b.name] = constrain_fn(flat) if constrain_fn else flat
        return out

    def unpack_flat_tree(self, bucket_dict, constrain_fn=None):
        """Bucket layout -> per-leaf flat padded tree. On a
        dim-0-sharded bucket every member extraction is a shard-local
        column slice (layout comment above) — no data movement."""
        out_leaves = [None] * self.n_leaves
        for b in self.buckets:
            mat = bucket_dict[b.name].reshape(self.dp, -1)
            for m in b.members:
                c0 = m.offset // self.dp
                seg = mat[:, c0:c0 + m.padded // self.dp].reshape(-1)
                out_leaves[m.index] = (constrain_fn(seg) if constrain_fn
                                       else seg)
        return jax.tree.unflatten(self.treedef, out_leaves)

    def unpack_tree(self, bucket_dict, like_tree, prepare_fn=None):
        """{bucket_name: flat [S_b]} -> model-layout tree.
        ``prepare_fn`` (e.g. ``constrain_replicated`` — the
        one-all-gather-per-bucket materialization point) is applied to
        each bucket BEFORE the member slices."""
        like_leaves = self._leaves(like_tree)
        out_leaves = [None] * self.n_leaves
        for b in self.buckets:
            flat = bucket_dict[b.name]
            if prepare_fn is not None:
                flat = prepare_fn(flat)
            mat = flat.reshape(self.dp, -1)
            for m in b.members:
                c0 = m.offset // self.dp
                seg = mat[:, c0:c0 + m.padded // self.dp].reshape(-1)
                out_leaves[m.index] = unflatten_update_leaf(
                    seg, like_leaves[m.index])
        return jax.tree.unflatten(self.treedef, out_leaves)

    def buckets_to_flat_tree(self, bucket_dict):
        """Bucket layout -> the PER-LEAF flat padded layout (one
        ``[padded_flat_size]`` array a leaf). The checkpoint adapter
        uses this so on-disk moments are always per-leaf — a bucketed
        run's checkpoint restores into any arm and vice versa. Numpy in
        -> numpy out (the host-side restore path)."""
        out_leaves = [None] * self.n_leaves
        for b in self.buckets:
            mat = bucket_dict[b.name].reshape(self.dp, -1)
            for m in b.members:
                c0 = m.offset // self.dp
                out_leaves[m.index] = (
                    mat[:, c0:c0 + m.padded // self.dp].reshape(-1))
        return jax.tree.unflatten(self.treedef, out_leaves)

    def flat_tree_to_buckets(self, flat_tree):
        """Inverse of ``buckets_to_flat_tree``; numpy in -> numpy out."""
        import numpy as np

        leaves = self._leaves(flat_tree)
        out = {}
        for b in self.buckets:
            mats = []
            for m in b.members:
                l = leaves[m.index]
                if l.ndim != 1 or l.shape[0] != m.padded:
                    raise ValueError(
                        f"bucket plan expects per-leaf flat [{m.padded}] "
                        f"for {m.path}, got {l.shape}"
                    )
                mats.append(l.reshape(self.dp, -1))
            if all(isinstance(x, np.ndarray) for x in mats):
                mat = (mats[0] if len(mats) == 1
                       else np.concatenate(mats, axis=1))
            else:
                mat = (mats[0] if len(mats) == 1
                       else jnp.concatenate(mats, axis=1))
            out[b.name] = mat.reshape(-1)
        return out


# Payload target of one bucket, for the update-phase plan and the zero3
# gather plan alike: large enough that a bucket's collective sits on the
# flat part of the launch-latency curve (the >= 64 MB bin of the census
# size histogram), small enough that a ViT-L submodel still has >= 2
# buckets for the overlap schedule to overlap.
BUCKET_TARGET_BYTES = 128 * 2 ** 20


def make_bucket_plan(
    student: Any,
    dp: int,
    is_last_layer: Any = None,
    target_bytes: int = BUCKET_TARGET_BYTES,
) -> BucketPlan:
    """Build the leaf -> bucket assignment (see ``BucketPlan``).

    ``student``: the student param tree (abstract or concrete — only
    paths/shapes/dtypes are read). ``is_last_layer``: the param-group
    tree from ``build_multiplier_trees`` (None = no last-layer group).
    """
    import jax.tree_util as jtu

    dp = max(1, int(dp))
    flat, treedef = jtu.tree_flatten_with_path(student)
    ll_leaves = (jax.tree.leaves(is_last_layer)
                 if is_last_layer is not None else [False] * len(flat))
    if len(ll_leaves) != len(flat):
        raise ValueError(
            f"is_last_layer tree has {len(ll_leaves)} leaves, "
            f"student has {len(flat)}"
        )

    def top_key(path):
        k = path[0]
        return str(getattr(k, "key", getattr(k, "idx", k)))

    # group key -> ordered member list (tree order preserved per group)
    groups: dict = {}
    for i, (path, leaf) in enumerate(flat):
        key = (top_key(path), jnp.dtype(leaf.dtype).str,
               bool(ll_leaves[i]))
        n = leaf_size(leaf)
        groups.setdefault(key, []).append(BucketMember(
            index=i, path=jtu.keystr(path), shape=tuple(leaf.shape),
            size=n, padded=padded_flat_size(n, dp), offset=0,
        ))

    buckets = []
    for (group, dtype_str, is_ll), members in groups.items():
        itemsize = jnp.dtype(dtype_str).itemsize
        # greedy fill to the byte target; oversized leaves become
        # single-member buckets (never split)
        runs, run, run_bytes = [], [], 0
        for m in members:
            nbytes = m.padded * itemsize
            if run and run_bytes + nbytes > target_bytes:
                runs.append(run)
                run, run_bytes = [], 0
            run.append(m)
            run_bytes += nbytes
        if run:
            runs.append(run)
        # straggler rebalance: merge a tiny tail run into its
        # predecessor so the assignment cannot strand a bucket under
        # 1/8 of the target
        if len(runs) >= 2 and sum(
                m.padded for m in runs[-1]) * itemsize < target_bytes // 8:
            runs[-2].extend(runs.pop())
        for run in runs:
            off, placed = 0, []
            for m in run:
                placed.append(dataclasses.replace(m, offset=off))
                off += m.padded
            buckets.append(Bucket(
                name="", group=group, dtype=jnp.dtype(dtype_str),
                is_last_layer=is_ll, members=tuple(placed), size=off,
            ))

    # deterministic global order (by first member's tree position) and
    # zero-padded names so jax's sorted-dict-key traversal preserves it
    buckets.sort(key=lambda b: b.members[0].index)
    named = tuple(
        dataclasses.replace(
            b, name=f"b{i:03d}_{b.group}" + ("_ll" if b.is_last_layer
                                             else ""))
        for i, b in enumerate(buckets)
    )
    return BucketPlan(
        buckets=named, treedef=treedef, n_leaves=len(flat), dp=dp,
        target_bytes=int(target_bytes),
    )


def bucketed_adam_zeros(plan: BucketPlan) -> dict:
    """Adam moment zeros BORN in the bucket layout, boxed with the
    "bucket" logical axis for sharding derivation (each replica stores
    1/dp of every bucket)."""
    import flax.linen as nn

    def z(b):
        init = nn.with_logical_partitioning(
            lambda: jnp.zeros((b.size,), b.dtype), ("bucket",))
        return init()

    return {b.name: z(b) for b in plan.buckets}


def _check_bucketed_opt_state(opt_state, plan: BucketPlan) -> None:
    if not isinstance(opt_state, ScheduledAdamWState):
        raise TypeError(
            "bucketed update engine requires the scheduled_adamw state, "
            f"got {type(opt_state).__name__}"
        )
    mu = opt_state.adam.mu
    if not isinstance(mu, dict) or set(mu) != set(plan.names):
        raise TypeError(
            "bucketed update engine requires the bucket-layout opt "
            f"state (buckets {plan.names[:3]}...); init via "
            "build_train_setup with optim.bucketed_collectives on, or "
            "restore through Checkpointer with the setup's bucket_plan "
            "(which adapts per-leaf/replicated checkpoints to buckets)"
        )
    for b in plan.buckets:
        got = mu[b.name].shape
        if got != (b.size,):
            raise TypeError(
                f"bucket {b.name}: mu shape {got}, expected ({b.size},)"
            )


def make_bucketed_update(
    schedules: Schedules,
    lr_mult: Any,
    wd_mult: Any,
    is_last_layer: Any,
    mesh: Any,
    plan: BucketPlan,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    clip_grad: float | None = None,
    ema: bool = True,
) -> Callable:
    """Build the bucketed collective engine (section comment above).

    Same contract as ``make_fused_update`` — ``update(grads, params,
    teacher, opt_state, momentum) -> (new_params, new_teacher,
    new_opt_state, norms)`` — except ``opt_state.adam.mu/nu`` are
    {bucket_name: flat [S_b]} dicts in the shard-interleaved bucket
    layout (``bucketed_adam_zeros``). Params/teacher enter and leave in
    their model layout; BETWEEN the collectives the elementwise math —
    scalar multipliers, ``per_submodel_norms``, ``update_leaf_math`` —
    runs leaf by leaf on flat 1/dp shards. Grads are bucket-packed
    under the ``bucket_pack`` named scope (where GSPMD places the ONE
    reduce-scatter per bucket); the updated student/teacher are
    bucket-packed and re-materialized under ``bucket_unpack`` (the ONE
    all-gather per bucket site).
    """
    from dinov3_tpu.parallel.sharding import (
        constrain_bucket,
        constrain_replicated,
        constrain_update_shard,
        update_shard_size,
    )

    dp = update_shard_size(mesh)
    if dp != plan.dp:
        raise ValueError(f"plan built at dp={plan.dp}, mesh has dp={dp}")
    lr_arr = jnp.asarray(schedules.lr, jnp.float32)
    ll_lr_arr = jnp.asarray(schedules.last_layer_lr, jnp.float32)
    wd_arr = jnp.asarray(schedules.weight_decay, jnp.float32)
    do_clip = clip_grad is not None and clip_grad > 0
    # gather whole buckets only on model-parallel-free meshes: with a
    # tensor/seq/pipe/expert axis the member leaves carry model-parallel
    # placements a replicated bucket would undo — the per-leaf
    # unflatten + jit-level out_shardings then place the gathers
    gather_whole = mesh is None or all(
        int(mesh.shape.get(a, 1)) <= 1
        for a in ("tensor", "seq", "pipe", "expert"))

    def to_shard(x):
        with jax.named_scope("update_shard_pack"):
            return constrain_update_shard(flatten_update_leaf(x, dp), mesh)

    def mult_to_shard(m, like):
        # scalar multipliers ride along unchanged; scanned-stack [L,1,..]
        # multiplier arrays are materialized per element before the leaf
        # shape is flattened away (XLA fuses the broadcast into the
        # update kernel)
        if getattr(m, "ndim", 0) == 0:
            return m
        return to_shard(jnp.broadcast_to(m, like.shape).astype(jnp.float32))

    def update(grads, params, teacher, opt_state, momentum):
        _check_bucketed_opt_state(opt_state, plan)
        i = jnp.minimum(opt_state.count, lr_arr.shape[0] - 1)
        lr_t, ll_lr_t, wd_t = lr_arr[i], ll_lr_arr[i], wd_arr[i]
        count_inc = _safe_int32_increment(opt_state.adam.count)
        bc1 = 1 - b1 ** count_inc
        bc2 = 1 - b2 ** count_inc

        # grads: model layout -> ONE sharded bucket per group (the
        # coalesced reduce-scatter) -> shard-local per-leaf flat views
        with jax.named_scope("bucket_pack"):
            g_bkt = plan.pack_tree(
                grads, constrain_fn=lambda x: constrain_bucket(x, mesh))
        g_flat = plan.unpack_flat_tree(
            g_bkt, constrain_fn=lambda x: constrain_update_shard(x, mesh))
        p_flat = jax.tree.map(to_shard, params)
        t_flat = (jax.tree.map(to_shard, teacher) if ema
                  else jax.tree.map(lambda _: jnp.float32(0.0), g_flat))
        lm_flat = jax.tree.map(mult_to_shard, lr_mult, params)
        wm_flat = jax.tree.map(mult_to_shard, wd_mult, params)
        mu_flat = plan.unpack_flat_tree(opt_state.adam.mu)
        nu_flat = plan.unpack_flat_tree(opt_state.adam.nu)
        # fusion cut: behind this barrier the norms + per-leaf update
        # subgraph is the SAME graph over the same flat leaves whatever
        # the plan — the bucket slices/concats would otherwise fuse
        # into the math and vectorize it differently. Backends that
        # honor the barrier as a fusion boundary compile the same
        # kernels under every plan; XLA:CPU expands the barrier
        # pre-fusion, where the moments and clip norms still stay
        # bitwise (the interleaved layout fixes the reduction segments)
        # and params/teacher sit within ~1-2 ulp of FMA contraction
        # context between two plans (tests/test_buckets.py).
        (g_flat, p_flat, t_flat, lm_flat, wm_flat, mu_flat, nu_flat) = (
            jax.lax.optimization_barrier(
                (g_flat, p_flat, t_flat, lm_flat, wm_flat,
                 mu_flat, nu_flat)))

        norms = {}
        if do_clip:
            # the replicated engine's per_submodel_norms graph over the
            # flat sharded leaves: GSPMD lowers it as shard-local
            # partial norms + one small psum
            norms = per_submodel_norms(g_flat)
            scales = {
                k: jnp.minimum(1.0, clip_grad / jnp.maximum(n, 1e-12))
                for k, n in norms.items()
            }
            scale_tree = {
                k: jax.tree.map(lambda _, s=scales[k]: s, sub)
                for k, sub in g_flat.items()
            }
        else:
            scale_tree = jax.tree.map(lambda _: _NO_CLIP, g_flat)

        def leaf(g, p, mu, nu, t, lm, wm, is_ll, scale):
            return update_leaf_math(
                g, p, mu, nu, t, lm, wm, is_ll, scale,
                lr_t, ll_lr_t, wd_t, bc1, bc2, b1, b2, eps, momentum, ema,
            )

        n_out = 4 if ema else 3
        fused = jax.tree.map(
            leaf, g_flat, p_flat, mu_flat, nu_flat,
            t_flat, lm_flat, wm_flat, is_last_layer, scale_tree,
        )
        outs = jax.tree.transpose(
            jax.tree.structure(g_flat),
            jax.tree.structure(tuple(range(n_out))),
            fused,
        )
        # closing fusion cut (comment above)
        outs = jax.lax.optimization_barrier(outs)
        if ema:
            p_new_flat, new_mu, new_nu, t_new_flat = outs
        else:
            p_new_flat, new_mu, new_nu = outs

        # moments stay resident in the (sharded) bucket layout
        with jax.named_scope("bucket_pack"):
            mu_bkt = plan.pack_flat_tree(
                new_mu, constrain_fn=lambda x: constrain_bucket(x, mesh))
            nu_bkt = plan.pack_flat_tree(
                new_nu, constrain_fn=lambda x: constrain_bucket(x, mesh))

        # updated student/teacher: per-leaf shards -> ONE replicated
        # bucket per group (the coalesced all-gather) -> model layout
        def from_buckets(flat_tree, like):
            with jax.named_scope("bucket_unpack"):
                bkt = plan.pack_flat_tree(flat_tree)
                return plan.unpack_tree(
                    bkt, like,
                    prepare_fn=lambda x: constrain_replicated(x, mesh))

        def from_leaves(flat_tree, like):
            with jax.named_scope("update_shard_unpack"):
                return jax.tree.map(unflatten_update_leaf, flat_tree, like)

        unpack = from_buckets if gather_whole else from_leaves
        new_params = unpack(p_new_flat, params)
        new_teacher = unpack(t_new_flat, teacher) if ema else teacher
        new_opt_state = ScheduledAdamWState(
            count=opt_state.count + 1,
            adam=optax.ScaleByAdamState(
                count=count_inc, mu=mu_bkt, nu=nu_bkt),
        )
        return new_params, new_teacher, new_opt_state, norms

    return update


def build_bucketed_update(
    cfg, params: Any, schedules: Schedules, mesh: Any,
    plan: BucketPlan, ema: bool = True,
) -> Callable:
    """Wire config -> multiplier trees -> bucketed engine
    (``build_fused_update``'s twin; same inputs, same validation,
    plus the mesh and the setup-built ``BucketPlan``)."""
    lr_mult, wd_mult, is_last = build_multiplier_trees(
        params,
        layerwise_decay=cfg.optim.layerwise_decay,
        patch_embed_lr_mult=cfg.optim.patch_embed_lr_mult,
        dino_head_wd_multiplier=cfg.optim.dino_head_wd_multiplier,
    )
    if cfg.optim.optimizer != "adamw":
        raise ValueError(
            f"bucketed update engine supports adamw only, got "
            f"{cfg.optim.optimizer!r}; set optim.bucketed_collectives="
            f"false"
        )
    return make_bucketed_update(
        schedules, lr_mult, wd_mult, is_last, mesh, plan,
        b1=cfg.optim.adamw_beta1, b2=cfg.optim.adamw_beta2,
        clip_grad=cfg.optim.clip_grad, ema=ema,
    )


def make_bucketed_update_schedule(
    schedules: Schedules,
    lr_mult: Any,
    wd_mult: Any,
    is_last_layer: Any,
    mesh: Any,
    plan: BucketPlan,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    clip_grad: float | None = None,
    ema: bool = True,
) -> Callable:
    """The bucketed update schedule with EXPLICIT collectives.

    ``make_bucketed_update`` expresses the schedule through GSPMD
    annotations, which this container's XLA:CPU lowers as all-reduce +
    fused dynamic-slice (the pre-rewrite form of reduce-scatter; the
    TPU/GPU collective optimizer performs that rewrite). This builder
    writes the same schedule as a shard_map island whose collectives
    are spelled out, so the compiled HLO contains the literal
    reduce-scatter/all-gather ops on every backend
    (COST_BUCKET_r13.json is a census of it at ViT-L dp=8;
    tests/test_buckets.py pins its numerics against the engine and its
    collective set).

    Per bucket: the members' padded-flat partial grads are
    shard-interleaved into the bucket layout and reduce-scattered with
    ONE ``psum_scatter`` (scope ``bucket_pack``); because of the
    interleave, each replica's [S_b/dp] reduce-scatter result is the
    member-by-member concatenation of each member leaf's own shard, so
    the body slices the members back out LOCALLY and runs the
    shard-local program leaf by leaf (``update_leaf_math``,
    per-submodel partial norms + ONE small psum for the clip norms, the
    whole grad never materialized anywhere); the updated student and
    EMA'd teacher shards re-concatenate and come back with ONE
    ``all_gather`` per bucket each (scope ``bucket_unpack``).

    Returns ``schedule(grad_partials, params, teacher, opt_state,
    momentum) -> (new_params, new_teacher, new_opt_state, norms)`` where
    ``grad_partials`` leaves are [dp, *leaf_shape] stacks of the
    per-replica partial gradients (dim 0 sharded over the data axes —
    what the data-parallel backward holds before any grad sync), and
    ``opt_state`` is in the bucket layout (``bucketed_adam_zeros``).
    """
    from dinov3_tpu.parallel.sharding import (
        UPDATE_SHARD_AXES,
        update_shard_size,
    )
    from jax.sharding import PartitionSpec as P

    dp = update_shard_size(mesh)
    if dp != plan.dp:
        raise ValueError(f"plan built at dp={plan.dp}, mesh has dp={dp}")
    axes = tuple(a for a in UPDATE_SHARD_AXES if a in mesh.shape)
    lr_arr = jnp.asarray(schedules.lr, jnp.float32)
    ll_lr_arr = jnp.asarray(schedules.last_layer_lr, jnp.float32)
    wd_arr = jnp.asarray(schedules.weight_decay, jnp.float32)
    do_clip = clip_grad is not None and clip_grad > 0
    shard_spec, rep_spec = P(axes), P()

    def schedule(grad_partials, params, teacher, opt_state, momentum):
        _check_bucketed_opt_state(opt_state, plan)
        # flat padded shard-layout forms of everything the local body
        # consumes per LEAF (only the grads and the updated outputs
        # travel in bucket form; the in_specs slice each replica's
        # shard)
        p_flat = jax.tree.map(lambda p: flatten_update_leaf(p, dp), params)
        t_flat = (jax.tree.map(lambda t: flatten_update_leaf(t, dp), teacher)
                  if ema else jax.tree.map(lambda _: 0.0, grad_partials))
        mults = jax.tree.map(
            lambda m, p: m if getattr(m, "ndim", 0) == 0 else
            flatten_update_leaf(
                jnp.broadcast_to(m, p.shape).astype(jnp.float32), dp),
            {"lm": lr_mult, "wm": wd_mult},
            {"lm": params, "wm": params},
        )
        mults_spec = jax.tree.map(
            lambda m: rep_spec if getattr(m, "ndim", 0) == 0 else shard_spec,
            mults,
        )
        tf_spec = shard_spec if ema else rep_spec

        def body(gp, pf, tf, mu, nu, ms, count, adam_count, mom):
            i = jnp.minimum(count, lr_arr.shape[0] - 1)
            lr_t, ll_lr_t, wd_t = lr_arr[i], ll_lr_arr[i], wd_arr[i]
            count_inc = _safe_int32_increment(adam_count)
            bc1 = 1 - b1 ** count_inc
            bc2 = 1 - b2 ** count_inc
            g_leaves = jax.tree.leaves(jax.tree.map(lambda g: g[0], gp))
            # ONE reduce-scatter per bucket over the shard-interleaved
            # concat of the members' padded-flat partial grads; row k of
            # the interleave is the concat of the members' k-th shards,
            # so the local result is the concat of each member's own
            # summed shard
            rs = {}
            with jax.named_scope("bucket_pack"):
                for b in plan.buckets:
                    mats = [flatten_update_leaf(g_leaves[m.index], dp)
                            .reshape(dp, -1) for m in b.members]
                    mat = (mats[0] if len(mats) == 1
                           else jnp.concatenate(mats, axis=1))
                    rs[b.name] = jax.lax.psum_scatter(
                        mat.reshape(-1), axes,
                        scatter_dimension=0, tiled=True)
            # member shards back out of the local bucket shards — a
            # column slice of the interleave, local by construction
            g_shard_leaves = [None] * plan.n_leaves
            for b in plan.buckets:
                for m in b.members:
                    c0 = m.offset // dp
                    g_shard_leaves[m.index] = (
                        rs[b.name][c0:c0 + m.padded // dp])
            g_shard = jax.tree.unflatten(plan.treedef, g_shard_leaves)
            norms = {}
            if do_clip:
                partial = {
                    k: sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                           for l in jax.tree.leaves(sub))
                    for k, sub in g_shard.items()
                }
                norms = {k: jnp.sqrt(v)
                         for k, v in jax.lax.psum(partial, axes).items()}
                scale_tree = {
                    k: jax.tree.map(
                        lambda _, s=jnp.minimum(
                            1.0, clip_grad / jnp.maximum(norms[k], 1e-12)
                        ): s, sub)
                    for k, sub in g_shard.items()
                }
            else:
                scale_tree = jax.tree.map(lambda _: _NO_CLIP, g_shard)
            def split_shards(bucket_dict):
                # local [S_b/dp] bucket shards -> per-leaf local shards
                # (plain slices: the interleave makes them contiguous)
                leaves = [None] * plan.n_leaves
                for b in plan.buckets:
                    for m in b.members:
                        c0 = m.offset // dp
                        leaves[m.index] = (
                            bucket_dict[b.name][c0:c0 + m.padded // dp])
                return jax.tree.unflatten(plan.treedef, leaves)

            mu_flat = split_shards(mu)
            nu_flat = split_shards(nu)

            def leaf(g, p, mu_l, nu_l, t, lm, wm, is_ll, scale):
                return update_leaf_math(
                    g, p, mu_l, nu_l, t, lm, wm, is_ll, scale,
                    lr_t, ll_lr_t, wd_t, bc1, bc2, b1, b2, eps, mom, ema,
                )

            n_out = 4 if ema else 3
            fused = jax.tree.map(
                leaf, g_shard, pf, mu_flat, nu_flat, tf,
                ms["lm"], ms["wm"], is_last_layer, scale_tree,
            )
            outs = jax.tree.transpose(
                jax.tree.structure(g_shard),
                jax.tree.structure(tuple(range(n_out))),
                fused,
            )
            if ema:
                p_new, new_mu, new_nu, t_new = outs
            else:
                p_new, new_mu, new_nu = outs

            def cat_shards(flat_tree):
                # per-leaf local shards -> local [S_b/dp] bucket shards
                leaves = jax.tree.leaves(flat_tree)
                return {
                    b.name: (leaves[b.members[0].index]
                             if len(b.members) == 1 else
                             jnp.concatenate(
                                 [leaves[m.index] for m in b.members]))
                    for b in plan.buckets
                }

            # ONE all-gather per bucket (student, and teacher under ema)
            with jax.named_scope("bucket_unpack"):
                p_full = {k: jax.lax.all_gather(v, axes, tiled=True)
                          for k, v in cat_shards(p_new).items()}
                t_full = ({k: jax.lax.all_gather(v, axes, tiled=True)
                           for k, v in cat_shards(t_new).items()}
                          if ema else cat_shards(tf))
            return (p_full, t_full, cat_shards(new_mu),
                    cat_shards(new_nu), norms)

        p_full, t_full, new_mu, new_nu, norms = jax.shard_map(
            body, mesh=mesh,
            in_specs=(shard_spec, shard_spec, tf_spec, shard_spec,
                      shard_spec, mults_spec, rep_spec, rep_spec,
                      rep_spec),
            out_specs=(rep_spec, rep_spec, shard_spec, shard_spec,
                       rep_spec),
            check_vma=False,
        )(grad_partials, p_flat, t_flat, opt_state.adam.mu,
          opt_state.adam.nu, mults, opt_state.count,
          opt_state.adam.count, momentum)

        new_params = plan.unpack_tree(p_full, params)
        new_teacher = (plan.unpack_tree(t_full, teacher) if ema
                       else teacher)
        new_opt_state = ScheduledAdamWState(
            count=opt_state.count + 1,
            adam=optax.ScaleByAdamState(
                count=_safe_int32_increment(opt_state.adam.count),
                mu=new_mu, nu=new_nu,
            ),
        )
        return new_params, new_teacher, new_opt_state, norms

    return schedule


# ---------------- unified engine: zero3 gather buckets ----------------
#
# The bucketed engine above coalesces the UPDATE phase of the pure-dp
# flat layout. Under zero3 there is no flat update phase to bucket —
# the update is shard-local over model-shaped 1/dp leaves — but the
# per-step collective schedule has its own per-leaf tail: the NON-block
# subtree gathers of ssl_meta_arch._zero3_gather_params (heads, patch
# embed, norms, final layers — one all-gather per leaf, one transposed
# reduce-scatter per grad leaf; the block stacks stream per block
# inside the scan BY DESIGN and are excluded here). The zero3 gather
# buckets below coalesce exactly that tail: non-block leaves grouped by
# their ZeRO-3 leaf spec (top-level submodel, dtype, sharded dim) and
# packed into flat buckets whose gather is ONE hierarchy-aware staged
# all-gather per bucket (parallel/sharding.py hier_gather_bucket) and
# whose grad sync is ONE staged reduce-scatter per bucket — the PR-9
# shard-interleave lifted onto the zero3 layout.
#
# The bucket view is [n_inter, n_intra, cols]: element [i, j, :] is,
# member by member in tree order, the flat form of the shard device
# (i, j) already HOLDS under the leaf's zero3 spec (the sharded dim
# reshaped to (dp, d/dp) and moved to the front — d % dp == 0 by
# zero3_leaf_spec construction, so there is NO padding, unlike the flat
# engine's padded-leaf form). Packing is therefore shard-local data
# movement, the bucket reduce-scatter computes segment for segment the
# identical sums the per-leaf schedule computes, and member extraction
# from a gathered bucket is a column slice + inverse reshape. The
# per-leaf zero3 gather stays the oracle behind
# optim.bucketed_collectives=false.


@dataclasses.dataclass(frozen=True)
class Zero3BucketMember:
    """One non-block leaf's segment inside a zero3 gather bucket."""

    index: int       # leaf index in the gathered tree's flatten order
    path: str        # jax.tree_util.keystr of the leaf (diagnostics)
    shape: tuple     # original (model) leaf shape
    shard_dim: int   # the dim zero3_leaf_spec sharded over the data axes
    size: int        # element count
    cols: int        # size // dp — the member's column width
    offset: int      # column start inside the bucket


@dataclasses.dataclass(frozen=True)
class Zero3Bucket:
    """One coalesced zero3 gather bucket (layout comment above)."""

    name: str
    group: str       # top-level submodel key
    dtype: Any       # numpy dtype of every member
    shard_dim: int   # shared zero3 sharded-dim index of every member
    members: tuple   # tuple[Zero3BucketMember, ...]
    cols: int        # total column count (sum of member cols)


@dataclasses.dataclass(frozen=True)
class Zero3GatherPlan:
    """The non-block leaf -> gather bucket assignment for ONE param
    tree shape under the unified engine.

    Built per tree (student and frozen trees differ) from paths +
    shapes/dtypes only, so it works on tracers inside the step trace as
    well as on the abstract params at setup (train/setup.py builds the
    student plan once for the guardrail/census/tests; the step rebuilds
    it host-side per trace — deterministic, metadata-only).

    Leaf classes:
    * ``streamed`` — block-stack subtrees (``blocks``/``blocks_i``/
      ``pipeline``): untouched, their weights gather per block inside
      the scan;
    * bucket members — leaves with a zero3-dividing dim, grouped by
      (top-level submodel, dtype, shard_dim) — submodel and dtype for
      the same reasons as ``make_bucket_plan``, shard_dim because it IS
      the zero3 leaf spec under the gather's model-parallel-free gate
      (every other spec entry is None there) and members of one bucket
      must share the pack reshape's alignment;
    * ``perleaf`` — leaves with NO dividing dim: replicated under zero3
      anyway, gathered per leaf exactly as the oracle does.
    """

    buckets: tuple       # tuple[Zero3Bucket, ...]
    streamed: tuple      # leaf indices left to the in-scan block stream
    perleaf: tuple       # leaf indices gathered per leaf (no dividing dim)
    n_inter: int
    n_intra: int
    n_leaves: int
    target_bytes: int

    @property
    def dp(self) -> int:
        return self.n_inter * self.n_intra

    @property
    def names(self):
        return [b.name for b in self.buckets]

    def stats(self):
        """Per-bucket accounting rows (guardrail/bench/census style)."""
        return [
            {
                "name": b.name,
                "group": b.group,
                "dtype": str(jnp.dtype(b.dtype)),
                "shard_dim": int(b.shard_dim),
                "n_leaves": len(b.members),
                "elems": int(b.cols) * self.dp,
                "bytes": int(b.cols) * self.dp
                * jnp.dtype(b.dtype).itemsize,
            }
            for b in self.buckets
        ]


def zero3_streamed_path(path) -> bool:
    """Whether a leaf path belongs to a block-stack subtree the in-scan
    zero3 weight stream owns (the skip rule of
    ``ssl_meta_arch._zero3_gather_params``, shared so the plan and the
    per-leaf oracle walk can never disagree about which leaves the
    gather phase covers)."""
    for k in path:
        name = getattr(k, "key", None)
        if not isinstance(name, str):
            continue
        if name == "blocks" or name.startswith("blocks_") \
                or name == "pipeline":
            return True
    return False


def make_zero3_bucket_plan(
    tree: Any,
    mesh,
    target_bytes: int = BUCKET_TARGET_BYTES,
) -> Zero3GatherPlan:
    """Build the non-block leaf -> gather bucket assignment (see
    ``Zero3GatherPlan``). ``tree``: a zero3-sharded param tree (abstract
    or concrete — only paths/shapes/dtypes are read)."""
    import jax.tree_util as jtu

    from dinov3_tpu.parallel.sharding import (
        hierarchy_axes,
        zero3_leaf_spec,
    )

    inter, intra = hierarchy_axes(mesh)
    n_inter = 1
    for a in inter:
        n_inter *= int(mesh.shape[a])
    n_intra = 1
    for a in intra:
        n_intra *= int(mesh.shape[a])
    dp = n_inter * n_intra

    flat, _ = jtu.tree_flatten_with_path(tree)
    streamed, perleaf = [], []
    groups: dict = {}

    def top_key(path):
        k = path[0]
        return str(getattr(k, "key", getattr(k, "idx", k)))

    for i, (path, leaf) in enumerate(flat):
        if zero3_streamed_path(path):
            streamed.append(i)
            continue
        shape = tuple(leaf.shape)
        spec = (zero3_leaf_spec(shape, (None,) * len(shape), mesh)
                if dp > 1 else None)
        if spec is None:
            perleaf.append(i)
            continue
        shard_dim = next(j for j, s in enumerate(spec) if s is not None)
        n = leaf_size(leaf)
        key = (top_key(path), jnp.dtype(leaf.dtype).str, shard_dim)
        groups.setdefault(key, []).append(Zero3BucketMember(
            index=i, path=jtu.keystr(path), shape=shape,
            shard_dim=shard_dim, size=n, cols=n // dp, offset=0,
        ))

    buckets = []
    for (group, dtype_str, shard_dim), members in groups.items():
        itemsize = jnp.dtype(dtype_str).itemsize
        # greedy fill to the byte target (make_bucket_plan's rule:
        # oversized leaves become single-member buckets, never split)
        runs, run, run_bytes = [], [], 0
        for m in members:
            nbytes = m.size * itemsize
            if run and run_bytes + nbytes > target_bytes:
                runs.append(run)
                run, run_bytes = [], 0
            run.append(m)
            run_bytes += nbytes
        if run:
            runs.append(run)
        # straggler rebalance, same 1/8-of-target rule as the flat plan
        if len(runs) >= 2 and sum(
                m.size for m in runs[-1]) * itemsize < target_bytes // 8:
            runs[-2].extend(runs.pop())
        for run in runs:
            off, placed = 0, []
            for m in run:
                placed.append(dataclasses.replace(m, offset=off))
                off += m.cols
            buckets.append(Zero3Bucket(
                name="", group=group, dtype=jnp.dtype(dtype_str),
                shard_dim=shard_dim, members=tuple(placed), cols=off,
            ))

    buckets.sort(key=lambda b: b.members[0].index)
    named = tuple(
        dataclasses.replace(b, name=f"z{i:03d}_{b.group}")
        for i, b in enumerate(buckets)
    )
    return Zero3GatherPlan(
        buckets=named, streamed=tuple(streamed), perleaf=tuple(perleaf),
        n_inter=n_inter, n_intra=n_intra, n_leaves=len(flat),
        target_bytes=int(target_bytes),
    )


def _zero3_member_rows(leaf, member: Zero3BucketMember,
                       n_inter: int, n_intra: int):
    """Model-shaped zero3-sharded leaf -> its [n_inter, n_intra, cols]
    row view: the sharded dim splits into (dp, d/dp), the dp axis moves
    to the front and factors into the two tiers, the rest flattens
    row-major — so element [i, j, :] is EXACTLY device (i, j)'s shard
    flattened in original axis order (shard-local under GSPMD)."""
    dp = n_inter * n_intra
    j, shape = member.shard_dim, member.shape
    x = leaf.reshape(shape[:j] + (dp, shape[j] // dp) + shape[j + 1:])
    x = jnp.moveaxis(x, j, 0)
    return x.reshape(n_inter, n_intra, -1)


def _zero3_member_unrows(rows, member: Zero3BucketMember):
    """Inverse of ``_zero3_member_rows`` on a REPLICATED (gathered)
    [n_inter, n_intra, cols] member segment -> the model-shaped leaf."""
    j, shape = member.shard_dim, member.shape
    dp = rows.shape[0] * rows.shape[1]
    x = rows.reshape((dp,) + shape[:j] + (shape[j] // dp,) + shape[j + 1:])
    x = jnp.moveaxis(x, 0, j)
    return x.reshape(shape)


def gather_zero3_bucketed(tree: Any, mesh,
                          target_bytes: int = BUCKET_TARGET_BYTES,
                          plan: Zero3GatherPlan | None = None,
                          staging_order: str = STAGING_ORDER) -> Any:
    """The unified engine's replacement for the per-leaf non-block
    zero3 gather: pack the shardable non-block leaves into
    [n_inter, n_intra, cols] buckets (scope ``bucket_pack`` — pure
    shard-local movement), replicate each with ONE hierarchy-aware
    staged all-gather (``hier_gather_bucket``: scopes
    ``bucket_ag_inter``/``bucket_ag_intra``, whose hand-written
    backward is the staged per-bucket grad reduce-scatter under
    ``bucket_rs_intra``/``bucket_rs_inter``), and unpack to model
    shapes (scope ``bucket_unpack``). Streamed (block-stack) leaves
    pass through untouched; leaves with no dividing dim gather per leaf
    under ``zero3_gather`` exactly as the oracle walk does."""
    import jax.tree_util as jtu

    from dinov3_tpu.parallel.sharding import (
        constrain_replicated,
        hier_bucket_spec,
        hier_gather_bucket,
    )

    if plan is None:
        plan = make_zero3_bucket_plan(tree, mesh, target_bytes)
    flat, treedef = jtu.tree_flatten_with_path(tree)
    if len(flat) != plan.n_leaves:
        raise ValueError(
            f"zero3 gather plan built for {plan.n_leaves} leaves, got a "
            f"tree with {len(flat)}"
        )
    leaves = [leaf for _, leaf in flat]
    out = list(leaves)

    spec = hier_bucket_spec(mesh)
    for b in plan.buckets:
        with jax.named_scope("bucket_pack"):
            parts = [
                _zero3_member_rows(leaves[m.index], m,
                                   plan.n_inter, plan.n_intra)
                for m in b.members
            ]
            rows = (parts[0] if len(parts) == 1
                    else jnp.concatenate(parts, axis=-1))
            # pin the packed bucket to its tiered layout so GSPMD sees
            # the pack as shard-local movement, not a resharding
            rows = jax.lax.with_sharding_constraint(
                rows, jax.sharding.NamedSharding(mesh, spec))
        full = hier_gather_bucket(rows, mesh, staging_order=staging_order)
        with jax.named_scope("bucket_unpack"):
            for m in b.members:
                seg = full[:, :, m.offset:m.offset + m.cols]
                out[m.index] = _zero3_member_unrows(seg, m)

    if plan.perleaf:
        with jax.named_scope("zero3_gather"):
            for i in plan.perleaf:
                out[i] = constrain_replicated(leaves[i], mesh)

    return jtu.tree_unflatten(treedef, out)


def make_zero3_gather_schedule(
    plan: Zero3GatherPlan, mesh, bucketed: bool = True,
    staging_order: str = STAGING_ORDER,
) -> Callable:
    """The unified gather phase with EXPLICIT collectives — the
    ``make_bucketed_update_schedule`` convention applied to the zero3
    non-block gather, compiled by scripts/cost_unified.py for the
    committed census (this container's XLA:CPU lowers the GSPMD
    engine's reduce-scatters in the pre-rewrite all-reduce+slice form,
    so the schedule twin is the committed proof of the post-rewrite
    collective set, exactly as for the flat bucketed engine).

    Returns ``gather(tree) -> gathered tree`` as ONE shard_map island
    over the zero3-sharded non-block subtree (``plan`` must have no
    streamed leaves — the in-scan block stream is censused by
    scripts/cost_zero3.py, not here). ``bucketed=True`` packs each
    bucket's member shards into the flat row the device already holds
    (shard-local ``reshape``+concat, scope ``bucket_pack``) and
    replicates it with the STAGED schedule: ``all_gather`` over the
    inter tier first (small shards cross the slow tier), then the intra
    tier, ``swapaxes`` restoring device order — scopes
    ``bucket_ag_inter``/``bucket_ag_intra`` — with a hand-written
    transpose issuing the staged grad reduce-scatter ``psum_scatter``
    intra-first/inter-second (scopes ``bucket_rs_intra``/
    ``bucket_rs_inter``): ONE RS per bucket per backward, tier for
    tier the mirror of the forward gather. ``bucketed=False`` is the
    per-leaf oracle: one ``all_gather`` per leaf along its zero3 dim
    (scope ``zero3_gather``), whose built-in transpose is one
    ``psum_scatter`` per grad leaf — the collective set the bucket arm
    collapses.

    ``staging_order`` ("<ag>_<rs>", parallel/sharding.py
    ``split_staging_order``) picks which tier each direction releases
    first. The gathered values are bitwise order-invariant (pure
    movement); the backward's partial-sum tree permutes across tiers,
    so the RS orders match to reduction tolerance.
    """
    import jax.tree_util as jtu

    from dinov3_tpu.parallel.sharding import (
        hierarchy_axes,
        split_staging_order,
        update_shard_size,
    )
    from jax.sharding import PartitionSpec as P

    if plan.streamed:
        raise ValueError(
            f"gather schedule twin covers the NON-block subtree; plan "
            f"has {len(plan.streamed)} streamed leaves — pass the tree "
            f"with the block stacks dropped"
        )
    if update_shard_size(mesh) != plan.dp:
        raise ValueError(
            f"plan built at dp={plan.dp}, mesh has "
            f"dp={update_shard_size(mesh)}")
    inter, intra = hierarchy_axes(mesh)
    axes = inter + intra
    n_inter, n_intra = plan.n_inter, plan.n_intra
    ag_first, rs_first = split_staging_order(staging_order)

    def _staged_ag(row):
        # [cols] shard row -> replicated [n_inter, n_intra, cols]
        if ag_first == "inter":
            with jax.named_scope("bucket_ag_inter"):
                g = (jax.lax.all_gather(row, inter, tiled=False)
                     if inter else row[None])
            with jax.named_scope("bucket_ag_intra"):
                g = jax.lax.all_gather(g, intra, tiled=False)
            return jnp.swapaxes(g, 0, 1)
        with jax.named_scope("bucket_ag_intra"):
            g = jax.lax.all_gather(row, intra, tiled=False)
        with jax.named_scope("bucket_ag_inter"):
            return (jax.lax.all_gather(g, inter, tiled=False)
                    if inter else g[None])

    @jax.custom_vjp
    def staged_gather(row):
        return _staged_ag(row)

    def _fwd(row):
        return _staged_ag(row), None

    def _bwd(_, ct):
        # replicated [n_inter, n_intra, cols] cotangent -> this
        # device's [cols] grad shard, per staging_order's RS half (the
        # default mirrors the forward tier for tier: intra
        # reduce-scatter first)
        if rs_first == "intra":
            with jax.named_scope("bucket_rs_intra"):
                r = jax.lax.psum_scatter(
                    ct, intra, scatter_dimension=1, tiled=False)
            with jax.named_scope("bucket_rs_inter"):
                r = (jax.lax.psum_scatter(
                    r, inter, scatter_dimension=0, tiled=False)
                    if inter else r[0])
            return (r,)
        with jax.named_scope("bucket_rs_inter"):
            r = (jax.lax.psum_scatter(
                ct, inter, scatter_dimension=0, tiled=False)
                if inter else ct[0])
        with jax.named_scope("bucket_rs_intra"):
            r = jax.lax.psum_scatter(
                r, intra, scatter_dimension=0, tiled=False)
        return (r,)

    staged_gather.defvjp(_fwd, _bwd)

    shard_dims = {m.index: m.shard_dim
                  for b in plan.buckets for m in b.members}

    def body(*leaves):
        out = list(leaves)
        for b in plan.buckets:
            if bucketed:
                with jax.named_scope("bucket_pack"):
                    # the local shard flattened in axis order IS the
                    # member's bucket-row segment (layout comment on
                    # the unified engine above) — pack is a reshape
                    parts = [leaves[m.index].reshape(-1)
                             for m in b.members]
                    row = (parts[0] if len(parts) == 1
                           else jnp.concatenate(parts))
                full3 = staged_gather(row)
                with jax.named_scope("bucket_unpack"):
                    for m in b.members:
                        seg = full3[:, :, m.offset:m.offset + m.cols]
                        out[m.index] = _zero3_member_unrows(seg, m)
            else:
                with jax.named_scope("zero3_gather"):
                    for m in b.members:
                        out[m.index] = jax.lax.all_gather(
                            leaves[m.index], axes,
                            axis=m.shard_dim, tiled=True)
        return tuple(out)

    def gather(tree):
        flat, treedef = jtu.tree_flatten_with_path(tree)
        if len(flat) != plan.n_leaves:
            raise ValueError(
                f"plan built for {plan.n_leaves} leaves, got "
                f"{len(flat)}")
        leaves = [leaf for _, leaf in flat]
        in_specs = tuple(
            P(*((None,) * shard_dims[i] + (axes,)))
            if i in shard_dims else P()
            for i in range(len(leaves))
        )
        out_specs = tuple(P() for _ in leaves)
        out = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )(*leaves)
        return jtu.tree_unflatten(treedef, list(out))

    return gather
