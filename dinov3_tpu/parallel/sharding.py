"""Logical-axis -> mesh sharding rules and sharded-init helpers.

Every parameter in dinov3_tpu/ops carries *logical* axis names
(``part(...)`` in ops/common.py). This module maps them onto the physical
mesh and produces the ``NamedSharding`` trees that drive ``jax.jit``
in/out shardings — GSPMD replaces the reference's per-module
all-gather/reduce-scatter interceptor (dinov3_jax/fsdp/utils.py:19-94,
SURVEY.md §7.1): XLA inserts the identical collectives from the sharding
annotations, overlapped with compute.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Logical axis -> mesh axis (or tuple of axes, or None = replicated).
#
# Parameter axes:
#   embed  — the model dim of every kernel/bias: sharded over fsdp (ZeRO-3;
#            all-gathered by XLA per layer on use).
#   heads  — qkv out dim; mlp — FFN hidden; vocab — DINO-head prototypes:
#            tensor-parallel (Megatron-style column/row split + 262k-proto
#            head sharding, SURVEY.md §7.3).
# Activation axes:
#   batch   — global batch: split over every data-parallel axis.
#   seq_act — patch-token dim under sequence/context parallelism.
DEFAULT_LOGICAL_RULES = (
    ("batch", ("dcn_data", "data", "fsdp")),
    ("seq_act", "seq"),
    # seq-sharded token axis of ATTENTION OUTPUTS under ring attention
    # (ops/attention.py): a separate name from "seq_act" so the high-res
    # stage can pin the ring path's activations to the seq axis without
    # re-labelling every dense-path token dim (which stays replicated —
    # short local crops never ring). Same mesh axis either way.
    ("seq_tokens", "seq"),
    ("embed", "fsdp"),
    ("heads", "tensor"),
    ("mlp", "tensor"),
    ("vocab", "tensor"),
    ("embed_act", None),
    # pipeline parallelism: the leading stage axis of stage-stacked block
    # params ([n_stages, blocks_per_stage, ...], parallel/pipeline.py) lives
    # on the pipe mesh axis; each pipe device holds and runs its own stage.
    ("stages", "pipe"),
    # MoE expert parallelism: the leading expert axis of stacked expert
    # FFN params ([n_experts, ...], ops/ffn.py MoEFFN) lives on the expert
    # mesh axis; each expert device computes its experts, outputs combine
    # with an all-reduce over the axis.
    ("experts", "expert"),
    # scan-over-blocks layer axis stays replicated (sharding it would be
    # FSDP-along-depth: an all-gather per use, not a pipeline).
    ("layers", None),
    # crop packing: the mixed global+packed student row axis
    # ([2B + P, N_g, D], ops/packing.py) splits over the same data axes
    # as "batch" — see constrain_packed_rows below for why the row
    # ORDER, not just the rule, is what keeps the pack shard-local.
    ("packed_rows", ("dcn_data", "data", "fsdp")),
    # bucketed collective engine (train/fused_update.py
    # make_bucketed_update): the flat axis of every COALESCED update
    # bucket — a few large concatenations of padded-flat leaves grouped
    # by (submodel, dtype, param-group) — splits over the SAME axes as
    # "batch", so the one-reduce-scatter-per-bucket grad sync and the
    # one-all-gather-per-bucket param/teacher re-materialization ride
    # the mesh axes the batch already rides — each data replica owns
    # 1/dp of every master/moment/teacher leaf for the update phase
    # (Xu et al. 2020's automatic cross-replica sharding, realized
    # through GSPMD annotations instead of a manual pass).
    ("bucket", ("dcn_data", "data", "fsdp")),
)

# the mesh axes a sharded update splits over — one tuple shared by the
# logical rule above, the in-graph constraints below, and the
# setup-time axis-size product, so the three can never disagree
UPDATE_SHARD_AXES = ("dcn_data", "data", "fsdp")

# the bucketed collective engine splits its flat buckets over the same
# axes (one bucket shard per data replica, like one update shard)
BUCKET_AXES = UPDATE_SHARD_AXES

# the ZeRO-3 weight-streaming engine (parallel.zero3, train/setup.py)
# shards the fp32 masters / EMA teacher / adam moments over the same
# axes the batch and the update shard ride — each replica stores 1/dp
# of every weight-shaped state leaf and the compute weights are
# re-materialized (all-gathered) at use
ZERO3_AXES = UPDATE_SHARD_AXES

# logical dim names that must never carry the zero3 axes: the leading
# stacked dim of scanned / pipelined / expert-stacked params (sharding
# the scan dim would turn the per-block dynamic-slice into a full-stack
# gather OUTSIDE the loop — exactly what weight streaming avoids)
_ZERO3_STACKED_NAMES = frozenset({"layers", "stages", "experts"})


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def lowp_scale_specs(tree: Any, mesh: Mesh) -> Any:
    """Replicated NamedShardings for a lowp amax-history/scale tree
    (ops/lowp.py): every leaf is a tiny f32 [H] (or [L, H] scanned)
    ring at a castable-kernel scale site — bytes are negligible next to
    one master leaf, and every device needs the derived scale at the
    quantize sites, so replicated is the only placement that never adds
    a collective. Kept explicit (rather than relying on the unboxed ->
    replicated default of ``state_shardings_from_abstract``) so the
    zero3 ``_replace`` overrides in setup can pin the lowp subtree
    deliberately alongside the sharded params/moments."""
    return jax.tree.map(lambda _: replicated(mesh), tree)


def batch_sharding(mesh: Mesh, seq_dim: int | None = None) -> NamedSharding:
    """Sharding for one batch leaf: dim 0 over all data axes, optional
    token dim over seq."""
    spec: list = [("dcn_data", "data", "fsdp")]
    if seq_dim is not None:
        spec.extend([None] * (seq_dim - 1))
        spec.append("seq")
    return NamedSharding(mesh, P(*spec))


def constrain_batch_dim(x: jax.Array, dim: int,
                        mesh: Mesh | None = None) -> jax.Array:
    """Pin ONE dimension of an in-graph array onto the data axes.

    Used by the step-wide RNG plan (rng/plan.py) so its stacked
    randomness arrays are BORN sharded along the batch axis under the
    same logical rule batch leaves use (("dcn_data", "data", "fsdp") —
    DEFAULT_LOGICAL_RULES "batch"): the per-layer slices the scanned
    blocks consume then stay span-local to each data shard, like the
    activations they index. Dims other than ``dim`` are replicated
    (they are tiny: layer count, branch pair). No-op without a mesh or
    when the dim does not divide over the data axes (tiny test shapes).
    """
    if mesh is None:
        from dinov3_tpu.parallel.context import get_current_mesh

        mesh = get_current_mesh()
    if mesh is None:
        return x
    dp = 1
    for a in ("dcn_data", "data", "fsdp"):
        dp *= int(mesh.shape.get(a, 1))
    if dp <= 1 or x.shape[dim] % dp != 0:
        return x
    spec = [None] * x.ndim
    spec[dim] = ("dcn_data", "data", "fsdp")
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def update_shard_size(mesh: Mesh | None = None) -> int:
    """Number of update shards = product of the data-parallel axis sizes
    (``UPDATE_SHARD_AXES``). 1 without a mesh — the replicated engine."""
    if mesh is None:
        from dinov3_tpu.parallel.context import get_current_mesh

        mesh = get_current_mesh()
    if mesh is None:
        return 1
    dp = 1
    for a in UPDATE_SHARD_AXES:
        dp *= int(mesh.shape.get(a, 1))
    return max(1, dp)


def constrain_update_shard(x: jax.Array,
                           mesh: Mesh | None = None) -> jax.Array:
    """Pin a flat padded update-phase leaf (1-D, size divisible by
    ``update_shard_size``) onto the data axes. The bucketed engine
    routes every flattened master/teacher/multiplier leaf, and every
    member it slices out of a bucket, through this, so its leaf-by-leaf
    math runs on 1/dp shards over the same mesh axes as "batch". No-op
    without a mesh (replicated test shapes)."""
    if mesh is None:
        from dinov3_tpu.parallel.context import get_current_mesh

        mesh = get_current_mesh()
    if mesh is None:
        return x
    dp = update_shard_size(mesh)
    if dp <= 1 or x.shape[0] % dp != 0:
        return x
    spec = [None] * x.ndim
    spec[0] = tuple(a for a in UPDATE_SHARD_AXES if a in mesh.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def constrain_bucket(x: jax.Array, mesh: Mesh | None = None) -> jax.Array:
    """Pin one flat update BUCKET (1-D concatenation of padded-flat
    leaves, size divisible by ``update_shard_size``) onto the data axes
    — the "bucket" logical rule. The bucketed collective engine
    (train/fused_update.py make_bucketed_update) routes each coalesced
    grad/master/moment/teacher bucket through this, so the grad sync
    lowers as ONE reduce-scatter per bucket and the updated-param
    re-materialization as ONE all-gather per bucket, instead of one
    collective per leaf (``constrain_update_shard``). No-op without a
    mesh (replicated test shapes)."""
    if mesh is None:
        from dinov3_tpu.parallel.context import get_current_mesh

        mesh = get_current_mesh()
    if mesh is None:
        return x
    dp = update_shard_size(mesh)
    if dp <= 1 or x.shape[0] % dp != 0:
        return x
    spec = [None] * x.ndim
    spec[0] = tuple(a for a in BUCKET_AXES if a in mesh.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def packed_row_groups(mesh: Mesh | None = None) -> int:
    """Data-shard count for the crop-packed row layout (ops/packing.py).

    The packed student batch interleaves global and packed rows in
    data-shard-sized groups ([shard0 globals, shard0 packed, shard1
    globals, ...]) so that the even GSPMD split of the concatenated row
    axis coincides with a shard-local concatenation — each shard packs
    ITS OWN local crops and never moves rows at the pack boundary. A
    plain [globals..., packed...] order under the same even split would
    put ~half of every shard's rows on other shards and force a
    resharding all-to-all of the full token tensor per step direction.
    ``make_packed_layout`` degrades to 1 (plain order) when the row
    counts don't divide by this.
    """
    if mesh is None:
        from dinov3_tpu.parallel.context import get_current_mesh

        mesh = get_current_mesh()
    if mesh is None:
        return 1
    from dinov3_tpu.parallel.mesh import data_parallel_size

    return max(1, int(data_parallel_size(mesh)))


def constrain_packed_rows(x: jax.Array,
                          mesh: Mesh | None = None) -> jax.Array:
    """Pin the packed student row axis (dim 0 of [2B+P, N_g, D]) onto
    the data axes — the "packed_rows" logical rule. Combined with the
    shard-grouped row order (``packed_row_groups``), the pack/unpack
    reshapes stay shard-local under GSPMD. No-op without a mesh or when
    the row count does not divide (constrain_batch_dim's convention)."""
    return constrain_batch_dim(x, 0, mesh)


def batch_specs(mesh: Mesh, batch: dict) -> dict:
    """NamedSharding tree for a collated batch dict (all leaves are
    [global_batch, ...] arrays; scalars replicated)."""
    return jax.tree.map(
        lambda x: replicated(mesh) if getattr(x, "ndim", 0) == 0
        else batch_sharding(mesh),
        batch,
    )


def state_shardings_from_abstract(
    abstract_boxed: Any, mesh: Mesh, rules=DEFAULT_LOGICAL_RULES
) -> Any:
    """NamedSharding tree from an ``eval_shape`` of a *boxed* init.

    ``abstract_boxed`` is the pytree returned by
    ``jax.eval_shape(boxed_init_fn, ...)`` where params still carry
    ``nn.Partitioned`` logical metadata (optax state built from boxed
    params keeps the boxes in its mu/nu subtrees — so one call covers
    params AND optimizer state). Unboxed leaves (step counters, centers)
    come out replicated.
    """
    logical_specs = nn.get_partition_spec(abstract_boxed)
    return nn.logical_to_mesh_sharding(logical_specs, mesh, list(rules))


# ---------------- ZeRO-3 weight-streaming layout ----------------
#
# The zero3 engine (train/setup.py, parallel.zero3) stores every master/
# teacher/moment leaf in its MODEL shape but sharded over the data axes
# on one dividing dimension — unlike the flat padded layout of the
# bucketed UPDATE engine ("bucket" above), which is a step-internal
# packing. Keeping the model shape is what makes the rest of the system
# compose: the scanned block stack enters ``lax.scan`` still sharded and
# each block is all-gathered *inside* the loop at its use (a flat layout
# would force a pre-loop all-to-all back to model form, hoisting the
# whole-stack gather out of the scan); checkpoints keep the replicated
# arm's leaf shapes, so replicated <-> zero3 restores are pure
# re-placements; and the fused update engine runs unchanged — GSPMD
# makes its elementwise tree pass shard-local because every input and
# output leaf carries the same zero3 sharding.


def zero3_shard_size(mesh: Mesh | None = None) -> int:
    """Number of zero3 shards (== ``update_shard_size``: the data-axis
    product; the two engines split over the same mesh axes)."""
    return update_shard_size(mesh)


def zero3_leaf_spec(
    shape, names, mesh: Mesh, rules=DEFAULT_LOGICAL_RULES
):
    """The zero3 ``PartitionSpec`` for one master leaf, or None when no
    dimension can carry the data axes (the leaf stays on its
    logical-rules sharding, i.e. replicated over the data axes).

    Starts from the leaf's logical axis ``names`` (the ``nn.Partitioned``
    box): stacked dims (``layers``/``stages``/``experts``) and dims
    mapped to a >1 model-parallel mesh axis by the rules keep their
    assignment and are skipped; the ``embed`` -> fsdp rule is *subsumed*
    (zero3 shards over the full data-axis product, fsdp included). The
    update axes land on the largest remaining dim whose size divides the
    shard count (ties -> lowest index).
    """
    dp = zero3_shard_size(mesh)
    if dp <= 1 or not shape:
        return None
    rule_map = dict(rules)
    spec: list = [None] * len(shape)
    free = []
    for i, d in enumerate(shape):
        nm = names[i] if names is not None and i < len(names) else None
        if nm is None:
            free.append(i)
            continue
        if nm in _ZERO3_STACKED_NAMES:
            mapped = rule_map.get(nm)
            if mapped is not None and int(mesh.shape.get(mapped, 1)) > 1:
                spec[i] = mapped
            continue
        mapped = rule_map.get(nm)
        if mapped is None or mapped == "fsdp" or mapped == ("fsdp",):
            # unmapped or the embed->fsdp ZeRO-3-ish rule: free for zero3
            free.append(i)
            continue
        sizes = mapped if isinstance(mapped, tuple) else (mapped,)
        if any(int(mesh.shape.get(a, 1)) > 1 for a in sizes):
            spec[i] = mapped  # model-parallel dim: keep, don't touch
        else:
            free.append(i)
    best = None
    for i in free:
        if shape[i] % dp == 0 and (best is None or shape[i] > shape[best]):
            best = i
    if best is None:
        return None
    from jax.sharding import PartitionSpec as P

    spec[best] = tuple(a for a in ZERO3_AXES if a in mesh.shape)
    return P(*spec)


def zero3_shardings_from_abstract(
    abstract_boxed: Any, mesh: Mesh, rules=DEFAULT_LOGICAL_RULES
) -> Any:
    """NamedSharding tree for a *boxed* master subtree under zero3.

    Each ``nn.Partitioned`` leaf gets ``zero3_leaf_spec``'s placement;
    leaves without a dividing free dim (and unboxed leaves — step
    counters) fall back to the logical-rules sharding, exactly what
    ``state_shardings_from_abstract`` would have produced.
    """

    def leaf(x):
        if isinstance(x, nn.Partitioned):
            shape, names = x.value.shape, x.names
        else:
            shape, names = x.shape, (None,) * len(x.shape)
        spec = zero3_leaf_spec(shape, names, mesh, rules)
        if spec is None:
            logical = jax.sharding.PartitionSpec(
                *(names if names is not None else ()))
            return nn.logical_to_mesh_sharding(logical, mesh, list(rules))
        return NamedSharding(mesh, spec)

    return jax.tree.map(
        leaf, abstract_boxed,
        is_leaf=lambda x: isinstance(x, nn.Partitioned),
    )


def zero3_replicated_waste(
    shapes_and_names, mesh: Mesh, rules=DEFAULT_LOGICAL_RULES
) -> float:
    """Fraction of master elements zero3 cannot shard (no free dim
    divides the shard count) — the layout's per-device overhead over a
    perfect 1/dp split, the analogue of the bucketed engine's
    zero-padding waste. ``shapes_and_names``: iterable of (shape, names)
    pairs from the boxed abstract tree. Returns 0.0 for an empty tree."""
    total = stuck = 0
    for shape, names in shapes_and_names:
        n = 1
        for d in shape:
            n *= int(d)
        total += n
        if zero3_leaf_spec(shape, names, mesh, rules) is None:
            stuck += n
    return stuck / total if total else 0.0


def constrain_replicated(x: jax.Array, mesh: Mesh | None = None) -> jax.Array:
    """Pin one in-graph array to the fully replicated layout — the
    zero3 engine's *materialization* point: applied to a sharded master
    (or a bf16 cast of one) it makes GSPMD insert the all-gather exactly
    here, which the named scopes at the call sites
    (``zero3_gather``/``zero3_stream``/``zero3_prefetch``) then pin for
    the collective-census attribution. Only safe where the leaf carries
    no model-parallel dims (the zero3 stream gates itself on a
    model-parallel-free config). No-op without a mesh."""
    if mesh is None:
        from dinov3_tpu.parallel.context import get_current_mesh

        mesh = get_current_mesh()
    if mesh is None:
        return x
    # an op of the CALLER's scope that keeps x's own (sharded)
    # placement: the partitioner stamps a reshard with its producer's
    # op_name, so without this anchor the gather of a value produced
    # outside the scope (a scan's weight slice) carries the producer's
    # name and the collective census cannot attribute it
    # (traced values only: an eager array has no open dims to keep)
    P = jax.sharding.PartitionSpec
    if isinstance(x, jax.core.Tracer):
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*[P.UNCONSTRAINED] * x.ndim)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


def zero3_materialize_tree(tree: Any, mesh: Mesh | None = None) -> Any:
    """Replicate every leaf of a zero3-sharded master subtree for
    compute (the ZeRO-3 "gather params for this pass" step), under the
    ``zero3_gather`` named scope so the census attributes the
    collectives. Used by the meta arch for the NON-streamed subtrees
    (heads, patch embed, norms); the scanned block stack never goes
    through this — its weights are gathered per block inside the scan
    (ops/block.py zero3 stream). No-op without a mesh."""
    if mesh is None:
        from dinov3_tpu.parallel.context import get_current_mesh

        mesh = get_current_mesh()
    if mesh is None:
        return tree
    with jax.named_scope("zero3_gather"):
        return jax.tree.map(lambda x: constrain_replicated(x, mesh), tree)


# ---------------- hierarchy-aware bucketed gathers (the unified
# zero3 x bucketed-collectives engine, train/fused_update.py
# make_zero3_bucket_plan + ssl_meta_arch._zero3_gather_params) --------
#
# On a dp x fsdp mesh the data axes split into two bandwidth tiers:
# fsdp is the ICI-innermost (fast) tier, the remaining >1 data axes
# (dcn_data / data) the slow inter-slice tier. The bandwidth-optimal
# hierarchical all-gather (PAPERS.md 2408.13356) gathers over the SLOW
# tier first — each device moves its small 1/dp shard across the slow
# links once, then the fast tier broadcasts the assembled 1/n_intra
# segments — and its transpose reduce-scatters over the FAST tier
# first, shrinking the cotangent n_intra-fold before it ever touches a
# slow link. The staging below expresses both orders as sharding
# constraints on a [n_inter, n_intra, cols] bucket view, placed through
# the mesh axes by GSPMD (2105.04663) exactly like every other
# collective in this repo.


def hierarchy_axes(mesh: Mesh) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """Split the PRESENT (>1) zero3 data axes into the two bandwidth
    tiers: ``(inter_axes, intra_axes)``.

    ``intra`` is the innermost present axis (fsdp on dp x fsdp meshes —
    the ICI tier mesh construction places innermost/fastest); ``inter``
    is every other present data axis. A single-tier mesh degrades to
    ``((), (axis,))`` — the staged schedule then collapses to one
    gather/scatter stage; an all-replicated mesh returns ``((), ())``.
    """
    present = tuple(
        a for a in ZERO3_AXES if int(mesh.shape.get(a, 1)) > 1)
    if not present:
        return (), ()
    return present[:-1], present[-1:]


def hier_bucket_spec(mesh: Mesh):
    """The fully-sharded ``PartitionSpec`` of one gather bucket in its
    ``[n_inter, n_intra, cols]`` view: dim 0 over the inter tier, dim 1
    over the intra tier (empty tiers replicate their dim)."""
    inter, intra = hierarchy_axes(mesh)
    return P(inter or None, intra or None, None)


def _constrain3(x: jax.Array, mesh: Mesh, spec: P, scope: str) -> jax.Array:
    with jax.named_scope(scope):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


STAGING_ORDERS = ("inter_intra", "intra_inter", "inter_inter",
                  "intra_intra")


# The order every staged bucket gather runs in, from the bandwidth
# model: AG moves the small 1/dp shards over the slow inter links
# first, RS shrinks the cotangent n_intra-fold on the fast links before
# it touches a slow one (PAPERS.md 2408.13356).
STAGING_ORDER = "inter_intra"


def split_staging_order(order: str) -> tuple[str, str]:
    """``"<ag>_<rs>"`` -> ``(ag_first, rs_first)``, each "inter" or
    "intra" naming the tier the forward all-gather (resp. backward
    reduce-scatter) releases FIRST. The orders other than
    ``STAGING_ORDER`` are pure wire-schedule permutations of the same
    data movement, kept as arguments so the tests can show that all
    four give the same numbers."""
    if order not in STAGING_ORDERS:
        raise ValueError(
            f"staging order {order!r}: expected one of {STAGING_ORDERS}")
    ag, rs = order.split("_")
    return ag, rs


def hier_gather_bucket(
    x: jax.Array, mesh: Mesh, staging_order: str = STAGING_ORDER,
) -> jax.Array:
    """Replicate one flat gather bucket with the hierarchy-aware
    two-stage schedule, differentiable with direction-true scope names.

    ``x``: ``[n_inter, n_intra, cols]`` sharded per ``hier_bucket_spec``
    (device ``(i_inter, i_intra)`` holds element ``[i_inter, i_intra,
    :]`` — its own shard, so the pack that built the bucket was
    shard-local). Forward releases the tiers in ``staging_order``'s AG
    half — the default constrains dim 0 replicated under
    ``bucket_ag_inter`` (the slow tier moves 1/dp-sized shards), then
    dim 1 replicated under ``bucket_ag_intra`` (the fast tier
    broadcasts the assembled segments); "intra"-first releases dim 1
    before dim 0. Pure data movement — values are bitwise whatever the
    staging; the scopes keep their tier names under either order.

    The backward is a hand-written ``custom_vjp``, NOT the autodiff
    transpose: a transposed sharding constraint keeps the FORWARD
    scope in its ``op_name`` (``transpose(bucket_ag_inter)``), so the
    census could never tell the grad reduce-scatters from the gathers.
    The bwd applies ``staging_order``'s RS half to the cotangent — the
    default reduce-scatters the intra tier first (``bucket_rs_intra``:
    the fast links do the n_intra-fold volume reduction), then inter
    (``bucket_rs_inter``) — and GSPMD materializes the partial-sum
    reductions as reduce-scatters at exactly these constraint points.
    NOTE the RS order permutes the floating-point partial-sum tree
    across tiers, so the four orders match to reduction tolerance, not
    bitwise (tests/test_unified_buckets.py pins both properties).
    """
    inter, intra = hierarchy_axes(mesh)
    if not inter and not intra:
        return x
    ag_first, rs_first = split_staging_order(staging_order)
    sharded = P(inter or None, intra or None, None)
    # the intermediate layout after releasing one tier, keyed by which
    # tier went first (releasing an absent tier is a no-op constraint,
    # so single-tier meshes collapse to one stage under either order)
    inter_done = P(None, intra or None, None)
    intra_done = P(inter or None, None, None)

    def _primal(b):
        if ag_first == "inter":
            if inter:
                b = _constrain3(b, mesh, inter_done, "bucket_ag_inter")
            return _constrain3(
                b, mesh, P(None, None, None), "bucket_ag_intra")
        if intra:
            b = _constrain3(b, mesh, intra_done, "bucket_ag_intra")
        return _constrain3(b, mesh, P(None, None, None), "bucket_ag_inter")

    @jax.custom_vjp
    def gather(b):
        return _primal(b)

    def fwd(b):
        return _primal(b), None

    def bwd(_, ct):
        if rs_first == "intra":
            ct = _constrain3(ct, mesh, inter_done, "bucket_rs_intra")
            if inter:
                ct = _constrain3(ct, mesh, sharded, "bucket_rs_inter")
        else:
            if inter:
                ct = _constrain3(ct, mesh, intra_done, "bucket_rs_inter")
            ct = _constrain3(ct, mesh, sharded, "bucket_rs_intra")
        return (ct,)

    gather.defvjp(fwd, bwd)
    return gather(x)


def make_sharded_init(
    boxed_init_fn: Callable,
    mesh: Mesh,
    rules=DEFAULT_LOGICAL_RULES,
    example_args: tuple = (),
    example_kwargs: dict | None = None,
):
    """Compile ``boxed_init_fn`` so its outputs are born sharded.

    Returns ``(init_fn, shardings)``: ``init_fn(*args)`` produces the
    *unboxed* state tree laid out per ``shardings`` (the reference
    materialized replicated params then re-sharded with dynamic_slice —
    fsdp/utils.py:19-53; here each device only ever materializes its own
    shard).
    """
    example_kwargs = example_kwargs or {}
    abstract = jax.eval_shape(boxed_init_fn, *example_args, **example_kwargs)
    shardings = state_shardings_from_abstract(abstract, mesh, rules)

    def unboxed_init(*args, **kwargs):
        return nn.meta.unbox(boxed_init_fn(*args, **kwargs))

    jit_init = jax.jit(unboxed_init, out_shardings=shardings)
    return jit_init, shardings
