"""Unified parallelism engine (zero3 x bucketed collectives x
microbatched gradient accumulation) vs its per-leaf / monolithic
oracles.

The unified arm (``parallel.zero3`` + ``optim.bucketed_collectives`` on
an fsdp>1 mesh, train/setup.py) coalesces the NON-block zero3 subtree
gathers of the forward into hierarchy-aware flat buckets
(train/fused_update.py ``make_zero3_bucket_plan`` /
``gather_zero3_bucketed``): members grouped by (top-level submodel,
dtype, zero3 shard_dim), packed with NO padding (every member's sharded
dim divides the data-axis product by construction), gathered inter-tier
first then intra (scopes ``bucket_ag_inter`` / ``bucket_ag_intra``)
with the transposed grad reduce-scatter staged the other way
(``bucket_rs_intra`` / ``bucket_rs_inter``). The per-leaf zero3 gather
stays in the tree as the bitwise oracle behind
``bucketed_collectives=false``; the in-scan block stream is untouched
by design. ``optim.accum_steps`` scans the fwd/bwd over equal
microbatches with the gathers HOISTED as scan constants, so ONE
bucketed grad-RS per bucket fires per optimizer step regardless of
accum_steps.

These tests pin:

- the gather-plan layout (grouping key, zero-padding-free packing,
  streamed/perleaf classification, byte-target splitting) and the
  member pack/unpack round-trip;
- the microbatch split's crop-major regroup semantics and its
  guardrails (trace-time raise + ``warn_accum_batch_tiling``);
- setup wiring: unified auto-on for zero3 fsdp meshes, per-leaf oracle
  behind ``=false``, and the LIFTED raise (bucketed=true now composes
  with zero3 instead of raising);
- unified vs per-leaf zero3 dryrun equivalence on a dp x fsdp mesh
  (same-state seeding, PR-7 tolerances);
- accum_steps in {1,2,4} loss trajectories vs the monolithic oracle
  (fp32 + batch-decoupled losses: the microbatch means are the batch
  means up to summation order — the sliced microbatch is pinned back
  onto the canonical batch layout inside the scan, without which the
  partitioner picks a DIFFERENT layout than the monolithic arm and the
  arms diverge ~1e-2);
- the compiled step's collective census: coalesced bucket gathers
  attributed on BOTH mesh tiers, zero unattributed collectives, scoped
  grad-RS present, and bucket collective counts INVARIANT in
  accum_steps;
- the explicit schedule twin (``make_zero3_gather_schedule``): forward
  bitwise vs the per-leaf oracle and the host values, per-tier scope
  ops exactly one per bucket, grads matching at float tolerance;
- the hierarchical option of the bucketed stream scan (bitwise vs the
  flat gather, staged scopes present);
- cross-arm checkpoints (unified <-> per-leaf zero3 bitwise + resume
  determinism; a bucketed-arm checkpoint restoring into the unified
  arm);
- the committed COST_UNIFIED_r18.json acceptance numbers.
"""

import json
import os

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from dinov3_tpu.configs import apply_dot_overrides, get_default_config
from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
from dinov3_tpu.parallel.sharding import hierarchy_axes, zero3_leaf_spec
from dinov3_tpu.train.fused_update import (
    Zero3GatherPlan,
    _zero3_member_rows,
    _zero3_member_unrows,
    make_zero3_bucket_plan,
    make_zero3_gather_schedule,
    zero3_streamed_path,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOL = [
    "student.arch=vit_test", "student.patch_size=4",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2", "train.batch_size_per_device=2",
    "optim.scaling_rule=none", "train.scan_layers=true",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
    "dino.head_bottleneck_dim=16",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
    "ibot.head_bottleneck_dim=16",
    "train.OFFICIAL_EPOCH_LENGTH=4", "optim.epochs=4",
    "optim.warmup_epochs=1", "telemetry.async_metrics=false",
]

# batch-decoupled loss config for the accum trajectory pins: sinkhorn
# (batch-normalized), koleo (batch kNN) and drop-path (per-microbatch
# draws) genuinely couple the loss to the batch partition, so the
# microbatch means only equal the monolithic means without them
NEUTRAL = [
    "train.centering=softmax_center",
    "dino.koleo_loss_weight=0.0",
    "student.drop_path_rate=0.0",
    "compute_precision.compute_dtype=fp32",
]


def _setup(extra, batch_size, devices):
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    cfg = get_default_config()
    apply_dot_overrides(cfg, SMOL + list(extra))
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, batch_size, seed=0).items()}
    return build_train_setup(cfg, batch, devices=devices), batch


def _use(s):
    """Re-pin the ambient current-mesh to this setup's mesh: tests in
    this file build setups on several mesh shapes, and tracing a
    setup's step_fn under another setup's mesh context silently
    resolves the layout constraints against the wrong mesh."""
    from dinov3_tpu.parallel.context import set_current_mesh

    set_current_mesh(s.mesh)
    return s


def _flat_params(tree):
    return jtu.tree_flatten_with_path(tree)[0]


def assert_trees_bitwise(a, b, what, limit=None):
    fa, fb = _flat_params(a), _flat_params(b)
    assert len(fa) == len(fb)
    for (pa, la), (_, lb) in (zip(fa, fb) if limit is None
                              else zip(fa[:limit], fb[:limit])):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), (
            f"{what}: {jtu.keystr(pa)} differs")


def _dp_fsdp_mesh(devices):
    return build_mesh(MeshSpec(data=2, fsdp=4), devices=devices)


def _zero3_put(tree, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    def leaf(x):
        spec = zero3_leaf_spec(x.shape, (None,) * x.ndim, mesh)
        return jax.device_put(
            jnp.asarray(x), NamedSharding(mesh, spec if spec else P()))
    return jax.tree.map(leaf, tree)


def _toy_tree(with_blocks=False):
    rng = np.random.default_rng(0)
    tree = {
        "backbone": {
            "patch_embed": {
                "kernel": rng.normal(size=(4, 4, 3, 16)).astype(np.float32),
                "bias": rng.normal(size=(16,)).astype(np.float32)},
            "norm": {"scale": rng.normal(size=(16,)).astype(np.float32)},
            "cls_token": rng.normal(size=(1, 1, 16)).astype(np.float32),
            # no dim divides dp=8 -> perleaf
            "odd": rng.normal(size=(3, 5)).astype(np.float32),
        },
        "dino_head": {
            "mlp1": {"kernel": rng.normal(size=(16, 64)).astype(np.float32),
                     "bias": rng.normal(size=(64,)).astype(np.float32)},
            "last": {"kernel": rng.normal(size=(64, 32)).astype(np.float32)},
        },
    }
    if with_blocks:
        tree["backbone"]["blocks"] = {
            "attn": {"kernel": rng.normal(size=(4, 16, 16)
                                          ).astype(np.float32)}}
    return tree


# ---------------- gather-plan layout ----------------

def test_zero3_streamed_path_rule():
    class K:
        def __init__(self, key):
            self.key = key

    assert zero3_streamed_path((K("backbone"), K("blocks"), K("kernel")))
    assert zero3_streamed_path((K("blocks_3"), K("kernel")))
    assert zero3_streamed_path((K("pipeline"), K("w")))
    assert not zero3_streamed_path((K("backbone"), K("patch_embed")))
    assert not zero3_streamed_path((K("dino_head"), K("blocksmith")))


def test_bucket_plan_grouping_and_no_padding(eight_devices):
    mesh = _dp_fsdp_mesh(eight_devices)
    plan = make_zero3_bucket_plan(_toy_tree(), mesh)
    assert isinstance(plan, Zero3GatherPlan)
    assert plan.n_inter == 2 and plan.n_intra == 4 and plan.dp == 8
    assert not plan.streamed
    # the (3,5) leaf has no dp-dividing dim -> perleaf oracle gather
    assert len(plan.perleaf) == 1
    for b in plan.buckets:
        # one (submodel, dtype, shard_dim) per bucket
        assert all(m.shard_dim == b.shard_dim for m in b.members)
        assert b.name.endswith(b.group)
        # zero-padding-free packing: cols * dp == size member for
        # member, offsets contiguous
        off = 0
        for m in b.members:
            assert m.cols * plan.dp == m.size
            assert m.offset == off
            off += m.cols
        assert b.cols == off
    # every non-streamed, non-perleaf leaf is in exactly one bucket
    covered = sorted(m.index for b in plan.buckets for m in b.members)
    assert len(covered) == len(set(covered))
    assert len(covered) + len(plan.perleaf) == plan.n_leaves
    # submodels never share a bucket
    assert {b.group for b in plan.buckets} <= {"backbone", "dino_head"}


def test_bucket_plan_streamed_exclusion(eight_devices):
    mesh = _dp_fsdp_mesh(eight_devices)
    plan = make_zero3_bucket_plan(_toy_tree(with_blocks=True), mesh)
    assert len(plan.streamed) == 1
    bucketed = {m.index for b in plan.buckets for m in b.members}
    assert not bucketed & set(plan.streamed)
    for b in plan.buckets:
        for m in b.members:
            assert "blocks" not in m.path


def test_bucket_plan_byte_target_split(eight_devices):
    mesh = _dp_fsdp_mesh(eight_devices)
    small = make_zero3_bucket_plan(_toy_tree(), mesh, target_bytes=2 ** 10)
    big = make_zero3_bucket_plan(_toy_tree(), mesh, target_bytes=2 ** 30)
    assert len(small.buckets) > len(big.buckets)
    # the byte target caps buckets except single oversized members
    for b in small.buckets:
        nbytes = b.cols * small.dp * jnp.dtype(b.dtype).itemsize
        assert nbytes <= 2 ** 10 or len(b.members) == 1
    assert small.stats()  # accounting rows build


def test_member_rows_unrows_roundtrip(eight_devices):
    mesh = _dp_fsdp_mesh(eight_devices)
    plan = make_zero3_bucket_plan(_toy_tree(), mesh)
    leaves = [leaf for _, leaf in
              jtu.tree_flatten_with_path(_toy_tree())[0]]
    for b in plan.buckets:
        for m in b.members:
            leaf = jnp.asarray(leaves[m.index])
            rows = _zero3_member_rows(
                leaf, m, plan.n_inter, plan.n_intra)
            assert rows.shape == (plan.n_inter, plan.n_intra, m.cols)
            back = _zero3_member_unrows(rows, m)
            assert back.shape == m.shape
            np.testing.assert_array_equal(np.asarray(back),
                                          np.asarray(leaf))


def test_hierarchy_axes_tiers(eight_devices):
    mesh = _dp_fsdp_mesh(eight_devices)
    inter, intra = hierarchy_axes(mesh)
    assert inter == ("data",) and intra == ("fsdp",)
    dp_only = build_mesh(MeshSpec(data=8), devices=eight_devices)
    inter, intra = hierarchy_axes(dp_only)
    assert inter == () and intra == ("data",)


# ---------------- microbatch split ----------------

def test_split_microbatches_crop_major_regroup():
    from dinov3_tpu.train.train_step import split_microbatches

    B, accum = 8, 4
    # k=2 crop-major leaf: value encodes (crop, image)
    g = jnp.arange(2 * B).reshape(2 * B, 1)
    l = jnp.arange(3 * B).reshape(3 * B, 1)  # k=3
    out = split_microbatches({"global_crops": g, "local": l,
                              "s": jnp.float32(3.0)}, accum)
    m = B // accum
    for leaf, k in (("global_crops", 2), ("local", 3)):
        arr = out[leaf]
        assert arr.shape[0] == accum and arr.shape[1] == k * m
        for a in range(accum):
            for c in range(k):
                for i in range(m):
                    # microbatch a holds ALL k crops of image subset a,
                    # itself crop-major
                    assert int(arr[a, c * m + i, 0]) == c * B + a * m + i
    assert out["s"].ndim == 0  # scalars broadcast unchanged
    same = split_microbatches({"global_crops": g}, 1)
    assert same["global_crops"] is g  # accum=1 is a pass-through


def test_split_microbatches_raises_on_bad_tiling():
    from dinov3_tpu.train.train_step import split_microbatches

    g = jnp.zeros((2 * 6, 1))
    with pytest.raises(ValueError, match="optim.accum_steps"):
        split_microbatches({"global_crops": g}, 4)  # 6 % 4 != 0


def test_warn_accum_batch_tiling_guardrail():
    from dinov3_tpu.configs.config import warn_accum_batch_tiling

    cfg = get_default_config()
    apply_dot_overrides(cfg, SMOL + ["optim.accum_steps=3"])
    with pytest.warns(UserWarning, match="optim.accum_steps axis"):
        msgs = warn_accum_batch_tiling(cfg, per_chip_batch=2)
    assert msgs and "does not divide" in msgs[0]
    # dividing accum on a clean microbatch: silent
    cfg2 = get_default_config()
    apply_dot_overrides(cfg2, SMOL + ["optim.accum_steps=2"])
    assert warn_accum_batch_tiling(cfg2, per_chip_batch=16) == []


# ---------------- setup wiring ----------------

@pytest.fixture(scope="module")
def arms_unified(eight_devices):
    """Unified arm + its per-leaf zero3 oracle on the dp x fsdp mesh,
    fp32 compute (PR-7 dryrun convention), with the put batch."""
    from dinov3_tpu.train import put_batch

    common = ["parallel.data=-1", "parallel.fsdp=2",
              "parallel.zero3=auto",
              "compute_precision.compute_dtype=fp32"]
    s_u, batch = _setup(common, 16, eight_devices)
    s_o, _ = _setup(common + ["optim.bucketed_collectives=false"], 16,
                    eight_devices)
    d = put_batch(batch, s_u.batch_shardings)
    return s_u, s_o, d


def test_setup_unified_wiring(arms_unified):
    s_u, s_o, _ = arms_unified
    # auto composes zero3 + buckets on the fsdp mesh; =false keeps the
    # per-leaf oracle on the same zero3 layout
    assert s_u.zero3 and s_u.zero3_buckets
    assert s_o.zero3 and not s_o.zero3_buckets
    plan = s_u.zero3_bucket_plan
    assert plan is not None and len(plan.buckets) >= 1
    assert plan.streamed  # the block stack stays with the in-scan stream
    assert plan.dp == 8


def test_setup_explicit_bucketed_composes_with_zero3(eight_devices):
    """The lifted raise: bucketed_collectives=true + zero3 no longer
    conflicts — it selects the unified arm even with the fused update
    disabled."""
    s, _ = _setup(["parallel.data=-1", "parallel.fsdp=2",
                   "parallel.zero3=auto",
                   "optim.bucketed_collectives=true"], 16, eight_devices)
    assert s.zero3 and s.zero3_buckets


def test_setup_bucketed_raise_names_unified_arm(eight_devices):
    """On NON-zero3 meshes the explicit-bucketed requirements still
    raise, and the error text points at the unified arm as the
    exception."""
    with pytest.raises(ValueError, match="unified zero3 gather-bucket"):
        _setup(["parallel.data=-1", "parallel.zero3=false",
                "optim.fused_update=false",
                "optim.bucketed_collectives=true"], 16, jax.devices())


# ---------------- unified vs per-leaf dryrun equivalence ----------------

def test_dryrun_unified_vs_perleaf_zero3(arms_unified):
    """Both arms share the zero3 state layout, so they start from the
    SAME state (pure re-placement); two steps must match at the PR-7
    dp x fsdp tolerances — only reduction associativity separates the
    bucketed staged gathers from the per-leaf ones in fp32."""
    s_u, s_o, d = arms_unified
    results = {}
    for name, setup in (("unified", s_u), ("perleaf", s_o)):
        _use(setup)
        # step from a COPY: step_fn donates its state input, and the
        # two arms share the zero3 layout (device_put would alias)
        state = jax.tree.map(jnp.copy, s_u.state)
        for i in range(2):
            state, m = setup.step_fn(state, d, setup.scalars(i),
                                     jax.random.key(0))
        results[name] = (state, float(m["total_loss"]))
    assert results["unified"][1] == pytest.approx(results["perleaf"][1],
                                                  rel=1e-5)
    for (pa, la), (_, lb) in zip(
        _flat_params(results["unified"][0].params)[:48],
        _flat_params(results["perleaf"][0].params)[:48],
    ):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=5e-6, atol=1e-6,
            err_msg=f"unified vs perleaf params {jtu.keystr(pa)}")


# ---------------- collective census of the compiled step ----------------

def test_unified_step_census_both_tiers(arms_unified):
    """The compiled unified step gathers on BOTH mesh tiers under the
    bucket scopes with zero unattributed collectives, and its grad
    reduce-scatter carries the staged bucket_rs scopes; the per-leaf
    oracle has none of the bucket scopes."""
    from dinov3_tpu.utils import hlo_collective_census

    s_u, s_o, d = arms_unified
    _use(s_u)
    text = s_u.step_fn.lower(
        s_u.state, d, s_u.scalars(0), jax.random.key(0)
    ).compile().as_text()
    cen = hlo_collective_census(text)
    assert cen["unattributed"] == 0
    ag_inter = cen["by_scope"].get("bucket_ag_inter", {"ops": 0})["ops"]
    ag_intra = cen["by_scope"].get("bucket_ag_intra", {"ops": 0})["ops"]
    assert ag_inter > 0 and ag_intra > 0
    # the staged grad-RS scope reaches the compiled text (this backend
    # lowers reduce-scatter as all-reduce+slice and fuses the intra
    # stage away entirely — the exact per-tier RS pin lives in the
    # explicit schedule-twin test below)
    assert "bucket_rs_inter" in text

    _use(s_o)
    text_o = s_o.step_fn.lower(
        s_o.state, d, s_o.scalars(0), jax.random.key(0)
    ).compile().as_text()
    cen_o = hlo_collective_census(text_o)
    assert not any(k.startswith("bucket_") for k in cen_o["by_scope"])
    assert cen_o["by_scope"].get("zero3_gather", {"ops": 0})["ops"] > 0


# ---------------- microbatched accumulation ----------------

@pytest.fixture(scope="module")
def accum_arms(eight_devices):
    """Unified-arm setups at accum_steps 1/2/4 on the dp x fsdp mesh
    with the batch-decoupled fp32 config, each run 3 steps."""
    from dinov3_tpu.train import put_batch

    common = ["parallel.data=-1", "parallel.fsdp=2",
              "parallel.zero3=auto"]
    out = {}
    d = None
    for accum in (1, 2, 4):
        s, batch = _setup(
            common + NEUTRAL + [f"optim.accum_steps={accum}"], 16,
            eight_devices)
        assert s.accum_steps == accum and s.zero3_buckets
        if d is None:
            d = put_batch(batch, s.batch_shardings)
        # step from a copy: step_fn donates, and the census test below
        # still needs s.state alive to lower against
        state, losses = jax.tree.map(jnp.copy, s.state), []
        for i in range(3):
            state, m = s.step_fn(state, d, s.scalars(i),
                                 jax.random.key(0))
            losses.append(float(m["total_loss"]))
        out[accum] = (s, losses, state)
    return out, d


def test_accum_loss_trajectory_vs_monolithic(accum_arms):
    """accum_steps in {2,4} track the monolithic (accum=1) oracle: the
    losses are batch-decoupled, so the microbatch means equal the batch
    means up to fp32 summation order — plus the (intended) equal-weight
    ibot-center EMA mean, which enters from step 2. The sliced
    microbatch is pinned onto the canonical batch layout inside the
    scan (train_step.py); without that constraint the partitioner picks
    a different layout and the arms drift ~1e-2."""
    arms, _ = accum_arms
    l1 = np.array(arms[1][1])
    assert np.all(np.isfinite(l1))
    for a in (2, 4):
        la = np.array(arms[a][1])
        assert np.all(np.isfinite(la))
        np.testing.assert_allclose(la, l1, rtol=5e-4,
                                   err_msg=f"accum={a} trajectory")
        # params stay in lockstep (adam normalization amplifies the
        # summation-order noise, so this is a drift bound, not bitwise)
        for (pa, x), (_, y) in zip(
            _flat_params(arms[1][2].params["student"])[:48],
            _flat_params(arms[a][2].params["student"])[:48],
        ):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), atol=5e-3,
                err_msg=f"accum={a} params {jtu.keystr(pa)}")


def test_accum_invariant_bucket_collectives(accum_arms):
    """ONE gather per bucket and one staged grad-RS per bucket per
    OPTIMIZER STEP, regardless of accum_steps: the gathers are hoisted
    out of the microbatch scan as scan constants, so the bucket scope
    op counts of the compiled accum=2 step equal the accum=1 step's."""
    from dinov3_tpu.utils import hlo_collective_census

    arms, d = accum_arms
    counts = {}
    for a in (1, 2):
        s = _use(arms[a][0])
        text = s.step_fn.lower(
            s.state, d, s.scalars(0), jax.random.key(0)
        ).compile().as_text()
        cen = hlo_collective_census(text)
        assert cen["unattributed"] == 0
        # censused COLLECTIVE op counts only: raw scope-string line
        # counts also hit fusion metadata, which the microbatch scan
        # duplicates
        counts[a] = {
            "ag_inter": cen["by_scope"].get(
                "bucket_ag_inter", {"ops": 0})["ops"],
            "ag_intra": cen["by_scope"].get(
                "bucket_ag_intra", {"ops": 0})["ops"],
        }
        assert counts[a]["ag_inter"] > 0 and counts[a]["ag_intra"] > 0
        assert text.count("bucket_rs_inter") > 0
    assert counts[1] == counts[2]


# ---------------- explicit schedule twin ----------------

def test_gather_schedule_twin_numerics_and_census(eight_devices):
    """The explicit staged-bucket schedule: forward bitwise == the
    per-leaf oracle == the host values; per-tier scope ops exactly one
    per bucket; zero unattributed; grads match the oracle at float
    tolerance (the RS transpose only reorders the reduction)."""
    from dinov3_tpu.utils import hlo_collective_census

    mesh = _dp_fsdp_mesh(eight_devices)
    tree_np = _toy_tree()
    tree = _zero3_put(tree_np, mesh)
    plan = make_zero3_bucket_plan(tree, mesh, target_bytes=2 ** 10)
    assert len(plan.buckets) >= 2

    g_b = make_zero3_gather_schedule(plan, mesh, bucketed=True)
    g_o = make_zero3_gather_schedule(plan, mesh, bucketed=False)
    out_b = jax.jit(g_b)(tree)
    out_o = jax.jit(g_o)(tree)
    ref = jax.tree.map(jnp.asarray, tree_np)
    assert_trees_bitwise(out_b, out_o, "bucketed vs per-leaf forward")
    assert_trees_bitwise(out_b, ref, "bucketed forward vs host values")

    def loss_of(g):
        def loss(t):
            # NONLINEAR consume: a linear sum lets XLA reassociate
            # sum(all_gather(x)) into local-sum + all-reduce and the
            # censused gathers vanish from the compiled program
            return sum(jnp.sum(jnp.sin(l.astype(jnp.float32)))
                       for l in jax.tree.leaves(g(t)))
        return loss

    gb = jax.jit(jax.grad(loss_of(g_b)))(tree)
    go = jax.jit(jax.grad(loss_of(g_o)))(tree)
    for a, b in zip(jax.tree.leaves(gb), jax.tree.leaves(go)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)

    nb = len(plan.buckets)
    cen = hlo_collective_census(
        jax.jit(jax.grad(loss_of(g_b))).lower(tree).compile().as_text())
    assert cen["unattributed"] == 0
    for scope in ("bucket_ag_inter", "bucket_ag_intra",
                  "bucket_rs_intra", "bucket_rs_inter"):
        assert cen["by_scope"].get(scope, {"ops": 0})["ops"] == nb, scope


def test_hierarchical_stream_scan_bitwise(eight_devices):
    """The bucketed stream scan's hierarchical option: the staged
    inter->intra gather + order-restoring swap is BITWISE the flat
    tiled gather, with both tier scopes attributed."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.models.streaming import bucketed_stream_scan
    from dinov3_tpu.utils import hlo_collective_census

    mesh = _dp_fsdp_mesh(eight_devices)
    shards = jnp.arange(4 * 64, dtype=jnp.float32).reshape(4, 64) * 0.01
    x = jnp.ones((8, 16), jnp.bfloat16)
    sh = jax.device_put(
        shards, NamedSharding(mesh, P(None, ("data", "fsdp"))))
    xx = jax.device_put(x, NamedSharding(mesh, P("data")))

    y_flat = jax.jit(lambda s, v: bucketed_stream_scan(
        s, v, mesh=mesh))(sh, xx)
    y_hier = jax.jit(lambda s, v: bucketed_stream_scan(
        s, v, mesh=mesh, hierarchical=True))(sh, xx)
    np.testing.assert_array_equal(np.asarray(y_flat), np.asarray(y_hier))

    comp = jax.jit(lambda s, v: jnp.sum(bucketed_stream_scan(
        s, v, mesh=mesh, hierarchical=True).astype(jnp.float32))
    ).lower(sh, xx).compile()
    cen = hlo_collective_census(comp.as_text())
    assert cen["unattributed"] == 0
    assert cen["by_scope"].get("bucket_ag_inter", {"ops": 0})["ops"] > 0
    assert cen["by_scope"].get("bucket_ag_intra", {"ops": 0})["ops"] > 0


def test_bucketed_stream_prefetch_and_orders_bitwise(eight_devices):
    """bucketed_stream_scan: every prefetch depth AND every staging
    order of the hierarchical gather path is bitwise the flat
    double-buffered default — both are pure wire schedules."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.models.streaming import bucketed_stream_scan
    from dinov3_tpu.parallel.sharding import STAGING_ORDERS

    mesh = _dp_fsdp_mesh(eight_devices)
    shards = jnp.arange(4 * 64, dtype=jnp.float32).reshape(4, 64) * 0.01
    x = jnp.ones((8, 16), jnp.bfloat16)
    sh = jax.device_put(
        shards, NamedSharding(mesh, P(None, ("data", "fsdp"))))
    xx = jax.device_put(x, NamedSharding(mesh, P("data")))

    ref = np.asarray(jax.jit(lambda s, v: bucketed_stream_scan(
        s, v, mesh=mesh))(sh, xx))
    for depth in (0, 1, 2):
        got = jax.jit(lambda s, v, d=depth: bucketed_stream_scan(
            s, v, mesh=mesh, prefetch=d))(sh, xx)
        assert np.array_equal(ref, np.asarray(got)), f"depth {depth}"
    for order in STAGING_ORDERS:
        got = jax.jit(lambda s, v, o=order: bucketed_stream_scan(
            s, v, mesh=mesh, hierarchical=True,
            staging_order=o))(sh, xx)
        assert np.array_equal(ref, np.asarray(got)), order


def test_staging_orders_equivalent_through_gather_schedule(
        eight_devices):
    """make_zero3_gather_schedule under all four staging orders:
    forward bitwise identical (pure wire schedule), grads equal at
    float tolerance (the RS transpose only reorders the reduction)."""
    from dinov3_tpu.parallel.sharding import (
        STAGING_ORDER,
        STAGING_ORDERS,
        split_staging_order,
    )

    assert STAGING_ORDERS == (
        "inter_intra", "intra_inter", "inter_inter", "intra_intra")
    assert split_staging_order("intra_inter") == ("intra", "inter")
    with pytest.raises(ValueError, match="staging order"):
        split_staging_order("inter")

    mesh = _dp_fsdp_mesh(eight_devices)
    tree = _zero3_put(_toy_tree(), mesh)
    plan = make_zero3_bucket_plan(tree, mesh, target_bytes=2 ** 9)

    def loss_of(g):
        def loss(t):
            return sum(jnp.sum(jnp.sin(le.astype(jnp.float32)))
                       for le in jax.tree.leaves(g(t)))
        return loss

    outs, grads = {}, {}
    for order in STAGING_ORDERS:
        g = make_zero3_gather_schedule(plan, mesh, bucketed=True,
                                       staging_order=order)
        outs[order] = jax.jit(g)(tree)
        grads[order] = jax.jit(jax.grad(loss_of(g)))(tree)
    for order in STAGING_ORDERS:
        assert_trees_bitwise(outs[STAGING_ORDER], outs[order], order)
        for a, b in zip(jax.tree.leaves(grads[STAGING_ORDER]),
                        jax.tree.leaves(grads[order])):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6,
                err_msg=order)


# ---------------- cross-arm checkpoints ----------------

def test_checkpoint_unified_perleaf_roundtrip(tmp_path, arms_unified):
    """unified -> per-leaf zero3 -> unified: identical state layouts,
    so the round trip is a pure re-placement — bitwise both ways, and
    the resumed unified run is deterministic against the uninterrupted
    one."""
    from dinov3_tpu.checkpoint import Checkpointer

    s_u, s_o, d = arms_unified
    _use(s_u)
    state1, _ = s_u.step_fn(jax.tree.map(jnp.copy, s_u.state), d,
                            s_u.scalars(0), jax.random.key(0))
    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ck.save(1, state1)
    ck.wait_until_finished()

    o_state = ck.restore(s_o.state, 1)
    assert_trees_bitwise(state1.params, o_state.params,
                         "unified -> perleaf params")
    ck.save(2, o_state)
    ck.wait_until_finished()
    back = ck.restore(s_u.state, 2)
    assert_trees_bitwise(state1.opt_state, back.opt_state,
                         "round-trip opt state")

    # all cross-arm comparisons done; the steps below DONATE their
    # state inputs, so they come last
    _use(s_o)
    _, m_o = s_o.step_fn(o_state, d, s_o.scalars(1), jax.random.key(0))
    assert np.isfinite(float(m_o["total_loss"]))
    _use(s_u)
    st_a, m_a = s_u.step_fn(state1, d, s_u.scalars(1), jax.random.key(0))
    st_b, m_b = s_u.step_fn(back, d, s_u.scalars(1), jax.random.key(0))
    assert float(m_a["total_loss"]) == float(m_b["total_loss"])
    assert_trees_bitwise(st_a.params, st_b.params, "resume determinism",
                         limit=32)


def test_checkpoint_flat_arm_into_unified(tmp_path, eight_devices):
    """A dp-only bucketed-arm checkpoint (on disk: per-leaf flat padded
    moments) restores into the unified zero3 arm (moments come back
    model-shaped through the flat->full adapt path) and the unified
    step runs from it."""
    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.train import put_batch
    from dinov3_tpu.train.fused_update import unflatten_update_leaf

    s_flat, batch = _setup(["parallel.zero3=false"], 16, eight_devices)
    assert s_flat.arm == "bucketed"
    plan = s_flat.bucket_plan
    d_flat = put_batch(batch, s_flat.batch_shardings)
    state1, _ = s_flat.step_fn(s_flat.state, d_flat, s_flat.scalars(0),
                               jax.random.key(0))
    ck = Checkpointer(str(tmp_path / "ck"), async_save=False,
                      bucket_plan=plan)
    ck.save(1, state1)
    ck.wait_until_finished()

    s_u, batch_u = _setup(
        ["parallel.data=-1", "parallel.fsdp=2", "parallel.zero3=auto"],
        16, eight_devices)
    assert s_u.arm == "unified" and s_u.zero3_buckets
    restored = Checkpointer(str(tmp_path / "ck"), async_save=False).restore(
        s_u.state, 1)
    assert_trees_bitwise(state1.params, restored.params,
                         "flat -> unified params")
    assert_trees_bitwise(
        jax.tree.map(unflatten_update_leaf,
                     plan.buckets_to_flat_tree(state1.opt_state.adam.mu),
                     state1.params["student"]),
        restored.opt_state.adam.mu, "flat -> unified mu")
    d_u = put_batch(batch_u, s_u.batch_shardings)
    _, m = s_u.step_fn(restored, d_u, s_u.scalars(1), jax.random.key(0))
    assert np.isfinite(float(m["total_loss"]))


# ---------------- committed artifact acceptance ----------------

def test_cost_unified_artifact_acceptance():
    """COST_UNIFIED_r18.json (scripts/cost_unified.py, ViT-L on the
    2x4 data x fsdp mesh): the unified arm's committed collective-set
    numbers hold — per-leaf RS count equals the shardable leaf count,
    the unified arm pays one staged pair per bucket with fewer buckets
    than leaves, and the accum sweep is collective-count invariant with
    finite executed loss trajectories."""
    with open(os.path.join(REPO, "COST_UNIFIED_r18.json")) as f:
        j = json.load(f)
    assert j["mesh"] == {"data": 2, "fsdp": 4}
    gp = j["gather_phase"]
    n_shard = gp["n_shardable_leaves"]
    nb = gp["plan"]["n_buckets"]
    assert 1 <= nb < n_shard
    assert gp["plan"]["n_inter"] == 2 and gp["plan"]["n_intra"] == 4
    rs = j["reduce_scatter_ops"]
    assert rs["per_leaf"] == n_shard
    assert rs["unified"] == 2 * nb  # one intra + one inter stage/bucket
    assert rs["unified"] < rs["per_leaf"]
    ag = j["all_gather_ops"]
    assert ag["per_leaf"] == n_shard and ag["unified"] == 2 * nb
    sweep = j["accum_sweep"]
    assert [e["accum_steps"] for e in sweep] == [1, 2, 4]
    base = None
    for e in sweep:
        assert e["n_buckets"] == nb
        assert e["grad_rs_scope_lines"] > 0
        cen = e["collective_census"]
        assert cen["unattributed"] == 0
        tiers = {k: v["ops"] for k, v in cen["by_scope"].items()
                 if k.startswith("bucket_ag_")}
        assert tiers.get("bucket_ag_inter", 0) > 0
        assert tiers.get("bucket_ag_intra", 0) > 0
        if base is None:
            base = tiers
        assert tiers == base  # one gather per bucket per step
        assert all(np.isfinite(v) for v in e["loss_trajectory"])
