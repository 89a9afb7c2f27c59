"""Bucketed, overlap-scheduled collective engine
(train/fused_update.py make_bucketed_update + BucketPlan) vs the
replicated fused oracle.

The bucketed engine is the default update path on a pure data-parallel
mesh (``optim.bucketed_collectives``); the replicated fused engine under
GSPMD's all-reduce is its oracle behind ``=false``. These tests pin:
- BucketPlan assembly invariants (single dtype/submodel/last-layer-group
  per bucket, deterministic order, padded offsets) and the bitwise
  round-trips through every packing direction (pack/unpack, the
  shard-interleaved bucket layout <-> per-leaf padded flat), with the
  zero padding inert through the engine;
- multi-step equivalence of the bucketed engine with state feedback,
  against ``make_fused_update`` (rtol=1e-6/atol=1e-7, the
  reduction-associativity budget of the flat clip norm) and against
  ITSELF under a plan of one leaf a bucket: the result does not depend
  on the plan. The REDUCTION path is BITWISE — the shard-interleaved
  layout makes a coalesced reduce-scatter compute segment-for-segment
  the sums of a reduce-scatter a leaf, so the moments (mu/nu, every
  step) and the clip norms are bit-identical between the plans. The
  elementwise params/teacher outputs are pinned at the same tolerances
  plus an explicit <= 8-ulp ceiling: XLA:CPU expands the
  ``optimization_barrier`` fusion cuts away pre-fusion, so the two
  programs' math kernels FMA-contract in different fusion contexts
  (~1-2 ulp observed); on backends that honor the barrier the math
  subgraphs compile identically;
- the explicit-collective schedule twin (the program
  COST_BUCKET_r13.json is a census of) against the engine, and
  its compiled HLO carries exactly ONE reduce-scatter per bucket and ONE
  all-gather per bucket per output tree, all attributed to the
  ``bucket_pack``/``bucket_unpack`` scopes, with the per-class
  power-of-two size histogram populated;
- build_train_setup wiring: auto-on at dp > 1 (moments born as
  {bucket_name: flat} dicts), =false the replicated fallback, the
  explicit-true conflict (fused off) raising;
- full-step bucketed-vs-replicated A/B dryrun and the cross-arm
  checkpoint round-trip (on-disk format stays per-leaf flat; the
  Checkpointer's bucket_plan adapter converts at the boundary) with
  resume determinism;
- the ``warn_bucket_padding`` guardrail (pad-fraction + straggler
  messages, silent clean case);
- the bucketed overlap twin (models/streaming.py
  ``bucketed_stream_scan``): under ``jax.grad`` the per-bucket forward
  all-gather transposes to a reduce-scatter INSIDE the backward while
  loop — the overlap placement ``utils.hlo_collective_placement``
  classifies;
- the COST_BUCKET_r13.json acceptance census: 357 -> <=16 update-phase
  reduce-scatters, 714 -> <=32 all-gathers at ViT-L dp=8, zero
  unattributed, >= 90% of collective bytes in >=64MiB buckets.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
from dinov3_tpu.train import (
    build_multiplier_trees,
    make_bucket_plan,
    make_bucketed_update,
    make_bucketed_update_schedule,
    make_fused_update,
)
from dinov3_tpu.train.fused_update import (
    bucketed_adam_zeros,
    flatten_update_leaf,
    padded_flat_size,
    unflatten_update_leaf,
)
from dinov3_tpu.train.optimizer import scheduled_adamw
from test_fused_update import (
    assert_trees_close,
    fake_params,
    grads_like,
    make_sched,
    smol_cfg,
)

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _isolate_mesh_context():
    """build_train_setup registers its mesh in the process-global
    current-mesh registry; restore whatever was there so later test
    FILES (alphabetically after this one) don't inherit an 8-way data
    mesh their row/batch shapes can't divide."""
    from dinov3_tpu.parallel.context import get_current_mesh, set_current_mesh

    prev = get_current_mesh()
    yield
    set_current_mesh(prev)


@pytest.fixture(scope="module")
def mesh8(request):
    devs = jax.devices()
    assert len(devs) == 8
    return build_mesh(MeshSpec(data=8), devices=devs)


def small_plan(params=None, dp=8, target_bytes=256):
    """A plan over the smol fake tree with a tiny byte target so the
    greedy fill actually produces several buckets per group."""
    params = fake_params() if params is None else params
    _, _, ll = build_multiplier_trees(params, layerwise_decay=0.9)
    return params, make_bucket_plan(params, dp, is_last_layer=ll,
                                    target_bytes=target_bytes)


def bucketed_opt_init(params, sched, lm, wm, ll, plan):
    """Oracle-chain init with mu/nu swapped into the bucket layout —
    what build_train_setup's boxed init produces."""
    import flax.linen as nn

    s = scheduled_adamw(sched, lm, wm, ll).init(params)
    return s._replace(adam=s.adam._replace(
        mu=nn.meta.unbox(bucketed_adam_zeros(plan)),
        nu=nn.meta.unbox(bucketed_adam_zeros(plan)),
    ))


def moments_in_model_layout(adam, plan, params):
    """A bucket-layout ``ScaleByAdamState``'s (mu, nu) as param-shaped
    trees, through the lossless bucket -> per-leaf flat -> leaf walk."""
    return tuple(
        jax.tree.map(unflatten_update_leaf,
                     plan.buckets_to_flat_tree(m), params)
        for m in (adam.mu, adam.nu))


def assert_trees_bitwise(a, b, what, limit=None, max_ulps=0):
    """Leaf-for-leaf equality; ``max_ulps`` > 0 pins a MEASURED last-
    digit difference between two arms (conftest.assert_within_ulps)
    instead of bitwise equality."""
    from conftest import assert_within_ulps

    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(fa) == len(fb), f"{what}: leaf count {len(fa)} != {len(fb)}"
    if limit:
        fa, fb = fa[:limit], fb[:limit]
    for (pa, la), (_, lb) in zip(fa, fb):
        where = f"{what}: {jax.tree_util.keystr(pa)}"
        if np.asarray(la).dtype == np.float32:
            assert_within_ulps(la, lb, max_ulps, where)
        else:
            assert np.array_equal(np.asarray(la), np.asarray(lb)), where


def assert_trees_ulp(a, b, what, max_ulp=8):
    """Elementwise pin for the cross-arm fp32 outputs: PR-5 tolerances
    AND a last-digit ceiling in units of each leaf's scale
    (conftest.ulps_of_scale; the observed CPU FMA-contraction context
    drift is 1-2; 8 leaves margin without letting a real bug
    through)."""
    from conftest import assert_within_ulps

    for (pa, la), (_, lb) in zip(
        jax.tree_util.tree_flatten_with_path(a)[0],
        jax.tree_util.tree_flatten_with_path(b)[0],
    ):
        la, lb = np.asarray(la), np.asarray(lb)
        np.testing.assert_allclose(
            la, lb, rtol=1e-6, atol=1e-7,
            err_msg=f"{what}: {jax.tree_util.keystr(pa)}")
        if la.dtype == np.float32:
            assert_within_ulps(
                la, lb, max_ulp, f"{what}: {jax.tree_util.keystr(pa)}")


# ---------------- plan assembly + round-trips ----------------

def test_plan_grouping_invariants():
    """Every bucket is homogeneous in (submodel, dtype, last-layer
    group); member offsets tile the bucket exactly; the global order is
    deterministic (first member's tree position) and the names encode
    it."""
    params, plan = small_plan()
    n_leaves = len(jax.tree.leaves(params))
    assert plan.n_leaves == n_leaves
    assert sum(len(b.members) for b in plan.buckets) == n_leaves
    assert len(plan.buckets) >= 3  # tiny target forces a real partition
    seen = set()
    for b in plan.buckets:
        assert b.group in ("backbone", "dino_head")
        off = 0
        for m in b.members:
            assert m.index not in seen
            seen.add(m.index)
            assert m.offset == off
            assert m.padded % plan.dp == 0 and m.padded >= m.size
            off += m.padded
        assert off == b.size and b.size % plan.dp == 0
    # prototypes (the last-layer group) never share a bucket with the
    # rest of the head
    ll_buckets = [b for b in plan.buckets if b.is_last_layer]
    assert len(ll_buckets) >= 1
    assert all(b.group == "dino_head" for b in ll_buckets)
    assert all("prototypes" in m.path
               for b in ll_buckets for m in b.members)
    # deterministic order: names are the sorted traversal order
    assert list(plan.names) == sorted(plan.names)
    firsts = [b.members[0].index for b in plan.buckets]
    assert firsts == sorted(firsts)
    # rebuild -> identical plan
    _, plan2 = small_plan()
    assert plan2.names == plan.names
    assert [tuple(m.index for m in b.members) for b in plan2.buckets] == \
        [tuple(m.index for m in b.members) for b in plan.buckets]


def test_plan_pack_unpack_bitwise():
    """pack_tree -> unpack_tree and the per-leaf-flat <-> bucket
    conversions (the checkpoint boundary) round-trip bitwise, on both
    jax and numpy leaves."""
    params, plan = small_plan()
    key = jax.random.key(7)
    tree = grads_like(params, key)

    buckets = plan.pack_tree(tree)
    assert set(buckets) == set(plan.names)
    for b in plan.buckets:
        assert buckets[b.name].shape == (b.size,)
        assert buckets[b.name].dtype == b.dtype
    back = plan.unpack_tree(buckets, params)
    assert_trees_bitwise(tree, back, "pack/unpack")

    # shard-interleave layout: row k of the [dp, S/dp] view is the
    # member-by-member concat of each leaf's k-th flat shard
    flat_tree = jax.tree.map(
        lambda l: flatten_update_leaf(l, plan.dp), tree)
    b0 = plan.buckets[0]
    mat = np.asarray(buckets[b0.name]).reshape(plan.dp, -1)
    col = 0
    for m in b0.members:
        leaf = np.asarray(jax.tree.leaves(flat_tree)[m.index])
        w = m.padded // plan.dp
        assert np.array_equal(mat[:, col:col + w],
                              leaf.reshape(plan.dp, w))
        col += w

    # checkpoint boundary: bucket dict <-> per-leaf padded flat tree
    flat_back = plan.buckets_to_flat_tree(buckets)
    assert_trees_bitwise(flat_tree, flat_back, "buckets->flat")
    re_buckets = plan.flat_tree_to_buckets(flat_back)
    assert_trees_bitwise(buckets, re_buckets, "flat->buckets")
    # ... and numpy leaves (the local-npz checkpoint backend) too
    np_buckets = plan.flat_tree_to_buckets(
        jax.tree.map(np.asarray, flat_tree))
    assert_trees_bitwise(buckets, np_buckets, "np flat->buckets")

    # flat round-trip validates shapes
    bad = dict(jax.tree_util.tree_flatten_with_path(flat_tree)[0])
    with pytest.raises(ValueError):
        plan.flat_tree_to_buckets(
            jax.tree.map(lambda l: l[:-1], flat_tree))


def test_padded_lanes_inert_and_lossless(mesh8):
    """flatten/unflatten round-trips bitwise; the zero padding stays
    exactly 0 through 5 engine steps (so the bucket -> per-leaf flat ->
    leaf checkpoint conversions are lossless in both directions)."""
    x = jnp.arange(13.0)
    flat = flatten_update_leaf(x.reshape(13), 8)
    assert flat.shape == (16,)
    assert np.array_equal(np.asarray(unflatten_update_leaf(flat, x)), x)
    assert padded_flat_size(13, 8) == 16

    sched = make_sched()
    params, plan = small_plan()  # has a (5,)-bias: pads 5 -> 8
    lm, wm, ll = build_multiplier_trees(params)
    bucketed = make_bucketed_update(sched, lm, wm, ll, mesh8, plan,
                                    clip_grad=3.0, ema=True)
    momentum = jnp.asarray(0.9, jnp.float32)
    s = bucketed_opt_init(params, sched, lm, wm, ll, plan)
    p, t = params, jax.tree.map(jnp.copy, params)
    with mesh8:
        step = jax.jit(lambda g, p, t, s: bucketed(g, p, t, s, momentum)[:3])
        key = jax.random.key(1)
        for _ in range(5):
            key, k = jax.random.split(key)
            p, t, s = step(grads_like(params, k), p, t, s)
    padded = 0
    for (path, mu), (_, like) in zip(
        jax.tree_util.tree_flatten_with_path(
            plan.buckets_to_flat_tree(s.adam.mu))[0],
        jax.tree_util.tree_flatten_with_path(params)[0],
    ):
        pad = np.asarray(mu)[like.size:]
        assert pad.size == mu.shape[0] - like.size
        assert np.all(pad == 0.0), f"padding moved: {path}"
        assert np.any(np.asarray(mu)[:like.size] != 0.0), path
        padded += pad.size
    assert padded > 0


# ---------------- engine equivalence ----------------

@pytest.mark.parametrize("clip", [3.0, 0.05, None])
def test_bucketed_matches_fused_under_any_plan(mesh8, clip):
    """6 steps with state feedback, three programs from the same grads:
    the replicated fused engine (the oracle), the bucketed engine under
    a plan of several leaves a bucket, and the bucketed engine under a
    plan of ONE leaf a bucket. Against the oracle everything sits
    inside rtol=1e-6/atol=1e-7. Between the two plans the REDUCTION
    path is BITWISE — nu (through the lossless bucket <-> flat
    conversion) and the clip norms are bit-identical every step, mu to
    the last digit,
    because the shard-interleaved layout makes a coalesced
    reduce-scatter's segments exactly the reduce-scatters of its
    members — and the elementwise params/teacher outputs carry the ulp
    ceiling (module docstring: XLA:CPU drops the optimization_barrier
    fusion cut, so FMA contraction context may differ by 1-2 ulp
    between the compiled plans)."""
    sched = make_sched()
    params, plan = small_plan(target_bytes=512)
    _, plan1 = small_plan(target_bytes=1)
    assert len(plan1.buckets) == plan1.n_leaves > len(plan.buckets)
    lm, wm, ll = build_multiplier_trees(
        params, layerwise_decay=0.9, patch_embed_lr_mult=0.2,
        dino_head_wd_multiplier=0.5,
    )
    fused = make_fused_update(sched, lm, wm, ll, clip_grad=clip, ema=True)
    bucketed = make_bucketed_update(sched, lm, wm, ll, mesh8, plan,
                                    clip_grad=clip, ema=True)
    perleaf = make_bucketed_update(sched, lm, wm, ll, mesh8, plan1,
                                   clip_grad=clip, ema=True)
    momentum = jnp.asarray(0.95, jnp.float32)
    teacher = jax.tree.map(jnp.copy, params)
    s_f = scheduled_adamw(sched, lm, wm, ll).init(params)
    s_b = bucketed_opt_init(params, sched, lm, wm, ll, plan)
    s_1 = bucketed_opt_init(params, sched, lm, wm, ll, plan1)

    with mesh8:
        f_step = jax.jit(lambda g, p, t, s: fused(g, p, t, s, momentum))
        b_step = jax.jit(lambda g, p, t, s: bucketed(g, p, t, s, momentum))
        l_step = jax.jit(lambda g, p, t, s: perleaf(g, p, t, s, momentum))
        p_f = p_b = p_1 = params
        t_f = t_b = t_1 = teacher
        key = jax.random.key(0)
        for _ in range(6):
            key, k = jax.random.split(key)
            g = grads_like(params, k)
            p_f, t_f, s_f, n_f = f_step(g, p_f, t_f, s_f)
            p_b, t_b, s_b, n_b = b_step(g, p_b, t_b, s_b)
            p_1, t_1, s_1, n_1 = l_step(g, p_1, t_1, s_1)
            # the reduction path: clip norms and nu BITWISE between the
            # plans, every step; mu was bitwise under jax 0.4 and is 1
            # last-digit unit of the leaf's scale apart at clip=3.0
            # (measured; 0 at the other clips). Pinned at this file's
            # cross-program ceiling of 8 such units (assert_trees_ulp),
            # since the installed XLA:CPU does not round identically
            # from run to run.
            assert_trees_bitwise(
                plan1.buckets_to_flat_tree(s_1.adam.mu),
                plan.buckets_to_flat_tree(s_b.adam.mu), "mu", max_ulps=8)
            assert_trees_bitwise(
                plan1.buckets_to_flat_tree(s_1.adam.nu),
                plan.buckets_to_flat_tree(s_b.adam.nu), "nu")
            assert set(n_b) == set(n_1) == set(n_f)
            for k2 in n_b:
                assert float(n_1[k2]) == float(n_b[k2]), f"norm {k2}"
                np.testing.assert_allclose(
                    float(n_f[k2]), float(n_b[k2]), rtol=1e-5,
                    err_msg=f"clip norm {k2}")

    assert_trees_ulp(p_1, p_b, "params")
    assert_trees_ulp(t_1, t_b, "teacher")
    assert_trees_close(p_f, p_b, "params vs fused")
    assert_trees_close(t_f, t_b, "teacher vs fused")
    mu_b, nu_b = moments_in_model_layout(s_b.adam, plan, params)
    assert_trees_close(s_f.adam.mu, mu_b, "mu vs fused")
    assert_trees_close(s_f.adam.nu, nu_b, "nu vs fused")
    assert int(s_b.count) == 6 and int(s_b.adam.count) == 6
    # the updates were non-trivial
    assert not np.array_equal(np.asarray(jax.tree.leaves(p_b)[0]),
                              np.asarray(jax.tree.leaves(params)[0]))


def test_bucketed_rejects_foreign_opt_state(mesh8):
    sched = make_sched()
    params, plan = small_plan()
    lm, wm, ll = build_multiplier_trees(params)
    bucketed = make_bucketed_update(sched, lm, wm, ll, mesh8, plan,
                                    clip_grad=3.0, ema=True)
    momentum = jnp.asarray(0.9, jnp.float32)
    s_leaf = scheduled_adamw(sched, lm, wm, ll).init(params)
    with mesh8, pytest.raises(TypeError, match="bucket"):
        bucketed(fake_params(), params, params, s_leaf, momentum)


# ---------------- explicit schedule twin: bitwise + census ----------------

def test_bucketed_schedule_matches_engine_and_census(mesh8):
    """The explicit-collective bucketed schedule (ONE psum_scatter per
    bucket, ONE all_gather per bucket per output — the program
    COST_BUCKET_r13.json accounts) computes the engine's update from
    [dp, *leaf] stacks of per-replica partials: against
    ``make_bucketed_update`` fed their sum, params, teacher, moments
    and the reduce-scattered clip norms inside the tolerances the
    engine holds against the fused oracle (the two reduce in different
    orders). And the compiled HLO censuses to exactly n_buckets
    reduce-scatters and 2*n_buckets all-gathers, all attributed to
    bucket scopes with the size histogram populated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.parallel.sharding import UPDATE_SHARD_AXES
    from dinov3_tpu.utils import hlo_collective_census

    sched = make_sched()
    params, plan = small_plan(target_bytes=512)
    lm, wm, ll = build_multiplier_trees(params, layerwise_decay=0.9)
    clip = 0.05  # engaged every step: the RS'd norms must match too
    engine = make_bucketed_update(sched, lm, wm, ll, mesh8, plan,
                                  clip_grad=clip, ema=True)
    schedule = make_bucketed_update_schedule(sched, lm, wm, ll, mesh8,
                                             plan, clip_grad=clip, ema=True)
    momentum = jnp.asarray(0.9, jnp.float32)
    teacher = jax.tree.map(jnp.copy, params)
    s_e = bucketed_opt_init(params, sched, lm, wm, ll, plan)
    s_b = bucketed_opt_init(params, sched, lm, wm, ll, plan)

    with mesh8:
        e_step = jax.jit(lambda g, p, t, s: engine(g, p, t, s, momentum))
        c_step = jax.jit(lambda gp, p, t, s: schedule(gp, p, t, s, momentum))
        p_e = p_c = params
        t_e = t_c = teacher
        key = jax.random.key(3)
        for _ in range(3):
            key, k1, _ = jax.random.split(key, 3)
            # random per-replica partials; the engine consumes their sum
            parts = jax.tree.map(
                lambda l: jax.random.normal(
                    jax.random.fold_in(k1, l.size), (8,) + l.shape, l.dtype),
                params)
            g = jax.tree.map(lambda s_: jnp.sum(s_, 0), parts)
            p_e, t_e, s_e, norms_e = e_step(g, p_e, t_e, s_e)
            p_c, t_c, s_b, norms_c = c_step(parts, p_c, t_c, s_b)

    assert_trees_close(p_e, p_c, "schedule params")
    assert_trees_close(t_e, t_c, "schedule teacher")
    assert set(norms_e) == set(norms_c) and norms_c
    for k in norms_e:
        np.testing.assert_allclose(
            float(norms_e[k]), float(norms_c[k]), rtol=1e-5,
            err_msg=f"clip norm {k}")
    for what, m_e, m_c in zip(
            ("mu", "nu"), moments_in_model_layout(s_e.adam, plan, params),
            moments_in_model_layout(s_b.adam, plan, params)):
        assert_trees_close(m_e, m_c, f"schedule {what}")

    # census of the EXACT explicit twin, compiled with the training
    # shardings (stacked partials + bucket moments over the data axes)
    rep = NamedSharding(mesh8, P())
    axes = tuple(a for a in UPDATE_SHARD_AXES if a in mesh8.shape)
    stacks = NamedSharding(mesh8, P(axes))
    gstack = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((8,) + l.shape, l.dtype), params)
    abs_p = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), params)
    abs_s = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), s_b)
    rep_tree = jax.tree.map(lambda _: rep, abs_p)
    opt_sh = jax.tree.map(
        lambda l: rep if l.ndim == 0 else stacks, abs_s)
    compiled = jax.jit(
        lambda gp, p, t, s: schedule(gp, p, t, s, momentum)[:3],
        in_shardings=(jax.tree.map(lambda _: stacks, gstack),
                      rep_tree, rep_tree, opt_sh),
        out_shardings=(rep_tree, rep_tree, opt_sh),
    ).lower(gstack, abs_p, abs_p, abs_s).compile()
    census = hlo_collective_census(compiled.as_text())
    n = len(plan.buckets)
    assert census["unattributed"] == 0
    rs = census["by_class"].get("reduce_scatter", {"ops": 0})
    ag = census["by_class"].get("all_gather", {"ops": 0})
    assert rs["ops"] == n, (n, census["by_class"])
    assert ag["ops"] == 2 * n, (n, census["by_class"])  # student + teacher
    # attribution: every bucket collective under a bucket_* scope
    bucket_scopes = {k: v for k, v in census["by_scope"].items()
                     if k.startswith("bucket")}
    assert sum(v["ops"] for v in bucket_scopes.values()) >= 3 * n
    # satellite: the per-class power-of-two size histogram is populated
    for cls in (rs, ag):
        hist = cls["size_histogram"]
        assert hist and all("floor_bytes" in b for b in hist.values())
        assert sum(b["ops"] for b in hist.values()) == cls["ops"]
        assert sum(b["bytes"] for b in hist.values()) == cls["bytes"]


# ---------------- setup wiring ----------------

def _setup(extra, batch_size, eight_devices):
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    cfg = smol_cfg(["parallel.zero3=false"] + list(extra))
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, batch_size, seed=0).items()}
    return build_train_setup(cfg, batch, devices=eight_devices), batch


def test_setup_born_bucketed_and_toggles(eight_devices):
    """auto-on at dp > 1: moments born as bucket dicts; =false is the
    replicated oracle; explicit true + zero3 composes (the unified
    gather-bucket arm) while the remaining non-zero3 conflict still
    raises."""
    setup, _ = _setup(["parallel.data=-1"], 8, eight_devices)
    assert setup.arm == "bucketed" and setup.bucket_plan is not None
    assert setup.bucketed and setup.fused_update is not None
    mu = setup.state.opt_state.adam.mu
    assert isinstance(mu, dict)
    assert sorted(mu) == sorted(setup.bucket_plan.names)
    for b in setup.bucket_plan.buckets:
        leaf = mu[b.name]
        assert leaf.ndim == 1 and leaf.shape == (b.size,)

    # =false: the replicated oracle arm — whole param-shaped moments
    # on every device, the fused engine behind GSPMD's all-reduce
    setup_off, _ = _setup(["parallel.data=-1",
                           "optim.bucketed_collectives=false"], 8,
                          eight_devices)
    assert setup_off.arm == "replicated" and setup_off.bucket_plan is None
    assert not setup_off.bucketed and setup_off.fused_update is not None
    for mu, p in zip(
            jax.tree.leaves(setup_off.state.opt_state.adam.mu),
            jax.tree.leaves(setup_off.state.params["student"])):
        assert mu.shape == p.shape and mu.sharding.is_fully_replicated

    # explicit true + zero3 selects the unified gather-bucket arm (the
    # flat bucketed update stays out of the way: zero3 owns the update)
    setup_z3, _ = _setup(["parallel.data=-1", "parallel.zero3=true",
                          "optim.bucketed_collectives=true"], 8,
                         eight_devices)
    assert setup_z3.arm == "unified"
    assert setup_z3.zero3 and setup_z3.zero3_buckets
    assert setup_z3.zero3_bucket_plan is not None
    assert not setup_z3.bucketed and setup_z3.bucket_plan is None
    # explicit true + fused off likewise
    with pytest.raises(ValueError, match="bucketed_collectives"):
        _setup(["parallel.data=-1", "optim.fused_update=false",
                "optim.bucketed_collectives=true"], 8, eight_devices)


# Cross-PROGRAM comparisons of params after two full steps: the two
# programs' gradients differ in their last digits (reduce-scatter against
# all-reduce order), and Adam's m/sqrt(v) turns that into a visible
# difference on the elements whose gradient is at noise level. At the
# recipe's student.layerscale=1e-5 those are the LayerScale gammas
# (measured: 3.97e-5, 16% of one Adam step at this schedule's lr of
# 2.5e-4, on 2 of 151,648 elements, both in ``ls2.gamma``); at
# layerscale=1.0 every gradient is well above the noise and the worst
# element of the whole tree reads 1.6e-7. The pair runs at 1.0 and is
# pinned at 1e-6; the moments and clip norms keep their strict pins in
# the engine tests above.
FULL_STEP_ATOL = 1e-6


def test_full_step_bucketed_vs_replicated(eight_devices):
    """Dryrun A/B at dp=8: 2 full steps from the same init, the
    bucketed arm matches the replicated oracle at the PR-5 dryrun
    tolerances (losses to 1e-5, params/moments to 5e-6; the full step's
    forward/backward fuses differently around the two update engines,
    so the ulp-exact pins live in the engine/schedule tests above)."""
    from dinov3_tpu.train import put_batch

    results = {}
    for flag in ("auto", "false"):
        setup, batch = _setup(
            ["parallel.data=-1", "student.layerscale=1.0",
             f"optim.bucketed_collectives={flag}"], 8, eight_devices)
        assert setup.arm == ("bucketed" if flag == "auto" else "replicated")
        d = put_batch(batch, setup.batch_shardings)
        state = setup.state
        losses = []
        for i in range(2):
            state, m = setup.step_fn(state, d, setup.scalars(i),
                                     jax.random.key(0))
            losses.append(float(m["total_loss"]))
        results[flag] = (setup, state, losses)

    setup_b, st_b, loss_b = results["auto"]
    _, st_p, loss_p = results["false"]
    for a, b in zip(loss_b, loss_p):
        assert a == pytest.approx(b, rel=1e-5)
    for (pa, la), (_, lb) in zip(
        jax.tree_util.tree_flatten_with_path(st_p.params)[0],
        jax.tree_util.tree_flatten_with_path(st_b.params)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=5e-6, atol=FULL_STEP_ATOL,
            err_msg=f"dryrun params {jax.tree_util.keystr(pa)}")
    mu_b, _ = moments_in_model_layout(
        st_b.opt_state.adam, setup_b.bucket_plan, st_b.params["student"])
    for (pa, la), (_, lb) in zip(
        jax.tree_util.tree_flatten_with_path(st_p.opt_state.adam.mu)[0],
        jax.tree_util.tree_flatten_with_path(mu_b)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=5e-6, atol=1e-6,
            err_msg=f"dryrun mu {jax.tree_util.keystr(pa)}")


# ---------------- checkpoint round-trip + resume determinism ----------------

def test_checkpoint_cross_arm_roundtrip(tmp_path, eight_devices):
    """bucketed -> replicated -> bucketed checkpoint round-trip: on
    disk the bucketed arm's moments are ALWAYS per-leaf flat (the
    Checkpointer's bucket_plan adapter converts at the boundary — pure
    index permutations, bitwise lossless), a replicated run reads them
    through the flat -> leaf adapter and writes param-shaped ones the
    bucketed run reads back, and the resumed run is deterministic."""
    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.train import put_batch

    setup_bk, batch = _setup(["parallel.data=-1"], 8, eight_devices)
    assert setup_bk.bucketed
    d = put_batch(batch, setup_bk.batch_shardings)
    state1, _ = setup_bk.step_fn(setup_bk.state, d, setup_bk.scalars(0),
                                 jax.random.key(0))

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False,
                      bucket_plan=setup_bk.bucket_plan)
    ck.save(1, state1)
    ck.wait_until_finished()

    # restore into the replicated arm: a plain Checkpointer (no plan)
    # reads the same checkpoint — the disk format is per-leaf flat, and
    # dropping its zero padding gives the param-shaped moments
    setup_pl, _ = _setup(["parallel.data=-1",
                          "optim.bucketed_collectives=false"], 8,
                         eight_devices)
    assert setup_pl.arm == "replicated"
    ck_plain = Checkpointer(str(tmp_path / "ck"), async_save=False)
    pl_state = ck_plain.restore(setup_pl.state, 1)
    for got, want, what in zip(
            (pl_state.opt_state.adam.mu, pl_state.opt_state.adam.nu),
            moments_in_model_layout(state1.opt_state.adam,
                                    setup_bk.bucket_plan,
                                    state1.params["student"]),
            ("mu", "nu")):
        assert_trees_bitwise(got, want, f"disk {what} is the flat form")

    # ... and back: the replicated arm's save restores bitwise into the
    # bucketed arm through the adapter
    ck_plain.save(2, pl_state)
    ck_plain.wait_until_finished()
    back = ck.restore(setup_bk.state, 2)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(state1.opt_state)[0],
        jax.tree_util.tree_flatten_with_path(back.opt_state)[0],
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"round-trip changed {jax.tree_util.keystr(path)}")

    # resume determinism: the next step from the round-tripped state is
    # the next step from the original state
    s_orig, m_orig = setup_bk.step_fn(state1, d, setup_bk.scalars(1),
                                      jax.random.key(0))
    s_back, m_back = setup_bk.step_fn(back, d, setup_bk.scalars(1),
                                      jax.random.key(0))
    assert float(m_orig["total_loss"]) == float(m_back["total_loss"])
    assert_trees_bitwise(s_orig.params, s_back.params, "resume", limit=32)

    # the replicated arm also RUNS from the adapted state
    d_pl = put_batch(batch, setup_pl.batch_shardings)
    s_pl, m_pl = setup_pl.step_fn(pl_state, d_pl, setup_pl.scalars(1),
                                  jax.random.key(0))
    assert np.isfinite(float(m_pl["total_loss"]))
    assert int(s_pl.step) == 2


# ---------------- guardrail ----------------

def test_bucket_padding_guardrail(recwarn):
    from dinov3_tpu.configs.config import warn_bucket_padding

    def row(name, elems, pad, nbytes):
        return {"name": name, "group": "backbone", "dtype": "f32",
                "is_last_layer": False, "n_leaves": 1, "elems": elems,
                "pad_elems": pad, "bytes": nbytes}

    # clean plan: equal buckets, negligible padding -> silent
    clean = [row(f"b{i:03d}", 10 ** 6, 8, 4 * 10 ** 6) for i in range(4)]
    assert warn_bucket_padding(clean, 4 * 10 ** 6) == []
    assert len(recwarn.list) == 0

    # pad-fraction pathology: >5% zeros in the coalesced payload
    msgs = warn_bucket_padding(
        [row("b000_backbone", 100, 20, 480)], 4 * 10 ** 6)
    assert len(msgs) == 1 and "bucket flat axis [b000_backbone]" in msgs[0]

    # straggler pathology: one bucket under 1/8 the median
    frag = [row("b000", 10 ** 6, 0, 4 * 10 ** 6),
            row("b001", 10 ** 6, 0, 4 * 10 ** 6),
            row("b002_tail", 10 ** 4, 0, 4 * 10 ** 4)]
    msgs = warn_bucket_padding(frag, 4 * 10 ** 6)
    assert len(msgs) == 1 and "bucket size axis [b002_tail]" in msgs[0]
    w = [x for x in recwarn.list if "bucket" in str(x.message)]
    assert len(w) == 2  # one per pathology above

    # a REAL smol plan at the default target is clean (one bucket per
    # group -> no straggler comparison, padding under threshold is the
    # small-tree exemption the setup path relies on)


def test_setup_guardrail_fires_on_fragmented_plan(eight_devices):
    """The guardrail is wired into build_train_setup, on the plan the
    setup builds at the one target there is: the smol tree's tiny
    last-layer groups are stragglers beside its backbone bucket and
    the warning surfaces at setup build. The same tree cut at 16 KiB
    fragments the backbone too."""
    from dinov3_tpu.configs.config import warn_bucket_padding
    from dinov3_tpu.train.fused_update import BUCKET_TARGET_BYTES

    with pytest.warns(UserWarning, match="bucket size axis") as rec:
        setup, _ = _setup(["parallel.data=-1"], 8, eight_devices)
    assert setup.bucket_plan.target_bytes == BUCKET_TARGET_BYTES
    at_setup = [w for w in rec.list
                if "bucket size axis" in str(w.message)]
    assert all(str(BUCKET_TARGET_BYTES) in str(w.message)
               for w in at_setup)

    plan = make_bucket_plan(setup.state.params["student"], 8,
                            target_bytes=2 ** 14)
    assert len(plan.buckets) > len(setup.bucket_plan.buckets)
    with pytest.warns(UserWarning, match="bucket size axis"):
        msgs = warn_bucket_padding(
            plan.padding_stats(), plan.target_bytes)
    assert len(msgs) > len(at_setup)
    assert any("_backbone]" in m for m in msgs)


# ---------------- overlap twin ----------------

def test_overlap_twin_placement(mesh8):
    """grad of the bucketed stream scan: the per-bucket param
    all-gather rides the FORWARD while loop (plus the at-barrier
    priming gather of the double buffer); its transpose — the coalesced
    grad reduce-scatter — lands INSIDE the backward while loop. This is
    the overlap-placement evidence COST_BUCKET_r13.json commits at
    ViT-L scale."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.models.streaming import (
        bucketed_stream_scan,
        pack_stream_buckets,
    )
    from dinov3_tpu.parallel.sharding import UPDATE_SHARD_AXES
    from dinov3_tpu.utils import hlo_collective_census

    n_blocks, n_buckets, dp = 8, 4, 8
    stack = {
        "attn": {"qkv": {"kernel": jnp.zeros((n_blocks, 16, 48),
                                             jnp.bfloat16)},
                 "proj": {"kernel": jnp.zeros((n_blocks, 16, 16),
                                              jnp.bfloat16)}},
        "mlp": {"fc1": {"kernel": jnp.zeros((n_blocks, 16, 64),
                                            jnp.bfloat16)},
                "fc2": {"kernel": jnp.zeros((n_blocks, 64, 16),
                                            jnp.bfloat16)}},
    }
    shards = jax.eval_shape(
        lambda s: pack_stream_buckets(s, n_buckets, dp), stack)
    x = jax.ShapeDtypeStruct((dp * 4,), jnp.float32)
    axes = tuple(a for a in UPDATE_SHARD_AXES if a in mesh8.shape)

    def loss(shards, x):
        return jnp.sum(bucketed_stream_scan(
            shards, x, mesh=mesh8, prefetch=True))

    compiled = jax.jit(
        jax.grad(loss),
        in_shardings=(NamedSharding(mesh8, P(None, axes)),
                      NamedSharding(mesh8, P())),
        out_shardings=NamedSharding(mesh8, P(None, axes)),
    ).lower(shards, x).compile()
    census = hlo_collective_census(compiled.as_text())
    assert census["unattributed"] == 0
    ag = census["by_class"]["all_gather"]["by_placement"]
    rs = census["by_class"]["reduce_scatter"]["by_placement"]
    assert ag.get("in-forward-loop", {"ops": 0})["ops"] >= 1, census
    assert rs.get("in-backward-loop", {"ops": 0})["ops"] >= 1, census
    # the gathers ride the bucket scopes of the double buffer
    scopes = set(census["by_scope"])
    assert any(s.startswith("bucket") for s in scopes), scopes


def test_pack_stream_buckets_shape_and_divisibility():
    from dinov3_tpu.models.streaming import pack_stream_buckets

    stack = {"attn": {"qkv": {"kernel": jnp.ones((8, 4, 12),
                                                 jnp.bfloat16)}},
             "mlp": {"fc1": {"kernel": jnp.ones((8, 4, 16),
                                                jnp.bfloat16)}}}
    out = pack_stream_buckets(stack, 4, 8)
    assert out.shape[0] == 4 and out.shape[1] % 8 == 0
    # equal buckets: every bucket carries g = n_blocks/n_buckets block
    # slices of every streamable leaf
    assert out.shape[1] == (2 * (4 * 12) + 2 * (4 * 16))
    with pytest.raises(ValueError, match="must divide"):
        pack_stream_buckets(stack, 3, 8)


# ---------------- committed acceptance census ----------------

def test_cost_bucket_r13_acceptance():
    """The committed COST_BUCKET_r13.json (ViT-L dp=8, compile-only on
    8 simulated devices): update-phase RS 357 -> <= 16 and AG
    714 -> <= 32, zero unattributed in both twins, >= 90% of collective
    bytes in >= 64MiB buckets, and the overlap twin's grad RS placed
    in the backward loop."""
    rec = json.loads((REPO / "COST_BUCKET_r13.json").read_text())
    assert rec["dp"] == 8 and rec["arch"] == "vit_large"
    rs, ag = rec["reduce_scatter_ops"], rec["all_gather_ops"]
    assert rs["per_leaf"] >= 300 and ag["per_leaf"] >= 600
    assert rs["bucketed"] <= 16 and ag["bucketed"] <= 32

    up = rec["update_phase"]
    for arm in ("per_leaf", "bucketed"):
        assert up["collective_census"][arm]["unattributed"] == 0
    assert up["big_bin_fraction"]["bucketed"] >= 0.90
    assert up["plan"]["n_buckets"] == rs["bucketed"]
    assert up["n_param_leaves"] == rs["per_leaf"]

    ot = rec["overlap_twin"]
    oc = ot["collective_census"]
    assert oc["unattributed"] == 0
    rs_pl = oc["by_class"]["reduce_scatter"]["by_placement"]
    ag_pl = oc["by_class"]["all_gather"]["by_placement"]
    assert rs_pl.get("in-backward-loop", {"ops": 0})["ops"] >= 1
    assert ag_pl.get("in-forward-loop", {"ops": 0})["ops"] >= 1
