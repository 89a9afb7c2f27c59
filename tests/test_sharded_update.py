"""Cross-replica sharded update engine (train/fused_update.py
make_sharded_update) vs the replicated fused oracle.

The sharded engine is the default update path at data-parallel size > 1
(``optim.sharded_update``); the replicated fused engine stays in the
tree as the oracle. These tests pin:
- leaf-for-leaf multi-step equivalence (params, teacher, mu, nu via the
  lossless flat round-trip, both counts) with clip engaged
  (clip=0.05), mixed (3.0) and off (None) — tolerances rtol=1e-6/
  atol=1e-7, the reduction-associativity budget of the flat clip norm;
- the explicit-collective schedule program
  (``make_sharded_update_schedule``, the program
  scripts/cost_sharded_update.py commits the census of) computing the
  identical update from stacked per-replica partial grads;
- padded-lane inertness (flat zero padding stays exactly 0 through the
  engine) and flatten/unflatten losslessness;
- build_train_setup wiring: auto-on at dp > 1, moments born flat-
  sharded over the data axes, =false oracle fallback, the
  fused_update=false conflict raising;
- full-step sharded-vs-replicated dryruns under data x fsdp and
  data x tensor meshes, plus the collective/copy census of the exact
  compiled sharded step (zero unattributed collectives);
- resume determinism across a sharded -> replicated checkpoint
  round-trip and back (bitwise moment round-trip, identical next step);
- the ``warn_update_shard_padding`` guardrail and the
  ``classify_collective`` attribution;
- the COST_SHUP_r10.json acceptance census: reduce-scatter + all-gather
  with zero unattributed collectives on the sharded arm, all-reduce
  only on the replicated arm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.configs import apply_dot_overrides, get_default_config
from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
from dinov3_tpu.parallel.sharding import UPDATE_SHARD_AXES
from dinov3_tpu.train import (
    build_multiplier_trees,
    make_fused_update,
    make_sharded_update,
    make_sharded_update_schedule,
)
from dinov3_tpu.train.fused_update import (
    flatten_update_leaf,
    padded_flat_size,
    sharded_adam_zeros,
    unflatten_update_leaf,
)
from dinov3_tpu.train.optimizer import scheduled_adamw
from test_fused_update import (
    SMOL,
    assert_trees_close,
    fake_params,
    grads_like,
    make_sched,
    smol_cfg,
)

RTOL, ATOL = 1e-6, 1e-7


@pytest.fixture(scope="module")
def mesh8(request):
    devs = jax.devices()
    assert len(devs) == 8
    return build_mesh(MeshSpec(data=8), devices=devs)


def sharded_opt_init(params, sched, lm, wm, ll, dp=8):
    """Oracle-chain init with the mu/nu swapped into the flat sharded
    layout — what build_train_setup's boxed init produces."""
    import flax.linen as nn

    s = scheduled_adamw(sched, lm, wm, ll).init(params)
    return s._replace(adam=s.adam._replace(
        mu=nn.meta.unbox(sharded_adam_zeros(params, dp)),
        nu=nn.meta.unbox(sharded_adam_zeros(params, dp)),
    ))


# ---------------- engine equivalence ----------------

@pytest.mark.parametrize("clip", [0.05, 3.0, None])
def test_sharded_matches_fused_multistep(mesh8, clip):
    """10 steps, leaf-for-leaf: params, teacher, mu/nu (through the flat
    round-trip), both counts. clip=0.05 engages the clip every step,
    None takes the no-clip branch, 3.0 mixes."""
    sched = make_sched()
    params = fake_params()
    lm, wm, ll = build_multiplier_trees(
        params, layerwise_decay=0.9, patch_embed_lr_mult=0.2,
        dino_head_wd_multiplier=0.5,
    )
    fused = make_fused_update(sched, lm, wm, ll, clip_grad=clip, ema=True)
    sharded = make_sharded_update(sched, lm, wm, ll, mesh8,
                                  clip_grad=clip, ema=True)
    momentum = jnp.asarray(0.95, jnp.float32)
    teacher = jax.tree.map(jnp.copy, params)
    s_f = scheduled_adamw(sched, lm, wm, ll).init(params)
    s_s = sharded_opt_init(params, sched, lm, wm, ll)

    with mesh8:
        f_step = jax.jit(lambda g, p, t, s: fused(g, p, t, s, momentum)[:3])
        s_step = jax.jit(lambda g, p, t, s: sharded(g, p, t, s, momentum)[:3])
        p_f = p_s = params
        t_f = t_s = teacher
        key = jax.random.key(0)
        for _ in range(10):
            key, k = jax.random.split(key)
            g = grads_like(params, k)
            p_f, t_f, s_f = f_step(g, p_f, t_f, s_f)
            p_s, t_s, s_s = s_step(g, p_s, t_s, s_s)

    assert_trees_close(p_f, p_s, "params")
    assert_trees_close(t_f, t_s, "teacher")
    mu_back = jax.tree.map(unflatten_update_leaf, s_s.adam.mu, params)
    nu_back = jax.tree.map(unflatten_update_leaf, s_s.adam.nu, params)
    assert_trees_close(s_f.adam.mu, mu_back, "mu")
    assert_trees_close(s_f.adam.nu, nu_back, "nu")
    assert int(s_s.count) == 10 and int(s_s.adam.count) == 10
    # the updates were non-trivial
    assert not np.allclose(np.asarray(jax.tree.leaves(p_s)[0]),
                           np.asarray(jax.tree.leaves(params)[0]))


def test_schedule_program_matches_fused(mesh8):
    """The explicit-collective schedule (psum_scatter/all_gather under
    shard_map — the program COST_SHUP_r10.json accounts) computes the
    identical update from [dp, *leaf] stacks of per-replica partials."""
    sched = make_sched()
    params = fake_params()
    lm, wm, ll = build_multiplier_trees(params, layerwise_decay=0.9)
    clip = 0.05  # engaged every step: the RS'd norms must match too
    fused = make_fused_update(sched, lm, wm, ll, clip_grad=clip, ema=True)
    schedule = make_sharded_update_schedule(sched, lm, wm, ll, mesh8,
                                            clip_grad=clip, ema=True)
    momentum = jnp.asarray(0.9, jnp.float32)
    teacher = jax.tree.map(jnp.copy, params)
    s_f = scheduled_adamw(sched, lm, wm, ll).init(params)
    s_s = sharded_opt_init(params, sched, lm, wm, ll)

    with mesh8:
        f_step = jax.jit(lambda g, p, t, s: fused(g, p, t, s, momentum))
        c_step = jax.jit(lambda gp, p, t, s: schedule(gp, p, t, s, momentum))
        p_f = p_c = params
        t_f = t_c = teacher
        key = jax.random.key(3)
        for _ in range(3):
            key, k1, k2 = jax.random.split(key, 3)
            # random per-replica partials; the oracle consumes their sum
            # computed the same way the schedule's reduce-scatter does
            parts = jax.tree.map(
                lambda l: jax.random.normal(
                    jax.random.fold_in(k1, l.size), (8,) + l.shape, l.dtype),
                params)
            g = jax.tree.map(lambda s_: jnp.sum(s_, 0), parts)
            p_f, t_f, s_f, norms_f = f_step(g, p_f, t_f, s_f)
            p_c, t_c, s_s, norms_c = c_step(parts, p_c, t_c, s_s)

    assert_trees_close(p_f, p_c, "schedule params")
    assert_trees_close(t_f, t_c, "schedule teacher")
    for k in norms_f:
        np.testing.assert_allclose(
            float(norms_f[k]), float(norms_c[k]), rtol=1e-5,
            err_msg=f"clip norm {k}")
    mu_back = jax.tree.map(unflatten_update_leaf, s_s.adam.mu, params)
    assert_trees_close(s_f.adam.mu, mu_back, "schedule mu")


def test_padded_lanes_inert_and_lossless(mesh8):
    """flatten/unflatten round-trips bitwise; the zero padding stays
    exactly 0 through 5 engine steps (so flat -> full -> flat checkpoint
    conversions are lossless in both directions)."""
    x = jnp.arange(13.0)
    flat = flatten_update_leaf(x.reshape(13), 8)
    assert flat.shape == (16,)
    assert np.array_equal(np.asarray(unflatten_update_leaf(flat, x)), x)
    assert padded_flat_size(13, 8) == 16

    sched = make_sched()
    params = fake_params()  # has a (5,)-bias: pads 5 -> 8
    lm, wm, ll = build_multiplier_trees(params)
    sharded = make_sharded_update(sched, lm, wm, ll, mesh8,
                                  clip_grad=3.0, ema=True)
    momentum = jnp.asarray(0.9, jnp.float32)
    s = sharded_opt_init(params, sched, lm, wm, ll)
    p, t = params, jax.tree.map(jnp.copy, params)
    with mesh8:
        step = jax.jit(lambda g, p, t, s: sharded(g, p, t, s, momentum)[:3])
        key = jax.random.key(1)
        for _ in range(5):
            key, k = jax.random.split(key)
            p, t, s = step(grads_like(params, k), p, t, s)
    for (path, mu), (_, like) in zip(
        jax.tree_util.tree_flatten_with_path(s.adam.mu)[0],
        jax.tree_util.tree_flatten_with_path(params)[0],
    ):
        n = like.size
        pad = np.asarray(mu)[n:]
        assert pad.size == mu.shape[0] - n
        assert np.all(pad == 0.0), f"padding moved: {path}"


# ---------------- setup wiring + dryruns ----------------

def _setup(extra, batch_size, eight_devices):
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    # pin the PR-5 flat engine arms: zero3 (PR 7) otherwise auto-takes
    # the fsdp>1 meshes, and the bucketed engine (PR 9) otherwise
    # auto-supersedes the per-leaf schedule this file pins
    cfg = smol_cfg(["parallel.zero3=false",
                    "optim.bucketed_collectives=false"] + list(extra))
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, batch_size, seed=0).items()}
    return build_train_setup(cfg, batch, devices=eight_devices), batch


def test_setup_born_sharded_and_toggles(eight_devices):
    """auto-on at dp > 1: moments born flat over the data axes; =false
    selects the replicated oracle; sharded+unfused conflict raises."""
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup, put_batch

    setup, batch = _setup(["parallel.data=-1", "parallel.fsdp=2"], 8,
                          eight_devices)
    assert setup.sharded_update and setup.fused_update is not None
    mu_leaves = jax.tree.leaves(setup.state.opt_state.adam.mu)
    assert all(l.ndim == 1 for l in mu_leaves)
    specs = [l.sharding.spec for l in mu_leaves]
    assert all(s[0] == UPDATE_SHARD_AXES for s in specs), specs[:2]
    d = put_batch(batch, setup.batch_shardings)
    state, metrics = setup.step_fn(setup.state, d, setup.scalars(0),
                                   jax.random.key(0))
    assert np.isfinite(float(metrics["total_loss"]))
    assert int(state.step) == 1

    setup_off, _ = _setup(["parallel.data=-1", "parallel.fsdp=2",
                           "optim.sharded_update=false"], 8, eight_devices)
    assert not setup_off.sharded_update
    assert all(l.ndim > 0 and l.shape == p.shape for l, p in zip(
        jax.tree.leaves(setup_off.state.opt_state.adam.mu),
        jax.tree.leaves(setup_off.state.params["student"])))

    # auto quietly falls back when the fused engine is off...
    setup_oracle, _ = _setup(["parallel.data=-1",
                              "optim.fused_update=false"], 8, eight_devices)
    assert not setup_oracle.sharded_update
    assert setup_oracle.fused_update is None
    # ...but an EXPLICIT sharded_update=true with fused off is a
    # misconfiguration, not a silent fallback
    with pytest.raises(ValueError, match="sharded_update"):
        _setup(["parallel.data=-1", "optim.fused_update=false",
                "optim.sharded_update=true"], 8, eight_devices)


# Cross-PROGRAM comparisons of params after two full steps: the two
# programs' gradients differ in their last digits (reduction order), and
# Adam's m/sqrt(v) turns that into a visible difference on the few
# elements whose gradient is at noise level. Measured under jax 0.9 on
# this mesh: at most 4.84e-6 on 0.1-10% of a leaf's elements — 2% of one
# Adam step at this schedule's lr (2.5e-4) — where jax 0.4 happened to
# stay under 1e-6. Pinned at 1e-5 (4% of a step); the moments and clip
# norms keep their strict pins in the engine tests above.
FULL_STEP_ATOL = 1e-5


@pytest.mark.parametrize("axes", [
    ["parallel.data=-1", "parallel.fsdp=2"],
    ["parallel.data=-1", "parallel.tensor=2"],
])
def test_full_step_sharded_vs_replicated(axes, eight_devices):
    """Dryruns under data x fsdp and data x tensor: 2 full steps, the
    sharded arm matches the replicated oracle's losses and params."""
    from dinov3_tpu.train import put_batch

    results = {}
    for flag in ("auto", "false"):
        setup, batch = _setup(axes + [f"optim.sharded_update={flag}"], 8,
                              eight_devices)
        assert setup.sharded_update == (flag == "auto")
        d = put_batch(batch, setup.batch_shardings)
        state = setup.state
        for i in range(2):
            state, m = setup.step_fn(state, d, setup.scalars(i),
                                     jax.random.key(0))
        results[flag] = (state, float(m["total_loss"]))

    assert results["auto"][1] == pytest.approx(results["false"][1], rel=1e-5)
    for (pa, la), (_, lb) in zip(
        jax.tree_util.tree_flatten_with_path(
            results["auto"][0].params)[0][:64],
        jax.tree_util.tree_flatten_with_path(
            results["false"][0].params)[0][:64],
    ):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=5e-6, atol=FULL_STEP_ATOL,
            err_msg=f"dryrun params {jax.tree_util.keystr(pa)}")


def test_sharded_step_census(eight_devices):
    """Collective + copy census of the EXACT compiled sharded step: no
    unattributed collectives, and the engine's pack/unpack copies carry
    the "update_shard" attribution instead of inflating "large"."""
    from dinov3_tpu.train import put_batch
    from dinov3_tpu.utils import hlo_collective_census, hlo_copy_census

    setup, batch = _setup(["parallel.data=-1"], 8, eight_devices)
    assert setup.sharded_update
    d = put_batch(batch, setup.batch_shardings)
    compiled = setup.step_fn.lower(
        setup.state, d, setup.scalars(0), jax.random.key(0)).compile()
    text = compiled.as_text()
    coll = hlo_collective_census(text)
    assert coll["unattributed"] == 0
    # the sharded update's param re-gather is in the program (this
    # backend spells reduce-scatter as all-reduce + fused slice, so
    # all_gather is the structural signature to pin here)
    assert coll["by_class"].get("all_gather", {"ops": 0})["ops"] >= 1
    copies = hlo_copy_census(text)
    # ceiling with headroom over the measured smol program; the census
    # categories must stay attributed (no new unexplained "large" class)
    assert copies["hlo_copy_total"] <= 400, copies


# ---------------- checkpoint round-trip + resume determinism ----------------

def test_checkpoint_cross_arm_roundtrip(tmp_path, eight_devices):
    """sharded -> replicated -> sharded checkpoint round-trip: the
    moments survive bitwise (flat padding is lossless both directions)
    and the resumed run is deterministic — the next sharded step from
    the round-tripped state equals the next step from the original."""
    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.train import put_batch

    setup_sh, batch = _setup(["parallel.data=-1", "parallel.fsdp=2"], 8,
                             eight_devices)
    assert setup_sh.sharded_update
    d = put_batch(batch, setup_sh.batch_shardings)
    state1, _ = setup_sh.step_fn(setup_sh.state, d, setup_sh.scalars(0),
                                 jax.random.key(0))

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ck.save(1, state1)
    ck.wait_until_finished()

    # restore into the replicated arm: moments become param-shaped
    setup_rep, _ = _setup(["parallel.data=-1", "parallel.fsdp=2",
                           "optim.sharded_update=false"], 8, eight_devices)
    rep_state = ck.restore(setup_rep.state, 1)
    assert all(l.shape == p.shape for l, p in zip(
        jax.tree.leaves(rep_state.opt_state.adam.mu),
        jax.tree.leaves(rep_state.params["student"])))
    # ... and back: bitwise identical to the original sharded state
    ck.save(2, rep_state)
    ck.wait_until_finished()
    back = ck.restore(setup_sh.state, 2)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(state1.opt_state)[0],
        jax.tree_util.tree_flatten_with_path(back.opt_state)[0],
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"round-trip changed {jax.tree_util.keystr(path)}")

    # resume determinism: the next step from the round-tripped state is
    # the next step from the original state
    s_orig, m_orig = setup_sh.step_fn(state1, d, setup_sh.scalars(1),
                                      jax.random.key(0))
    s_back, m_back = setup_sh.step_fn(back, d, setup_sh.scalars(1),
                                      jax.random.key(0))
    assert float(m_orig["total_loss"]) == float(m_back["total_loss"])
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(s_orig.params)[0][:32],
        jax.tree_util.tree_flatten_with_path(s_back.params)[0][:32],
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"resume diverged at {jax.tree_util.keystr(path)}")

    # the replicated arm also RUNS from the adapted state (clip + update
    # consume the converted moments)
    d_rep = put_batch(batch, setup_rep.batch_shardings)
    s_rep, m_rep = setup_rep.step_fn(rep_state, d_rep, setup_rep.scalars(1),
                                     jax.random.key(0))
    assert np.isfinite(float(m_rep["total_loss"]))
    assert int(s_rep.step) == 2


# ---------------- guardrail ----------------

def test_update_shard_padding_guardrail(recwarn):
    from dinov3_tpu.configs.config import (
        update_shard_padding_waste,
        warn_update_shard_padding,
    )

    # well-divisible leaves: zero waste, no warning
    assert update_shard_padding_waste([64, 128, 1024], 8) == 0.0
    assert warn_update_shard_padding([64, 128, 1024], 8) is None
    # tiny-leaf pathology: [3, 5, 7] at dp=8 pads 15 -> 24 (60%)
    waste = update_shard_padding_waste([3, 5, 7], 8)
    assert waste > 0.5
    msg = warn_update_shard_padding([3, 5, 7], 8)
    assert msg is not None and "sharded-update flat master axis" in msg
    assert "dp=8" in msg
    w = [x for x in recwarn.list
         if "sharded-update flat master axis" in str(x.message)]
    assert len(w) == 1
    # threshold respected: 1 padded element in 1e6 is silent
    assert warn_update_shard_padding([10 ** 6 - 1], 8) is None


# ---------------- collective census ----------------

def test_classify_collective_attribution():
    from dinov3_tpu.utils import classify_collective

    ent = "ENTRY %main.1 (p0: f32[8]) -> f32[8] {\n"
    cases = {
        "  %ar = f32[128]{0} all-reduce(f32[128]{0} %x), replica_groups={}":
            "all_reduce",
        "  %ars = (f32[128], f32[128]) all-reduce-start(f32[128] %x)":
            "all_reduce",
        "  %rs = f32[16]{0} reduce-scatter(f32[128]{0} %x), dimensions={0}":
            "reduce_scatter",
        "  %ag = f32[128]{0} all-gather(f32[16]{0} %x), dimensions={0}":
            "all_gather",
        "  %cp = f32[16]{0} collective-permute(f32[16]{0} %x)": "ppermute",
        "  %aa = f32[16]{0} all-to-all(f32[16]{0} %x)": "all_to_all",
        "  %cb = f32[16]{0} collective-broadcast(f32[16]{0} %x)":
            "unattributed",
        # -done halves and non-collectives don't count
        "  %ard = f32[128]{0} all-reduce-done((f32[128], f32[128]) %ars)":
            None,
        "  %f = f32[128]{0} fusion(f32[128]{0} %x), kind=kLoop": None,
        "  %red = f32[] reduce(f32[128]{0} %x, f32[] %c)": None,
    }
    for line, want in cases.items():
        assert classify_collective(line) == want, line
    # whole-module census over the same lines
    from dinov3_tpu.utils import hlo_collective_census

    census = hlo_collective_census(ent + "\n".join(cases) + "\n}")
    assert census["by_class"]["all_reduce"]["ops"] == 2
    assert census["by_class"]["reduce_scatter"]["ops"] == 1
    assert census["by_class"]["reduce_scatter"]["bytes"] == 16 * 4
    assert census["unattributed"] == 1


def test_cost_script_census_acceptance(mesh8):
    """The COST_SHUP acceptance pins, on the test-scale trees: the
    schedule program's census is reduce-scatter + all-gather + the one
    small clip psum with ZERO unattributed collectives (one RS per leaf,
    two AG per leaf — student and teacher); the replicated arm is
    all-reduce only, with no RS/AG."""
    from dinov3_tpu.utils import hlo_collective_census

    sched = make_sched()
    params = fake_params()
    n_leaves = len(jax.tree.leaves(params))
    lm, wm, ll = build_multiplier_trees(params)
    fused = make_fused_update(sched, lm, wm, ll, clip_grad=3.0, ema=True)
    schedule = make_sharded_update_schedule(sched, lm, wm, ll, mesh8,
                                            clip_grad=3.0, ema=True)
    momentum = jnp.asarray(0.9, jnp.float32)
    s_sh = sharded_opt_init(params, sched, lm, wm, ll)
    s_rep = scheduled_adamw(sched, lm, wm, ll).init(params)
    gstack = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((8,) + l.shape, l.dtype), params)

    with mesh8:
        from jax.sharding import NamedSharding, PartitionSpec as P

        axes = tuple(a for a in UPDATE_SHARD_AXES if a in mesh8.shape)
        stacks = jax.tree.map(lambda _: NamedSharding(mesh8, P(axes)),
                              gstack)
        c_sh = jax.jit(
            lambda gp, p, t, s: schedule(gp, p, t, s, momentum),
            in_shardings=(stacks, None, None, None),
        ).lower(gstack, params, params, s_sh).compile()
        c_rep = jax.jit(
            lambda gp, p, t, s: fused(
                jax.tree.map(lambda x: jnp.sum(x, 0), gp), p, t, s,
                momentum),
            in_shardings=(stacks, None, None, None),
        ).lower(gstack, params, params, s_rep).compile()

    sh = hlo_collective_census(c_sh.as_text())
    assert sh["unattributed"] == 0
    assert sh["by_class"]["reduce_scatter"]["ops"] == n_leaves
    assert sh["by_class"]["all_gather"]["ops"] == 2 * n_leaves
    # the only all-reduce is the small clip-norm psum (scalar bytes)
    ar = sh["by_class"].get("all_reduce", {"ops": 0, "bytes": 0})
    assert ar["bytes"] <= 64

    rep = hlo_collective_census(c_rep.as_text())
    assert rep["unattributed"] == 0
    assert rep["by_class"].get("reduce_scatter", {"ops": 0})["ops"] == 0
    assert rep["by_class"].get("all_gather", {"ops": 0})["ops"] == 0
    assert rep["by_class"]["all_reduce"]["ops"] >= 1
    # the committed ViT-L artifact tells the same story at scale
    import json
    import os

    art = os.path.join(os.path.dirname(__file__), "..", "COST_SHUP_r10.json")
    with open(art) as f:
        rec = json.load(f)
    assert rec["weight_shaped_reduction_pct"] >= 60.0
    assert rec["collective_census"]["sharded"]["unattributed"] == 0
    assert rec["collective_census"]["replicated"]["by_class"].keys() == {
        "all_reduce"}
    assert "reduce_scatter" in rec["collective_census"]["sharded"]["by_class"]
    assert "all_gather" in rec["collective_census"]["sharded"]["by_class"]
