"""ZeRO-3 weight-streaming engine (parallel.zero3: train/setup.py +
parallel/sharding.py zero3_* + ops/block.py stream wrapper +
models/streaming.py explicit twin) vs the replicated-masters oracle.

The zero3 engine is the default master layout at ``parallel.fsdp > 1``
(and at any data-axis product > 1 via ``parallel.zero3=true``); the
replicated layout stays in the tree as the oracle behind ``=false``.
These tests pin:

- leaf-for-leaf BITWISE equivalence of the two arms on the same mesh:
  every loss metric (values), the first-step adam mu (grads — mu is
  (1-b1)*g_clipped at step one), and the post-update masters/teacher/
  moments, over multiple steps;
- the weight-stream structure of the compiled step: all-gathers INSIDE
  the block scan's while body, attributed to the ``zero3_stream``/
  ``zero3_gather`` named scopes, zero unattributed collectives
  (``utils.hlo_collective_census`` by_scope / prefetch_overlap);
- the explicit double-buffered twin (``streamed_block_scan``): numerics
  bitwise against a per-block oracle loop and against its own at-use
  variant, and the prefetch-overlap census columns (every in-loop
  gather ``zero3_prefetch``-scoped = issued a block ahead of its
  consumer);
- dp-only and dp x fsdp dryruns, plus the unrolled (scan_layers=false)
  path;
- setup wiring: auto-on at fsdp > 1, model-SHAPED sharded moments (not
  the bucketed arm's flat layout), oracle fallback;
- cross-arm checkpoints in all directions (replicated <-> zero3 as pure
  re-placements; the bucketed arm's per-leaf flat on-disk layout <->
  zero3 through the _adapt_opt_leaf flat/full path), with bitwise
  round-trips and resume determinism (tests/test_ckpt_zero3.py);
- the layout guardrails (warn_zero3_padding / warn_zero3_no_stream) and
  the committed COST_Z3_r12.json / MEM_r12.json acceptance numbers
  (>= 70% master reduction, replicated-fraction pin, attributed
  gathers, populated prefetch column);
- the ViT-7B compile-only dryrun (slow) — the unlock deliverable.
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
from dinov3_tpu.parallel.sharding import (
    ZERO3_AXES,
    zero3_leaf_spec,
    zero3_replicated_waste,
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
    "optim.warmup_epochs=1",
]


def _setup(extra, batch_size, devices):
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    cfg = get_default_config()
    # pin the buckets off: this file pins the per-leaf zero3 arm against
    # the replicated one, and =false selects exactly those two (zero3
    # without gather buckets; the replicated fused engine on a mesh
    # zero3 does not take)
    apply_dot_overrides(
        cfg, SMOL + ["optim.bucketed_collectives=false"] + list(extra))
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, batch_size, seed=0).items()}
    return build_train_setup(cfg, batch, devices=devices), batch


def _flat_params(tree):
    return jtu.tree_flatten_with_path(tree)[0]


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


# ---------------- layout / spec unit tests ----------------

def test_zero3_leaf_spec_dim_choice(eight_devices):
    mesh = build_mesh(MeshSpec(data=8), devices=eight_devices)
    # largest dividing dim wins
    spec = zero3_leaf_spec((64, 192), ("embed", "heads"), mesh)
    assert spec[1] == ZERO3_AXES and spec[0] is None
    # stacked scan dim is never taken, even though it divides
    spec = zero3_leaf_spec((8, 64, 192), ("layers", "embed", "heads"), mesh)
    assert spec[0] is None and spec[2] == ZERO3_AXES
    # no dividing dim -> None (leaf stays on the logical-rules layout)
    assert zero3_leaf_spec((3, 5), (None, None), mesh) is None
    # scalars/empty shapes -> None
    assert zero3_leaf_spec((), (), mesh) is None
    # 1-device mesh -> None (nothing to shard)
    mesh1 = build_mesh(MeshSpec(data=1), devices=eight_devices[:1])
    assert zero3_leaf_spec((64,), ("embed",), mesh1) is None


def test_zero3_leaf_spec_respects_tensor_axes(eight_devices):
    mesh = build_mesh(MeshSpec(data=4, tensor=2), devices=eight_devices)
    # "heads" maps to the >1 tensor axis: kept, zero3 lands elsewhere
    spec = zero3_leaf_spec((64, 192), ("embed", "heads"), mesh)
    assert spec[1] == "tensor"
    assert spec[0] == ZERO3_AXES
    # both dims tensor-owned at tensor>1, none free -> None
    spec = zero3_leaf_spec((192,), ("heads",), mesh)
    assert spec is None


def test_zero3_replicated_waste():
    mesh = build_mesh(MeshSpec(data=8), devices=jax.devices())
    # everything shardable -> 0
    assert zero3_replicated_waste(
        [((64, 64), (None, None)), ((128,), (None,))], mesh) == 0.0
    # a stuck leaf contributes its element share
    waste = zero3_replicated_waste(
        [((64,), (None,)), ((3, 5), (None, None))], mesh)
    assert waste == pytest.approx(15 / 79)


# ---------------- guardrails ----------------

def test_zero3_guardrails(recwarn):
    from dinov3_tpu.configs.config import (
        warn_zero3_no_stream,
        warn_zero3_padding,
    )

    assert warn_zero3_padding(0.0, 8) is None
    msg = warn_zero3_padding(0.25, 8)
    assert msg is not None and "zero3 master layout" in msg
    assert "dp=8" in msg
    assert len([w for w in recwarn.list
                if "zero3 master layout" in str(w.message)]) == 1

    # no-stream warning: zero3 wished (fsdp>1) + scan_layers=false
    cfg = get_default_config()
    apply_dot_overrides(cfg, ["parallel.fsdp=2", "train.scan_layers=false"])
    msg = warn_zero3_no_stream(cfg)
    assert msg is not None and "scan_layers" in msg
    # scan on, or zero3 off: silent
    cfg2 = get_default_config()
    apply_dot_overrides(cfg2, ["parallel.fsdp=2", "train.scan_layers=true"])
    assert warn_zero3_no_stream(cfg2) is None
    cfg3 = get_default_config()
    assert warn_zero3_no_stream(cfg3) is None


# ---------------- setup wiring + toggles ----------------

def test_setup_wiring_and_toggles(eight_devices):
    # explicit true on a dp-only mesh: masters sharded, moments
    # model-SHAPED and sharded (not the bucketed arm's flat layout)
    setup, _ = _setup(["parallel.zero3=true"], 16, eight_devices)
    assert setup.arm == "zero3" and setup.zero3
    for (path, leaf), (_, sh) in zip(
        _flat_params(setup.state.params["student"])[:16],
        _flat_params(setup.state_shardings.params["student"])[:16],
    ):
        if any(d % 8 == 0 for d in leaf.shape):
            assert any(s == ZERO3_AXES for s in sh.spec), (
                jtu.keystr(path), sh.spec)
    mu0 = jax.tree.leaves(setup.state.opt_state.adam.mu)[0]
    p0 = jax.tree.leaves(setup.state.params["student"])[0]
    assert mu0.shape == p0.shape  # model-shaped, not flat

    # auto: on at fsdp>1, off on a dp-only mesh
    s_fsdp, _ = _setup(["parallel.fsdp=2"], 16, eight_devices)
    assert s_fsdp.arm == "zero3" and s_fsdp.zero3
    s_dp, _ = _setup([], 16, eight_devices)
    assert s_dp.arm == "replicated" and not s_dp.zero3

    # =false: the replicated update, placed by the logical rules alone
    s_off, _ = _setup(["parallel.fsdp=2", "parallel.zero3=false"], 16,
                      eight_devices)
    assert s_off.arm == "replicated" and not s_off.zero3
    assert jax.tree.leaves(s_off.state.opt_state.adam.mu)[0].shape \
        == p0.shape


# ---------------- bitwise equivalence ----------------

@pytest.fixture(scope="module")
def arms_dp(eight_devices):
    """zero3 vs replicated arms on the dp-only 8-device mesh: the
    comparison isolates the master layout (both arms run the fused
    update)."""
    from dinov3_tpu.train import put_batch

    s_z, batch = _setup(["parallel.zero3=true"], 16, eight_devices)
    s_r, _ = _setup(["parallel.zero3=false"], 16, eight_devices)
    d = put_batch(batch, s_z.batch_shardings)
    return s_z, s_r, d


def test_bitwise_equivalence_dp_only(arms_dp):
    """Values (every loss metric), grads (step-1 mu) and post-update
    masters/teacher/moments: BITWISE equal between the zero3 and
    replicated arms over 2 steps."""
    s_z, s_r, d = arms_dp
    st_z, st_r = s_z.state, s_r.state
    for i in range(2):
        st_z, m_z = s_z.step_fn(st_z, d, s_z.scalars(i), jax.random.key(0))
        st_r, m_r = s_r.step_fn(st_r, d, s_r.scalars(i), jax.random.key(0))
        for k in m_r:
            assert float(m_z[k]) == float(m_r[k]), (i, k)
        if i == 0:
            # step-1 mu is (1-b1) * clipped grad: grads bitwise
            assert_trees_bitwise(st_z.opt_state.adam.mu,
                                 st_r.opt_state.adam.mu, "grads (mu)")
    # bitwise under jax 0.4; under jax 0.9 the two arms' fused updates
    # round differently, and not identically from run to run: measured
    # 3 last-digit units of the leaf's scale on the masters and 0 / 0.5
    # / 1.5 on nu over five runs (values and grads stay bitwise). The
    # pin is the suite's existing cross-arm ceiling of 8 such units
    # (tests/test_buckets.py assert_trees_ulp) — 1e-6 of the scale.
    assert_trees_bitwise(st_z.params, st_r.params, "post-update masters",
                         max_ulps=8)
    assert_trees_bitwise(st_z.opt_state.adam.nu, st_r.opt_state.adam.nu,
                         "nu", max_ulps=8)
    # the zero3 masters really are sharded (not silently replicated)
    from dinov3_tpu.telemetry.memory import layout_split

    split = layout_split(st_z.params, s_z.state_shardings.params)
    assert split["replicated_fraction"] < 0.05
    rep = layout_split(st_r.params, s_r.state_shardings.params)
    assert rep["replicated_fraction"] > 0.9


def test_dryrun_dp_fsdp(eight_devices):
    """dp x fsdp mesh: the zero3 arm (auto-on) runs 2 finite steps and
    matches the replicated arm at PR-5 dryrun tolerances. Both arms
    START FROM THE SAME STATE (zero3 keeps model shapes, so the zero3
    init re-places losslessly into the oracle arm's shardings): on this
    backend the init DRAWS themselves depend on the init program's
    shardings (the fsdp-mesh embed-sharded init already differs from
    the eager init on 10 leaves pre-PR-7), so per-arm inits would
    compare two different models. fp32 compute: the fsdp-mesh oracle
    partitions its matmuls over the embed axis where zero3 gathers the
    weights — in fp32 only reduction associativity separates the
    programs."""
    from dinov3_tpu.train import put_batch

    # layerscale=1: at the recipe's 1e-5 the collapsed-init KoLeo term
    # amplifies last-digit noise between two PROGRAMS into 2e-3 of the
    # loss (measured here: 8.5426 vs 8.5593 at step 2; cause pinned in
    # tests/test_parallel.py test_sharded_matches_single_device)
    common = ["parallel.data=-1", "parallel.fsdp=2",
              "student.layerscale=1.0",
              "compute_precision.compute_dtype=fp32"]
    s_z, batch = _setup(common + ["parallel.zero3=auto"], 16,
                        eight_devices)
    s_r, _ = _setup(common + ["parallel.zero3=false"], 16, eight_devices)
    assert s_z.zero3 and not s_r.zero3
    # a COPY: device_put onto an equal sharding may hand back the same
    # buffer, and the zero3 arm's step donates its state
    state_r = jax.device_put(s_z.state, s_r.state_shardings,
                             may_alias=False)
    results = {}
    for name, setup, state in (("zero3", s_z, s_z.state),
                               ("oracle", s_r, state_r)):
        d = put_batch(batch, setup.batch_shardings)
        for i in range(2):
            state, m = setup.step_fn(state, d, setup.scalars(i),
                                     jax.random.key(0))
        results[name] = (state, float(m["total_loss"]))
    assert results["zero3"][1] == pytest.approx(results["oracle"][1],
                                                rel=1e-5)
    for (pa, la), (_, lb) in zip(
        _flat_params(results["zero3"][0].params)[:48],
        _flat_params(results["oracle"][0].params)[:48],
    ):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=5e-6, atol=1e-6,
            err_msg=f"dp x fsdp params {jtu.keystr(pa)}")


def test_dryrun_unrolled_blocks(eight_devices):
    """scan_layers=false + zero3: the unrolled path still runs (gathers
    in the flat graph, no stream loop) and stays bitwise with its own
    replicated arm."""
    from dinov3_tpu.train import put_batch

    s_z, batch = _setup(["parallel.zero3=true", "train.scan_layers=false"],
                        16, eight_devices)
    s_r, _ = _setup(["parallel.zero3=false", "train.scan_layers=false"],
                    16, eight_devices)
    d = put_batch(batch, s_z.batch_shardings)
    st_z, m_z = s_z.step_fn(s_z.state, d, s_z.scalars(0), jax.random.key(0))
    st_r, m_r = s_r.step_fn(s_r.state, d, s_r.scalars(0), jax.random.key(0))
    assert float(m_z["total_loss"]) == float(m_r["total_loss"])
    assert_trees_bitwise(st_z.params, st_r.params, "unrolled masters",
                         limit=48)


# ---------------- weight-stream HLO structure ----------------

def test_stream_gathers_in_loop_and_scoped(arms_dp):
    """The compiled zero3 step's census: gathers inside the block scan's
    while body, zero3_stream/zero3_gather scope attribution present,
    zero unattributed collectives; the replicated arm has none of the
    zero3 scopes."""
    from dinov3_tpu.utils import hlo_collective_census, hlo_copy_census

    s_z, s_r, d = arms_dp
    comp = s_z.step_fn.lower(
        s_z.state, d, s_z.scalars(0), jax.random.key(0)).compile()
    text = comp.as_text()
    cen = hlo_collective_census(text)
    assert cen["unattributed"] == 0
    assert cen["by_scope"].get("zero3_stream", {"ops": 0})["ops"] > 0
    assert cen["by_scope"].get("zero3_gather", {"ops": 0})["ops"] > 0
    pf = cen["prefetch_overlap"]
    assert pf["all_gather_in_loop_ops"] > 0
    assert pf["at_use_scoped_ops"] > 0  # engine gathers at use in-loop
    # copy census: the zero3 scopes never surface as unexplained "large"
    copies = hlo_copy_census(text)
    assert copies["hlo_copy_total"] <= 400, copies

    comp_r = s_r.step_fn.lower(
        s_r.state, d, s_r.scalars(0), jax.random.key(0)).compile()
    cen_r = hlo_collective_census(comp_r.as_text())
    assert not any(k.startswith("zero3") for k in cen_r["by_scope"])


# ---------------- explicit double-buffered twin ----------------

def _twin_fixture(dtype):
    import flax.linen as nn

    from dinov3_tpu.models.streaming import (
        cast_stream_leaves,
        make_block_apply,
    )
    from dinov3_tpu.ops.block import SelfAttentionBlock
    from dinov3_tpu.parallel.context import set_current_mesh

    mesh = build_mesh(MeshSpec(data=8), devices=jax.devices())
    set_current_mesh(mesh)
    kwargs = dict(dim=64, num_heads=2, ffn_ratio=2.0, drop_path_rate=0.0,
                  dtype=dtype)
    L, N, D = 4, 17, 64
    block = SelfAttentionBlock(**kwargs)
    one = nn.meta.unbox(
        block.init(jax.random.key(0), jnp.zeros((1, N, D), dtype))
    )["params"]
    stack = jax.tree.map(
        lambda p: jnp.stack([p + 0.01 * i for i in range(L)]), one)
    stack = cast_stream_leaves(stack, dtype)
    x = jax.random.normal(jax.random.key(1), (16, N, D), dtype)
    return mesh, kwargs, stack, x, L, make_block_apply(kwargs)


def _twin_shardings(stack, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    def sh(p):
        spec = zero3_leaf_spec(
            p.shape, ("layers",) + (None,) * (p.ndim - 1), mesh)
        return NamedSharding(mesh, spec if spec is not None else P())

    return jax.tree.map(sh, stack)


def test_streamed_twin_matches_oracle():
    """fp32 twin: double-buffered schedule bitwise == at-use schedule
    bitwise == the per-block oracle loop."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.models.streaming import streamed_block_scan

    mesh, kwargs, stack, x, L, apply_fn = _twin_fixture(jnp.float32)
    stack_sh = _twin_shardings(stack, mesh)
    stack_dev = jax.device_put(stack, stack_sh)
    x_sh = NamedSharding(mesh, P("data"))
    x_dev = jax.device_put(x, x_sh)

    def oracle(s, xx):
        for i in range(L):
            xx = apply_fn(jax.tree.map(lambda p: p[i], s), xx)
        return xx

    xo = jax.jit(oracle)(stack, x)
    with mesh:
        x_pf = jax.jit(
            lambda s, xx: streamed_block_scan(apply_fn, s, xx, L, mesh),
            in_shardings=(stack_sh, x_sh))(stack_dev, x_dev)
        x_au = jax.jit(
            lambda s, xx: streamed_block_scan(apply_fn, s, xx, L, mesh,
                                              prefetch=False),
            in_shardings=(stack_sh, x_sh))(stack_dev, x_dev)
    assert np.array_equal(np.asarray(x_pf), np.asarray(x_au))
    assert np.array_equal(np.asarray(x_pf), np.asarray(xo))


def test_twin_prefetch_overlap_census():
    """The prefetch-overlap HLO check: every in-loop gather of the
    double-buffered twin is zero3_prefetch-scoped (issued one block
    ahead of its consumer; the priming gather sits outside the loop
    under zero3_gather); the at-use variant flips the attribution."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.models.streaming import streamed_block_scan
    from dinov3_tpu.utils import hlo_collective_census

    mesh, kwargs, stack, x, L, apply_fn = _twin_fixture(jnp.float32)
    stack_sh = _twin_shardings(stack, mesh)
    x_sh = NamedSharding(mesh, P("data"))
    n_leaves = len(jax.tree.leaves(stack))

    with mesh:
        c_pf = jax.jit(
            lambda s, xx: streamed_block_scan(apply_fn, s, xx, L, mesh),
            in_shardings=(stack_sh, x_sh)).lower(stack, x).compile()
        c_au = jax.jit(
            lambda s, xx: streamed_block_scan(apply_fn, s, xx, L, mesh,
                                              prefetch=False),
            in_shardings=(stack_sh, x_sh)).lower(stack, x).compile()

    cen = hlo_collective_census(c_pf.as_text())
    pf = cen["prefetch_overlap"]
    assert pf["prefetch_scoped_ops"] == n_leaves
    assert pf["at_use_scoped_ops"] == 0
    assert pf["all_gather_in_loop_ops"] == n_leaves
    assert cen["by_scope"]["zero3_gather"]["ops"] == n_leaves  # priming
    assert cen["unattributed"] == 0

    cen_au = hlo_collective_census(c_au.as_text())
    pf_au = cen_au["prefetch_overlap"]
    assert pf_au["prefetch_scoped_ops"] == 0
    assert pf_au["at_use_scoped_ops"] == n_leaves


def test_stream_prefetch_depths_bitwise_equivalent():
    """Every lookahead depth (and the boolean spellings) computes the
    SAME forward bitwise: depth is purely a gather schedule."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.models.streaming import (
        STREAM_PREFETCH,
        prefetch_depth,
        streamed_block_scan,
    )

    assert (prefetch_depth(False), prefetch_depth(True)) == (0, 1)
    assert (prefetch_depth(0), prefetch_depth(1), prefetch_depth(3)) \
        == (0, 1, 3)
    assert prefetch_depth(STREAM_PREFETCH) == 1
    with pytest.raises(ValueError, match="prefetch depth"):
        prefetch_depth(-1)

    mesh, kwargs, stack, x, L, apply_fn = _twin_fixture(jnp.float32)
    stack_sh = _twin_shardings(stack, mesh)
    stack_dev = jax.device_put(stack, stack_sh)
    x_sh = NamedSharding(mesh, P("data"))
    x_dev = jax.device_put(x, x_sh)
    outs = []
    with mesh:
        for depth in (False, 0, True, 1, 2, 3):
            outs.append(np.asarray(jax.jit(
                lambda s, xx, d=depth: streamed_block_scan(
                    apply_fn, s, xx, L, mesh, prefetch=d),
                in_shardings=(stack_sh, x_sh))(stack_dev, x_dev)))
    for o in outs[1:]:
        assert np.array_equal(outs[0], o)


# the cross-arm checkpoints: tests/test_ckpt_zero3.py


# ---------------- committed artifacts ----------------

def test_cost_artifact_acceptance():
    """COST_Z3_r12.json: >= 70% per-device master reduction at dp=8
    ViT-L, every gather attributed (zero unattributed), the
    prefetch-overlap column populated, masters' replicated fraction
    pinned ~0 on the zero3 arm (the MEM pin), and the 7B unlock section
    present with a compiling dryrun."""
    with open(os.path.join(REPO, "COST_Z3_r12.json")) as f:
        rec = json.load(f)
    assert rec["dp"] == 8 and rec["arch"] == "vit_large"
    assert rec["master_weight_state_reduction_pct"] >= 70.0
    z3 = rec["arms"]["zero3"]
    for k in ("params_student", "params_teacher"):
        assert z3["per_device_state"][k]["replicated_fraction"] < 0.05
    rep = rec["arms"]["replicated"]
    assert rep["per_device_state"]["params_student"][
        "replicated_fraction"] > 0.9
    cen = z3["collective_census"]
    assert cen["unattributed"] == 0
    assert cen["by_scope"].get("zero3_stream", {"ops": 0})["ops"] > 0
    twin = rec["prefetch_twin"]["collective_census"]
    assert twin["prefetch_overlap"]["prefetch_scoped_ops"] >= \
        rec["prefetch_twin"]["stack_param_leaves"]
    v7 = rec["vit7b_unlock"]
    assert v7["compiled"] and v7["dp"] == 8
    assert v7["n_student_params"] > 6e9
    # the unlock arithmetic: sharded state fits where replicated cannot
    assert (v7["state_bytes_per_device_total"]
            < 0.2 * v7["replicated_equivalent_bytes_per_device"])

    with open(os.path.join(REPO, "MEM_r12.json")) as f:
        mem = json.load(f)
    for k in ("params_student", "params_teacher"):
        assert mem["arms"]["zero3"]["replicated_fraction"][k] < 0.05
    z_mem = mem["arms"]["zero3"]["bytes_in_use_per_device"]
    r_mem = mem["arms"]["replicated"]["bytes_in_use_per_device"]
    # the headline: 2 x 1.40 GB replicated masters -> ~2 x 175 MB/device
    assert r_mem["params_student"] > 1.3e9
    assert z_mem["params_student"] < 0.3 * r_mem["params_student"]


# ---------------- the 7B unlock dryrun ----------------

@pytest.mark.slow
def test_vit7b_zero3_compile_dryrun(eight_devices):
    """The flagship unlock, end-to-end: the committed ViT-7B zero3
    recipe builds abstractly (init_state=False — nothing materializes)
    and its train step lowers AND compiles on the 8-simulated-device
    mesh, with the per-device accounting sharded (not replicated)."""
    from dinov3_tpu.configs import load_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.telemetry.memory import layout_split
    from dinov3_tpu.train import build_train_setup

    cfg = load_config(os.path.join(REPO, "configs/train/vit7b16_zero3.yaml"))
    B = int(cfg.train.batch_size_per_device) * 8
    batch_np = make_synthetic_batch(cfg, B, seed=0)
    setup = build_train_setup(cfg, batch_np, devices=eight_devices,
                              init_state=False)
    assert setup.zero3
    split = layout_split(setup.state.params, setup.state_shardings.params)
    assert split["replicated_fraction"] < 0.05
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch_np.items()}
    scalars = {"teacher_temp": jax.ShapeDtypeStruct((), jnp.float32),
               "momentum": jax.ShapeDtypeStruct((), jnp.float32)}
    compiled = setup.step_fn.lower(
        setup.state, batch, scalars, jax.random.key(0)).compile()
    assert compiled is not None