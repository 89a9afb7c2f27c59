"""Elastic topology engine (parallel/reshard.py) + topology-elastic
checkpoints (ISSUE 19).

Pins, on the 8-virtual-device CPU mesh:

- in-memory reshard round-trips BITWISE across meshes (dp=8 <->
  dp=2 x fsdp=4) and opt-state arms (replicated / zero3 / bucketed),
  every transfer one jitted program per leaf-group with every inserted
  collective attributed to its ``reshard_*`` scope (zero unattributed,
  zero "other" leakage);
- the in-memory path is bitwise-interchangeable with the disk path
  (checkpoint save + cross-arm restore) on the same transition, and one
  train step from either resumed state is bitwise-deterministic;
- a TRUE resize (8 -> 4 devices) takes the staged device_put transfer
  path — still in memory, still bitwise;
- the cross-topology checkpoint matrix: a state saved at each of
  {replicated, zero3, unified} x {dp=8, dp=2x4} restores at a different
  (arm, mesh) bitwise (satellite: the checkpoint generalization);
- atomic checkpoint finalization: an interrupted/truncated save is
  unreadable-as-latest in BOTH backends (write-then-finalize marker in
  the local-npz backend, structural readability probe over orbax step
  dirs), so resume picks the previous step;
- ``elastic_resume`` policy routing (auto/memory/disk) and the
  ``topology.json`` sidecar.
"""

import json
import os

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from dinov3_tpu.configs import apply_dot_overrides, get_default_config
from dinov3_tpu.parallel.reshard import (
    ARM_LAYOUT,
    RESHARD_SCOPES,
    describe_topology,
    moments_convert_needed,
    reshard_state,
    topology_of,
)

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

REP8 = ["parallel.data=8", "parallel.zero3=false",
        "optim.bucketed_collectives=false"]
Z24 = ["parallel.data=2", "parallel.fsdp=4", "parallel.zero3=true",
       "optim.bucketed_collectives=false"]
BUK8 = ["parallel.data=8", "parallel.zero3=false",
        "optim.bucketed_collectives=true"]
U24 = ["parallel.data=2", "parallel.fsdp=4", "parallel.zero3=true",
       "optim.bucketed_collectives=true"]
Z8 = ["parallel.data=8", "parallel.zero3=true",
      "optim.bucketed_collectives=false"]
REP24 = ["parallel.data=2", "parallel.fsdp=4", "parallel.zero3=false",
         "optim.bucketed_collectives=false"]


def _setup(extra, devices=None, init_state=True):
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    cfg = get_default_config()
    apply_dot_overrides(cfg, SMOL + list(extra))
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, 16, seed=0).items()}
    return build_train_setup(cfg, batch, devices=devices,
                             init_state=init_state), batch


def assert_bitwise(a, b, what):
    fa = jtu.tree_flatten_with_path(a)[0]
    fb = jtu.tree_flatten_with_path(b)[0]
    assert len(fa) == len(fb), (what, len(fa), len(fb))
    for (pa, la), (_, lb) in zip(fa, fb):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), (
            f"{what}: {jtu.keystr(pa)} differs")


@pytest.fixture(scope="module")
def topo(eight_devices):
    """One stepped replicated dp=8 state + the zero3 dp=2x4 setup it
    reshards into (concrete: the disk path and the determinism step
    need real arrays there) + the bucketed dp=8 target (abstract)."""
    from dinov3_tpu.train import put_batch

    s_r, batch = _setup(REP8, devices=eight_devices)
    d_r = put_batch(batch, s_r.batch_shardings)
    state1, _ = s_r.step_fn(s_r.state, d_r, s_r.scalars(0),
                            jax.random.key(0))
    s_z, _ = _setup(Z24, devices=eight_devices)
    s_b, _ = _setup(BUK8, devices=eight_devices, init_state=False)
    return {"s_r": s_r, "s_z": s_z, "s_b": s_b, "batch": batch,
            "d_r": d_r, "state1": state1}


# ---------------- unit: vocabulary / descriptors ----------------

def test_reshard_scopes_registered():
    from dinov3_tpu.utils import (
        HLO_COLLECTIVE_SCOPES,
        classify_collective_scope,
    )

    markers = [m for m, _ in HLO_COLLECTIVE_SCOPES]
    for scope in RESHARD_SCOPES:
        assert scope in markers
        line = (f'  %all-to-all.1 = f32[8]{{0}} all-to-all(%x), '
                f'metadata={{op_name="jit(prog)/jit(main)/{scope}/'
                f'sharding_constraint"}}')
        assert classify_collective_scope(line) == scope


def test_arm_layout_table():
    assert set(ARM_LAYOUT) == {
        "replicated", "zero3", "unified", "bucketed"}
    assert ARM_LAYOUT["replicated"] == "model"
    assert ARM_LAYOUT["unified"] == "model"
    assert ARM_LAYOUT["bucketed"] == "bucket"


def test_arm_name_resolution(topo):
    assert topo["s_r"].arm == "replicated"
    assert topo["s_z"].arm == "zero3"
    assert topo["s_b"].arm == "bucketed"
    for s in topo["s_r"], topo["s_z"], topo["s_b"]:
        assert topology_of(s).arm == s.arm


def test_describe_topology(topo):
    d = describe_topology(topology_of(topo["s_z"]))
    assert d["arm"] == "zero3" and d["dp"] == 8
    assert d["mesh"] == {"data": 2, "fsdp": 4}
    json.dumps(d)  # must be a committable record


# ---------------- in-memory reshard: bitwise + census ----------------

def test_roundtrip_mesh_and_arm_bitwise(topo):
    """rep@dp8 -> zero3@2x4 -> rep@dp8: bitwise round-trip, every group
    one jitted program, every census clean, and the gather-back
    direction's collectives attributed to their reshard scopes."""
    src = topology_of(topo["s_r"])
    dst = topology_of(topo["s_z"])
    assert not moments_convert_needed(src, dst)  # model layout both ends

    st_z, rep = reshard_state(topo["state1"], src, dst)
    assert rep["census_ok"] and rep["same_devices"]
    assert set(rep["groups"]) == set(RESHARD_SCOPES)
    for scope, row in rep["groups"].items():
        assert row["mode"] == "jit"
        assert row["census"]["unattributed"] == 0
        assert set(row["census"]["by_scope"]) <= {scope}
    # placement actually changed: a zero3 leaf is sharded over ZERO3_AXES
    shardings = jtu.tree_flatten(
        topo["s_z"].state_shardings.params["student"])[0]
    assert any(any(p is not None for p in s.spec) for s in shardings)

    back, rep2 = reshard_state(st_z, dst, src)
    assert rep2["census_ok"]
    # zero3 -> replicated re-materializes shards: at least one group
    # really moved data through an attributed collective
    moved = [r for r in rep2["groups"].values()
             if r["census"]["by_scope"]]
    assert moved, rep2["groups"]
    assert_bitwise(topo["state1"], back, "mesh+arm roundtrip")


def test_arm_conversion_bucketed_roundtrip(topo):
    """replicated (model moments) -> bucketed (bucket-dict moments):
    the layout conversion rides INSIDE the scoped programs, the mu tree
    comes out keyed by the plan's buckets, and the round-trip is
    bitwise."""
    src = topology_of(topo["s_r"])
    dst = topology_of(topo["s_b"])
    assert moments_convert_needed(src, dst)

    st_b, rep = reshard_state(topo["state1"], src, dst)
    assert rep["census_ok"]
    mu = st_b.opt_state.adam.mu
    assert sorted(dict(mu)) == sorted(dst.bucket_plan.names)
    back, rep2 = reshard_state(st_b, dst, src)
    assert rep2["census_ok"]
    assert_bitwise(topo["state1"], back, "bucketed roundtrip")


def test_in_memory_matches_disk_and_resume_determinism(
        topo, tmp_path, eight_devices):
    """The tentpole interchange pin: the in-memory reshard of a live
    state equals the disk round-trip (save at rep@dp8, cross-arm
    restore at zero3@2x4) BITWISE — and one train step from either
    resumed state is bitwise-identical, so the two resume paths are
    interchangeable mid-run."""
    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.train import put_batch

    src = topology_of(topo["s_r"])
    dst = topology_of(topo["s_z"])
    mem_state, rep = reshard_state(topo["state1"], src, dst)
    assert rep["census_ok"]

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ck.save(1, topo["state1"], topology=describe_topology(src))
    ck.wait_until_finished()
    disk_state = ck.restore(topo["s_z"].state, 1)
    assert_bitwise(mem_state, disk_state, "memory vs disk reshard")

    side = ck.saved_topology()
    assert side["arm"] == "replicated" and side["step"] == 1

    d_z = put_batch(topo["batch"], topo["s_z"].batch_shardings)
    st_m, m_m = topo["s_z"].step_fn(mem_state, d_z,
                                    topo["s_z"].scalars(1),
                                    jax.random.key(0))
    st_d, m_d = topo["s_z"].step_fn(disk_state, d_z,
                                    topo["s_z"].scalars(1),
                                    jax.random.key(0))
    assert float(m_m["total_loss"]) == float(m_d["total_loss"])
    assert np.isfinite(float(m_m["total_loss"]))
    assert_bitwise(st_m.params, st_d.params, "resume determinism")


def test_true_resize_transfer_path(topo, eight_devices):
    """dp=8 -> dp=4 on HALF the devices: no single program spans two
    device sets, so every group ships via the staged device_put path —
    still in memory, values bitwise, placement on the 4-device mesh."""
    s_4, _ = _setup(["parallel.data=4", "parallel.zero3=false",
                     "optim.bucketed_collectives=false"],
                    devices=eight_devices[:4], init_state=False)
    src = topology_of(topo["s_r"])
    dst = topology_of(s_4)
    assert src.device_ids() != dst.device_ids()

    st_4, rep = reshard_state(topo["state1"], src, dst)
    assert not rep["same_devices"]
    for row in rep["groups"].values():
        assert row["mode"] == "transfer"
    assert_bitwise(topo["state1"].params, st_4.params, "resize values")
    got = {d.id for d in
           jax.tree.leaves(st_4.params)[0].sharding.mesh.devices.flat}
    assert got == {d.id for d in eight_devices[:4]}


# ---------------- cross-topology checkpoint matrix ----------------

@pytest.mark.parametrize("cell_name,cell_over", [
    ("zero3@dp8", Z8),
    ("replicated@2x4", REP24),
    ("unified@2x4", U24),
])
def test_checkpoint_matrix_save_anywhere_restore_anywhere(
        topo, tmp_path, eight_devices, cell_name, cell_over):
    """A state carried to {zero3, replicated, unified} x {dp8, 2x4}
    cells by the in-memory engine, SAVED there, then restored at a
    DIFFERENT (arm, mesh) — both back at rep@dp8 and across to
    zero3@2x4 — bitwise against the original. With rep@dp8 -> zero3@2x4
    covered by the interchange test above, every matrix row saves and
    restores across topologies."""
    from dinov3_tpu.checkpoint import Checkpointer

    s_c, _ = _setup(cell_over, devices=eight_devices, init_state=False)
    src = topology_of(topo["s_r"])
    cell = topology_of(s_c)
    st_c, rep = reshard_state(topo["state1"], src, cell)
    assert rep["census_ok"], (cell_name, rep)

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False,
                      bucket_plan=getattr(s_c, "bucket_plan", None))
    ck.save(1, st_c, topology=describe_topology(cell))
    ck.wait_until_finished()
    assert ck.saved_topology()["arm"] == cell.arm

    back_r = ck.restore(topo["s_r"].state, 1)
    assert_bitwise(topo["state1"], back_r,
                   f"{cell_name} -> replicated@dp8")
    back_z = ck.restore(topo["s_z"].state, 1)
    assert_bitwise(topo["state1"].params, back_z.params,
                   f"{cell_name} -> zero3@2x4 params")
    assert_bitwise(topo["state1"].opt_state, back_z.opt_state,
                   f"{cell_name} -> zero3@2x4 moments")
    ck.close()


# ---------------- elastic_resume policy routing ----------------

def test_elastic_resume_policies(topo, tmp_path):
    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.train import elastic_resume

    src = topology_of(topo["s_r"])
    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ck.save(1, topo["state1"], topology=describe_topology(src))
    ck.wait_until_finished()

    # auto + live state whose mesh is reachable -> memory path
    st, info = elastic_resume(
        topo["s_z"], ck, live_state=topo["state1"], live_topology=src,
        policy="auto")
    assert info["path"] == "memory"
    assert info["report"]["census_ok"]
    assert_bitwise(topo["state1"].params, st.params, "memory resume")

    # no live state (a real preemption) -> disk path
    st_d, info_d = elastic_resume(topo["s_z"], ck, policy="auto")
    assert info_d["path"] == "disk"
    assert_bitwise(st.params, st_d.params, "disk resume")

    # forced disk ignores the live state
    _, info_f = elastic_resume(
        topo["s_z"], ck, live_state=topo["state1"], live_topology=src,
        policy="disk")
    assert info_f["path"] == "disk"

    with pytest.raises(ValueError, match="live state"):
        elastic_resume(topo["s_z"], ck, policy="memory")
    with pytest.raises(ValueError, match="policy"):
        elastic_resume(topo["s_z"], ck, policy="sideways")
    ck.close()


# ---------------- atomic finalization ----------------

def _abstract_like(state):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype), state)


def test_local_backend_truncated_save_not_latest(topo, tmp_path):
    """Local-npz backend: a mid-flight save killed after the payload
    started but before finalization (no marker / torn npz) must be
    invisible to latest_step — resume picks the previous step."""
    from dinov3_tpu.checkpoint import Checkpointer

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ck._local, ck.manager = True, None  # force the npz backend
    ck._local_save(1, topo["state1"])
    ck._local_save(2, topo["state1"])
    assert ck.latest_step() == 2

    # simulate the kill: step 3's payload exists but truncated, marker
    # never written (the finalize order guarantees this state)
    d3 = tmp_path / "ck" / "3"
    os.makedirs(d3)
    with open(tmp_path / "ck" / "2" / "state.npz", "rb") as f:
        blob = f.read()
    with open(d3 / "state.npz", "wb") as f:
        f.write(blob[: len(blob) // 2])
    assert ck.latest_step() == 2

    # a save killed mid-payload (tmp dir never renamed) is invisible too
    os.makedirs(tmp_path / "ck" / "tmp.4")
    assert ck.latest_step() == 2

    # ...and the announced step actually restores
    restored = ck._local_restore(topo["state1"], 2)
    assert_bitwise(topo["state1"], restored, "restore at previous step")

    # a finalized dir whose marker was lost is equally unreadable:
    # marker-gated discovery, not mtime heuristics
    os.remove(tmp_path / "ck" / "2" / ck.FINALIZED)
    assert ck.latest_step() == 1


def test_orbax_backend_truncated_save_not_latest(topo, tmp_path):
    """Orbax backend: a digit-named step dir that lost its item payload
    (truncated transfer / kill during finalize) fails the structural
    readability probe, so latest_step falls back to the previous
    restorable step."""
    import shutil

    from dinov3_tpu.checkpoint import Checkpointer

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ck.save(1, topo["state1"])
    ck.save(2, topo["state1"])
    ck.wait_until_finished()
    assert ck.latest_step() == 2

    item = tmp_path / "ck" / "2" / "state"
    assert item.is_dir()
    shutil.rmtree(item)  # the payload vanished mid-flight
    assert ck.latest_step() == 1

    restored = ck.restore(topo["s_r"].state)  # step=None -> discovery
    assert int(restored.step) == int(topo["state1"].step)
    assert_bitwise(topo["state1"], restored, "restore previous step")
    ck.close()


def test_reshard_report_padding_warnings(topo, eight_devices):
    """A transition into the bucketed arm, whose members are flat and
    padded to a multiple of dp, records the re-padding guardrail
    outcome (ISSUE 19 satellite: captured into bench records like the
    PR-9 bucket guardrail). vit_test leaves divide dp=8 cleanly, so the
    list is present and empty here."""
    assert topo["s_b"].arm == "bucketed"
    _, rep = reshard_state(
        topo["state1"], topology_of(topo["s_r"]),
        topology_of(topo["s_b"]))
    assert rep["padding_warnings"] == []
    assert rep["census_ok"]
