"""ZeRO-3's cross-arm checkpoints (moved out of tests/test_zero3.py, the
last file the suite's workers are handed and so the one that ended the run
alone: names and bodies as they were, the helpers imported from there):
zero3 -> replicated -> zero3 round-trips bitwise and resumes
deterministically; a checkpoint in the bucketed arm's per-leaf flat
on-disk layout restores into a zero3 run."""

import jax
import jax.tree_util as jtu
import numpy as np
from test_zero3 import _flat_params, _setup, assert_trees_bitwise


# ---------------- cross-arm checkpoints ----------------

def test_checkpoint_replicated_zero3_roundtrip(tmp_path, eight_devices):
    """zero3 -> replicated -> zero3: shapes never change (model layout
    both arms), values round-trip bitwise, and the resumed zero3 run is
    deterministic against the uninterrupted one."""
    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.train import put_batch

    s_z, batch = _setup(["parallel.zero3=true"], 16, eight_devices)
    s_r, _ = _setup(["parallel.zero3=false"], 16, eight_devices)
    d = put_batch(batch, s_z.batch_shardings)
    state1, _ = s_z.step_fn(s_z.state, d, s_z.scalars(0), jax.random.key(0))

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ck.save(1, state1)
    ck.wait_until_finished()

    rep_state = ck.restore(s_r.state, 1)
    assert_trees_bitwise(state1.params, rep_state.params,
                         "zero3 -> replicated params")
    ck.save(2, rep_state)
    ck.wait_until_finished()
    # the replicated arm RUNS from it (last use: the step donates it)
    s_rep2, m_rep = s_r.step_fn(rep_state, d, s_r.scalars(1),
                                jax.random.key(0))
    assert np.isfinite(float(m_rep["total_loss"]))

    back = ck.restore(s_z.state, 2)
    assert_trees_bitwise(state1.opt_state, back.opt_state,
                         "round-trip opt state")

    st_orig, m_orig = s_z.step_fn(state1, d, s_z.scalars(1),
                                  jax.random.key(0))
    st_back, m_back = s_z.step_fn(back, d, s_z.scalars(1),
                                  jax.random.key(0))
    assert float(m_orig["total_loss"]) == float(m_back["total_loss"])
    assert_trees_bitwise(st_orig.params, st_back.params,
                         "resume determinism", limit=32)


def test_checkpoint_flat_arm_to_zero3(tmp_path, eight_devices):
    """A bucketed-arm checkpoint (on disk: per-leaf flat padded
    moments) restores into a zero3 run: the moments come back
    model-shaped through the _adapt_opt_leaf flat->full path, bitwise
    equal to the unpadded flat values, and the zero3 step runs from
    them."""
    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.train import put_batch
    from dinov3_tpu.train.fused_update import unflatten_update_leaf

    s_flat, batch = _setup(["parallel.zero3=false",
                            "optim.bucketed_collectives=auto"], 16,
                           eight_devices)
    assert s_flat.arm == "bucketed"  # the dp-only default
    d = put_batch(batch, s_flat.batch_shardings)
    state1, _ = s_flat.step_fn(s_flat.state, d, s_flat.scalars(0),
                               jax.random.key(0))
    ck = Checkpointer(str(tmp_path / "ck"), async_save=False,
                      bucket_plan=s_flat.bucket_plan)
    ck.save(1, state1)
    ck.wait_until_finished()

    s_z, _ = _setup(["parallel.zero3=true"], 16, eight_devices)
    restored = Checkpointer(str(tmp_path / "ck"), async_save=False).restore(
        s_z.state, 1)
    for (path, flat), (_, full), (_, like) in zip(
        _flat_params(s_flat.bucket_plan.buckets_to_flat_tree(
            state1.opt_state.adam.mu)),
        _flat_params(restored.opt_state.adam.mu),
        _flat_params(s_z.state.params["student"]),
    ):
        want = np.asarray(unflatten_update_leaf(flat, like))
        assert np.array_equal(want, np.asarray(full)), jtu.keystr(path)
        assert full.shape == like.shape
    st, m = s_z.step_fn(restored, d, s_z.scalars(1), jax.random.key(0))
    assert np.isfinite(float(m["total_loss"]))
    assert int(st.step) == 2
