"""Gram-teacher refresh cadence + params-only (hrft) checkpoint restore."""

import jax
import jax.numpy as jnp
import numpy as np

from dinov3_tpu.configs import apply_dot_overrides, get_default_config
from dinov3_tpu.data import make_synthetic_batch
from dinov3_tpu.train.gram_refresh import (
    gram_updates_before,
    refresh_gram,
    should_refresh_gram,
)

SMOL = [
    "student.arch=vit_test", "student.patch_size=4",
    "student.drop_path_rate=0.0",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
    "dino.head_bottleneck_dim=16",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
    "ibot.head_bottleneck_dim=16",
    "train.OFFICIAL_EPOCH_LENGTH=4", "optim.epochs=4",
    "optim.scaling_rule=none",
]


def _gram_cfg(extra=()):
    cfg = get_default_config()
    apply_dot_overrides(cfg, SMOL + [
        "gram.use_loss=true", "gram.ema_teacher=false",
        "gram.rep_update=true", "gram.update_frequency=2",
        "gram.it_first_update=2", "gram.max_updates=2",
        "crops.gram_teacher_crops_size=16",
    ] + list(extra))
    return cfg


def test_refresh_cadence():
    cfg = _gram_cfg()
    # first refresh after finishing iteration 1 (it+1 == 2 == first_update)
    assert not should_refresh_gram(cfg, 0, 0)
    assert should_refresh_gram(cfg, 1, 0)
    assert should_refresh_gram(cfg, 3, 1)
    assert not should_refresh_gram(cfg, 5, 2)  # max_updates reached
    assert gram_updates_before(cfg, 0) == 0
    assert gram_updates_before(cfg, 3) == 1
    assert gram_updates_before(cfg, 100) == 2  # clamped by max_updates


def test_refresh_copies_teacher_into_gram():
    cfg = _gram_cfg()
    from dinov3_tpu.train import build_train_setup, put_batch

    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, 4, seed=0).items()}
    setup = build_train_setup(cfg, batch)
    assert "gram" in setup.state.params
    state, _ = setup.step_fn(
        setup.state, put_batch(batch, setup.batch_shardings),
        setup.scalars(0), jax.random.key(0),
    )
    # after a step the teacher EMA moved away from the gram init
    t_leaf = jax.tree.leaves(state.params["teacher"]["backbone"])[1]
    g_leaf = jax.tree.leaves(state.params["gram"]["backbone"])[1]
    state2 = refresh_gram(state)
    g2 = jax.tree.leaves(state2.params["gram"]["backbone"])[1]
    assert np.allclose(np.asarray(g2), np.asarray(t_leaf))
    # and the copy is a new buffer, not an alias
    assert state2.params["gram"]["backbone"] is not \
        state2.params["teacher"]["backbone"]


def test_gram_stage_on_dp_seq_mesh(monkeypatch):
    """Gram-anchored step dryrun on a dp x seq mesh: the ring path
    engages (a ring floor of 1 makes even vit_test's 17-token passes
    ring), the gram loss lands finite in the metrics, and the refresh
    cadence still fires — the ISSUE-15 high-res stage in miniature."""
    from dinov3_tpu.ops import attention
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.train import build_train_setup, put_batch

    monkeypatch.setattr(attention, "RING_MIN_SEQ", 1)
    cfg = _gram_cfg([
        "parallel.data=4", "parallel.seq=2", "parallel.zero3=false",
    ])
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, 4, seed=0).items()}
    try:
        setup = build_train_setup(cfg, batch)
        assert setup.mesh.shape["seq"] == 2
        assert "gram" in setup.state.params
        # ring engagement itself is pinned by the HLO-census tests
        # (test_ring_attention.py) and the committed COST_HIRES_r19.json;
        # here the point is the gram stage surviving the dp x seq mesh
        state, metrics = setup.step_fn(
            setup.state, put_batch(batch, setup.batch_shardings),
            setup.scalars(0), jax.random.key(0),
        )
        assert jnp.isfinite(metrics["total_loss"])
        assert jnp.isfinite(metrics["gram_loss"])
        # cadence unchanged by the mesh: first refresh after iteration 1
        assert not should_refresh_gram(cfg, 0, 0)
        assert should_refresh_gram(cfg, 1, 0)
        state2 = refresh_gram(state)
        g2 = jax.tree.leaves(state2.params["gram"]["backbone"])[1]
        t = jax.tree.leaves(state2.params["teacher"]["backbone"])[1]
        assert np.allclose(np.asarray(g2), np.asarray(t))
    finally:
        set_current_mesh(None)


def test_hrft_params_only_restore(tmp_path):
    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.train import build_train_setup, put_batch

    cfg = get_default_config()
    apply_dot_overrides(cfg, SMOL)
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, 4, seed=0).items()}
    setup = build_train_setup(cfg, batch)
    state, _ = setup.step_fn(
        setup.state, put_batch(batch, setup.batch_shardings),
        setup.scalars(0), jax.random.key(0),
    )
    ckpt = Checkpointer(str(tmp_path / "ckpt"), async_save=False)
    ckpt.save(1, state)
    ckpt.close()

    # fresh run restores params only: step resets, params match
    setup2 = build_train_setup(cfg, batch)
    ckpt2 = Checkpointer(str(tmp_path / "ckpt"))
    restored = ckpt2.restore_params_only(setup2.state)
    ckpt2.close()
    assert int(restored.step) == 0
    want = jax.tree.leaves(state.params["student"])
    got = jax.tree.leaves(restored.params["student"])
    for w, g in zip(want, got):
        assert np.allclose(np.asarray(w), np.asarray(g))


def test_load_gram_teacher_from_checkpoint(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup, put_batch
    from dinov3_tpu.train.gram_refresh import load_gram_teacher

    smol = [
        "student.arch=vit_test", "student.patch_size=4",
        "student.drop_path_rate=0.0",
        "crops.global_crops_size=16", "crops.local_crops_size=8",
        "crops.local_crops_number=2",
        "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
        "dino.head_bottleneck_dim=16",
        "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
        "ibot.head_bottleneck_dim=16",
        "train.OFFICIAL_EPOCH_LENGTH=4", "optim.epochs=4",
        "optim.scaling_rule=none",
    ]
    # teacher pretraining run -> checkpoint
    cfg = get_default_config()
    apply_dot_overrides(cfg, smol)
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, 4, seed=0).items()}
    setup = build_train_setup(cfg, batch)
    dbatch = put_batch(batch, setup.batch_shardings)
    state, _ = setup.step_fn(setup.state, dbatch, setup.scalars(0),
                             jax.random.key(0))
    ckpt = Checkpointer(str(tmp_path / "ckpt"), max_to_keep=1)
    ckpt.save(1, state)
    ckpt.wait_until_finished()
    ckpt.close()
    teacher_leaf = np.asarray(
        jax.tree.leaves(state.params["teacher"]["backbone"])[0])

    # gram-anchor run: gram backbone loads the prior EMA teacher
    cfg2 = get_default_config()
    apply_dot_overrides(cfg2, smol + [
        "gram.use_loss=true", f"gram.ckpt={tmp_path / 'ckpt'}",
        "gram.it_load_ema_teacher=-1",
    ])
    batch2 = {k: jnp.asarray(v) for k, v in
              make_synthetic_batch(cfg2, 4, seed=1).items()}
    setup2 = build_train_setup(cfg2, batch2)
    assert "gram" in setup2.state.params
    state2 = load_gram_teacher(cfg2, setup2.state, setup2.state_shardings)
    got = np.asarray(jax.tree.leaves(state2.params["gram"]["backbone"])[0])
    np.testing.assert_allclose(got, teacher_leaf)
