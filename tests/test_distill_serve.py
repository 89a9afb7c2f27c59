"""Serve-backed multi-student distillation (ROADMAP item 2): the packed
teacher engine's patch-feature plane, the precomputed-targets loss arm,
the content-addressed fan-out cache, and the one-forward-per-image
dedup across co-hosted student subgroups.

Pins:

- packed patch extraction: the ONE compiled packed forward's per-token
  features match the per-image oracle on ragged traffic (compile count
  stays 1), and the default CLS+pool path keeps a ZERO-width patch
  plane (same donated ring pytree, no patch HBM);
- the precomputed-targets arm of ``get_teacher_output`` is BITWISE
  equal to the in-step oracle when fed the oracle's own features —
  targets AND center state — because both arms share
  ``teacher_targets_from_features`` and the f32 batch planes
  round-trip the bf16 compute values exactly;
- cache fingerprint audit: int8 and bf16 serving trees of the same
  checkpoint never cross-serve a patch-plane entry, and a hit replays
  the SAME frozen buffers a miss stored;
- TeacherServer dedup: within-batch duplicates forward once, epoch
  replays hit the cache with bitwise-equal planes, and TWO co-hosted
  student subgroups sharing one teacher get ONE TeacherServer — one
  teacher evaluation per unique image, k students or not
  (COST_DISTILL_r22.json prices the same invariants on-chip).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from dinov3_tpu.configs import apply_dot_overrides, get_default_config
from dinov3_tpu.data import make_synthetic_batch
from dinov3_tpu.serve import (
    OracleServeEngine,
    PackedServeEngine,
    cast_serving_tree,
    load_serving_model,
    serve_layout_from_cfg,
)
from dinov3_tpu.serve.cache import FeatureCache, weights_fingerprint
from dinov3_tpu.train.distillation import (
    TeacherServer,
    teacher_feature_example,
)
from dinov3_tpu.train.multidistillation import (
    _SHARED_TEACHERS,
    shared_teacher_server,
)

SMOL = [
    "student.patch_size=4", "student.drop_path_rate=0.0",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
    "dino.head_bottleneck_dim=16",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
    "ibot.head_bottleneck_dim=16",
    "train.OFFICIAL_EPOCH_LENGTH=4", "optim.epochs=4",
    "optim.scaling_rule=none",
]

SERVE_SMOL = SMOL + [
    "student.arch=vit_test",
    "serve.min_px=8", "serve.max_px=24", "serve.rows=3",
    "serve.row_tokens=40", "serve.max_segments_per_row=6",
]


def _teacher_yaml(tmp_path, hidden=48):
    recipe = {
        "student": {"arch": "vit_test_big", "patch_size": 4,
                    "drop_path_rate": 0.0},
        "dino": {"head_n_prototypes": 64, "head_hidden_dim": hidden,
                 "head_bottleneck_dim": 16},
        "ibot": {"head_n_prototypes": 64, "head_hidden_dim": hidden,
                 "head_bottleneck_dim": 16},
        "crops": {"global_crops_size": 16, "local_crops_size": 8,
                  "local_crops_number": 2},
        "optim": {"scaling_rule": "none"},
    }
    path = tmp_path / "teacher.yaml"
    path.write_text(yaml.safe_dump(recipe))
    return str(path)


def _distill_cfg(tmp_path, source="in_step"):
    cfg = get_default_config()
    apply_dot_overrides(cfg, SMOL + [
        "student.arch=vit_test",
        "distillation.enabled=true",
        f"distillation.full_cfg_path={_teacher_yaml(tmp_path)}",
        f"distillation.teacher_source={source}",
    ])
    return cfg


@pytest.fixture(scope="module")
def tiny_serve():
    """One vit_test serving model + bf16 params + layout."""
    import flax.linen as nn

    from dinov3_tpu.models import build_backbone

    cfg = get_default_config()
    apply_dot_overrides(cfg, SERVE_SMOL)
    model = build_backbone(cfg, teacher=True)
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 16, 16, 3)))
    )["params"]
    params = cast_serving_tree(params)
    return cfg, model, params, serve_layout_from_cfg(cfg)


# ------------------- packed patch-feature extraction -------------------

def test_packed_patch_features_match_oracle_single_compile(tiny_serve):
    """Ragged traffic: packed per-token features match the per-image
    oracle's, CLS unchanged, ONE packed compile."""
    cfg, model, params, layout = tiny_serve
    rng = np.random.default_rng(2)
    eng = PackedServeEngine(model, params, layout, warn=False,
                            patch_features=True)
    ora = OracleServeEngine(model, params, layout, mode="per_image",
                            patch_features=True)
    sizes = [(16, 16), (8, 8), (24, 16), (8, 12), (16, 16)]
    images = [rng.standard_normal((h, w, 3)).astype(np.float32)
              for h, w in sizes]
    for e in (eng, ora):
        for i, im in enumerate(images):
            e.submit(im, request_id=i)
    packed = []
    while eng.queue_len:
        packed.extend(eng.flush())
    oracle = {r.request_id: r for r in ora.flush()}
    assert len(packed) == len(images)
    for r in packed:
        o = oracle[r.request_id]
        assert r.patch_tokens is not None
        assert r.patch_tokens.shape == (o.n_patches, model.embed_dim)
        np.testing.assert_allclose(
            r.patch_tokens, o.patch_tokens, atol=1e-5,
            err_msg=f"patch tokens, request {r.request_id}")
        np.testing.assert_allclose(
            r.cls_feature, o.cls_feature, atol=1e-5,
            err_msg=f"cls, request {r.request_id}")
    assert eng.compile_count == 1


def test_patch_plane_zero_width_when_off(tiny_serve):
    """The default CLS+pool engine allocates a ZERO-token patch plane —
    same donated ring pytree structure, no patch HBM — and its
    responses carry patch_tokens=None."""
    cfg, model, params, layout = tiny_serve
    eng = PackedServeEngine(model, params, layout, warn=False)
    assert eng._ring.patch.shape[2] == 0
    on = PackedServeEngine(model, params, layout, warn=False,
                           patch_features=True)
    assert on._ring.patch.shape[2] == layout.row_tokens
    # identical pytree STRUCTURE (donation contract) across both arms
    assert (jax.tree_util.tree_structure(eng._ring)
            == jax.tree_util.tree_structure(on._ring))
    eng.submit(np.zeros((8, 8, 3), np.float32), request_id=0)
    (r,) = eng.flush()
    assert r.patch_tokens is None


# ------------------- precomputed-targets loss arm -------------------

def test_precomputed_targets_bitwise_vs_in_step_oracle(tmp_path):
    """Feeding the oracle's own backbone features through the serve arm
    reproduces the in-step teacher targets AND center state bitwise:
    both arms share ``teacher_targets_from_features``, and f32 plane
    storage round-trips the bf16 compute values exactly."""
    from dinov3_tpu.train import build_train_setup

    cfg = _distill_cfg(tmp_path)
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, 4, seed=0).items()}
    setup = build_train_setup(cfg, batch)
    meta = setup.meta
    assert meta.teacher_source == "in_step"
    frozen = setup.state.params["teacher"]
    state0 = meta.init_state()
    temp = 0.05

    masked = meta.masked_rows(batch)
    oracle_out, oracle_state = meta.get_teacher_output(
        frozen, batch, temp, state0, masked=masked)

    cls, patches = meta.teacher_backbone_features(frozen, batch)
    sbatch = dict(batch)
    sbatch["teacher_cls"] = jnp.asarray(np.asarray(cls, np.float32))
    sbatch["teacher_patches"] = jnp.asarray(np.asarray(patches, np.float32))
    meta.teacher_source = "serve"
    try:
        serve_out, serve_state = meta.get_teacher_output(
            frozen, sbatch, temp, state0, masked=masked)
        # missing planes is a hard error, not a silent oracle fallback
        with pytest.raises(ValueError, match="teacher_cls"):
            meta.get_teacher_output(frozen, batch, temp, state0,
                                    masked=masked)
    finally:
        meta.teacher_source = "in_step"

    for name, a, b in (("targets", oracle_out, serve_out),
                       ("state", oracle_state, serve_state)):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y)), name


def test_teacher_feature_example_shapes(tmp_path):
    """The trace-batch planes match what TeacherServer.annotate emits:
    teacher embed dim x student-run patch grid."""
    cfg = _distill_cfg(tmp_path)
    ex = teacher_feature_example(cfg, 6)
    assert ex["teacher_cls"].shape == (6, 96)         # vit_test_big dim
    assert ex["teacher_patches"].shape == (6, 16, 96)  # (16/4)^2 tokens
    assert all(v.dtype == np.float32 for v in ex.values())


def test_setup_rejects_serve_source_without_planes(tmp_path):
    """teacher_source=serve with an example batch missing the planes
    fails at setup time, not at step-trace time."""
    from dinov3_tpu.train import build_train_setup

    cfg = _distill_cfg(tmp_path, source="serve")
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, 4, seed=0).items()}
    with pytest.raises(ValueError, match="teacher_cls"):
        build_train_setup(cfg, batch)


# ------------------- cache fingerprint audit -------------------

def test_patch_plane_cache_never_cross_serves_quant_trees(tiny_serve):
    """int8 and bf16 serving trees of the SAME checkpoint have distinct
    fingerprints; a patch-plane entry stored under one is a MISS under
    the other, and a hit replays the SAME frozen buffers."""
    from dinov3_tpu.serve.quant import quantize_serving_tree

    _, _, params, _ = tiny_serve
    f_bf16 = weights_fingerprint(params)
    f_int8 = weights_fingerprint(quantize_serving_tree(params))
    assert f_bf16 != f_int8

    rng = np.random.default_rng(5)
    img = rng.standard_normal((16, 16, 3)).astype(np.float32)
    cache = FeatureCache(capacity=4)
    patch = rng.standard_normal((16, 8)).astype(np.float32)
    cache.put(cache.key(img, f_bf16),
              (np.zeros(8, np.float32), np.zeros(8, np.float32), 16, patch))
    assert cache.get(cache.key(img, f_int8)) is None
    hit = cache.get(cache.key(img, f_bf16))
    assert hit is not None and len(hit) == 4
    # the hit IS the stored plane (bitwise by construction), frozen
    assert np.array_equal(hit[3], patch)
    assert not hit[3].flags.writeable


def test_bench_distill_summary_block():
    """bench.py's "distill" record block: arm/teacher_source/embed dim,
    the distill_fanout scope slice of the census, and any process-level
    TeacherServer counters."""
    import bench

    class _Meta:
        distillation = True
        teacher_source = "serve"
        teacher_embed_dim = 96

    class _Setup:
        meta = _Meta()

    _SHARED_TEACHERS.clear()
    census = {"by_scope": {"distill_fanout": {"ops": 2},
                           "zero3_stream": {"ops": 9}}}
    out = bench._distill_summary(_Setup(), census)
    assert out["arm"] is True
    assert out["teacher_source"] == "serve"
    assert out["teacher_embed_dim"] == 96
    assert out["collectives_by_scope"] == {"distill_fanout": {"ops": 2}}
    assert "teacher_servers" not in out
    # non-distilling bench: arm off, no teacher dim
    class _Plain:
        meta = None
    plain = bench._distill_summary(_Plain(), None)
    assert plain["arm"] is False and plain["teacher_embed_dim"] is None


# ------------------- TeacherServer fan-out dedup -------------------

@pytest.fixture(scope="module")
def teacher_server_env(tmp_path_factory):
    """One distillation cfg + frozen teacher params + its TeacherServer
    (compiled once for the module — engine builds are the slow part)."""
    import flax.linen as nn

    from dinov3_tpu.models import build_backbone
    from dinov3_tpu.train.distillation import resolve_distillation_cfg

    tmp = tmp_path_factory.mktemp("distill_serve")
    cfg = _distill_cfg(tmp, source="serve")
    teacher_cfg = resolve_distillation_cfg(cfg)
    tmodel = build_backbone(teacher_cfg, teacher=True)
    tparams = nn.meta.unbox(
        jax.jit(tmodel.init)(jax.random.key(1), jnp.zeros((1, 16, 16, 3)))
    )["params"]
    srv = TeacherServer(cfg, teacher_params=tparams, warn=False)
    return cfg, tparams, srv


def test_teacher_server_dedups_and_replays_bitwise(teacher_server_env):
    cfg, _, srv = teacher_server_env
    base_fwd = srv.teacher_forwards
    rng = np.random.default_rng(7)
    g = rng.standard_normal((4, 16, 16, 3)).astype(np.float32)
    ann = srv.annotate({"global_crops": g})
    assert ann["teacher_cls"].shape == (4, srv.engine.model.embed_dim)
    assert ann["teacher_patches"].shape[1] == srv.patch_grid ** 2
    assert srv.teacher_forwards - base_fwd == 4
    # epoch replay: zero new forwards, bitwise-equal planes
    ann2 = srv.annotate({"global_crops": g})
    assert srv.teacher_forwards - base_fwd == 4
    assert np.array_equal(ann["teacher_cls"], ann2["teacher_cls"])
    assert np.array_equal(ann["teacher_patches"], ann2["teacher_patches"])
    # within-batch duplicates forward once (fresh images, repeated 2x)
    fresh = rng.standard_normal((2, 16, 16, 3)).astype(np.float32)
    before = srv.teacher_forwards
    srv.annotate({"global_crops": np.concatenate([fresh, fresh], axis=0)})
    assert srv.teacher_forwards - before == 2
    # the compile pin survives all of it
    assert srv.engine.compile_count == 1
    s = srv.stats()
    assert s["teacher_forwards"] < s["requests"]


def test_two_subgroups_share_one_teacher_server(teacher_server_env,
                                                tmp_path):
    """The two-subgroup dryrun: two student configs distilling from the
    SAME teacher resolve to the SAME process-level TeacherServer, so k
    students pay ONE teacher evaluation per unique image."""
    cfg, tparams, _ = teacher_server_env
    _SHARED_TEACHERS.clear()
    try:
        a = shared_teacher_server(cfg, teacher_params=tparams, warn=False)
        # subgroup B: different student arch, same teacher
        cfg_b = get_default_config()
        apply_dot_overrides(cfg_b, SMOL + [
            "student.arch=vit_test_big",
            "dino.head_hidden_dim=48", "ibot.head_hidden_dim=48",
            "distillation.enabled=true",
            f"distillation.full_cfg_path={cfg.distillation.full_cfg_path}",
            "distillation.teacher_source=serve",
        ])
        b = shared_teacher_server(cfg_b, teacher_params=tparams, warn=False)
        assert a is b
        rng = np.random.default_rng(11)
        g = rng.standard_normal((3, 16, 16, 3)).astype(np.float32)
        base = a.teacher_forwards
        a.annotate({"global_crops": g})    # subgroup A's pass
        b.annotate({"global_crops": g})    # subgroup B: all cache hits
        assert a.teacher_forwards - base == 3
        assert a.engine.compile_count == 1
        # a DIFFERENT teacher (other weights) gets its own server
        other = jax.tree.map(lambda x: x + 1e-3, tparams)
        c = shared_teacher_server(cfg, teacher_params=other, warn=False)
        assert c is not a
    finally:
        _SHARED_TEACHERS.clear()
