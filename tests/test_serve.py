"""Continuous-packing serve engine (serve/): batcher, packed forward,
weights, guardrails, and the committed SERVE_r14.json acceptance.

Pins:

- batcher mechanics: FFD row assignment (budget + extraction-slot
  caps, leftover requests queued in arrival order), flush policy
  (budget full / oldest-waited deadline), plane assembly (segment ids,
  prefix indices, CLS landing sites, patchify/coords parity with the
  ops/ twins), oversize admission rejection;
- feature equivalence: the ONE ahead-of-time-compiled packed forward
  reproduces the per-image oracle's CLS + pooled-patch features on
  ragged traffic, while its compile count stays pinned at 1 (the
  oracle's grows with shape diversity — the pathology under test);
- serving weights: checkpoints from all THREE opt-state layouts
  (replicated / bucketed / zero3) resolve to
  ONE bitwise-identical bf16 serving tree, and the bf16 cast is
  deterministic + idempotent;
- the evals/features.py ragged-tail fix: a partial final batch runs
  through the same compiled program (compile count 1), padded rows
  sliced off, and the serve-engine extraction path returns the same
  features;
- the warn_serve_pad_waste guardrail (axis-labelled fire/silent) and
  the serve copy-census category;
- the committed SERVE_r14.json: packed >= 2x the rectangular oracle's
  sustained img/s on the mixed ragged mix at equal features, p50/p99
  for every mix, exactly 1 packed compile, zero unattributed
  collectives.
"""

import json
import os
import warnings

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from dinov3_tpu.configs import apply_dot_overrides, get_default_config
from dinov3_tpu.serve import (
    ContinuousBatcher,
    OracleServeEngine,
    PackedServeEngine,
    ServeLayout,
    ServeRequest,
    cast_serving_tree,
    load_serving_model,
    patch_coords_np,
    patchify,
    serve_layout_from_cfg,
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

SERVE_SMOL = SMOL + [
    "serve.min_px=8", "serve.max_px=24", "serve.rows=3",
    "serve.row_tokens=40", "serve.max_segments_per_row=6",
]


def _layout(**kw) -> ServeLayout:
    base = dict(rows=2, row_tokens=20, n_prefix=1, max_segments_per_row=3,
                patch_size=4, min_px=8, max_px=16)
    base.update(kw)
    return ServeLayout(**base)


def _req(rid, h, w, arrival=0.0, rng=None):
    img = (rng.standard_normal((h, w, 3)).astype(np.float32)
           if rng is not None else np.zeros((h, w, 3), np.float32))
    return ServeRequest(request_id=rid, image=img, arrival_s=arrival)


@pytest.fixture(scope="module")
def tiny_serve():
    """One vit_test serving model + bf16 params + layout for the file."""
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


# ---------------- batcher unit tests ----------------

def test_layout_seq_len_budget_and_oversize():
    L = _layout()
    assert L.token_budget == 40
    assert L.seq_len(8, 8) == 1 + 4          # 2x2 patches
    assert L.seq_len(16, 12) == 1 + 4 * 3
    with pytest.raises(ValueError):
        L.seq_len(10, 8)                      # not patch-divisible
    b = ContinuousBatcher(L)
    with pytest.raises(ValueError, match="row budget"):
        b.admit(_req(0, 24, 16))              # 25 tokens > row_tokens 20


def test_patchify_and_coords_match_ops_twins():
    rng = np.random.default_rng(0)
    img = rng.standard_normal((12, 8, 3)).astype(np.float32)
    pats = patchify(img, 4)
    assert pats.shape == (6, 4, 4, 3)
    # same patch order + inner layout as PatchEmbed's reshape
    ref = img.reshape(3, 4, 2, 4, 3).transpose(0, 2, 1, 3, 4)
    assert np.array_equal(pats, ref.reshape(6, 4, 4, 3))
    # bitwise f32 parity with ops/rope.patch_coords
    from dinov3_tpu.ops.rope import patch_coords

    for mode in ("separate", "max", "min"):
        want = np.asarray(patch_coords(3, 2, normalize=mode))
        assert np.array_equal(patch_coords_np(3, 2, mode), want), mode


def test_ffd_row_assignment_and_leftovers():
    # row_tokens 20: a 13-token and a 5-token share a row (18), the
    # second 13-token opens row 1, the trailing 5-token first-fits
    # back into row 0; the third 13-token doesn't fit anywhere and
    # stays queued (arrival order preserved)
    L = _layout()
    b = ContinuousBatcher(L)
    for rid, (h, w) in enumerate(
            [(16, 12), (8, 8), (16, 12), (8, 8), (16, 12)]):
        b.admit(_req(rid, h, w))
    plan = b.next_pack()
    by_id = {pl.request.request_id: pl for pl in plan.placements}
    assert sorted(by_id) == [0, 1, 2, 3]
    assert by_id[0].row == 0 and by_id[0].offset == 0
    assert by_id[2].row == 1                  # first-fit: row 0 full at 13+13
    assert by_id[1].row == 0 and by_id[1].offset == 13
    assert by_id[3].row == 1 and by_id[3].offset == 13
    assert plan.tokens_used == 13 + 13 + 5 + 5
    assert plan.pad_waste == pytest.approx(1 - 36 / 40)
    # leftover 13-token request ships in the next pack
    assert b.queue_len == 1
    plan2 = b.next_pack()
    assert [pl.request.request_id for pl in plan2.placements] == [4]
    assert b.next_pack() is None


def test_segment_slot_cap():
    # 5-token images: 4 fit a 20-token row, but max_segments_per_row=3
    # caps occupancy at 3 per row
    L = _layout()
    b = ContinuousBatcher(L)
    for rid in range(8):
        b.admit(_req(rid, 8, 8))
    plan = b.next_pack()
    rows = [pl.row for pl in plan.placements]
    assert len(plan.placements) == 6
    assert rows.count(0) == 3 and rows.count(1) == 3
    assert b.queue_len == 2


def test_flush_policy_budget_and_deadline():
    L = _layout()
    b = ContinuousBatcher(L, flush_ms=10.0)
    assert not b.should_flush(0.0)            # empty queue never flushes
    b.admit(_req(0, 8, 8, arrival=1.0))
    assert not b.should_flush(1.005)          # 5ms < deadline, budget free
    assert b.should_flush(1.010)              # oldest waited 10ms
    assert b.flush_deadline() == pytest.approx(1.010)
    for rid in range(1, 8):
        b.admit(_req(rid, 8, 8, arrival=1.0))
    assert b.queued_tokens == 40
    assert b.should_flush(1.0)                # budget full, no wait needed


def test_plane_assembly():
    rng = np.random.default_rng(1)
    L = _layout()
    b = ContinuousBatcher(L)
    b.admit(_req(0, 16, 12, rng=rng))         # 13 tokens, row 0
    b.admit(_req(1, 8, 8, rng=rng))           # 5 tokens, row 0 @ 13
    plan = b.next_pack()
    pl0, pl1 = sorted(plan.placements, key=lambda p: p.request.request_id)
    seg, pidx = plan.planes["seg"], plan.planes["prefix_idx"]
    assert list(seg[0, :18]) == [0] * 13 + [1] * 5
    assert list(seg[0, 18:]) == [-1] * 2 and np.all(seg[1] == -1)
    assert pidx[0, 0] == 0 and pidx[0, 13] == 0   # CLS at each offset
    assert np.all(pidx[0, 1:13] == -1)
    assert plan.planes["cls_index"][0, 0] == 0
    assert plan.planes["cls_index"][0, 1] == 13
    assert np.array_equal(
        plan.planes["patches"][0, 1:13], patchify(pl0.request.image, 4))
    assert np.array_equal(
        plan.planes["coords"][0, 14:18], patch_coords_np(2, 2))
    # pad slots stay zeroed
    assert not plan.planes["patches"][0, 18:].any()
    assert not plan.planes["patches"][1].any()


# ---------------- packed forward vs oracle ----------------

def test_packed_features_match_oracle_single_compile(tiny_serve):
    """Ragged traffic through the packed engine: CLS + pooled features
    match the per-image oracle within bf16-compute tolerance, packed
    compile count stays 1 while the oracle's grows per shape."""
    cfg, model, params, layout = tiny_serve
    rng = np.random.default_rng(2)
    eng = PackedServeEngine(model, params, layout, warn=False)
    ora = OracleServeEngine(model, params, layout, mode="per_image")
    sizes = [(8, 8), (16, 16), (12, 8), (24, 16), (8, 12), (16, 24),
             (20, 20)]
    images = [rng.standard_normal((h, w, 3)).astype(np.float32)
              for h, w in sizes]
    for e in (eng, ora):
        for i, im in enumerate(images):
            e.submit(im, request_id=i)
    packed, oracle = [], []
    while eng.queue_len:
        packed.extend(eng.flush())
    oracle.extend(ora.flush())
    assert len(packed) == len(oracle) == len(images)
    by_id = {r.request_id: r for r in oracle}
    for r in packed:
        o = by_id[r.request_id]
        assert r.n_patches == o.n_patches
        np.testing.assert_allclose(
            r.cls_feature, o.cls_feature, atol=1e-5,
            err_msg=f"cls, request {r.request_id}")
        np.testing.assert_allclose(
            r.pooled_patch_feature, o.pooled_patch_feature, atol=1e-5,
            err_msg=f"pooled, request {r.request_id}")
    assert eng.compile_count == 1
    assert eng.packs_run >= 2                 # traffic spanned packs
    assert ora.compile_count == len(set(sizes))


def test_packed_census_serve_attribution(tiny_serve):
    """The one packed program's HLO: serve-scoped copies classified to
    the "serve" category, zero unattributed collectives."""
    from dinov3_tpu.utils import (
        classify_copy,
        hlo_collective_census,
        hlo_copy_census,
    )

    cfg, model, params, layout = tiny_serve
    eng = PackedServeEngine(model, params, layout, warn=False)
    hlo = eng.compiled_text()
    census = hlo_copy_census(hlo)
    assert hlo_collective_census(hlo)["unattributed"] == 0
    # the classifier routes every serve scope; only categories the
    # census knows appear
    assert classify_copy("  %x = f32[4]{0} copy(a), metadata={op_name="
                         "\"jit/serve_pack/reshape\"}") == "serve"
    assert classify_copy("  %x = f32[4]{0} copy(a), metadata={op_name="
                         "\"jit/serve_ring/dus\"}") == "serve"
    known = {"donation_async", "gather_pack", "update_shard", "telemetry",
             "zero3", "bucket", "serve", "rng", "small", "large"}
    assert set(census["by_category"]) <= known


def test_build_serve_engine_dispatch(tiny_serve):
    """continuous_packing=false routes to the configured oracle arm."""
    from dinov3_tpu.configs.config import continuous_packing_wished
    from dinov3_tpu.serve import build_serve_engine

    cfg, model, params, layout = tiny_serve
    ocfg = get_default_config()
    apply_dot_overrides(ocfg, SERVE_SMOL + [
        "serve.continuous_packing=false", "serve.oracle=per_image"])
    assert continuous_packing_wished(cfg)
    assert not continuous_packing_wished(ocfg)
    eng = build_serve_engine(ocfg, params=params, warn=False)
    assert isinstance(eng, OracleServeEngine) and eng.mode == "per_image"


# ---------------- serving weights: the three arms ----------------

def test_serving_tree_from_every_arm(tmp_path, eight_devices):
    """One training step per opt-state arm from the same init, one
    checkpoint each; load_serving_model resolves every one of them to
    the SAME bf16 serving tree bitwise (the params tree is model-shaped
    in every arm — only the adam moments' layout differs)."""
    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup, put_batch

    arms = {
        "replicated": ["parallel.zero3=false",
                       "optim.bucketed_collectives=false"],
        "bucketed": ["parallel.zero3=false",
                     "optim.bucketed_collectives=true"],
        "zero3": ["parallel.zero3=true"],
    }
    trees = {}
    for name, extra in arms.items():
        cfg = get_default_config()
        apply_dot_overrides(cfg, SMOL + extra)
        batch = {k: jnp.asarray(v) for k, v in
                 make_synthetic_batch(cfg, 16, seed=0).items()}
        setup = build_train_setup(cfg, batch, devices=eight_devices)
        state, _ = setup.step_fn(
            setup.state, put_batch(batch, setup.batch_shardings),
            setup.scalars(0), jax.random.key(0))
        ck = Checkpointer(str(tmp_path / name), async_save=False,
                          bucket_plan=getattr(setup, "bucket_plan", None))
        ck.save(1, state)
        ck.wait_until_finished()
        ck.close()

        ecfg = get_default_config()
        apply_dot_overrides(ecfg, SMOL)
        _, tree = load_serving_model(ecfg, str(tmp_path / name))
        trees[name] = tree

    flat = {n: jtu.tree_flatten_with_path(t)[0] for n, t in trees.items()}
    ref = flat["replicated"]
    for name in ("bucketed", "zero3"):
        assert len(flat[name]) == len(ref)
        for (path, a), (_, b) in zip(ref, flat[name]):
            assert a.dtype == b.dtype
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                f"replicated vs {name}: {jtu.keystr(path)}")
    floats = [l for _, l in ref if jnp.issubdtype(l.dtype, jnp.floating)]
    assert floats and all(l.dtype == jnp.bfloat16 for l in floats)

    # int8 quantization is a pure function of the serving tree, so the
    # three arms must also quantize identically — bitwise q AND scale
    # (the fleet's weights fingerprint keys the feature cache on this)
    from dinov3_tpu.serve import (
        QuantLeaf,
        quantize_serving_tree,
        weights_fingerprint,
    )

    qtrees = {n: quantize_serving_tree(t) for n, t in trees.items()}
    qflat = {n: jtu.tree_flatten_with_path(
        t, is_leaf=lambda x: isinstance(x, QuantLeaf))[0]
        for n, t in qtrees.items()}
    qref = qflat["replicated"]
    assert any(isinstance(l, QuantLeaf) for _, l in qref)
    for name in ("bucketed", "zero3"):
        for (path, a), (_, b) in zip(qref, qflat[name]):
            if isinstance(a, QuantLeaf):
                assert np.array_equal(np.asarray(a.q), np.asarray(b.q)), (
                    f"replicated vs {name}: {jtu.keystr(path)} q")
                assert np.array_equal(np.asarray(a.scale),
                                      np.asarray(b.scale)), (
                    f"replicated vs {name}: {jtu.keystr(path)} scale")
    fps = {weights_fingerprint(t) for t in qtrees.values()}
    assert len(fps) == 1


def test_cast_serving_tree_deterministic(tiny_serve):
    cfg, model, params, _ = tiny_serve
    # params already bf16: idempotent bitwise
    again = cast_serving_tree(params)
    for (p, a), (_, b) in zip(jtu.tree_flatten_with_path(params)[0],
                              jtu.tree_flatten_with_path(again)[0]):
        assert np.array_equal(np.asarray(a), np.asarray(b)), jtu.keystr(p)
    # two independent casts of the same f32 leaf agree bitwise, ints
    # pass through untouched
    leaf = np.float32([1.0000153, -2.5000305, 3.141592653])
    tree = {"w": jnp.asarray(leaf), "n": jnp.asarray([3], jnp.int32)}
    c1, c2 = cast_serving_tree(tree), cast_serving_tree(tree)
    assert c1["w"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(c1["w"]), np.asarray(c2["w"]))
    assert c1["n"].dtype == jnp.int32
    assert np.array_equal(np.asarray(c1["n"]), np.asarray(tree["n"]))


# ---------------- evals/features.py: ragged tail + serve path ----------------

def test_features_ragged_tail_single_compile(tiny_serve):
    from dinov3_tpu.evals.features import extract_features, make_feature_fn

    cfg, model, params, _ = tiny_serve
    rng = np.random.default_rng(3)
    full = rng.standard_normal((10, 16, 16, 3)).astype(np.float32)
    labels = np.arange(10)

    def batches(bs):
        for i in range(0, 10, bs):
            yield {"image": full[i:i + bs], "label": labels[i:i + bs]}

    feat = make_feature_fn(model, params)
    # 4 + 4 + 2: the ragged tail pads to 4 rows, same program
    feats, labs = extract_features(model, params, batches(4), feat=feat)
    assert feats.shape == (10, model.embed_dim)
    assert np.array_equal(labs, labels)
    assert feat._cache_size() == 1   # the 2-row tail reused the [4,...] program
    # values match the one-shot full batch (pad rows sliced; rows are
    # independent through the network up to vectorization reassociation)
    want = np.asarray(feat(jnp.asarray(full)))
    np.testing.assert_allclose(feats, want, atol=1e-5)


def test_extract_features_serve_rides_engine(tiny_serve):
    from dinov3_tpu.evals.features import extract_features_serve

    cfg, model, params, layout = tiny_serve
    rng = np.random.default_rng(4)
    sizes = [(8, 8), (16, 16), (12, 16), (24, 24)]
    images = [rng.standard_normal((h, w, 3)).astype(np.float32)
              for h, w in sizes]
    eng = PackedServeEngine(model, params, layout, warn=False)
    feats, labs = extract_features_serve(eng, iter(images), iter([7, 8, 9, 10]))
    assert feats.shape == (4, model.embed_dim)
    assert list(labs) == [7, 8, 9, 10]
    assert eng.compile_count == 1
    # submission order preserved: request i is image i
    ora = OracleServeEngine(model, params, layout, mode="per_image")
    for i, im in enumerate(images):
        ora.submit(im, request_id=i)
    want = {r.request_id: r.cls_feature for r in ora.flush()}
    for i in range(4):
        np.testing.assert_allclose(feats[i], want[i], atol=1e-5)


# ---------------- guardrail ----------------

def test_warn_serve_pad_waste_fire_and_silent():
    from dinov3_tpu.configs.config import (
        serve_pad_waste_floor,
        warn_serve_pad_waste,
    )

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert warn_serve_pad_waste(0.10) is None     # below threshold
    with pytest.warns(UserWarning, match=r"serve pad-waste axis \[mix-x\]"):
        msg = warn_serve_pad_waste(0.40, axis="mix-x")
    assert "40.0%" in msg and "serve.row_tokens" in msg

    # floor: row_tokens 40, patch 4, prefix 1: 16px images (17 tokens)
    # fit twice wasting 6/40; 12px (10 tokens) fit 4x wasting 0
    floor = serve_pad_waste_floor(40, 4, 1, 8, 16)
    assert floor["px"] == 16 and floor["seq_len"] == 17
    assert floor["waste"] == pytest.approx(6 / 40)
    assert 0.0 < floor["mean_waste"] < floor["waste"]


def test_packed_engine_build_warns_on_wasteful_envelope(tiny_serve):
    cfg, model, params, _ = tiny_serve
    # 8px-only traffic (5 tokens) in an 8-token row: 37.5% of every
    # row is structurally padding
    bad = _layout(rows=1, row_tokens=8, n_prefix=1, max_segments_per_row=2,
                  patch_size=4, min_px=8, max_px=8)
    with pytest.warns(UserWarning, match="serve pad-waste axis"):
        PackedServeEngine(model, params, bad, warn=True)


# ---------------- committed artifact ----------------

def test_serve_r14_acceptance():
    """The committed SERVE_r14.json (vit_small, CPU): packed >= 2x the
    rectangular oracle's sustained img/s on the mixed ragged mix at
    equal features, p50/p99 present for all three mixes, exactly one
    packed compile across the full replay, zero unattributed
    collectives in the packed program's census."""
    rec = json.loads(open(os.path.join(REPO, "SERVE_r14.json")).read())
    assert not rec["smoke"]
    assert rec["packed_compile_count"] == 1
    assert rec["packed_census"]["collective_unattributed"] == 0
    mixes = rec["mixes"]
    assert set(mixes) == {"uniform_224", "mixed_ragged", "heavy_tail"}
    for name, mix in mixes.items():
        for arm in ("packed", "oracle_rectangular", "oracle_per_image"):
            lat = mix[arm]["latency"]
            assert lat["p50_ms"] > 0 and lat["p99_ms"] >= lat["p50_ms"], (
                name, arm)
        assert mix["packed"]["compile_growth_during_measurement"] == 0
        assert mix["packed"]["serve"]["host_sync"]["fetches"] >= 1
    mr = mixes["mixed_ragged"]
    assert mr["speedup_vs_rectangular"] >= 2.0
    # equal features: bf16-compute reassociation tolerance on O(1)
    # layernormed outputs
    for arm in ("oracle_rectangular", "oracle_per_image"):
        agree = mr[f"features_vs_{arm}"]
        assert agree["cls_max_abs_diff"] <= 0.1
        assert agree["pooled_max_abs_diff"] <= 0.1
