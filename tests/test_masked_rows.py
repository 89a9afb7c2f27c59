"""Batch-wide compaction of the masked-token rows (SSLMetaArch.masked_rows).

The two iBOT heads, Sinkhorn / softmax centering and the iBOT loss run
over ``M_c`` compact rows — the sampler's own count of masked tokens
(data/masking.py ``masked_rows_bound``) — instead of the per-image
worst-case ``[2B, M_img]`` buffers. Pinned here:

- the bound is exact for the repo's sampler (property, many seeds), for
  several sampler calls a batch and for a microbatch of one;
- loss, target factors on valid rows and gradients equal the same
  ``losses/`` functions called on all ``2B * M_img`` rows, for both
  centerings, streaming and materialized;
- a mask source over the bound makes ``ibot_loss`` (and its gradient)
  non-finite and counts the overflow; the sampler's fill the buffer to
  ``n_valid / M_c`` with overflow 0;
- on the 8-device mesh the losses match one device and no collective
  carries a ``[rows, K]`` plane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.data import make_synthetic_batch
from dinov3_tpu.data.masking import (
    ibot_mask_targets,
    masked_rows_bound,
    sample_ibot_masks,
)
from dinov3_tpu.losses import ibot_loss_from_spec, sinkhorn_knopp
from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch
from test_meta_arch import smol_cfg

GRID = 14
T = GRID * GRID
CAP = T // 2

# the test width of the step-level cases: 64 tokens a global crop (the
# suite's usual 16 would round M_c up to the whole 2B * M_img buffer),
# 16 global crops: 153 masked tokens, M_c 256 of 512 buffer rows
WIDE = ["crops.global_crops_size=32", "crops.local_crops_size=16"]
N_IMG, N_TOK, M_IMG = 16, 64, 32


# ---------------- (i) the bound is the sampler's own count ----------------

@pytest.mark.parametrize("n_images", [2, 6, 24, 64, 128])
def test_sampler_count_is_its_targets_sum_and_within_bound(n_images):
    targets = ibot_mask_targets(n_images, T, CAP)
    bound = masked_rows_bound(n_images, T, CAP)
    assert len(targets) == round(n_images * 0.5)
    assert sum(targets) <= bound <= n_images * CAP
    for seed in range(25):
        rng = np.random.default_rng((seed, n_images))
        masks, _, w, valid = sample_ibot_masks(
            rng, n_images, T, CAP, (GRID, GRID),
            random_circular_shift=bool(seed % 2))
        assert valid.sum() == sum(targets) == masks.sum()
        # per image: exactly one of the targets each, whichever image
        assert sorted(valid.sum(1)[valid.any(1)]) == sorted(
            t for t in targets if t)
        np.testing.assert_allclose(w.sum(1)[valid.any(1)], 1.0, rtol=1e-5)


def test_bound_of_the_benchmark_cells_and_the_recipe():
    # 30.0 % of the per-image buffers' rows at every batch size
    assert sum(ibot_mask_targets(64, T, CAP)) == 1881
    assert masked_rows_bound(64, T, CAP) == 1920          # ViT-S, B=32
    assert sum(ibot_mask_targets(24, T, CAP)) == 706
    assert masked_rows_bound(24, T, CAP) == 768           # ViT-L, B=12
    assert sum(ibot_mask_targets(128, T, CAP)) == 3765
    assert masked_rows_bound(128, T, CAP) == 3840         # recipe, B=64


@pytest.mark.parametrize("n_calls", [2, 4])
def test_bound_counts_every_sampler_call(n_calls):
    """A global batch assembled from ``n_calls`` hosts' collate calls."""
    n_images = 8 * n_calls
    one = ibot_mask_targets(8, T, CAP)
    bound = masked_rows_bound(n_images, T, CAP, n_calls=n_calls)
    assert n_calls * sum(one) <= bound
    rng = np.random.default_rng(n_calls)
    n_valid = sum(
        int(sample_ibot_masks(rng, 8, T, CAP, (GRID, GRID))[3].sum())
        for _ in range(n_calls))
    assert n_valid == n_calls * sum(one)
    with pytest.raises(ValueError, match="sampler calls"):
        masked_rows_bound(9, T, CAP, n_calls=2)


@pytest.mark.parametrize("n_micro", [2, 4])
def test_bound_of_a_microbatch_holds_for_any_image_subset(n_micro):
    """``n_seen``: the most-masked ``n_images / n_micro`` images fit."""
    n_images = 16
    n_seen = n_images // n_micro
    bound = masked_rows_bound(n_images, T, CAP, n_seen=n_seen)
    assert bound <= n_seen * CAP
    worst = 0
    for seed in range(25):
        valid = sample_ibot_masks(
            np.random.default_rng(seed), n_images, T, CAP, (GRID, GRID))[3]
        per_image = valid.sum(1)
        # split_microbatches deals image j of each crop to microbatch
        # j // (B / n_micro); any subset at all is covered
        worst = max(worst, int(np.sort(per_image)[-n_seen:].sum()))
        for j in range(n_micro):
            assert per_image[j * n_seen:(j + 1) * n_seen].sum() <= bound
    assert worst <= bound


# ---------------- (ii) compact rows == all rows ----------------

def _setup(extra=(), B=N_IMG // 2, seed=0):
    cfg = smol_cfg(WIDE + list(extra))
    meta = SSLMetaArch(cfg)
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, B, seed=seed).items()}
    params = meta.init_params(jax.random.key(0), batch)
    # non-trivial weights on both sides (teacher != student)
    def noisy(tree, salt):
        leaves, treedef = jax.tree.flatten(tree)
        key = jax.random.key(salt)
        return treedef.unflatten([
            x + 0.1 * jax.random.normal(jax.random.fold_in(key, i), x.shape)
            for i, x in enumerate(leaves)])

    return (cfg, meta, batch, noisy(params["student"], 1),
            noisy(params["teacher"], 2))


def _full_rows_reference(meta, batch, s_head, t_head, s_tok, t_tok, temp,
                         center):
    """The pre-compaction computation: every [2B * M_img] row through
    the heads and the same ``losses/`` functions, padding weighted out."""
    idx = batch["mask_indices"]
    take = lambda tok: jnp.take_along_axis(  # noqa: E731
        tok, idx[..., None], axis=1).reshape(-1, tok.shape[-1])
    valid = batch["mask_valid"].reshape(-1)
    t_logits = meta.teacher_ibot_head.apply({"params": t_head}, take(t_tok))
    s_logits = meta.ibot_head.apply({"params": s_head}, take(s_tok))
    stream = meta.streaming_targets
    out = {}
    if meta.centering == "sinkhorn_knopp":
        tt = sinkhorn_knopp(t_logits, temp, row_weights=valid.astype(
            jnp.float32), return_factors=stream)
        spec = ({"kind": "sinkhorn", "factors": tt} if stream
                else {"kind": "probs", "probs": tt})
        out["target"] = tt
    else:
        from dinov3_tpu.losses import softmax_center_teacher

        if stream:
            spec = {"kind": "softmax_center", "logits": t_logits,
                    "center": center, "temp": temp}
            out["target"] = t_logits
        else:
            q = softmax_center_teacher(t_logits, center, temp) * valid[
                :, None].astype(jnp.float32)
            spec = {"kind": "probs", "probs": q}
            out["target"] = q
        w = valid.astype(jnp.float32)[:, None]
        out["center"] = center * 0.9 + 0.1 * (
            jnp.sum(t_logits * w, 0, keepdims=True) / jnp.sum(w))
    out["loss"] = ibot_loss_from_spec(
        s_logits, spec, batch["mask_weights"].reshape(-1),
        n_images=idx.shape[0], k_tile=meta.loss_k_tile)
    return out


@pytest.mark.parametrize("streaming", ["true", "false"])
@pytest.mark.parametrize("centering", ["sinkhorn_knopp", "softmax_center"])
def test_compact_rows_match_all_rows(centering, streaming):
    cfg, meta, batch, student, teacher = _setup(
        [f"train.centering={centering}",
         f"loss.streaming_targets={streaming}", "loss.k_tile=8"])
    n_img, m_img = batch["mask_indices"].shape
    D = meta.embed_dim
    k1, k2, k3 = jax.random.split(jax.random.key(7), 3)
    s_tok = jax.random.normal(k1, (n_img, N_TOK, D))
    t_tok = jax.random.normal(k2, (n_img, N_TOK, D))
    cls = jax.random.normal(k3, (n_img, D))
    temp = 0.07
    state = {"dino_center": jnp.zeros((1, 32)),
             "ibot_center": 0.1 * jax.random.normal(k3, (1, 32))}

    masked = meta.masked_rows(batch)
    m_c = masked.slots.shape[0]
    n_valid = int(batch["mask_valid"].sum())
    assert (n_img, m_img) == (N_IMG, M_IMG)
    assert n_valid <= m_c < n_img * m_img       # the compaction is real
    assert int(masked.valid.sum()) == n_valid
    np.testing.assert_allclose(
        np.asarray(masked.weights)[:n_valid],
        np.asarray(batch["mask_weights"])[np.asarray(batch["mask_valid"])])

    t_out, new_state = meta.teacher_targets_from_features(
        teacher, cls, t_tok, batch, temp, state, masked=masked)
    spec = t_out["masked_target"]

    def compact_loss(s_head, tok):
        logits = meta.ibot_head.apply(
            {"params": s_head},
            meta._gather_masked(tok, masked))
        assert logits.shape == (m_c, 32)
        return ibot_loss_from_spec(
            logits, spec, masked.weights, n_images=n_img,
            k_tile=meta.loss_k_tile)

    def full_loss(s_head, tok):
        return _full_rows_reference(
            meta, batch, s_head, teacher["ibot_head"], tok, t_tok, temp,
            state["ibot_center"])["loss"]

    ref = _full_rows_reference(
        meta, batch, student["ibot_head"], teacher["ibot_head"], s_tok,
        t_tok, temp, state["ibot_center"])
    (lc, gc) = jax.value_and_grad(compact_loss, argnums=(0, 1))(
        student["ibot_head"], s_tok)
    (lf, gf) = jax.value_and_grad(full_loss, argnums=(0, 1))(
        student["ibot_head"], s_tok)
    np.testing.assert_allclose(float(lc), float(ref["loss"]), rtol=2e-6)
    np.testing.assert_allclose(float(lc), float(lf), rtol=2e-6)
    assert float(jnp.abs(gf[1]).sum()) > 0
    for a, b in zip(jax.tree.leaves(gc), jax.tree.leaves(gf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-7)

    # the teacher's targets on the valid rows, and the center update
    keep_c = np.asarray(masked.valid)
    keep_f = np.asarray(batch["mask_valid"]).reshape(-1)
    tgt = spec["factors"] if spec["kind"] == "sinkhorn" else spec[
        "probs" if spec["kind"] == "probs" else "logits"]
    if spec["kind"] == "sinkhorn":
        np.testing.assert_allclose(
            np.asarray(tgt.xs)[keep_c], np.asarray(ref["target"].xs)[keep_f],
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(tgt.r)[keep_c], np.asarray(ref["target"].r)[keep_f],
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(tgt.c, ref["target"].c,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(float(tgt.log_B), np.log(n_valid),
                                   rtol=1e-6)
    else:
        np.testing.assert_allclose(
            np.asarray(tgt)[keep_c], np.asarray(ref["target"])[keep_f],
            rtol=1e-5, atol=1e-6)
    if centering == "softmax_center":
        np.testing.assert_allclose(
            new_state["ibot_center"], ref["center"], rtol=1e-5, atol=1e-7)


# ---------------- (iii) the guard and the counters ----------------

def _forward(meta, batch, student, teacher):
    def loss_fn(sp):
        total, (ld, _) = meta.forward(
            sp, {"teacher": teacher}, batch, teacher_temp=0.07,
            state=meta.init_state(), iteration=0,
            rngs={"drop_path": jax.random.key(1), "rope": jax.random.key(2),
                  "dropout": jax.random.key(3)})
        return total, ld
    return jax.value_and_grad(loss_fn, has_aux=True)(student)


def test_sampler_masks_fill_the_buffer_without_overflow():
    cfg, meta, batch, student, teacher = _setup()
    (total, ld), grads = _forward(meta, batch, student, teacher)
    n_valid = int(batch["mask_valid"].sum())
    m_c = masked_rows_bound(N_IMG, N_TOK, M_IMG)
    assert float(ld["ibot_rows_overflow"]) == 0
    assert float(ld["ibot_rows_fill"]) == pytest.approx(n_valid / m_c)
    assert 0 < float(ld["ibot_rows_fill"]) <= 1
    assert np.isfinite(float(total)) and np.isfinite(float(ld["ibot_loss"]))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))


def test_masks_over_the_bound_make_ibot_loss_nonfinite():
    """A foreign mask source (every image masked to capacity) cannot be
    trained on with tokens dropped: loss and gradient go non-finite, so
    the non-finite streak stops the run, and the overflow is counted."""
    cfg, meta, batch, student, teacher = _setup()
    n_img, m_img = batch["mask_indices"].shape
    batch = dict(batch)
    batch["mask_valid"] = jnp.ones((n_img, m_img), bool)
    batch["mask_indices"] = jnp.tile(
        jnp.arange(m_img, dtype=jnp.int32), (n_img, 1))
    batch["mask_weights"] = jnp.full((n_img, m_img), 1.0 / m_img)
    batch["masks"] = jnp.zeros_like(batch["masks"]).at[:, :m_img].set(True)
    (total, ld), grads = _forward(meta, batch, student, teacher)
    m_c = masked_rows_bound(n_img, N_TOK, m_img)
    assert float(ld["ibot_rows_overflow"]) == n_img * m_img - m_c > 0
    assert float(ld["ibot_rows_fill"]) > 1
    assert not np.isfinite(float(ld["ibot_loss"]))
    assert not np.isfinite(float(total))
    assert not np.isfinite(
        np.asarray(jax.tree.leaves(grads["ibot_head"])[0])).all()
    # the other terms are untouched
    assert np.isfinite(float(ld["dino_global_crops_loss"]))


def test_student_output_rows_are_compact():
    cfg, meta, batch, student, teacher = _setup()
    g_out, _ = meta.get_student_output(
        student, batch,
        {"drop_path": jax.random.key(1), "rope": jax.random.key(2),
         "dropout": jax.random.key(3)}, masked=meta.masked_rows(batch))
    assert g_out["masked_patch_after_head"].shape == (
        masked_rows_bound(N_IMG, N_TOK, M_IMG), 32)


def test_microbatches_size_their_buffer_for_the_most_masked_one():
    """accum_steps=2: one microbatch may hold all eight masked images."""
    cfg, meta, batch, student, teacher = _setup()
    half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
    assert meta.masked_rows(half, n_micro=2).slots.shape[0] == \
        masked_rows_bound(N_IMG, N_TOK, M_IMG, n_seen=N_IMG // 2) == 256
    from dinov3_tpu.train.train_step import split_microbatches

    micro = split_microbatches(batch, 2)
    for j in range(2):
        mb = {k: v[j] for k, v in micro.items()}
        rows = meta.masked_rows(mb, n_micro=2)
        assert float(rows.overflow) == 0
        assert int(rows.valid.sum()) == int(mb["mask_valid"].sum())


# ---------------- the loader's call pattern is the caller's to say ----------------

def _two_call_batch(cfg):
    """A global batch of N_IMG mask rows made by TWO sampler calls (two
    hosts' collates, each host's rows together as ``put_batch`` lays them)."""
    halves = [make_synthetic_batch(cfg, N_IMG // 4, seed=s) for s in (0, 1)]
    return {k: jnp.asarray(np.concatenate([h[k] for h in halves]))
            for k in halves[0]}


def test_buffer_follows_the_sampler_calls_it_is_told():
    """``mask_sampler_calls`` comes from whoever builds the loader
    (train.py: its world size), never from the mesh: the same global
    shape gets the buffer of one call or of two."""
    from dinov3_tpu.train import build_train_setup

    cfg = smol_cfg(WIDE)
    batch = _two_call_batch(cfg)
    assert batch["mask_valid"].shape == (N_IMG, M_IMG)
    n_valid = int(batch["mask_valid"].sum())
    assert n_valid == 2 * sum(ibot_mask_targets(N_IMG // 2, N_TOK, M_IMG))
    two = SSLMetaArch(cfg, mask_sampler_calls=2).masked_rows(batch)
    assert two.slots.shape[0] == masked_rows_bound(
        N_IMG, N_TOK, M_IMG, n_calls=2)
    assert float(two.overflow) == 0
    assert int(two.valid.sum()) == n_valid
    assert SSLMetaArch(cfg).masked_rows(batch).slots.shape[0] == \
        masked_rows_bound(N_IMG, N_TOK, M_IMG)
    setup = build_train_setup(cfg, batch, init_state=False,
                              mask_sampler_calls=2)
    assert setup.meta.mask_sampler_calls == 2


def test_put_batch_refuses_masks_over_the_samplers_count():
    """The host check names the numbers before the device sees the
    batch; without a limit the NaN guard above is the backstop."""
    from dinov3_tpu.train import build_train_setup, put_batch

    cfg = smol_cfg(WIDE)
    batch = make_synthetic_batch(cfg, N_IMG // 2, seed=0)
    setup = build_train_setup(
        cfg, {k: jnp.asarray(v) for k, v in batch.items()},
        init_state=False)
    limit = setup.mask_rows_limit(batch)
    assert limit == int(batch["mask_valid"].sum()) == sum(
        ibot_mask_targets(N_IMG, N_TOK, M_IMG))
    put_batch(batch, setup.batch_shardings, limit)
    over = dict(batch, mask_valid=np.ones((N_IMG, M_IMG), bool))
    with pytest.raises(ValueError, match=f"masks {N_IMG * M_IMG} tokens.*"
                                         f"makes {limit} for its {N_IMG}"):
        put_batch(over, setup.batch_shardings, limit)
    put_batch(over, setup.batch_shardings)      # no limit given: no check


# ---------------- the mesh ----------------

@pytest.mark.parametrize("axes", [
    {"data": -1, "fsdp": 1},          # pure DP
    {"data": -1, "fsdp": 2},          # DP x FSDP (ZeRO-3 masters)
])
def test_compact_rows_on_the_mesh(eight_devices, axes):
    """Losses within the suite's one-device tolerance, and the compiled
    step moves no [rows, K] plane between devices: the row gather
    crosses data shards with [M_c, D]-sized rows (or the tokens), the
    heads' planes stay where their rows are."""
    from dinov3_tpu.train import build_train_setup, put_batch
    import re

    from dinov3_tpu.utils import (
        classify_collective,
        hlo_collective_census,
        hlo_non_fusion_lines,
    )

    B, K = N_IMG // 2, 96                     # K: the iBOT head's alone
    common = WIDE + ["student.layerscale=1.0", "ibot.head_n_prototypes=96",
                     "student.drop_path_rate=0.0"]
    cfg = smol_cfg(common + [f"parallel.{k}={v}" for k, v in axes.items()])
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, B, seed=0).items()}
    setup8 = build_train_setup(cfg, batch, devices=eight_devices)
    d8 = put_batch(batch, setup8.batch_shardings)
    compiled = setup8.step_fn.lower(
        setup8.state, d8, setup8.scalars(0), jax.random.key(0)).compile()
    _, m8 = setup8.step_fn(setup8.state, d8, setup8.scalars(0),
                           jax.random.key(0))
    cfg1 = smol_cfg(common + ["parallel.data=1", "parallel.fsdp=1"])
    setup1 = build_train_setup(cfg1, batch, devices=eight_devices[:1])
    d1 = put_batch(batch, setup1.batch_shardings)
    _, m1 = setup1.step_fn(setup1.state, d1, setup1.scalars(0),
                           jax.random.key(0))
    for key in ("total_loss", "ibot_loss", "ibot_rows_fill",
                "ibot_rows_overflow"):
        np.testing.assert_allclose(
            float(m8[key]), float(m1[key]), rtol=2e-6, err_msg=key)
    assert float(m8["ibot_rows_overflow"]) == 0

    m_c = masked_rows_bound(N_IMG, N_TOK, M_IMG)
    assert m_c % 8 == 0                       # splits over the data axes
    text = compiled.as_text()
    assert hlo_collective_census(text)["hlo_collective_total"] > 0
    # a [rows, K] plane, compact or per-image, whole or one device's rows
    # (no weight of the model has K columns and one of these row counts)
    row_counts = {m_c, m_c // 8, N_IMG * M_IMG, N_IMG * M_IMG // 8}
    shape = re.compile(r"\b[a-z]+\d+\[(\d+),(\d+)\]")
    n_collectives = 0
    for line in hlo_non_fusion_lines(text):
        if classify_collective(line) is None:
            continue
        n_collectives += 1
        for rows, cols in shape.findall(line):
            assert not (int(cols) == K and int(rows) in row_counts), line
    assert n_collectives > 0
    # the heads' planes are one device's rows of the compact buffer: the
    # per-device program holds [M_c / 8, K] planes and no whole [M_c, K]
    planes = {int(r) for r in re.findall(
        r"\b(?:f32|bf16)\[(\d+),%d\]" % K, text)}
    assert m_c // 8 in planes and m_c not in planes, sorted(planes)
