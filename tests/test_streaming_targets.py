"""Streaming prototype-axis target/CE engine (losses/streaming.py) vs
the materialized oracle, plus the compiled-HLO guarantees.

Pinned here:
- loss-value AND student-gradient equivalence of the streaming engine
  against the materialized path (dino pairwise + ibot rows), for both
  centering modes (softmax-center, Sinkhorn) and both target storage
  dtypes (fp32, bf16);
- the full meta-arch forward agreeing between ``loss.streaming_targets``
  on and off, both centerings, including the center-EMA state;
- sharded-prototype correctness: the streaming step under a
  tensor-parallel (prototype-sharded "vocab") mesh matches the
  materialized step;
- the compiled-HLO claim: with streaming on, NO [*, K] fp32
  teacher-target buffer is materialized (softmax-center), and the
  Sinkhorn path materializes fewer [rows, K] buffers than the oracle
  (q eliminated, only the xs iterate remains);
- the copy census of the exact jitted train step does not regress
  (ceiling on copy-class HLO ops outside fusions; zero donation
  warnings).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.configs import apply_dot_overrides, get_default_config
from dinov3_tpu.losses import (
    choose_k_tile,
    dino_loss,
    ibot_loss_from_spec,
    ibot_patch_loss_masked,
    pair_ce_from_spec,
    pair_ce_to_loss,
    sinkhorn_knopp,
    softmax_center_teacher,
)

_CTP_PATH = os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "cost_target_phase.py")


def _load_cost_script():
    spec = importlib.util.spec_from_file_location(
        "cost_target_phase", _CTP_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMOL = [
    "student.arch=vit_test", "student.patch_size=4",
    "student.drop_path_rate=0.0", "student.layerscale=1.0e-5",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=24",
    "dino.head_bottleneck_dim=8",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=24",
    "ibot.head_bottleneck_dim=8",
    "train.OFFICIAL_EPOCH_LENGTH=4", "optim.epochs=4",
    "optim.warmup_epochs=1", "optim.freeze_last_layer_epochs=1",
    "compute_precision.compute_dtype=fp32",
    "optim.scaling_rule=none",
]


def smol_cfg(extra=()):
    cfg = get_default_config()
    apply_dot_overrides(cfg, list(SMOL) + list(extra))
    return cfg


# ---------------- unit equivalence: engine vs oracle ----------------


def _pair_data(K=256, S=4, T=2, B=6, scale=3.0):
    key = jax.random.key(0)
    sl = jax.random.normal(key, (S, B, K)) * 2
    tl = jax.random.normal(jax.random.fold_in(key, 1), (T, B, K)) * scale
    center = jax.random.normal(jax.random.fold_in(key, 2), (1, K)) * 0.5
    return sl, tl, center


@pytest.mark.parametrize("tgt", [None, jnp.bfloat16])
def test_streaming_softmax_pairwise_matches_oracle(tgt):
    sl, tl, center = _pair_data()
    T, B, K = tl.shape
    temp = 0.07
    probs = softmax_center_teacher(
        tl.reshape(T * B, K), center, temp, storage_dtype=tgt
    ).reshape(T, B, K)
    oracle = dino_loss(sl, probs)
    spec = {"kind": "softmax_center", "logits": tl, "center": center,
            "temp": temp}
    stream = pair_ce_to_loss(pair_ce_from_spec(sl, spec, k_tile=64), B)
    # the streaming engine computes q in fp32 regardless of target
    # storage: vs a bf16-stored oracle the tolerance covers the oracle's
    # own bf16 target rounding
    rtol = 1e-5 if tgt is None else 5e-3
    np.testing.assert_allclose(float(stream), float(oracle), rtol=rtol)
    # ignore_diagonal normalization shared through pair_ce_to_loss
    oracle_d = dino_loss(sl[:T], probs, ignore_diagonal=True)
    stream_d = pair_ce_to_loss(
        pair_ce_from_spec(sl[:T], spec, k_tile=64), B,
        ignore_diagonal=True)
    np.testing.assert_allclose(float(stream_d), float(oracle_d), rtol=rtol)


@pytest.mark.parametrize("tgt", [None, jnp.bfloat16])
def test_streaming_sinkhorn_pairwise_matches_oracle(tgt):
    sl, tl, center = _pair_data()
    T, B, K = tl.shape
    temp = 0.07
    q = sinkhorn_knopp(tl.reshape(T * B, K), temp,
                       storage_dtype=tgt).reshape(T, B, K)
    oracle = dino_loss(sl, q)
    f = sinkhorn_knopp(tl.reshape(T * B, K), temp, storage_dtype=tgt,
                       return_factors=True)
    stream = pair_ce_to_loss(
        pair_ce_from_spec(sl, {"kind": "sinkhorn", "factors": f},
                          k_tile=64), B)
    # both paths share the storage-typed xs iterate; only the q
    # reconstruction differs (oracle stores q in tgt, streaming keeps it
    # fp32 in-register)
    rtol = 1e-5 if tgt is None else 5e-3
    np.testing.assert_allclose(float(stream), float(oracle), rtol=rtol)


@pytest.mark.parametrize("centering", ["softmax_center", "sinkhorn_knopp"])
def test_streaming_ibot_rows_match_oracle_with_padding(centering):
    K, M = 192, 12
    key = jax.random.key(3)
    sm = jax.random.normal(key, (M, K))
    tm = jax.random.normal(jax.random.fold_in(key, 1), (M, K)) * 2
    center = jax.random.normal(jax.random.fold_in(key, 2), (1, K)) * 0.3
    valid = jnp.array([1.0] * 8 + [0.0] * 4)
    w = jnp.where(valid > 0, 1 / 8.0, 0.0)
    temp = 0.07
    if centering == "softmax_center":
        probs = softmax_center_teacher(tm, center, temp) * valid[:, None]
        spec = {"kind": "softmax_center", "logits": tm, "center": center,
                "temp": temp}
    else:
        probs = sinkhorn_knopp(tm, temp, row_weights=valid)
        spec = {"kind": "sinkhorn", "factors": sinkhorn_knopp(
            tm, temp, row_weights=valid, return_factors=True)}
    oracle = ibot_patch_loss_masked(sm, probs, w, n_images=2)
    stream = ibot_loss_from_spec(sm, spec, w, 2, k_tile=64)
    np.testing.assert_allclose(float(stream), float(oracle), rtol=1e-5)


def test_streaming_gradients_match_oracle():
    """Student-logit gradients through the checkpointed scan == oracle
    gradients, softmax-center and sinkhorn."""
    sl, tl, center = _pair_data(K=128)
    T, B, K = tl.shape
    temp = 0.05
    probs = softmax_center_teacher(tl.reshape(T * B, K), center,
                                   temp).reshape(T, B, K)
    spec = {"kind": "softmax_center", "logits": tl, "center": center,
            "temp": temp}
    g_o = jax.grad(lambda s: dino_loss(s, probs))(sl)
    g_s = jax.grad(lambda s: pair_ce_to_loss(
        pair_ce_from_spec(s, spec, k_tile=32), B))(sl)
    np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_o),
                               rtol=1e-4, atol=1e-6)
    q = sinkhorn_knopp(tl.reshape(T * B, K), temp).reshape(T, B, K)
    f = sinkhorn_knopp(tl.reshape(T * B, K), temp, return_factors=True)
    g_o = jax.grad(lambda s: dino_loss(s, q))(sl)
    g_s = jax.grad(lambda s: pair_ce_to_loss(pair_ce_from_spec(
        s, {"kind": "sinkhorn", "factors": f}, k_tile=32), B))(sl)
    np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_o),
                               rtol=1e-4, atol=1e-6)


# ---------------- the closed-form backward (PR 36) ----------------


def _scan_row_ce_sinkhorn(student_logits, factors, s_temp, tk):
    """The row CE as PR 36's parent (d66609e) computed and differentiated
    it: a K-tile scan with online rescaling, JAX's own transposition of
    its checkpointed body. Kept here as the second reference of the
    whole-plane forward and the closed-form rule."""
    from dinov3_tpu.losses.streaming import _slice_k

    M, K = student_logits.shape
    f32 = jnp.float32
    r = factors.r.astype(f32)
    log_B = factors.log_B.astype(f32)

    def body(carry, i):
        dot, qsum, m_s, s_s = carry
        lq = (_slice_k(factors.xs, i, tk, 1).astype(f32) - r
              - _slice_k(factors.c, i, tk, 1).astype(f32) + log_B)
        q = jnp.exp(lq)
        xt_f = (_slice_k(student_logits, i, tk, 1) / jnp.asarray(
            s_temp, student_logits.dtype)).astype(f32)
        dot = dot + (xt_f * q).sum(-1)
        qsum = qsum + q.sum(-1)
        new_m_s = jnp.maximum(m_s, xt_f.max(-1))
        s_s = (s_s * jnp.exp(m_s - new_m_s)
               + jnp.exp(xt_f - new_m_s[:, None]).sum(-1))
        return (dot, qsum, new_m_s, s_s), None

    z = jnp.zeros((M,), f32)
    (dot, qsum, m_s, s_s), _ = jax.lax.scan(
        jax.checkpoint(body), (z, z, jnp.full((M,), -jnp.inf, f32), z),
        jnp.arange(K // tk))
    return dot, qsum, m_s + jnp.log(s_s)


def _scan_pair_ce_sinkhorn(student_logits, factors, s_temp, tk):
    """The pair CE under the parent's scan autodiff (see above)."""
    from dinov3_tpu.losses.streaming import _slice_k

    S, B, K = student_logits.shape
    T = factors.xs.shape[0] // B
    f32 = jnp.float32
    r = factors.r.astype(f32)
    log_B = factors.log_B.astype(f32)

    def body(carry, i):
        dot, qsum, m_s, s_s = carry
        lq = (_slice_k(factors.xs, i, tk, 1).astype(f32) - r
              - _slice_k(factors.c, i, tk, 1).astype(f32) + log_B)
        q = jnp.exp(lq).reshape(T, B, tk)
        xt_f = (_slice_k(student_logits, i, tk, 2) / jnp.asarray(
            s_temp, student_logits.dtype)).astype(f32)
        dot = dot + jnp.einsum(
            "sbk,tbk->stb", xt_f, q, preferred_element_type=f32)
        qsum = qsum + q.sum(-1)
        new_m_s = jnp.maximum(m_s, xt_f.max(-1))
        s_s = (s_s * jnp.exp(m_s - new_m_s)
               + jnp.exp(xt_f - new_m_s[..., None]).sum(-1))
        return (dot, qsum, new_m_s, s_s), None

    init = (jnp.zeros((S, T, B), f32), jnp.zeros((T, B), f32),
            jnp.full((S, B), -jnp.inf, f32), jnp.zeros((S, B), f32))
    (dot, qsum, m_s, s_s), _ = jax.lax.scan(
        jax.checkpoint(body), init, jnp.arange(K // tk))
    lse_s = m_s + jnp.log(s_s)
    return jnp.einsum("sb,tb->st", lse_s, qsum) - dot.sum(-1)


def _ibot_rows(tgt, K=192, M=12, n_valid=8):
    key = jax.random.key(3)
    sm = jax.random.normal(key, (M, K))
    tm = jax.random.normal(jax.random.fold_in(key, 1), (M, K)) * 2
    valid = jnp.arange(M) < n_valid
    w = jnp.where(valid, 1.0 / n_valid, 0.0)
    kw = dict(row_weights=valid.astype(jnp.float32), storage_dtype=tgt)
    probs = sinkhorn_knopp(tm, 0.07, **kw)
    factors = sinkhorn_knopp(tm, 0.07, return_factors=True, **kw)
    return sm, probs, factors, w, valid


# the rule shares xs with both references; what differs is where q is
# rounded (the oracle stores it in tgt) and the scan's online rescaling
_RULE_TOL = {None: dict(rtol=1e-4, atol=1e-7),
             jnp.bfloat16: dict(rtol=2e-2, atol=1e-5)}


@pytest.mark.parametrize("tgt", [None, jnp.bfloat16], ids=["fp32", "bf16"])
def test_ibot_whole_plane_forward_matches_the_scan(tgt):
    """(dot, qsum, lse) as reductions over the whole plane == the parent's
    K-tile scan with its online rescaling, row by row, padding included."""
    from dinov3_tpu.losses.streaming import _row_ce_sinkhorn_stream

    sm, _, factors, _, valid = _ibot_rows(tgt)
    plane = _row_ce_sinkhorn_stream(sm, factors, 0.1)
    scan = _scan_row_ce_sinkhorn(sm, factors, 0.1, 64)
    for a, b in zip(plane, scan):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    assert np.all(np.asarray(plane[1])[~np.asarray(valid)] == 0.0)


@pytest.mark.parametrize("tgt", [None, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("upstream", [1.0, -2.5], ids=["unit", "scaled"])
def test_ibot_rule_matches_oracle_and_scan_autodiff(tgt, upstream):
    """The closed-form backward of the row CE == the materialized
    oracle's gradient == the parent's scan autodiff, with padding rows and
    a non-unit upstream cotangent; padding rows take exactly zero."""
    from dinov3_tpu.losses.ibot_loss import ibot_patch_loss_from_parts

    sm, probs, factors, w, valid = _ibot_rows(tgt)
    spec = {"kind": "sinkhorn", "factors": factors}

    def scan_loss(s):
        return ibot_patch_loss_from_parts(
            *_scan_row_ce_sinkhorn(s, factors, 0.1, 64), w, 2)

    g_rule = jax.grad(lambda s: upstream * ibot_loss_from_spec(
        s, spec, w, 2, k_tile=64))(sm)
    g_scan = jax.grad(lambda s: upstream * scan_loss(s))(sm)
    g_oracle = jax.grad(lambda s: upstream * ibot_patch_loss_masked(
        s, probs, w, n_images=2))(sm)
    assert g_rule.dtype == sm.dtype
    np.testing.assert_allclose(np.asarray(g_rule), np.asarray(g_scan),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(g_rule), np.asarray(g_oracle),
                               **_RULE_TOL[tgt])
    assert np.all(np.asarray(g_rule)[~np.asarray(valid)] == 0.0)
    assert np.abs(np.asarray(g_rule)[np.asarray(valid)]).min() > 0.0


@pytest.mark.parametrize("tgt", [None, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("ignore_diagonal", [False, True],
                         ids=["all_pairs", "off_diagonal"])
def test_dino_pair_rule_matches_oracle_and_scan_autodiff(tgt,
                                                         ignore_diagonal):
    """The same rule with its sum over the teacher crops; the
    off-diagonal case gives the pairs unequal (and zero) cotangents."""
    sl, tl, _ = _pair_data(K=128)
    T, B, K = tl.shape
    S = T if ignore_diagonal else sl.shape[0]
    sl = sl[:S]
    flat = tl.reshape(T * B, K)
    q = sinkhorn_knopp(flat, 0.05, storage_dtype=tgt).reshape(T, B, K)
    f = sinkhorn_knopp(flat, 0.05, storage_dtype=tgt, return_factors=True)
    kw = dict(ignore_diagonal=ignore_diagonal)
    g_rule = jax.grad(lambda s: pair_ce_to_loss(pair_ce_from_spec(
        s, {"kind": "sinkhorn", "factors": f}, k_tile=32), B, **kw))(sl)
    g_scan = jax.grad(lambda s: pair_ce_to_loss(
        _scan_pair_ce_sinkhorn(s, f, 0.1, 32), B, **kw))(sl)
    g_oracle = jax.grad(lambda s: dino_loss(s, q, **kw))(sl)
    np.testing.assert_allclose(np.asarray(g_rule), np.asarray(g_scan),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(g_rule), np.asarray(g_oracle),
                               **_RULE_TOL[tgt])


def _sinkhorn_term(term, K):
    """(student logits, the loss under the rule, the same loss under the
    parent's scan autodiff) of the iBOT rows or the DINO pairs."""
    from dinov3_tpu.losses.ibot_loss import ibot_patch_loss_from_parts

    if term == "ibot":
        sm, _, factors, w, _ = _ibot_rows(None, K=K)
        spec = {"kind": "sinkhorn", "factors": factors}

        def rule(s):
            return ibot_loss_from_spec(s, spec, w, 2, k_tile=64)

        def scan(s):
            return ibot_patch_loss_from_parts(
                *_scan_row_ce_sinkhorn(s, factors, 0.1, 64), w, 2)
    else:
        sm, tl, _ = _pair_data(K=K)
        T, B, _ = tl.shape
        f = sinkhorn_knopp(tl.reshape(T * B, K), 0.05, return_factors=True)

        def rule(s):
            return pair_ce_to_loss(pair_ce_from_spec(
                s, {"kind": "sinkhorn", "factors": f}, k_tile=64), B)

        def scan(s):
            return pair_ce_to_loss(
                _scan_pair_ce_sinkhorn(s, f, 0.1, 64), B)
    return sm, rule, scan


@pytest.mark.parametrize("term", ["ibot", "dino"])
def test_sinkhorn_rule_carries_a_nan_cotangent(term):
    """The overflow guard multiplies the loss by NaN
    (ssl_meta_arch: ``masked.overflow``): the NaN must reach every
    student logit through the rule, as it did through the scan."""
    sm, loss, _ = _sinkhorn_term(term, K=128)
    val, g = jax.value_and_grad(lambda s: loss(s) * jnp.nan)(sm)
    assert np.isnan(float(val))
    assert np.isnan(np.asarray(g)).all()
    assert np.isfinite(np.asarray(jax.grad(loss)(sm))).all()


def test_ibot_rule_bf16_student_logits():
    """Student logits in bf16 (a head with ``reduce_dtype`` bf16; no
    recipe has one): the cotangent comes back in bf16, within what
    rounding ``x / tau`` (|x / tau| up to 40: half an ulp is 0.125) to
    bf16 does to an exponential."""
    from dinov3_tpu.losses.ibot_loss import ibot_patch_loss_from_parts

    sm, _, factors, w, _ = _ibot_rows(None)
    sm = sm.astype(jnp.bfloat16)
    g_rule = jax.grad(lambda s: ibot_loss_from_spec(
        s, {"kind": "sinkhorn", "factors": factors}, w, 2, k_tile=64))(sm)
    g_scan = jax.grad(lambda s: ibot_patch_loss_from_parts(
        *_scan_row_ce_sinkhorn(s, factors, 0.1, 64), w, 2))(sm)
    assert g_rule.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(g_rule, np.float32), np.asarray(g_scan, np.float32),
        rtol=0.15, atol=1e-6)


@pytest.mark.parametrize("term", ["ibot", "dino"])
def test_sinkhorn_rule_lowers_without_a_backward_loop(term):
    """The counter of PR 36: the lowered gradient of the iBOT term holds
    no ``while`` and no ``dynamic_update_slice`` (forward: whole-plane
    reductions; backward: the rule); the DINO term's holds ONE ``while``,
    its forward's K-tile scan, whose carry is [S,T,B] statistics. The
    parent's programs held a second, transposed loop that carried the
    cotangent plane and wrote it tile by tile (the local scan references
    validate the detector). The iBOT rule's ``dx`` passes ONE
    ``optimization_barrier`` (it keeps the rule a fusion of its own, out
    of the head's backward matmuls: the ViT-L step is 3 % slower without
    it, ``PERF.md`` section 6, PR 36); the DINO rule's passes none (the
    three of that program are the forward scan's pinned tiles)."""
    sm, rule, scan = _sinkhorn_term(term, K=256)

    def lowered(fn):
        return jax.jit(jax.grad(fn)).lower(sm).as_text()

    def loops_and_updates(text):
        return (text.count("stablehlo.while"),
                text.count("dynamic_update_slice"))

    text = lowered(rule)
    assert loops_and_updates(text) == ((0 if term == "ibot" else 1), 0)
    assert text.count("optimization_barrier") == (
        1 if term == "ibot" else 3)
    loops, updates = loops_and_updates(lowered(scan))
    assert loops >= 2 and updates >= 1


def test_choose_k_tile():
    assert choose_k_tile(65536, 8192) == 8192
    assert choose_k_tile(65536, 8000) == 4096  # largest divisor <= cap
    assert choose_k_tile(300, 128) == 100
    assert choose_k_tile(64, 8192) == 64       # cap above K: one tile
    assert choose_k_tile(64, 0) == 64          # 0 = unset


# ---------------- meta-arch integration ----------------


@pytest.mark.parametrize("centering", ["sinkhorn_knopp", "softmax_center"])
def test_meta_arch_streaming_matches_materialized(centering):
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch

    results = {}
    for flag in ("true", "false"):
        cfg = smol_cfg([f"train.centering={centering}",
                        f"loss.streaming_targets={flag}",
                        "loss.k_tile=16"])
        meta = SSLMetaArch(cfg)
        assert meta.streaming_targets == (flag == "true")
        batch = {k: jnp.asarray(v) for k, v in
                 make_synthetic_batch(cfg, 4, seed=0).items()}
        params = meta.init_params(jax.random.key(0), batch)
        rngs = {"drop_path": jax.random.key(1), "rope": jax.random.key(2),
                "dropout": jax.random.key(3)}
        total, (loss_dict, state) = meta.forward(
            params["student"], {"teacher": params["teacher"]}, batch,
            teacher_temp=0.07, state=meta.init_state(), iteration=0,
            rngs=rngs,
        )
        results[flag] = (float(total),
                         {k: float(v) for k, v in loss_dict.items()},
                         state)
    t_on, d_on, s_on = results["true"]
    t_off, d_off, s_off = results["false"]
    np.testing.assert_allclose(t_on, t_off, rtol=1e-5)
    for k in d_off:
        np.testing.assert_allclose(d_on[k], d_off[k], rtol=2e-5,
                                   err_msg=k)
    # center EMA state is computed from the raw logits on both paths:
    # bit-identical fp32 accumulation
    for k in s_off:
        np.testing.assert_array_equal(np.asarray(s_on[k]),
                                      np.asarray(s_off[k]))


def test_streaming_auto_defaults_on():
    from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch

    assert SSLMetaArch(smol_cfg()).streaming_targets is True
    assert SSLMetaArch(
        smol_cfg(["loss.streaming_targets=false"])).streaming_targets is False
    with pytest.raises(ValueError, match="streaming_targets"):
        SSLMetaArch(smol_cfg(["loss.streaming_targets=sometimes"]))


def test_sharded_prototypes_streaming_matches_materialized(eight_devices):
    """Tensor-axis ("vocab") sharded prototype heads: the streaming step
    under dp x tensor == the materialized step, same batch (the 8-device
    dryrun regression the ISSUE requires)."""
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup, put_batch

    losses = {}
    for flag in ("true", "false"):
        cfg = smol_cfg(["parallel.data=-1", "parallel.tensor=2",
                        f"loss.streaming_targets={flag}", "loss.k_tile=16"])
        batch = {k: jnp.asarray(v) for k, v in
                 make_synthetic_batch(cfg, 8, seed=0).items()}
        setup = build_train_setup(cfg, batch, devices=eight_devices)
        d = put_batch(batch, setup.batch_shardings)
        state, m = setup.step_fn(setup.state, d, setup.scalars(0),
                                 jax.random.key(0))
        assert np.isfinite(float(m["total_loss"]))
        losses[flag] = float(m["total_loss"])
    np.testing.assert_allclose(losses["true"], losses["false"], rtol=2e-5)


# ---------------- compiled-HLO guarantees ----------------

_K, _TILE, _T, _B, _S = 512, 64, 2, 4, 4


def _phase_programs(centering, target_dtype):
    """Compile the DINO target/CE phase two ways on abstract bf16 logits
    and return {"streaming": hlo, "materialized": hlo} plus row count."""
    sd = jax.ShapeDtypeStruct
    student = sd((_S, _B, _K), jnp.bfloat16)
    t_logits = sd((_T * _B, _K), jnp.bfloat16)
    center = sd((1, _K), jnp.float32)
    temp = sd((), jnp.float32)

    def streaming(s, tl, c, t):
        if centering == "softmax_center":
            spec = {"kind": "softmax_center",
                    "logits": tl.reshape(_T, _B, _K), "center": c,
                    "temp": t}
        else:
            spec = {"kind": "sinkhorn", "factors": sinkhorn_knopp(
                tl, t, storage_dtype=target_dtype, return_factors=True)}
        return pair_ce_to_loss(
            pair_ce_from_spec(s, spec, k_tile=_TILE), _B)

    def materialized(s, tl, c, t):
        if centering == "softmax_center":
            q = softmax_center_teacher(tl, c, t, storage_dtype=target_dtype)
        else:
            q = sinkhorn_knopp(tl, t, storage_dtype=target_dtype)
        return pair_ce_to_loss(pair_ce_from_spec(
            s, {"kind": "probs", "probs": q.reshape(_T, _B, _K)}), _B)

    texts = {}
    for name, fn in (("streaming", streaming),
                     ("materialized", materialized)):
        texts[name] = jax.jit(jax.value_and_grad(fn)).lower(
            student, t_logits, center, temp).compile().as_text()
    return texts


_TARGET_OPS = r"(exponential|divide|multiply)\("


def test_hlo_no_fp32_target_values_when_streaming():
    """The acceptance claim, in its version-robust form: in the compiled
    streaming program (softmax-center, bf16 logits) NO op — fusion
    internals included — produces a full [T*B, K] fp32 TARGET value
    (exp/divide/multiply of the softmax chain), so the fp32 teacher-
    target buffer provably never exists however the backend fuses; the
    materialized oracle program does produce them, which also validates
    the detector. (A backend may still hoist a one-time fp32 convert of
    the loop-invariant logits — XLA:CPU does, and strips the
    optimization barriers guarding against it; that scheduling choice is
    visible in, and already paid by, the pass-granularity bytes numbers
    in COST_TARGET_r07.json, which show streaming -69.5% anyway.)"""
    ctp = _load_cost_script()
    texts = _phase_programs("softmax_center", None)
    rows = _T * _B

    def full_target_values(text):
        return (ctp.count_materialized(text, "f32", _K, rows,
                                       include_fusions=True,
                                       op_pattern=_TARGET_OPS)
                + ctp.count_materialized(text, "f32", _K, _T * _B * _S,
                                         include_fusions=True,
                                         op_pattern=_TARGET_OPS))

    assert full_target_values(texts["streaming"]) == 0
    assert full_target_values(texts["materialized"]) > 0


def test_hlo_sinkhorn_streaming_drops_q_values():
    """Sinkhorn's ITERATIONS exp at full width inside their logsumexp
    reductions on both paths (algorithmically required — the iterate is
    what Sinkhorn is), but the q reconstruction stays K-tiled under
    streaming: strictly fewer full-[rows, K] exp/divide values than the
    materialized program, which reconstructs q at full width on top of
    the iterations."""
    ctp = _load_cost_script()
    texts = _phase_programs("sinkhorn_knopp", jnp.bfloat16)
    rows = _T * _B
    counts = {
        name: sum(
            ctp.count_materialized(t, dt, _K, rows,
                                   include_fusions=True,
                                   op_pattern=r"(exponential|divide)\(")
            for dt in ("f32", "bf16"))
        for name, t in texts.items()
    }
    assert counts["streaming"] < counts["materialized"], counts


def test_cost_target_reduction_mechanism():
    """scripts/cost_target_phase.py's pass-granularity accounting on a
    small config: streaming accesses >=30% fewer bytes than the
    materialized passes on the softmax-center path (the committed ViT-L
    K=65536 numbers in COST_TARGET_r07.json use the same code path;
    -69.5% there)."""
    ctp = _load_cost_script()
    cfg = smol_cfg(["dino.head_n_prototypes=2048",
                    "ibot.head_n_prototypes=2048", "loss.k_tile=256"])
    rec = ctp.measure_target_phase(cfg, "softmax_center", None)
    assert rec["bytes_streaming"] < rec["bytes_materialized_total"]
    assert rec["reduction_pct"] >= 30.0, rec
    assert set(rec["bytes_materialized_passes"]) == {
        "targets", "dino_ce", "ibot_ce"}


# ---------------- copy census + donation ----------------


def test_copy_census_does_not_regress():
    """Compile the exact jitted train step on CPU; the copy-class HLO op
    count outside fusions must stay at/below the audited ceiling and
    donation must produce zero warnings.

    Audited at PR-2 commit time on the drop-path-active census program
    (COST_TARGET_r07.json): 518 copies, ~98% of them scalar/u32[4]
    RNG-key plumbing (threefry fold_ins). PR-3's step-wide RNG-plan
    engine (rng/plan.py, default on) removes that plumbing: the same
    program now measures 144 copies (COST_RNG_r08.json, -72.2%; the
    legacy rng.plan=false oracle still measures 518). The ceiling is
    tightened from the old 700 to 200 — headroom for jax-version layout
    variation, not for structural regressions (a new weight-shaped copy
    pass is O(params) copies and a reintroduced per-layer key chain is
    O(layers); either blows straight through).

    The per-category attribution (utils.classify_copy) must also be
    present so a future regression names its source (RNG plumbing vs
    donation/async vs pack/unpack vs activation-sized copies).

    Re-pinned for PR-4 (crop packing, default on): the packed
    single-pass program measures 96 copies — packing REMOVED the old
    two-pass crop-boundary copies on top of the RNG-plan's cut — and
    its pack/unpack assembly lowers to slice/bitcast on this backend
    (zero copy-class ops; the "gather_pack" census category attributes
    them wherever a backend does materialize them, so the ceiling names
    a packing regression instead of silently absorbing it). The ceiling
    drops 200 -> 150 for the packed default; the two-pass oracle
    program keeps the prior 200 ceiling.
    """
    ctp = _load_cost_script()
    # the RNG-heavy program: drop-path active (the smol default of 0.0
    # has no device-side draws and measures ~11 copies on both paths)
    cfg = smol_cfg(["student.drop_path_rate=0.3"])
    rec = ctp.copy_census(cfg, B=4)
    assert rec["donation_warnings"] == []
    assert rec["hlo_copy_total"] <= 150, rec["hlo_copy_ops"]
    cats = {"rng", "donation_async", "small", "large", "gather_pack"}
    assert set(rec["by_category"]) <= cats
    assert rec["by_category"].get("gather_pack", {}).get("ops", 0) <= 40, rec
    assert rec["hlo_copy_bytes"] >= sum(
        c["bytes"] for c in rec["by_category"].values()) >= 0
    # the two-pass oracle program keeps its pre-packing ceiling
    rec_oracle = ctp.copy_census(
        smol_cfg(["student.drop_path_rate=0.3",
                  "model.crop_packing=false"]), B=4)
    assert rec_oracle["donation_warnings"] == []
    assert rec_oracle["hlo_copy_total"] <= 200, rec_oracle["hlo_copy_ops"]
