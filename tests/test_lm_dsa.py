"""The ``keye_vl2`` decoder family (PR 39), on the CPU at a small size.

(a) ``DSAMixer`` against the reference's whole-row softmax over the
    selected keys: output, index loss and the gradient of every leaf.
(b) ``ops/sparse_index.py``: the exact threshold against ``lax.top_k``
    with planted ties; the causal kernel pair (interpreted) under a
    selection against the plain tiles, both passes (the index loss's
    kernel against the plain strips: ``tests/test_index_loss_kernel.py``).
(c) The two stop-gradients; a sequence no longer than ``topk`` is the
    dense layer bit for bit; what a rematerialised layer makes again.
(d) The share tied to the model: 8 shards' routed parts add up to the
    uncut reference layer.
(e) The family on the normal path: config rules, one step of
    ``LMMetaArch`` through ``build_train_setup`` with its phases, ring
    columns and param groups, the paths at the published sizes. (The whole
    model against ``benchmark/reference/keye_vl2_fp32.py`` is
    ``tests/test_lm_dsa_benchmark.py``'s; a whole run of the cell is
    ``benchmark/tests/test_lm_dsa_rehearsal.py``'s, by hand.)
"""

import dataclasses
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.configs import load_config
from dinov3_tpu.utils import LM_STEP_PHASES, STEP_PHASES, classify_step_phase

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

RECIPE = os.path.join(REPO, "configs", "train", "keye_vl2_ep8.yaml")
# 8 query heads on 2 key/value heads of 16; an indexer of 4 heads of 8 that
# keeps 24 keys a query, its planes by strips of 16; 16 experts, 8 held
TINY = [
    "lm.hidden_size=64", "lm.num_attention_heads=8",
    "lm.num_key_value_heads=2", "lm.head_dim=16",
    "lm.sa_config.indexer_head_dim=8", "lm.sa_config.indexer_num_heads=4",
    "lm.sa_config.topk=24", "lm.sa_config.q_chunk_size=16",
    "lm.sa_config.kv_chunk_size=16", "lm.num_experts=16",
    "lm.num_experts_per_tok=4", "lm.moe_intermediate_size=32",
    "lm.expert_shards=2", "lm.vocab_size=250", "lm.num_hidden_layers=2",
    "lm.seq_len=96", "train.batch_size_per_device=2", "telemetry.flush_every=2"]


def tiny_cfg(extra=()):
    return load_config(RECIPE, overrides=[*TINY, *extra])


def _reference_shape(dc, first_expert=0):
    from reference import keye_vl2_fp32 as ref

    return ref.Shape(
        layers=dc.layers, heads=dc.num_attention_heads,
        kv_heads=dc.num_key_value_heads, rope_theta=dc.rope_theta,
        index_heads=dc.index_num_heads, index_topk=dc.index_topk,
        top_k=dc.num_experts_per_token, first_expert=first_expert,
        eps=dc.rms_norm_eps)


def _rel(got, want):
    return jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b))
        / max(float(jnp.linalg.norm(b)), 1e-30), got, want)


def _spread(params, key, scale=0.3):
    """Weights large enough that every rule moves the output by far more
    than float32's rounding (norm scales and biases drawn too)."""
    import flax.linen as nn

    flat, treedef = jax.tree_util.tree_flatten_with_path(nn.meta.unbox(params))
    out = []
    for i, (path, leaf) in enumerate(flat):
        draw = jax.random.normal(jax.random.fold_in(key, i), leaf.shape)
        out.append(1.0 + 0.2 * draw if str(getattr(
            path[-1], "key", path[-1])) == "scale" else scale * draw)
    return jax.tree_util.tree_unflatten(treedef, out)


def _mixer(dc, **kw):
    from dinov3_tpu.models.decoder import DSAMixer
    from dinov3_tpu.ops.norms import RMSNorm

    return DSAMixer(
        dc.num_attention_heads, dc.num_key_value_heads, dc.head_dim,
        dc.rope_theta, dc.index_num_heads, dc.index_head_dim, dc.index_topk,
        dc.index_chunk, lambda name: RMSNorm(epsilon=dc.rms_norm_eps, name=name),
        dc.rms_norm_eps, dtype=jnp.float32, **kw)


# ---------------- (a) the mixer against the reference ----------------

def test_dsa_mixer_is_the_reference_softmax_over_the_selected_keys():
    import lm_dsa_weights
    from reference import keye_vl2_fp32 as ref

    from dinov3_tpu.models import DecoderConfig

    dc = DecoderConfig.from_cfg(tiny_cfg(["compute_precision.compute_dtype=fp32"]))
    shape = _reference_shape(dc)
    mixer = _mixer(dc, keep_selection=True)
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (1, 96, 64))
    w = jax.random.normal(ks[1], (1, 96, 64))
    params = _spread(jax.jit(mixer.init)(ks[2], x)["params"], ks[3])
    names = lambda p: {k: p[a][b] for k, (a, b) in lm_dsa_weights._MIXER.items()}  # noqa: E731

    def program(p, x):
        y, aux = mixer.apply({"params": p}, x)
        return jnp.sum(y * w) + aux["index_loss"], (y, aux)

    def reference(m, x, bits):
        y, loss, same, pairs = ref.attention(x, m, shape, bits, "fp32", block=32)
        return jnp.sum(y * w) + loss, (y, loss, same, pairs)

    with jax.default_matmul_precision("highest"):
        (_, (y, aux)), (gp, gx) = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True))(params, x)
        bits = aux["selection"]
        (_, (want, loss, same, pairs)), (gm, gxr) = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True))(names(params), x, bits)
        own = jax.jit(reference)(names(params), x, None)[1]
    kept = np.unpackbits(np.asarray(bits), axis=-1).sum(-1)[0]
    np.testing.assert_array_equal(kept, np.minimum(np.arange(96) + 1, 24))
    assert int(aux["select_excess"]) == 0
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert abs(float(aux["index_loss"]) - float(loss)) < 1e-6 < float(loss)
    # the reference's own indexer selects the same keys (float32 both)
    assert int(same) == int(pairs) == 24 * (96 - 24)
    np.testing.assert_allclose(own[0], want, atol=2e-5)
    rel = _rel(names(gp), gm)
    assert max(jax.tree.leaves(rel)) < 5e-5, rel
    # the indexer reads the input detached: x's gradient is the main path's
    assert float(jnp.linalg.norm(gx - gxr)) < 5e-5 * float(jnp.linalg.norm(gxr))
    assert min(float(jnp.linalg.norm(g)) for g in jax.tree.leaves(gp)) > 0


# ---------------- (b) the selection and the kernels under it ----------------

@pytest.mark.parametrize("rows, width, keep, levels", [
    (16, 64, 8, 5),      # a handful of values: every threshold is tied
    (16, 64, 64, 3),     # keep the whole row
    (8, 200, 31, 1000),  # hardly a tie
    (8, 96, 1, 2),       # the largest alone, the first of many equals
])
def test_exact_threshold_is_top_k_with_planted_ties(rows, width, keep, levels):
    from dinov3_tpu.ops import sparse_index as si

    rng = np.random.default_rng(rows * width + keep)
    scores = rng.integers(-levels, levels + 1, (rows, width)).astype(np.float32) / 4
    scores[0, :5] = [0.0, -0.0, 0.0, -0.0, 0.0]
    scores = jnp.where(jnp.asarray(scores) == 0.0, 0.0, jnp.asarray(scores))
    keys = si.ordered_key(scores)
    # the map keeps the order and is its own inverse
    order = np.argsort(np.asarray(scores).ravel(), kind="stable")
    assert np.all(np.diff(np.asarray(keys).ravel()[order]) >= 0)
    np.testing.assert_array_equal(si.key_to_float(keys), scores)
    k = jnp.full((rows,), keep, jnp.int32)
    thr, last = jax.jit(si.select_rows)(keys, k)
    got = np.asarray(si.selected(keys, thr, last, jnp.ones(keys.shape, bool)))
    value, idx = jax.lax.top_k(scores, keep)
    want = np.zeros((rows, width), bool)
    want[np.arange(rows)[:, None], np.asarray(idx)] = True
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(si.key_to_float(thr), value[:, -1])


def test_selection_of_a_sequence_is_top_k_of_every_causal_row():
    """``select_thresholds`` + ``selection_plane`` over strips and groups
    of strips (three groups here, the last one short), a planted run of
    equal scores across the threshold."""
    from dinov3_tpu.ops import sparse_index as si

    ks = jax.random.split(jax.random.key(1), 3)
    b, t, h, d, topk = 1, 80, 3, 8, 12
    qi = jax.random.normal(ks[0], (b, t, h, d))
    ki = jax.random.normal(ks[1], (b, t, d))
    ki = ki.at[0, 20:40].set(ki[0, 20])          # twenty keys score alike
    a = jax.random.normal(ks[2], (b, t, h))
    thr, last = si.select_thresholds(qi, ki, a, topk=topk, chunk=16, group=2)
    plane, excess = si.selection_plane(qi, ki, a, thr, last, topk=topk,
                                       chunk=16, group=2)
    assert int(excess) == 0 and plane.dtype == jnp.int8
    causal = np.tril(np.ones((t, t), bool))
    for s in range(b):
        scores = np.where(causal, np.asarray(si.index_scores(qi[s], ki[s], a[s])),
                          -np.inf)
        _, idx = jax.lax.top_k(jnp.asarray(scores), topk)   # ties: lower key
        idx = np.asarray(idx)
        want = np.zeros((t, t), np.int8)
        for row in range(t):
            want[row, idx[row, :min(row + 1, topk)]] = 1
        np.testing.assert_array_equal(np.asarray(plane[s]), want)
    assert int(jnp.sum(last < t)) > 0  # some row had to cut among equal scores
    packed = si.pack_selection(plane)
    np.testing.assert_array_equal(
        np.unpackbits(np.asarray(packed), axis=-1)[..., :t], np.asarray(plane))


def _random_selection(rng, n, keep):
    sel = np.zeros((1, n, n), np.int8)
    for t in range(n):
        sel[0, t, rng.choice(t + 1, min(t + 1, keep), replace=False)] = 1
    return jnp.asarray(sel)


@pytest.mark.parametrize("n, block_q, block_kv, heads, kv_heads", [
    (256, 128, 256, 2, 1),
    pytest.param(256, 128, 128, 2, 2, marks=pytest.mark.slow),
])
def test_kernel_pair_under_a_selection_is_the_plain_tiles(
        n, block_q, block_kv, heads, kv_heads):
    """Both passes, the kernels interpreted: a selection that keeps 40
    keys a query, not always the query's own, so whole tiles of a row are
    masked before its first kept key comes."""
    from dinov3_tpu.ops.attention import causal_tiles
    from dinov3_tpu.ops.causal_attention import kernel_attention_selected

    rng = np.random.default_rng(n)
    ks = jax.random.split(jax.random.key(n), 4)
    q = jax.random.normal(ks[0], (1, n, heads, 128))
    k, v = (jax.random.normal(key, (1, n, kv_heads, 128)) for key in ks[1:3])
    do = jax.random.normal(ks[3], q.shape)
    sel = _random_selection(rng, n, 40)
    kernel = lambda *x: kernel_attention_selected(  # noqa: E731
        *x, sel, 128 ** -0.5, block_q, block_kv, True)[0]
    tiles = lambda *x: causal_tiles(  # noqa: E731
        *x, block_q, block_kv, jnp.float32, None, sel)

    def dense(q, k, v):
        g = heads // kv_heads
        z = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, g, 2)) / math.sqrt(128)
        p = jax.nn.softmax(jnp.where(sel[:, None] != 0, z, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, g, 2))

    with jax.default_matmul_precision("highest"):
        out = {name: jax.jit(lambda *x, fn=fn: (fn(*x), *jax.vjp(fn, *x)[1](do)))(
            q, k, v) for name, fn in (("kernel", kernel), ("tiles", tiles),
                                      ("dense", dense))}
        # under a layer's remat the backward is the same
        again = jax.jit(jax.grad(lambda *x: jnp.sum(
            jax.checkpoint(kernel)(*x) * do), argnums=(0, 1, 2)))(q, k, v)
    for name in ("kernel", "tiles"):
        for got, want in zip(out[name], out["dense"]):
            np.testing.assert_allclose(got, want, atol=3e-5, err_msg=name)
    for got, want in zip(again, out["kernel"][1:]):
        np.testing.assert_allclose(got, want, atol=1e-6)


def test_a_selection_is_the_causal_paths_alone():
    from dinov3_tpu.ops.attention import dispatch_attention

    q = jnp.zeros((1, 32, 2, 16))
    sel = jnp.tril(jnp.ones((1, 32, 32), jnp.int8))
    with pytest.raises(ValueError, match="causal path"):
        dispatch_attention(q, q, q, selection=sel)
    with pytest.raises(ValueError, match="no window"):
        dispatch_attention(q, q, q, causal=True, window=4, selection=sel)
    full, lse = dispatch_attention(q + 1.0, q + 1.0, q + 1.0, causal=True,
                                   selection=sel)
    np.testing.assert_allclose(full, 1.0, atol=1e-6)
    assert lse is None  # the plain tiles hand on no log-sum-exp


# ---------------- (c) stop-gradients, the short sequence, remat ----------------

@pytest.fixture(scope="module")
def tiny_model():
    import flax.linen as nn

    from dinov3_tpu.models import build_backbone

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32"])
    model = build_backbone(cfg, param_dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(0), (2, 96), 0, 250)
    params = _spread(jax.jit(model.init)(jax.random.key(1), tokens)["params"],
                     jax.random.key(2), 0.1)
    return model, nn.meta.unbox(params), tokens


def test_each_loss_reaches_its_own_leaves_alone(tiny_model):
    """The next-token loss's gradient on the indexer's leaves is exactly
    0, the index loss's on every other leaf too; each moves all of its
    own."""
    model, params, tokens = tiny_model

    def losses(p):
        loss, aux = model.apply({"params": p}, tokens, with_loss=True)
        return loss, jnp.sum(aux["index_loss"])

    g_lm, g_ix = jax.jit(lambda p: (jax.grad(lambda p: losses(p)[0])(p),
                                    jax.grad(lambda p: losses(p)[1])(p)))(params)
    flat = lambda t: {  # noqa: E731
        "/".join(str(getattr(k, "key", k)) for k in path): float(jnp.max(jnp.abs(v)))
        for path, v in jax.tree_util.tree_flatten_with_path(t)[0]}
    lm, ix = flat(g_lm), flat(g_ix)
    indexer = {k for k in lm if "/index_" in k}
    assert len(indexer) == 2 * 5 and len(lm) == 35
    for name in lm:
        assert (lm[name] == 0.0) == (name in indexer), (name, lm[name])
        assert (ix[name] == 0.0) == (name not in indexer), (name, ix[name])


def test_a_sequence_no_longer_than_topk_is_the_dense_layer_bit_for_bit():
    from dinov3_tpu.models import DecoderConfig
    from dinov3_tpu.models.decoder import GQAMixer
    from dinov3_tpu.ops.norms import RMSNorm

    dc = DecoderConfig.from_cfg(tiny_cfg(["compute_precision.compute_dtype=fp32"]))
    mixer = _mixer(dc)
    dense = GQAMixer(dc.num_attention_heads, dc.num_key_value_heads, dc.head_dim,
                     None, dc.rope_theta, None, False,
                     lambda name: RMSNorm(epsilon=dc.rms_norm_eps, name=name),
                     dtype=jnp.float32)
    ks = jax.random.split(jax.random.key(3), 3)
    for t in (24, 7):  # t == topk, t < topk
        x = jax.random.normal(ks[0], (2, t, 64))
        params = _spread(jax.jit(mixer.init)(ks[1], x)["params"], ks[2])
        y, aux = jax.jit(mixer.apply)({"params": params}, x)
        shared = {k: v for k, v in params.items() if not k.startswith("index_")}
        want = jax.jit(dense.apply)({"params": shared}, x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
        assert int(aux["select_excess"]) == 0 and float(aux["index_loss"]) > 0


def test_a_rematerialised_layer_makes_everything_again(tiny_model):
    """The layer's remat keeps nothing of the selection: the counting
    passes, the score planes, the core and the index loss stand in both
    passes (kept thresholds gave a wrong gradient on the chip: PERF.md
    section 6, PR 39)."""
    model, params, tokens = tiny_model

    def total(p):
        loss, aux = model.apply({"params": p}, tokens, with_loss=True)
        return loss + jnp.sum(aux["index_loss"])

    text = jax.jit(jax.value_and_grad(total)).lower(params).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    seen = {(inner, classify_step_phase(n)[1]) for n in names
            for inner in ("dsa_select", "dsa_index", "dsa_index_loss", "dsa_core")
            if f"/{inner}/" in n}
    for inner in ("dsa_select", "dsa_index", "dsa_index_loss", "dsa_core"):
        assert {(inner, "fwd"), (inner, "bwd")} <= seen, inner


# ---------------- (d) the share tied to the model ----------------

@pytest.mark.slow  # 10 s idle: the whole suite runs close to its limit
def test_all_shards_parts_make_the_uncut_layer():
    """Guide section 4: at a small size, the routed parts that all 8
    shards give, with what every chip computes alike — the mixer and the
    residual stream — counted once, add up to the uncut reference layer."""
    import lm_dsa_weights
    from reference import keye_vl2_fp32 as ref

    from dinov3_tpu.models.decoder import DecoderConfig, DecoderLayer
    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    shards, held, d = 8, 2, 32
    e = shards * held
    dc = DecoderConfig.from_cfg(tiny_cfg([
        "compute_precision.compute_dtype=fp32", f"lm.hidden_size={d}",
        f"lm.num_experts={e}", f"lm.expert_shards={shards}"]))
    kinds = ("dsa", "moe")
    ks = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(ks[0], (1, 32, d))
    layer = DecoderLayer(*kinds, dc)
    params = _spread(jax.jit(layer.init)(ks[1], x)["params"], ks[2])
    router = params["experts"]["router"]
    full = {"w12": 0.3 * jax.random.normal(ks[1], (e, d, 64)),
            "w3": 0.3 * jax.random.normal(ks[2], (e, 32, d))}

    def held_by(shard, w3_scale=1.0):
        own = slice(shard * held, (shard + 1) * held)
        return {"router": router, "w12": full["w12"][own],
                "w3": w3_scale * full["w3"][own]}

    def whole(shard, experts):
        (y, aux), seen = DecoderLayer(*kinds, dataclasses.replace(
            dc, expert_shard=shard), True).apply(
                {"params": {**params, "experts": experts}}, x,
                capture_intermediates=lambda m, _: m.name == "norm2",
                mutable=["intermediates"])
        return y, aux, seen["intermediates"]["norm2"]["__call__"][0]

    def routed_part(shard, experts, u):
        return RoutedExpertsFFN(
            dc.moe_intermediate_size, e, dc.num_experts_per_token, shards,
            shard, router="softmax", gate="silu", dtype=jnp.float32).apply(
                {"params": experts}, u)

    with jax.default_matmul_precision("highest"):
        alike, aux, u = jax.jit(whole, static_argnums=0)(0, held_by(0, 0.0))
        total, choices = alike, []
        # (one program for the eight shards' parts)
        for routed, r_aux in jax.jit(lambda u: [
                routed_part(shard, held_by(shard), u) for shard in range(shards)])(u):
            assert float(r_aux["overflow"]) == 0
            total = total + routed
            choices.append(np.asarray(r_aux["choice"]))
        uncut = lm_dsa_weights.reference_tree(
            {"layers_0": {**params, "experts": {"router": router, **full}},
             "token_embed": 0, "lm_head": 0, "norm": {"scale": 0}})["layers"][0]
        shape = _reference_shape(dc)
        want, agree, index_loss, same, pairs = jax.jit(
            lambda lw: ref.layer(x, lw, shape, None, aux["selection"], "fp32"))(uncut)
    assert float(agree) == 1.0 and int(same) == int(pairs) > 0
    for c in choices[1:]:  # every shard routes over all the experts alike
        np.testing.assert_array_equal(c, choices[0])
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert abs(float(index_loss) - float(aux["index_loss"])) < 1e-6
    assert float(jnp.max(jnp.abs(alike - want))) > 1e-2


# ---------------- (e) the family on the normal path ----------------

def test_one_compiled_step_its_phases_ring_columns_and_param_groups():
    """One step of ``LMMetaArch`` on the recipe at test width, through
    ``build_train_setup`` and the telemetry step ``do_train`` runs: the
    family's phases in the compiled text, the ring's row (the two new
    columns with the routed layer's), and the decay multipliers of
    ``build_multiplier_trees`` are the reference's (none on any norm's
    scale nor on the indexer's LayerNorm bias)."""
    import lm_dsa_step_check
    import lm_dsa_weights
    from reference import keye_vl2_fp32 as ref

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup
    from dinov3_tpu.train.param_groups import build_multiplier_trees

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32"])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 2, seed=0).items()}
    setup = build_train_setup(cfg, batch, devices=jax.devices()[:1])
    plan = setup.telemetry()
    assert set(plan.metric_names) == {
        "total_loss", "lm_loss", "lm_index_loss", "dsa_select_excess",
        "moe_rows_fill", "moe_rows_overflow", "moe_load_max_over_mean"}
    args = (setup.state, jax.tree.map(jnp.asarray, plan.init_ring()), batch,
            setup.scalars(1250), jax.random.key(0))
    with setup.mesh:
        compiled = plan.step_fn.lower(*args).compile()
        state, ring = compiled(*args)
    row = dict(zip(plan.metric_names, np.asarray(ring.buf)[0]))
    assert abs(row["lm_loss"] - math.log(250)) < 0.5, row
    assert 0 < row["lm_index_loss"] < 1.0 and row["dsa_select_excess"] == 0
    assert row["total_loss"] == pytest.approx(
        row["lm_loss"] + row["lm_index_loss"], rel=1e-6)
    assert row["moe_rows_overflow"] == 0 and 0 < row["moe_rows_fill"] <= 1
    assert int(state.step) == 1 and set(state.params) == {"student"}

    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    found = {classify_step_phase(n) for n in names}
    family = {"lm_embed", "dsa_mixer", "moe_ffn", "lm_head_loss"}
    assert {p for p, _ in found} - {None} == family | {"update", "telemetry_ring"}
    for phase in family - {"lm_embed"}:
        assert {(phase, "fwd"), (phase, "bwd")} <= found, phase
    for phase, inner in (("dsa_mixer", "dsa_index"), ("dsa_mixer", "dsa_select"),
                         ("dsa_mixer", "dsa_core"), ("dsa_mixer", "dsa_index_loss"),
                         ("moe_ffn", "moe_route"), ("moe_ffn", "moe_experts")):
        assert any(phase in n and f"/{inner}/" in n for n in names), inner
    # the indexer's projections stand under dsa_index
    assert any("/dsa_index/index_q_proj/dot_general" in n for n in names)
    assert family < set(LM_STEP_PHASES) < set(STEP_PHASES)

    _, wd, _ = build_multiplier_trees(state.params["student"])
    tree = lm_dsa_weights.reference_tree(wd["backbone"])
    flat = jax.tree.leaves(jax.tree.map(
        lambda a, b: (float(a), float(b)), tree, ref.decays(tree)))
    assert all(a == b for a, b in zip(flat[::2], flat[1::2]))
    assert 0.0 in flat and 1.0 in flat
    # every leaf of the reference's layout has a group, the indexer its own
    groups = {p: lm_dsa_step_check.group_of(p)
              for p in lm_dsa_step_check.leaf_paths(tree)}
    assert set(groups.values()) == set(lm_dsa_step_check.GROUPS)
    assert groups["layers/1/ffn/router"] == "router"
    assert {groups[f"layers/0/mixer/{k}"] for k in lm_dsa_step_check.INDEXER} \
        == {"indexer"}
    assert groups["layers/0/mixer/q_norm"] == groups["layers/1/mixer/wo"] \
        == groups["layers/1/norm1"] == "mixers"


def test_benchmark_vocabulary_of_the_family_is_the_programs():
    with open(os.path.join(BENCH, "lm_dsa_phases.json")) as f:
        bench = json.load(f)
    named = set(bench["phases"]) | set(bench["inner"])
    named |= {p for sums in bench["metrics"].values() for p, _ in sums}
    named |= {p for p, _ in bench["inner_metrics"].values()}
    assert named <= set(STEP_PHASES), named - set(STEP_PHASES)
    for phase, inner in bench["inner_metrics"].values():
        assert inner in bench["inner"][phase]
    assert set(bench["inner"]["dsa_mixer"]) == {
        "dsa_index", "dsa_select", "dsa_core", "dsa_index_loss"}


def test_config_rules():
    from dinov3_tpu.configs.config import LM_ARCHS, is_lm_arch
    from dinov3_tpu.models import DecoderConfig, LMDecoder, build_backbone

    cfg = tiny_cfg()
    assert is_lm_arch(cfg) and "keye_vl2" in LM_ARCHS
    model = build_backbone(cfg)
    assert isinstance(model, LMDecoder) and model.embed_dim == 64
    dc = model.cfg
    assert dc.layers == (("dsa", "moe"),) * 2
    assert (dc.router, dc.gate, dc.router_reads_layer_input,
            dc.num_shared_experts, dc.zero_centered_norms) == (
                "softmax", "silu", False, 0, False)
    assert (dc.index_num_heads, dc.index_head_dim, dc.index_topk,
            dc.index_chunk) == (4, 8, 24, 16)
    with pytest.raises(ValueError, match="softmax router"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.norm_topk_prob=false"]))
    with pytest.raises(ValueError, match="routed"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.mlp_only_layers=[1]"]))
    with pytest.raises(ValueError, match="ONE key head"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.sa_config.indexer_num_kv_heads=2"]))
    with pytest.raises(ValueError, match="one"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.sa_config.kv_chunk_size=32"]))
    # the recipe as it stands holds the published widths
    lm = load_config(RECIPE).lm
    assert (lm.hidden_size, lm.num_attention_heads, lm.num_key_value_heads,
            lm.head_dim, lm.rope_theta, lm.rms_norm_eps) == (
                2048, 32, 4, 128, 10000000, 1e-6)
    assert dict(lm.sa_config) == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert (lm.num_experts, lm.num_experts_per_tok, lm.moe_intermediate_size,
            lm.seq_len) == (128, 8, 768, 16384)
    full = DecoderConfig.from_cfg(load_config(RECIPE))
    assert (full.num_experts // full.expert_shards, full.vocab_size,
            len(full.layers)) == (16, 18992, 5)


def test_the_paths_are_read_off_shapes_at_the_published_sizes():
    """``causal_attention_path`` at the cell's shape: on a TPU
    (``interpret=False``: described, not attached) the core takes the
    kernels, ONE row of 16,384 tokens of 128-wide heads fitting the
    backward's VMEM; here, on the CPU, the plain tiles, and the set-up log
    says which, a line a layer. The selection is an operand: the path does
    not ask for it."""
    import logging

    from dinov3_tpu.ops.causal_attention import causal_attention_path
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    shapes = ((1, 16384, 32, 128), (1, 16384, 4, 128), (1, 16384, 4, 128))
    assert causal_attention_path(shapes, None, False)[0] == "kernel"
    path, why = causal_attention_path(shapes)
    assert path == "tiles" and "not a TPU" in why
    longer = tuple((1, 32768) + s[2:] for s in shapes)
    assert causal_attention_path(longer, None, False)[0] == "tiles"
    # (a handler of the test's own: an earlier test of the worker may have
    # set the package's logger up not to propagate)
    logger, lines = logging.getLogger("dinov3"), []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    was = logger.level, logger.disabled
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    logger.disabled = False
    try:
        LMMetaArch(load_config(RECIPE))
    finally:
        logger.removeHandler(handler)
        logger.setLevel(was[0])
        logger.disabled = was[1]
    said = [m for m in lines if "dsa_core" in m]
    assert len(said) == 5 and all("tiles" in m for m in said)
    said = [m for m in lines if "dsa_index_loss" in m]
    assert len(said) == 5 and all(
        "strips (the core hands on no log-sum-exp" in m for m in said)
