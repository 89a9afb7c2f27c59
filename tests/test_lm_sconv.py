"""The ``lfm2_moe`` decoder family (PR 41), on the CPU at a small size.

(a) ``ShortConvMixer`` (the plain chain, T no multiple of the time block)
    against the reference's three shifted products, output and the
    gradient of every leaf; the chain's kernel pair, interpreted, against
    the plain chain: both passes, two sequences, the first block's zero
    tail.
(b) The causal kernel pair at heads of 64 (two key/value heads a lane
    group), interpreted, against the plain tiles, both passes.
(c) The routed layer's rule: the bias moves the choice and not the
    weight; the 1e-6 in the normaliser.
(d) The tied leaf's gradient is the embedding's part + the head's part.
(e) The share tied to the model: the 8 shards' routed parts add up to
    the uncut reference layer.
(f) The family on the normal path: config rules, one step of
    ``LMMetaArch`` through ``build_train_setup`` with its ring columns and
    param groups, the phases in the compiled step, the paths at the
    published sizes. (The whole model against
    ``benchmark/reference/lfm2_moe_fp32.py`` is
    ``tests/test_lm_sconv_benchmark.py``'s; three steps against the
    reference's three, and a whole run of the cell, are
    ``benchmark/tests/test_lm_sconv_rehearsal.py``'s, by hand.)
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

RECIPE = os.path.join(REPO, "configs", "train", "lfm2_ep8.yaml")
# 4 query heads on 2 key/value heads of 16; 16 experts, 4 held
TINY = [
    "lm.hidden_size=64", "lm.intermediate_size=96", "lm.num_attention_heads=4",
    "lm.num_key_value_heads=2", "lm.num_experts=16", "lm.num_experts_per_tok=4",
    "lm.moe_intermediate_size=32", "lm.expert_shards=4", "lm.vocab_size=250",
    "lm.seq_len=100", "train.batch_size_per_device=2", "telemetry.flush_every=2"]


def tiny_cfg(extra=()):
    return load_config(RECIPE, overrides=[*TINY, *extra])


def reference_shape(dc, first_expert=0):
    from reference import lfm2_moe_fp32 as ref

    return ref.Shape(
        layers=dc.layers, heads=dc.num_attention_heads,
        kv_heads=dc.num_key_value_heads, rope_theta=dc.rope_theta,
        top_k=dc.num_experts_per_token, first_expert=first_expert,
        routed_scaling_factor=dc.routed_scaling_factor, eps=dc.rms_norm_eps)


def _rel(got, want):
    return jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b))
        / max(float(jnp.linalg.norm(b)), 1e-30), got, want)


def spread(params, key, scale=0.3):
    """Weights large enough that every rule moves the output by far more
    than float32's rounding (norm scales as they were made)."""
    import flax.linen as nn

    flat, treedef = jax.tree_util.tree_flatten_with_path(nn.meta.unbox(params))
    out = []
    for i, (path, leaf) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        out.append(leaf if name == "scale" else scale * jax.random.normal(
            jax.random.fold_in(key, i), leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------- (a) the gated short convolution ----------------

def test_short_conv_mixer_is_three_shifted_products():
    import flax.linen as nn
    import lm_sconv_weights
    from reference import lfm2_moe_fp32 as ref

    from dinov3_tpu.models.decoder import ShortConvMixer
    from dinov3_tpu.ops.mixer_chains import TIME_BLOCK

    d, t = 32, 100
    assert t % TIME_BLOCK
    mixer = ShortConvMixer(3, dtype=jnp.float32)
    ks = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(ks[0], (2, t, d))
    params = nn.meta.unbox(jax.jit(mixer.init)(ks[1], x)["params"])
    assert params["in_proj"]["kernel"].shape == (d, 3 * d)
    assert params["conv"].shape == (3, d)
    assert float(jnp.max(jnp.abs(params["conv"]))) <= 3 ** -0.5
    params = spread(params, ks[2])
    rename = lambda p: {k: lm_sconv_weights._get(p, path)  # noqa: E731
                        for k, path in lm_sconv_weights._CONV.items()}

    def both(fn):
        return jax.jit(lambda p, x: (fn(p, x), jax.grad(
            lambda p, x: jnp.sum(jnp.sin(fn(p, x))), argnums=(0, 1))(p, x)))

    with jax.default_matmul_precision("highest"):
        y, (gp, gx) = both(lambda p, x: mixer.apply({"params": p}, x))(params, x)
        for variant, same in (("fp32", True), ("no_conv", False)):
            want, (wp, wx) = both(lambda p, x, v=variant: ref.short_conv(
                x, rename(p), v))(params, x)
            gaps = jax.tree.leaves(_rel((y, rename(gp), gx), (want, rename(wp), wx)))
            assert (max(gaps) < 1e-5) == same, (variant, gaps)
            if not same:   # the control moves the output and the taps' gradient
                assert min(gaps[0], _rel(gp["conv"], wp["conv"])) > 0.1, gaps
    # the first token of a sequence reads nothing before it: its output is
    # the last tap's alone
    plane = x[:, :1] @ params["in_proj"]["kernel"]
    first = (plane[..., d:2 * d] * params["conv"][2] * plane[..., :d]
             * plane[..., 2 * d:]) @ params["out_proj"]["kernel"]
    np.testing.assert_allclose(y[:, :1], first, rtol=2e-4, atol=1e-5)


def test_interpreted_chain_kernels_are_the_plain_chain():
    """Both passes, two sequences of two blocks (a block's tail comes from
    the block before, the first block's is zero; the backward carries dc
    the other way), bfloat16 planes."""
    from dinov3_tpu.models.decoder import causal_depthwise_conv
    from dinov3_tpu.ops.mixer_chains import (
        TIME_BLOCK,
        gated_short_conv,
        mixer_chain_path,
    )

    b, t, c = 2, 2 * TIME_BLOCK, 256
    assert mixer_chain_path(t, (c,), (), jnp.bfloat16, interpret=True)[0] == "kernel"
    assert mixer_chain_path(t, (c,), (), jnp.bfloat16)[0] == "plain"
    assert mixer_chain_path(t + 4, (c,), (), jnp.bfloat16, interpret=True)[0] == "plain"
    assert mixer_chain_path(t, (c + 64,), (), jnp.bfloat16, interpret=True)[0] == "plain"
    ks = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(ks[0], (b, t, 3 * c)).astype(jnp.bfloat16)
    taps = jax.random.normal(ks[1], (3, c))
    dy = jax.random.normal(ks[2], (b, t, c)).astype(jnp.bfloat16)

    def plain(x, taps):
        gate, mid, u = (x[..., i * c:(i + 1) * c].astype(jnp.float32)
                        for i in range(3))
        return (mid * causal_depthwise_conv(gate * u, taps)).astype(x.dtype)

    def both(fn):
        def run(x, taps):
            y, vjp = jax.vjp(fn, x, taps)
            return (y, *vjp(dy))
        return jax.jit(run)(x, taps)

    got = both(lambda x, w: gated_short_conv(x, w, interpret=True))
    want = both(plain)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    for name, a, w in zip(("y", "dx", "dtaps"), got, want):
        assert a.dtype == w.dtype and a.shape == w.shape
        # one rounding to bfloat16 apart at most (the sums' order differs)
        assert float(jnp.linalg.norm(f32(a) - f32(w))) \
            < 2e-3 * float(jnp.linalg.norm(f32(w))), name
    # each third of dx by itself: dB, dC, du
    for i in range(3):
        part = slice(i * c, (i + 1) * c)
        assert float(jnp.linalg.norm(f32(got[1][..., part] - want[1][..., part]))) \
            < 3e-3 * float(jnp.linalg.norm(f32(want[1][..., part])))
    # the second sequence's first block reads nothing of the first's last
    alone = jax.jit(lambda x, w: gated_short_conv(x, w, interpret=True))(x[1:], taps)
    np.testing.assert_array_equal(f32(alone[0]), f32(got[0][1]))


# ---------------- (b) heads of 64 on the kernel pair ----------------

@pytest.mark.parametrize("window", [None, 100], ids=["global", "window"])
def test_interpreted_64_wide_pair_is_the_plain_tiles(window):
    """8 query heads on 2 key/value heads of 64 (one lane group's pair:
    the least this layout takes; g = 4 as published), float32, blocks of
    128: output, and the gradient of q, k and v."""
    from dinov3_tpu.ops.attention import causal_tiles
    from dinov3_tpu.ops.causal_attention import (
        causal_attention_path,
        kernel_attention,
    )

    b, n, h, hk, d = 1, 256, 8, 2, 64
    ks = jax.random.split(jax.random.key(7), 4)
    q, do = (jax.random.normal(k, (b, n, h, d)) for k in ks[:2])
    k, v = (jax.random.normal(k, (b, n, hk, d)) for k in ks[2:])
    shapes = (q.shape, k.shape, v.shape)
    assert causal_attention_path(shapes, window, True, 128, 128,
                                 jnp.float32)[0] == "kernel"
    # an odd number of key/value heads has no pair; nor have heads of 32
    for odd in (((b, n, 4, d), (b, n, 1, d), (b, n, 1, d)),
                ((b, n, h, 32), (b, n, hk, 32), (b, n, hk, 32))):
        path, why = causal_attention_path(odd, window, True, 128, 128, jnp.float32)
        assert path == "tiles" and "64 + 64" in why

    def both(fn):
        def run(q, k, v):
            o, vjp = jax.vjp(fn, q, k, v)
            return (o, *vjp(do))
        return jax.jit(run)(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = both(lambda q, k, v: kernel_attention(
            q, k, v, d ** -0.5, window, 128, 128, True))
        want = both(lambda q, k, v: causal_tiles(
            q, k, v, 128, 128, jnp.float32, window))
    for name, a, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.shape == w.shape
        assert _rel(a, w) < 2e-5, (name, _rel(a, w))


# ---------------- (c) the routed layer's rule ----------------

def test_router_bias_moves_the_choice_and_not_the_weight():
    from reference import lfm2_moe_fp32 as ref

    from dinov3_tpu.models.decoder import LFM2_ROUTER_EPS
    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    d, e, k = 16, 8, 2
    layer = RoutedExpertsFFN(8, e, k, 1, 0, 1.0, router="sigmoid",
                             dtype=jnp.float32, norm_eps=LFM2_ROUTER_EPS)
    ks = jax.random.split(jax.random.key(11), 3)
    x = jax.random.normal(ks[0], (64, d))
    params = spread(jax.jit(layer.init)(ks[1], x)["params"], ks[2])
    params["router_bias"] = jnp.zeros((e,))
    shape = ref.Shape(layers=(), heads=1, kv_heads=1, rope_theta=1.0, top_k=k,
                      first_expert=0)
    run = jax.jit(lambda p: layer.apply({"params": p}, x))
    with jax.default_matmul_precision("highest"):
        y0, aux0 = run(params)
        # a bias on expert 5 large enough that every token chooses it
        biased = {**params, "router_bias": params["router_bias"].at[5].set(2.0)}
        y1, aux1 = run(biased)
        (choice, weight, agree), (want, _) = jax.jit(lambda f: (
            ref.route(x, f, shape), ref.experts(x, f, shape, None, "fp32")))(biased)
    assert np.all(np.any(np.asarray(aux1["choice"]) == 5, -1))
    assert not np.all(np.any(np.asarray(aux0["choice"]) == 5, -1))
    np.testing.assert_array_equal(np.sort(aux1["choice"], -1), np.sort(choice, -1))
    assert float(agree) == 1.0
    # the weights are the SCORES', not score + bias: under 1 a token, by
    # the 1e-6 exactly
    scores = jax.nn.sigmoid(x @ params["router"])
    picked = jnp.take_along_axis(scores, choice, -1)
    np.testing.assert_allclose(
        weight, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    total = np.asarray(jnp.sum(picked, -1))
    np.testing.assert_allclose(1.0 - np.asarray(weight.sum(-1)),
                               1e-6 / (total + 1e-6), atol=2e-7)
    np.testing.assert_allclose(y1, want, atol=2e-5)
    # the bias takes no gradient
    g = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(
        layer.apply({"params": p}, x)[0]))))(biased)
    assert float(jnp.max(jnp.abs(g["router_bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g["router"]))) > 0.0
    # Kimi's rule has no such term (and lowers without the addition:
    # scripts/lowered_step_sha.py)
    assert RoutedExpertsFFN(8, e, k).norm_eps == 0.0 and y0.shape == x.shape


# ---------------- (d) the tied leaf ----------------

def test_tied_leaf_gradient_is_the_embeddings_part_plus_the_heads():
    from dinov3_tpu.models import LMDecoder, build_backbone

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32",
                    "lm.num_hidden_layers=1", "lm.layer_types=[conv]"])
    model = build_backbone(cfg, param_dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(1), (2, 24), 0, 250)
    params = spread(jax.jit(model.init)(jax.random.key(2), tokens)["params"],
                    jax.random.key(3), 0.1)
    assert "lm_head" not in params and params["token_embed"].shape == (250, 64)

    # the same decoder with the head a leaf of its own, equal to the table
    # turned: its two gradients are the two parts
    twin = LMDecoder(dataclasses.replace(model.cfg, tie_word_embeddings=False))
    apart = {**params, "lm_head": params["token_embed"].T}
    grad = lambda m: jax.jit(jax.grad(lambda p: m.apply(  # noqa: E731
        {"params": p}, tokens, with_loss=True)[0]))
    with jax.default_matmul_precision("highest"):
        tied = grad(model)(params)["token_embed"]
        parts = grad(twin)(apart)
        g_in, g_out = parts["token_embed"], parts["lm_head"].T
        logits = jax.jit(lambda p: model.apply({"params": p}, tokens))(params)
        np.testing.assert_allclose(logits, jax.jit(lambda p: twin.apply(
            {"params": p}, tokens))(apart), atol=1e-5)
    assert logits.shape == (2, 24, 250)
    assert min(float(jnp.linalg.norm(g)) for g in (g_in, g_out)) > 1e-3
    assert _rel(tied, g_in + g_out) < 1e-5
    assert _rel(tied, g_in) > 0.1 and _rel(tied, g_out) > 0.1


# ---------------- (e) the shards' parts add up ----------------

def test_eight_shards_parts_add_up_to_the_uncut_layer():
    """What every chip computes alike (the mixer, the residual stream) is
    counted once; a shard's routed part is the program's routed layer on
    that layer's own normed stream."""
    import lm_sconv_weights
    from reference import lfm2_moe_fp32 as ref

    from dinov3_tpu.models.decoder import DecoderConfig, DecoderLayer
    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    shards, held, d = 8, 2, 32
    e = shards * held
    dc = DecoderConfig.from_cfg(tiny_cfg([
        "compute_precision.compute_dtype=fp32", f"lm.hidden_size={d}",
        f"lm.num_experts={e}", f"lm.expert_shards={shards}"]))
    kinds = ("conv", "moe")
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (2, 24, d))
    layer = DecoderLayer(*kinds, dc)
    params = spread(jax.jit(layer.init)(ks[1], x)["params"], ks[2])
    router, bias = params["experts"]["router"], 0.1 * jax.random.normal(ks[3], (e,))
    full = {"w12": 0.3 * jax.random.normal(ks[1], (e, d, 64)),
            "w3": 0.3 * jax.random.normal(ks[2], (e, 32, d))}

    def held_by(shard, w3_scale=1.0):
        own = slice(shard * held, (shard + 1) * held)
        return {"router": router, "router_bias": bias, "w12": full["w12"][own],
                "w3": w3_scale * full["w3"][own]}

    def whole(shard, experts):
        (y, _), seen = DecoderLayer(*kinds, dataclasses.replace(
            dc, expert_shard=shard)).apply(
                {"params": {**params, "experts": experts}}, x,
                capture_intermediates=lambda m, _: m.name == "norm2",
                mutable=["intermediates"])
        return y, seen["intermediates"]["norm2"]["__call__"][0]

    def routed_part(shard, experts, u):
        return RoutedExpertsFFN(
            dc.moe_intermediate_size, e, dc.num_experts_per_token, shards,
            shard, dc.routed_scaling_factor, router="sigmoid", gate="silu",
            norm_eps=dc.router_norm_eps, dtype=jnp.float32).apply(
                {"params": experts}, u)

    with jax.default_matmul_precision("highest"):
        alike, u = jax.jit(whole, static_argnums=0)(0, held_by(0, 0.0))
        total, choices = alike, []
        for shard in range(shards):
            routed, aux = jax.jit(routed_part, static_argnums=0)(
                shard, held_by(shard), u)
            assert float(aux["overflow"]) == 0
            total = total + routed
            choices.append(np.asarray(aux["choice"]))
        own, _ = jax.jit(whole, static_argnums=0)(3, held_by(3))
        np.testing.assert_allclose(
            own, alike + routed_part(3, held_by(3), u)[0], atol=1e-5)
        uncut = lm_sconv_weights.reference_tree(
            {"layers_0": {**params, "experts": {
                "router": router, "router_bias": bias, **full}},
             "token_embed": 0, "norm": {"scale": 0}})["layers"][0]
        want, agree = jax.jit(lambda lw: ref.layer(
            x, lw, kinds, reference_shape(dc), None, "fp32"))(uncut)
    assert float(agree) == 1.0
    for c in choices[1:]:  # every shard routes over all the experts alike
        np.testing.assert_array_equal(c, choices[0])
    assert len({int(v) // held for v in choices[0].reshape(-1)}) > 4
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(alike - want))) > 1e-2


# ---------------- (f) the family on the normal path ----------------

def test_one_compiled_step_its_phases_and_param_groups():
    """One step of ``LMMetaArch`` on the recipe at test width, through
    ``build_train_setup`` and the telemetry step ``do_train`` runs: the
    family's phases in the compiled text, a finite loss near
    log(vocabulary) in the ring's row, no overflow, no ``lm_head`` leaf;
    the decay multipliers of ``build_multiplier_trees`` are the
    reference's (none on the norms' scales and the selection bias; the
    taps and the tied table decay)."""
    import lm_sconv_step_check
    import lm_sconv_weights
    from reference import lfm2_moe_fp32 as ref

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup
    from dinov3_tpu.train.param_groups import build_multiplier_trees

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32"])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 2, seed=0).items()}
    setup = build_train_setup(cfg, batch, devices=jax.devices()[:1])
    plan = setup.telemetry()
    assert set(plan.metric_names) == {
        "total_loss", "lm_loss", "moe_rows_fill", "moe_rows_overflow",
        "moe_load_max_over_mean"}
    args = (setup.state, jax.tree.map(jnp.asarray, plan.init_ring()), batch,
            setup.scalars(1250), jax.random.key(0))
    with setup.mesh:
        compiled = plan.step_fn.lower(*args).compile()
        state, ring = compiled(*args)
    row = dict(zip(plan.metric_names, np.asarray(ring.buf)[0]))
    assert abs(row["total_loss"] - math.log(250)) < 0.5, row
    assert row["moe_rows_overflow"] == 0 and 0 < row["moe_rows_fill"] <= 1
    assert int(state.step) == 1 and set(state.params) == {"student"}
    backbone = state.params["student"]["backbone"]
    assert "lm_head" not in backbone and "token_embed" in backbone

    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    found = {classify_step_phase(n) for n in names}
    family = {"lm_embed", "sconv_mixer", "full_attn_mixer", "dense_ffn",
              "moe_ffn", "lm_head_loss"}
    assert {p for p, _ in found} - {None} == family | {
        "update", "telemetry_ring"}
    for phase in family - {"lm_embed"}:
        assert {(phase, "fwd"), (phase, "bwd")} <= found, phase
    for phase, inner in (("sconv_mixer", "sconv_chain"),
                         ("full_attn_mixer", "gqa_core"),
                         ("moe_ffn", "moe_route"), ("moe_ffn", "moe_experts")):
        assert any(phase in n and f"/{inner}/" in n for n in names), inner
    # the chain's scope holds the chain and nothing else: no matmul
    assert not any("/sconv_chain/" in n and "dot_general" in n for n in names)
    assert family < set(LM_STEP_PHASES) < set(STEP_PHASES)

    _, wd, _ = build_multiplier_trees(state.params["student"])
    tree = lm_sconv_weights.reference_tree(wd["backbone"])
    flat = jax.tree.leaves(jax.tree.map(
        lambda a, b: (float(a), float(b)), tree, ref.decays(tree)))
    assert all(a == b for a, b in zip(flat[::2], flat[1::2]))
    assert tree["embed"] == 1.0 and tree["layers"][0]["mixer"]["conv"] == 1.0
    assert tree["layers"][1]["ffn"]["router_bias"] == 0.0
    # every leaf of the reference's layout has a group: the conv mixers'
    # theirs, the attention's its own, the tied leaf with the final norm
    paths = lm_sconv_step_check.leaf_paths(tree)
    conv = lm_sconv_step_check.conv_layers(paths)
    assert conv == {"0", "2", "3", "4"}
    groups = {p: lm_sconv_step_check.group_of(p, conv) for p in paths}
    assert set(groups.values()) == set(lm_sconv_step_check.GROUPS)
    assert groups["layers/0/mixer/conv"] == groups["layers/2/mixer/win"] \
        == groups["layers/4/norm1"] == "conv"
    assert groups["layers/1/mixer/wq"] == groups["layers/1/norm1"] \
        == groups["layers/1/mixer/k_norm"] == "mixers"
    assert groups["layers/0/ffn/w12"] == groups["layers/2/norm2"] == "ffn"
    assert groups["layers/2/ffn/router"] == groups["layers/2/ffn/router_bias"] \
        == "router"
    assert groups["embed"] == groups["norm"] == "head_embed"


def test_benchmark_vocabulary_of_the_family_is_the_programs():
    with open(os.path.join(BENCH, "lm_sconv_phases.json")) as f:
        bench = json.load(f)
    named = set(bench["phases"]) | set(bench["inner"])
    named |= {p for sums in bench["metrics"].values() for p, _ in sums}
    named |= {p for p, _ in bench["inner_metrics"].values()}
    assert named <= set(STEP_PHASES), named - set(STEP_PHASES)
    for phase, inner in bench["inner_metrics"].values():
        assert inner in bench["inner"][phase]


def test_config_rules():
    from dinov3_tpu.configs.config import LM_ARCHS, is_lm_arch
    from dinov3_tpu.models import DecoderConfig, LMDecoder, build_backbone

    cfg = tiny_cfg()
    assert is_lm_arch(cfg) and "lfm2_moe" in LM_ARCHS
    model = build_backbone(cfg)
    assert isinstance(model, LMDecoder) and model.embed_dim == 64
    dc = model.cfg
    assert dc.layers == (("conv", "dense"), ("full_attn", "moe")) \
        + (("conv", "moe"),) * 3
    assert (dc.router, dc.gate, dc.router_norm_eps, dc.routed_scaling_factor,
            dc.num_shared_experts, dc.tie_word_embeddings) == (
                "sigmoid", "silu", 1e-6, 1.0, 0, True)
    assert (dc.head_dim, dc.full_attn_rotary, dc.attn_qk_norm, dc.rope_theta,
            dc.short_conv_kernel_size, dc.rms_norm_eps) == (
                16, True, True, 1e6, 3, 1e-5)
    # the other families' full_attn layers neither rotate nor norm
    other = DecoderConfig.from_cfg(load_config(
        os.path.join(REPO, "configs", "train", "smallthinker_ep4.yaml")))
    assert not (other.full_attn_rotary or other.attn_qk_norm
                or other.tie_word_embeddings or other.router_norm_eps)
    two = DecoderConfig.from_cfg(tiny_cfg(["lm.num_dense_layers=2"]))
    assert [f for _, f in two.layers] == ["dense"] * 2 + ["moe"] * 3
    with pytest.raises(ValueError, match="sigmoid router"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.use_expert_bias=false"]))
    with pytest.raises(ValueError, match="conv_bias"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.conv_bias=true"]))
    with pytest.raises(ValueError, match="layer_types"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.num_hidden_layers=4"]))
    with pytest.raises(ValueError, match="layer_types"):
        DecoderConfig.from_cfg(tiny_cfg([
            "lm.layer_types=[conv,swa,conv,conv,conv]"]))
    # the recipe as it stands holds the published widths
    lm = load_config(RECIPE).lm
    assert (lm.hidden_size, lm.intermediate_size, lm.conv_L_cache,
            lm.num_attention_heads, lm.num_key_value_heads, lm.norm_eps) == (
                2048, 11776, 3, 32, 8, 1e-5)
    assert (lm.num_experts, lm.num_experts_per_tok, lm.moe_intermediate_size,
            lm.rope_parameters.rope_theta, lm.seq_len) == (
                64, 4, 1536, 1000000, 8192)
    full = DecoderConfig.from_cfg(load_config(RECIPE))
    assert (full.num_experts // full.expert_shards, full.vocab_size,
            full.head_dim) == (8, 8192, 64)


def test_the_paths_are_read_off_shapes_at_the_published_sizes(caplog):
    """``mixer_chain_path`` and ``causal_attention_path`` at the cell's
    shapes: on a TPU (``interpret=False``: described, not attached) the
    chain of every conv layer and the 64-wide core take the kernels; here,
    on the CPU, the plain paths, and the set-up log says which, a line a
    layer."""
    import logging

    from dinov3_tpu.ops.causal_attention import causal_attention_path
    from dinov3_tpu.ops.mixer_chains import mixer_chain_path
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    shapes = ((4, 8192, 32, 64), (4, 8192, 8, 64), (4, 8192, 8, 64))
    assert mixer_chain_path(8192, (2048,), (), jnp.bfloat16,
                            interpret=False)[0] == "kernel"
    assert causal_attention_path(shapes, None, False)[0] == "kernel"
    assert mixer_chain_path(8192, (2048,), (), jnp.bfloat16)[0] == "plain"
    assert causal_attention_path(shapes)[0] == "tiles"
    # a pair of key/value heads resident: 24,576 tokens fit the backward's
    # VMEM, 32,768 do not
    fits = lambda n: causal_attention_path(  # noqa: E731
        tuple((1, n) + s[2:] for s in shapes), None, False)[0]
    assert (fits(24576), fits(32768)) == ("kernel", "tiles")
    with caplog.at_level(logging.INFO, logger="dinov3"):
        LMMetaArch(load_config(RECIPE))
    said = [r.getMessage() for r in caplog.records]
    assert sum("sconv_chain (conv), both passes: plain" in s for s in said) == 4
    assert sum("gqa_core (full_attn), both passes: tiles" in s for s in said) == 1
    assert sum("moe_experts, both passes: ragged_dot (the backend is cpu, not a "
               "TPU); rows moved by gathers through index lists, the combine "
               "scatter at 4.0 (token, choice) pairs a buffer row" in s
               for s in said) == 4  # a line a routed layer
