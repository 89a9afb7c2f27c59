"""The ``deepseek_v3`` decoder family (PR 45), on the CPU at a small size.

(a) The interleaved turn (``ops/rope.py rope_apply_interleaved``) against
    complex multiplication, on a slice of a head and on a one-head key;
    relative position: the scores do not change when every position moves
    by a constant.
(b) ``MLAMixer``'s rotary arm against the reference's ``attention``, value
    and every leaf's gradient (the two controls of the turn differ); its
    ``rope_theta`` None arm bit-equal to the lines the parent had.
(c) ``MLAMixer`` at 128 | 64 | 128 on the latent kernel pair,
    interpreted (``core_interpret``: the test's switch), against its plain
    arm, value and every leaf's gradient, turned and unturned; the turn
    that leaves each result where its channel was is a permutation of
    the interleaved one.
(d) The routed layer's rule: the bias moves the choice and not the
    weight; the 1e-20; the 2.448. Two shared experts are one MLP of twice
    the width.
(e) The share tied to the model: the 8 shards' routed parts + the shared
    part counted once add up to the uncut reference layer.
(f) The family on the normal path: config rules, one step of
    ``LMMetaArch`` through ``build_train_setup`` with its ring columns and
    param groups, the phases in the compiled step, the paths at the
    published sizes. (The whole model against
    ``benchmark/reference/kanana2_fp32.py`` is
    ``tests/test_lm_mla_benchmark.py``'s; three steps against the
    reference's three, and a whole run of the cell, are
    ``benchmark/tests/test_lm_mla_rehearsal.py``'s, by hand.)
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

RECIPE = os.path.join(REPO, "configs", "train", "kanana2_ep8.yaml")
# 4 heads of 16 + 8 | 16 on a 32-wide latent; 16 experts, 4 held, 3 a token
TINY = [
    "lm.hidden_size=64", "lm.intermediate_size=96", "lm.num_attention_heads=4",
    "lm.kv_lora_rank=32", "lm.qk_nope_head_dim=16", "lm.qk_rope_head_dim=8",
    "lm.v_head_dim=16", "lm.n_routed_experts=16", "lm.num_experts_per_tok=3",
    "lm.moe_intermediate_size=32", "lm.expert_shards=4", "lm.vocab_size=250",
    "lm.num_hidden_layers=3", "lm.seq_len=100",
    "train.batch_size_per_device=2", "telemetry.flush_every=2"]


def tiny_cfg(extra=()):
    return load_config(RECIPE, overrides=[*TINY, *extra])


def reference_shape(dc, first_expert=0):
    from reference import kanana2_fp32 as ref

    return ref.Shape(
        layers=dc.layers, heads=dc.num_attention_heads,
        kv_lora_rank=dc.kv_lora_rank, qk_nope_head_dim=dc.qk_nope_head_dim,
        qk_rope_head_dim=dc.qk_rope_head_dim, v_head_dim=dc.v_head_dim,
        rope_theta=dc.rope_theta, top_k=dc.num_experts_per_token,
        routed_scaling_factor=dc.routed_scaling_factor,
        first_expert=first_expert, eps=dc.rms_norm_eps)


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


# ---------------- (a) the interleaved turn ----------------

@pytest.mark.parametrize("heads, width", [(3, 24), (1, 8)],
                         ids=["slice_of_a_head", "one_head_key"])
def test_interleaved_turn_is_a_complex_multiplication(heads, width):
    """Channels (2i, 2i + 1) of the trailing 8 are the real and imaginary
    part of one number, multiplied by exp(i t theta^(-2i/8)); the results
    come back reals before imaginaries, the leading channels untouched."""
    from dinov3_tpu.ops.rope import rope_apply_interleaved, token_rope_pair_sincos

    n, rope, theta = 37, 8, 1e6
    x = jax.random.normal(jax.random.key(0), (2, n, heads, width))
    sin, cos = token_rope_pair_sincos(n, rope, theta)
    assert sin.shape == cos.shape == (n, rope // 2)
    got = np.asarray(rope_apply_interleaved(x, sin, cos), np.float64)
    z = np.asarray(x[..., width - rope:], np.float64)
    angle = np.arange(n)[:, None] * theta ** (-np.arange(0, rope, 2) / rope)
    turned = (z[..., 0::2] + 1j * z[..., 1::2]) \
        * np.exp(1j * angle)[None, :, None, :]
    np.testing.assert_allclose(got[..., width - rope:width - rope // 2],
                               turned.real, atol=2e-5)
    np.testing.assert_allclose(got[..., width - rope // 2:], turned.imag,
                               atol=2e-5)
    np.testing.assert_array_equal(got[..., :width - rope],
                                  np.asarray(x[..., :width - rope], np.float64))
    # a turn keeps every pair's length; token 0 is not turned at all
    np.testing.assert_allclose(np.abs(turned), np.hypot(
        got[..., width - rope:width - rope // 2], got[..., width - rope // 2:]),
        atol=2e-5)
    np.testing.assert_allclose(got[:, 0, :, width - rope:], np.concatenate(
        [z[:, 0, :, 0::2], z[:, 0, :, 1::2]], -1), atol=1e-6)
    # bfloat16 ends, float32 in between
    half = rope_apply_interleaved(x.astype(jnp.bfloat16), sin, cos)
    assert half.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(half, np.float64), got, atol=0.05)
    with pytest.raises(ValueError, match="turned channels"):
        rope_apply_interleaved(x[..., :rope - 2], sin, cos)


def test_scores_depend_on_relative_position_alone():
    """q at t + c against the shared key at s + c gives the score of q at
    t against the key at s, whatever c: a table's rows c.. in place of
    rows 0..; and a score moves when only ONE side moves."""
    from dinov3_tpu.ops.rope import rope_apply_interleaved, token_rope_pair_sincos

    n, rope, shift = 16, 8, 1000
    ks = jax.random.split(jax.random.key(1), 2)
    q = jax.random.normal(ks[0], (1, n, 4, 24))
    kpe = jax.random.normal(ks[1], (1, n, 1, rope))
    sin, cos = token_rope_pair_sincos(n + shift, rope, 1e4)

    def scores(q_from, k_from):
        tq = rope_apply_interleaved(q, sin[q_from:q_from + n], cos[q_from:q_from + n])
        tk = rope_apply_interleaved(kpe, sin[k_from:k_from + n], cos[k_from:k_from + n])
        return jnp.einsum("bqhd,bkd->bhqk", tq[..., -rope:], tk[:, :, 0])

    np.testing.assert_allclose(scores(shift, shift), scores(0, 0), atol=2e-4)
    assert float(jnp.max(jnp.abs(scores(shift, 0) - scores(0, 0)))) > 0.1


# ---------------- (b) the mixer ----------------

def _parent_mla(params, x, h, rank, nope, rope, dv, eps):
    """``MLAMixer.__call__`` as the parent of PR 45 had it (no turn)."""
    from dinov3_tpu.ops.attention import dispatch_attention

    b, t, _ = x.shape
    q = (x @ params["q_proj"]["kernel"]).reshape(b, t, h, nope + rope)
    kva = x @ params["kv_a"]["kernel"]
    c, kpe = kva[..., :rank], kva[..., rank:]
    c = (c * jax.lax.rsqrt(jnp.mean(jnp.square(c), -1, keepdims=True) + eps)
         * params["kv_a_norm"]["scale"])
    kvb = (c @ params["kv_b"]["kernel"]).reshape(b, t, h, nope + dv)
    k = jnp.concatenate([
        kvb[..., :nope],
        jnp.broadcast_to(kpe[:, :, None, :], (b, t, h, rope))], axis=-1)
    o = dispatch_attention(q, k, kvb[..., nope:], causal=True,
                           reduce_dtype=jnp.float32)
    return o.reshape(b, t, h * dv) @ params["o_proj"]["kernel"]


def test_mla_mixer_turns_as_the_reference_and_not_at_all_without_theta():
    import flax.linen as nn
    import lm_mla_weights
    from reference import kanana2_fp32 as ref

    from dinov3_tpu.models.decoder import MLAMixer

    d, t, h, rank, nope, rope, dv = 32, 100, 4, 16, 8, 8, 8
    mixer = MLAMixer(h, rank, nope, rope, dv, 1e-6, 1e6, dtype=jnp.float32)
    ks = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(ks[0], (2, t, d))
    params = spread(nn.meta.unbox(jax.jit(mixer.init)(ks[1], x)["params"]), ks[2])
    assert params["q_proj"]["kernel"].shape == (d, h * (nope + rope))
    assert params["kv_a"]["kernel"].shape == (d, rank + rope)
    shape = ref.Shape(layers=(), heads=h, kv_lora_rank=rank,
                      qk_nope_head_dim=nope, qk_rope_head_dim=rope,
                      v_head_dim=dv, rope_theta=1e6, top_k=1,
                      routed_scaling_factor=1.0, first_expert=0)

    rename = lambda p: {k: lm_mla_weights._get(p, path)  # noqa: E731
                        for k, path in lm_mla_weights._MLA.items()}

    def both(fn):
        return jax.jit(lambda p, x: (fn(p, x), jax.grad(
            lambda p, x: jnp.sum(jnp.sin(fn(p, x))), argnums=(0, 1))(p, x)))

    with jax.default_matmul_precision("highest"):
        y, (gp, gx) = both(lambda p, x: mixer.apply({"params": p}, x))(params, x)
        for variant, same in (("fp32", True), ("no_rope", False),
                              ("rotate_half", False)):
            want, (wp, wx) = both(lambda p, x, v=variant: ref.attention(
                x, rename(p), shape, v))(params, x)
            named = _rel(rename(gp), rename(wp))
            gaps = [_rel(y, want), _rel(gx, wx), *named.values()]
            assert (max(gaps) < 2e-5) == same, (variant, gaps)
            if not same:   # the output and both leaves the turn acts on move
                assert min(gaps[0], named["wq"], named["wkva"]) > 0.05, gaps
        # rope_theta None: the parent's lines, bit for bit
        plain = MLAMixer(h, rank, nope, rope, dv, 1e-6, dtype=jnp.float32)
        assert plain.rope_theta is None
        got = jax.jit(lambda p, x: plain.apply({"params": p}, x))(params, x)
        want = jax.jit(lambda p, x: _parent_mla(
            p, x, h, rank, nope, rope, dv, 1e-6))(params, x)
    np.testing.assert_array_equal(got, want)
    assert _rel(got, y) > 0.05


# ---------------- (c) 128 | 64 | 128 on the latent kernel pair ----------------

@pytest.mark.parametrize("theta", [None, 1e6], ids=["unturned", "turned"])
def test_mixer_on_the_interpreted_latent_pair_is_its_plain_arm(theta):
    """2 heads of 128 | 64 | 128 over 2 rows of 2,048 tokens (the shipped
    blocks: two query blocks, two key tiles), float32: the kernel arm
    reads q, kvb and the shared key as the projections leave them (no key
    a head, nothing padded) under the SAME parameter tree."""
    import flax.linen as nn

    from dinov3_tpu.models.decoder import MLAMixer
    from dinov3_tpu.ops.causal_attention import latent_attention_path

    kw = dict(num_heads=2, kv_lora_rank=32, qk_nope_head_dim=128,
              qk_rope_head_dim=64, v_head_dim=128, eps=1e-6, rope_theta=theta,
              dtype=jnp.float32)
    plain, pair = MLAMixer(**kw), MLAMixer(**kw, core_interpret=True)
    assert latent_attention_path(2048, 2, (128, 64, 128), None,
                                 dtype=jnp.float32)[0] == "tiles"
    assert latent_attention_path(2048, 2, (128, 64, 128), True,
                                 dtype=jnp.float32) == ("kernel", "interpreted")
    ks = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(ks[0], (2, 2048, 64))
    params = spread(nn.meta.unbox(jax.jit(plain.init)(ks[1], x)["params"]), ks[2])
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        jnp.shape, nn.meta.unbox(jax.eval_shape(pair.init, ks[1], x)["params"]))

    def both(mixer):
        fn = lambda p, x: mixer.apply({"params": p}, x)  # noqa: E731
        return jax.jit(lambda p, x: (fn(p, x), jax.grad(
            lambda p, x: jnp.sum(jnp.sin(fn(p, x))), argnums=(0, 1))(p, x)))(
                params, x)

    with jax.default_matmul_precision("highest"):
        (y, (gp, gx)), (want, (wp, wx)) = both(pair), both(plain)
    assert set(wp) == {"q_proj", "kv_a", "kv_a_norm", "kv_b", "o_proj"}
    gaps = {"y": _rel(y, want), "dx": _rel(gx, wx),
            **{k: jax.tree.leaves(_rel(gp[k], wp[k]))[0] for k in wp}}
    assert max(gaps.values()) < 2e-5, gaps


def test_turn_in_place_is_a_permutation_of_the_interleaved_turn():
    from dinov3_tpu.ops.rope import (
        rope_apply_interleaved,
        rope_apply_pairs,
        token_rope_pair_sincos,
    )

    x = jax.random.normal(jax.random.key(2), (2, 50, 64))
    table = token_rope_pair_sincos(50, 64, 1e6)
    there = rope_apply_interleaved(x[:, :, None, :], *table)[:, :, 0]
    here = rope_apply_pairs(x, *table)
    np.testing.assert_array_equal(here[..., 0::2], there[..., :32])
    np.testing.assert_array_equal(here[..., 1::2], there[..., 32:])
    low = rope_apply_pairs(x.astype(jnp.bfloat16), *table)
    assert low.dtype == jnp.bfloat16


# ---------------- (d) the routed layer's rule, the shared experts ----------------

def test_router_bias_moves_the_choice_and_not_the_weight():
    from reference import kanana2_fp32 as ref

    from dinov3_tpu.models.decoder import DEEPSEEK_V3_ROUTER_EPS
    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    d, e, k, scale = 16, 8, 2, 2.448
    assert DEEPSEEK_V3_ROUTER_EPS == ref.ROUTER_EPS == 1e-20
    layer = RoutedExpertsFFN(8, e, k, 1, 0, scale, router="sigmoid",
                             dtype=jnp.float32, norm_eps=DEEPSEEK_V3_ROUTER_EPS)
    ks = jax.random.split(jax.random.key(11), 3)
    x = jax.random.normal(ks[0], (64, d))
    params = spread(jax.jit(layer.init)(ks[1], x)["params"], ks[2])
    params["router_bias"] = jnp.zeros((e,))
    shape = ref.Shape(layers=(), heads=1, kv_lora_rank=1, qk_nope_head_dim=1,
                      qk_rope_head_dim=2, v_head_dim=1, rope_theta=1.0, top_k=k,
                      routed_scaling_factor=scale, first_expert=0)
    run = jax.jit(lambda p: layer.apply({"params": p}, x))
    with jax.default_matmul_precision("highest"):
        _, aux0 = run(params)
        # a bias on expert 5 large enough that every token chooses it
        biased = {**params, "router_bias": params["router_bias"].at[5].set(2.0)}
        y1, aux1 = run(biased)
        choice, weight, agree = jax.jit(
            lambda f: ref.route(x, f, shape))(biased)
        # (the reference's ``experts`` adds the shared part: none here)
        zero = {"w12": jnp.zeros((d, 4)), "w3": jnp.zeros((2, d))}
        want, _ = jax.jit(lambda f: ref.experts(
            x, {**f, "shared": zero}, shape, None, "fp32"))(biased)
    assert np.all(np.any(np.asarray(aux1["choice"]) == 5, -1))
    assert not np.all(np.any(np.asarray(aux0["choice"]) == 5, -1))
    np.testing.assert_array_equal(np.sort(aux1["choice"], -1), np.sort(choice, -1))
    assert float(agree) == 1.0
    # the weights are the SCORES', not score + bias, and add up to the
    # 2.448 a token: 1e-20 is under float32's rounding of any such sum
    scores = jax.nn.sigmoid(x @ params["router"])
    picked = jnp.take_along_axis(scores, choice, -1)
    np.testing.assert_allclose(
        weight, scale * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weight.sum(-1), scale, rtol=1e-6)
    np.testing.assert_allclose(y1, want, atol=5e-5)
    # ... and keeps a token whose chosen scores are all 0 finite
    assert float(scale * 0.0 / (0.0 + np.float32(DEEPSEEK_V3_ROUTER_EPS))) == 0.0
    # the bias takes no gradient
    g = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(
        layer.apply({"params": p}, x)[0]))))(biased)
    assert float(jnp.max(jnp.abs(g["router_bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g["router"]))) > 0.0


def test_two_shared_experts_are_one_mlp_of_twice_the_width():
    """The layer's ``shared`` leaf at ``n_shared_experts`` 2 is ONE SwiGLU
    of 2 x ``moe_intermediate_size``: what two experts of one width give
    side by side, their gate, value and output blocks joined."""
    from dinov3_tpu.models.decoder import DecoderConfig, DecoderLayer, _swiglu

    dc = DecoderConfig.from_cfg(tiny_cfg(["compute_precision.compute_dtype=fp32"]))
    assert dc.num_shared_experts == 2 and dc.moe_intermediate_size == 32
    x = jax.random.normal(jax.random.key(0), (2, 10, 64))
    layer = DecoderLayer("mla", "moe", dc)
    shapes = jax.tree.map(lambda a: a.shape, jax.eval_shape(
        layer.init, jax.random.key(1), x)["params"]["shared"])
    assert jax.tree.leaves(shapes, is_leaf=lambda s: isinstance(s, tuple)) \
        == [(64, 128), (64, 64)]
    f = 32
    ks = jax.random.split(jax.random.key(2), 4)
    one = [{"w12": {"kernel": 0.3 * jax.random.normal(ks[2 * i], (64, 2 * f))},
            "w3": {"kernel": 0.3 * jax.random.normal(ks[2 * i + 1], (f, 64))}}
           for i in range(2)]
    joined = {
        "w12": {"kernel": jnp.concatenate(
            [one[0]["w12"]["kernel"][:, :f], one[1]["w12"]["kernel"][:, :f],
             one[0]["w12"]["kernel"][:, f:], one[1]["w12"]["kernel"][:, f:]], 1)},
        "w3": {"kernel": jnp.concatenate(
            [one[0]["w3"]["kernel"], one[1]["w3"]["kernel"]], 0)}}
    kw = dict(dtype=jnp.float32, param_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        apart = sum(_swiglu(f, "e", **kw).apply({"params": p}, x) for p in one)
        got = _swiglu(2 * f, "shared", **kw).apply({"params": joined}, x)
    np.testing.assert_allclose(got, apart, atol=1e-5)


# ---------------- (e) the shards' parts add up ----------------

def test_eight_shards_parts_and_the_shared_part_once_add_up_to_the_uncut_layer():
    """What every chip computes alike (the mixer, the residual stream, the
    shared experts) is counted once; a shard's routed part is the
    program's routed layer on that layer's own normed stream."""
    import lm_mla_weights
    from reference import kanana2_fp32 as ref

    from dinov3_tpu.models.decoder import DecoderConfig, DecoderLayer
    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    shards, held, d = 8, 2, 32
    e = shards * held
    dc = DecoderConfig.from_cfg(tiny_cfg([
        "compute_precision.compute_dtype=fp32", f"lm.hidden_size={d}",
        f"lm.n_routed_experts={e}", f"lm.expert_shards={shards}"]))
    kinds = ("mla", "moe")
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
        # the stream, the mixer and the SHARED part, the routed one zeroed
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
            own, alike + routed_part(3, held_by(3), u)[0], atol=2e-5)
        uncut = lm_mla_weights.reference_tree(
            {"layers_0": {**params, "experts": {
                "router": router, "router_bias": bias, **full}},
             "token_embed": 0, "lm_head": 0, "norm": {"scale": 0}})["layers"][0]
        want, agree = jax.jit(lambda lw: ref.layer(
            x, lw, kinds, reference_shape(dc), None, "fp32"))(uncut)
        # the shared part alone is no small share of the layer's write
        no_shared = {**uncut, "ffn": {**uncut["ffn"], "shared": jax.tree.map(
            jnp.zeros_like, uncut["ffn"]["shared"])}}
        bare, _ = jax.jit(lambda lw: ref.layer(
            x, lw, kinds, reference_shape(dc), None, "fp32"))(no_shared)
    assert float(agree) == 1.0
    for c in choices[1:]:  # every shard routes over all the experts alike
        np.testing.assert_array_equal(c, choices[0])
    assert len({int(v) // held for v in choices[0].reshape(-1)}) > 4
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert float(jnp.max(jnp.abs(alike - want))) > 1e-2
    assert float(jnp.max(jnp.abs(bare - want))) > 1e-2


# ---------------- (f) the family on the normal path ----------------

def test_one_compiled_step_its_phases_and_param_groups():
    """One step of ``LMMetaArch`` on the recipe at test width, through
    ``build_train_setup`` and the telemetry step ``do_train`` runs: the
    family's phases in the compiled text, a finite loss near
    log(vocabulary) in the ring's row, no overflow; the decay multipliers
    of ``build_multiplier_trees`` are the reference's (none on the norms'
    scales, the latent's among them, and the selection bias)."""
    import lm_mla_step_check
    import lm_mla_weights
    from reference import kanana2_fp32 as ref

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
    assert {"lm_head", "token_embed"} <= set(backbone)
    assert set(backbone["layers_1"]) == {"norm1", "mla", "norm2", "experts",
                                         "shared"}

    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    found = {classify_step_phase(n) for n in names}
    family = {"lm_embed", "mla_mixer", "dense_ffn", "moe_ffn", "lm_head_loss"}
    assert {p for p, _ in found} - {None} == family | {
        "update", "telemetry_ring"}
    for phase in family - {"lm_embed"}:
        assert {(phase, "fwd"), (phase, "bwd")} <= found, phase
    for phase, inner in (("mla_mixer", "mla_rope"), ("mla_mixer", "mla_core"),
                         ("moe_ffn", "moe_route"), ("moe_ffn", "moe_experts"),
                         ("moe_ffn", "moe_shared")):
        assert any(phase in n and f"/{inner}/" in n for n in names), inner
    # the turn's scope holds the two turns and nothing else: no matmul
    assert not any("/mla_rope/" in n and "dot_general" in n for n in names)
    assert family < set(LM_STEP_PHASES) < set(STEP_PHASES)

    _, wd, _ = build_multiplier_trees(state.params["student"])
    tree = lm_mla_weights.reference_tree(wd["backbone"])
    flat = jax.tree.leaves(jax.tree.map(
        lambda a, b: (float(a), float(b)), tree, ref.decays(tree)))
    assert all(a == b for a, b in zip(flat[::2], flat[1::2]))
    assert tree["embed"] == tree["head"] == 1.0
    assert tree["layers"][0]["mixer"]["kv_norm"] == 0.0
    assert tree["layers"][0]["mixer"]["wkva"] == 1.0
    assert tree["layers"][1]["ffn"]["router_bias"] == 0.0
    assert tree["layers"][1]["ffn"]["shared"]["w3"] == 1.0
    # every leaf of the reference's layout has a group: the two leaves the
    # turn acts on theirs, by themselves
    paths = lm_mla_step_check.leaf_paths(tree)
    groups = {p: lm_mla_step_check.group_of(p) for p in paths}
    assert set(groups.values()) == set(lm_mla_step_check.GROUPS)
    assert {p for p, g in groups.items() if g == "turned"} == {
        f"layers/{i}/mixer/{leaf}" for i in range(3) for leaf in ("wq", "wkva")}
    assert groups["layers/1/mixer/wkvb"] == groups["layers/1/norm1"] \
        == groups["layers/2/mixer/kv_norm"] == groups["layers/0/mixer/wo"] \
        == "mixers"
    assert groups["layers/0/ffn/w12"] == groups["layers/2/norm2"] \
        == groups["layers/1/ffn/shared/w12"] == "ffn"
    assert groups["layers/2/ffn/router"] == groups["layers/2/ffn/router_bias"] \
        == "router"
    assert groups["embed"] == groups["head"] == groups["norm"] == "head_embed"


def test_benchmark_vocabulary_of_the_family_is_the_programs():
    with open(os.path.join(BENCH, "lm_mla_phases.json")) as f:
        bench = json.load(f)
    named = set(bench["phases"]) | set(bench["inner"])
    named |= {p for sums in bench["metrics"].values() for p, _ in sums}
    named |= {p for p, _ in bench["inner_metrics"].values()}
    assert named <= set(STEP_PHASES), named - set(STEP_PHASES)
    for phase, inner in bench["inner_metrics"].values():
        assert inner in bench["inner"][phase]
    # the mixer's whole time is the older reader's, from the older file
    with open(os.path.join(BENCH, "lm_phases.json")) as f:
        assert "lm_mla_ms_per_step" in json.load(f)["metrics"]


@pytest.mark.parametrize("override, named", [
    ("lm.n_group=2", "n_group"), ("lm.topk_group=2", "topk_group"),
    ("lm.q_lora_rank=1536", "q_lora_rank"),
    ("lm.scoring_func=softmax", "scoring_func"),
    ("lm.rope_interleave=false", "rope_interleave"),
    ("lm.moe_layer_freq=2", "moe_layer_freq")])
def test_what_the_family_cannot_run_is_refused_by_name(override, named):
    from dinov3_tpu.models import DecoderConfig

    with pytest.raises(ValueError, match=named):
        DecoderConfig.from_cfg(tiny_cfg([override]))


def test_config_rules():
    from dinov3_tpu.configs.config import LM_ARCHS, is_lm_arch
    from dinov3_tpu.models import DecoderConfig, LMDecoder, build_backbone

    cfg = tiny_cfg()
    assert is_lm_arch(cfg) and "deepseek_v3" in LM_ARCHS
    model = build_backbone(cfg)
    assert isinstance(model, LMDecoder) and model.embed_dim == 64
    dc = model.cfg
    assert dc.layers == (("mla", "dense"),) + (("mla", "moe"),) * 2
    assert (dc.router, dc.gate, dc.router_norm_eps, dc.routed_scaling_factor,
            dc.num_shared_experts, dc.tie_word_embeddings) == (
                "sigmoid", "silu", 1e-20, 2.448, 2, False)
    assert (dc.mla_rotary, dc.rope_theta, dc.rms_norm_eps, dc.kv_lora_rank,
            dc.qk_nope_head_dim, dc.qk_rope_head_dim, dc.v_head_dim,
            dc.expert_rows_factor) == (True, 1e6, 1e-6, 32, 16, 8, 16, 4.0)
    # the other family's latent layer turns nothing
    other = DecoderConfig.from_cfg(load_config(
        os.path.join(REPO, "configs", "train", "kimi_linear_ep32.yaml")))
    assert not (other.mla_rotary or other.router_norm_eps)
    two = DecoderConfig.from_cfg(tiny_cfg(["lm.first_k_dense_replace=2"]))
    assert [f for _, f in two.layers] == ["dense"] * 2 + ["moe"]
    # the recipe as it stands holds the published widths
    lm = load_config(RECIPE).lm
    assert (lm.hidden_size, lm.intermediate_size, lm.num_attention_heads,
            lm.kv_lora_rank, lm.qk_nope_head_dim, lm.qk_rope_head_dim,
            lm.v_head_dim, lm.rms_norm_eps, lm.rope_theta) == (
                2048, 6144, 32, 512, 128, 64, 128, 1e-6, 1000000)
    assert (lm.n_routed_experts, lm.num_experts_per_tok, lm.n_shared_experts,
            lm.moe_intermediate_size, lm.routed_scaling_factor,
            lm.seq_len) == (128, 6, 2, 768, 2.448, 16384)
    full = DecoderConfig.from_cfg(load_config(RECIPE))
    assert (full.num_experts // full.expert_shards, full.vocab_size,
            len(full.layers)) == (16, 16032, 5)


def test_the_paths_are_read_off_shapes_at_the_published_sizes(caplog):
    """``latent_attention_path`` and ``grouped_matmul_path`` at the cells'
    shapes: on a TPU (``interpret=False``: described, not attached) the
    latent core of ONE row of 16,384 tokens at 32 heads of 128 | 64 | 128
    (this recipe's) and of two rows of 8,192 (``kimi_linear``'s) takes the
    latent kernel pair — a key a head at 192 would take the plain tiles:
    the pad arm is gone — and the routed layers their kernels; here, on
    the CPU, the plain paths, and the set-up log says which, a line a
    layer, for both latent recipes."""
    import logging

    from dinov3_tpu.ops import causal_attention
    from dinov3_tpu.ops.causal_attention import (
        causal_attention_path,
        latent_attention_path,
    )
    from dinov3_tpu.ops.ffn import routed_rows_capacity
    from dinov3_tpu.ops.grouped_matmul import grouped_matmul_path
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    widths = (128, 64, 128)
    for n in (16384, 8192):
        assert latent_attention_path(n, 32, widths, False) == (
            "kernel", "compiled for the TPU")
        assert latent_attention_path(n, 32, widths)[0] == "tiles"
    # a pair's dk_nope and dv, float32 and the bfloat16 block they leave
    # in, and the shared key's float32: 56 MiB of the 64 the path allows
    assert 16384 * (512 * 6 + 512) == 56 << 20
    assert causal_attention._LATENT_RESIDENT_BYTES == 64 << 20
    path, why = latent_attention_path(32768, 32, widths, False)
    assert path == "tiles" and "do not fit the backward's VMEM" in why
    a_key_a_head = ((1, 16384, 32, 192),) * 2 + ((1, 16384, 32, 128),)
    assert causal_attention_path(a_key_a_head, None, False)[0] == "tiles"
    # (the recipe's lm.expert_rows_factor: 4.0 even shares of 12,288 rows)
    cap = routed_rows_capacity(16384, 6, 128, 16,
                               load_config(RECIPE).lm.expert_rows_factor)
    assert cap == 49152
    assert grouped_matmul_path(cap, 2048, 768, jnp.bfloat16, False)[0] == "kernel"
    with caplog.at_level(logging.INFO, logger="dinov3"):
        LMMetaArch(load_config(RECIPE))
    said = [r.getMessage() for r in caplog.records]
    assert sum("mla_core (mla), both passes: tiles (the backend is cpu, not a "
               "TPU)" in s for s in said) == 5
    assert sum("moe_experts, both passes: ragged_dot (the backend is cpu, not a "
               "TPU); rows moved by gathers through index lists, the combine "
               "scatter at 2.0 (token, choice) pairs a buffer row" in s
               for s in said) == 4  # a line a routed layer
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="dinov3"):
        LMMetaArch(load_config(os.path.join(
            REPO, "configs", "train", "kimi_linear_ep32.yaml")))
    said = [r.getMessage() for r in caplog.records]
    assert sum("mla_core (mla), both passes: tiles (the backend is cpu, not a "
               "TPU)" in s for s in said) == 1
