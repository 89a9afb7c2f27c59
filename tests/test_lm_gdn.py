"""The ``qwen3_next`` decoder family (PR 35), on the CPU at a small size.

(a) ``GDNMixer`` through ``kda_chunked`` — the plain path, and the kernels
    interpreted at 128 / 128 / 64 on a short row — against the reference's
    token recurrence, output and the gradient of every leaf.
(b) The gated attention (``GQAMixer`` with an output gate, q/k norms and a
    partial rotary) against the reference's masked softmax; a table as
    wide as the head is the rotation ``GQAMixer`` had.
(c) (The whole model against ``benchmark/reference/qwen3_next_fp32.py`` is
    ``tests/test_lm_gdn_benchmark.py``'s, with the benchmark's other files.)
(d) The share tied to the model: the 16 shards' routed parts plus what
    every chip computes alike (the gated shared expert), counted ONCE, add
    up to the uncut reference layer.
(e) The family on the normal path: config rules, one step of
    ``LMMetaArch`` through ``build_train_setup`` with its ring columns and
    param groups, the phases in the compiled step. (Three steps against
    the reference's three, and a whole run of the cell, are
    ``benchmark/tests/test_lm_gdn_rehearsal.py``'s, by hand.)
"""

import dataclasses
import functools
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

RECIPE = os.path.join(REPO, "configs", "train", "qwen3_next_ep16.yaml")
# 4 value heads on 2 key heads of 16; 4 query heads on 2 key/value heads
# of 16, of which the first 4 channels rotate; 16 experts, 4 held
TINY = [
    "lm.hidden_size=64", "lm.linear_num_key_heads=2",
    "lm.linear_num_value_heads=4", "lm.linear_key_head_dim=16",
    "lm.linear_value_head_dim=16", "lm.num_attention_heads=4",
    "lm.num_key_value_heads=2", "lm.head_dim=16", "lm.num_experts=16",
    "lm.num_experts_per_tok=4", "lm.moe_intermediate_size=32",
    "lm.shared_expert_intermediate_size=32", "lm.expert_shards=4",
    "lm.vocab_size=250", "lm.seq_len=100", "train.batch_size_per_device=2",
    "telemetry.flush_every=2"]


def tiny_cfg(extra=()):
    return load_config(RECIPE, overrides=[*TINY, *extra])


def _reference_shape(dc, first_expert=0):
    from reference import qwen3_next_fp32 as ref

    return ref.Shape(
        layers=dc.layers, gdn_key_heads=dc.linear_num_key_heads,
        gdn_value_heads=dc.linear_num_value_heads,
        gdn_key_dim=dc.linear_key_head_dim, heads=dc.num_attention_heads,
        kv_heads=dc.num_key_value_heads, rotary_dim=dc.rotary_dim or dc.head_dim,
        rope_theta=dc.rope_theta, top_k=dc.num_experts_per_token,
        first_expert=first_expert, eps=dc.rms_norm_eps)


def _rel(got, want):
    return jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b))
        / max(float(jnp.linalg.norm(b)), 1e-30), got, want)


def _spread(params, key, scale=0.3):
    """Weights large enough that every rule moves the output by far more
    than float32's rounding (norm scales and the decays' leaves as they
    were made)."""
    import flax.linen as nn

    flat, treedef = jax.tree_util.tree_flatten_with_path(nn.meta.unbox(params))
    out = []
    for i, (path, leaf) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        out.append(leaf if leaf.ndim == 1 and name != "conv" else scale
                   * jax.random.normal(jax.random.fold_in(key, i), leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------- (a) the Gated DeltaNet mixer ----------------

@pytest.mark.parametrize("hk, hv, dk, dv, t, interpret", [
    (2, 4, 16, 32, 100, False),    # the plain path, a tail of 36 tokens
    (1, 2, 128, 128, 128, True),   # the kernels, interpreted, two chunks
], ids=["plain", "kernels"])
def test_gdn_mixer_is_the_token_recurrence(monkeypatch, hk, hv, dk, dv, t,
                                           interpret):
    import lm_gdn_weights
    from reference import qwen3_next_fp32 as ref

    from dinov3_tpu.models import decoder
    from dinov3_tpu.ops.kda import kda_chunked, kda_path

    if interpret:  # the test steers; the program has no option
        assert kda_path(dk, dv, interpret=True)[0] == "kernel"
        monkeypatch.setattr(decoder, "kda_chunked", functools.partial(
            kda_chunked, interpret=True))
    else:
        assert kda_path(dk, dv)[0] == "scan"
    d = 32
    mixer = decoder.GDNMixer(hk, hv, dk, dv, 4, 1e-6, dtype=jnp.float32)
    ks = jax.random.split(jax.random.key(t + hv), 3)
    x = jax.random.normal(ks[0], (1 if interpret else 2, t, d))
    params = _spread(jax.jit(mixer.init)(ks[1], x)["params"], ks[2])
    assert params["A_log"].shape == params["dt_bias"].shape == (hv,)
    assert float(jnp.min(params["dt_bias"])) == 1.0
    assert params["in_proj_qkvz"]["kernel"].shape == (d, 2 * hk * dk + 2 * hv * dv)
    assert params["conv"].shape == (4, 2 * hk * dk + hv * dv)
    shape = ref.Shape(layers=(), gdn_key_heads=hk, gdn_value_heads=hv,
                      gdn_key_dim=dk, heads=1, kv_heads=1, rotary_dim=2,
                      rope_theta=1.0, top_k=1, first_expert=0)
    rename = lambda p: {k: lm_gdn_weights._get(p, path)  # noqa: E731
                        for k, path in lm_gdn_weights._GDN.items()}

    def both(fn):
        return jax.jit(lambda p, x: (fn(p, x), jax.grad(
            lambda p, x: jnp.sum(jnp.sin(fn(p, x))), argnums=(0, 1))(p, x)))

    with jax.default_matmul_precision("highest"):
        got = both(lambda p, x: mixer.apply({"params": p}, x))(params, x)
        want = both(lambda p, x: ref.gdn(x, rename(p), shape, "fp32"))(params, x)
        planted = ref.gdn(x, rename(params), shape, "no_decay")
    rel = _rel((got[0], rename(got[1][0]), got[1][1]),
               (want[0], rename(want[1][0]), want[1][1]))
    assert max(jax.tree.leaves(rel)) < 2e-4, rel
    assert min(float(jnp.linalg.norm(g)) for g in jax.tree.leaves(got[1])) > 0
    # the gate is not idle at these values: without it the output moves
    assert float(jnp.max(jnp.abs(planted - want[0]))) > 1e-2


# ---------------- (b) the gated attention ----------------

@pytest.mark.parametrize("rotary", [4, 16], ids=["quarter", "whole_head"])
def test_gated_attention_is_the_masked_softmax(rotary):
    import lm_gdn_weights
    from reference import qwen3_next_fp32 as ref

    from dinov3_tpu.models.decoder import GQAMixer

    h, hk, d, t, width = 4, 2, 16, 70, 32
    from dinov3_tpu.ops.norms import RMSNorm

    mixer = GQAMixer(
        h, hk, d, None, 1e7, rotary, True,
        lambda name: RMSNorm(epsilon=1e-6, zero_centered=True, name=name),
        dtype=jnp.float32)
    ks = jax.random.split(jax.random.key(rotary), 3)
    x = jax.random.normal(ks[0], (2, t, width))
    params = _spread(jax.jit(mixer.init)(ks[1], x)["params"], ks[2])
    assert params["q_proj"]["kernel"].shape == (width, h * 2 * d)
    # zero-centred scales start at 0: give them values, so that 1 + w shows
    params["q_norm"]["scale"] = 0.3 * jax.random.normal(ks[2], (d,))
    params["k_norm"]["scale"] = 0.3 * jax.random.normal(ks[1], (d,))
    shape = ref.Shape(layers=(), gdn_key_heads=1, gdn_value_heads=1,
                      gdn_key_dim=1, heads=h, kv_heads=hk, rotary_dim=rotary,
                      rope_theta=1e7, top_k=1, first_expert=0)
    rename = lambda p: {k: lm_gdn_weights._get(p, path)  # noqa: E731
                        for k, path in lm_gdn_weights._ATTN.items()}

    def both(fn):
        return jax.jit(lambda p, x: (fn(p, x), jax.grad(
            lambda p, x: jnp.sum(jnp.sin(fn(p, x))), argnums=(0, 1))(p, x)))

    with jax.default_matmul_precision("highest"):
        got = both(lambda p, x: mixer.apply({"params": p}, x))(params, x)
        want = both(lambda p, x: ref.attention(x, rename(p), shape))(params, x)
        np.testing.assert_allclose(got[0], want[0], atol=2e-5)
        rel = _rel(got[1], want[1])
        assert max(jax.tree.leaves(rel)) < 1e-4, rel
        # each rule moves the output: another width of the rotation, a
        # scale of 1 + w read as w
        other = ref.attention(x, rename(params), dataclasses.replace(
            shape, rotary_dim=8))
        assert float(jnp.max(jnp.abs(other - want[0]))) > 1e-3
        if rotary == d:
            # a table as wide as the head is the rotation GQAMixer had
            plain = dict(rotary_dim=None, output_gate=False, qk_norm=None)
            old = GQAMixer(h, hk, d, None, 1e7, dtype=jnp.float32, **plain)
            new = GQAMixer(h, hk, d, None, 1e7, dtype=jnp.float32,
                           **{**plain, "rotary_dim": d})
            p = jax.jit(old.init)(ks[1], x)
            np.testing.assert_array_equal(old.apply(p, x), new.apply(p, x))


def test_partial_rotary_turns_the_leading_channels_alone():
    from dinov3_tpu.ops.rope import (
        rope_apply_full,
        rope_apply_leading,
        token_rope_sincos,
    )

    q = jax.random.normal(jax.random.key(0), (1, 9, 2, 16))
    k = jax.random.normal(jax.random.key(1), (1, 9, 1, 16))
    sin, cos = token_rope_sincos(9, 4, 1e7)
    rq, rk = rope_apply_leading(q, k, sin, cos)
    np.testing.assert_array_equal(rq[..., 4:], q[..., 4:])
    np.testing.assert_array_equal(rk[..., 4:], k[..., 4:])
    want_q, want_k = rope_apply_full(q[..., :4], k[..., :4], sin, cos)
    np.testing.assert_array_equal(rq[..., :4], want_q)
    np.testing.assert_array_equal(rk[..., :4], want_k)
    # channel j pairs with j + 2 inside the 4, at t x theta^(-2j/4)
    t, j = 5, 1
    angle = t * 1e7 ** (-2 * j / 4)
    want = q[0, t, 0, j] * math.cos(angle) - q[0, t, 0, j + 2] * math.sin(angle)
    assert float(rq[0, t, 0, j]) == pytest.approx(float(want), abs=1e-6)
    full = token_rope_sincos(9, 16, 1e7)
    for a, b in zip(rope_apply_leading(q, k, *full), rope_apply_full(q, k, *full)):
        np.testing.assert_array_equal(a, b)


def test_zero_centred_norm():
    from dinov3_tpu.ops.norms import RMSNorm

    x = jax.random.normal(jax.random.key(0), (3, 8))
    import flax.linen as nn

    plain, centred = RMSNorm(), RMSNorm(zero_centered=True)
    p0, p1 = (nn.meta.unbox(m.init(jax.random.key(1), x))
              for m in (plain, centred))
    assert float(jnp.max(jnp.abs(p1["params"]["scale"]))) == 0.0
    assert float(jnp.min(p0["params"]["scale"])) == 1.0
    np.testing.assert_allclose(centred.apply(p1, x), plain.apply(p0, x), atol=1e-7)
    w = jax.random.normal(jax.random.key(2), (8,))
    np.testing.assert_allclose(
        centred.apply({"params": {"scale": w}}, x),
        plain.apply({"params": {"scale": 1.0 + w}}, x), atol=1e-6)


# ---------------- (d) the share tied to the model ----------------

def test_all_shards_and_the_shared_expert_once_make_the_uncut_layer():
    """Guide section 4: at a small size, the parts of the result that all
    16 shards give, with what every chip computes alike — the mixer, the
    residual stream and the GATED shared expert — counted once, add up to
    the uncut reference layer. What every chip computes alike is the
    program's own layer with its held experts' output matrices at zero;
    a shard's routed part is the program's routed layer on that layer's
    own normed stream."""
    import lm_gdn_weights
    from reference import qwen3_next_fp32 as ref

    from dinov3_tpu.models.decoder import DecoderConfig, DecoderLayer
    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    shards, held, d = 16, 2, 32
    e = shards * held
    dc = DecoderConfig.from_cfg(tiny_cfg([
        "compute_precision.compute_dtype=fp32", f"lm.hidden_size={d}",
        f"lm.num_experts={e}", f"lm.expert_shards={shards}"]))
    kinds = ("gated_attn", "moe")
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (2, 24, d))
    layer = DecoderLayer(*kinds, dc)
    params = _spread(jax.jit(layer.init)(ks[1], x)["params"], ks[2])
    params["shared_gate"]["kernel"] = jax.random.normal(ks[3], (d, 1))
    router = params["experts"]["router"]
    full = {"w12": 0.3 * jax.random.normal(ks[1], (e, d, 64)),
            "w3": 0.3 * jax.random.normal(ks[2], (e, 32, d))}

    def held_by(shard, w3_scale=1.0):
        own = slice(shard * held, (shard + 1) * held)
        return {"router": router, "w12": full["w12"][own],
                "w3": w3_scale * full["w3"][own]}

    def whole(shard, experts):
        """The program's layer as shard ``shard`` runs it, and the normed
        stream its experts read."""
        (y, _), seen = DecoderLayer(*kinds, dataclasses.replace(
            dc, expert_shard=shard)).apply(
                {"params": {**params, "experts": experts}}, x,
                capture_intermediates=lambda m, _: m.name == "norm2",
                mutable=["intermediates"])
        return y, seen["intermediates"]["norm2"]["__call__"][0]

    def routed_part(shard, experts, u):
        return RoutedExpertsFFN(
            dc.moe_intermediate_size, e, dc.num_experts_per_token, shards,
            shard, router="softmax", gate="silu", dtype=jnp.float32).apply(
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
        # a shard's own layer is what is alike + its part
        own, _ = jax.jit(whole, static_argnums=0)(3, held_by(3))
        np.testing.assert_allclose(
            own, alike + routed_part(3, held_by(3), u)[0], atol=1e-5)
        uncut = lm_gdn_weights.reference_tree(
            {"layers_0": {**params, "experts": {"router": router, **full}},
             "token_embed": 0, "lm_head": 0, "norm": {"scale": 0}})["layers"][0]
        shape = _reference_shape(dc)
        reference = jax.jit(lambda lw: ref.layer(x, lw, kinds, shape, None, "fp32"))
        want, agree = reference(uncut)
        # the shared expert is not idle, and its gate neither
        moved = reference(dict(uncut, ffn=dict(
            uncut["ffn"], shared_gate=jnp.zeros((d, 1)))))[0]
    assert float(agree) == 1.0
    for c in choices[1:]:  # every shard routes over all the experts alike
        np.testing.assert_array_equal(c, choices[0])
    assert len({int(v) // held for v in choices[0].reshape(-1)}) > 8
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(moved - want))) > 1e-2
    assert float(jnp.max(jnp.abs(alike - want))) > 1e-2


# ---------------- (e) the family on the normal path ----------------

def test_one_compiled_step_its_phases_and_param_groups():
    """One step of ``LMMetaArch`` on the recipe at test width, through
    ``build_train_setup`` and the telemetry step ``do_train`` runs: the
    family's phases in the compiled text, a finite loss near
    log(vocabulary) in the ring's row, no overflow; and the decay
    multipliers of ``build_multiplier_trees`` are the reference's (none on
    the norms' scales, ``A_log`` and ``dt_bias``)."""
    import lm_gdn_weights
    import lm_step_check
    from reference import qwen3_next_fp32 as ref

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

    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    found = {classify_step_phase(n) for n in names}
    family = {"lm_embed", "gdn_mixer", "gated_attn_mixer", "moe_ffn",
              "lm_head_loss"}
    assert {p for p, _ in found} - {None} == family | {
        "update", "telemetry_ring"}
    for phase in family - {"lm_embed"}:
        assert {(phase, "fwd"), (phase, "bwd")} <= found, phase
    for phase, inner in (("gdn_mixer", "gdn_core"),
                         ("gated_attn_mixer", "gqa_core"),
                         ("moe_ffn", "moe_route"), ("moe_ffn", "moe_experts"),
                         ("moe_ffn", "moe_shared")):
        assert any(phase in n and f"/{inner}/" in n for n in names), inner
    # the shared expert's gate stands under moe_shared with the expert
    assert any("/moe_shared/shared_gate/dot_general" in n for n in names)
    assert family < set(LM_STEP_PHASES) < set(STEP_PHASES)

    _, wd, _ = build_multiplier_trees(state.params["student"])
    tree = lm_gdn_weights.reference_tree(wd["backbone"])
    flat = jax.tree.leaves(jax.tree.map(
        lambda a, b: (float(a), float(b)), tree, ref.decays(tree)))
    assert all(a == b for a, b in zip(flat[::2], flat[1::2]))
    assert 0.0 in flat and 1.0 in flat
    # every leaf of the reference's layout has a group, the routers theirs
    groups = {p: lm_step_check.group_of(p) for p in lm_step_check.leaf_paths(tree)}
    assert set(groups.values()) == set(lm_step_check.GROUPS)
    assert groups["layers/2/ffn/router"] == "router"
    assert groups["layers/1/ffn/shared_gate"] == "ffn"
    assert groups["layers/0/mixer/A_log"] == groups["layers/3/mixer/q_norm"] \
        == groups["layers/3/norm1"] == "mixers"


def test_benchmark_vocabulary_of_the_family_is_the_programs():
    with open(os.path.join(BENCH, "lm_gdn_phases.json")) as f:
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
    assert is_lm_arch(cfg) and "qwen3_next" in LM_ARCHS
    model = build_backbone(cfg)
    assert isinstance(model, LMDecoder) and model.embed_dim == 64
    dc = model.cfg
    assert dc.layers == (("gdn", "moe"),) * 3 + (("gated_attn", "moe"),)
    assert (dc.router, dc.gate, dc.router_reads_layer_input,
            dc.num_shared_experts, dc.shared_expert_gate) == (
                "softmax", "silu", False, 1, True)
    assert (dc.rotary_dim, dc.zero_centered_norms, dc.expert_rows_factor) == (
        4, True, load_config(RECIPE).lm.expert_rows_factor)
    eight = DecoderConfig.from_cfg(tiny_cfg(["lm.num_hidden_layers=8"]))
    assert [m for m, _ in eight.layers] == (["gdn"] * 3 + ["gated_attn"]) * 2
    with pytest.raises(ValueError, match="softmax router"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.norm_topk_prob=false"]))
    with pytest.raises(ValueError, match="routed"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.mlp_only_layers=[1]"]))
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.partial_rotary_factor=0.3"]))
    with pytest.raises(ValueError, match="multiple"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.shared_expert_intermediate_size=48"]))
    # the recipe as it stands holds the published widths
    lm = load_config(RECIPE).lm
    assert (lm.hidden_size, lm.linear_num_key_heads, lm.linear_num_value_heads,
            lm.linear_key_head_dim, lm.linear_value_head_dim,
            lm.linear_conv_kernel_dim) == (2048, 16, 32, 128, 128, 4)
    assert (lm.num_attention_heads, lm.num_key_value_heads, lm.head_dim,
            lm.partial_rotary_factor, lm.rope_theta) == (16, 2, 256, 0.25, 10000000)
    assert (lm.num_experts, lm.num_experts_per_tok, lm.moe_intermediate_size,
            lm.shared_expert_intermediate_size, lm.rms_norm_eps,
            lm.seq_len) == (512, 10, 512, 512, 1e-6, 8192)
    full = DecoderConfig.from_cfg(load_config(RECIPE))
    assert (full.num_experts // full.expert_shards, full.vocab_size,
            full.rotary_dim) == (32, 18992, 64)


def test_the_paths_are_read_off_shapes_at_the_published_sizes():
    """``kda_path`` and ``causal_attention_path`` at the cell's shapes: on
    a TPU (``interpret=False``: described, not attached) both cores take
    the kernels; here, on the CPU, the plain paths, and the set-up log
    says which."""
    from dinov3_tpu.ops.causal_attention import causal_attention_path
    from dinov3_tpu.ops.kda import kda_path

    shapes = ((2, 8192, 16, 256), (2, 8192, 2, 256), (2, 8192, 2, 256))
    assert kda_path(128, 128, interpret=False, gate_heads=(16, 32)) == (
        "kernel", "scalar gate, compiled for the TPU")
    assert causal_attention_path(shapes, None, False)[0] == "kernel"
    assert kda_path(128, 128, gate_heads=(16, 32))[0] == "scan"
    assert causal_attention_path(shapes)[0] == "tiles"
    # ONE row of 16,384 tokens would not fit the backward's VMEM
    one_row = tuple((1, 16384) + s[2:] for s in shapes)
    path, why = causal_attention_path(one_row, None, False)
    assert path == "tiles" and "64 MiB" in why
