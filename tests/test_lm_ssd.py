"""The ``nemotron_h`` decoder family (PR 48), on the CPU at a small size.

(a) The chunked state-space scan (``ops/ssd.py``): the plain path, and
    the kernel pair interpreted at 64-wide heads on a 128-wide state with
    8 heads a group, against the one-token recurrence, value and every
    gradient (the step's and the rate's among them), at chunks that do and
    do not divide the row; two sequences in a batch equal each alone (the
    state is zeroed between them).
(b) ``Mamba2Mixer`` against the reference's ``mamba2``, value and every
    leaf's gradient (``A_log``, ``dt_bias``, ``D``, the taps and the bias
    by name); the convolution's bias and its first three tokens; the
    grouped gated norm, NOT equal to the norm-then-gate order nor to one
    group's B and C for all.
(c) A block of ONE sublayer: one norm, one residual add; the older
    families' tiny steps lowered as the parent lowers them.
(d) The un-gated experts: ``ragged_experts_block`` and the interpreted
    kernels at a hidden width of 1.5 lane tiles against a per-expert loop,
    both passes, beside a gated arm; the router's 2.5 and 1e-20.
(e) The share tied to the model: the 16 shards' routed parts + the shared
    part counted once add up to the uncut reference block.
(f) The family on the normal path: config rules, what it cannot run
    refused by name, one step of ``LMMetaArch`` through
    ``build_train_setup`` with its ring columns and param groups, the
    phases in the compiled step, the paths at the published sizes. (The
    whole model against ``benchmark/reference/nemotron_h_fp32.py`` is
    ``tests/test_lm_ssd_benchmark.py``'s; a whole run of the cell is
    ``benchmark/tests/test_lm_ssd_rehearsal.py``'s, by hand.)
"""

import dataclasses
import importlib
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

RECIPE = os.path.join(REPO, "configs", "train", "nemotron3_nano_ep16.yaml")
# 4 heads of 16 on a state of 32 in 2 groups; 4 | 2 heads of 16; 16
# experts, 4 held, 3 a token
TINY = [
    "lm.hidden_size=64", "lm.mamba_num_heads=4", "lm.mamba_head_dim=16",
    "lm.ssm_state_size=32", "lm.n_groups=2", "lm.num_attention_heads=4",
    "lm.num_key_value_heads=2", "lm.head_dim=16", "lm.n_routed_experts=16",
    "lm.num_experts_per_tok=3", "lm.moe_intermediate_size=24",
    "lm.moe_shared_expert_intermediate_size=48", "lm.expert_shards=4",
    "lm.vocab_size=250", "lm.num_hidden_layers=4",
    "lm.hybrid_override_pattern=ME*E", "lm.seq_len=100",
    "train.batch_size_per_device=2", "telemetry.flush_every=2"]
FP32 = ["compute_precision.compute_dtype=fp32"]


def tiny_cfg(extra=()):
    return load_config(RECIPE, overrides=[*TINY, *extra])


def reference_shape(dc, first_expert=0):
    from reference import nemotron_h_fp32 as ref

    return ref.Shape(
        layers=dc.layers, heads=dc.num_attention_heads,
        kv_heads=dc.num_key_value_heads, mamba_heads=dc.mamba_num_heads,
        mamba_head_dim=dc.mamba_head_dim, groups=dc.mamba_n_groups,
        state=dc.ssm_state_size, top_k=dc.num_experts_per_token,
        routed_scaling_factor=dc.routed_scaling_factor,
        first_expert=first_expert, eps=dc.rms_norm_eps)


def _rel(got, want):
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(f32(a) - f32(b)))
        / max(float(jnp.linalg.norm(f32(b))), 1e-30), got, want)


def spread(params, key, scale=0.3):
    """Weights large enough that every rule moves the output by far more
    than float32's rounding (norm scales, the decays' leaves and the skip
    as they were made)."""
    import flax.linen as nn

    flat, treedef = jax.tree_util.tree_flatten_with_path(nn.meta.unbox(params))
    out = []
    for i, (path, leaf) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        kept = name in ("scale", "norm_scale", "A_log", "dt_bias", "D")
        out.append(leaf if kept else scale * jax.random.normal(
            jax.random.fold_in(key, i), leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------- (a) the scan ----------------

def _scan_operands(key, b, t, heads, p, groups, n, dtype):
    ks = jax.random.split(key, 4)
    xbc = (0.5 * jax.random.normal(
        ks[0], (b, t, heads * p + 2 * groups * n))).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7))
    return xbc, dt, a, jax.random.normal(ks[3], (b, t, heads * p))


def _value_and_grads(fn, xbc, dt, a, w):
    y, vjp = jax.vjp(fn, xbc, dt, a)
    return (y, *vjp(w.astype(y.dtype)))


@pytest.mark.parametrize("chunk", [50, 32], ids=["divides", "pads"])
def test_plain_scan_is_the_one_token_recurrence(chunk):
    from dinov3_tpu.ops import ssd

    sizes = (4, 16, 2, 32)
    xbc, dt, a, w = _scan_operands(jax.random.key(0), 2, 100, *sizes,
                                   jnp.float32)
    assert ssd.ssd_path(*sizes, 100, jnp.float32)[0] == "scan"
    want = _value_and_grads(
        lambda *o: ssd.ssd_recurrent(*o, *sizes), xbc, dt, a, w)
    got = _value_and_grads(jax.jit(
        lambda *o: ssd.ssd_chunked(*o, *sizes, chunk=chunk)), xbc, dt, a, w)
    assert max(_rel(got, want)) < 2e-5, _rel(got, want)
    # the state is zeroed between a batch's sequences
    alone = jnp.concatenate([ssd.ssd_chunked(xbc[i:i + 1], dt[i:i + 1], a,
                                             *sizes, chunk=chunk)
                             for i in range(2)])
    np.testing.assert_allclose(got[0], alone, atol=1e-6)


def test_kernel_pair_interpreted_is_the_one_token_recurrence():
    """Heads of 64 on a state of 128, 8 heads a group, two chunks a row:
    bfloat16 planes, the masked plane rounded as the kernels round it."""
    from dinov3_tpu.ops import ssd

    sizes = (8, 64, 1, 128)
    xbc, dt, a, w = _scan_operands(jax.random.key(1), 2, 256, *sizes,
                                   jnp.bfloat16)
    assert ssd.ssd_path(*sizes, 256, jnp.bfloat16, True) == (
        "kernel", "interpreted")
    assert ssd.ssd_path(*sizes, 256, jnp.bfloat16)[0] == "scan"
    want = _value_and_grads(
        lambda *o: ssd.ssd_recurrent(*o, *sizes), xbc, dt, a, w)
    run = jax.jit(lambda *o: ssd.ssd_chunked(*o, *sizes, interpret=True))
    got = _value_and_grads(run, xbc, dt, a, w)
    assert got[0].dtype == got[1].dtype == jnp.bfloat16
    assert max(_rel(got, want)) < 1e-2, _rel(got, want)
    # u's, B's and C's parts of the plane's gradient, each by itself
    inner = 8 * 64
    for part in (slice(0, inner), slice(inner, inner + 128),
                 slice(inner + 128, None)):
        assert _rel(got[1][..., part], want[1][..., part]) < 1e-2
    alone = jnp.concatenate([run(xbc[i:i + 1], dt[i:i + 1], a)
                             for i in range(2)])
    np.testing.assert_array_equal(got[0], alone)


@pytest.mark.parametrize("sizes, tokens, dtype, why", [
    ((64, 64, 8, 128), 8192, jnp.float32, "the plane is float32"),
    ((4, 16, 2, 32), 8192, jnp.bfloat16, "heads of 16 on a state of 32"),
    ((12, 64, 4, 128), 8192, jnp.bfloat16, "a pair of heads a lane tile"),
    ((64, 64, 8, 128), 8200, jnp.bfloat16, "not whole chunks of 128"),
])
def test_scan_path_falls_back_by_reason(sizes, tokens, dtype, why):
    from dinov3_tpu.ops.ssd import ssd_path

    path, said = ssd_path(*sizes, tokens, dtype, True)
    assert path == "scan" and why in said, said


# ---------------- (b) the mixer ----------------

def _mixer_and_reference(key, t=40, d=48):
    import lm_ssd_weights
    from reference import nemotron_h_fp32 as ref

    from dinov3_tpu.models.decoder import Mamba2Mixer

    h, p, g, n = 4, 8, 2, 16
    mixer = Mamba2Mixer(h, p, g, n, dtype=jnp.float32)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (2, t, d))
    params = spread(jax.jit(mixer.init)(ks[1], x)["params"], ks[2])
    params["D"] = 1.0 + 0.3 * jax.random.normal(ks[3], (h,))
    shape = ref.Shape(layers=(), heads=1, kv_heads=1, mamba_heads=h,
                      mamba_head_dim=p, groups=g, state=n, top_k=1,
                      first_expert=0)
    to_ref = lambda tree: {  # noqa: E731
        k: lm_ssd_weights._get(tree, path)
        for k, path in lm_ssd_weights._SSM.items()}
    return mixer, params, x, shape, to_ref, ref


def test_mamba2_mixer_is_the_references_block_value_and_every_gradient():
    mixer, params, x, shape, to_ref, ref = _mixer_and_reference(
        jax.random.key(2))
    w = jax.random.normal(jax.random.key(3), x.shape)
    with jax.default_matmul_precision("highest"):
        got, (gp, gx) = jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(w * mixer.apply({"params": p}, x)),
            argnums=(0, 1)))(params, x)
        want, (rp, rx) = jax.jit(jax.value_and_grad(
            lambda m, x: jnp.sum(w * ref.mamba2(x, m, shape, "fp32")),
            argnums=(0, 1)))(to_ref(params), x)
        others = {v: float(jnp.sum(w * ref.mamba2(x, to_ref(params), shape, v)))
                  for v in ("norm_then_gate", "one_group")}
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    gaps = _rel({**to_ref(gp), "x": gx}, {**rp, "x": rx})
    assert set(gaps) == {"win", "conv", "conv_bias", "A_log", "dt_bias", "D",
                         "gnorm", "wout", "x"}
    assert max(gaps.values()) < 1e-4, gaps
    # the gate BEFORE the grouped norm, a group's own B and C: the other
    # conventions are other functions
    for v, other in others.items():
        assert abs(other - float(want)) > 1e-2 * abs(float(want)), v


def test_convolution_has_a_bias_and_zeros_before_the_sequence():
    """The first three tokens see the taps on tokens that are not there
    as zeros, and every token the bias."""
    mixer, params, x, shape, to_ref, ref = _mixer_and_reference(
        jax.random.key(4), t=3)
    run = jax.jit(lambda p, x: mixer.apply({"params": p}, x))
    with jax.default_matmul_precision("highest"):
        got = run(params, x)
        want = ref.mamba2(x, to_ref(params), shape, "fp32")
        # token 0 alone is what a sequence of one token gives
        first = run(params, x[:, :1])
        unbiased = run({**params, "conv_bias": jnp.zeros_like(
            params["conv_bias"])}, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got[:, :1], first, atol=1e-6)
    assert float(jnp.max(jnp.abs(got - unbiased))) > 1e-2


def test_mixer_on_the_interpreted_kernels_is_its_plain_path():
    """``Mamba2Mixer`` at 64 | 128, 8 heads a group, bfloat16: the kernel
    pair (``core_interpret``: the test's switch) against the plain scan,
    value and every leaf's gradient."""
    from dinov3_tpu.models.decoder import Mamba2Mixer

    ks = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(ks[0], (1, 128, 64), jnp.bfloat16)
    mixers = [Mamba2Mixer(8, 64, 1, 128, core_interpret=flag)
              for flag in (None, True)]
    params = spread(jax.jit(mixers[0].init)(ks[1], x)["params"], ks[2], 0.1)
    out = [jax.jit(jax.value_and_grad(lambda p, x, m=m: jnp.sum(jnp.sin(
        m.apply({"params": p}, x).astype(jnp.float32))), argnums=(0, 1)))(
            params, x) for m in mixers]
    (plain, gp), (kernel, gk) = out
    assert abs(float(plain) - float(kernel)) < 2e-2 * abs(float(plain)) + 1e-2
    gaps = _rel(gk, gp)
    assert max(jax.tree.leaves(gaps)) < 5e-2, gaps


def test_mixer_on_the_interpreted_chains_is_its_plain_path():
    """``Mamba2Mixer`` at 8 heads of 64 in 2 norm groups, bfloat16, two
    time blocks: the two chains' kernel pairs (``chains_interpret``: the
    test's switch; in_proj's plane then ends in zero lanes up to a whole
    tile) against the plain chains on ONE set of parameters, value and
    every leaf's gradient; the scan between them is the plain one on both
    sides."""
    from dinov3_tpu.models.decoder import Mamba2Mixer
    from dinov3_tpu.ops.mixer_chains import SSM_TIME_BLOCK

    ks = jax.random.split(jax.random.key(6), 3)
    x = jax.random.normal(ks[0], (2, 2 * SSM_TIME_BLOCK, 64), jnp.bfloat16)
    mixers = [Mamba2Mixer(8, 64, 2, 128, chains_interpret=flag)
              for flag in (None, True)]
    params = spread(jax.jit(mixers[0].init)(ks[1], x)["params"], ks[2], 0.1)
    assert params["in_proj"]["kernel"].shape == (64, 512 + 1024 + 8)
    out = [jax.jit(jax.value_and_grad(lambda p, x, m=m: jnp.sum(jnp.sin(
        m.apply({"params": p}, x).astype(jnp.float32))), argnums=(0, 1)))(
            params, x) for m in mixers]
    (plain, gp), (kernel, gk) = out
    assert abs(float(plain) - float(kernel)) < 2e-2 * abs(float(plain)) + 1e-2
    assert jax.tree.structure(gk) == jax.tree.structure(gp)
    assert {k for k in gp[0]} == {"in_proj", "conv", "conv_bias", "A_log",
                                  "dt_bias", "D", "norm_scale", "out_proj"}
    gaps = _rel(gk, gp)
    assert max(jax.tree.leaves(gaps)) < 5e-2, gaps


# ---------------- (c) a block of one sublayer ----------------

def test_a_block_of_one_sublayer_is_one_norm_and_one_residual_add():
    from dinov3_tpu.models.decoder import (
        DecoderConfig,
        DecoderLayer,
        Mamba2Mixer,
    )
    from dinov3_tpu.ops.norms import RMSNorm

    dc = DecoderConfig.from_cfg(tiny_cfg(FP32))
    assert dc.layers == (("ssm", None), (None, "moe"), ("full_attn", None),
                         (None, "moe"))
    ks = jax.random.split(jax.random.key(6), 3)
    x = jax.random.normal(ks[0], (2, 20, 64))
    kinds = {"ssm": {"norm", "ssm"}, "full_attn": {"norm", "attn"},
             None: {"norm", "experts", "shared"}}
    for mixer, ffn in dc.layers[:3]:
        layer = DecoderLayer(mixer, ffn, dc)
        params = spread(jax.jit(layer.init)(ks[1], x)["params"], ks[2])
        assert set(params) == kinds[mixer]
    layer = DecoderLayer("ssm", None, dc)
    params = spread(jax.jit(layer.init)(ks[1], x)["params"], ks[2])
    y, aux = layer.apply({"params": params}, x)
    assert aux is None
    normed = RMSNorm(epsilon=dc.rms_norm_eps).apply(
        {"params": params["norm"]}, x)
    inside = Mamba2Mixer(
        dc.mamba_num_heads, dc.mamba_head_dim, dc.mamba_n_groups,
        dc.ssm_state_size, dc.short_conv_kernel_size, dc.rms_norm_eps,
        dtype=jnp.float32).apply({"params": params["ssm"]}, normed)
    np.testing.assert_allclose(y, x + inside, atol=1e-6)
    # the whole model: a final norm, embedding, head, a block a letter
    from dinov3_tpu.models import build_backbone

    tree = jax.eval_shape(
        build_backbone(tiny_cfg()).init, jax.random.key(0),
        jnp.zeros((2, 100), jnp.int32))["params"]
    assert set(tree) == {"token_embed", "lm_head", "norm",
                         *(f"layers_{i}" for i in range(4))}
    assert set(tree["layers_1"]["experts"]) == {"router", "router_bias", "w1",
                                                "w2"}


# sha256 of the StableHLO text of four programs the parent of PR 48 (commit
# 3c6eba1) lowers in this sandbox under this suite's conftest, no
# locations: the whole telemetry step of each older family's test module
# at its TINY (``kimi_linear``'s and ``smallthinker``'s are pinned in
# tests/test_lm_gqa.py and tests/test_lm_gdn_benchmark.py and read the same
# on both sides). What PR 48 added to ``DecoderLayer`` (a sublayer that
# may be absent, a third gate, a seventh family) moves none of them.
PARENTS_STEPS = {
    "test_lm_gdn": "0ece73e5e2d61daf890ffb999b9231d0e11ad417580aed3293a8822d52287d56",
    "test_lm_dsa": "2912b2b3632d0b06070847fd4b42ec6bbbb78a9c0ce9ccafadf6c5609bee777a",
    "test_lm_sconv": "6c82009d72cf4c4166e95c7bcefc8f412cdace5e957e04459de7a3165322d8d9",
    "test_lm_mla": "38d518c9bbec13a67999d2f6687c43ef55460a5f458c36fcf0397ba81c2b42d5",
}


@pytest.mark.parametrize("module", sorted(PARENTS_STEPS))
def test_the_older_families_tiny_steps_are_the_parents(module):
    from test_lm_gqa import _sha, lowered_tiny_step

    cfg = importlib.import_module(module).tiny_cfg()
    assert _sha(lowered_tiny_step(cfg)) == PARENTS_STEPS[module]


# ---------------- (d) the un-gated experts, the router's rule ----------------

@pytest.mark.parametrize("gate", ["relu2", "silu"])
def test_experts_block_at_one_and_a_half_lane_tiles_both_passes(gate):
    """A hidden width of 192 = 1.5 lane tiles: ``ragged_experts_block``
    and the five kernels interpreted against a loop over the experts,
    values and the four gradients; an expert with no rows among them."""
    from dinov3_tpu.ops import grouped_matmul as gm

    cap, held, d, h = 512, 3, 128, 192
    wide = h if gate == "relu2" else 2 * h
    groups = [130, 0, 200]
    ks = jax.random.split(jax.random.key(7), 5)
    rows = jax.random.normal(ks[0], (cap, d)).astype(jnp.bfloat16)
    w1 = 0.1 * jax.random.normal(ks[1], (held, d, wide))
    w2 = 0.1 * jax.random.normal(ks[2], (held, h, d))
    w_rows = jax.random.uniform(ks[3], (cap,))
    ct = jax.random.normal(ks[4], (cap, d))
    sizes, kept = jnp.array(groups, jnp.int32), jnp.arange(cap) < sum(groups)
    assert gm.grouped_matmul_path(cap, d, h, jnp.bfloat16, True, gate)[0] \
        == "kernel"
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731

    def loop(rows, w1, w2, w_rows):
        out, start = jnp.zeros((cap, d), jnp.float32), 0
        for e, n in enumerate(groups):
            own = slice(start, start + n)
            hid = low(low(rows[own]) @ low(w1[e]))
            if gate == "relu2":
                hid = jnp.square(jax.nn.relu(hid))
            else:
                hid = jax.nn.silu(hid[:, :h]) * hid[:, h:]
            out = out.at[own].set(low(hid) @ low(w2[e]) * w_rows[own, None])
            start += n
        return out

    def both(fn):
        out, vjp = jax.vjp(fn, rows, w1, w2, w_rows)
        return (out, *vjp(ct))

    want = both(loop)
    for name, fn in (
            ("ragged_dot", lambda *a: gm.ragged_experts_block(
                *a, sizes, kept, gate)),
            ("kernel", lambda *a: gm.experts_block(
                *a, sizes, gate, gm.row_tile(cap), True))):
        gaps = _rel(both(jax.jit(fn)), want)
        assert max(gaps) < 1e-2, (name, gaps)


def test_router_scales_by_two_and_a_half_over_the_sum_and_1e_20():
    from reference import nemotron_h_fp32 as ref

    from dinov3_tpu.models.decoder import DEEPSEEK_V3_ROUTER_EPS
    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    d, e, k, scale = 16, 8, 2, 2.5
    assert DEEPSEEK_V3_ROUTER_EPS == ref.ROUTER_EPS == 1e-20
    layer = RoutedExpertsFFN(8, e, k, 1, 0, scale, router="sigmoid",
                             gate="relu2", dtype=jnp.float32,
                             norm_eps=DEEPSEEK_V3_ROUTER_EPS)
    ks = jax.random.split(jax.random.key(11), 3)
    x = jax.random.normal(ks[0], (64, d))
    params = spread(jax.jit(layer.init)(ks[1], x)["params"], ks[2])
    assert set(params) == {"router", "router_bias", "w1", "w2"}
    assert params["w1"].shape == (8, 16, 8) and params["w2"].shape == (8, 8, 16)
    params["router_bias"] = jnp.zeros((e,)).at[5].set(2.0)
    shape = ref.Shape(layers=(), heads=1, kv_heads=1, mamba_heads=1,
                      mamba_head_dim=1, groups=1, state=1, top_k=k,
                      routed_scaling_factor=scale, first_expert=0)
    with jax.default_matmul_precision("highest"):
        y, aux = jax.jit(lambda p: layer.apply({"params": p}, x))(params)
        choice, weight, agree = ref.route(x, params, shape)
        want, _ = ref.experts(x, params, shape, None, "fp32", with_shared=False)
        unsquared, _ = ref.experts(x, params, shape, None, "relu",
                                   with_shared=False)
    # the bias moves the choice (every token takes expert 5) and not the weight
    assert np.all(np.any(np.asarray(aux["choice"]) == 5, -1))
    np.testing.assert_array_equal(np.sort(aux["choice"], -1), np.sort(choice, -1))
    assert float(agree) == 1.0
    picked = jnp.take_along_axis(jax.nn.sigmoid(x @ params["router"]), choice, -1)
    np.testing.assert_allclose(
        weight, scale * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weight.sum(-1), scale, rtol=1e-6)
    np.testing.assert_allclose(y, want, atol=5e-5)
    assert float(jnp.max(jnp.abs(unsquared - want))) > 1e-2
    assert float(scale * 0.0 / (0.0 + np.float32(DEEPSEEK_V3_ROUTER_EPS))) == 0.0
    g = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(
        layer.apply({"params": p}, x)[0]))))(params)
    assert float(jnp.max(jnp.abs(g["router_bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g["router"]))) > 0.0


# ---------------- (e) the shards' parts add up ----------------

def test_sixteen_shards_parts_and_the_shared_part_once_add_up_to_the_uncut_block():
    """What every chip computes alike (the residual stream, the shared
    expert) is counted once; a shard's routed part is the program's routed
    layer on the block's own normed stream."""
    import lm_ssd_weights
    from reference import nemotron_h_fp32 as ref

    from dinov3_tpu.models.decoder import DecoderConfig, DecoderLayer
    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    shards, held, d = 16, 2, 32
    e = shards * held
    dc = DecoderConfig.from_cfg(tiny_cfg([
        *FP32, f"lm.hidden_size={d}", f"lm.n_routed_experts={e}",
        f"lm.expert_shards={shards}"]))
    kinds = (None, "moe")
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (2, 24, d))
    layer = DecoderLayer(*kinds, dc)
    params = spread(jax.jit(layer.init)(ks[1], x)["params"], ks[2])
    router, bias = params["experts"]["router"], 0.1 * jax.random.normal(ks[3], (e,))
    full = {"w1": 0.3 * jax.random.normal(ks[1], (e, d, 24)),
            "w2": 0.3 * jax.random.normal(ks[2], (e, 24, d))}

    def held_by(shard, w2_scale=1.0):
        own = slice(shard * held, (shard + 1) * held)
        return {"router": router, "router_bias": bias, "w1": full["w1"][own],
                "w2": w2_scale * full["w2"][own]}

    def whole(shard, experts):
        (y, _), seen = DecoderLayer(*kinds, dataclasses.replace(
            dc, expert_shard=shard)).apply(
                {"params": {**params, "experts": experts}}, x,
                capture_intermediates=lambda m, _: m.name == "norm",
                mutable=["intermediates"])
        return y, seen["intermediates"]["norm"]["__call__"][0]

    def routed_part(shard, experts, u):
        return RoutedExpertsFFN(
            dc.moe_intermediate_size, e, dc.num_experts_per_token, shards,
            shard, dc.routed_scaling_factor, router="sigmoid", gate="relu2",
            norm_eps=dc.router_norm_eps, dtype=jnp.float32).apply(
                {"params": experts}, u)

    with jax.default_matmul_precision("highest"):
        # the stream and the SHARED part, the routed one zeroed
        alike, u = jax.jit(whole, static_argnums=0)(0, held_by(0, 0.0))
        total, choices = alike, []
        for shard in range(shards):
            # (eagerly: a compile a shard would be sixteen compiles)
            routed, aux = routed_part(shard, held_by(shard), u)
            assert float(aux["overflow"]) == 0
            total = total + routed
            choices.append(np.asarray(aux["choice"]))
        own, _ = jax.jit(whole, static_argnums=0)(3, held_by(3))
        np.testing.assert_allclose(
            own, alike + routed_part(3, held_by(3), u)[0], atol=2e-5)
        uncut = lm_ssd_weights.reference_tree(
            {"layers_0": {**params, "experts": {
                "router": router, "router_bias": bias, **full}},
             "token_embed": 0, "lm_head": 0, "norm": {"scale": 0}})["layers"][0]
        shape = reference_shape(dc)
        want, agree = jax.jit(lambda lw: ref.layer(
            x, lw, kinds, shape, None, "fp32"))(uncut)
        no_shared = {**uncut, "ffn": {**uncut["ffn"], "shared": jax.tree.map(
            jnp.zeros_like, uncut["ffn"]["shared"])}}
        bare, _ = jax.jit(lambda lw: ref.layer(
            x, lw, kinds, shape, None, "fp32"))(no_shared)
    assert float(agree) == 1.0
    for c in choices[1:]:  # every shard routes over all the experts alike
        np.testing.assert_array_equal(c, choices[0])
    assert len({int(v) // held for v in choices[0].reshape(-1)}) > 8
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
    scales, ``A_log``, ``dt_bias``, ``D``, the convolution's bias and the
    selection bias)."""
    import lm_ssd_step_check
    import lm_ssd_weights
    from reference import nemotron_h_fp32 as ref

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup
    from dinov3_tpu.train.param_groups import build_multiplier_trees

    cfg = tiny_cfg(FP32)
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
    assert set(backbone["layers_0"]) == {"norm", "ssm"}
    assert set(backbone["layers_0"]["ssm"]) == {
        "in_proj", "conv", "conv_bias", "A_log", "dt_bias", "D", "norm_scale",
        "out_proj"}
    assert set(backbone["layers_1"]) == {"norm", "experts", "shared"}
    assert set(backbone["layers_2"]) == {"norm", "attn"}

    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    found = {classify_step_phase(n) for n in names}
    family = {"lm_embed", "ssm_mixer", "full_attn_mixer", "moe_ffn",
              "lm_head_loss"}
    assert {p for p, _ in found} - {None} == family | {
        "update", "telemetry_ring"}
    for phase in family - {"lm_embed"}:
        assert {(phase, "fwd"), (phase, "bwd")} <= found, phase
    for phase, inner in (("ssm_mixer", "ssd_core"), ("ssm_mixer", "ssm_chain"),
                         ("full_attn_mixer", "gqa_core"),
                         ("moe_ffn", "moe_route"), ("moe_ffn", "moe_experts"),
                         ("moe_ffn", "moe_rows"), ("moe_ffn", "moe_shared")):
        assert any(phase in n and f"/{inner}/" in n for n in names), inner
    # the chains hold no matmul: the projections are the mixer's own
    assert not any("/ssm_chain/" in n and "dot_general" in n for n in names)
    assert family < set(LM_STEP_PHASES) < set(STEP_PHASES)

    _, wd, _ = build_multiplier_trees(state.params["student"])
    tree = lm_ssd_weights.reference_tree(wd["backbone"])
    flat = jax.tree.leaves(jax.tree.map(
        lambda a, b: (float(a), float(b)), tree, ref.decays(tree)))
    assert all(a == b for a, b in zip(flat[::2], flat[1::2]))
    assert tree["embed"] == tree["head"] == 1.0
    ssm = tree["layers"][0]["mixer"]
    assert [ssm[k] for k in ("A_log", "dt_bias", "D", "conv_bias", "gnorm")] \
        == [0.0] * 5
    assert ssm["conv"] == ssm["win"] == ssm["wout"] == 1.0
    assert tree["layers"][1]["ffn"]["router_bias"] == 0.0
    assert tree["layers"][1]["ffn"]["shared"]["w2"] == 1.0
    # every leaf of the reference's layout has a group: the leaves only
    # the recurrence reaches theirs, by themselves
    paths = lm_ssd_step_check.leaf_paths(tree)
    groups = {p: lm_ssd_step_check.group_of(p) for p in paths}
    assert set(groups.values()) == set(lm_ssd_step_check.GROUPS)
    assert {p for p, g in groups.items() if g == "scan"} == {
        f"layers/0/mixer/{leaf}"
        for leaf in ("A_log", "dt_bias", "D", "conv", "conv_bias")}
    assert groups["layers/0/mixer/win"] == groups["layers/0/norm"] \
        == groups["layers/2/mixer/wq"] == groups["layers/2/norm"] == "mixers"
    assert groups["layers/1/ffn/w1"] == groups["layers/1/norm"] \
        == groups["layers/3/ffn/shared/w2"] == "ffn"
    assert groups["layers/3/ffn/router"] == groups["layers/3/ffn/router_bias"] \
        == "router"
    assert groups["embed"] == groups["head"] == groups["norm"] == "head_embed"


def test_benchmark_vocabulary_of_the_family_is_the_programs():
    with open(os.path.join(BENCH, "lm_ssd_phases.json")) as f:
        bench = json.load(f)
    named = set(bench["phases"]) | set(bench["inner"])
    named |= {p for sums in bench["metrics"].values() for p, _ in sums}
    named |= {p for p, _ in bench["inner_metrics"].values()}
    assert named <= set(STEP_PHASES), named - set(STEP_PHASES)
    for phase, inner in bench["inner_metrics"].values():
        assert inner in bench["inner"][phase]
    # the experts' whole scope is the older reader's, from the older file:
    # this vocabulary names the row movement inside it instead
    with open(os.path.join(BENCH, "lm_phases.json")) as f:
        assert json.load(f)["inner_metrics"]["lm_moe_experts_ms_per_step"] \
            == ["moe_ffn", "moe_experts"]
    assert "moe_experts" not in bench["inner"]["moe_ffn"]


@pytest.mark.parametrize("override, named", [
    ("lm.hybrid_override_pattern=ME-E", "hybrid_override_pattern"),
    ("lm.hybrid_override_pattern=MEE", "hybrid_override_pattern"),
    ("lm.n_group=2", "n_group"), ("lm.topk_group=2", "topk_group"),
    ("lm.mlp_hidden_act=silu", "mlp_hidden_act"),
    ("lm.use_conv_bias=false", "use_conv_bias"),
    ("lm.mamba_proj_bias=true", "mamba_proj_bias")])
def test_what_the_family_cannot_run_is_refused_by_name(override, named):
    from dinov3_tpu.models import DecoderConfig

    with pytest.raises(ValueError, match=named):
        DecoderConfig.from_cfg(tiny_cfg([override]))


def test_config_rules():
    from dinov3_tpu.configs.config import LM_ARCHS, is_lm_arch
    from dinov3_tpu.models import DecoderConfig, LMDecoder, build_backbone

    cfg = tiny_cfg()
    assert is_lm_arch(cfg) and "nemotron_h" in LM_ARCHS
    model = build_backbone(cfg)
    assert isinstance(model, LMDecoder) and model.embed_dim == 64
    dc = model.cfg
    assert (dc.router, dc.gate, dc.router_norm_eps, dc.routed_scaling_factor,
            dc.num_shared_experts, dc.shared_expert_width,
            dc.tie_word_embeddings) == (
                "sigmoid", "relu2", 1e-20, 2.5, 1, 48, False)
    assert (dc.mamba_num_heads, dc.mamba_head_dim, dc.mamba_n_groups,
            dc.ssm_state_size, dc.short_conv_kernel_size, dc.rms_norm_eps,
            dc.time_step_limits, dc.expert_rows_factor) == (
                4, 16, 2, 32, 4, 1e-5, (1e-3, 1e-1, 1e-4), 6.0)
    assert not (dc.full_attn_rotary or dc.attn_qk_norm or dc.sliding_window)
    # the recipe as it stands holds the published widths
    lm = load_config(RECIPE).lm
    assert (lm.hidden_size, lm.mamba_num_heads, lm.mamba_head_dim,
            lm.ssm_state_size, lm.n_groups, lm.conv_kernel,
            lm.num_attention_heads, lm.num_key_value_heads, lm.head_dim) == (
                2688, 64, 64, 128, 8, 4, 32, 2, 128)
    assert (lm.n_routed_experts, lm.num_experts_per_tok, lm.n_shared_experts,
            lm.moe_intermediate_size, lm.moe_shared_expert_intermediate_size,
            lm.routed_scaling_factor, lm.seq_len) == (
                128, 6, 1, 1856, 3712, 2.5, 8192)
    full = DecoderConfig.from_cfg(load_config(RECIPE))
    assert (full.num_experts // full.expert_shards, full.vocab_size) == (8, 16384)
    assert "".join({"ssm": "M", "full_attn": "*", None: "E"}[m]
                   for m, _ in full.layers) == "MEMEM*EME"


def test_the_paths_are_read_off_shapes_at_the_published_sizes(caplog):
    """``ssd_path``, ``causal_attention_path`` and ``grouped_matmul_path``
    at the cell's shapes: on a TPU (``interpret=False``: described, not
    attached) the scan at 64 heads of 64 on 128 in 8 groups, the two
    chains around it, the causal
    core at [2, 8192, 32 | 2, 128] (SIXTEEN query heads a key/value head)
    and the un-gated experts at 2688 x 1856 (14.5 lane tiles) take their
    kernels; here, on the CPU, the plain paths, and the set-up log says
    which, a line a block."""
    import logging

    from dinov3_tpu.ops.causal_attention import causal_attention_path
    from dinov3_tpu.ops.ffn import routed_rows_capacity
    from dinov3_tpu.ops.grouped_matmul import grouped_matmul_path
    from dinov3_tpu.ops.mixer_chains import ssm_chain_path
    from dinov3_tpu.ops.ssd import ssd_path
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    assert ssd_path(64, 64, 8, 128, 8192, jnp.bfloat16, False) == (
        "kernel", "compiled for the TPU")
    # the chains around it, at in_proj's [2, 8192, 10304]: 4,096 channels
    # in 8 norm groups, 6,144 under the convolution
    assert ssm_chain_path(8192, 4096, 6144, 8, jnp.bfloat16,
                          interpret=False) == ("kernel", "compiled for the TPU")
    shapes = ((2, 8192, 32, 128),) + ((2, 8192, 2, 128),) * 2
    assert causal_attention_path(shapes, None, False) == (
        "kernel", "compiled for the TPU")
    cap = routed_rows_capacity(16384, 6, 128, 8,
                               load_config(RECIPE).lm.expert_rows_factor)
    assert cap == 36864
    assert grouped_matmul_path(cap, 2688, 1856, jnp.bfloat16, False,
                               "relu2") == ("kernel", "compiled for the TPU")
    # a GATED expert of this width would be [2688, 3712]: past a VMEM block
    assert grouped_matmul_path(cap, 2688, 1856, jnp.bfloat16, False)[0] \
        == "ragged_dot"
    with caplog.at_level(logging.INFO, logger="dinov3"):
        LMMetaArch(load_config(RECIPE))
    said = [r.getMessage() for r in caplog.records]
    cpu = "(the backend is cpu, not a TPU)"
    assert sum(f"ssd_core, both passes: scan {cpu}" in s for s in said) == 4
    assert sum(f"ssm_mixer's chains, both passes: plain {cpu}" in s
               for s in said) == 4
    assert sum(f"gqa_core (full_attn), both passes: tiles {cpu}" in s
               for s in said) == 1
    assert sum(f"moe_experts, both passes: ragged_dot {cpu}; rows moved by "
               "gathers through index lists, the combine scatter at 2.7 "
               "(token, choice) pairs a buffer row" in s for s in said) == 4
