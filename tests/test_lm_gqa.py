"""The ``smallthinker`` decoder family (PR 32), on the CPU at a small size.

(a) ``causal_blockwise_attention`` with a window and grouped heads is the
    dense masked softmax, values and gradients, at T < W, T = W and
    T > W, with windows no block size divides and with the shipped blocks
    on both sides of the band; the calls the parent had lower to the
    parent's programs (sha256 of the StableHLO text).
(b) The two router rules and the two gates of ``RoutedExpertsFFN``
    against the sum written out by hand; a router fed from another
    tensor; the four expert shards' parts add up to the uncut reference
    layer.
(c) The model is ``benchmark/reference/smallthinker_fp32.py``: logits,
    loss, every leaf's gradient as a DIFFERENCE, the reference's
    layer-by-layer gradient against ``jax.grad`` of the whole, the
    controls; three steps of the compiled step against its three steps.
(d) The family on the normal path: config rules, phases in the compiled
    step, ring columns, ``do_train`` with a save and a resume.
"""

import dataclasses
import hashlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.configs import load_config
from dinov3_tpu.utils import STEP_PHASES, classify_step_phase

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

RECIPE = os.path.join(REPO, "configs", "train", "smallthinker_ep4.yaml")
# 6 query heads on 2 key/value heads; a window of 37 keys under 100
# tokens: longer than the window, and no block size divides it
TINY = [
    "lm.hidden_size=64", "lm.num_attention_heads=6",
    "lm.num_key_value_heads=2", "lm.head_dim=16", "lm.sliding_window_size=37",
    "lm.moe_ffn_hidden_size=32", "lm.moe_num_primary_experts=16",
    "lm.moe_num_active_primary_experts=4", "lm.vocab_size=250",
    "lm.seq_len=100", "train.batch_size_per_device=2",
    "telemetry.flush_every=2"]


def tiny_cfg(extra=()):
    return load_config(RECIPE, overrides=[*TINY, *extra])


def _reference_shape(dc, first_expert=0):
    from reference import smallthinker_fp32 as ref

    return ref.Shape(
        layers=dc.layers, heads=dc.num_attention_heads,
        kv_heads=dc.num_key_value_heads, window=dc.sliding_window,
        rope_theta=dc.rope_theta, top_k=dc.num_experts_per_token,
        first_expert=first_expert, eps=dc.rms_norm_eps)


def _sha(lowered) -> str:
    text = lowered.as_text()
    assert "loc(" not in text.split("\n", 1)[0]
    return hashlib.sha256(text.encode()).hexdigest()


# ---------------- (a) the banded, grouped core ----------------

def _dense_attention(q, k, v, window=None):
    n, g = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    z = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    at = jnp.arange(n)
    seen = at[None, :] <= at[:, None]
    if window is not None:
        seen = seen & (at[None, :] > at[:, None] - window)
    z = jnp.where(seen, z, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(z, -1), v)


@pytest.mark.parametrize("n, window, block_q, block_kv, heads, kv_heads", [
    (50, 64, 16, 32, 6, 2),       # T < W: the window never bites
    (64, 64, 32, 32, 6, 2),       # T = W
    (100, 37, 16, 32, 6, 2),      # T > W, a window no block divides
    (100, 37, 48, 20, 4, 4),      # ... blocks that divide nothing, no groups
    (96, 1, 32, 32, 6, 3),        # the query's own key alone
    (100, None, 32, 64, 6, 1),    # grouped heads without a window
    (300, 64, 32, 32, 6, 2),      # seven blocks of one geometry: one program
    (1300, 300, 512, 1024, 4, 2),  # the shipped blocks, tiles skipped below
])
def test_banded_grouped_attention_is_masked_softmax(
        n, window, block_q, block_kv, heads, kv_heads):
    from dinov3_tpu.ops.attention import (
        causal_blockwise_attention,
        dispatch_attention,
    )

    ks = jax.random.split(jax.random.key(n), 3)
    q = jax.random.normal(ks[0], (2, n, heads, 16))
    k = jax.random.normal(ks[1], (2, n, kv_heads, 16))
    v = jax.random.normal(ks[2], (2, n, kv_heads, 8))  # narrower than q and k
    tiles = lambda *a: causal_blockwise_attention(  # noqa: E731
        *a, block_q=block_q, block_kv=block_kv, window=window)
    if (block_q, block_kv) == (512, 1024):
        tiles = lambda *a: dispatch_attention(  # noqa: E731
            *a, causal=True, window=window)

    def both(fn):  # the output, and a gradient that weighs every element
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *b: jnp.sum(jnp.sin(fn(*b))), argnums=(0, 1, 2))(*a)))(q, k, v)

    got, want = both(tiles), both(lambda *a: _dense_attention(*a, window))
    np.testing.assert_allclose(got[0], want[0], atol=3e-6)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_band_tiles_are_skipped_on_both_sides():
    """At 16 blocks of 32 and a window of 4 x 32 keys, a block of queries
    makes the tiles of its band and no others, and the twelve blocks past
    the window, whose geometry is one, are ONE traced program under a
    loop: the matmuls of the jaxpr are counted (two a tile), against 136
    tiles under the diagonal without a window, every block its own."""
    from dinov3_tpu.ops.attention import causal_blockwise_attention

    q = jnp.zeros((1, 16 * 32, 2, 8))

    def tiles(window):
        text = str(jax.make_jaxpr(lambda q: causal_blockwise_attention(
            q, q, q, block_q=32, block_kv=32, window=window))(q))
        return text.count("dot_general") // 2, text.count("scan[")

    assert tiles(None) == (16 * 17 // 2, 0)
    # blocks 0-3 see 1, 2, 3, 4 tiles; every later one the diagonal tile
    # and four below it, the lowest crossed by the window's lower edge
    assert tiles(4 * 32) == (1 + 2 + 3 + 4 + 5, 1)
    # one key more is still in that lowest tile; two more need a sixth
    assert tiles(4 * 32 + 1) == tiles(4 * 32)
    assert tiles(4 * 32 + 2) == (1 + 2 + 3 + 4 + 5 + 6, 1)
    with pytest.raises(ValueError, match="query heads"):
        causal_blockwise_attention(q, q[:, :, :1], q)
    with pytest.raises(ValueError, match="window"):
        causal_blockwise_attention(q, q, q, window=0)


# sha256 of the StableHLO text of three programs the parent of PR 32
# (commit b34c4ac) lowers in this sandbox under this suite's conftest, no
# locations: the decoder's
# whole telemetry step at tests/test_lm_decoder.py's TINY (KDA, MLA, the
# sigmoid router and SiLU experts with the shared one); MLA's call of the
# causal core (equal head counts, no window) with its gradient at the
# shipped blocks; RoutedExpertsFFN under its defaults with its gradient.
# What PR 32 added for a window, grouped heads, a second router rule and
# a second gate moves none of them.
# PR 47 MOVED two of the three on purpose (ROADMAP D17) and re-made them on
# its final tree: the routed layer's row movement is ``ops/routed_rows.py``
# now (the gather and the scatter-add promise their indices in bounds, the
# mask on the gathered rows went into ``ragged_experts_block``, the combine
# rounds inside its rule), which changes KIMI_STEP and KIMI_FFN (before:
# a2e309d5...c1f63aa and fa404533...4c43f26) and leaves MLA's call alone.
KIMI_STEP_SHA256 = "5d0a0bcf65054badb7bd6886bd10aaf87f6660f96599b0460d1bcc78bc1b535e"
MLA_CALL_SHA256 = "5d23c5c8ca6c3d6c0de7b74d917a73fed322cf10f629e1b69f970352649df0df"
KIMI_FFN_SHA256 = "3b5c0bf9f20c66cb6a3f7538f144001170558f6b9a35c8b069c28f8775e272b6"


def lowered_tiny_step(cfg):
    """The telemetry step of a decoder's tiny configuration, lowered on
    one device from an abstract state."""
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 2, seed=0).items()}
    setup = build_train_setup(cfg, batch, devices=jax.devices()[:1],
                              init_state=False)
    plan = setup.telemetry()
    args = (setup.state, jax.eval_shape(plan.init_ring), batch,
            setup.scalars(0), jax.random.key(0))
    with setup.mesh:
        return plan.step_fn.lower(*args)


def _kimi_step():
    from test_lm_decoder import tiny_cfg as kimi_tiny_cfg

    return lowered_tiny_step(kimi_tiny_cfg())


def _mla_call():
    from dinov3_tpu.ops.attention import dispatch_attention

    q = jax.ShapeDtypeStruct((2, 1300, 3, 24), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 1300, 3, 16), jnp.bfloat16)
    f = lambda q, k, v: dispatch_attention(q, k, v, causal=True)  # noqa: E731
    return jax.jit(jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
                            argnums=(0, 1, 2))).lower(q, q, v)


def _kimi_ffn():
    import flax.linen as nn

    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    layer = RoutedExpertsFFN(16, 16, 4, 4, 1, 2.446)
    x = jax.ShapeDtypeStruct((2, 40, 32), jnp.bfloat16)
    params = nn.meta.unbox(jax.eval_shape(lambda: layer.init(
        jax.random.key(0), jnp.zeros((2, 40, 32), jnp.bfloat16))))
    f = lambda p, x: layer.apply(p, x)[0]  # noqa: E731
    return jax.jit(jax.grad(lambda p, x: jnp.sum(f(p, x).astype(jnp.float32)),
                            argnums=(0, 1))).lower(params, x)


@pytest.mark.parametrize("lower, want", [
    (_kimi_step, KIMI_STEP_SHA256), (_mla_call, MLA_CALL_SHA256),
    (_kimi_ffn, KIMI_FFN_SHA256)], ids=["kimi_step", "mla_call", "kimi_ffn"])
def test_the_parents_programs_are_unchanged(lower, want):
    assert _sha(lower()) == want


# ---------------- (b) the routed layer's rules ----------------

def _by_hand(x, xr, p, k, router, gate, first=0, scale=1.0):
    """The routed layer written out: every held expert on every token."""
    s = xr @ p["router"]
    if router == "sigmoid":
        s = jax.nn.sigmoid(s)
        _, choice = jax.lax.top_k(s + p["router_bias"], k)
        sel = jnp.take_along_axis(s, choice, -1)
        w = scale * sel / jnp.sum(sel, -1, keepdims=True)
    else:
        _, choice = jax.lax.top_k(s, k)
        # the softmax over ALL the experts, renormalised over the chosen
        # ones (= the softmax over the chosen logits, as the layer has it)
        full = jnp.take_along_axis(jax.nn.softmax(s, -1), choice, -1)
        w = full / jnp.sum(full, -1, keepdims=True)
    act = jax.nn.silu if gate == "silu" else jax.nn.relu
    y = 0.0
    for e in range(p["w12"].shape[0]):
        a, b = jnp.split(x @ p["w12"][e], 2, -1)
        w_e = jnp.sum(jnp.where(choice == first + e, w, 0.0), -1)
        y = y + w_e[:, None] * ((act(a) * b) @ p["w3"][e])
    return y, choice


@pytest.mark.parametrize("router", ["sigmoid", "softmax"])
@pytest.mark.parametrize("gate", ["silu", "relu"])
@pytest.mark.parametrize("other_input", [False, True])
def test_router_rules_and_gates(router, gate, other_input):
    import flax.linen as nn

    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    d, e, k = 16, 8, 3
    layer = RoutedExpertsFFN(8, e, k, shards=2, shard=1, scale=1.0,
                             rows_factor=4.0, dtype=jnp.float32,
                             router=router, gate=gate)
    ks = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(ks[0], (2, 20, d))
    xr = jax.random.normal(ks[1], (2, 20, d)) if other_input else None
    params = nn.meta.unbox(layer.init(ks[2], x)["params"])
    params = jax.tree.map(
        lambda a: jax.random.normal(ks[3], a.shape) * 0.3, params)
    # the softmax router has no selection bias at all
    assert ("router_bias" in params) == (router == "sigmoid")

    def both(params, x, xr):
        got, aux = layer.apply({"params": params}, x, xr)
        want, choice = _by_hand(
            x.reshape(-1, d), (x if xr is None else xr).reshape(-1, d),
            params, k, router, gate, first=4)
        return got.reshape(-1, d), want, aux, choice

    got, want, aux, choice = both(params, x, xr)
    assert float(aux["overflow"]) == 0
    np.testing.assert_array_equal(aux["choice"], choice)
    np.testing.assert_allclose(got, want, atol=2e-6)
    grads = [jax.grad(lambda p, x, xr, i=i: jnp.sum(jnp.sin(both(p, x, xr)[i])),
                      argnums=(0, 1) + ((2,) if other_input else ()))(
                          params, x, xr) for i in (0, 1)]
    for a, b in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(grads[1])):
        np.testing.assert_allclose(a, b, atol=1e-5)
    if other_input:  # the router's input takes the weights' gradient alone
        assert float(jnp.max(jnp.abs(grads[0][2]))) > 0
    with pytest.raises(ValueError, match="router"):
        RoutedExpertsFFN(8, e, k, router="tanh").init(ks[2], x)


def test_all_shards_make_the_uncut_layer():
    """Guide section 4: at a small size, the parts of the result that all
    four shards give add up to the uncut reference layer (this family has
    nothing that every chip computes alike: no shared expert)."""
    from reference import smallthinker_fp32 as ref

    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    d, e, k, width, shards = 32, 16, 4, 16, 4
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (2, 40, d))
    xr = jax.random.normal(ks[1], (2, 40, d))   # the layer's input
    full = {"router": jax.random.normal(ks[2], (d, e)) * 0.5,
            "w12": jax.random.normal(ks[3], (e, d, 2 * width)) * 0.2,
            "w3": jax.random.normal(ks[4], (e, width, d)) * 0.2}
    shape = ref.Shape(layers=(), heads=1, kv_heads=1, window=1, rope_theta=1.0,
                      top_k=k, first_expert=0)
    with jax.default_matmul_precision("highest"):
        want, agree = ref.experts(x.reshape(-1, d), xr.reshape(-1, d), full,
                                  shape, None, "fp32")
        total, choices = 0.0, []
        held = e // shards
        for shard in range(shards):
            layer = RoutedExpertsFFN(width, e, k, shards, shard, 1.0, 4.0,
                                     dtype=jnp.float32, router="softmax",
                                     gate="relu")
            part = {"router": full["router"],
                    "w12": full["w12"][shard * held:(shard + 1) * held],
                    "w3": full["w3"][shard * held:(shard + 1) * held]}
            y, aux = layer.apply({"params": part}, x, xr)
            assert float(aux["overflow"]) == 0
            total = total + y.reshape(-1, d)
            choices.append(np.asarray(aux["choice"]))
            # one shard alone is the reference given that shard's share
            own, _ = ref.experts(
                x.reshape(-1, d), xr.reshape(-1, d), part,
                dataclasses.replace(shape, first_expert=shard * held),
                None, "fp32")
            np.testing.assert_allclose(y.reshape(-1, d), own, atol=1e-5)
    assert float(agree) == 1.0
    for c in choices[1:]:  # every shard routes over all the experts alike
        np.testing.assert_array_equal(c, choices[0])
    np.testing.assert_allclose(total, want, atol=1e-5)


# ---------------- (c) the model against the reference ----------------

@pytest.fixture(scope="module")
def tiny_model():
    """(cfg, meta, batch, seed-made student tree, reference weights,
    reference shape), float32 compute."""
    import lm_gqa_weights

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32"])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 2, seed=0).items()}
    meta = LMMetaArch(cfg)
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0))["student"]
    student = lm_gqa_weights.fill(abstract, 5)
    # seed-made routers of N(0, 0.02) put the logits within 1e-2 of each
    # other: spread them, so that float32 rounding moves no choice here
    # ... and the mixers' small residual writes (lm_gqa_weights
    # RESIDUAL_OUT_STD) back at N(0, 0.02), so that each rule of the
    # attention moves the logits by far more than float32's rounding
    for i in range(4):
        layer = student["backbone"][f"layers_{i}"]
        layer["experts"]["router"] *= 25.0
        layer["attn"]["o_proj"]["kernel"] *= math.sqrt(2 * 52)
    w = lm_gqa_weights.reference_tree(student["backbone"])
    return cfg, meta, batch, student, w, _reference_shape(
        meta.student_backbone.cfg)


def _rel(got, want):
    return jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b))
        / max(float(jnp.linalg.norm(b)), 1e-30), got, want)


def test_model_is_the_reference(tiny_model):
    import lm_gqa_weights
    from reference import smallthinker_fp32 as ref

    _, meta, batch, student, w, shape = tiny_model
    assert shape.layers == (("full_attn", "moe"),) + (("swa", "moe"),) * 3
    tokens = batch["tokens"]
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p: meta.student_backbone.apply(
            {"params": p["backbone"]}, tokens))(student)
        (loss, (metrics, state)), grad = jax.jit(jax.value_and_grad(
            lambda p: meta.forward(p, {}, batch, state=meta.init_state(),
                                   iteration=0), has_aux=True))(student)
        assert state == {}  # the step keeps no routing
        choice = jax.jit(meta.routing)(student, batch)
        assert choice.shape == (4, 2 * 100, 4) and int(choice.max()) < 16
        want_logits = jax.jit(ref.logits, static_argnums=2)(
            w, tokens, shape, choice)
        (want_loss, agree), want_grad = jax.jit(jax.value_and_grad(
            ref.loss_fn, has_aux=True), static_argnums=2)(w, tokens, shape, choice)
        # the reference's layer-by-layer gradient is jax.grad of the whole
        recipe = ref.Recipe(clip_grad=1e9)
        by_layer, loss_by_layer, _ = ref.gradient(
            w, tokens, choice, s=shape, r=recipe)
    assert logits.shape == (2, 100, 250) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want_logits, atol=5e-6)
    assert abs(float(loss) - float(want_loss)) < 5e-6
    assert abs(float(loss_by_layer) - float(want_loss)) < 5e-6
    assert abs(float(loss) - math.log(250)) < 0.1
    assert float(agree) == 1.0 and float(metrics["moe_rows_overflow"]) == 0
    got = lm_gqa_weights.reference_tree(grad["backbone"])
    assert jax.tree.structure(got) == jax.tree.structure(want_grad)
    rel = _rel(got, want_grad)
    assert max(jax.tree.leaves(rel)) < 5e-5, rel
    assert max(jax.tree.leaves(_rel(by_layer, want_grad))) < 5e-5
    # every leaf takes a gradient, the routers (fed from the layer's
    # input) among them
    assert min(float(jnp.linalg.norm(g)) for g in jax.tree.leaves(got)) > 0
    # the reference's own router makes the same choices in float32
    _, own = jax.jit(ref.loss_fn, static_argnums=2)(w, tokens, shape, None)
    assert float(own) == 1.0


def test_the_layer_reads_what_the_issue_says(tiny_model):
    """The comparison above has the resolution to tell the layer's rules
    apart: each of them changed in the REFERENCE's place moves the logits
    by far more than the tolerance the program met — a global layer that
    rotates and has a window, another theta, a window one key shorter,
    every layer global."""
    from reference import smallthinker_fp32 as ref

    _, _, batch, _, w, shape = tiny_model
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(ref.logits, static_argnums=2)
        base = logits(w, batch["tokens"], shape)
        moved = {name: float(jnp.max(jnp.abs(
            logits(w, batch["tokens"], other) - base)))
            for name, other in {
                "global layer as a window layer": dataclasses.replace(
                    shape, layers=(("swa", "moe"),) * 4),
                "theta": dataclasses.replace(shape, rope_theta=1e4),
                "window": dataclasses.replace(shape, window=36)}.items()}
        hidden = jax.jit(ref.hidden, static_argnums=(2, 4))
        moved["every layer global"] = float(jnp.max(jnp.abs(
            hidden(w, batch["tokens"], shape, None, "no_window")[0]
            - hidden(w, batch["tokens"], shape, None, "fp32")[0])))
    # (test_model_is_the_reference met 5e-6; the least here, theta, reads
    # 1.5e-4 under the unit embedding of lm_gqa_weights)
    assert all(v > 1e-4 for v in moved.values()), moved


def test_reference_controls_differ(tiny_model):
    """The controls of the configuration's check are other functions: the
    float32 set lowered to bfloat16 (the loss moves by bfloat16's
    rounding, not float32's), the window left out, a held expert left
    out."""
    from reference import smallthinker_fp32 as ref

    _, _, batch, _, w, shape = tiny_model
    fn = jax.jit(ref.loss_fn, static_argnums=(2, 4))
    with jax.default_matmul_precision("highest"):
        loss = {v: float(fn(w, batch["tokens"], shape, None, v)[0])
                for v in ref.VARIANTS}
    assert 1e-5 < abs(loss["bf16"] - loss["fp32"]) < 0.1
    grad = jax.jit(jax.grad(lambda w: ref.loss_fn(
        w, batch["tokens"], shape, None, "bf16")[0]))(w)
    assert {x.dtype for x in jax.tree.leaves(grad)} == {jnp.dtype("float32")}
    assert abs(loss["no_window"] - loss["fp32"]) > 1e-7
    assert abs(loss["drop_expert"] - loss["fp32"]) > 1e-7
    assert ref.__name__ and "dinov3_tpu" not in open(ref.__file__).read().split(
        '"""', 2)[2]


# ---------------- (d) the family on the normal path ----------------

@pytest.fixture(scope="module")
def tiny_setup():
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32"])
    batch = make_synthetic_batch(cfg, 2, seed=0)
    setup = build_train_setup(
        cfg, {k: jnp.asarray(v) for k, v in batch.items()},
        devices=jax.devices()[:1])
    return cfg, batch, setup


def test_three_steps_are_the_references(tiny_setup):
    """The compiled step on do_train's state against
    ``smallthinker_fp32.first_steps`` on the same weights, tokens and
    expert choices: each step's loss, the first gradient (from the first
    moment) leaf by leaf as a difference, every leaf's change after three
    steps — the numbers of the cell's check, at float32."""
    import lm_gqa_weights
    import lm_step_check
    from reference import smallthinker_fp32 as ref

    from dinov3_tpu.train import put_batch

    cfg, batch, setup = tiny_setup
    meta = setup.meta
    student = lm_gqa_weights.fill(setup.state.params["student"], 11)
    start = 1250
    state = jax.tree.map(lambda x: jnp.array(x, copy=True), setup.state)
    state = state._replace(
        params={"student": jax.tree.map(jnp.copy, student)},
        step=jnp.asarray(start, jnp.int32),
        opt_state=state.opt_state._replace(count=jnp.asarray(start, jnp.int32)))
    dc = meta.student_backbone.cfg
    recipe = ref.Recipe()
    dbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    losses, choices, moment = [], [], None
    routing = jax.jit(meta.routing)
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            choices.append(routing(state.params["student"], dbatch))
            state, metrics = setup.step_fn(
                state, put_batch(batch, setup.batch_shardings),
                setup.scalars(start + i), jax.random.key(1))
            losses.append(float(metrics["total_loss"]))
            assert set(metrics) == {"total_loss", "lm_loss", "moe_rows_fill",
                                    "moe_rows_overflow", "moe_load_max_over_mean"}
            assert float(metrics["moe_rows_overflow"]) == 0
            if i == 0:
                moment = jax.tree.map(np.asarray,
                                      state.opt_state.adam.mu["backbone"])
        grads = {}
        want = ref.first_steps(
            lm_gqa_weights.reference_tree(jax.tree.map(jnp.copy, student)["backbone"]),
            [dbatch["tokens"]] * 3, choices, _reference_shape(dc), recipe, start,
            keep_gradient=lambda g: grads.update(g=jax.tree.map(np.asarray, g)))
    first = jax.tree.map(lambda m: m / (1.0 - recipe.beta1),
                         lm_gqa_weights.reference_tree(moment))
    change = lm_gqa_weights.reference_tree(jax.tree.map(
        lambda a, b: np.float64(jnp.linalg.norm(a - b)),
        state.params["student"], student)["backbone"])
    program = {"losses": losses, "change_norms": change}
    reference = dict(want, grad_diff_norms=jax.tree.map(
        lambda a, b: np.float64(np.linalg.norm(a - b)), first, grads["g"]))
    gaps = lm_step_check.gaps(program, reference)
    assert gaps["router_agreement_share"] > 0.99, gaps
    assert gaps["loss_rel_gap"] < 1e-5, gaps
    assert gaps["param_change_gap"] < 1e-3, gaps
    for group in lm_step_check.GROUPS:
        assert gaps[f"grad_diff_gap_{group}"] < 2e-3, gaps
    # every leaf of the reference's layout has a group, the routers theirs
    groups = {p: lm_step_check.group_of(p)
              for p in lm_step_check.leaf_paths(want["grad_norms"])}
    assert set(groups.values()) == set(lm_step_check.GROUPS)
    assert groups["layers/2/ffn/router"] == "router"
    assert groups["layers/0/mixer/wk"] == groups["layers/3/norm1"] == "mixers"
    # no decay on the norms' scales, decay on everything else
    from dinov3_tpu.train.param_groups import build_multiplier_trees

    _, wd, _ = build_multiplier_trees(state.params["student"])
    flat = jax.tree.leaves(jax.tree.map(
        lambda a, b: (float(a), float(b)),
        lm_gqa_weights.reference_tree(wd["backbone"]),
        ref.decays(want["grad_norms"])))
    assert all(a == b for a, b in zip(flat[::2], flat[1::2]))


def test_compiled_step_holds_the_family_phases(tiny_setup):
    _, batch, setup = tiny_setup
    plan = setup.telemetry()
    assert plan.metric_names == setup.telemetry().metric_names
    assert set(plan.metric_names) == {
        "total_loss", "lm_loss", "moe_rows_fill", "moe_rows_overflow",
        "moe_load_max_over_mean"}
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), setup.state)
    args = (abstract, jax.eval_shape(plan.init_ring),
            {k: jnp.asarray(v) for k, v in batch.items()},
            setup.scalars(0), jax.random.key(0))
    with setup.mesh:
        text = plan.step_fn.lower(*args).compile().as_text()
    import re

    names = re.findall(r'op_name="([^"]*)"', text)
    found = {classify_step_phase(n) for n in names}
    family = {"lm_embed", "swa_mixer", "full_attn_mixer", "moe_ffn",
              "lm_head_loss"}
    assert {p for p, _ in found} - {None} == family | {
        "update", "telemetry_ring"}
    for phase in family - {"lm_embed"}:
        assert {(phase, "fwd"), (phase, "bwd")} <= found, phase
    for phase, inner in (("swa_mixer", "gqa_core"),
                         ("full_attn_mixer", "gqa_core"),
                         ("moe_ffn", "moe_route"), ("moe_ffn", "moe_experts")):
        assert any(phase in n and f"/{inner}/" in n for n in names), inner
    assert not any("/moe_shared/" in n for n in names)
    # the router's matmul reads the layer's input and stands under
    # moe_route all the same
    assert any("moe_route" in n and "dot_general" in n for n in names)
    assert family < set(STEP_PHASES)


def test_step_on_the_kernel_path_holds_one_kernel_body_a_shape(monkeypatch):
    """The family's whole step, traced with ``causal_attention_path``
    answering "kernel" (heads of 128, a sequence of whole blocks; the test
    steers, the program has no option): every attention layer gives its
    primal pass, the forward rule again under the layer's remat and ONE
    backward kernel, and no layer a loop over query blocks; the kernel
    bodies are traced once a shape (the window layers' and the global
    layer's), not once a call: a ``pallas_call`` traces its body whenever
    it is called, and the step is traced twice a set-up (PERF.md, PR 31)."""
    from test_lm_decoder import _loops_and_kernels

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.ops import causal_attention as kernels
    from dinov3_tpu.train import build_train_setup

    def names(extra):
        cfg = tiny_cfg(extra)
        batch = {k: jnp.asarray(v)
                 for k, v in make_synthetic_batch(cfg, 2, seed=0).items()}
        setup = build_train_setup(cfg, batch, devices=jax.devices()[:1],
                                  init_state=False)
        args = (setup.state, batch, setup.scalars(0), jax.random.key(0))
        layers = [m for m, _ in setup.meta.student_backbone.cfg.layers]
        return layers, _loops_and_kernels(
            jax.make_jaxpr(setup.step_fn)(*args).jaxpr, [])

    layers, plain = names([])
    assert layers == ["full_attn", "swa", "swa", "swa"]
    assert not [n for n in plain if n.startswith("causal_attn")]
    monkeypatch.setattr(kernels, "causal_attention_path",
                        lambda *a, **k: ("kernel", "the test says so"))
    bodies = []
    for body in ("_fwd_kernel", "_bwd_kernel"):
        def counted(*a, _body=getattr(kernels, body), **k):
            bodies.append((_body.__name__, k["window"], k.get("keep_lse")))
            return _body(*a, **k)
        monkeypatch.setattr(kernels, body, counted)
    _, found = names(["lm.head_dim=128", "lm.seq_len=1024",
                      "lm.sliding_window_size=300"])
    assert found.count(kernels.KERNEL_NAME) == 2 * len(layers)
    assert found.count(kernels.BACKWARD_KERNEL_NAME) == len(layers)
    assert sorted(bodies, key=str) == sorted([
        ("_fwd_kernel", w, keep) for w in (300, None)
        for keep in (False, True)] + [
            ("_bwd_kernel", w, None) for w in (300, None)], key=str)
    # no attention layer brought a loop (at TINY's 100 tokens the plain
    # tiles have none either: a single block of queries), nothing else moved
    loops = lambda xs: sum(n in ("scan", "while") for n in xs)  # noqa: E731
    assert loops(plain) == loops(found)


def test_benchmark_vocabulary_of_the_family_is_the_programs():
    with open(os.path.join(BENCH, "lm_gqa_phases.json")) as f:
        bench = json.load(f)
    named = set(bench["phases"]) | set(bench["inner"])
    named |= {p for sums in bench["metrics"].values() for p, _ in sums}
    named |= {p for p, _ in bench["inner_metrics"].values()}
    assert named <= set(STEP_PHASES), named - set(STEP_PHASES)
    for phase, inner in bench["inner_metrics"].values():
        assert inner in bench["inner"][phase]


def test_config_rules():
    from dinov3_tpu.configs.config import LM_ARCHS, is_lm_arch
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.models import DecoderConfig, LMDecoder, build_backbone

    cfg = tiny_cfg()
    assert is_lm_arch(cfg) and LM_ARCHS[:2] == ("kimi_linear", "smallthinker")
    tokens = make_synthetic_batch(cfg, 3, seed=(7, 0, 1))["tokens"]
    assert tokens.shape == (3, 100) and 0 <= tokens.min() and tokens.max() < 250
    model = build_backbone(cfg)
    assert isinstance(model, LMDecoder) and model.embed_dim == 64
    dc = model.cfg
    assert dc.layers == (("full_attn", "moe"), ("swa", "moe"), ("swa", "moe"),
                         ("swa", "moe"))
    assert (dc.router, dc.gate, dc.router_reads_layer_input,
            dc.num_shared_experts) == ("softmax", "relu", True, 0)
    with pytest.raises(ValueError, match="same flag"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.rope_layout=[1,1,1,1]"]))
    with pytest.raises(ValueError, match="softmax router"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.norm_topk_prob=false"]))
    # the recipe as it stands holds the published widths
    lm = load_config(RECIPE).lm
    assert (lm.hidden_size, lm.num_attention_heads, lm.num_key_value_heads,
            lm.head_dim, lm.sliding_window_size, lm.rope_theta) == (
                2560, 28, 4, 128, 4096, 1500000)
    assert (lm.moe_ffn_hidden_size, lm.moe_num_primary_experts,
            lm.moe_num_active_primary_experts, lm.rms_norm_eps,
            lm.seq_len) == (768, 64, 6, 1e-6, 16384)
    full = DecoderConfig.from_cfg(load_config(RECIPE))
    assert (full.num_experts // full.expert_shards, full.vocab_size) == (
        16, 37984)


def test_token_rope_is_the_rotation():
    """Position t turns the pair (j, j + d/2) by t * theta^(-2j/d): the
    scores of rotated q and k depend on positions through t - s alone."""
    from dinov3_tpu.ops.rope import rope_apply_full, token_rope_sincos

    d, theta = 16, 1.5e6
    sin, cos = token_rope_sincos(12, d, theta)
    assert sin.shape == cos.shape == (12, d)
    t, j = 7, 3
    angle = t * theta ** (-2 * j / d)
    assert float(sin[t, j]) == pytest.approx(math.sin(angle), abs=1e-6)
    assert float(cos[t, j + d // 2]) == pytest.approx(math.cos(angle), abs=1e-6)
    x = jax.random.normal(jax.random.key(0), (1, 1, 1, d))
    q = jnp.broadcast_to(x, (1, 12, 1, d))          # one vector at every position
    rq, rk = rope_apply_full(q, q, sin, cos)
    scores = jnp.einsum("bqhd,bkhd->qk", rq, rk)
    for lag in (0, 1, 5):
        diag = jnp.diagonal(scores, offset=-lag)
        np.testing.assert_allclose(diag, diag[0], rtol=1e-5)
    np.testing.assert_allclose(rq[0, 0], x[0, 0], atol=1e-7)  # position 0: identity


def test_save_and_resume_through_do_train(tmp_path):
    """The family through the normal entry point: three steps and a save,
    then a resume for one more."""
    from dinov3_tpu.train.train import main as train_main

    common = ["--config-file", RECIPE, "--output-dir", str(tmp_path / "run"),
              *TINY, "MODEL.DEVICE=cpu"]
    first = train_main(["--no-resume", "--max-iterations", "3", *common])
    assert first["iterations"] == 3 and len(first["losses"]) == 3
    assert all(abs(x - math.log(250)) < 0.5 for x in first["losses"])
    again = train_main(["--max-iterations", "4", *common])
    assert again["iterations"] == 4 and len(again["losses"]) == 1
    assert math.isfinite(again["final_loss"])
    assert abs(again["final_loss"] - first["losses"][-1]) < 0.05
