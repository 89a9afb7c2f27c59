"""The ``kimi_linear`` decoder and its next-token step (models/decoder.py,
ops/kda.py, ops/attention.py's causal blockwise path, ops/ffn.py's
``RoutedExpertsFFN``, train/lm_meta_arch.py) at a tiny size on the CPU,
seeded weights:

(a) the chunked delta rule against the recurrence and (b) latent
    attention's blockwise causal core against a plain masked softmax are in
    tests/test_lm_ops.py (a file, and so a worker, of their own);
(c) the whole model's logits, loss and gradient against the benchmark's
    float32 reference (benchmark/reference/kimi_linear_fp32.py, which
    imports nothing of the program and computes KDA by the recurrence);
(d) the share test of expert parallelism: the routed sums of all the
    shards plus the shared expert counted once equal the uncut layer;
(e) dropless routing under a skewed router, and a non-finite loss with the
    overflow counted when the router sends more than the row capacity;
(f) the step on the normal path: param groups, the router bias left where
    it was, phases in the compiled text, save and resume through do_train;
(g) the SSL step's StableHLO is the parent's, byte for byte.
"""

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
from dinov3_tpu.utils import LM_STEP_PHASES, STEP_PHASES, classify_step_phase

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
for _p in (BENCH,):
    if _p not in sys.path:
        sys.path.insert(0, _p)

RECIPE = os.path.join(REPO, "configs", "train", "kimi_linear_ep32.yaml")
TINY = [
    "lm.hidden_size=64", "lm.intermediate_size=128", "lm.kda_num_heads=2",
    "lm.kda_head_dim=16", "lm.num_attention_heads=2", "lm.kv_lora_rank=32",
    "lm.qk_nope_head_dim=16", "lm.qk_rope_head_dim=8", "lm.v_head_dim=16",
    "lm.num_experts=16", "lm.num_experts_per_token=4",
    "lm.moe_intermediate_size=32", "lm.expert_shards=4", "lm.vocab_size=256",
    "lm.seq_len=96", "train.batch_size_per_device=2",
    "telemetry.flush_every=2"]


def tiny_cfg(extra=()):
    return load_config(RECIPE, overrides=[*TINY, *extra])


def _reference_shape(dc, first_expert=0):
    from reference import kimi_linear_fp32 as ref

    return ref.Shape(
        layers=dc.layers, kda_heads=dc.kda_num_heads,
        mla_heads=dc.num_attention_heads, kv_lora_rank=dc.kv_lora_rank,
        qk_nope_head_dim=dc.qk_nope_head_dim,
        qk_rope_head_dim=dc.qk_rope_head_dim, v_head_dim=dc.v_head_dim,
        top_k=dc.num_experts_per_token,
        routed_scaling_factor=dc.routed_scaling_factor,
        first_expert=first_expert, eps=dc.rms_norm_eps)


# (a) the delta rule and (b) the causal blockwise core: tests/test_lm_ops.py


def _loops_and_kernels(jaxpr, found):
    """Names of the Pallas calls, and the loops OUTSIDE them, of a
    program."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
            continue
        if eqn.primitive.name in ("while", "scan"):
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _loops_and_kernels(sub, found)
    return found


# ---------------- (c) the model against the reference ----------------

@pytest.fixture(scope="module")
def tiny_model():
    """(cfg, meta, batch, seed-made student tree, reference weights,
    reference shape), float32 compute."""
    import lm_weights

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32"])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 2, seed=0).items()}
    meta = LMMetaArch(cfg)
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0))["student"]
    student = lm_weights.fill(abstract, 5)
    w = lm_weights.reference_tree(student["backbone"])
    return cfg, meta, batch, student, w, _reference_shape(
        meta.student_backbone.cfg)


def test_model_is_the_reference(tiny_model):
    import lm_weights
    from reference import kimi_linear_fp32 as ref

    _, meta, batch, student, w, shape = tiny_model
    with jax.default_matmul_precision("highest"):
        logits = meta.student_backbone.apply(
            {"params": student["backbone"]}, batch["tokens"])
        (loss, (metrics, state)), grad = jax.value_and_grad(
            lambda p: meta.forward(p, {}, batch, state=meta.init_state(),
                                   iteration=0), has_aux=True)(student)
        assert state == {}  # the step keeps no routing
        choice = meta.routing(student, batch)
        assert choice.shape == (4, 2 * 96, 4) and int(choice.max()) < 16
        want_logits = ref.logits(w, batch["tokens"], shape, choice)
        (want_loss, agree), want_grad = jax.value_and_grad(
            ref.loss_fn, has_aux=True)(w, batch["tokens"], shape, choice)
    assert logits.shape == (2, 96, 256) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want_logits, atol=5e-6)
    assert abs(float(loss) - float(want_loss)) < 5e-6
    assert abs(float(loss) - math.log(256)) < 0.1
    assert float(agree) == 1.0 and float(metrics["moe_rows_overflow"]) == 0
    got = lm_weights.reference_tree(grad["backbone"])
    rel = jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b))
        / max(float(jnp.linalg.norm(b)), 1e-30), got, want_grad)
    assert max(jax.tree.leaves(rel)) < 5e-5, rel
    # the selection bias takes no gradient, in either
    for lw in got["layers"][1:]:
        assert float(jnp.max(jnp.abs(lw["ffn"]["router_bias"]))) == 0.0
    # the reference's own router makes the same choices in float32
    _, own = ref.loss_fn(w, batch["tokens"], shape, None)
    assert float(own) == 1.0


def test_reference_controls_differ(tiny_model):
    """The two controls of the configuration's check are other functions:
    the float32 set lowered to bfloat16 (the loss moves by bfloat16's
    rounding, not float32's) and a held expert left out."""
    from reference import kimi_linear_fp32 as ref

    _, _, batch, _, w, shape = tiny_model
    with jax.default_matmul_precision("highest"):
        loss = {v: float(ref.loss_fn(w, batch["tokens"], shape, None, v)[0])
                for v in ref.VARIANTS}
    assert 1e-4 < abs(loss["bf16"] - loss["fp32"]) < 0.1
    grad = jax.grad(lambda w: ref.loss_fn(w, batch["tokens"], shape, None,
                                          "bf16")[0])(w)
    assert {x.dtype for x in jax.tree.leaves(grad)} == {jnp.dtype("float32")}
    assert abs(loss["drop_expert"] - loss["fp32"]) > 1e-7
    assert ref.__name__ and "dinov3_tpu" not in open(ref.__file__).read().split(
        '"""', 2)[2]


# ---------------- (d) the share test ----------------

# the routed layer's two paths (``ops/grouped_matmul.py``): float32 rows
# take ``lax.ragged_dot``; bfloat16 rows at lane-wide widths with the
# kernels interpreted take the kernel path, two roundings away
PATHS = pytest.mark.parametrize("kernel", [False, True],
                                ids=["ragged_dot", "kernel_interpreted"])


def _how(kernel):
    return dict(dtype=jnp.bfloat16, interpret=True) if kernel else dict(
        dtype=jnp.float32)


def _close(got, want, kernel, atol):
    if not kernel:
        return np.testing.assert_allclose(got, want, atol=atol)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.linalg.norm(got - want) <= 2e-2 * np.linalg.norm(want)


@PATHS
def test_all_shards_and_the_shared_expert_once_make_the_uncut_layer(kernel):
    """Guide section 4: at a small size, the parts of the result that all
    the shards give, with what every chip computes alike (the shared
    expert) counted once, add up to the uncut reference layer."""
    from reference import kimi_linear_fp32 as ref

    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    d, e, k, width, shards = (128, 16, 4, 128, 4) if kernel else (32, 16, 4, 16, 4)
    ks = jax.random.split(jax.random.key(0), 7)
    x = jax.random.normal(ks[0], (2, 64 if kernel else 40, d))
    full = {"router": jax.random.normal(ks[1], (d, e)) * 0.5,
            "router_bias": jax.random.normal(ks[2], (e,)) * 0.1,
            "w12": jax.random.normal(ks[3], (e, d, 2 * width)) * 0.2,
            "w3": jax.random.normal(ks[4], (e, width, d)) * 0.2,
            "shared": {"w12": jax.random.normal(ks[5], (d, 2 * width)) * 0.2,
                       "w3": jax.random.normal(ks[6], (width, d)) * 0.2}}
    shape = ref.Shape(layers=(), kda_heads=1, mla_heads=1, kv_lora_rank=1,
                      qk_nope_head_dim=1, qk_rope_head_dim=1, v_head_dim=1,
                      top_k=k, routed_scaling_factor=2.446, first_expert=0)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(x.reshape(-1, d), full, shape, None, "fp32")
        shared = ref.swiglu(x.reshape(-1, d), full["shared"]["w12"],
                            full["shared"]["w3"])
        total, choices = shared, []
        held = e // shards
        for shard in range(shards):
            layer = RoutedExpertsFFN(width, e, k, shards, shard, 2.446, 4.0,
                                     **_how(kernel))
            part = {"router": full["router"], "router_bias": full["router_bias"],
                    "w12": full["w12"][shard * held:(shard + 1) * held],
                    "w3": full["w3"][shard * held:(shard + 1) * held]}
            y, aux = layer.apply({"params": part}, x)
            assert float(aux["overflow"]) == 0
            total = total + y.reshape(-1, d)
            choices.append(np.asarray(aux["choice"]))
    for c in choices[1:]:  # every shard routes over all the experts alike
        np.testing.assert_array_equal(c, choices[0])
    _close(total, want, kernel, atol=1e-5)


# ---------------- (e) dropless routing ----------------

def _skewed_layer(rows_factor, kernel):
    from dinov3_tpu.ops.ffn import RoutedExpertsFFN

    d, e, k = 128 if kernel else 16, 8, 2
    layer = RoutedExpertsFFN(128 if kernel else 8, e, k, shards=2, shard=0,
                             scale=1.0, rows_factor=rows_factor, **_how(kernel))
    x = jax.random.normal(jax.random.key(1), (64, d))
    import flax.linen as nn

    params = nn.meta.unbox(layer.init(jax.random.key(2), x)["params"])
    # a router that sends every token to experts 0 and 1, both held here
    params = dict(params, router_bias=jnp.array(
        [5.0, 4.0, 0, 0, 0, 0, 0, 0], jnp.float32))
    return layer, params, x


@PATHS
def test_skewed_router_drops_nothing_inside_the_bound(kernel):
    from dinov3_tpu.ops.ffn import routed_rows_capacity

    layer, params, x = _skewed_layer(rows_factor=2.0, kernel=kernel)
    y, aux = layer.apply({"params": params}, x)
    # every one of the 128 pairs lands here: twice the even share of 64
    assert float(aux["rows"]) == 128 and float(aux["capacity"]) == 128
    assert float(aux["overflow"]) == 0
    assert float(aux["load_max_over_mean"]) == 2.0  # 64, 64, 0, 0
    # ... and the result is the dense sum over the two chosen experts
    s = jax.nn.sigmoid(x @ params["router"])[:, :2]
    w = s / jnp.sum(s, -1, keepdims=True)
    want = 0.0
    for i in range(2):
        gate, value = jnp.split(x @ params["w12"][i], 2, -1)
        want = want + w[:, i:i + 1] * ((jax.nn.silu(gate) * value)
                                       @ params["w3"][i])
    _close(y, want, kernel, atol=1e-6)
    assert routed_rows_capacity(16384, 8, 256, 8) == 8192  # the shipped factor
    assert routed_rows_capacity(64, 2, 8, 4, 1.0) == 128  # rounded up to 128
    assert routed_rows_capacity(64, 2, 8, 4, 100.0) == 128  # every pair there is


@PATHS
def test_overflow_is_counted_and_the_loss_is_not_finite(kernel):
    layer, params, x = _skewed_layer(rows_factor=0.5, kernel=kernel)
    # (the bound's floor of 128 rows is every pair of this tiny layer:
    # shrink the buffer by more tokens instead)
    x = jnp.tile(x, (4, 1))
    y, aux = layer.apply({"params": params}, x)
    assert float(aux["capacity"]) == 128 and float(aux["rows"]) == 512
    assert float(aux["overflow"]) == 384
    assert bool(jnp.all(jnp.isfinite(y.astype(jnp.float32))))
    if kernel:  # (the tiny model's widths take ``ragged_dot``)
        return
    # through the meta-arch: the loss is NaN and the counter says why
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    cfg = tiny_cfg()
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 4, seed=0).items()}
    meta = LMMetaArch(cfg)
    params = meta.init_params(jax.random.key(0), batch)
    # every token of layer 2 to the 4 experts held: twice the capacity
    experts = params["student"]["backbone"]["layers_1"]["experts"]
    experts["router_bias"] = experts["router_bias"].at[:4].set(5.0)
    loss, (metrics, _) = meta.forward(
        params["student"], {}, batch, state=meta.init_state(), iteration=0)
    assert float(metrics["moe_rows_overflow"]) == 4 * 96 * 4 - 768
    assert float(metrics["moe_rows_fill"]) == 2.0
    assert not math.isfinite(float(loss))
    assert math.isfinite(float(metrics["lm_loss"]))


# ---------------- (f) the step on the normal path ----------------

@pytest.fixture(scope="module")
def tiny_setup():
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    cfg = tiny_cfg()
    batch = make_synthetic_batch(cfg, 2, seed=0)
    setup = build_train_setup(
        cfg, {k: jnp.asarray(v) for k, v in batch.items()},
        devices=jax.devices()[:1])
    return cfg, batch, setup


def test_teacherless_state_and_param_groups(tiny_setup):
    from dinov3_tpu.train.param_groups import build_multiplier_trees

    cfg, batch, setup = tiny_setup
    state = setup.state
    assert set(state.params) == {"student"}
    assert state.center_state == {}
    assert setup.meta.ema_teacher is False
    assert setup.meta.supports_accum is False
    assert setup.mask_rows_limit(batch) is None
    _, wd, last = build_multiplier_trees(state.params["student"])
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(wd)[0]}
    free = {k for k, v in flat.items() if v == 0.0}
    assert all(k.endswith(("scale", "A_log", "dt_bias", "router_bias"))
               for k in free), free
    assert {k.rsplit("/", 1)[-1] for k in free} == {
        "scale", "o_norm_scale", "A_log", "dt_bias", "router_bias"}
    assert not any(jax.tree.leaves(last))


def test_steps_train_and_leave_the_router_bias(tiny_setup):
    from dinov3_tpu.train import put_batch

    cfg, batch, setup = tiny_setup
    state = jax.tree.map(lambda x: jnp.array(x, copy=True), setup.state)
    before = jax.tree.map(np.asarray, state.params["student"]["backbone"])
    rng = jax.random.key(1)
    losses = []
    for i in range(3):
        state, metrics = setup.step_fn(
            state, put_batch(batch, setup.batch_shardings),
            setup.scalars(1250 + i), rng)
        losses.append(float(metrics["total_loss"]))
        assert set(metrics) == {"total_loss", "lm_loss", "moe_rows_fill",
                                "moe_rows_overflow", "moe_load_max_over_mean"}
        assert float(metrics["moe_rows_overflow"]) == 0
        assert 0 < float(metrics["moe_rows_fill"]) <= 1
    assert all(math.isfinite(x) for x in losses)
    assert int(state.step) == 3 and set(state.params) == {"student"}
    after = state.params["student"]["backbone"]
    assert state.center_state == {}
    np.testing.assert_array_equal(
        after["layers_1"]["experts"]["router_bias"],
        before["layers_1"]["experts"]["router_bias"])
    assert float(jnp.max(jnp.abs(
        after["layers_1"]["experts"]["w12"]
        - before["layers_1"]["experts"]["w12"]))) > 0
    for name in ("q_proj", "f_b", "o_proj"):
        assert float(jnp.max(jnp.abs(
            after["layers_0"]["kda"][name]["kernel"]
            - before["layers_0"]["kda"][name]["kernel"]))) > 0, name
    with pytest.raises(ValueError, match="accum_steps"):
        from dinov3_tpu.train.train_step import make_train_step

        make_train_step(setup.meta, setup.optimizer, accum_steps=2)


def test_compiled_step_holds_the_decoder_phases(tiny_setup):
    _, batch, setup = tiny_setup
    plan = setup.telemetry()
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
    # (the other decoder families' mixers are tests/test_lm_gqa.py's and
    # tests/test_lm_gdn.py's and tests/test_lm_dsa.py's ... test_lm_ssd.py's)
    assert {p for p, _ in found} - {None} == set(LM_STEP_PHASES) - {
        "swa_mixer", "full_attn_mixer", "gdn_mixer", "gated_attn_mixer",
        "dsa_mixer", "sconv_mixer", "ssm_mixer"} | {
            "update", "telemetry_ring"}
    for phase in ("kda_mixer", "mla_mixer", "dense_ffn", "moe_ffn",
                  "lm_head_loss"):
        assert {(phase, "fwd"), (phase, "bwd")} <= found, phase
    for phase, inner in (("kda_mixer", "kda_core"), ("mla_mixer", "mla_core"),
                         ("moe_ffn", "moe_route"), ("moe_ffn", "moe_experts"),
                         ("moe_ffn", "moe_shared")):
        assert any(phase in n and f"/{inner}/" in n for n in names), inner
    assert set(LM_STEP_PHASES) < set(STEP_PHASES)


def test_step_on_the_kernel_path_holds_one_backward_call_a_kda_layer(monkeypatch):
    """The decoder's whole step, traced with ``kda_path`` answering
    "kernel" (heads of 128 x 128; the test steers, the program has no
    option): every KDA layer gives its primal pass, the forward rule again
    under the layer's remat and ONE backward kernel, and no KDA layer a
    loop over chunks (the loops left are MLA's and the loss blocks');
    the kernel bodies are traced once a shape, not once a call."""
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.ops import kda
    from dinov3_tpu.train import build_train_setup

    def names(extra):
        cfg = tiny_cfg(extra)
        batch = make_synthetic_batch(cfg, 2, seed=0)
        setup = build_train_setup(
            cfg, {k: jnp.asarray(v) for k, v in batch.items()},
            devices=jax.devices()[:1], init_state=False)
        args = (setup.state, {k: jnp.asarray(v) for k, v in batch.items()},
                setup.scalars(0), jax.random.key(0))
        n_kda = sum(mixer == "kda" for mixer, _ in setup.meta.student_backbone.cfg.layers)
        return n_kda, _loops_and_kernels(
            jax.make_jaxpr(setup.step_fn)(*args).jaxpr, [])

    n_kda, plain = names([])
    assert not [n for n in plain if n.startswith("kda_chunk")]
    monkeypatch.setattr(kda, "kda_path",
                        lambda *a, **k: ("kernel", "the test says so"))
    bodies = []
    for body in ("_fwd_kernel", "_bwd_kernel"):
        def counted(*a, _body=getattr(kda, body), **k):
            bodies.append(_body.__name__)
            return _body(*a, **k)
        monkeypatch.setattr(kda, body, counted)
    n_kda, found = names(["lm.kda_head_dim=128", "lm.seq_len=128"])
    assert found.count(kda.BACKWARD_KERNEL_NAME) == n_kda
    assert found.count(kda.KERNEL_NAME) == 2 * n_kda
    # the layers share ONE trace of each kernel body a shape (the pass,
    # the forward rule, the backward), whatever their number: a body
    # costs seconds of host time to trace at 32 heads (PERF.md, PR 31)
    assert sorted(bodies) == ["_bwd_kernel", "_fwd_kernel", "_fwd_kernel"]
    # the scan path's three loops a KDA layer are gone, nothing else moved
    loops = lambda xs: sum(n in ("scan", "while") for n in xs)  # noqa: E731
    assert loops(plain) - loops(found) == 3 * n_kda


def test_benchmark_vocabulary_of_the_decoder_is_the_programs():
    with open(os.path.join(BENCH, "lm_phases.json")) as f:
        bench = json.load(f)
    named = set(bench["phases"]) | set(bench["inner"])
    named |= {p for sums in bench["metrics"].values() for p, _ in sums}
    named |= {p for p, _ in bench["inner_metrics"].values()}
    assert named <= set(STEP_PHASES), named - set(STEP_PHASES)
    for phase, inner in bench["inner_metrics"].values():
        assert inner in bench["inner"][phase]


def test_synthetic_tokens_and_config_rules():
    from dinov3_tpu.data import batch_spec, make_synthetic_batch
    from dinov3_tpu.models import DecoderConfig, LMDecoder, build_backbone

    cfg = tiny_cfg()
    a = make_synthetic_batch(cfg, 3, seed=(7, 0, 1))
    b = make_synthetic_batch(cfg, 3, seed=(7, 0, 1))
    c = make_synthetic_batch(cfg, 3, seed=(7, 0, 2))
    assert set(a) == {"tokens"} and a["tokens"].shape == (3, 96)
    assert a["tokens"].dtype == np.int32 == batch_spec(cfg, 3)["tokens"][1]
    assert 0 <= a["tokens"].min() and a["tokens"].max() < 256
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert (a["tokens"] != c["tokens"]).any()
    model = build_backbone(cfg)
    assert isinstance(model, LMDecoder) and model.embed_dim == 64
    assert model.cfg.layers == (("kda", "dense"), ("kda", "moe"),
                                ("kda", "moe"), ("mla", "moe"), ("kda", "moe"))
    with pytest.raises(ValueError, match="must split layers"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.full_attn_layers=[3,4]"]))
    with pytest.raises(ValueError, match="q_lora_rank"):
        DecoderConfig.from_cfg(tiny_cfg(["lm.q_lora_rank=8"]))
    # the recipe as it stands holds the published widths
    lm = load_config(RECIPE).lm
    assert (lm.hidden_size, lm.kda_num_heads, lm.kda_head_dim,
            lm.short_conv_kernel_size) == (2304, 32, 128, 4)
    assert (lm.kv_lora_rank, lm.qk_nope_head_dim, lm.qk_rope_head_dim,
            lm.v_head_dim, lm.num_attention_heads) == (512, 128, 64, 128, 32)
    assert (lm.intermediate_size, lm.moe_intermediate_size, lm.num_experts,
            lm.num_experts_per_token, lm.routed_scaling_factor,
            lm.num_shared_experts, lm.rms_norm_eps) == (
                9216, 1024, 256, 8, 2.446, 1, 1e-5)


def test_save_and_resume_through_do_train(tmp_path):
    """The teacher-less state through the normal entry point: three
    steps and a save, then a resume for one more. (Two layers, one of
    each kind of mixer and of FFN: the step compiles twice here, and what
    is saved and restored does not depend on the depth.)"""
    from dinov3_tpu.train.train import main as train_main

    common = ["--config-file", RECIPE, "--output-dir", str(tmp_path / "run"),
              *TINY, "lm.num_hidden_layers=2", "lm.kda_layers=[1]",
              "lm.full_attn_layers=[2]", "MODEL.DEVICE=cpu"]
    first = train_main(["--no-resume", "--max-iterations", "3", *common])
    assert first["iterations"] == 3 and len(first["losses"]) == 3
    assert all(abs(x - math.log(256)) < 0.5 for x in first["losses"])
    again = train_main(["--max-iterations", "4", *common])
    assert again["iterations"] == 4 and len(again["losses"]) == 1
    assert math.isfinite(again["final_loss"])
    # the resumed run continued the first one's state, not a fresh one
    assert abs(again["final_loss"] - first["losses"][-1]) < 0.05


# ---------------- (g) the SSL step is the parent's ----------------

# sha256 of the default SSL telemetry step's StableHLO text (``.lower(
# ...).as_text()``, no locations) at test width, read UNDER PYTEST from
# PR 36's tree in this sandbox. A change to the step's skeleton that
# reaches the SSL program moves it; what the decoder PRs (27, 32, 35)
# added does not. Moved on purpose by PR 36 (82f65802... at its parent,
# d66609e, and since PR 27's parent b2aaaf8): the Sinkhorn CEs of
# losses/streaming.py differentiate by a closed-form ``custom_vjp``, so
# the transposed K-tile scans left the step, and the iBOT rows' forward
# is reductions over the whole plane, so its K-tile scan left it too.
SSL_STEP_SHA256 = "3971a7eba21b956b05819fe5a7d94101d3c02f744334d93d380ddcfc4b21eeac"


def test_ssl_step_stablehlo_is_unchanged():
    from test_fused_update import smol_cfg

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    cfg = smol_cfg(["student.drop_path_rate=0.1"])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 4, seed=0).items()}
    setup = build_train_setup(cfg, batch, devices=jax.devices()[:1],
                              init_state=False)
    plan = setup.telemetry()
    args = (setup.state, jax.eval_shape(plan.init_ring), batch,
            setup.scalars(0), jax.random.key(0))
    with setup.mesh:
        text = plan.step_fn.lower(*args).as_text()
    assert "loc(" not in text.split("\n", 1)[0]
    assert hashlib.sha256(text.encode()).hexdigest() == SSL_STEP_SHA256
