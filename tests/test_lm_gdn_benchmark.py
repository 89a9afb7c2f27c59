"""What the benchmark gained with the ``qwen3_next`` decoder's cell,
checked on the CPU (counts and file rules; times come from the chip
alone):

(c) the whole model is ``benchmark/reference/qwen3_next_fp32.py``: logits,
    loss, every leaf's gradient as a DIFFERENCE, the reference's
    layer-by-layer gradient against ``jax.grad`` of the whole, the
    controls;
(f) what the other families lower to is the parent's: the ``smallthinker``
    decoder's whole tiny step and its ``GQAMixer`` call with the new
    fields at their defaults (sha256 of the StableHLO text; Kimi's tiny
    step and the SSL step are pinned in ``tests/test_lm_gqa.py`` and
    ``tests/test_lm_decoder.py``; the four cells' full-width steps are
    ``scripts/lowered_step_sha.py``'s, by hand);
(g) ``benchmark/lm_gdn_flops.py`` against counts by hand and ISSUE 35's
    table, the cell's entries in ``BENCHMARK.json`` with a reader file for
    every per-layer metric it lists, the configuration's file against the
    published ``config.json`` and the sizing's arithmetic against the
    program's own tree, the driver's swap of the reference and the
    renaming of leaves.
"""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_lm_gdn import _reference_shape, _rel, tiny_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CELL = "qwen3-next-ep16-pretrain-8k"
CONFIG = os.path.join(BENCH, "configs", "qwen3-next-ep16-pretrain.json")
# config.json of Qwen/Qwen3-Next-80B-A3B-Instruct as the catalog beside
# the model-configs guide gives it
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}


@pytest.fixture(scope="module")
def conf():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------- (c) the model against the reference ----------------

@pytest.fixture(scope="module")
def tiny_model():
    """(cfg, meta, batch, seed-made student tree, reference weights,
    reference shape), float32 compute."""
    import lm_gdn_weights

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32"])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 2, seed=0).items()}
    meta = LMMetaArch(cfg)
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0))["student"]
    student = lm_gdn_weights.fill(abstract, 5)
    # seed-made routers of N(0, 0.02) put the logits within 1e-2 of each
    # other: spread them, so that float32 rounding moves no choice here;
    # and give the zero-centred scales values, so that 1 + w is not 1
    for i, (path, leaf) in enumerate(
            jax.tree_util.tree_flatten_with_path(student)[0]):
        names = [str(getattr(p, "key", p)) for p in path]
        node = student
        for n in names[:-1]:
            node = node[n]
        if names[-1] == "router":
            node["router"] = leaf * 25.0
        elif names[-1] == "scale":
            node["scale"] = 0.2 * jax.random.normal(
                jax.random.key(100 + i), leaf.shape)
    w = lm_gdn_weights.reference_tree(student["backbone"])
    return cfg, meta, batch, student, w, _reference_shape(
        meta.student_backbone.cfg)


def test_model_is_the_reference(tiny_model):
    import lm_gdn_weights
    from reference import qwen3_next_fp32 as ref

    _, meta, batch, student, w, shape = tiny_model
    assert shape.layers == (("gdn", "moe"),) * 3 + (("gated_attn", "moe"),)
    assert shape.rotary_dim == 4
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
        by_layer, loss_by_layer, _ = ref.gradient(
            w, tokens, choice, s=shape, r=ref.Recipe(clip_grad=1e9))
        moved = {name: float(jnp.max(jnp.abs(jax.jit(
            ref.logits, static_argnums=2)(w, tokens, other) - want_logits)))
            for name, other in {
                "rotary width": dataclasses.replace(shape, rotary_dim=8)}.items()}
    assert logits.shape == (2, 100, 250) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want_logits, atol=5e-6)
    assert abs(float(loss) - float(want_loss)) < 5e-6
    assert abs(float(loss_by_layer) - float(want_loss)) < 5e-6
    assert abs(float(loss) - math.log(250)) < 0.1
    assert float(agree) == 1.0 and float(metrics["moe_rows_overflow"]) == 0
    got = lm_gdn_weights.reference_tree(grad["backbone"])
    assert jax.tree.structure(got) == jax.tree.structure(want_grad)
    assert len(jax.tree.leaves(got)) == len(jax.tree.leaves(student))
    rel = _rel(got, want_grad)
    assert max(jax.tree.leaves(rel)) < 5e-5, rel
    assert max(jax.tree.leaves(_rel(by_layer, want_grad))) < 5e-5
    # every leaf takes a gradient
    assert min(float(jnp.linalg.norm(g)) for g in jax.tree.leaves(got)) > 0
    # the comparison has the resolution to tell the rules apart
    assert all(v > 1e-5 for v in moved.values()), moved


def test_reference_controls_differ(tiny_model):
    """The controls of the configuration's check are other functions: the
    float32 set lowered to bfloat16 (the loss moves by bfloat16's
    rounding, not float32's), the delta rule without its gate, a held
    expert left out, the chosen experts' weights not renormalised."""
    from reference import qwen3_next_fp32 as ref

    _, _, batch, _, w, shape = tiny_model
    assert ref.VARIANTS == ("fp32", "bf16", "no_decay", "drop_expert",
                            "no_renorm")
    fn = jax.jit(ref.loss_fn, static_argnums=(2, 4))
    with jax.default_matmul_precision("highest"):
        loss = {v: float(fn(w, batch["tokens"], shape, None, v)[0])
                for v in ref.VARIANTS}
    assert 1e-5 < abs(loss["bf16"] - loss["fp32"]) < 0.1
    assert abs(loss["no_decay"] - loss["fp32"]) > 1e-7
    assert abs(loss["drop_expert"] - loss["fp32"]) > 1e-7
    assert abs(loss["no_renorm"] - loss["fp32"]) > 1e-7
    with pytest.raises(ValueError):
        ref.first_steps(w, [], [], shape, ref.Recipe(), 0, "no_window")
    source = open(ref.__file__).read().split('"""', 2)[2]
    assert "dinov3_tpu" not in source and "pallas" not in source


# ---------------- (f) the other families' programs ----------------

# sha256 of the StableHLO text of two programs the parent of PR 35 (commit
# 3dc5595) lowers in this sandbox under this suite's conftest, no
# locations: the ``smallthinker`` decoder's whole telemetry step at
# tests/test_lm_gqa.py's TINY, and its ``GQAMixer`` call (a window,
# rotary over the whole head) with its gradient. What PR 35 added (a
# partial rotary, q/k norms, an output gate, zero-centred norms, a gated
# shared expert) moves neither.
# PR 47 MOVED the step's on purpose (ROADMAP D17: the routed layer's row
# movement is ``ops/routed_rows.py`` now; before: 0dcaf532...b32435e1) and
# re-made it on its final tree; the mixer's call did not move.
SMALLTHINKER_STEP_SHA256 = "30d95867eaabc09b91b057cfbd29afb57779768abc28f3641c406411a615761d"
GQA_MIXER_SHA256 = "5957a0747fda7ffd526d7ff03ce375ef05dc8d2558c19ea314746c9af49ee7dc"


def _smallthinker_step():
    from test_lm_gqa import lowered_tiny_step
    from test_lm_gqa import tiny_cfg as gqa_tiny_cfg

    return lowered_tiny_step(gqa_tiny_cfg())


def _gqa_mixer_call():
    import flax.linen as nn

    from dinov3_tpu.models.decoder import GQAMixer

    mixer = GQAMixer(6, 2, 16, 37, 1.5e6)
    x = jax.ShapeDtypeStruct((2, 100, 64), jnp.bfloat16)
    params = nn.meta.unbox(jax.eval_shape(
        lambda: mixer.init(jax.random.key(0), jnp.zeros(x.shape, x.dtype))))
    return jax.jit(jax.grad(
        lambda p, x: jnp.sum(mixer.apply(p, x).astype(jnp.float32)),
        argnums=(0, 1))).lower(params, x)


@pytest.mark.parametrize("lower, want", [
    (_smallthinker_step, SMALLTHINKER_STEP_SHA256),
    (_gqa_mixer_call, GQA_MIXER_SHA256)], ids=["smallthinker_step", "gqa_mixer"])
def test_the_parents_programs_are_unchanged(lower, want):
    from test_lm_gqa import _sha

    assert _sha(lower()) == want


# ---------------- (g) the benchmark's files ----------------

def test_required_flops_are_the_issues_table(conf):
    """By hand, from the published widths: a Gated DeltaNet mixer's
    projections 2 x 33.69 M, its convolution and 7 d_k d_v a head; the
    attention mixer's projections 2 x 27.26 M and the causal core at
    (T + 1) / 2 keys; a routed layer's router, shared expert with its gate
    and the 0.625 rows a token this shard's experts get; the head."""
    import lm_flops
    import lm_gdn_flops

    shape = conf["flops"]
    d, t = 2048, 8192
    gdn = (2 * (d * 12288 + d * 64 + 4096 * d) + 2 * 4 * 8192
           + 7 * 32 * 128 * 128)
    assert 2 * (d * 12288 + d * 64 + 4096 * d) == pytest.approx(2 * 33.69e6, rel=1e-3)
    attn = 2 * (d * 8192 + 2 * d * 512 + 4096 * d) + 2 * (t + 1) / 2 * 16 * 512
    assert 2 * (d * 8192 + 2 * d * 512 + 4096 * d) == pytest.approx(2 * 27.26e6, rel=1e-3)
    rows = 10 * 32 / 512
    moe = 2 * d * 512 + 2 * 3 * d * 512 + 2 * d + rows * 2 * 3 * d * 512
    parts = lm_gdn_flops.forward_flops_per_token(shape)
    assert parts["gdn"] == pytest.approx(3 * gdn)
    assert parts["gated_attn"] == pytest.approx(attn)
    assert parts["ffn"] == pytest.approx(4 * moe)
    assert parts["head"] == 2 * d * 18992
    per_step = lm_gdn_flops.train_flops_per_token(shape) * 2 * t
    assert per_step == pytest.approx(22.71e12, rel=1e-3)
    # the delta rule's core: Kimi's count of the chunked form at the same
    # shape, the bytes less (one decay a head, q and k at 16 heads)
    tokens = 2 * t
    ops, nbytes = lm_gdn_flops.gdn_core_train(tokens, 16, 32, 128, 128)
    assert ops == lm_flops.kda_core_train(tokens, 32, 128, 128)[0]
    per_token = 2 * 16 * 128 * 2 + 32 * (128 * 2 + 4 + 4 + 4 * 128)
    out = 4 * 128 * 32
    assert nbytes == tokens * (per_token + 2 * (per_token - out) + out)
    assert nbytes < 0.6 * lm_flops.kda_core_train(tokens, 32, 128, 128)[1]
    # the routed experts' rows: ten pairs a token at 32 of 512 held
    assert rows * tokens == 10240
    # the attention core: every causal pair of 16 heads of 256 + 256
    import lm_gqa_flops

    a_ops, a_bytes = lm_gqa_flops.gqa_core_train(t, None, 16, 2, 256)
    assert a_ops == 3 * (t * (t + 1) // 2) * 16 * 2 * 512
    assert a_bytes == 2 * 2 * t * 256 * (2 * 16 + 2 * 2)
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]["TPU v5 lite"]
    # the attention core is bound by operations; the delta rule's required
    # work sits at the ridge (1.83 ms of products, 1.65 ms of bytes a layer)
    assert a_ops / peaks["bf16_flops_per_s"] > 20 * a_bytes / peaks["hbm_bytes_per_s"]
    assert 1.0 < (ops / peaks["bf16_flops_per_s"]) / (nbytes / peaks["hbm_bytes_per_s"]) < 1.2


def test_cell_and_its_files(bench, conf):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and bench["workloads"][4] is cell
    assert len(cell["why"]) <= 200
    assert all(w["chips"] == 1 for w in bench["workloads"])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == conf["source"] and entry["file"].endswith(
        cell["config"] + ".json")
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == sorted(REDUCED)
    traffic = json.load(open(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json")))
    assert traffic["driver"] == "lm_gdn_train_steps"
    assert os.path.isfile(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    assert (traffic["pool_batches"], traffic["warmup_steps"],
            traffic["trace_lead_steps"], traffic["traced_steps"],
            traffic["start_iteration"]) == (8, 3, 2, 8, 1250)
    # the metrics of the step (set-up's seven: tests/test_setup_spans.py)
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())
              and m["moves"] == "train_img_per_s_chip"]
    assert len(listed) == 16 and listed[-8:] == [
        "lm_gdn_ms_per_step", "lm_gdn_core_ms_per_step",
        "lm_gdn_core_roofline_pct", "lm_gated_attn_ms_per_step",
        "lm_gated_attn_core_roofline_pct", "lm_gdn_unattributed_pct",
        "lm_gdn_mfu_pct", "lm_gated_attn_core_ms_per_step"]
    assert set(listed[:8]) == {
        "train_host_ms_per_step", "train_device_ms_per_step",
        "train_device_idle_pct", "train_update_ms_per_step",
        "lm_ffn_ms_per_step", "lm_moe_experts_ms_per_step",
        "lm_head_loss_ms_per_step", "lm_moe_load_max_over_mean"}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert os.path.isfile(os.path.join(
                BENCH, "layer_metrics", m["name"] + ".py"))
            assert m["moves"] in ("train_img_per_s_chip", "setup_s")
            if m["name"] in listed[-7:]:
                assert m["workloads"] == [CELL], m["name"]
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"setup_s", "train_img_per_s_chip"}
    # every key of the published config, every width the published one;
    # the cut is depth, experts held, vocabulary
    for key, value in PUBLISHED.items():
        assert conf[key] == REDUCED.get(key, value), key
    assert conf["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert conf["deployment"]["chips_sharing_a_layers_experts"] == 16
    assert len(json.dumps(bench)) < 64 * 1024


def test_limits_lie_between_their_readings(conf):
    """Every limit lies over what sound runs read, and each control (the
    next precision down, the planted fault) is refused by at least one
    limit on each of its seeds."""
    import lm_step_check

    check = conf["check"]
    for key in lm_step_check.UPPER + lm_step_check.LOWER:
        assert isinstance(check[key], float), key
    sound = check["readings"]["sound"]
    assert min(len(v) for v in sound.values()) >= 4
    for key in lm_step_check.UPPER:
        assert max(sound[key]) < check[key], key
    for key in lm_step_check.LOWER:
        assert min(sound[key]) > check[key], key
    for name in ("bf16", "no_decay", "no_renorm", "drop_expert"):
        # a control follows the program's expert choices: it reads no
        # router_agreement_share of its own
        control = {k: v for k, v in check["readings"][name].items()
                   if k in lm_step_check.UPPER}
        assert set(control) == set(lm_step_check.UPPER), name
        seeds = min(len(v) for v in control.values())
        assert seeds >= 2
        for i in range(seeds):
            assert any(control[key][i] > check[key] for key in control), (name, i)


def test_recipe_reference_and_program_agree(conf):
    from reference import qwen3_next_fp32 as ref

    from dinov3_tpu.configs import load_config
    from dinov3_tpu.models import DecoderConfig
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch
    from dinov3_tpu.train.schedules import build_schedules

    cfg = load_config(os.path.join(REPO, conf["recipe"]), conf["overrides"])
    recipe = ref.Recipe.from_config(conf["reference"])
    sched = build_schedules(cfg)
    assert recipe.schedule(1250)["lr"] == pytest.approx(3e-4 * 1250 / 12499)
    for it in (0, 1250, 1252, 12499, 12500, 60000):
        want, got = sched.at(it), recipe.schedule(it)
        assert got["lr"] == pytest.approx(want["lr"], rel=1e-6)
        assert got["weight_decay"] == pytest.approx(want["weight_decay"], rel=1e-6)
    assert (recipe.beta1, recipe.beta2, recipe.clip_grad) == (
        cfg.optim.adamw_beta1, cfg.optim.adamw_beta2, cfg.optim.clip_grad)
    shape = ref.Shape.from_config(conf["shape"])
    dc = DecoderConfig.from_cfg(cfg)
    assert shape == _reference_shape(dc)
    assert shape.layers == tuple(map(tuple, conf["flops"]["layers"]))
    # the recipe holds what the file says it holds, the file what was published
    for key in PUBLISHED.keys() - REDUCED.keys() & set(cfg.lm):
        if key in cfg.lm:
            assert cfg.lm[key] == PUBLISHED[key], key
    assert (dc.num_experts, dc.num_experts // dc.expert_shards, dc.vocab_size,
            len(dc.layers)) == (PUBLISHED["num_experts"], 32, 18992, 4)
    flops = conf["flops"]
    assert (flops["seq_len"], flops["experts_held"], flops["num_experts"]) == (
        int(cfg.lm.seq_len), 32, 512)
    for key in ("hidden_size", "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "moe_intermediate_size", "shared_expert_intermediate_size"):
        assert flops[key] == PUBLISHED[key], key
    # the sizing's arithmetic, from the program's own tree at full width:
    # ISSUE 35's 625.7 M parameters held, 10.0 GB of state
    meta = LMMetaArch(cfg)
    tree = jax.eval_shape(lambda r: meta.init_params(
        r, {"tokens": jnp.zeros((2, 8192), jnp.int32)}), jax.random.key(0))
    count = lambda t: sum(math.prod(a.shape) for a in jax.tree.leaves(t))  # noqa: E731
    layers = tree["student"]["backbone"]
    assert count(layers["layers_0"]["gdn"]) == pytest.approx(33.72e6, rel=1e-3)
    assert count(layers["layers_3"]["attn"]) == pytest.approx(27.26e6, rel=1e-3)
    assert count(layers["layers_1"]["experts"]) == 2048 * 512 + 32 * 3 * 2048 * 512
    assert count(layers["layers_0"]) == pytest.approx(138.58e6, rel=1e-3)
    assert count(layers["layers_3"]) == pytest.approx(132.12e6, rel=1e-3)
    held = count(tree)
    assert held == pytest.approx(625.7e6, rel=2e-4)
    assert conf["sizing"]["parameters_held_M"]["all"] == pytest.approx(
        held / 1e6, abs=0.05)
    assert held * 16 == pytest.approx(10.0e9, rel=2e-3)


def test_driver_swaps_the_reference_and_the_leaves_names():
    """The driver runs a copy of ``lm_train_steps`` of its own with this
    family's reference and renaming in it; the other decoder cells' own
    modules keep theirs."""
    import run as harness

    sys.modules.setdefault("run", harness)
    import lm_gdn_weights
    from reference import qwen3_next_fp32

    kimi = harness.load_module(harness.DRIVER_DIR, "lm_train_steps")
    mine = harness.load_module(harness.DRIVER_DIR, "lm_gdn_train_steps")
    assert kimi.kimi_linear_fp32.__name__.endswith("kimi_linear_fp32")
    g = mine.run.__globals__
    assert g["kimi_linear_fp32"] is qwen3_next_fp32
    assert g["lm_weights"] is lm_gdn_weights and g["Rig"] is mine.Rig
    assert callable(mine.train_steps.host_pool)
    # the fill: zero-centred scales 0, the delta rule's output norm and
    # dt_bias 1, A_log the log of a draw on (0, 16), matrices N(0, 0.02)
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    meta = LMMetaArch(tiny_cfg())
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, {"tokens": jnp.zeros((2, 100), jnp.int32)}),
        jax.random.key(0))["student"]
    tree = lm_gdn_weights.reference_tree(lm_gdn_weights.fill(abstract, 3)["backbone"])
    for leaf in (tree["norm"], tree["layers"][2]["norm1"],
                 tree["layers"][3]["mixer"]["q_norm"]):
        assert float(np.max(np.abs(leaf))) == 0.0
    gdn = tree["layers"][0]["mixer"]
    assert float(np.min(gdn["o_norm"])) == float(np.min(gdn["dt_bias"])) == 1.0
    assert np.all(np.isfinite(gdn["A_log"])) and float(np.max(gdn["A_log"])) < math.log(16)
    assert abs(float(np.std(tree["layers"][1]["ffn"]["w12"])) - 0.02) < 2e-3
    assert abs(float(np.std(tree["embed"])) - 0.02) < 2e-3
    assert set(tree["layers"][0]["ffn"]) == {
        "router", "w12", "w3", "shared", "shared_gate"}
