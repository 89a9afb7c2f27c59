"""What the benchmark gained with the ``lfm2_moe`` decoder's cell, checked
on the CPU (counts and file rules; times come from the chip alone):

(c) the whole model is ``benchmark/reference/lfm2_moe_fp32.py``: logits,
    loss, every leaf's gradient as a DIFFERENCE (the conv leaves and the
    tied leaf by themselves), the reference's layer-by-layer gradient
    against ``jax.grad`` of the whole, the four controls;
(g) ``benchmark/lm_sconv_flops.py`` against counts by hand and ISSUE 41's
    table, the chain's bytes, the cell's entries in ``BENCHMARK.json`` with
    a reader file for every per-layer metric it lists, the configuration's
    file against the published ``config.json`` and the sizing's arithmetic
    against the program's own tree, the check's limits against their
    readings, the driver's swap of reference, renaming and check.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_lm_sconv import _rel, reference_shape, tiny_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CELL = "lfm2-ep8-pretrain-8k"
CONFIG = os.path.join(BENCH, "configs", "lfm2-ep8-pretrain.json")
PERIOD = ["full_attention", "conv", "conv", "conv"]
# config.json of LiquidAI/LFM2-24B-A2B as the catalog beside the
# model-configs guide gives it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv", *(PERIOD * 10)][:40],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
REDUCED = {"num_hidden_layers": 5, "layer_types": ["conv", *PERIOD],
           "num_dense_layers": 1, "num_experts": 8, "vocab_size": 8192}
CONTROLS = ("bf16", "no_conv", "untied_head", "drop_expert")


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
    """(meta, batch, seed-made student tree, reference weights, reference
    shape), float32 compute."""
    import lm_sconv_weights

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32", "lm.seq_len=48"])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 2, seed=0).items()}
    meta = LMMetaArch(cfg)
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0))["student"]
    student = lm_sconv_weights.fill(abstract, 5)
    # seed-made routers of N(0, 0.02) put the scores within 1e-2 of each
    # other: spread them, so that float32 rounding moves no choice here;
    # and give the norm scales values, so that a scale left out shows
    for i, (path, leaf) in enumerate(
            jax.tree_util.tree_flatten_with_path(student)[0]):
        names = [str(getattr(p, "key", p)) for p in path]
        node = student
        for n in names[:-1]:
            node = node[n]
        if names[-1] == "router":
            node["router"] = leaf * 25.0
        elif names[-1] == "scale":
            node["scale"] = 1.0 + 0.2 * jax.random.normal(
                jax.random.key(100 + i), leaf.shape)
    w = lm_sconv_weights.reference_tree(student["backbone"])
    return meta, batch, student, w, reference_shape(meta.student_backbone.cfg)


def test_model_is_the_reference(tiny_model):
    import lm_sconv_weights
    from reference import lfm2_moe_fp32 as ref

    meta, batch, student, w, shape = tiny_model
    assert shape.layers == (("conv", "dense"), ("full_attn", "moe")) \
        + (("conv", "moe"),) * 3
    tokens = batch["tokens"]
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p: meta.student_backbone.apply(
            {"params": p["backbone"]}, tokens))(student)
        (loss, (metrics, state)), grad = jax.jit(jax.value_and_grad(
            lambda p: meta.forward(p, {}, batch, state=meta.init_state(),
                                   iteration=0), has_aux=True))(student)
        assert state == {}  # the step keeps no routing
        choice = jax.jit(meta.routing)(student, batch)
        # the routed layers alone: the leading dense layer has no router
        assert choice.shape == (4, 2 * 48, 4) and int(choice.max()) < 16
        want_logits = jax.jit(ref.logits, static_argnums=2)(
            w, tokens, shape, choice)
        (want_loss, agree), want_grad = jax.jit(jax.value_and_grad(
            ref.loss_fn, has_aux=True), static_argnums=2)(w, tokens, shape, choice)
        # the reference's layer-by-layer gradient is jax.grad of the whole,
        # and under "untied_head" it is the gradient less the head's part
        by_layer, loss_by_layer, _ = ref.gradient(
            w, tokens, choice, s=shape, r=ref.Recipe(clip_grad=1e9))
        untied, _, _ = ref.gradient(
            w, tokens, choice, s=shape, r=ref.Recipe(clip_grad=1e9),
            variant="untied_head")
    assert logits.shape == (2, 48, 250) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want_logits, atol=5e-6)
    assert abs(float(loss) - float(want_loss)) < 5e-6
    assert abs(float(loss_by_layer) - float(want_loss)) < 5e-6
    assert abs(float(loss) - math.log(250)) < 0.1
    assert float(agree) == 1.0 and float(metrics["moe_rows_overflow"]) == 0
    got = lm_sconv_weights.reference_tree(grad["backbone"])
    assert jax.tree.structure(got) == jax.tree.structure(want_grad)
    assert len(jax.tree.leaves(got)) == len(jax.tree.leaves(student))
    rel = _rel(got, want_grad)
    assert max(jax.tree.leaves(rel)) < 5e-5, rel
    # the conv leaves and the tied leaf by themselves
    assert max(jax.tree.leaves(rel["layers"][2]["mixer"])) < 5e-5
    assert rel["embed"] < 5e-5 and rel["layers"][0]["mixer"]["conv"] < 5e-5
    assert max(jax.tree.leaves(_rel(by_layer, want_grad))) < 5e-5
    # every leaf takes a gradient but the selection bias
    norms = jax.tree.map(lambda g: float(jnp.linalg.norm(g)), got)
    for lw in norms["layers"][1:]:
        assert lw["ffn"].pop("router_bias") == 0.0
    assert min(jax.tree.leaves(norms)) > 0
    # without the head's part the tied leaf's gradient is another, and
    # only that leaf's
    parts = _rel(untied, want_grad)
    assert parts.pop("embed") > 0.1 and max(jax.tree.leaves(parts)) < 5e-5


def test_reference_controls_differ(tiny_model):
    """The controls of the configuration's check are other functions: the
    float32 set lowered to bfloat16 (the loss moves by bfloat16's
    rounding, not float32's), the convolution without its past taps, a
    held expert left out; the untied head is the same FORWARD pass."""
    from reference import lfm2_moe_fp32 as ref

    _, batch, _, w, shape = tiny_model
    assert ref.VARIANTS == ("fp32", *CONTROLS)
    fn = jax.jit(ref.loss_fn, static_argnums=(2, 4))
    with jax.default_matmul_precision("highest"):
        loss = {v: float(fn(w, batch["tokens"], shape, None, v)[0])
                for v in ref.VARIANTS}
    assert 1e-5 < abs(loss["bf16"] - loss["fp32"]) < 0.1
    assert abs(loss["no_conv"] - loss["fp32"]) > 1e-6
    assert abs(loss["drop_expert"] - loss["fp32"]) > 1e-7
    assert loss["untied_head"] == loss["fp32"]
    with pytest.raises(ValueError):
        ref.first_steps(w, [], [], shape, ref.Recipe(), 0, "no_window")
    source = open(ref.__file__).read().split('"""', 2)[2]
    assert "dinov3_tpu" not in source and "pallas" not in source


# ---------------- (g) the benchmark's files ----------------

def test_required_flops_and_bytes_are_the_issues_table(conf):
    """By hand, from the published widths (ISSUE 41's table, MFLOP a token
    forward): four conv mixers 134.2, attention 21.0 + 33.6, the dense FFN
    144.7, the held experts 4 x 9.4, the head 33.6: 404.7 with the
    routers."""
    import lm_gqa_flops
    import lm_sconv_flops

    shape = conf["flops"]
    d, t = 2048, 8192
    conv = 2 * (d * 6144 + d * d) + (2 + 2 * 3) * d
    attn_proj = 2 * (2 * d * 2048 + 2 * d * 512)
    attn_core = 2 * (t + 1) / 2 * 32 * 128
    dense = 2 * 3 * d * 11776
    rows = 4 * 8 / 64
    moe = 2 * d * 64 + rows * 2 * 3 * d * 1536
    parts = lm_sconv_flops.forward_flops_per_token(shape)
    assert parts["conv"] == pytest.approx(4 * conv) == pytest.approx(134.2e6, rel=1e-3)
    assert parts["full_attn"] == pytest.approx(attn_proj + attn_core)
    assert attn_proj == pytest.approx(21.0e6, rel=2e-3)
    assert attn_core == pytest.approx(33.6e6, rel=2e-3)
    assert parts["ffn"] == pytest.approx(dense + 4 * moe)
    assert dense == pytest.approx(144.7e6, rel=1e-3)
    assert rows * 2 * 3 * d * 1536 == pytest.approx(9.44e6, rel=1e-3)
    assert parts["head"] == 2 * d * 8192 == pytest.approx(33.6e6, rel=2e-3)
    assert sum(parts.values()) == pytest.approx(404.7e6, rel=3e-3)
    per_step = lm_sconv_flops.train_flops_per_token(shape) * 4 * t
    assert per_step == pytest.approx(39.8e12, rel=3e-3)
    # the chain: 4 planes of [tokens, 2048] bf16 forward, 7 backward
    tokens = 4 * t
    assert lm_sconv_flops.sconv_chain_train_bytes(tokens, d) \
        == (4 + 7) * tokens * d * 2 == 1476395008
    # the 64-wide core: every causal pair of 32 heads, scores and values 64
    ops = lm_sconv_flops.attn_core_train_ops(t, 32, 64)
    assert ops == 3 * (t * (t + 1) // 2) * 32 * 2 * 128
    assert ops == lm_gqa_flops.gqa_core_train(t, None, 32, 8, 64)[0]
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]["TPU v5 lite"]
    # the least a step's four chains can take, and its one core (x 4 rows)
    assert 4 * 1476395008 / peaks["hbm_bytes_per_s"] == pytest.approx(7.2e-3, rel=2e-2)
    assert 4 * ops / peaks["bf16_flops_per_s"] == pytest.approx(16.7e-3, rel=2e-2)
    # the routed experts' rows: four pairs a token at 8 of 64 held
    assert rows * tokens / 8 == 2048


def test_cell_and_its_files(bench, conf):
    cell = bench["workloads"][6]
    assert cell["name"] == CELL and cell["chips"] == 1
    assert len(cell["why"]) <= 200 and len(bench["workloads"]) >= 7
    assert all(w["chips"] == 1 for w in bench["workloads"])
    entry = bench["configs"][6]
    assert entry["name"] == cell["config"] and len(bench["configs"]) >= 7
    assert entry["source"] == conf["source"] and entry["file"].endswith(
        cell["config"] + ".json")
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == sorted(REDUCED)
    traffic = json.load(open(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json")))
    assert traffic["driver"] == "lm_sconv_train_steps"
    assert os.path.isfile(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    assert (traffic["pool_batches"], traffic["warmup_steps"],
            traffic["trace_lead_steps"], traffic["traced_steps"],
            traffic["start_iteration"]) == (8, 3, 2, 8, 1250)  # ISSUE 41's
    # the metrics of the step (set-up's seven: tests/test_setup_spans.py)
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())
              and m["moves"] == "train_img_per_s_chip"]
    new = ["lm_sconv_ms_per_step", "lm_sconv_chain_ms_per_step",
           "lm_sconv_chain_roofline_pct", "lm_sconv_attn_ms_per_step",
           "lm_sconv_attn_core_ms_per_step", "lm_sconv_attn_core_roofline_pct",
           "lm_sconv_unattributed_pct", "lm_sconv_mfu_pct"]
    assert len(listed) == 16 and listed[-8:] == new
    names = [m["name"] for m in bench["per_layer"]]   # appended together
    at = names.index(new[0])
    assert names[at:at + 8] == new
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
            assert CELL in m["workloads"][-3:]   # (PRs 45, 48 appended one each)
            if m["name"] in new:
                assert m["workloads"] == [CELL], m["name"]
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"setup_s", "train_img_per_s_chip"}
    # every key of the published config, every width the published one;
    # the cut is depth (with the layer table and the leading dense layers
    # counted once), experts held, vocabulary
    for key, value in PUBLISHED.items():
        assert conf[key] == REDUCED.get(key, value), key
    assert conf["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert conf["layer_types"] == [PUBLISHED["layer_types"][i] for i in (0, 2, 3, 4, 5)]
    assert conf["deployment"]["chips_sharing_a_layers_experts"] == 8
    for key in ("tie_word_embeddings", "in_proj_order", "router_normaliser",
                "expert_bias", "rotary", "optimizer", "row_capacity", "weights"):
        assert key in conf["assumed"], key
    assert len(json.dumps(bench)) < 64 * 1024


def test_limits_lie_between_their_readings(conf):
    """Every limit lies over what sound runs read, and each control (the
    next precision down, the three planted faults) is refused by at least
    one limit on each of its seeds."""
    import lm_sconv_step_check

    check = conf["check"]
    for key in lm_sconv_step_check.UPPER + lm_sconv_step_check.LOWER:
        assert isinstance(check[key], float), key
    sound = check["readings"]["sound"]
    assert min(len(v) for v in sound.values()) >= 6
    for key in lm_sconv_step_check.UPPER:
        assert max(sound[key]) < check[key], key
    for key in lm_sconv_step_check.LOWER:
        assert min(sound[key]) > check[key], key
    for name in CONTROLS:
        # a control follows the program's expert choices: it reads no
        # router_agreement_share of its own
        control = {k: v for k, v in check["readings"][name].items()
                   if k in lm_sconv_step_check.UPPER}
        assert set(control) == set(lm_sconv_step_check.UPPER), name
        seeds = min(len(v) for v in control.values())
        assert seeds >= 2
        for i in range(seeds):
            assert any(control[key][i] > check[key] for key in control), (name, i)
    # the taps' fault is the conv group's to refuse, the tying's the tied
    # leaf's: neither hides among the other leaves
    assert min(check["readings"]["no_conv"]["grad_diff_gap_conv"]) \
        > check["grad_diff_gap_conv"]
    assert min(check["readings"]["untied_head"]["grad_diff_gap_head_embed"]) \
        > check["grad_diff_gap_head_embed"]


def test_recipe_reference_and_program_agree(conf):
    from reference import lfm2_moe_fp32 as ref

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
    assert shape == reference_shape(dc)
    assert shape.layers == tuple(map(tuple, conf["flops"]["layers"]))
    # the recipe holds what the file says it holds, the file what was published
    for key in set(PUBLISHED) & set(cfg.lm):
        got = cfg.lm[key]
        got = got.to_dict() if hasattr(got, "to_dict") else got
        want = conf["published"].get(key, PUBLISHED[key]) \
            if key == "num_experts" else conf[key]
        assert (dict(got) if isinstance(want, dict) else
                list(got) if isinstance(want, list) else got) == want, key
    assert (dc.num_experts, dc.num_experts // dc.expert_shards, dc.vocab_size,
            len(dc.layers), int(cfg.train.batch_size_per_device)) == (
                PUBLISHED["num_experts"], 8, 8192, 5, 4)
    flops = conf["flops"]
    assert (flops["seq_len"], flops["experts_held"], flops["num_experts"]) == (
        int(cfg.lm.seq_len), 8, 64)
    for key in ("hidden_size", "conv_L_cache", "num_attention_heads",
                "num_key_value_heads", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok"):
        assert flops[key] == PUBLISHED[key], key
    # the sizing's arithmetic, from the program's own tree at full width:
    # ISSUE 41's 469.285 M parameters held, 7.509 GB of state
    meta = LMMetaArch(cfg)
    tree = jax.eval_shape(lambda r: meta.init_params(
        r, {"tokens": jnp.zeros((4, 8192), jnp.int32)}), jax.random.key(0))
    count = lambda t: sum(math.prod(a.shape) for a in jax.tree.leaves(t))  # noqa: E731
    layers = tree["student"]["backbone"]
    assert "lm_head" not in layers
    assert count(layers["layers_0"]["conv"]) == 16783360
    assert count(layers["layers_1"]["attn"]) == 10485888
    assert count(layers["layers_0"]["mlp"]) == 72351744
    assert count(layers["layers_1"]["experts"]) == 2048 * 64 + 64 + 8 * 3 * 2048 * 1536
    assert count(layers["layers_0"]) == pytest.approx(89.139e6, rel=1e-5)
    assert count(layers["layers_1"]) == pytest.approx(86.119e6, rel=1e-5)
    assert count(layers["layers_2"]) == pytest.approx(92.416e6, rel=1e-5)
    assert count(layers["token_embed"]) == 8192 * 2048
    held = count(tree)
    assert held == pytest.approx(469.285e6, rel=2e-6)
    assert conf["sizing"]["parameters_held_M"]["all"] == pytest.approx(
        held / 1e6, abs=0.001)
    assert held * 16 == pytest.approx(7.509e9, rel=1e-4)
    assert held * 12 == pytest.approx(5.631e9, rel=1e-4)


def test_driver_swaps_the_reference_the_leaves_names_and_the_check():
    """The driver runs a copy of ``lm_train_steps`` of its own with this
    family's reference, renaming and check in it; the other decoder cells'
    own modules keep theirs."""
    import run as harness

    sys.modules.setdefault("run", harness)
    import lm_sconv_step_check
    import lm_sconv_weights
    from reference import lfm2_moe_fp32

    kimi = harness.load_module(harness.DRIVER_DIR, "lm_train_steps")
    mine = harness.load_module(harness.DRIVER_DIR, "lm_sconv_train_steps")
    assert kimi.kimi_linear_fp32.__name__.endswith("kimi_linear_fp32")
    assert kimi.lm_step_check.__name__ == "lm_step_check"
    g = mine.run.__globals__
    assert g["kimi_linear_fp32"] is lfm2_moe_fp32
    assert g["lm_weights"] is lm_sconv_weights and g["Rig"] is mine.Rig
    assert g["lm_step_check"] is lm_sconv_step_check
    assert callable(mine.train_steps.host_pool)
    # the fill: norm scales 1, matrices and the tied table N(0, 0.02),
    # residual writes N(0, 0.02 / sqrt(80)), taps uniform on +-1/sqrt(3),
    # the selection bias N(0, 0.005): non-zero
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    meta = LMMetaArch(tiny_cfg())
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, {"tokens": jnp.zeros((2, 100), jnp.int32)}),
        jax.random.key(0))["student"]
    tree = lm_sconv_weights.reference_tree(
        lm_sconv_weights.fill(abstract, 3)["backbone"])
    for leaf in (tree["norm"], tree["layers"][2]["norm1"],
                 tree["layers"][1]["mixer"]["q_norm"]):
        assert float(np.min(leaf)) == float(np.max(leaf)) == 1.0
    conv = tree["layers"][0]["mixer"]
    assert float(np.max(np.abs(conv["conv"]))) <= 3 ** -0.5
    assert abs(float(np.std(conv["conv"])) - 1 / 3) < 0.05
    assert abs(float(np.std(conv["win"])) - 0.02) < 2e-3
    out = 0.02 / math.sqrt(80)
    for leaf in (conv["wout"], tree["layers"][1]["mixer"]["wo"],
                 tree["layers"][0]["ffn"]["w3"], tree["layers"][2]["ffn"]["w3"]):
        assert abs(float(np.std(leaf)) - out) < 0.15 * out
    assert abs(float(np.std(tree["embed"])) - 0.02) < 2e-3
    bias = tree["layers"][1]["ffn"]["router_bias"]
    assert 0.002 < float(np.std(bias)) < 0.01
    assert set(tree["layers"][1]["ffn"]) == {"router", "router_bias", "w12", "w3"}
    assert set(tree["layers"][0]["ffn"]) == {"w12", "w3"}
    assert set(tree) == {"embed", "norm", "layers"}
