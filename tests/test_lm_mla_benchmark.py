"""What the benchmark gained with the ``deepseek_v3`` decoder's cell,
checked on the CPU (counts and file rules; times come from the chip alone):

(c) the whole model is ``benchmark/reference/kanana2_fp32.py``: logits,
    loss, every leaf's gradient as a DIFFERENCE (the two leaves the turn
    acts on by themselves), the reference's layer-by-layer gradient
    against ``jax.grad`` of the whole, the four controls;
(g) ``benchmark/lm_mla_flops.py`` against counts by hand and ISSUE 45's
    table, the cell's entries in ``BENCHMARK.json`` with a reader file for
    every per-layer metric it lists, the configuration's file against the
    published ``config.json`` and the sizing's arithmetic against the
    program's own tree, the check's limits against their readings, the
    driver's swap of reference, renaming and check.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_lm_mla import _rel, reference_shape, tiny_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CELL = "kanana2-ep8-pretrain-16k"
CONFIG = os.path.join(BENCH, "configs", "kanana2-ep8-pretrain.json")
# config.json of kakaocorp/kanana-2-30b-a3b-instruct-2601 as the catalog
# beside the model-configs guide gives it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 16032}
CONTROLS = ("bf16", "no_rope", "rotate_half", "drop_expert")
NEW = ["lm_mla_core_ms_per_step", "lm_mla_core_roofline_pct",
       "lm_mla_rope_ms_per_step", "lm_mla_unattributed_pct", "lm_mla_mfu_pct"]


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
    import lm_mla_weights

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32", "lm.seq_len=48"])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 2, seed=0).items()}
    meta = LMMetaArch(cfg)
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0))["student"]
    student = lm_mla_weights.fill(abstract, 5)
    # seed-made routers of N(0, 0.02) put the scores within 1e-2 of each
    # other: spread them, so that float32 rounding moves no choice here;
    # give the norm scales values, so that a scale left out shows; and the
    # query and key projections weight, so that the turn moves the scores
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
        elif names[-2] in ("q_proj", "kv_a", "kv_b"):
            node["kernel"] = leaf * 10.0
    w = lm_mla_weights.reference_tree(student["backbone"])
    return meta, batch, student, w, reference_shape(meta.student_backbone.cfg)


def test_model_is_the_reference(tiny_model):
    import lm_mla_weights
    from reference import kanana2_fp32 as ref

    meta, batch, student, w, shape = tiny_model
    assert shape.layers == (("mla", "dense"),) + (("mla", "moe"),) * 2
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
        assert choice.shape == (2, 2 * 48, 3) and int(choice.max()) < 16
        want_logits = jax.jit(ref.logits, static_argnums=2)(
            w, tokens, shape, choice)
        (want_loss, agree), want_grad = jax.jit(jax.value_and_grad(
            ref.loss_fn, has_aux=True), static_argnums=2)(w, tokens, shape, choice)
        # the reference's layer-by-layer gradient is jax.grad of the whole
        by_layer, loss_by_layer, _ = ref.gradient(
            w, tokens, choice, s=shape, r=ref.Recipe(clip_grad=1e9))
    assert logits.shape == (2, 48, 250) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want_logits, atol=5e-6)
    assert abs(float(loss) - float(want_loss)) < 5e-6
    assert abs(float(loss_by_layer) - float(want_loss)) < 5e-6
    assert abs(float(loss) - math.log(250)) < 0.1
    assert float(agree) == 1.0 and float(metrics["moe_rows_overflow"]) == 0
    got = lm_mla_weights.reference_tree(grad["backbone"])
    assert jax.tree.structure(got) == jax.tree.structure(want_grad)
    assert len(jax.tree.leaves(got)) == len(jax.tree.leaves(student))
    rel = _rel(got, want_grad)
    assert max(jax.tree.leaves(rel)) < 5e-5, rel
    # the two leaves that carry the turn, by themselves, in every layer
    for lw in rel["layers"]:
        assert lw["mixer"]["wq"] < 5e-5 and lw["mixer"]["wkva"] < 5e-5
    assert max(jax.tree.leaves(_rel(by_layer, want_grad))) < 5e-5
    # every leaf takes a gradient but the selection bias
    norms = jax.tree.map(lambda g: float(jnp.linalg.norm(g)), got)
    for lw in norms["layers"][1:]:
        assert lw["ffn"].pop("router_bias") == 0.0
    assert min(jax.tree.leaves(norms)) > 0


def test_reference_controls_differ(tiny_model):
    """The controls of the configuration's check are other functions: the
    float32 set lowered to bfloat16 (the loss moves by bfloat16's
    rounding, not float32's), no turn, the turn on the wrong pairs, a held
    expert left out; and the two controls of the turn move the gradient of
    the leaves it acts on."""
    from reference import kanana2_fp32 as ref

    _, batch, _, w, shape = tiny_model
    assert ref.VARIANTS == ("fp32", *CONTROLS)
    fn = jax.jit(ref.loss_fn, static_argnums=(2, 4))
    grad = jax.jit(jax.grad(lambda w, v: ref.loss_fn(
        w, batch["tokens"], shape, None, v)[0]), static_argnums=1)
    with jax.default_matmul_precision("highest"):
        loss = {v: float(fn(w, batch["tokens"], shape, None, v)[0])
                for v in ref.VARIANTS}
        sound = grad(w, "fp32")
        for variant in ("no_rope", "rotate_half"):
            moved = _rel(grad(w, variant), sound)["layers"][0]["mixer"]
            assert min(moved["wq"], moved["wkva"]) > 0.05, (variant, moved)
    assert 1e-5 < abs(loss["bf16"] - loss["fp32"]) < 0.1
    assert abs(loss["no_rope"] - loss["fp32"]) > 1e-6
    assert abs(loss["rotate_half"] - loss["fp32"]) > 1e-6
    assert abs(loss["rotate_half"] - loss["no_rope"]) > 1e-6
    assert abs(loss["drop_expert"] - loss["fp32"]) > 1e-7
    with pytest.raises(ValueError):
        ref.first_steps(w, [], [], shape, ref.Recipe(), 0, "no_conv")
    source = open(ref.__file__).read().split('"""', 2)[2]
    assert "dinov3_tpu" not in source and "pallas" not in source


# ---------------- (g) the benchmark's files ----------------

def test_required_flops_are_the_issues_table(conf):
    """By hand, from the published widths (ISSUE 45's table, MFLOP a token
    forward): a mixer's projections 52.7, the dense FFN 75.5, a routed
    layer 19.4 shared and router + 7.1 held, the head 65.7: 510.5; the
    core 2.749 TFLOP a layer forward; 66.3 TFLOP a step."""
    import lm_mla_flops

    shape = conf["flops"]
    d, t, h = 2048, 16384, 32
    proj = 2 * (d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d)
    pairs = t * (t + 1) // 2
    core = pairs * h * 2 * (192 + 128)
    dense = 2 * 3 * d * 6144
    rows = 6 * 16 / 128
    shared_router = 2 * d * 128 + 2 * 3 * d * 1536
    held = rows * 2 * 3 * d * 768
    parts = lm_mla_flops.forward_flops_per_token(shape)
    assert proj == pytest.approx(52.7e6, rel=1e-3)
    assert parts["mla_proj"] == pytest.approx(5 * proj)
    assert pairs == pytest.approx(134.2e6, rel=1e-3)
    assert core == lm_mla_flops.mla_core_forward_ops(t, h, 192, 128) \
        == pytest.approx(2.749e12, rel=1e-3)
    assert parts["mla_core"] == pytest.approx(5 * core / t)
    assert dense == pytest.approx(75.5e6, rel=1e-3)
    assert shared_router == pytest.approx(19.4e6, rel=2e-3)
    assert held == pytest.approx(7.1e6, rel=5e-3)
    assert parts["ffn"] == pytest.approx(dense + 4 * (shared_router + held))
    assert parts["head"] == 2 * d * 16032 == pytest.approx(65.7e6, rel=1e-3)
    without_core = sum(v for k, v in parts.items() if k != "mla_core")
    assert without_core == pytest.approx(510.5e6, rel=1e-3)
    assert 3 * without_core * t == pytest.approx(25.1e12, rel=2e-3)
    assert lm_mla_flops.mla_core_train_ops(t, h, 192, 128) * 5 \
        == 3 * 5 * core == pytest.approx(41.2e12, rel=1e-3)
    per_step = lm_mla_flops.train_flops_per_token(shape) * t
    assert per_step == pytest.approx(66.3e12, rel=1e-3)
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]["TPU v5 lite"]
    # the least a step's five cores can take, and the whole step
    assert 15 * core / peaks["bf16_flops_per_s"] == pytest.approx(0.209, rel=1e-2)
    assert per_step / peaks["bf16_flops_per_s"] == pytest.approx(0.337, rel=1e-2)
    # the kernels' padded operands multiply (256 + 128) for each (192 + 128)
    assert (192 + 128) / (256 + 128) == pytest.approx(0.833, abs=1e-3)
    # the routed experts' rows: six pairs a token at 16 of 128 held
    assert rows * t / 16 == 768


def test_cell_and_its_files(bench, conf):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and bench["workloads"][7] is cell
    assert len(cell["why"]) <= 200 and len(bench["workloads"]) >= 8
    assert all(w["chips"] == 1 for w in bench["workloads"])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert bench["configs"][7] is entry and len(entry["why"]) <= 200
    assert entry["source"] == conf["source"] and entry["file"].endswith(
        cell["config"] + ".json")
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == sorted(REDUCED)
    traffic = json.load(open(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json")))
    assert cell["traffic"] == "lm-mla-pretrain-steps-16k"
    assert traffic["driver"] == "lm_mla_train_steps"
    assert os.path.isfile(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    assert (traffic["pool_batches"], traffic["warmup_steps"],
            traffic["trace_lead_steps"], traffic["traced_steps"],
            traffic["start_iteration"]) == (8, 3, 2, 8, 1250)  # ISSUE 45's
    # the metrics of the step (set-up's seven: tests/test_setup_spans.py)
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())
              and m["moves"] == "train_img_per_s_chip"]
    assert len(listed) == 14 and listed[-5:] == NEW
    names = [m["name"] for m in bench["per_layer"]]   # appended together
    at = names.index(NEW[0])
    assert names[at:at + 5] == NEW
    assert set(listed[:9]) == {
        "train_host_ms_per_step", "train_device_ms_per_step",
        "train_device_idle_pct", "train_update_ms_per_step",
        "lm_mla_ms_per_step", "lm_ffn_ms_per_step",
        "lm_moe_experts_ms_per_step", "lm_head_loss_ms_per_step",
        "lm_moe_load_max_over_mean"}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert os.path.isfile(os.path.join(
                BENCH, "layer_metrics", m["name"] + ".py"))
            assert m["moves"] in ("train_img_per_s_chip", "setup_s")
            if m["name"] in NEW:
                assert m["workloads"] == [CELL], m["name"]
                assert set(m) == {"name", "unit", "better", "source", "layer",
                                  "moves", "workloads"}
    # the older mixer metric now has two cells: the other family's first
    mla = next(m for m in bench["per_layer"] if m["name"] == "lm_mla_ms_per_step")
    assert mla["workloads"][:2] == ["kimi-linear-ep32-pretrain-8k", CELL]
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"setup_s", "train_img_per_s_chip"}
    # every key of the published config, every width the published one;
    # the cut is depth, experts held, vocabulary
    for key, value in PUBLISHED.items():
        assert conf[key] == REDUCED.get(key, value), key
    assert conf["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert conf["deployment"]["chips_sharing_a_layers_experts"] == 8
    for key in ("e_score_correction_bias", "router_normaliser",
                "shared_experts", "rotary", "optimizer", "row_capacity",
                "weights"):
        assert key in conf["assumed"], key
    assert len(json.dumps(bench)) < 64 * 1024


def test_limits_lie_between_their_readings(conf):
    """Every limit lies over what sound runs read, and each control (the
    next precision down, the three planted faults) is refused by at least
    one limit on each of its seeds."""
    import lm_mla_step_check

    check = conf["check"]
    for key in lm_mla_step_check.UPPER + lm_mla_step_check.LOWER:
        assert isinstance(check[key], float), key
    sound = check["readings"]["sound"]
    assert min(len(v) for v in sound.values()) >= 6
    for key in lm_mla_step_check.UPPER:
        assert max(sound[key]) < check[key], key
    for key in lm_mla_step_check.LOWER:
        assert min(sound[key]) > check[key], key
    for name in CONTROLS:
        # a control follows the program's expert choices: it reads no
        # router_agreement_share of its own
        control = {k: v for k, v in check["readings"][name].items()
                   if k in lm_mla_step_check.UPPER}
        assert set(control) == set(lm_mla_step_check.UPPER), name
        seeds = min(len(v) for v in control.values())
        assert seeds >= 2
        for i in range(seeds):
            assert any(control[key][i] > check[key] for key in control), (name, i)
    # the turn's two faults are the turned leaves' own group's to refuse
    for name in ("no_rope", "rotate_half"):
        assert min(check["readings"][name]["grad_diff_gap_turned"]) \
            > check["grad_diff_gap_turned"], name


def test_recipe_reference_and_program_agree(conf):
    from reference import kanana2_fp32 as ref

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
        want = PUBLISHED[key] if key == "n_routed_experts" else conf[key]
        assert cfg.lm[key] == want, key
    assert (dc.num_experts, dc.num_experts // dc.expert_shards, dc.vocab_size,
            len(dc.layers), int(cfg.train.batch_size_per_device)) == (
                PUBLISHED["n_routed_experts"], 16, 16032, 5, 1)
    flops = conf["flops"]
    assert (flops["seq_len"], flops["experts_held"],
            flops["n_routed_experts"]) == (int(cfg.lm.seq_len), 16, 128)
    for key in ("hidden_size", "num_attention_heads", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "n_shared_experts"):
        assert flops[key] == PUBLISHED[key], key
    # the sizing's arithmetic, from the program's own tree at full width:
    # ISSUE 45's 575.956 M parameters held, 9.215 GB of state
    meta = LMMetaArch(cfg)
    tree = jax.eval_shape(lambda r: meta.init_params(
        r, {"tokens": jnp.zeros((1, 16384), jnp.int32)}), jax.random.key(0))
    count = lambda t: sum(math.prod(a.shape) for a in jax.tree.leaves(t))  # noqa: E731
    layers = tree["student"]["backbone"]
    assert count(layers["layers_0"]["mla"]) == 26345472 + 512
    assert count(layers["layers_0"]["mlp"]) == 37748736
    assert count(layers["layers_1"]["experts"]) \
        == 2048 * 128 + 128 + 16 * 3 * 2048 * 768
    assert count(layers["layers_1"]["shared"]) == 3 * 2048 * 1536
    assert count(layers["layers_0"]) == pytest.approx(64.099e6, rel=1e-5)
    assert count(layers["layers_1"]) == pytest.approx(111.547e6, rel=1e-5)
    assert count(layers["token_embed"]) + count(layers["lm_head"]) \
        == 2 * 16032 * 2048
    held = count(tree)
    assert held == pytest.approx(575.956e6, rel=2e-6)
    assert conf["sizing"]["parameters_held_M"]["all"] == pytest.approx(
        held / 1e6, abs=0.001)
    assert held * 16 == pytest.approx(9.215e9, rel=1e-4)
    assert held * 12 == pytest.approx(6.911e9, rel=1e-4)


def test_driver_swaps_the_reference_the_leaves_names_and_the_check():
    """The driver runs a copy of ``lm_train_steps`` of its own with this
    family's reference, renaming and check in it; the other decoder cells'
    own modules keep theirs."""
    import run as harness

    sys.modules.setdefault("run", harness)
    import lm_mla_step_check
    import lm_mla_weights
    from reference import kanana2_fp32

    kimi = harness.load_module(harness.DRIVER_DIR, "lm_train_steps")
    mine = harness.load_module(harness.DRIVER_DIR, "lm_mla_train_steps")
    assert kimi.kimi_linear_fp32.__name__.endswith("kimi_linear_fp32")
    assert kimi.lm_step_check.__name__ == "lm_step_check"
    g = mine.run.__globals__
    assert g["kimi_linear_fp32"] is kanana2_fp32
    assert g["lm_weights"] is lm_mla_weights and g["Rig"] is mine.Rig
    assert g["lm_step_check"] is lm_mla_step_check
    assert callable(mine.train_steps.host_pool)
    # the fill: norm scales 1, matrices, embedding and head N(0, 0.02),
    # residual writes N(0, 0.02 / sqrt(96)), the selection bias N(0, 0.02):
    # non-zero
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    meta = LMMetaArch(tiny_cfg())
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, {"tokens": jnp.zeros((2, 100), jnp.int32)}),
        jax.random.key(0))["student"]
    tree = lm_mla_weights.reference_tree(
        lm_mla_weights.fill(abstract, 3)["backbone"])
    for leaf in (tree["norm"], tree["layers"][2]["norm1"],
                 tree["layers"][1]["mixer"]["kv_norm"]):
        assert float(np.min(leaf)) == float(np.max(leaf)) == 1.0
    mixer = tree["layers"][0]["mixer"]
    for leaf in (mixer["wq"], mixer["wkva"], mixer["wkvb"], tree["embed"],
                 tree["head"], tree["layers"][1]["ffn"]["shared"]["w12"]):
        assert abs(float(np.std(leaf)) - 0.02) < 2e-3
    out = 0.02 / math.sqrt(96)
    for leaf in (mixer["wo"], tree["layers"][0]["ffn"]["w3"],
                 tree["layers"][2]["ffn"]["w3"],
                 tree["layers"][2]["ffn"]["shared"]["w3"]):
        assert abs(float(np.std(leaf)) - out) < 0.15 * out
    bias = tree["layers"][1]["ffn"]["router_bias"]
    assert 0.008 < float(np.std(bias)) < 0.04
    assert set(tree["layers"][1]["ffn"]) == {"router", "router_bias", "w12",
                                             "w3", "shared"}
    assert set(tree["layers"][0]["ffn"]) == {"w12", "w3"}
    assert set(tree) == {"embed", "head", "norm", "layers"}
