"""What the benchmark gained with the ``smallthinker`` decoder's cell,
checked on the CPU (counts and file rules; times come from the chip
alone): ``benchmark/lm_gqa_flops.py`` against counts by hand and against
ISSUE 32's table, the cell's entries in ``BENCHMARK.json`` with a reader
file for every per-layer metric it lists, the configuration's file
against the published ``config.json``, the driver's swap of the reference
and the renaming of leaves."""

import json
import math
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CELL = "smallthinker-ep4-pretrain-16k"
CONFIG = os.path.join(BENCH, "configs", "smallthinker-ep4-pretrain.json")
# config.json of PowerInfer/SmallThinker-21BA3B-Instruct as the catalog
# beside the model-configs guide gives it (the two layouts: 52 entries,
# 0 on every fourth layer from 0)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "moe_num_primary_experts": 16,
           "vocab_size": 37984}


@pytest.fixture(scope="module")
def conf():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("tokens, window", [
    (1, None), (7, None), (7, 1), (7, 3), (7, 7), (7, 9), (50, 16)])
def test_band_pairs_by_hand(tokens, window):
    import lm_gqa_flops

    by_hand = sum(1 for t in range(tokens) for j in range(tokens)
                  if j <= t and (window is None or j > t - window))
    assert lm_gqa_flops.band_pairs(tokens, window) == by_hand


def test_required_flops_are_the_issues_table(conf):
    """ISSUE 32: projections 4 x 41.9 M, attention core 117.4 M (global) +
    3 x 51.4 M (window), experts as held 4 x 18.0 M, head 194.5 M = 705.9 M
    a token forward; x 3 x 16,384 = 34.7 TFLOP a step; the core's own
    13.35 TFLOP."""
    import lm_gqa_flops

    shape = conf["flops"]
    d, t = 2560, 16384
    proj = 2 * (2 * d * 28 * 128 + 2 * d * 4 * 128)
    assert proj == pytest.approx(41.9e6, rel=2e-3)
    core_global = 2 * (t + 1) / 2 * 28 * 256
    core_window = 2 * (4096 * 4097 / 2 + (t - 4096) * 4096) / t * 28 * 256
    assert core_global == pytest.approx(117.4e6, rel=1e-3)
    assert core_window == pytest.approx(51.4e6, rel=1e-3)
    expert = 2 * d * 64 + 6 * 16 / 64 * 2 * 3 * d * 768
    assert expert == pytest.approx(18.0e6, rel=2e-3)
    head = 2 * d * 37984
    parts = lm_gqa_flops.forward_flops_per_token(shape)
    assert parts["full_attn"] == pytest.approx(proj + core_global)
    assert parts["swa"] == pytest.approx(3 * (proj + core_window))
    assert parts["ffn"] == pytest.approx(4 * expert)
    assert parts["head"] == head
    assert sum(parts.values()) == pytest.approx(705.9e6, rel=1e-3)
    per_step = lm_gqa_flops.train_flops_per_token(shape) * t
    assert per_step == pytest.approx(34.7e12, rel=2e-3)
    ops = [lm_gqa_flops.gqa_core_train(t, w, 28, 4, 128)
           for w in (None, 4096, 4096, 4096)]
    assert sum(o for o, _ in ops) == pytest.approx(13.35e12, rel=1e-3)
    assert sum(o for o, _ in ops) == pytest.approx(
        3 * t * (core_global + 3 * core_window))
    # q and o at 28 heads, k and v at 4, and as many cotangents, bf16
    assert ops[0][1] == 2 * 2 * t * 128 * (2 * 28 + 2 * 4)
    # the core is bound by its operations on a v5e, by a factor over 40
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]["TPU v5 lite"]
    assert min(o / peaks["bf16_flops_per_s"] / (n / peaks["hbm_bytes_per_s"])
               for o, n in ops) > 15


def test_cell_and_its_files(bench, conf):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == conf["source"] and entry["file"].endswith(
        cell["config"] + ".json")
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == sorted(REDUCED)
    traffic = json.load(open(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json")))
    assert os.path.isfile(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    assert (traffic["pool_batches"], traffic["warmup_steps"],
            traffic["trace_lead_steps"], traffic["traced_steps"],
            traffic["start_iteration"]) == (8, 3, 2, 8, 1250)
    # the metrics of the step (set-up's seven: tests/test_setup_spans.py)
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())
              and m["moves"] == "train_img_per_s_chip"]
    assert len(listed) == 14 and listed[-6:] == [
        "lm_swa_ms_per_step", "lm_full_attn_ms_per_step",
        "lm_gqa_core_ms_per_step", "lm_gqa_core_roofline_pct",
        "lm_gqa_unattributed_pct", "lm_gqa_mfu_pct"]
    # the other family's vocabulary and FLOP model are not this cell's
    assert not {"lm_unattributed_pct", "lm_mfu_pct", "lm_kda_ms_per_step",
                "lm_mla_ms_per_step"} & set(listed)
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert os.path.isfile(os.path.join(
                BENCH, "layer_metrics", m["name"] + ".py"))
            assert m["moves"] in ("train_img_per_s_chip", "setup_s")
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"setup_s", "train_img_per_s_chip"}
    # every key of the published config, every width the published one;
    # the cut is depth, experts held, vocabulary
    for key, value in PUBLISHED.items():
        assert conf[key] == REDUCED.get(key, value), key
    assert conf["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert len(json.dumps(bench)) < 64 * 1024


def test_limits_lie_between_their_readings(conf):
    """Every limit lies between what sound runs read and what the control
    of the next precision down reads where that control separates, and
    each control is refused by at least one limit."""
    import lm_step_check

    check = conf["check"]
    for key in lm_step_check.UPPER + lm_step_check.LOWER:
        assert isinstance(check[key], float), key
    sound = check["readings"]["sound"]
    assert min(len(v) for v in sound.values()) >= 4
    for name in ("bf16", "no_window"):
        control = check["readings"][name]
        assert min(len(v) for v in control.values()) >= 2
        refused = []
        for key in lm_step_check.UPPER:
            assert max(sound[key]) < check[key], key
            refused.append(min(control[key]) > check[key])
        for key in lm_step_check.LOWER:
            assert min(sound[key]) > check[key], key
            refused.append(max(control[key]) < check[key])
        assert any(refused), name


def test_recipe_reference_and_program_agree(conf):
    from reference import smallthinker_fp32 as ref

    from dinov3_tpu.configs import load_config
    from dinov3_tpu.models import DecoderConfig
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
    assert shape.layers == dc.layers == tuple(map(tuple, conf["flops"]["layers"]))
    assert (shape.heads, shape.kv_heads, shape.window, shape.rope_theta,
            shape.top_k, shape.eps) == (
        dc.num_attention_heads, dc.num_key_value_heads, dc.sliding_window,
        dc.rope_theta, dc.num_experts_per_token, dc.rms_norm_eps)
    # the recipe holds what the file says it holds, the file what was published
    assert (dc.num_experts, dc.num_experts // dc.expert_shards, dc.vocab_size,
            len(dc.layers)) == (PUBLISHED["moe_num_primary_experts"], 16, 37984, 4)
    assert int(cfg.lm.seq_len) == conf["flops"]["seq_len"] == \
        PUBLISHED["max_position_embeddings"]
    # the sizing's arithmetic: parameters held
    per_layer = (2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64
                 + 16 * 3 * 2560 * 768 + 2 * 2560)
    held = 4 * per_layer + 2 * 37984 * 2560 + 2560
    assert held == pytest.approx(656.5e6, rel=1e-3)
    assert conf["sizing"]["parameters_held_M"]["all"] == pytest.approx(
        held / 1e6, abs=0.05)


def test_driver_swaps_the_reference_and_the_leaves_names():
    """The driver runs a copy of ``lm_train_steps`` of its own with this
    family's reference and renaming in it; the decoder cell's own module
    keeps Kimi's."""
    import run as harness

    sys.modules.setdefault("run", harness)
    import lm_gqa_weights
    from reference import smallthinker_fp32

    kimi = harness.load_module(harness.DRIVER_DIR, "lm_train_steps")
    mine = harness.load_module(harness.DRIVER_DIR, "lm_gqa_train_steps")
    assert kimi.kimi_linear_fp32.__name__.endswith("kimi_linear_fp32")
    assert mine.Rig.__module__ != kimi.Rig.__module__ or mine.Rig is not kimi.Rig
    g = mine.run.__globals__
    assert g["kimi_linear_fp32"] is smallthinker_fp32
    assert g["lm_weights"] is lm_gqa_weights and g["Rig"] is mine.Rig
    assert callable(mine.train_steps.host_pool)
    # the renaming covers every leaf of the program's tree, once
    from test_lm_gqa import tiny_cfg

    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    cfg = tiny_cfg()
    import jax.numpy as jnp

    meta = LMMetaArch(cfg)
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, {"tokens": jnp.zeros((2, 100), jnp.int32)}),
        jax.random.key(0))["student"]
    filled = lm_gqa_weights.fill(abstract, 3)
    tree = lm_gqa_weights.reference_tree(filled["backbone"])
    assert len(jax.tree.leaves(tree)) == len(jax.tree.leaves(filled))
    assert float(tree["layers"][2]["norm1"][0]) == 1.0  # norm scales are 1
    assert abs(float(np.std(tree["layers"][1]["ffn"]["w12"])) - 0.02) < 2e-3
    # the embedding at unit variance, the two writes into the residual
    # stream scaled by the published depth (assumed.weights)
    assert abs(float(np.std(tree["embed"])) - 1.0) < 0.02
    out_std = 0.02 / math.sqrt(2 * 52)
    for leaf in (tree["layers"][3]["mixer"]["wo"], tree["layers"][0]["ffn"]["w3"]):
        assert abs(float(np.std(leaf)) / out_std - 1.0) < 0.1
    assert set(tree["layers"][0]["ffn"]) == {"router", "w12", "w3"}
