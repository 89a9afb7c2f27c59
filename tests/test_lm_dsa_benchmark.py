"""What the benchmark gained with the ``keye_vl2`` decoder's cell, checked
on the CPU (counts and file rules; times come from the chip alone):

(c) the whole model is ``benchmark/reference/keye_vl2_fp32.py``: both
    losses, every leaf's gradient as a DIFFERENCE in every leaf
    group, the reference's layer-by-layer gradient against ``jax.grad`` of
    the whole, the four controls, each moving its own number of the check;
(g) ``benchmark/lm_dsa_flops.py`` against counts by hand, the cell's
    entries in ``BENCHMARK.json`` with a reader file for every per-layer
    metric it lists, the configuration's file against the published
    ``config.json`` and the sizing's arithmetic (562.3 M parameters, 9.00
    GB of state) against the program's own tree, the limits between their
    readings, the driver's rebinding.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_lm_dsa import _reference_shape, _rel, tiny_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CELL = "keye-vl2-ep8-pretrain-16k"
CONFIG = os.path.join(BENCH, "configs", "keye-vl2-ep8-pretrain.json")
# config.json of Kwai-Keye/Keye-VL-2.0-30B-A3B as the catalog beside the
# model-configs guide gives it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 18992}
CONTROLS = ("bf16", "no_select", "no_index_loss", "drop_expert")


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
    import lm_dsa_weights

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32"])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 2, seed=0).items()}
    meta = LMMetaArch(cfg)
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0))["student"]
    student = lm_dsa_weights.fill(abstract, 5)
    # spread the routers (seed-made logits lie within 1e-2 of each other)
    # and the indexers, so that float32 rounding moves no choice here; give
    # the norms' scales and the LayerNorm's bias values of their own
    for i, (path, leaf) in enumerate(
            jax.tree_util.tree_flatten_with_path(student)[0]):
        names = [str(getattr(p, "key", p)) for p in path]
        node = student
        for n in names[:-1]:
            node = node[n]
        if names[-1] == "router" or names[-2].startswith("index_") \
                and names[-1] == "kernel":
            node[names[-1]] = leaf * (25.0 if names[-1] == "router" else 5.0)
        elif names[-1] in ("scale", "bias"):
            node[names[-1]] = leaf + 0.2 * jax.random.normal(
                jax.random.key(100 + i), leaf.shape)
    w = lm_dsa_weights.reference_tree(student["backbone"])
    return meta, batch, student, w, _reference_shape(meta.student_backbone.cfg)


def test_model_is_the_reference(tiny_model):
    import lm_dsa_step_check
    import lm_dsa_weights
    from reference import keye_vl2_fp32 as ref

    meta, batch, student, w, shape = tiny_model
    assert shape.layers == (("dsa", "moe"),) * 2
    assert (shape.index_heads, shape.index_topk) == (4, 24)
    tokens = batch["tokens"]
    with jax.default_matmul_precision("highest"):
        (loss, (metrics, state)), grad = jax.jit(jax.value_and_grad(
            lambda p: meta.forward(p, {}, batch, state=meta.init_state(),
                                   iteration=0), has_aux=True))(student)
        assert state == {}  # the step keeps neither routing nor selection
        choice, bits = jax.jit(meta.selection)(student, batch)
        np.testing.assert_array_equal(choice, jax.jit(meta.routing)(student, batch))
        assert choice.shape == (2, 2 * 96, 4) and int(choice.max()) < 16
        assert bits.shape == (2, 2, 96, 12) and bits.dtype == jnp.uint8
        (want_loss, (agree, index_loss, share)), want_grad = jax.jit(
            jax.value_and_grad(ref.loss_fn, has_aux=True), static_argnums=2)(
                w, tokens, shape, choice, bits)
        # the reference's layer-by-layer gradient is jax.grad of the whole
        by_layer, loss_by_layer, (_, index_by_layer, share_by_layer) = \
            ref.gradient(w, tokens, choice, bits, s=shape,
                         r=ref.Recipe(clip_grad=1e9))
    assert abs(float(loss) - float(want_loss)) < 5e-6
    assert abs(float(loss_by_layer) - float(want_loss)) < 5e-6
    assert float(metrics["total_loss"]) == float(loss)
    assert float(metrics["lm_index_loss"]) == pytest.approx(float(index_loss), rel=2e-6)
    assert float(index_by_layer) == pytest.approx(float(index_loss), rel=2e-6)
    assert 0.01 < float(index_loss) and float(metrics["lm_loss"]) == pytest.approx(
        float(loss) - float(index_loss), abs=1e-5)
    assert float(agree) == 1.0 and float(share) == float(share_by_layer) == 1.0
    assert float(metrics["moe_rows_overflow"]) == 0
    assert float(metrics["dsa_select_excess"]) == 0
    got = lm_dsa_weights.reference_tree(grad["backbone"])
    assert jax.tree.structure(got) == jax.tree.structure(want_grad)
    assert len(jax.tree.leaves(got)) == len(jax.tree.leaves(student))
    rel = _rel(got, want_grad)
    paths = lm_dsa_step_check.leaf_paths(rel)
    worst = {}
    for path, value in zip(paths, jax.tree.leaves(rel)):
        group = lm_dsa_step_check.group_of(path)
        worst[group] = max(worst.get(group, 0.0), value)
    assert set(worst) == set(lm_dsa_step_check.GROUPS)
    assert max(worst.values()) < 5e-5, worst
    assert max(jax.tree.leaves(_rel(by_layer, want_grad))) < 5e-5
    # every leaf takes a gradient
    assert min(float(jnp.linalg.norm(g)) for g in jax.tree.leaves(got)) > 0


def test_each_control_moves_its_own_number(tiny_model):
    """The controls of the configuration's check are other functions, and
    the check's numbers tell them apart: the float32 set lowered to
    bfloat16 moves the loss by bfloat16's rounding; dense attention moves
    the mixers' gradient and the index loss; the index loss left out
    leaves the indexer's leaves without a gradient (a gap of 1) and
    nothing else; a held expert left out moves the FFN's."""
    import lm_dsa_step_check as check
    from reference import keye_vl2_fp32 as ref

    meta, batch, student, w, shape = tiny_model
    assert ref.VARIANTS == ("fp32", *CONTROLS)
    tokens = batch["tokens"]
    with jax.default_matmul_precision("highest"):
        choice, bits = jax.jit(meta.selection)(student, batch)
        out = {}
        for v in ref.VARIANTS:
            g, loss, (_, index_loss, _) = ref.gradient(
                w, tokens, choice, bits, s=shape, r=ref.Recipe(clip_grad=1e9),
                variant=v)  # (no clip: one global norm would couple the groups)
            out[v] = (g, float(loss), float(index_loss))
    sound, loss, index_loss = out["fp32"]
    norms = jax.tree.map(lambda a: float(jnp.linalg.norm(a)), sound)
    groups = np.array([check.group_of(p) for p in check.leaf_paths(norms)])

    def gaps(v):
        diff = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)),
                            out[v][0], sound)
        each = check.diff_gaps(diff, norms)
        return {g: float(np.max(each[groups == g])) for g in check.GROUPS}

    bf16, dense, untrained, dropped = (gaps(v) for v in CONTROLS)
    assert 1e-5 < abs(out["bf16"][1] - loss) / loss < 0.05
    assert max(bf16.values()) > 1e-3
    assert dense["mixers"] > 0.05 and abs(out["no_select"][2] - index_loss) > 1e-3
    assert untrained["indexer"] == pytest.approx(1.0, abs=1e-6)
    assert max(v for k, v in untrained.items() if k != "indexer") < 1e-5
    assert out["no_index_loss"][1] == pytest.approx(loss - index_loss, abs=1e-5)
    assert dropped["ffn"] > 0.05 > 100 * dropped["indexer"]
    with pytest.raises(ValueError):
        ref.first_steps(w, [], [], shape, ref.Recipe(), 0, "no_window")
    source = open(ref.__file__).read().split('"""', 2)[2]
    assert "dinov3_tpu" not in source and "pallas" not in source
    for word in ("assumed", "stop_gradient", "ReLU", "LayerNorm", "topk"):
        assert word in ref.__doc__


def test_the_checks_numbers(tiny_model):
    """``lm_dsa_step_check``: its eleven numbers from a program side and a
    reference side, an excess in any ring row refused."""
    import lm_dsa_step_check as check

    tree = {"embed": 1.0, "head": 1.0, "norm": 1.0, "layers": [{
        "norm1": 1.0, "norm2": 1.0,
        "mixer": {k: 1.0 for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm",
                                   *check.INDEXER)},
        "ffn": {"router": 1.0, "w12": 1.0, "w3": 1.0}}]}
    tree = jax.tree.map(np.float64, tree)
    diff = jax.tree.map(lambda a: a * 0.01, tree)
    diff["layers"][0]["mixer"]["wiw"] = np.float64(0.5)
    program = {"losses": [3.0, 2.9], "index_losses": [0.50, 0.40],
               "change_norms": tree,
               "rows": [{"dsa_select_excess": 0.0}, {"dsa_select_excess": 0.0}]}
    reference = {"losses": [3.0, 2.9], "index_losses": [0.50, 0.41],
                 "change_norms": tree, "grad_norms": tree,
                 "grad_diff_norms": diff, "other_grad_norms": tree,
                 "router_agreement": 0.99, "index_agreement": 0.97}
    g = check.gaps(program, reference)
    assert set(g) == {*check.UPPER, *check.LOWER, "dsa_select_excess"}
    assert g["grad_diff_gap_indexer"] == 0.5 and g["grad_diff_gap_mixers"] == 0.01
    assert g["index_loss_rel_gap"] == pytest.approx(0.01 / 0.41)
    assert (g["index_agreement_share"], g["dsa_select_excess"]) == (0.97, 0)
    limits = {**{k: 0.1 for k in check.UPPER}, **{k: 0.9 for k in check.LOWER}}
    checks = {c["name"]: c["ok"] for c in check.checks_from_gaps(g, limits)}
    assert len(checks) == 11 and not checks["step_grad_diff_gap_indexer"]
    assert sum(checks.values()) == 10
    program["rows"].append({"dsa_select_excess": 2.0})
    bad = check.checks_from_gaps(check.gaps(program, reference), limits)
    assert not next(c for c in bad if c["name"] == "dsa_select_excess")["ok"]
    assert check.worst_leaves(program, reference)[-1][1] == 0.5


# ---------------- (g) the benchmark's files ----------------

def test_required_flops_by_hand(conf):
    """From the published widths: a mixer's projections 2 x 18.87 M and
    the indexer's 2 x 2.26 M, the core at the SELECTED pairs (31.46 M of
    134.2 M causal), the index scores over every causal pair forward and
    the selected pairs backward, a routed layer's router and the one row a
    token this shard's experts get, the head."""
    import lm_dsa_flops

    shape = conf["flops"]
    d, t, topk = 2048, 16384, 2048
    selected = topk * (topk + 1) // 2 + (t - topk) * topk
    causal = t * (t + 1) // 2
    assert lm_dsa_flops.selected_pairs(t, topk) == selected == 31458304
    assert selected / causal == pytest.approx(0.234, abs=1e-3)
    assert lm_dsa_flops.selected_pairs(1000, topk) == 1000 * 1001 // 2
    proj = 2 * (2 * d * 4096 + 2 * d * 512)
    assert proj == pytest.approx(2 * 18.87e6, rel=1e-3)
    index_proj = 2 * (d * 1024 + d * 64 + d * 16)
    assert index_proj == pytest.approx(2 * 2.26e6, rel=2e-3)
    core = 2 * selected / t * 32 * 256
    scores = 2 * causal / t * 16 * 64
    rows = 8 * 16 / 128
    moe = 2 * d * 128 + rows * 2 * 3 * d * 768
    parts = lm_dsa_flops.forward_flops_per_token(shape)
    assert parts["dsa"] == pytest.approx(5 * (proj + index_proj + core))
    assert parts["index"] == pytest.approx(5 * scores)
    assert parts["ffn"] == pytest.approx(5 * moe)
    assert parts["head"] == 2 * d * 18992
    back = 5 * 2 * 2 * selected / t * 16 * 64
    per_token = 3 * (parts["dsa"] + parts["ffn"] + parts["head"]) \
        + parts["index"] + back
    assert lm_dsa_flops.train_flops_per_token(shape) == pytest.approx(per_token)
    assert per_token * t == pytest.approx(26.41e12, rel=1e-3)
    assert rows * t == 16384  # 1,024 rows an expert, 16 held
    ops, nbytes = lm_dsa_flops.dsa_core_train(t, topk, 32, 4, 128)
    assert ops == 3 * selected * 32 * 2 * 256
    assert nbytes == 2 * 2 * t * 128 * (2 * 32 + 2 * 4) + 2 * t * t / 8
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]["TPU v5 lite"]
    # bound by operations: 7.8 ms of products a layer against 0.8 of bytes
    assert ops / peaks["bf16_flops_per_s"] > 5 * nbytes / peaks["hbm_bytes_per_s"]


def test_cell_and_its_files(bench, conf):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and bench["workloads"][5] is cell
    assert len(cell["why"]) <= 200 and len(bench["workloads"]) >= 6
    assert all(w["chips"] == 1 for w in bench["workloads"])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert bench["configs"][5] is entry and len(entry["why"]) <= 200
    assert entry["source"] == conf["source"] and entry["file"].endswith(
        cell["config"] + ".json")
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == sorted(REDUCED)
    traffic = json.load(open(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json")))
    assert traffic["driver"] == "lm_dsa_train_steps"
    assert os.path.isfile(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    assert (traffic["pool_batches"], traffic["warmup_steps"],
            traffic["trace_lead_steps"], traffic["traced_steps"],
            traffic["start_iteration"]) == (8, 3, 2, 8, 1250)
    # the metrics of the step (set-up's seven: tests/test_setup_spans.py)
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())
              and m["moves"] == "train_img_per_s_chip"]
    assert len(listed) == 15 and listed[-7:] == [
        "lm_dsa_ms_per_step", "lm_dsa_core_ms_per_step",
        "lm_dsa_core_roofline_pct", "lm_dsa_index_ms_per_step",
        "lm_dsa_select_ms_per_step", "lm_dsa_unattributed_pct",
        "lm_dsa_mfu_pct"]
    names = [m["name"] for m in bench["per_layer"]]   # appended together
    at = names.index(listed[-7])
    assert names[at:at + 7] == listed[-7:]
    assert set(listed[:8]) == {
        "train_host_ms_per_step", "train_device_ms_per_step",
        "train_device_idle_pct", "train_update_ms_per_step",
        "lm_ffn_ms_per_step", "lm_moe_experts_ms_per_step",
        "lm_head_loss_ms_per_step", "lm_moe_load_max_over_mean"}
    setup = [m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ()) and m["moves"] == "setup_s"]
    assert len(setup) == 7 and all(n.startswith("setup_") for n in setup)
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert os.path.isfile(os.path.join(
                BENCH, "layer_metrics", m["name"] + ".py"))
            assert CELL in m["workloads"][-4:]   # (PRs 41, 45, 48 appended one each)
            if m["name"] in listed[-7:]:
                assert m["workloads"] == [CELL], m["name"]
                assert set(m) == {"name", "unit", "better", "source", "layer",
                                  "moves", "workloads"}
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"setup_s", "train_img_per_s_chip"}
    # every key of the published config, every width the published one;
    # the cut is depth, experts held, vocabulary; sa_config whole
    for key, value in PUBLISHED.items():
        assert conf[key] == REDUCED.get(key, value), key
    assert conf["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert conf["deployment"]["chips_sharing_a_layers_experts"] == 8
    for key in ("q_k_norms", "indexer_rotary", "indexer_layer_norm",
                "chunk_sizes", "index_loss_weight", "vision_tower",
                "dense_warm_up", "weights"):
        assert conf["assumed"][key], key
    assert len(json.dumps(bench)) < 64 * 1024


def test_limits_lie_between_their_readings(conf):
    """Every limit lies over (a lower limit: under) what sound runs read,
    and each control (the next precision down, the three planted faults)
    is refused by at least one limit on each of its seeds."""
    import lm_dsa_step_check

    check = conf["check"]
    for key in lm_dsa_step_check.UPPER + lm_dsa_step_check.LOWER:
        assert isinstance(check[key], float), key
    sound = check["readings"]["sound"]
    assert min(len(v) for v in sound.values()) >= 6
    for key in lm_dsa_step_check.UPPER:
        assert max(sound[key]) < check[key], key
    for key in lm_dsa_step_check.LOWER:
        assert min(sound[key]) > check[key], key
    for name in CONTROLS:
        # a control follows the program's choices and selection: it reads
        # no agreement share of its own
        control = {k: v for k, v in check["readings"][name].items()
                   if k in lm_dsa_step_check.UPPER}
        assert set(control) == set(lm_dsa_step_check.UPPER), name
        seeds = min(len(v) for v in control.values())
        assert seeds >= 2
        for i in range(seeds):
            assert any(control[key][i] > check[key] for key in control), (name, i)


def test_recipe_reference_and_program_agree(conf):
    from reference import keye_vl2_fp32 as ref

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
    for key in PUBLISHED.keys() - REDUCED.keys():
        if key in cfg.lm:
            got = cfg.lm[key]
            assert (dict(got) if key == "sa_config" else got) == PUBLISHED[key], key
    assert (dc.num_experts, dc.num_experts // dc.expert_shards, dc.vocab_size,
            len(dc.layers)) == (PUBLISHED["num_experts"], 16, 18992, 5)
    flops = conf["flops"]
    assert (flops["seq_len"], flops["experts_held"], flops["num_experts"]) == (
        int(cfg.lm.seq_len), 16, 128)
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_intermediate_size",
                "num_experts_per_tok"):
        assert flops[key] == PUBLISHED[key], key
    sa = PUBLISHED["sa_config"]
    assert (flops["indexer_num_heads"], flops["indexer_head_dim"],
            flops["index_topk"]) == (sa["indexer_num_heads"],
                                     sa["indexer_head_dim"], sa["topk"])
    # the sizing's arithmetic, from the program's own tree at full width:
    # ISSUE 39's 562.3 M parameters held, 9.00 GB of state
    meta = LMMetaArch(cfg)
    tree = jax.eval_shape(lambda r: meta.init_params(
        r, {"tokens": jnp.zeros((1, 16384), jnp.int32)}), jax.random.key(0))
    count = lambda t: sum(math.prod(a.shape) for a in jax.tree.leaves(t))  # noqa: E731
    layers = tree["student"]["backbone"]
    attn = layers["layers_0"]["attn"]
    indexer = {k: v for k, v in attn.items() if k.startswith("index_")}
    assert count(indexer) == pytest.approx(2.261e6, rel=1e-3)
    assert count(attn) - count(indexer) == pytest.approx(18.875e6, rel=1e-3)
    assert count(layers["layers_1"]["experts"]) == 2048 * 128 + 16 * 3 * 2048 * 768
    assert count(layers["layers_4"]) == pytest.approx(96.90e6, rel=1e-3)
    held = count(tree)
    assert held == pytest.approx(562.3e6, rel=2e-4)
    assert conf["sizing"]["parameters_held_M"]["all"] == pytest.approx(
        held / 1e6, abs=0.05)
    assert held * 16 == pytest.approx(9.00e9, rel=2e-3)


def test_driver_rebinds_reference_weights_and_check():
    """The driver runs a copy of ``lm_train_steps`` of its own with this
    family's reference, renaming and check in it; the other decoder
    cells' own modules keep theirs."""
    import run as harness

    sys.modules.setdefault("run", harness)
    import lm_dsa_step_check
    import lm_dsa_weights
    from reference import keye_vl2_fp32

    kimi = harness.load_module(harness.DRIVER_DIR, "lm_train_steps")
    mine = harness.load_module(harness.DRIVER_DIR, "lm_dsa_train_steps")
    assert kimi.kimi_linear_fp32.__name__.endswith("kimi_linear_fp32")
    assert kimi.lm_step_check.__name__ == "lm_step_check"
    g = mine.run.__globals__
    assert g["kimi_linear_fp32"] is keye_vl2_fp32
    assert g["lm_weights"] is lm_dsa_weights and g["Rig"] is mine.Rig
    assert g["lm_step_check"] is lm_dsa_step_check
    assert mine.Rig.first_steps is not mine.Rig.__mro__[1].first_steps
    assert callable(mine.train_steps.host_pool)
    # the fill: every scale 1 and the LayerNorm's bias 0, the embedding
    # N(0, 1), the residual writes N(0, 0.02 / sqrt(96)), the rest N(0, 0.02)
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    meta = LMMetaArch(tiny_cfg())
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, {"tokens": jnp.zeros((2, 96), jnp.int32)}),
        jax.random.key(0))["student"]
    tree = lm_dsa_weights.reference_tree(lm_dsa_weights.fill(abstract, 3)["backbone"])
    mixer = tree["layers"][1]["mixer"]
    for leaf in (tree["norm"], tree["layers"][0]["norm1"], mixer["q_norm"],
                 mixer["ik_scale"]):
        assert float(np.min(leaf)) == float(np.max(leaf)) == 1.0
    assert float(np.max(np.abs(mixer["ik_bias"]))) == 0.0
    assert abs(float(np.std(tree["embed"])) - 1.0) < 0.05
    assert abs(float(np.std(mixer["wiq"])) - 0.02) < 2e-3
    want = 0.02 / math.sqrt(96)
    for leaf in (mixer["wo"], tree["layers"][0]["ffn"]["w3"]):
        assert abs(float(np.std(leaf)) - want) < 0.15 * want
