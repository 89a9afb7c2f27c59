"""What the benchmark gained with the ``nemotron_h`` decoder's cell,
checked on the CPU (counts and file rules; times come from the chip alone):

(c) the whole model is ``benchmark/reference/nemotron_h_fp32.py``: logits,
    loss, every leaf's gradient as a DIFFERENCE (the leaves only the
    recurrence reaches by themselves), the reference's block-by-block
    gradient against ``jax.grad`` of the whole, the five controls;
(g) ``benchmark/lm_ssd_flops.py`` against counts by hand and ISSUE 48's
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

from test_lm_ssd import _rel, reference_shape, tiny_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CELL = "nemotron3-nano-ep16-pretrain-8k"
CONFIG = os.path.join(BENCH, "configs", "nemotron3-nano-ep16-pretrain.json")
# config.json of nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 as the catalog
# beside the model-configs guide gives it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
           "n_routed_experts": 8, "vocab_size": 16384}
CONTROLS = ("bf16", "norm_then_gate", "one_group", "relu", "drop_expert")
NEW = ["lm_ssd_ms_per_step", "lm_ssd_core_ms_per_step",
       "lm_ssd_core_roofline_pct", "lm_ssd_chain_ms_per_step",
       "lm_ssd_attn_ms_per_step", "lm_ssd_attn_core_ms_per_step",
       "lm_ssd_attn_core_roofline_pct", "lm_ssd_experts_roofline_pct",
       "lm_ssd_unattributed_pct", "lm_ssd_mfu_pct"]


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
    import lm_ssd_weights

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    cfg = tiny_cfg(["compute_precision.compute_dtype=fp32", "lm.seq_len=48"])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 2, seed=0).items()}
    meta = LMMetaArch(cfg)
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0))["student"]
    student = lm_ssd_weights.fill(abstract, 5)
    # seed-made routers of N(0, 0.02) put the scores within 1e-2 of each
    # other: spread them, so that float32 rounding moves no choice here;
    # give the norm scales and the skip values, so that one left out shows;
    # and the in-projection weight, so that the gate, B and C matter
    for i, (path, leaf) in enumerate(
            jax.tree_util.tree_flatten_with_path(student)[0]):
        names = [str(getattr(p, "key", p)) for p in path]
        node = student
        for n in names[:-1]:
            node = node[n]
        if names[-1] == "router":
            node["router"] = leaf * 25.0
        elif names[-1] in ("scale", "norm_scale", "D"):
            node[names[-1]] = 1.0 + 0.2 * jax.random.normal(
                jax.random.key(100 + i), leaf.shape)
        elif names[-2] == "in_proj":
            node["kernel"] = leaf * 10.0
    w = lm_ssd_weights.reference_tree(student["backbone"])
    return meta, batch, student, w, reference_shape(meta.student_backbone.cfg)


def test_model_is_the_reference(tiny_model):
    import lm_ssd_weights
    from reference import nemotron_h_fp32 as ref

    meta, batch, student, w, shape = tiny_model
    assert shape.layers == (("ssm", None), (None, "moe"), ("full_attn", None),
                            (None, "moe"))
    tokens = batch["tokens"]
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p: meta.student_backbone.apply(
            {"params": p["backbone"]}, tokens))(student)
        (loss, (metrics, state)), grad = jax.jit(jax.value_and_grad(
            lambda p: meta.forward(p, {}, batch, state=meta.init_state(),
                                   iteration=0), has_aux=True))(student)
        assert state == {}  # the step keeps no routing
        choice = jax.jit(meta.routing)(student, batch)
        # the routed blocks alone
        assert choice.shape == (2, 2 * 48, 3) and int(choice.max()) < 16
        want_logits = jax.jit(ref.logits, static_argnums=2)(
            w, tokens, shape, choice)
        (want_loss, agree), want_grad = jax.jit(jax.value_and_grad(
            ref.loss_fn, has_aux=True), static_argnums=2)(w, tokens, shape, choice)
        # the reference's block-by-block gradient is jax.grad of the whole
        by_block, loss_by_block, _ = ref.gradient(
            w, tokens, choice, s=shape, r=ref.Recipe(clip_grad=1e9))
    assert logits.shape == (2, 48, 250) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want_logits, atol=5e-6)
    assert abs(float(loss) - float(want_loss)) < 5e-6
    assert abs(float(loss_by_block) - float(want_loss)) < 5e-6
    assert abs(float(loss) - math.log(250)) < 0.1
    assert float(agree) == 1.0 and float(metrics["moe_rows_overflow"]) == 0
    got = lm_ssd_weights.reference_tree(grad["backbone"])
    assert jax.tree.structure(got) == jax.tree.structure(want_grad)
    assert len(jax.tree.leaves(got)) == len(jax.tree.leaves(student))
    rel = _rel(got, want_grad)
    assert max(jax.tree.leaves(rel)) < 5e-5, rel
    # the leaves only the recurrence and its convolution reach, by themselves
    scan = rel["layers"][0]["mixer"]
    for leaf in ("A_log", "dt_bias", "D", "conv", "conv_bias"):
        assert scan[leaf] < 5e-5, (leaf, scan[leaf])
    assert max(jax.tree.leaves(_rel(by_block, want_grad))) < 5e-5
    # every leaf takes a gradient but the selection bias
    norms = jax.tree.map(lambda g: float(jnp.linalg.norm(g)), got)
    for lw in (norms["layers"][1], norms["layers"][3]):
        assert lw["ffn"].pop("router_bias") == 0.0
    assert min(jax.tree.leaves(norms)) > 0


def test_reference_controls_differ(tiny_model):
    """The controls of the configuration's check are other functions: the
    float32 set lowered to bfloat16 (the loss moves by bfloat16's
    rounding, not float32's), the gate after the grouped norm, one
    group's B and C for every head, the activation without its square, a
    held expert left out; and the two controls of the mixer move the
    gradient of its leaves."""
    from reference import nemotron_h_fp32 as ref

    _, batch, _, w, shape = tiny_model
    assert ref.VARIANTS == ("fp32", *CONTROLS)
    fn = jax.jit(ref.loss_fn, static_argnums=(2, 4))
    grad = jax.jit(jax.grad(lambda w, v: ref.loss_fn(
        w, batch["tokens"], shape, None, v)[0]), static_argnums=1)
    with jax.default_matmul_precision("highest"):
        loss = {v: float(fn(w, batch["tokens"], shape, None, v)[0])
                for v in ref.VARIANTS}
        sound = grad(w, "fp32")
        for variant in ("norm_then_gate", "one_group"):
            moved = _rel(grad(w, variant), sound)["layers"][0]["mixer"]
            assert min(moved["win"], moved["wout"]) > 0.05, (variant, moved)
        moved = _rel(grad(w, "relu"), sound)["layers"][1]["ffn"]
        assert min(moved["w1"], moved["shared"]["w1"]) > 0.05, moved
    assert 1e-5 < abs(loss["bf16"] - loss["fp32"]) < 0.1
    others = [loss[v] for v in ("fp32", "norm_then_gate", "one_group", "relu")]
    assert min(abs(a - b) for i, a in enumerate(others)
               for b in others[i + 1:]) > 1e-6, loss
    assert abs(loss["drop_expert"] - loss["fp32"]) > 1e-7
    with pytest.raises(ValueError):
        ref.first_steps(w, [], [], shape, ref.Recipe(), 0, "no_rope")
    source = open(ref.__file__).read().split('"""', 2)[2]
    assert "dinov3_tpu" not in source and "pallas" not in source
    # the recurrence is a token at a time: no chunked product in the source
    assert "jax.lax.scan(token" in source and "cumsum" not in source


# ---------------- (g) the benchmark's files ----------------

def test_required_flops_are_the_issues_table(conf):
    """By hand, from the published widths (ISSUE 48's table, MFLOP a token
    forward): a Mamba-2 block's projections 77.41 + its scan 2.76 at
    ``chunk_size`` 128 (0.66 inside a chunk, 2.10 to and from the state),
    x 4; attention's projections 46.79; a routed block 48.08 (shared 39.91,
    router 0.69, held experts 7.48), x 4; the head 88.08: 647.9; x 3 x
    16,384 = 31.85 TFLOP; attention's core 1.100 TFLOP forward; 35.1 a
    step."""
    import lm_ssd_flops

    shape = conf["flops"]
    d, t, tokens = 2688, 8192, 16384
    proj = 2 * (d * (4096 + 6144 + 64) + 4096 * d)
    inside = (128 + 1) / 2 * (8 * 2 * 128 + 64 * 2 * 64)
    state = 2 * 64 * 2 * 128 * 64
    attn_proj = 2 * (2 * d * 32 * 128 + 2 * d * 2 * 128)
    pairs = t * (t + 1) // 2
    core = pairs * 32 * 2 * (128 + 128)
    rows = 6 * 8 / 128
    shared, router = 2 * 2 * d * 3712, 2 * d * 128
    held = rows * 2 * 2 * d * 1856
    parts = lm_ssd_flops.forward_flops_per_token(shape)
    assert proj == pytest.approx(77.41e6, rel=1e-4)
    assert inside == pytest.approx(0.66e6, rel=2e-3)
    assert state == pytest.approx(2.10e6, rel=2e-3)
    assert lm_ssd_flops.ssd_scan_forward_flops_per_token(shape) \
        == inside + state == pytest.approx(2.76e6, rel=1e-3)
    assert parts["ssm_proj"] == pytest.approx(4 * proj)
    assert parts["ssd_core"] == pytest.approx(4 * (inside + state))
    assert attn_proj == parts["attn_proj"] == pytest.approx(46.79e6, rel=1e-4)
    assert 2 * pairs == pytest.approx(2 * 33.56e6, rel=1e-3)
    assert 2 * core == 2 * lm_ssd_flops.attn_core_forward_ops(t, 32, 128) \
        == pytest.approx(1.100e12, rel=1e-3)
    assert parts["attn_core"] == pytest.approx(core / t)
    assert shared == pytest.approx(39.91e6, rel=1e-4)
    assert router == pytest.approx(0.69e6, rel=5e-3)
    assert held == pytest.approx(7.48e6, rel=1e-3)
    assert parts["ffn"] == pytest.approx(4 * (shared + router + held))
    assert shared + router + held == pytest.approx(48.08e6, rel=1e-4)
    assert parts["head"] == 2 * d * 16384 == pytest.approx(88.08e6, rel=1e-4)
    without_core = sum(v for k, v in parts.items() if k != "attn_core")
    assert without_core == pytest.approx(647.9e6, rel=1e-4)
    assert 3 * without_core * tokens == pytest.approx(31.85e12, rel=1e-3)
    assert 2 * lm_ssd_flops.attn_core_train_ops(t, 32, 128) \
        == pytest.approx(3.30e12, rel=1e-3)
    per_step = lm_ssd_flops.train_flops_per_token(shape) * tokens
    assert per_step == pytest.approx(35.1e12, rel=2e-3)
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]["TPU v5 lite"]
    assert per_step / peaks["bf16_flops_per_s"] == pytest.approx(0.178, rel=1e-2)
    # the scan a block and step: 45 GFLOP and 340 MB forward; the bytes decide
    assert tokens * (inside + state) == pytest.approx(45.2e9, rel=1e-2)
    assert tokens * ((6144 + 4096) * 2 + 64 * 4) == pytest.approx(340e6, rel=1e-2)
    ops_s = lm_ssd_flops.ssd_scan_train_ops(tokens, shape) / peaks["bf16_flops_per_s"]
    bytes_s = lm_ssd_flops.ssd_scan_train_bytes(tokens, shape) / peaks["hbm_bytes_per_s"]
    assert ops_s == pytest.approx(0.69e-3, rel=1e-2)
    assert bytes_s == pytest.approx(1.08e-3, rel=1e-2) and bytes_s > ops_s
    # the routed experts' rows: six pairs a token at 8 of 128 held
    assert rows * tokens / 8 == 768
    assert lm_ssd_flops.experts_train_ops(rows * tokens, shape) \
        == pytest.approx(3 * tokens * held)


def test_cell_and_its_files(bench, conf):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and bench["workloads"][8] is cell
    assert len(cell["why"]) <= 200 and len(bench["workloads"]) >= 9
    assert all(w["chips"] == 1 for w in bench["workloads"])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert bench["configs"][8] is entry and len(entry["why"]) <= 200
    assert entry["source"] == conf["source"] and entry["file"].endswith(
        cell["config"] + ".json")
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == sorted(REDUCED)
    traffic = json.load(open(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json")))
    assert cell["traffic"] == "lm-ssd-pretrain-steps-8k"
    assert traffic["driver"] == "lm_ssd_train_steps"
    assert os.path.isfile(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    assert (traffic["pool_batches"], traffic["warmup_steps"],
            traffic["trace_lead_steps"], traffic["traced_steps"],
            traffic["start_iteration"]) == (8, 3, 2, 8, 1250)  # ISSUE 48's
    # the metrics of the step (set-up's seven: tests/test_setup_spans.py)
    listed = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())
              and m["moves"] == "train_img_per_s_chip"]
    assert len(listed) == 18 and listed[-10:] == NEW
    names = [m["name"] for m in bench["per_layer"]]   # appended together
    at = names.index(NEW[0])
    assert names[at:at + 10] == NEW == names[-10:]
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
            if m["name"] in NEW:
                assert m["workloads"] == [CELL], m["name"]
                assert set(m) == {"name", "unit", "better", "source", "layer",
                                  "moves", "workloads"}
                assert m["layer"] in ("step program", "kernels")
            else:
                assert m["workloads"][-1] == CELL, m["name"]
    # a share of a roofline or of a peak is named so, in percent
    for name in NEW:
        m = next(x for x in bench["per_layer"] if x["name"] == name)
        if "roofline" in name or "mfu" in name:
            assert (m["unit"], m["better"]) == ("%", "higher")
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"setup_s", "train_img_per_s_chip"}
    # every key of the published config, every width the published one;
    # the cut is depth with its pattern, experts held, vocabulary
    for key, value in PUBLISHED.items():
        assert conf[key] == REDUCED.get(key, value), key
    assert conf["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert conf["deployment"]["chips_sharing_a_routed_blocks_experts"] == 16
    assert conf["deployment"]["chips_sharing_the_vocabulary"] == 8
    assert PUBLISHED["hybrid_override_pattern"].startswith(
        REDUCED["hybrid_override_pattern"])
    for key in ("attention_positions", "time_step_limit", "in_proj_order",
                "gate_before_norm", "e_score_correction_bias",
                "router_normaliser", "initial_values", "optimizer",
                "row_capacity"):
        assert key in conf["assumed"], key
    assert "precision" in conf and "stands_for" in conf
    assert len(json.dumps(bench)) < 64 * 1024
    # the benchmark's own vocabulary of the family's phases
    with open(os.path.join(BENCH, "lm_ssd_phases.json")) as f:
        vocab = json.load(f)
    assert set(vocab["metrics"]) | set(vocab["inner_metrics"]) | {
        vocab["unattributed_metric"]} >= {
            n for n in NEW if "roofline" not in n and "mfu" not in n}


def test_limits_lie_between_their_readings(conf):
    """Every limit lies over what sound runs read, and each control (the
    next precision down, the four planted faults) is refused by at least
    one limit on each of its seeds."""
    import lm_ssd_step_check

    check = conf["check"]
    for key in lm_ssd_step_check.UPPER + lm_ssd_step_check.LOWER:
        assert isinstance(check[key], float), key
    sound = check["readings"]["sound"]
    assert min(len(v) for v in sound.values()) >= 6
    for key in lm_ssd_step_check.UPPER:
        assert max(sound[key]) < check[key], key
    for key in lm_ssd_step_check.LOWER:
        assert min(sound[key]) > check[key], key
    for name in CONTROLS:
        # a control follows the program's expert choices: it reads no
        # router_agreement_share of its own
        control = {k: v for k, v in check["readings"][name].items()
                   if k in lm_ssd_step_check.UPPER}
        assert set(control) == set(lm_ssd_step_check.UPPER), name
        seeds = min(len(v) for v in control.values())
        assert seeds >= 2
        for i in range(seeds):
            assert any(control[key][i] > check[key] for key in control), (name, i)


def test_recipe_reference_and_program_agree(conf):
    from reference import nemotron_h_fp32 as ref

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
            len(dc.layers), int(cfg.train.batch_size_per_device),
            int(cfg.lm.seq_len)) == (128, 8, 16384, 9, 2, 8192)
    flops = conf["flops"]
    assert (flops["seq_len"], flops["experts_held"],
            flops["n_routed_experts"]) == (8192, 8, 128)
    for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups",
                "ssm_state_size", "chunk_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "num_experts_per_tok"):
        assert flops[key] == PUBLISHED[key], key
    # the sizing's arithmetic, from the program's own tree at full width:
    # ISSUE 48's 666.963 M parameters held, 10.671 GB of state
    meta = LMMetaArch(cfg)
    tree = jax.eval_shape(lambda r: meta.init_params(
        r, {"tokens": jnp.zeros((2, 8192), jnp.int32)}), jax.random.key(0))
    count = lambda t: sum(math.prod(a.shape) for a in jax.tree.leaves(t))  # noqa: E731
    blocks = tree["student"]["backbone"]
    assert count(blocks["layers_0"]["ssm"]["in_proj"]) == 2688 * 10304
    assert count(blocks["layers_0"]) == pytest.approx(38.745e6, rel=1e-5)
    assert count(blocks["layers_5"]) == pytest.approx(23.399e6, rel=1e-5)
    assert count(blocks["layers_1"]["experts"]) \
        == 2688 * 128 + 128 + 8 * 2 * 2688 * 1856
    assert count(blocks["layers_1"]["shared"]) == 2 * 2688 * 3712
    assert count(blocks["layers_1"]) == pytest.approx(100.125e6, rel=1e-5)
    assert count(blocks["token_embed"]) + count(blocks["lm_head"]) \
        == 2 * 16384 * 2688
    held = count(tree)
    assert held == pytest.approx(666.963e6, rel=2e-6)
    assert conf["sizing"]["parameters_held_M"]["all"] == pytest.approx(
        held / 1e6, abs=0.001)
    assert held * 16 == pytest.approx(10.671e9, rel=1e-4)
    assert held * 12 == pytest.approx(8.004e9, rel=1e-4)


def test_driver_swaps_the_reference_the_leaves_names_and_the_check():
    """The driver runs a copy of ``lm_train_steps`` of its own with this
    family's reference, renaming and check in it; the other decoder cells'
    own modules keep theirs."""
    import run as harness

    sys.modules.setdefault("run", harness)
    import lm_ssd_step_check
    import lm_ssd_weights
    from reference import nemotron_h_fp32

    kimi = harness.load_module(harness.DRIVER_DIR, "lm_train_steps")
    mine = harness.load_module(harness.DRIVER_DIR, "lm_ssd_train_steps")
    assert kimi.kimi_linear_fp32.__name__.endswith("kimi_linear_fp32")
    assert kimi.lm_step_check.__name__ == "lm_step_check"
    g = mine._base.run.__globals__
    assert g["kimi_linear_fp32"] is nemotron_h_fp32
    assert g["lm_weights"] is lm_ssd_weights and g["Rig"] is mine.Rig
    assert g["lm_step_check"] is lm_ssd_step_check
    assert callable(mine.train_steps.host_pool)
    # the fill: norm scales and the skip 1, matrices, embedding and head
    # N(0, 0.02), residual writes N(0, 0.02 / sqrt(52)), the taps and their
    # bias uniform on +-1/2, the rates in [1, 16), the steps in [1e-3,
    # 1e-1], the selection bias N(0, 0.02): non-zero
    from dinov3_tpu.train.lm_meta_arch import LMMetaArch

    meta = LMMetaArch(tiny_cfg())
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, {"tokens": jnp.zeros((2, 100), jnp.int32)}),
        jax.random.key(0))["student"]
    tree = lm_ssd_weights.reference_tree(
        lm_ssd_weights.fill(abstract, 3)["backbone"])
    ssm, attn = tree["layers"][0]["mixer"], tree["layers"][2]["mixer"]
    for leaf in (tree["norm"], tree["layers"][1]["norm"], ssm["gnorm"], ssm["D"]):
        assert float(np.min(leaf)) == float(np.max(leaf)) == 1.0
    for leaf in (ssm["win"], attn["wq"], attn["wk"], tree["embed"],
                 tree["head"], tree["layers"][1]["ffn"]["shared"]["w1"],
                 tree["layers"][1]["ffn"]["w1"]):
        assert abs(float(np.std(leaf)) - 0.02) < 2e-3
    out = 0.02 / math.sqrt(52)
    for leaf in (ssm["wout"], attn["wo"], tree["layers"][1]["ffn"]["w2"],
                 tree["layers"][3]["ffn"]["shared"]["w2"]):
        assert abs(float(np.std(leaf)) - out) < 0.15 * out
    for leaf in (ssm["conv"], ssm["conv_bias"]):
        assert 0.4 < float(np.max(np.abs(leaf))) <= 0.5
    assert 0.0 <= float(np.min(ssm["A_log"])) \
        and float(np.max(ssm["A_log"])) < math.log(16)
    dt = np.log1p(np.exp(np.asarray(ssm["dt_bias"], np.float64)))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    bias = tree["layers"][1]["ffn"]["router_bias"]
    assert 0.008 < float(np.std(bias)) < 0.04
    assert set(tree["layers"][1]["ffn"]) == {"router", "router_bias", "w1",
                                             "w2", "shared"}
    assert set(tree["layers"][0]) == {"norm", "mixer"}
    assert set(tree) == {"embed", "head", "norm", "layers"}
