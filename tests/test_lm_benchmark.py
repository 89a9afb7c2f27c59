"""What the benchmark gained with the decoder's cell, checked on the CPU
(counts and file rules; times come from the chip alone): the FLOP and
byte counts of ``benchmark/lm_flops.py`` against XLA's cost analysis and
against ISSUE 27's table, the comparison of ``lm_step_check.py``, and the
cell's entries in ``BENCHMARK.json`` with a reader file for every
per-layer metric it lists."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

CELL = "kimi-linear-ep32-pretrain-8k"
CONFIG = os.path.join(BENCH, "configs", "kimi-linear-ep32-pretrain.json")


@pytest.fixture(scope="module")
def conf():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("b, h, dk, dv, chunk", [(2, 3, 32, 32, 16),
                                                 (1, 4, 64, 64, 64)])
def test_kda_core_ops_against_xla_cost_analysis(b, h, dk, dv, chunk):
    """The program's chunked delta rule, compiled over three chunks: XLA
    counts a loop's body once, so its count is one chunk's — the matmuls
    ``lm_flops.kda_core_ops`` counts, plus the elementwise work around
    them (exponentials, the blocks' placement, the decay of the state:
    a sixth more at the smaller size), which the count leaves out. (Over ONE chunk the compiler folds the products with the
    all-zero first state away and counts less.) This reads the PLAIN
    path's loop body: XLA cannot count inside the kernel's custom call,
    and the kernel makes the same products."""
    import lm_flops

    from dinov3_tpu.ops.kda import kda_chunked, kda_path

    assert kda_path(dk, dv, chunk)[0] == "scan"
    t = 3 * chunk
    x = (jnp.zeros((b, t, h, dk)), jnp.zeros((b, t, h, dk)),
         jnp.zeros((b, t, h, dv)), jnp.zeros((b, t, h, dk)),
         jnp.zeros((b, t, h)))
    cost = jax.jit(lambda *a: kda_chunked(*a, chunk=chunk)).lower(
        *x).compile().cost_analysis()
    counted = lm_flops.kda_core_ops(b * chunk, h, dk, dv, chunk)
    assert counted <= cost["flops"] <= 1.20 * counted, (counted, cost["flops"])
    assert lm_flops.kda_core_ops(b * t, h, dk, dv, chunk) == 3 * counted
    # bytes: inputs and output of the call, no more
    want = b * chunk * h * ((2 * dk + dv) * 2 + 4 * dk + 4 + 4 * dv)
    assert lm_flops.kda_core_bytes(b * chunk, h, dk, dv) == want
    ops, nbytes = lm_flops.kda_core_train(b * chunk, h, dk, dv, chunk)
    assert ops == 3 * counted and nbytes > 2 * want


def test_required_flops_are_the_issues_table(conf):
    import lm_flops

    parts = lm_flops.forward_flops_per_token(conf["flops"])
    giga = {k: round(v / 1e9, 2) for k, v in parts.items()}
    assert giga == {"kda": 0.33, "mla": 0.14, "ffn": 0.20, "head": 0.09}
    per_step = lm_flops.train_flops_per_token(conf["flops"]) * 2 * 8192
    assert 37.5e12 < per_step < 38.5e12
    # the recurrence's count of the delta rule is under the chunked form's
    d = conf["flops"]["kda_head_dim"]
    assert 7.0 * 32 * d * d < lm_flops.kda_core_ops(64, 32, d, d, 64) / 64


def test_step_check_numbers():
    import lm_step_check

    def tree(mixer, router, w12, head):
        return {"head": np.float64(head), "layers": [
            {"norm1": np.float64(mixer), "mixer": {"wq": np.float64(mixer)},
             "ffn": {"router": np.float64(router), "w12": np.float64(w12)}}]}

    norms = tree(2.0, 0.5, 1.0, 1e-9)
    ref = {"losses": [10.0, 9.0], "router_agreement": 0.97,
           "grad_norms": norms, "change_norms": norms,
           "grad_diff_norms": tree(0.2, 0.1, 0.05, 0.0)}
    program = {"losses": [10.01, 9.0], "change_norms": tree(2.2, 0.5, 1.0, 1e-9)}
    assert [lm_step_check.group_of(p) for p in lm_step_check.leaf_paths(norms)] == [
        "head_embed", "router", "ffn", "mixers", "mixers"]
    g = lm_step_check.gaps(program, ref)
    assert g["loss_rel_gap"] == pytest.approx(1e-3)
    # each group's worst leaf by itself: the router's 0.2 hides no mixer
    assert g["grad_diff_gap_mixers"] == pytest.approx(0.1)
    assert g["grad_diff_gap_router"] == pytest.approx(0.2)
    assert g["grad_diff_gap_ffn"] == pytest.approx(0.05)
    assert g["grad_diff_gap_head_embed"] == 0.0
    assert g["param_change_gap"] == pytest.approx(0.1)
    limits = {"loss_rel_gap": 0.01, "grad_diff_gap_mixers": 0.15,
              "grad_diff_gap_ffn": 0.1, "grad_diff_gap_head_embed": 0.1,
              "grad_diff_gap_router": 0.3, "param_change_gap": 0.2,
              "router_agreement_share": 0.9}
    assert all(c["ok"] for c in lm_step_check.checks_from_gaps(g, limits))
    bad = [c["name"] for c in lm_step_check.checks_from_gaps(
        g, dict(limits, grad_diff_gap_mixers=0.09)) if not c["ok"]]
    assert bad == ["step_grad_diff_gap_mixers"]
    worse = dict(g, router_agreement_share=0.5)
    bad = [c["name"] for c in lm_step_check.checks_from_gaps(worse, limits)
           if not c["ok"]]
    assert bad == ["step_router_agreement_share"]
    # a leaf whose gradient is all but zero is held to the median's hundredth
    ref["grad_diff_norms"]["head"] = np.float64(1e-3)
    assert lm_step_check.gaps(program, ref)["grad_diff_gap_head_embed"] == \
        pytest.approx(0.1)
    ref["grad_diff_norms"]["head"] = np.float64(np.nan)
    assert lm_step_check.gaps(program, ref)["grad_diff_gap_mixers"] == np.inf
    rows = lm_step_check.worst_leaves(
        program, dict(ref, other_grad_norms=norms))
    assert rows[-1][0].startswith("all leaves")


def test_cell_and_its_files(bench, conf):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["source"] == conf["source"] and entry["file"].endswith(
        cell["config"] + ".json")
    assert sorted(entry["reduced"]) == sorted(conf["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    traffic = json.load(open(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json")))
    assert os.path.isfile(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    # the metrics of the step (set-up's seven: tests/test_setup_spans.py)
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())
              and m["moves"] == "train_img_per_s_chip"]
    assert len(listed) == 14
    for m in listed:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"setup_s", "train_img_per_s_chip"}
    # every width is the published one; the cut is depth, experts, vocabulary
    assert (conf["hidden_size"], conf["intermediate_size"],
            conf["moe_intermediate_size"], conf["kv_lora_rank"]) == (
                2304, 9216, 1024, 512)
    assert conf["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                 "vocab_size": 163840}
    import lm_step_check

    for key in lm_step_check.UPPER + lm_step_check.LOWER:
        assert isinstance(conf["check"][key], float), key
    # every limit lies between what sound runs read and what the control
    # of the next precision down reads, and that control fails one
    sound, control = (conf["check"]["readings"][k] for k in ("sound", "bf16"))
    failed = []
    for key in lm_step_check.UPPER:
        assert max(sound[key]) < conf["check"][key], key
        failed.append(min(control[key]) > conf["check"][key])
    for key in lm_step_check.LOWER:
        assert min(sound[key]) > conf["check"][key], key
        failed.append(max(control[key]) < conf["check"][key])
    assert any(failed)
    assert len(json.dumps(bench)) < 64 * 1024


def test_recipe_and_reference_agree_on_the_schedule(conf):
    from reference import kimi_linear_fp32 as ref

    from dinov3_tpu.configs import load_config
    from dinov3_tpu.train.schedules import build_schedules

    cfg = load_config(os.path.join(REPO, conf["recipe"]), conf["overrides"])
    recipe = ref.Recipe.from_config(conf["reference"])
    sched = build_schedules(cfg)
    assert recipe.schedule(1250)["lr"] == pytest.approx(3e-4 * 1250 / 12499)
    for it in (0, 1250, 1252, 12499, 12500, 60000):
        want = sched.at(it)
        got = recipe.schedule(it)
        assert got["lr"] == pytest.approx(want["lr"], rel=1e-6)
        assert got["weight_decay"] == pytest.approx(want["weight_decay"], rel=1e-6)
    assert (recipe.beta1, recipe.beta2, recipe.clip_grad) == (
        cfg.optim.adamw_beta1, cfg.optim.adamw_beta2, cfg.optim.clip_grad)
    shape = ref.Shape.from_config(conf["shape"])
    from dinov3_tpu.models import DecoderConfig

    assert shape.layers == DecoderConfig.from_cfg(cfg).layers
