"""The ``smallthinker`` decoder's cell, a whole run on the CPU at test
width (by hand, like the rest of ``benchmark/tests``): untraced, traced
with every per-layer metric the cell lists, and the timed path broken
underneath — the window left out — which has to come out as not
correct."""

from __future__ import annotations

import json
import os

import pytest

import run

CELL = "smallthinker-ep4-pretrain-16k"
TINY = ["lm.hidden_size=64", "lm.num_attention_heads=6",
        "lm.num_key_value_heads=2", "lm.head_dim=16",
        "lm.sliding_window_size=37", "lm.moe_ffn_hidden_size=32",
        "lm.moe_num_primary_experts=16", "lm.moe_num_active_primary_experts=4",
        "lm.vocab_size=250", "lm.seq_len=100",
        "train.batch_size_per_device=2", "telemetry.flush_every=4"]
LAYERS = [["full_attn", "moe"], ["swa", "moe"], ["swa", "moe"], ["swa", "moe"]]
TEST_CONFIG = {
    "recipe": "configs/train/smallthinker_ep4.yaml",
    "overrides": ["data.backend=synthetic", *TINY],
    "reference": {"base_lr": 3e-4, "min_lr": 3e-5, "warmup_epochs": 10, "epochs": 100,
                  "epoch_length": 1250, "weight_decay": 0.1, "weight_decay_end": 0.1,
                  "clip_grad": 1.0, "beta1": 0.9, "beta2": 0.95, "adam_eps": 1e-8},
    "shape": {"layers": LAYERS, "heads": 6, "kv_heads": 2, "window": 37,
              "rope_theta": 1500000.0, "top_k": 4, "first_expert": 0, "eps": 1e-6},
    "flops": {"hidden_size": 64, "vocab_size": 250, "layers": LAYERS,
              "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
              "sliding_window_size": 37, "moe_num_primary_experts": 16,
              "experts_held": 4, "moe_num_active_primary_experts": 4,
              "moe_ffn_hidden_size": 32, "seq_len": 100},
    # width 64, 2 x 100 tokens, bf16 against the float32 reference (this
    # sandbox): see the readings the test prints
    "check": {"loss_rel_gap": 0.005, "grad_diff_gap_mixers": 0.15,
              "grad_diff_gap_ffn": 0.15, "grad_diff_gap_head_embed": 0.15,
              "grad_diff_gap_router": 0.15, "param_change_gap": 0.1,
              "router_agreement_share": 0.9},
}
TEST_TRAFFIC = {"driver": "lm_gqa_train_steps", "pool_batches": 3, "warmup_steps": 2,
                "traced_steps": 2, "trace_lead_steps": 1, "start_iteration": 1250}


@pytest.fixture
def lm_rehearsal(rehearsal):
    with open(os.path.join(run.CONFIG_DIR, "smallthinker-ep4-pretrain.json"), "w") as f:
        json.dump(TEST_CONFIG, f)
    with open(os.path.join(run.TRAFFIC_DIR, "lm-pretrain-steps-16k.json"), "w") as f:
        json.dump(TEST_TRAFFIC, f)
    return rehearsal


def _names(section: str) -> set:
    bench = run.load_json(run.BENCHMARK_JSON)
    return {m["name"] for m in bench[section]
            if "workloads" not in m or CELL in m["workloads"]}


def test_run_end_to_end(lm_rehearsal, capsys):
    out = lm_rehearsal(capsys, CELL, trace=0)
    assert out["correct"] is True, out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == _names("end_to_end")


def test_run_traced_reports_every_metric(lm_rehearsal, capsys):
    out = lm_rehearsal(capsys, CELL, trace=1)
    assert out["correct"] is True, out
    assert set(out["metrics"]) == _names("per_layer"), \
        _names("per_layer") ^ set(out["metrics"])
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # (the phases' sum against train_device_ms_per_step is a claim about the
    # chip: the CPU runs operations side by side, and their sum passes the
    # busy union)
    assert all(m[k] > 0 for k in (
        "lm_swa_ms_per_step", "lm_full_attn_ms_per_step", "lm_ffn_ms_per_step",
        "lm_head_loss_ms_per_step", "train_update_ms_per_step",
        "lm_gqa_mfu_pct", "lm_gqa_core_roofline_pct")), m
    assert 0 < m["lm_gqa_core_ms_per_step"] < \
        m["lm_swa_ms_per_step"] + m["lm_full_attn_ms_per_step"]
    assert 0 < m["lm_moe_experts_ms_per_step"] < m["lm_ffn_ms_per_step"]
    assert 0 <= m["lm_gqa_unattributed_pct"] < 50
    assert m["lm_moe_load_max_over_mean"] >= 1.0


def test_step_without_its_window_is_not_correct(lm_rehearsal, capsys, monkeypatch):
    """Break the timed path underneath: every layer attends to every key
    up to its own."""
    from dinov3_tpu.ops import attention

    real = attention.causal_blockwise_attention
    monkeypatch.setattr(
        attention, "causal_blockwise_attention",
        lambda *a, window=None, **kw: real(*a, **kw))
    out = lm_rehearsal(capsys, CELL, trace=0)
    assert out["correct"] is False, out


def test_a_program_without_the_scopes_leaves_the_new_metrics_out(
        lm_rehearsal, capsys, monkeypatch):
    """What the parent of PR 32 gives a traced run of a cell it can run:
    no ``swa_mixer`` / ``full_attn_mixer`` / ``gqa_core`` in the trace, so
    the six new readers return nothing and raise nothing."""
    import jax

    real = jax.named_scope
    hidden = ("swa_mixer", "full_attn_mixer", "gqa_core")
    monkeypatch.setattr(jax, "named_scope", lambda name: real(
        "anon" if name in hidden else name))
    out = lm_rehearsal(capsys, CELL, trace=1)
    new = {"lm_swa_ms_per_step", "lm_full_attn_ms_per_step",
           "lm_gqa_core_ms_per_step", "lm_gqa_core_roofline_pct"}
    assert not new & set(out["metrics"]), out["metrics"]
    assert "lm_ffn_ms_per_step" in out["metrics"]
