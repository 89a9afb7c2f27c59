"""The ``qwen3_next`` decoder's cell, a whole run on the CPU at test width
(by hand, like the rest of ``benchmark/tests``): untraced, traced with
every per-layer metric the cell lists, and the timed path broken
underneath — the delta rule without its gate — which has to come out as
not correct."""

from __future__ import annotations

import json
import os

import pytest

import run

CELL = "qwen3-next-ep16-pretrain-8k"
TINY = ["lm.hidden_size=64", "lm.linear_num_key_heads=2",
        "lm.linear_num_value_heads=4", "lm.linear_key_head_dim=16",
        "lm.linear_value_head_dim=16", "lm.num_attention_heads=4",
        "lm.num_key_value_heads=2", "lm.head_dim=16", "lm.num_experts=16",
        "lm.num_experts_per_tok=4", "lm.moe_intermediate_size=32",
        "lm.shared_expert_intermediate_size=32", "lm.expert_shards=4",
        "lm.vocab_size=250", "lm.seq_len=100",
        "train.batch_size_per_device=2", "telemetry.flush_every=4"]
LAYERS = [["gdn", "moe"], ["gdn", "moe"], ["gdn", "moe"], ["gated_attn", "moe"]]
TEST_CONFIG = {
    "recipe": "configs/train/qwen3_next_ep16.yaml",
    "overrides": ["data.backend=synthetic", *TINY],
    "reference": {"base_lr": 3e-4, "min_lr": 3e-5, "warmup_epochs": 10, "epochs": 100,
                  "epoch_length": 1250, "weight_decay": 0.1, "weight_decay_end": 0.1,
                  "clip_grad": 1.0, "beta1": 0.9, "beta2": 0.95, "adam_eps": 1e-8},
    "shape": {"layers": LAYERS, "gdn_key_heads": 2, "gdn_value_heads": 4,
              "gdn_key_dim": 16, "heads": 4, "kv_heads": 2, "rotary_dim": 4,
              "rope_theta": 10000000.0, "top_k": 4, "first_expert": 0, "eps": 1e-6},
    "flops": {"hidden_size": 64, "vocab_size": 250, "layers": LAYERS,
              "linear_num_key_heads": 2, "linear_num_value_heads": 4,
              "linear_key_head_dim": 16, "linear_value_head_dim": 16,
              "linear_conv_kernel_dim": 4, "gdn_chunk": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
              "num_experts": 16, "experts_held": 4, "num_experts_per_tok": 4,
              "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
              "seq_len": 100},
    # width 64, 2 x 100 tokens, bf16 against the float32 reference (this
    # sandbox): see the readings the test prints
    "check": {"loss_rel_gap": 0.005, "grad_diff_gap_mixers": 0.15,
              "grad_diff_gap_ffn": 0.15, "grad_diff_gap_head_embed": 0.15,
              "grad_diff_gap_router": 0.4, "param_change_gap": 0.1,
              "router_agreement_share": 0.9},
}
TEST_TRAFFIC = {"driver": "lm_gdn_train_steps", "pool_batches": 3, "warmup_steps": 2,
                "traced_steps": 2, "trace_lead_steps": 1, "start_iteration": 1250}


@pytest.fixture
def lm_rehearsal(rehearsal):
    with open(os.path.join(run.CONFIG_DIR, "qwen3-next-ep16-pretrain.json"), "w") as f:
        json.dump(TEST_CONFIG, f)
    with open(os.path.join(run.TRAFFIC_DIR, "lm-gdn-pretrain-steps-8k.json"), "w") as f:
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
        "lm_gdn_ms_per_step", "lm_gated_attn_ms_per_step", "lm_ffn_ms_per_step",
        "lm_head_loss_ms_per_step", "train_update_ms_per_step",
        "lm_gdn_mfu_pct", "lm_gdn_core_roofline_pct",
        "lm_gated_attn_core_roofline_pct")), m
    assert 0 < m["lm_gdn_core_ms_per_step"] < m["lm_gdn_ms_per_step"]
    assert 0 < m["lm_gated_attn_core_ms_per_step"] < m["lm_gated_attn_ms_per_step"]
    assert 0 < m["lm_moe_experts_ms_per_step"] < m["lm_ffn_ms_per_step"]
    assert 0 <= m["lm_gdn_unattributed_pct"] < 50
    assert m["lm_moe_load_max_over_mean"] >= 1.0


def test_step_without_its_gate_is_not_correct(lm_rehearsal, capsys, monkeypatch):
    """Break the timed path underneath: the delta rule with g = 0."""
    from dinov3_tpu.models import decoder

    real = decoder.kda_chunked
    monkeypatch.setattr(
        decoder, "kda_chunked",
        lambda q, k, v, g, beta, **kw: real(q, k, v, 0.0 * g, beta, **kw))
    out = lm_rehearsal(capsys, CELL, trace=0)
    assert out["correct"] is False, out


def test_a_program_without_the_scopes_leaves_the_new_metrics_out(
        lm_rehearsal, capsys, monkeypatch):
    """What a parent of PR 35 gives a traced run of a cell it can run: no
    ``gdn_mixer`` / ``gated_attn_mixer`` / ``gdn_core`` in the trace, so
    the six new phase readers return nothing and raise nothing."""
    import jax

    real = jax.named_scope
    hidden = ("gdn_mixer", "gated_attn_mixer", "gdn_core")
    monkeypatch.setattr(jax, "named_scope", lambda name: real(
        "anon" if name in hidden else name))
    out = lm_rehearsal(capsys, CELL, trace=1)
    new = {"lm_gdn_ms_per_step", "lm_gdn_core_ms_per_step",
           "lm_gdn_core_roofline_pct", "lm_gated_attn_ms_per_step",
           "lm_gated_attn_core_roofline_pct", "lm_gated_attn_core_ms_per_step"}
    assert not new & set(out["metrics"]), out["metrics"]
    assert "lm_ffn_ms_per_step" in out["metrics"]
