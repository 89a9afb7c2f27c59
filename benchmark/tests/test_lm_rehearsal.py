"""The decoder's cell, a whole run on the CPU at test width (by hand, like
the rest of ``benchmark/tests``): untraced, traced with every ``lm_*``
metric, and the timed path broken underneath, which has to come out as
not correct."""

from __future__ import annotations

import json
import os

import pytest

import run

CELL = "kimi-linear-ep32-pretrain-8k"
TINY = ["lm.hidden_size=64", "lm.intermediate_size=128", "lm.kda_num_heads=2",
        "lm.kda_head_dim=16", "lm.num_attention_heads=2", "lm.kv_lora_rank=32",
        "lm.qk_nope_head_dim=16", "lm.qk_rope_head_dim=8", "lm.v_head_dim=16",
        "lm.num_experts=16", "lm.num_experts_per_token=4",
        "lm.moe_intermediate_size=32", "lm.expert_shards=4", "lm.vocab_size=256",
        "lm.seq_len=96", "telemetry.flush_every=4"]
LAYERS = [["kda", "dense"], ["kda", "moe"], ["kda", "moe"], ["mla", "moe"],
          ["kda", "moe"]]
TEST_CONFIG = {
    "recipe": "configs/train/kimi_linear_ep32.yaml",
    "overrides": ["data.backend=synthetic", *TINY],
    "reference": {"base_lr": 3e-4, "min_lr": 3e-5, "warmup_epochs": 10, "epochs": 100,
                  "epoch_length": 1250, "weight_decay": 0.1, "weight_decay_end": 0.1,
                  "clip_grad": 1.0, "beta1": 0.9, "beta2": 0.95, "adam_eps": 1e-8},
    "shape": {"layers": LAYERS, "kda_heads": 2, "mla_heads": 2, "kv_lora_rank": 32,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
              "top_k": 4, "routed_scaling_factor": 2.446, "first_expert": 0},
    "flops": {"hidden_size": 64, "vocab_size": 256, "intermediate_size": 128,
              "layers": LAYERS, "kda_num_heads": 2, "kda_head_dim": 16,
              "short_conv_kernel_size": 4, "kda_chunk": 64,
              "num_attention_heads": 2, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 16,
              "experts_held": 4, "num_experts_per_token": 4,
              "moe_intermediate_size": 32, "num_shared_experts": 1, "seq_len": 96},
    # width 64, 2 x 96 tokens, bf16 against the float32 reference (this
    # sandbox): see the readings the test prints
    "check": {"loss_rel_gap": 0.005, "grad_diff_gap_mixers": 0.15,
              "grad_diff_gap_ffn": 0.15, "grad_diff_gap_head_embed": 0.15,
              "grad_diff_gap_router": 0.15, "param_change_gap": 0.1,
              "router_agreement_share": 0.9},
}
TEST_TRAFFIC = {"driver": "lm_train_steps", "pool_batches": 3, "warmup_steps": 2,
                "traced_steps": 2, "trace_lead_steps": 1, "start_iteration": 1250}


@pytest.fixture
def lm_rehearsal(rehearsal):
    with open(os.path.join(run.CONFIG_DIR, "kimi-linear-ep32-pretrain.json"), "w") as f:
        json.dump(TEST_CONFIG, f)
    with open(os.path.join(run.TRAFFIC_DIR, "lm-pretrain-steps-8k.json"), "w") as f:
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
        "lm_kda_ms_per_step", "lm_mla_ms_per_step", "lm_ffn_ms_per_step",
        "lm_head_loss_ms_per_step", "train_update_ms_per_step")), m
    assert 0 < m["lm_kda_core_ms_per_step"] < m["lm_kda_ms_per_step"]
    assert 0 < m["lm_moe_experts_ms_per_step"] < m["lm_ffn_ms_per_step"]
    assert m["lm_moe_load_max_over_mean"] >= 1.0


def test_step_that_leaves_an_expert_out_is_not_correct(lm_rehearsal, capsys,
                                                       monkeypatch):
    """Break the timed path underneath: the last held expert's rows come
    back as zeros."""
    import jax

    real = jax.lax.ragged_dot

    def ragged_dot(lhs, rhs, group_sizes, **kw):
        return real(lhs, rhs, group_sizes.at[-1].set(0), **kw)

    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)
    out = lm_rehearsal(capsys, CELL, trace=0)
    assert out["correct"] is False, out
