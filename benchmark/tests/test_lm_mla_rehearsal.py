"""The ``deepseek_v3`` decoder's cell, a whole run on the CPU at test width
(by hand, like the rest of ``benchmark/tests``): untraced, traced with
every per-layer metric the cell lists, the timed path broken underneath —
the rotary turn left out — which has to come out as not correct, and a
program without the new scope, whose traced run leaves its metric out."""

from __future__ import annotations

import json
import os

import pytest

import run

CELL = "kanana2-ep8-pretrain-16k"
TINY = ["lm.hidden_size=64", "lm.intermediate_size=96",
        "lm.num_attention_heads=4", "lm.kv_lora_rank=32",
        "lm.qk_nope_head_dim=16", "lm.qk_rope_head_dim=8", "lm.v_head_dim=16",
        "lm.n_routed_experts=16", "lm.num_experts_per_tok=3",
        "lm.moe_intermediate_size=32", "lm.expert_shards=4",
        "lm.vocab_size=250", "lm.seq_len=100",
        "train.batch_size_per_device=2", "telemetry.flush_every=4"]
LAYERS = [["mla", "dense"]] + [["mla", "moe"]] * 4
TEST_CONFIG = {
    "recipe": "configs/train/kanana2_ep8.yaml",
    "overrides": ["data.backend=synthetic", *TINY],
    "reference": {"base_lr": 3e-4, "min_lr": 3e-5, "warmup_epochs": 10, "epochs": 100,
                  "epoch_length": 1250, "weight_decay": 0.1, "weight_decay_end": 0.1,
                  "clip_grad": 1.0, "beta1": 0.9, "beta2": 0.95, "adam_eps": 1e-8},
    "shape": {"layers": LAYERS, "heads": 4, "kv_lora_rank": 32,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
              "rope_theta": 1000000.0, "top_k": 3, "first_expert": 0,
              "routed_scaling_factor": 2.448, "eps": 1e-6},
    "flops": {"hidden_size": 64, "vocab_size": 250, "layers": LAYERS,
              "num_attention_heads": 4, "kv_lora_rank": 32,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
              "intermediate_size": 96, "n_routed_experts": 16,
              "experts_held": 4, "num_experts_per_tok": 3,
              "n_shared_experts": 2, "moe_intermediate_size": 32,
              "seq_len": 100},
    # width 64, 2 x 100 tokens, bf16 against the float32 reference (this
    # sandbox): see the readings the test prints
    "check": {"loss_rel_gap": 0.005, "grad_diff_gap_turned": 0.15,
              "grad_diff_gap_mixers": 0.15, "grad_diff_gap_ffn": 0.15,
              "grad_diff_gap_head_embed": 0.15, "grad_diff_gap_router": 0.4,
              "param_change_gap": 0.1, "router_agreement_share": 0.9},
}
TEST_TRAFFIC = {"driver": "lm_mla_train_steps", "pool_batches": 3,
                "warmup_steps": 2, "traced_steps": 2, "trace_lead_steps": 1,
                "start_iteration": 1250}
NEW = {"lm_mla_core_ms_per_step", "lm_mla_core_roofline_pct",
       "lm_mla_rope_ms_per_step", "lm_mla_unattributed_pct", "lm_mla_mfu_pct"}


@pytest.fixture
def lm_rehearsal(rehearsal):
    with open(os.path.join(run.CONFIG_DIR, "kanana2-ep8-pretrain.json"), "w") as f:
        json.dump(TEST_CONFIG, f)
    with open(os.path.join(run.TRAFFIC_DIR, "lm-mla-pretrain-steps-16k.json"), "w") as f:
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
        "lm_mla_ms_per_step", "lm_ffn_ms_per_step", "lm_head_loss_ms_per_step",
        "train_update_ms_per_step", "lm_mla_mfu_pct",
        "lm_mla_core_roofline_pct")), m
    assert 0 < m["lm_mla_core_ms_per_step"] < m["lm_mla_ms_per_step"]
    assert 0 < m["lm_mla_rope_ms_per_step"] < m["lm_mla_ms_per_step"]
    assert 0 < m["lm_moe_experts_ms_per_step"] < m["lm_ffn_ms_per_step"]
    assert 0 <= m["lm_mla_unattributed_pct"] < 50
    assert m["lm_moe_load_max_over_mean"] >= 1.0


def test_step_without_its_turn_is_not_correct(lm_rehearsal, capsys, monkeypatch):
    """Break the timed path underneath: no channel is turned (the latent
    layer ``kimi_linear`` runs, under this model's name)."""
    from dinov3_tpu.models import decoder

    monkeypatch.setattr(decoder, "rope_apply_interleaved",
                        lambda x, sin, cos: x)
    out = lm_rehearsal(capsys, CELL, trace=0)
    assert out["correct"] is False, out


def test_a_program_without_the_scope_leaves_its_metric_out(
        lm_rehearsal, capsys, monkeypatch):
    """What a program that lacks this PR's scope gives a traced run: no
    ``mla_rope`` in the trace, so its reader returns nothing and raises
    nothing; the core's scope, which the program had, is read."""
    import jax

    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda name: real(
        "anon" if name == "mla_rope" else name))
    out = lm_rehearsal(capsys, CELL, trace=1)
    assert "lm_mla_rope_ms_per_step" not in out["metrics"], out["metrics"]
    assert "lm_mla_core_ms_per_step" in out["metrics"]
    assert "lm_ffn_ms_per_step" in out["metrics"]
