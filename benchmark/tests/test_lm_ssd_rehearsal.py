"""The ``nemotron_h`` decoder's cell, a whole run on the CPU at test width
(by hand, like the rest of ``benchmark/tests``): untraced, traced with
every per-layer metric the cell lists, the timed path broken underneath —
every head reading group 0's B and C — which has to come out as not
correct, and a program without the new scopes, whose traced run leaves
their metrics out."""

from __future__ import annotations

import json
import os

import pytest

import run

CELL = "nemotron3-nano-ep16-pretrain-8k"
TINY = ["lm.hidden_size=64", "lm.mamba_num_heads=4", "lm.mamba_head_dim=16",
        "lm.ssm_state_size=32", "lm.n_groups=2", "lm.num_attention_heads=4",
        "lm.num_key_value_heads=2", "lm.head_dim=16",
        "lm.n_routed_experts=16", "lm.num_experts_per_tok=3",
        "lm.moe_intermediate_size=24",
        "lm.moe_shared_expert_intermediate_size=48", "lm.expert_shards=4",
        "lm.vocab_size=250", "lm.seq_len=100",
        "train.batch_size_per_device=2", "telemetry.flush_every=4"]
LAYERS = [{"M": ["ssm", None], "E": [None, "moe"], "*": ["full_attn", None]}[k]
          for k in "MEMEM*EME"]
TEST_CONFIG = {
    "recipe": "configs/train/nemotron3_nano_ep16.yaml",
    "overrides": ["data.backend=synthetic", *TINY],
    "reference": {"base_lr": 3e-4, "min_lr": 3e-5, "warmup_epochs": 10, "epochs": 100,
                  "epoch_length": 1250, "weight_decay": 0.1, "weight_decay_end": 0.1,
                  "clip_grad": 1.0, "beta1": 0.9, "beta2": 0.95, "adam_eps": 1e-8},
    "shape": {"layers": LAYERS, "heads": 4, "kv_heads": 2, "mamba_heads": 4,
              "mamba_head_dim": 16, "groups": 2, "state": 32, "top_k": 3,
              "first_expert": 0, "routed_scaling_factor": 2.5, "eps": 1e-5},
    "flops": {"hidden_size": 64, "vocab_size": 250, "layers": LAYERS,
              "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2,
              "ssm_state_size": 32, "chunk_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "n_routed_experts": 16, "experts_held": 4,
              "num_experts_per_tok": 3, "moe_intermediate_size": 24,
              "moe_shared_expert_intermediate_size": 48, "seq_len": 100},
    # width 64, 2 x 100 tokens, bf16 against the float32 reference (this
    # sandbox): see the readings the test prints
    "check": {"loss_rel_gap": 0.005, "grad_diff_gap_scan": 0.15,
              "grad_diff_gap_mixers": 0.15, "grad_diff_gap_ffn": 0.15,
              "grad_diff_gap_head_embed": 0.15, "grad_diff_gap_router": 0.4,
              "param_change_gap": 0.1, "router_agreement_share": 0.9},
}
TEST_TRAFFIC = {"driver": "lm_ssd_train_steps", "pool_batches": 3,
                "warmup_steps": 2, "traced_steps": 2, "trace_lead_steps": 1,
                "start_iteration": 1250}


@pytest.fixture
def lm_rehearsal(rehearsal):
    with open(os.path.join(run.CONFIG_DIR, "nemotron3-nano-ep16-pretrain.json"), "w") as f:
        json.dump(TEST_CONFIG, f)
    with open(os.path.join(run.TRAFFIC_DIR, "lm-ssd-pretrain-steps-8k.json"), "w") as f:
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
        "lm_ssd_ms_per_step", "lm_ssd_attn_ms_per_step", "lm_ffn_ms_per_step",
        "lm_head_loss_ms_per_step", "train_update_ms_per_step",
        "lm_ssd_mfu_pct", "lm_ssd_core_roofline_pct",
        "lm_ssd_attn_core_roofline_pct", "lm_ssd_experts_roofline_pct")), m
    assert 0 < m["lm_ssd_core_ms_per_step"] < m["lm_ssd_ms_per_step"]
    assert 0 < m["lm_ssd_chain_ms_per_step"] < m["lm_ssd_ms_per_step"]
    assert 0 < m["lm_ssd_attn_core_ms_per_step"] < m["lm_ssd_attn_ms_per_step"]
    assert 0 < m["lm_moe_experts_ms_per_step"] < m["lm_ffn_ms_per_step"]
    assert 0 <= m["lm_ssd_unattributed_pct"] < 50
    assert m["lm_moe_load_max_over_mean"] >= 1.0


def test_step_on_one_group_is_not_correct(lm_rehearsal, capsys, monkeypatch):
    """Break the timed path underneath: every head reads group 0's B and
    C (the check's ``one_group`` control, planted in the program)."""
    import jax.numpy as jnp

    from dinov3_tpu.models import decoder

    real = decoder.ssd_chunked

    def one_group(xbc, dt, a, heads, head_dim, groups, state, **kw):
        inner = heads * head_dim
        first = lambda at: jnp.tile(  # noqa: E731
            xbc[..., at:at + state], (1, 1, groups))
        return real(jnp.concatenate(
            [xbc[..., :inner], first(inner), first(inner + groups * state)],
            -1), dt, a, heads, head_dim, groups, state, **kw)

    monkeypatch.setattr(decoder, "ssd_chunked", one_group)
    out = lm_rehearsal(capsys, CELL, trace=0)
    assert out["correct"] is False, out


def test_a_program_without_the_scopes_leaves_their_metrics_out(
        lm_rehearsal, capsys, monkeypatch):
    """What a program that lacks this PR's scopes gives a traced run: no
    ``ssd_core`` / ``ssm_chain`` in the trace, so their readers return
    nothing and raise nothing; the scopes the program had are read."""
    import jax

    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda name: real(
        "anon" if name in ("ssd_core", "ssm_chain") else name))
    out = lm_rehearsal(capsys, CELL, trace=1)
    for gone in ("lm_ssd_core_ms_per_step", "lm_ssd_core_roofline_pct",
                 "lm_ssd_chain_ms_per_step"):
        assert gone not in out["metrics"], out["metrics"]
    assert "lm_ssd_ms_per_step" in out["metrics"]
    assert "lm_ffn_ms_per_step" in out["metrics"]
