"""The whole of a run, both drivers, on the CPU at test width; and the
timed path broken underneath, which has to come out as not correct."""

from __future__ import annotations

import json

import pytest

REQUIRED = {"correct", "attempted", "failed", "metrics", "device"}


def _names(section: str, cell: str) -> set:
    import run

    bench = run.load_json(run.BENCHMARK_JSON)
    return {m["name"] for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", ["vitl16-pretrain", "vitl16-serve-closed64"])
def test_run_end_to_end(rehearsal, capsys, cell):
    out = rehearsal(capsys, cell, trace=0)
    assert REQUIRED <= set(out), out
    assert out["correct"] is True, out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == _names("end_to_end", cell)
    assert all(v["value"] > 0 for v in out["metrics"].values()), out


@pytest.mark.parametrize("cell", ["vitl16-pretrain", "vitl16-serve-closed64"])
def test_run_traced(rehearsal, capsys, cell):
    out = rehearsal(capsys, cell, trace=1)
    assert out["correct"] is True, out
    # one reader needs the configuration's flops group, which the
    # test-width configuration does not carry: it returns nothing and the
    # harness leaves that metric out
    names = _names("per_layer", cell)
    assert set(out["metrics"]) <= names and len(names - set(out["metrics"])) <= 1
    assert out["device"]["busy_s"] > 0
    assert out["device"]["window_s"] >= out["device"]["busy_s"]
    assert out["breakdown"]["device_ops"], out
    json.dumps(out)


def test_step_that_leaves_the_parameters_alone_is_not_correct(
        rehearsal, capsys, monkeypatch):
    """Break the timed path underneath: a step that advances its counter
    and writes its metrics but returns the parameters unchanged."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.train import setup as setup_mod

    real_telemetry = setup_mod.TrainSetup.telemetry

    def broken_telemetry(self):
        plan = real_telemetry(self)
        if getattr(plan, "_broken", False):
            return plan
        real_step = plan.step_fn

        def step(state, ring, batch, scalars, rng):
            kept = jax.tree.map(jnp.copy, state.params)
            new_state, new_ring = real_step(state, ring, batch, scalars, rng)
            return new_state._replace(params=kept), new_ring

        plan.step_fn = step
        plan._broken = True
        return plan

    monkeypatch.setattr(setup_mod.TrainSetup, "telemetry", broken_telemetry)
    out = rehearsal(capsys, "vitl16-pretrain", trace=0)
    assert out["correct"] is False, out


def test_answer_altered_where_it_is_produced_is_not_correct(
        rehearsal, capsys, monkeypatch):
    """Break the timed path underneath: every served CLS feature scaled by
    1.2 as the engine hands it out."""
    from dinov3_tpu.serve import engine as engine_mod

    real_run_pack = engine_mod.PackedServeEngine.run_pack

    def run_pack(self, *a, **kw):
        out = real_run_pack(self, *a, **kw)
        for r in out:
            r.cls_feature = r.cls_feature * 1.2
        return out

    monkeypatch.setattr(engine_mod.PackedServeEngine, "run_pack", run_pack)
    out = rehearsal(capsys, "vitl16-serve-closed64", trace=0)
    assert out["correct"] is False, out


def test_no_tpu_is_refused():
    import run

    with pytest.raises(SystemExit):
        run.require_devices(1)  # the CPU of this sandbox


def test_unknown_device_kind_is_an_error():
    import run

    with pytest.raises(SystemExit):
        run.peaks_for("TPU v9 imaginary")
    assert run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
