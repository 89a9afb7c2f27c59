"""The plain reference of the training step against the program's step at
test width: in float32 the two agree closely (the reference means what the
program means); in the configuration's bfloat16 the gaps stay under the
test's limits; and the controls — the reference's forward pass in fp8, the
program's own fp8 path — come out over them."""

from __future__ import annotations

import pytest

from conftest import TEST_PRETRAIN, TEST_TRAFFIC

SEED = 2**31 + 7


@pytest.fixture(scope="module")
def readings():
    """{kind: gaps} of one seed: the program in float32 and in bfloat16,
    the program with its fp8 path on, the reference in int8 and fp8."""
    import jax

    import run
    import step_check

    driver = run.load_module(run.DRIVER_DIR, "train_steps")
    mix = {**TEST_TRAFFIC["pretrain-steps"], "warmup_steps": 3}
    out, reference = {}, None
    for kind, extra in (("bf16", ()),
                        ("fp32", ("compute_precision.compute_dtype=fp32",)),
                        ("program-fp8", ("student.fp8_enabled=true",))):
        rig = driver.Rig(TEST_PRETRAIN, mix, jax.devices()[:1], SEED,
                         run.SpanRecorder(), extra_overrides=extra)
        rig.start(SEED)
        program = rig.first_steps()
        rig.free()
        if reference is None:
            reference = rig.reference()
            for p in ("int8", "fp8"):
                out["ref-" + p] = step_check.gaps(rig.reference(p), reference)
        out[kind] = step_check.gaps(program, reference)
    return out


def test_reference_means_what_the_program_means(readings):
    # float32 both sides; the program keeps its attention probabilities in
    # bfloat16 whatever the compute type
    g = readings["fp32"]
    assert g["loss_rel_gap"] < 1e-3 and g["loss_terms_gap"] < 1e-2, g
    assert g["grad_norm_gap"] < 5e-3 and g["param_change_gap"] < 2e-2, g
    assert g["teacher_change_gap"] < 1e-3, g


def test_sound_program_is_correct(readings):
    import step_check

    checks = step_check.checks_from_gaps(readings["bf16"], TEST_PRETRAIN["check"])
    assert all(c["ok"] for c in checks), checks


@pytest.mark.parametrize("control", ["ref-fp8", "program-fp8"])
def test_control_is_not_correct(readings, control):
    import step_check

    checks = step_check.checks_from_gaps(readings[control], TEST_PRETRAIN["check"])
    assert not all(c["ok"] for c in checks), checks
    assert readings[control]["grad_norm_gap"] > 3 * readings["bf16"]["grad_norm_gap"]


def test_worst_leaf_gap_measures_against_the_median_leaf():
    import step_check

    want = {"a": [1.0, 2.0, 4.0], "b": 1e-9}   # median of the leaves: 1.5
    got = {"a": [1.0, 2.0, 4.4], "b": 0.0}     # the all-but-zero leaf is off by 1e-9
    assert step_check.worst_leaf_gap(got, want) == pytest.approx(0.1)
    assert step_check.worst_leaf_gap(
        {"a": [0.0, 0.0, 0.0], "b": 0.0}, want) == pytest.approx(1.0)
