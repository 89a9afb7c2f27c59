"""Unit tests for bench.py's harness helpers (the measurement path is
round evidence — its plumbing gets the same test rigor as the library)."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py")
)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def test_split_overrides_plain():
    assert bench._split_overrides("a=1,b=2") == ["a=1", "b=2"]


def test_split_overrides_brackets():
    s = "crops.global_crops_size=[512,768],kernels.flash_attention=xla"
    assert bench._split_overrides(s) == [
        "crops.global_crops_size=[512,768]",
        "kernels.flash_attention=xla",
    ]


def test_split_overrides_nested_and_trailing():
    assert bench._split_overrides("x=[(1,2),(3,4)],y=5,") == [
        "x=[(1,2),(3,4)]", "y=5",
    ]
    assert bench._split_overrides("") == []


def test_build_step_overrides_shared_contract():
    """scripts/count_flops.py counts FLOPs of bench.py's exact program
    through this builder — its env-independent output is the contract."""
    ov = bench.build_step_overrides("vit_large", 0)
    assert "student.arch=vit_large" in ov
    assert "student.n_storage_tokens=4" in ov
    assert not any(o.startswith("crops.") for o in ov)
    assert not any("drop_path_mode" in o for o in ov)  # config default rules
    ov = bench.build_step_overrides(
        "vit_large", 512, drop_path_mode="mask", probs="fp32",
        extra=["train.scan_layers=false"])
    assert "crops.global_crops_size=512" in ov
    assert "crops.local_crops_size=128" in ov
    assert "student.drop_path_mode=mask" in ov
    assert "compute_precision.probs_dtype=fp32" in ov
    assert ov[-1] == "train.scan_layers=false"
    # 768px: local crops floor at 96*2=192? no — max(96, 768//4)=192
    ov = bench.build_step_overrides("vit_large", 768)
    assert "crops.local_crops_size=192" in ov


def test_measure_calibration_fixed_program():
    """The calibration rung is a fixed program whose record lands in
    every bench JSON line (and thus every phases-JSONL row a queue
    harness embeds): assert the program tag is pinned and the measured
    fields are sane on whatever backend this suite runs."""
    import jax
    import jax.numpy as jnp

    calib = bench._measure_calibration(jax, jnp)
    assert calib["program"] == "matmul1024_bf16_chain_x10"
    assert calib["ms_per_matmul"] > 0
    assert calib["tflops"] > 0


def test_bench_guardrail_import_path():
    """bench.py warns through the same guardrail as config build."""
    import warnings

    from dinov3_tpu.configs.config import warn_bad_batch_tiling

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert warn_bad_batch_tiling(10) is not None   # the measured cliff
        assert warn_bad_batch_tiling(12) is None       # the bench default
        assert len(caught) == 1
