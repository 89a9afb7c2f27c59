import pytest
import yaml

from dinov3_tpu.configs import (
    apply_dot_overrides,
    get_default_config,
    load_config,
)


def test_default_schema_keys():
    cfg = get_default_config()
    # reference-compatible sections (dinov3_jax/configs/ssl_default_config.yaml)
    for section in [
        "dino", "ibot", "gram", "train", "student", "teacher",
        "distillation", "multidistillation", "hrft", "optim", "crops",
        "evaluation", "checkpointing", "compute_precision",
    ]:
        assert section in cfg, section
    assert cfg.dino.head_n_prototypes == 65536
    assert cfg.student.arch == "vit_large"
    assert cfg.ibot.mask_ratio_min_max == [0.1, 0.5]


def test_dot_overrides_typing():
    cfg = get_default_config()
    apply_dot_overrides(cfg, [
        "optim.lr=0.005",
        "student.arch=vit_small",
        "train.batch_size_per_device=4",
        "dino.koleo_loss_distributed=true",
        "crops.local_crops_number=2",
    ])
    assert cfg.optim.lr == 0.005
    assert cfg.student.arch == "vit_small"
    assert cfg.train.batch_size_per_device == 4
    assert cfg.dino.koleo_loss_distributed is True
    assert cfg.crops.local_crops_number == 2


def test_run_yaml_merge(tmp_path):
    run = {"student": {"arch": "vit_base"}, "optim": {"lr": 0.002}}
    p = tmp_path / "run.yaml"
    p.write_text(yaml.safe_dump(run))
    cfg = load_config(p, overrides=["optim.scaling_rule=none"])
    assert cfg.student.arch == "vit_base"
    assert cfg.optim.lr == 0.002
    # untouched default survives the merge
    assert cfg.ibot.separate_head is True


def test_sqrt_lr_scaling(tmp_path):
    import jax

    cfg = load_config(overrides=["train.batch_size_per_device=128",
                                 "optim.lr=0.004"])
    # reference formula: lr *= 4 * sqrt(B/1024)  (dinov3_jax/configs/config.py:54)
    B = 128 * jax.device_count()
    assert abs(cfg.optim.lr - 0.004 * 4.0 * (B / 1024.0) ** 0.5) < 1e-12
    # idempotent
    from dinov3_tpu.configs import apply_scaling_rules_to_cfg
    lr = cfg.optim.lr
    apply_scaling_rules_to_cfg(cfg)
    assert cfg.optim.lr == lr


def test_schedules_v2_skips_lr_scaling(tmp_path):
    import yaml as _yaml

    p = tmp_path / "run.yaml"
    p.write_text(_yaml.safe_dump(
        {"schedules": {"lr": {"start": 0.0, "peak": 1e-3, "end": 1e-6,
                              "warmup_epochs": 10}},
         "optim": {"lr": 0.004}}))
    cfg = load_config(p)
    assert cfg.optim.lr == 0.004  # untouched (reference config.py:45-46)


def test_batch_size_per_gpu_alias(tmp_path):
    import yaml as _yaml

    p = tmp_path / "run.yaml"
    p.write_text(_yaml.safe_dump({"train": {"batch_size_per_gpu": 32}}))
    cfg = load_config(p, overrides=["optim.scaling_rule=none"])
    assert cfg.train.batch_size_per_device == 32
    assert "batch_size_per_gpu" not in cfg.train


def test_list_index_override():
    cfg = get_default_config()
    apply_dot_overrides(cfg, ["ibot.mask_ratio_min_max.1=0.6"])
    assert cfg.ibot.mask_ratio_min_max == [0.1, 0.6]


def test_model_parallel_excluded_from_global_batch():
    from dinov3_tpu.configs import global_batch_size

    cfg = get_default_config()
    apply_dot_overrides(cfg, ["train.batch_size_per_device=4",
                              "parallel.tensor=8"])
    # 8 CPU devices / tensor=8 -> 1 data shard
    assert global_batch_size(cfg) == 4


def test_dot_overrides_reject_unknown_keys():
    """Typos cannot silently train with defaults (the reference's
    OmegaConf set_struct strictness, configs/config.py:84)."""
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config

    cfg = get_default_config()
    with pytest.raises(KeyError, match="lrr"):
        apply_dot_overrides(cfg, ["optim.lrr=0.1"])
    with pytest.raises(KeyError, match="brandnew"):
        apply_dot_overrides(cfg, ["brandnew.section=1"])
    # '+' prefix opts in to genuinely new keys
    apply_dot_overrides(cfg, ["+extras.tag=v1"])
    assert cfg.extras.tag == "v1"
    # nested-but-existing sections still work, including null sections
    apply_dot_overrides(cfg, ["optim.lr=0.5"])
    assert cfg.optim.lr == 0.5


def test_dot_overrides_reject_scalar_to_section():
    """optim.lr.x=1 must not silently clobber the scalar optim.lr into a
    section (losing the configured value)."""
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config

    cfg = get_default_config()
    apply_dot_overrides(cfg, ["optim.lr=0.5"])
    with pytest.raises(KeyError, match="value, not a section"):
        apply_dot_overrides(cfg, ["optim.lr.x=1"])
    assert cfg.optim.lr == 0.5
    # explicit opt-in with '+' still allows replacing it with a section
    apply_dot_overrides(cfg, ["+optim.lr.x=1"])
    assert cfg.optim.lr.x == 1
    # ... and the symmetric direction: a scalar must not wipe a section
    with pytest.raises(KeyError, match="section, not a value"):
        apply_dot_overrides(cfg, ["optim=5"])
    assert cfg.optim.lr.x == 1
    apply_dot_overrides(cfg, ["+optim=5"])
    assert cfg.optim == 5


# ---------------- batch-tiling guardrail ----------------

def test_sublane_padding_waste_model():
    from dinov3_tpu.configs.config import sublane_padding_waste

    # the measured triple (round 5, before PR 1, one v5e chip): B=10 pads to
    # 16, B=8 and B=12 (8+4) tile cleanly
    assert sublane_padding_waste(10) == pytest.approx(0.6)
    assert sublane_padding_waste(8) == 0.0
    assert sublane_padding_waste(12) == 0.0
    # small power-of-two batches (the 512px high-res configs) are fine
    assert sublane_padding_waste(2) == 0.0
    assert sublane_padding_waste(4) == 0.0


def test_batch_tiling_guardrail_fires_on_b10_only():
    import warnings

    from dinov3_tpu.configs.config import warn_bad_batch_tiling

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        msg = warn_bad_batch_tiling(10)
        assert msg is not None
        # cites the measurement and suggests the nearest good sizes
        assert "24.22" in msg and "58.56" in msg
        assert "8 or 12" in msg
        assert len(caught) == 1
        assert warn_bad_batch_tiling(8) is None
        assert warn_bad_batch_tiling(12) is None
        assert len(caught) == 1  # no extra warnings for good sizes


def test_batch_tiling_guardrail_at_config_build():
    import warnings

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        load_config(overrides=["train.batch_size_per_device=10",
                               "optim.scaling_rule=none"])
        assert any("sublane" in str(w.message) for w in caught)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        load_config(overrides=["train.batch_size_per_device=12",
                               "optim.scaling_rule=none"])
        assert not any("sublane" in str(w.message) for w in caught)


def test_reshard_guardrail_config_and_live_modes():
    """warn_reshard_padding (ISSUE 19): config mode rejects typo'd
    elastic-resume knobs at load; live mode prices the target
    topology's flat-shard re-padding on a reshape."""
    import warnings

    from dinov3_tpu.configs.config import warn_reshard_padding

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        load_config(overrides=["train.resume_topology=sideways",
                               "train.reshard_padding_tol=7",
                               "optim.scaling_rule=none"])
        text = " ".join(str(w.message) for w in caught)
        assert "resume_topology" in text and "reshard_padding_tol" in text
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        load_config(overrides=["train.resume_topology=memory",
                               "optim.scaling_rule=none"])
        assert not any("resume_topology" in str(w.message)
                       for w in caught)

    # live mode: 7 elements at dp=8 pad 1/8; clean at dp=7
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        msgs = warn_reshard_padding(leaf_sizes=[7], src_dp=7, dst_dp=8,
                                    threshold=0.05)
        assert len(msgs) == 1 and "dp=8" in msgs[0]
        assert any("re-padding" in str(w.message) for w in caught)
    assert warn_reshard_padding(leaf_sizes=[7], src_dp=8, dst_dp=7,
                                threshold=0.05) == []
