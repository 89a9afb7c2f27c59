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


# ---------------- the ``auto`` keys ----------------
#
# Every key of ssl_default_config.yaml whose default is ``auto`` is
# resolved by code from what it can observe (the config, the mesh, the
# platform). The list is READ from the yaml, so a new ``auto`` key
# without a row below fails here.

# mesh kind -> (overrides, devices, the engine the mesh picks)
_MESHES = {
    "one": ([], 1, dict(arm="replicated", bucketed=False, zero3=False,
                        zero3_buckets=False)),
    "dp8": (["parallel.data=-1"], 8,
            dict(arm="bucketed", bucketed=True, zero3=False,
                 zero3_buckets=False)),
    "fsdp8": (["parallel.fsdp=8"], 8,
              dict(arm="unified", bucketed=False, zero3=True,
                   zero3_buckets=True)),
}


def _device_share(tree):
    """Share of ``tree``'s elements that the first device holds."""
    import jax

    leaves = jax.tree.leaves(tree)
    return (sum(x.addressable_shards[0].data.size for x in leaves)
            / sum(x.size for x in leaves))


def _auto_streaming_targets(s, want):
    assert s.meta.streaming_targets is True


def _auto_crop_packing(s, want):
    from dinov3_tpu.configs.config import crop_packing_wished

    # three 5-token local crops fit one 17-token global row: k >= 2
    assert crop_packing_wished(s.cfg) and s.meta.crop_packing is True


def _auto_rng_plan(s, want):
    assert s.meta.rng_plan is True


def _auto_async_metrics(s, want):
    from dinov3_tpu.telemetry import telemetry_wished

    assert telemetry_wished(s.cfg) and s.telemetry_builder is not None


def _auto_serve_spans(s, want):
    from dinov3_tpu.configs.config import serve_obs_wished

    assert serve_obs_wished(s.cfg) is True


def _auto_anatomy(s, want):
    from dinov3_tpu.configs.config import anatomy_wished

    assert anatomy_wished(s.cfg) is True


def _auto_continuous_packing(s, want):
    from dinov3_tpu.configs.config import continuous_packing_wished

    assert continuous_packing_wished(s.cfg) is True


def _auto_row_tokens(s, want):
    from dinov3_tpu.serve.engine import serve_layout_from_cfg

    # two images of the largest size served: (512 / 4)^2 patches + CLS
    assert s.cfg.serve.max_px == 512 and s.cfg.student.patch_size == 4
    assert serve_layout_from_cfg(s.cfg).row_tokens == 2 * (1 + 128 ** 2)


def _auto_serve_cache(s, want):
    from dinov3_tpu.configs.config import serve_cache_wished

    assert serve_cache_wished(s.cfg) is True


def _auto_flash_attention(s, want):
    import jax
    import numpy as np

    from dinov3_tpu.models import backbone_kwargs_from_cfg
    from dinov3_tpu.ops.attention import dispatch_attention, xla_attention

    assert backbone_kwargs_from_cfg(s.cfg)["attn_impl"] == "auto"
    # the platform decides inside the dispatch: dense off the TPU
    assert jax.default_backend() == "cpu"
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 8, 2, 4))
               for i in range(3))
    assert np.array_equal(dispatch_attention(q, k, v, impl="auto"),
                          xla_attention(q, k, v))


def _auto_flash_min_seq(s, want):
    from dinov3_tpu.configs.config import FLASH_NEVER_SEQ
    from dinov3_tpu.models import backbone_kwargs_from_cfg

    # CROSSOVER_r19.json's verdict is null: flash won no measured point
    assert backbone_kwargs_from_cfg(s.cfg)["flash_min_seq"] \
        == FLASH_NEVER_SEQ


def _auto_bucketed_collectives(s, want):
    # a sharded engine owns the update on every mesh of several devices
    # (bucketed on dp, zero3 on fsdp), and the moments are 1/dp
    from dinov3_tpu.parallel.sharding import update_shard_size

    assert s.arm == want["arm"]
    assert _device_share(s.state.opt_state.adam.mu) \
        == 1 / update_shard_size(s.mesh)
    assert (s.bucketed, s.zero3_buckets) \
        == (want["bucketed"], want["zero3_buckets"])
    assert (s.bucket_plan is not None) == want["bucketed"]
    assert (s.zero3_bucket_plan is not None) == want["zero3_buckets"]


def _auto_zero3(s, want):
    from dinov3_tpu.configs.config import zero3_wished
    from dinov3_tpu.parallel.sharding import update_shard_size

    assert zero3_wished(s.cfg) == s.zero3 == want["zero3"]
    share = 1 / update_shard_size(s.mesh) if want["zero3"] else 1.0
    assert _device_share(s.state.params["student"]) == share


def _auto_resume_topology(s, want):
    # a state that is still live on reachable devices is resharded in
    # memory; after a preemption there is none and the checkpoint is read
    from dinov3_tpu.parallel.reshard import topology_of
    from dinov3_tpu.train.setup import elastic_resume

    class Ckpt:
        def restore(self, template):
            return template

    policy = s.cfg.train.resume_topology
    _, info = elastic_resume(s, Ckpt(), policy=policy)
    assert info["path"] == "disk"
    _, info = elastic_resume(s, Ckpt(), live_state=s.state,
                             live_topology=topology_of(s), policy=policy)
    assert info["path"] == "memory"


_AUTO_KEYS = {
    "loss.streaming_targets": _auto_streaming_targets,
    "model.crop_packing": _auto_crop_packing,
    "rng.plan": _auto_rng_plan,
    "telemetry.async_metrics": _auto_async_metrics,
    "telemetry.serve_spans": _auto_serve_spans,
    "telemetry.anatomy": _auto_anatomy,
    "serve.continuous_packing": _auto_continuous_packing,
    "serve.row_tokens": _auto_row_tokens,
    "serve.cache.enabled": _auto_serve_cache,
    "kernels.flash_attention": _auto_flash_attention,
    "kernels.flash_min_seq": _auto_flash_min_seq,
}
_AUTO_MESH_KEYS = {
    "optim.bucketed_collectives": _auto_bucketed_collectives,
    "parallel.zero3": _auto_zero3,
    "train.resume_topology": _auto_resume_topology,
}


def _auto_cases():
    def walk(node, prefix=""):
        for k, v in node.items():
            if isinstance(v, dict):
                yield from walk(v, f"{prefix}{k}.")
            elif v == "auto":
                yield f"{prefix}{k}"

    for key in walk(get_default_config().to_dict()):
        for mesh in (_MESHES if key in _AUTO_MESH_KEYS else ("one",)):
            yield pytest.param(key, mesh, id=f"{key}-{mesh}")


@pytest.fixture(scope="module")
def auto_setups(eight_devices):
    import jax.numpy as jnp
    from test_fused_update import smol_cfg

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.train import build_train_setup

    built = {}

    def get(mesh):
        if mesh not in built:
            overrides, n, _ = _MESHES[mesh]
            cfg = smol_cfg(overrides)
            batch = {k: jnp.asarray(v) for k, v in
                     make_synthetic_batch(cfg, 2 * n, seed=0).items()}
            built[mesh] = build_train_setup(
                cfg, batch, devices=eight_devices[:n])
        set_current_mesh(built[mesh].mesh)
        return built[mesh]

    yield get
    set_current_mesh(None)


@pytest.mark.filterwarnings("ignore:bucket size axis")
@pytest.mark.parametrize("key,mesh", list(_auto_cases()))
def test_auto_key_resolves_from_config_and_mesh(auto_setups, key, mesh):
    """No ``auto`` key is resolved from a record or a plan: each is
    decided from the config, the mesh or the platform, and the three
    mesh kinds pick one engine each (replicated / bucketed / unified)."""
    check = {**_AUTO_KEYS, **_AUTO_MESH_KEYS}.get(key)
    assert check is not None, (
        f"{key} defaults to auto in ssl_default_config.yaml and has no "
        f"row here: say what resolves it, and from what")
    check(auto_setups(mesh), _MESHES[mesh][2])


# ---------------- the update engine a mesh gets ----------------
#
# train/setup.py resolve_update_arm is the one place that chooses. The
# table: mesh kind x switches -> the arm, or the raise. Under all-auto
# one device is replicated, pure dp bucketed, an fsdp axis unified.

_ARM_MESHES = {
    "one": ([], 1),
    "dp8": (["parallel.data=-1"], 8),
    "dp2xfsdp4": (["parallel.data=2", "parallel.fsdp=4"], 8),
    "fsdp8": (["parallel.fsdp=8"], 8),
}
_BUCKETS_NEED_FUSED = "raises: bucketed_collectives=true requires"
# switches -> the arm on (one, dp8, dp2xfsdp4, fsdp8)
_ARM_TABLE = {
    "auto": ("replicated", "bucketed", "unified", "unified"),
    "optim.fused_update=false":
        ("replicated", "replicated", "unified", "unified"),
    "optim.bucketed_collectives=false":
        ("replicated", "replicated", "zero3", "zero3"),
    "optim.bucketed_collectives=true":
        ("replicated", "bucketed", "unified", "unified"),
    "parallel.zero3=false":
        ("replicated", "bucketed", "bucketed", "bucketed"),
    "parallel.zero3=true":
        ("replicated", "unified", "unified", "unified"),
    # the one conflict left: update buckets ARE the fused single pass,
    # so insisting on them without it raises wherever zero3 does not own
    # the update (one device included: the wish cannot hold there)
    "optim.bucketed_collectives=true optim.fused_update=false":
        (_BUCKETS_NEED_FUSED, _BUCKETS_NEED_FUSED, "unified", "unified"),
    # zero3 owns the update with or without buckets of its own
    "parallel.zero3=true optim.bucketed_collectives=false":
        ("replicated", "zero3", "zero3", "zero3"),
}


def _arm_cases():
    for switches, arms in _ARM_TABLE.items():
        for mesh, want in zip(_ARM_MESHES, arms):
            yield pytest.param(
                mesh, switches, want,
                id=f"{mesh}-{switches.replace(' ', '+')}")


@pytest.mark.parametrize("mesh,switches,want", list(_arm_cases()))
def test_resolve_update_arm(eight_devices, mesh, switches, want):
    """The arm is a function of the config and the mesh's shape: no
    model is built."""
    from dinov3_tpu.configs.config import zero3_stream_wished
    from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
    from dinov3_tpu.train.setup import resolve_update_arm

    overrides, n = _ARM_MESHES[mesh]
    cfg = get_default_config()
    apply_dot_overrides(
        cfg, overrides + ([] if switches == "auto" else switches.split()))
    m = build_mesh(MeshSpec.from_cfg(cfg.parallel),
                   devices=eight_devices[:n])
    gathers = zero3_stream_wished(cfg)  # what SSLMetaArch hands setup
    if want.startswith("raises: "):
        with pytest.raises(ValueError, match=want[len("raises: "):]):
            resolve_update_arm(cfg, m, gathers)
        return
    assert resolve_update_arm(cfg, m, gathers) == want
    # a meta-arch that gathers nothing itself (a decoder; a
    # model-parallel mesh) has no gathers to bucket
    assert resolve_update_arm(cfg, m, False) \
        == {"unified": "zero3"}.get(want, want)


def test_removed_key_is_refused(tmp_path):
    """A recipe or an override that still sets ``optim.sharded_update``
    fails at ``load_config`` as an unknown key does, and the message
    names the key that decides now."""
    import yaml as _yaml

    for overrides in (["optim.sharded_update=false"],
                      ["+optim.sharded_update=false"]):
        with pytest.raises(KeyError, match="optim.bucketed_collectives"):
            load_config(overrides=overrides)
    p = tmp_path / "run.yaml"
    p.write_text(_yaml.safe_dump({"optim": {"sharded_update": True}}))
    with pytest.raises(KeyError, match="optim.bucketed_collectives"):
        load_config(p)
    assert "sharded_update" not in get_default_config().optim


@pytest.mark.parametrize("arch", ["ssl", "lm"])
def test_package_reads_no_root_record(arch, monkeypatch):
    """Loading a config and building the default backbone and meta-arch
    opens no record of the repo root but CROSSOVER_r19.json (the last
    one, ROADMAP D4), and warns of no tuned plan."""
    import builtins
    import io
    import os
    import warnings

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    opened = []
    real_open = io.open

    def recording_open(file, *args, **kwargs):
        if isinstance(file, (str, os.PathLike)):
            opened.append(os.path.abspath(os.fspath(file)))
        return real_open(file, *args, **kwargs)

    monkeypatch.setattr(builtins, "open", recording_open)
    monkeypatch.setattr(io, "open", recording_open)

    from test_fused_update import SMOL

    from dinov3_tpu.models import build_backbone

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        if arch == "ssl":
            from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch

            cfg = load_config(overrides=SMOL)
            build_backbone(cfg)
            SSLMetaArch(cfg)
        else:
            from dinov3_tpu.train.lm_meta_arch import LMMetaArch

            cfg = load_config(
                os.path.join(repo, "configs", "train",
                             "kimi_linear_ep32.yaml"),
                overrides=[
                    "lm.hidden_size=64", "lm.intermediate_size=128",
                    "lm.kda_num_heads=2", "lm.kda_head_dim=16",
                    "lm.num_attention_heads=2", "lm.kv_lora_rank=32",
                    "lm.qk_nope_head_dim=16", "lm.qk_rope_head_dim=8",
                    "lm.v_head_dim=16", "lm.num_experts=16",
                    "lm.num_experts_per_token=4",
                    "lm.moe_intermediate_size=32", "lm.expert_shards=4",
                    "lm.vocab_size=256", "lm.seq_len=96",
                    "train.batch_size_per_device=2",
                ])
            build_backbone(cfg)
            LMMetaArch(cfg)
    assert not [str(w.message) for w in caught
                if "tuned plan" in str(w.message)]
    root_records = {os.path.basename(p) for p in opened
                    if os.path.dirname(p) == repo
                    and p.endswith((".json", ".jsonl"))}
    assert root_records <= {"CROSSOVER_r19.json"}, root_records
    assert opened, "the recorder saw no open() at all"
