"""Every shipped run recipe loads, schedules build, and the meta-arch
initializes abstractly (zero FLOPs) with the recipe's model settings."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from dinov3_tpu.configs import apply_dot_overrides, load_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECIPES = sorted(glob.glob(os.path.join(REPO, "configs/train/*.yaml")))


def test_recipes_exist():
    names = {os.path.basename(p) for p in RECIPES}
    assert {
        "vitl16_im1k.yaml", "vitl16_im1k_smol.yaml", "vit7b16_pretrain.yaml",
        "vit7b16_gram_anchor.yaml", "vit7b16_high_res_adapt.yaml",
        "vitl16_distilled.yaml",
    } <= names


@pytest.mark.parametrize(
    "path", RECIPES, ids=[os.path.basename(p) for p in RECIPES]
)
def test_recipe_abstract_build(path):
    cfg = load_config(path)
    if cfg.distillation.enabled:
        pytest.skip("needs a teacher checkpoint; covered in test_distillation")
    from dinov3_tpu.configs.config import is_lm_arch
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train.schedules import build_schedules
    from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch

    if is_lm_arch(cfg):
        # a decoder's recipe: a student and no teacher. Shrink the widths
        # (the family's TINY) and KEEP the structure: the layer table, the
        # experts' share, the schedules
        import importlib

        TINY = importlib.import_module({
            "kimi_linear": "test_lm_decoder", "smallthinker": "test_lm_gqa",
            "qwen3_next": "test_lm_gdn",
            "keye_vl2": "test_lm_dsa",
            "lfm2_moe": "test_lm_sconv",
            "deepseek_v3": "test_lm_mla",
            "nemotron_h": "test_lm_ssd"}[str(cfg.student.arch)]).TINY

        from dinov3_tpu.train.lm_meta_arch import LMMetaArch

        apply_dot_overrides(cfg, [*TINY, "train.OFFICIAL_EPOCH_LENGTH=2"])
        assert build_schedules(cfg).at(0)["lr"] >= 0.0
        batch = {k: jnp.asarray(v) for k, v in
                 make_synthetic_batch(cfg, 2, seed=0).items()}
        meta = LMMetaArch(cfg)
        abstract = jax.eval_shape(lambda r: meta.init_params(r, batch),
                                  jax.random.key(0))
        assert set(abstract) == {"student"}
        layers = [k for k in abstract["student"]["backbone"]
                  if k.startswith("layers_")]
        assert len(layers) == cfg.lm.num_hidden_layers
        return
    # shrink the compute-heavy dials but KEEP the recipe's structure
    # (arch, ffn kind, norms, rope flags, gram, schedules)
    small_arch = {
        "vit_7b": "vit_test", "vit_giant2": "vit_test",
        "vit_large": "vit_test", "vit_base": "vit_test",
        "vit_small": "vit_test",
    }.get(cfg.student.arch)
    overrides = [
        "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
        "dino.head_bottleneck_dim=16",
        "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
        "ibot.head_bottleneck_dim=16",
        "train.OFFICIAL_EPOCH_LENGTH=2",
    ]
    if small_arch:
        overrides.append(f"student.arch={small_arch}")
    apply_dot_overrides(cfg, overrides)
    if isinstance(cfg.crops.global_crops_size, list):
        cfg.crops.global_crops_size = 32
        cfg.crops.local_crops_size = 16
        cfg.crops.gram_teacher_crops_size = 48
    else:
        cfg.crops.global_crops_size = 32
        cfg.crops.local_crops_size = 16
        if cfg.crops.get("gram_teacher_crops_size"):
            cfg.crops.gram_teacher_crops_size = 48
    cfg.student.patch_size = 4

    schedules = build_schedules(cfg)
    assert schedules.at(0)["lr"] >= 0.0

    meta = SSLMetaArch(cfg)
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, 2, seed=0).items()}
    abstract = jax.eval_shape(lambda r: meta.init_params(r, batch),
                              jax.random.key(0))
    assert "student" in abstract and "teacher" in abstract
    if cfg.gram.use_loss and not cfg.gram.ema_teacher:
        assert "gram" in abstract


def test_multires_recipe_combines_loaders():
    cfg = load_config(
        os.path.join(REPO, "configs/train/vit7b16_high_res_adapt.yaml"))
    assert isinstance(cfg.crops.global_crops_size, list)
    assert len(cfg.crops.global_crops_size) == 5
    apply_dot_overrides(cfg, [
        "student.arch=vit_test", "student.patch_size=4",
        "train.dataset_path=Synthetic:size=32:image_size=48",
        "train.num_workers=2", "data.backend=folder",
    ])
    cfg.crops.global_crops_size = [16, 24]
    cfg.crops.local_crops_size = [8, 8]
    cfg.crops.gram_teacher_crops_size = [24, 32]
    cfg.crops.global_local_crop_pairs_ratios = [0.5, 0.5]
    from dinov3_tpu.data.pipeline import make_multires_train_pipeline

    it = make_multires_train_pipeline(cfg, global_batch_size=2)
    seen = set()
    for _ in range(6):
        b = next(it)
        seen.add(b["global_crops"].shape[1])
        assert b["gram_teacher_crops"].shape[1] in (24, 32)
    assert seen <= {16, 24} and len(seen) == 2
