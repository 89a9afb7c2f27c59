"""Floor baselines for the texture ablation (context for ABLATION_r04).

Two floors show where the trained numbers stand:
  pixel k-NN        — k-NN on raw normalized 32px pixels: measures how
                      much of the class is readable without any
                      learning (the dataset was built so palette is
                      uninformative; this should sit near chance).
  random-init       — the in-training eval harness run on an UNTRAINED
                      vit_test4 backbone: the iteration-0 point of every
                      trajectory/ablation curve.

Usage: JAX_PLATFORMS=cpu python scripts/texture_baselines.py [out_dir]
(out_dir should be the ablation out_dir so the same texture tree is
reused; defaults to /tmp/abl_full.)
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import numpy as np
    from PIL import Image

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data.textures import materialize_textures
    from dinov3_tpu.evals.knn import knn_eval

    out = sys.argv[1] if len(sys.argv) > 1 else "/tmp/abl_full"
    tex_root = os.path.join(out, "textures")
    manifest_path = os.path.join(tex_root, "manifest.json")
    if not os.path.isfile(manifest_path):
        # NEVER generate here: the whole point of these floors is that
        # they are computed on the exact tree the ablation curves used —
        # fabricating a fresh default tree would silently decouple them
        raise SystemExit(
            f"no texture manifest under {tex_root}; run "
            "scripts/ablation_recipe.py into this out_dir first")
    with open(manifest_path) as f:
        m = json.load(f)
    train_dir, val_dir = materialize_textures(
        tex_root, n_train_per_class=m["n_train_per_class"],
        n_val_per_class=m["n_val_per_class"], px=m["px"],
        seed=m["seed"])

    def load_split(root, px=32):
        xs, ys = [], []
        classes = sorted(os.listdir(root))
        for ci, c in enumerate(classes):
            cdir = os.path.join(root, c)
            for f in sorted(os.listdir(cdir)):
                im = Image.open(os.path.join(cdir, f)).resize(
                    (px, px), Image.BICUBIC)
                xs.append(np.asarray(im, np.float32).reshape(-1) / 255.0)
                ys.append(ci)
        return np.stack(xs), np.asarray(ys)

    xtr, ytr = load_split(train_dir)
    xva, yva = load_split(val_dir)
    # population note (ADVICE r4): the eval harness's loaders shuffle
    # (seeded) BEFORE drop_last=True at batch 64, so the trajectory
    # numbers see a random subset with the tail dropped — NOT a prefix
    # in dataset order. Rather than replicate the loader's shuffle here,
    # the pixel floor is computed on ALL samples; the difference is the
    # dropped tail (< one batch per split, ~8 of 360 val samples) and is
    # negligible for a chance-floor calibration.
    pixel_knn = knn_eval(xtr, ytr, xva, yva, n_classes=12, k=10)

    # untrained backbone through the SAME eval harness the trajectories
    # use — the iteration-0 point of every committed curve. The shared
    # builder (random init when ckpt_dir is None) keeps the init path —
    # jit + unbox — identical to the certification CLI's.
    from dinov3_tpu.evals import do_eval
    from dinov3_tpu.models import build_model_for_eval

    cfg = get_default_config()
    apply_dot_overrides(cfg, [
        "student.arch=vit_test4", "student.patch_size=4",
        "crops.global_crops_size=32", "crops.local_crops_size=16",
        f"data.root={train_dir}", "data.backend=folder",
        f"evaluation.train_dataset_path=Folder:root={train_dir}",
        f"evaluation.val_dataset_path=Folder:root={val_dir}",
    ])
    model, params = build_model_for_eval(cfg, ckpt_dir=None)
    # default n_classes (1000-way probe) to match the in-training
    # do_eval call every committed trajectory point used
    rand = do_eval(cfg, model, params)

    rec = {
        "pixel_knn_top1": round(pixel_knn, 4),
        "random_init_knn_top1": round(rand["knn_top1"], 4),
        "random_init_linear_top1": round(rand["linear_top1"], 4),
        "chance": round(1 / 12, 4),
    }
    print(json.dumps(rec))
    with open(os.path.join(out, "BASELINES.json"), "w") as f:
        json.dump(rec, f, indent=2)


if __name__ == "__main__":
    main()
