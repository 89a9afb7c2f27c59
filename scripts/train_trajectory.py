"""Accuracy-trajectory run: SSL pretraining must make features BETTER.

Trains a miniature ViT with the full DINOv3 recipe on a real-file
(class-per-directory PNG folder) backend and runs the in-training eval
harness periodically; the committed artifact (TRAJECTORY_r0N.json) records
k-NN / linear-probe accuracy of the EMA teacher's features rising over
training — the first rung toward the reference's 83.3% IN1k target
(reference: dinov3_jax/configs/train/vitl_im1k_lin834.yaml:1-2, whose
`do_test` was a stub — train/train.py:315-316).

Data: scikit-learn's bundled handwritten digits (1797 real 8x8 images,
10 classes — the only real labeled image data reachable in a zero-egress
environment), upscaled and materialized as PNGs so the trainer exercises
the real folder pipeline (decode -> augment -> collate -> device).

Usage:  JAX_PLATFORMS=cpu python scripts/train_trajectory.py [out_dir]
Env: TRAJ_STEPS (default 600), TRAJ_EVAL_EVERY (default 100),
     TRAJ_ARCH (vit_test4), TRAJ_BATCH (48).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def materialize_digits(root: str, img_px: int = 64) -> tuple[str, str]:
    """Write sklearn digits as root/{train,val}/<class>/<i>.png."""
    import numpy as np
    from PIL import Image
    from sklearn.datasets import load_digits

    d = load_digits()
    n_train = 1500
    rng = np.random.default_rng(0)
    order = rng.permutation(len(d.images))
    for split, idxs in (("train", order[:n_train]),
                        ("val", order[n_train:])):
        for i in idxs:
            img = d.images[i]  # 8x8 float 0..16
            arr = np.clip(img * 15.9375, 0, 255).astype(np.uint8)
            pil = Image.fromarray(arr).convert("RGB").resize(
                (img_px, img_px), Image.BICUBIC
            )
            cls_dir = os.path.join(root, split, f"{d.target[i]:02d}")
            os.makedirs(cls_dir, exist_ok=True)
            pil.save(os.path.join(cls_dir, f"{i}.png"))
    return os.path.join(root, "train"), os.path.join(root, "val")


def main():
    out = sys.argv[1] if len(sys.argv) > 1 else "/tmp/trajectory_run"
    steps = int(os.environ.get("TRAJ_STEPS", "600"))
    eval_every = int(os.environ.get("TRAJ_EVAL_EVERY", "100"))
    arch = os.environ.get("TRAJ_ARCH", "vit_test4")
    batch = int(os.environ.get("TRAJ_BATCH", "48"))

    train_dir, val_dir = materialize_digits(os.path.join(out, "digits"))

    from dinov3_tpu.train.train import main as train_main

    epoch_len = eval_every
    epochs = steps // epoch_len
    result = train_main([
        "--output-dir", os.path.join(out, "run"), "--no-resume",
        f"student.arch={arch}", "student.patch_size=4",
        "student.drop_path_rate=0.1", "student.layerscale=1.0e-5",
        "crops.global_crops_size=32", "crops.local_crops_size=16",
        "crops.local_crops_number=6",
        "dino.head_n_prototypes=1024", "dino.head_hidden_dim=256",
        "dino.head_bottleneck_dim=64",
        "ibot.head_n_prototypes=1024", "ibot.head_hidden_dim=256",
        "ibot.head_bottleneck_dim=64",
        f"train.batch_size_per_device={batch}",
        f"train.OFFICIAL_EPOCH_LENGTH={epoch_len}",
        f"optim.epochs={epochs}",
        "optim.warmup_epochs=1", "optim.lr=0.001",
        "optim.scaling_rule=none",
        "teacher.warmup_teacher_temp_epochs=2",
        "train.num_workers=4",
        "data.backend=folder", f"data.root={train_dir}",
        "train.dataset_path=Folder:split=TRAIN",
        f"evaluation.eval_period_iterations={eval_every}",
        f"evaluation.train_dataset_path=Folder:root={train_dir}",
        f"evaluation.val_dataset_path=Folder:root={val_dir}",
    ])

    # one record per eval (the trainer writes evals.json exactly for
    # this; the meter JSONL smooths values into running medians)
    traj = []
    with open(os.path.join(out, "run", "evals.json")) as f:
        for line in f:
            traj.append(json.loads(line))
    artifact = {
        "dataset": "sklearn-digits (1500 train / 297 val PNGs, folder backend)",
        "arch": arch, "steps": steps, "batch": batch,
        "trajectory": traj,
        "final_loss": result.get("final_loss"),
    }
    print(json.dumps(artifact, indent=2))
    with open(os.path.join(out, "TRAJECTORY.json"), "w") as f:
        json.dump(artifact, f, indent=2)


if __name__ == "__main__":
    main()
