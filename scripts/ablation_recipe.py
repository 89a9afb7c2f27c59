"""Recipe ablation: the DINOv3 loss set deleted one piece at a time.

VERDICT r3 #7: the digits trajectory proves the recipe *trains*, but
nothing showed the iBOT/KoLeo parts of the recipe *mattering*. This
harness trains loss-ablation arms on the procedural texture dataset
(dinov3_tpu/data/textures.py — class = spatial structure, color
decorrelated from label):

  full:       DINO + iBOT + KoLeo (the pretrain recipe defaults)
  dino_only:  ibot.loss_weight=0, dino.koleo_loss_weight=0
  no_koleo:   DINO + iBOT        (dino.koleo_loss_weight=0)
  no_ibot:    DINO + KoLeo       (ibot.loss_weight=0)

The default ABL_ARMS runs the headline pair (full vs dino_only); the
committed ABLATION_r04.json is the full 2x2 factorial, i.e. two more
invocations with ABL_ARMS=no_koleo and ABL_ARMS=no_ibot into the same
out_dir — out_dir/ABLATION.json merges arms by name across invocations
(a re-run arm replaces its previous record).

and records the held-out k-NN / linear-probe trajectory of each arm via
the in-training eval harness (reference's do_test slot —
dinov3_jax/train/train.py:315-316 was a stub). The committed artifact is
the side-by-side curve: the full recipe must beat DINO-only on held-out
k-NN for the extra losses to be pulling weight.

Usage:  JAX_PLATFORMS=cpu python scripts/ablation_recipe.py [out_dir]
Env: ABL_STEPS (default 1200), ABL_EVAL_EVERY (400), ABL_ARCH
     (vit_test4), ABL_BATCH (48), ABL_ARMS (comma list, default
     "full,dino_only"), ABL_TRAIN_PER_CLASS (150), ABL_VAL_PER_CLASS
     (30) — shrink the last two for smoke runs.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ARMS = {
    "full": [],
    "dino_only": ["ibot.loss_weight=0.0", "dino.koleo_loss_weight=0.0"],
    # single-loss deletions complete the factorial with dino_only/full:
    "no_koleo": ["dino.koleo_loss_weight=0.0"],
    "no_ibot": ["ibot.loss_weight=0.0"],
}

DEFAULT_ARCH = "vit_test4"


def record_name(name: str, arch: str) -> str:
    """Merge key for ABLATION.json: arm name, arch-suffixed when the
    arch is non-default so invocations at different widths never
    silently replace each other's records (ADVICE r4)."""
    return name if arch == DEFAULT_ARCH else f"{name}_{arch}"


def run_arm(name: str, out: str, train_dir: str, val_dir: str,
            steps: int, eval_every: int, arch: str, batch: int) -> dict:
    from dinov3_tpu.train.train import main as train_main

    epoch_len = eval_every
    run_dir = os.path.join(out, f"run_{record_name(name, arch)}")
    # train.py appends to <run_dir>/evals.json and --no-resume does not
    # clear the output dir, so a re-run arm would otherwise read the
    # stale previous invocation's eval lines concatenated with its own
    # (ADVICE r4): truncate before training.
    try:
        os.remove(os.path.join(run_dir, "evals.json"))
    except OSError:
        pass
    result = train_main([
        "--output-dir", run_dir, "--no-resume",
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
        f"optim.epochs={steps // epoch_len}",
        "optim.warmup_epochs=1", "optim.lr=0.001",
        "optim.scaling_rule=none",
        "teacher.warmup_teacher_temp_epochs=2",
        "train.num_workers=4",
        "data.backend=folder", f"data.root={train_dir}",
        "train.dataset_path=Folder:split=TRAIN",
        f"evaluation.eval_period_iterations={eval_every}",
        f"evaluation.train_dataset_path=Folder:root={train_dir}",
        f"evaluation.val_dataset_path=Folder:root={val_dir}",
    ] + ARMS[name])
    traj = []
    with open(os.path.join(run_dir, "evals.json")) as f:
        for line in f:
            traj.append(json.loads(line))
    return {"arm": record_name(name, arch), "overrides": ARMS[name],
            "trajectory": traj,
            "final_loss": result.get("final_loss"),
            # per-arm metadata: merged artifacts can span invocations
            # with different settings, so each arm records its own
            "steps": steps, "arch": arch, "batch": batch}


def main():
    from dinov3_tpu.data.textures import materialize_textures
    out = sys.argv[1] if len(sys.argv) > 1 else "/tmp/ablation_run"
    steps = int(os.environ.get("ABL_STEPS", "1200"))
    eval_every = int(os.environ.get("ABL_EVAL_EVERY", "400"))
    arch = os.environ.get("ABL_ARCH", "vit_test4")
    batch = int(os.environ.get("ABL_BATCH", "48"))
    arms = [a.strip() for a in
            os.environ.get("ABL_ARMS", "full,dino_only").split(",")
            if a.strip()]
    unknown = [a for a in arms if a not in ARMS]
    if unknown:
        raise SystemExit(f"unknown ABL_ARMS {unknown}; known: {list(ARMS)}")
    if steps < eval_every or steps % eval_every:
        raise SystemExit(
            f"ABL_STEPS={steps} must be a positive multiple of "
            f"ABL_EVAL_EVERY={eval_every} (epochs are eval periods)")

    n_train = int(os.environ.get("ABL_TRAIN_PER_CLASS", "150"))
    n_val = int(os.environ.get("ABL_VAL_PER_CLASS", "30"))
    train_dir, val_dir = materialize_textures(
        os.path.join(out, "textures"),
        n_train_per_class=n_train, n_val_per_class=n_val,
    )

    art_path = os.path.join(out, "ABLATION.json")
    results = []
    if os.path.isfile(art_path):
        # merge across invocations by arm name (a re-run arm replaces
        # its old record), so the documented multi-invocation factorial
        # accumulates into ONE artifact instead of each run clobbering
        # the previous arms. A truncated artifact (killed mid-write of
        # a non-atomic writer from an older revision) must not brick
        # every later invocation — start fresh instead.
        replaced = {record_name(a, arch) for a in arms}

        def _stale(rec: dict) -> bool:
            # a record is replaced only when BOTH its arm key and its
            # recorded arch match this invocation's (arm, arch) cell:
            # the arch guard keeps a default-arch rerun from deleting an
            # old-format bare-name record that was written at a
            # DIFFERENT arch (a distinct cell). Also drop OLD-format
            # records from the pre-suffix revision (bare arm name at a
            # non-default arch) when their arch metadata matches.
            rec_arch = rec.get("arch", DEFAULT_ARCH)
            return ((rec["arm"] in replaced and rec_arch == arch)
                    or (rec["arm"] in arms and rec_arch == arch))

        try:
            with open(art_path) as f:
                results = [a for a in json.load(f).get("arms", [])
                           if not _stale(a)]
        except ValueError:
            print(f"[ablation] {art_path} unreadable; starting fresh",
                  flush=True)
    for arm in arms:
        print(f"[ablation] arm={arm} steps={steps}", flush=True)
        results.append(run_arm(arm, out, train_dir, val_dir, steps,
                               eval_every, arch, batch))
        # incremental + atomic: a killed later arm still leaves a
        # parseable artifact with every completed arm
        tmp_path = art_path + ".tmp"
        with open(tmp_path, "w") as f:
            json.dump({
                "dataset": "procedural textures, 12 classes = motif x "
                           "frequency-band, per-image palette "
                           f"({12 * n_train} train / {12 * n_val} val "
                           "PNGs, folder backend; eval batches are 64 "
                           "with drop_last, so metrics are over 320 of "
                           "the 360 val images)",
                # no top-level arch/steps/batch: the merged artifact can
                # span invocations with different settings — the per-arm
                # records are authoritative (r5 code review)
                "arms": results,
            }, f, indent=2)
        os.replace(tmp_path, art_path)
    print(json.dumps(results[-1]["trajectory"][-1:], indent=2))


if __name__ == "__main__":
    main()
