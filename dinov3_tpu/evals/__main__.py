"""Standalone protocol-scale evaluation.

    python -m dinov3_tpu.evals --ckpt /runs/vitl/ckpt \
        --config-file configs/train/vitl16_im1k.yaml \
        evaluation.train_dataset_path="ImageNet:split=TRAIN" \
        evaluation.val_dataset_path="ImageNet:split=VAL" data.root=/data/in1k

Restores the EMA teacher backbone from a framework checkpoint, extracts
features over the full train/val sets (sharded per host under multi-host
JAX), runs the DINOv2-protocol linear-probe lr sweep and k-NN at
k=10/20, and prints one JSON line. This is the certification path for the
reference's 83.3% linear / 82.2% k-NN targets
(dinov3_jax/configs/train/vitl_im1k_lin834.yaml:1-4); the reference's own
``do_test`` raised NotImplemented (train/train.py:315-316).
"""

from __future__ import annotations

import argparse
import json
import sys


def get_args_parser():
    p = argparse.ArgumentParser("dinov3_tpu standalone evaluation")
    p.add_argument("--ckpt", required=True,
                   help="checkpoint directory (the trainer's <out>/ckpt)")
    p.add_argument("--config-file", default="", help="run recipe YAML")
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--probe-epochs", type=int, default=10)
    p.add_argument("--max-train-samples", type=int, default=0,
                   help="0 = the full dataset")
    p.add_argument("--max-val-samples", type=int, default=0,
                   help="0 = the full dataset")
    p.add_argument("--output", default="", help="also write JSON here")
    p.add_argument("opts", nargs="*", default=[],
                   help="key.path=value config overrides")
    return p


def main(argv=None):
    from dinov3_tpu.utils import configure_compile_cache, require_accelerator

    configure_compile_cache()
    args = get_args_parser().parse_args(argv)

    from dinov3_tpu.configs import load_config
    from dinov3_tpu.evals.harness import do_eval
    from dinov3_tpu.models import build_model_for_eval
    from dinov3_tpu.parallel import initialize_distributed, is_main_process

    cfg = load_config(args.config_file or None, overrides=list(args.opts))
    initialize_distributed()
    # MODEL.DEVICE as in the trainer: no chip and no explicit CPU
    # request is an error, not a CPU evaluation
    require_accelerator((cfg.get("MODEL") or {}).get("DEVICE"))
    model, params = build_model_for_eval(cfg, args.ckpt)
    results = do_eval(
        cfg, model, params,
        batch_size=args.batch_size,
        probe_epochs=args.probe_epochs,
        max_train_samples=args.max_train_samples or None,
        max_val_samples=args.max_val_samples or None,
        protocol=True,
    )
    line = json.dumps(results)
    if is_main_process():
        print(line)
        if args.output:
            with open(args.output, "w") as f:
                f.write(line + "\n")
    return results


if __name__ == "__main__":
    main(sys.argv[1:])
