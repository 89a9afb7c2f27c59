"""Count step-program FLOPs with XLA cost analysis (committed artifact).

Round 4 committed `FLOPS_r04.json` from an ad-hoc console session; this
script makes the count reproducible and extends it to the arch ladder.
It compiles the EXACT bench step program (same override path bench.py
uses) on the host CPU backend and reads ``compiled.cost_analysis()``.

Caveats the artifact must carry (VERDICT r4 weak #4):
- ``cost_analysis`` counts a ``lax.scan`` body ONCE, so scanned-stack
  programs undercount by ~n_blocks; every point here compiles the
  UNROLLED stack (train.scan_layers=false) so numbers are comparable.
- These are executed-FLOP counts on a host compile — a compute ceiling,
  not a measurement; the measured img/s live in BENCH_* artifacts.

Usage: JAX_PLATFORMS=cpu python scripts/count_flops.py [out.json]
Env: FLOPS_POINTS — comma list of POINTS keys; the default is EVERY
     point, so running the script as documented regenerates the full
     committed artifact (compile_s and date vary; the persistent
     compile cache makes warm reruns fast).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (arch, batch, res_override_px_or_0, drop_path_mode, extra overrides)
#
# The pre-PR-4 points pin ``model.crop_packing=false``: they were
# committed against the two-pass student program (FLOPS_r04/r05) and
# serve as the stable cross-check rungs; the crop-packed default
# program (one backbone scan, pad tokens priced in) gets its own
# standing ledger point so the pad-waste FLOPs sit next to the subset
# drop-path cut in the artifact.
_TWO_PASS = "model.crop_packing=false"
POINTS = {
    # the r4 pair, reproduced: the subset drop-path FLOP cut on the
    # default bench program (ViT-L/16, B=8, 224px + 8x96px)
    "vitl_mask": ("vit_large", 8, 0, "mask", [_TWO_PASS]),
    "vitl_subset": ("vit_large", 8, 0, "subset", [_TWO_PASS]),
    # the r5 default program: B=12, the on-chip sweep peak
    # (58.56 img/s/chip; round 5, before PR 1, one v5e chip)
    "vitl_subset_b12": ("vit_large", 12, 0, "subset", [_TWO_PASS]),
    # the PR-4 default program: crop-packed single-pass student (44
    # packed rows instead of 120; attention runs over 197-token rows
    # for the locals too, so the pad/cross-segment waste shows up HERE
    # as extra counted FLOPs — the engine trades them for one weight
    # stream and clean tiling, COST_PACK_r09.json)
    "vitl_packed_b12": ("vit_large", 12, 0, "subset", []),
    # ladder points for the fp32-master BENCH_ARCH rungs (phH); the
    # _mask variants exist because the r1 bf16-master measurements ran
    # the mask program — utilization comparisons must divide them by
    # mask-program ceilings, not subset ones
    "vits": ("vit_small", 32, 0, "subset", [_TWO_PASS]),
    "vits_mask": ("vit_small", 32, 0, "mask", [_TWO_PASS]),
    "vitb": ("vit_base", 16, 0, "subset", [_TWO_PASS]),
    "vitb_mask": ("vit_base", 16, 0, "mask", [_TWO_PASS]),
    # high-res points (SLOW: the unrolled 512px host compile is ~4.5 min,
    # 768px substantially more) — request explicitly via FLOPS_POINTS
    "hr512": ("vit_large", 2, 512, "subset",
              ["kernels.flash_attention=xla", _TWO_PASS]),
    # B=2, not 1: KoLeo requires >=2 samples per group — a B=1 program
    # fails at build (this is also why the r5 queue's phF_hr768 is B=2)
    "hr768": ("vit_large", 2, 768, "subset",
              ["kernels.flash_attention=xla", _TWO_PASS]),
}


def count_point(arch: str, per_chip: int, res: int, mode: str,
                extra: list[str]) -> float:
    """TFLOP per step from a host compile of the bench program."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup, put_batch

    # the override list comes from bench.py itself (single source of
    # truth), so these ceilings are always ceilings OF THE BENCHED
    # program — plus the unroll override: cost_analysis counts a scan
    # body once, so the stack must be unrolled on every point
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    cfg = get_default_config()
    apply_dot_overrides(cfg, bench.build_step_overrides(
        arch, res, drop_path_mode=mode,
        extra=["train.scan_layers=false"] + extra))
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, per_chip, seed=0).items()}
    setup = build_train_setup(cfg, batch, devices=jax.devices()[:1])
    dbatch = put_batch(batch, setup.batch_shardings)
    compiled = setup.step_fn.lower(
        setup.state, dbatch, setup.scalars(0), jax.random.key(0)
    ).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return float(ca["flops"]) / 1e12


def main():
    import time

    import jax

    jax.config.update("jax_platforms", "cpu")
    from dinov3_tpu.utils import configure_compile_cache

    configure_compile_cache()

    out_path = sys.argv[1] if len(sys.argv) > 1 else "FLOPS.json"
    names = [p.strip() for p in os.environ.get(
        "FLOPS_POINTS", ",".join(POINTS)).split(",") if p.strip()]
    unknown = [n for n in names if n not in POINTS]
    if unknown:
        raise SystemExit(f"unknown FLOPS_POINTS {unknown}; "
                         f"known: {list(POINTS)}")

    rec = {
        "what": ("XLA cost_analysis of the exact bench step program "
                 "(fwd+bwd+opt, unrolled stack on every point for scan "
                 "comparability), host CPU compile — executed-FLOP "
                 "ceilings, not measurements"),
        "script": "scripts/count_flops.py",
        "date": time.strftime("%Y-%m-%d"),
        "cross_check": ("vitl_mask/vitl_subset/hr512 must reproduce "
                        "FLOPS_r04.json (13.680/10.083/9.344) — they pin "
                        "model.crop_packing=false, so any drift means "
                        "the two-pass program itself changed; the "
                        "crop-packed default program is the separate "
                        "vitl_packed_b12 point"),
        "points": {},
    }
    # incremental: each point is written as soon as it is counted, so a
    # killed later compile (the hr points are many-minute compiles)
    # still leaves a parseable artifact
    for name in names:
        arch, b, res, mode, extra = POINTS[name]
        t0 = time.perf_counter()
        tflop = count_point(arch, b, res, mode, extra)
        rec["points"][name] = {
            "arch": arch, "batch_per_chip": b,
            "global_crops_px": res or 224, "drop_path_mode": mode,
            "extra_overrides": extra,
            "tflop_per_step": round(tflop, 3),
            "tflop_per_img": round(tflop / b, 4),
            "compile_s": round(time.perf_counter() - t0, 1),
        }
        with open(out_path + ".tmp", "w") as f:
            json.dump(rec, f, indent=1)
        os.replace(out_path + ".tmp", out_path)
        print(f"[flops] {name}: {tflop:.3f} TFLOP/step "
              f"({time.perf_counter() - t0:.0f}s)", flush=True)
    print(json.dumps(rec["points"], indent=1))


if __name__ == "__main__":
    main()
