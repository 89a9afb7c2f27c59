"""Copy-class HLO accounting for the step-wide RNG-plan engine
(rng/plan.py): op counts + bytes + per-category attribution, plan vs
the legacy fold_in oracle, at two pass granularities.

Methodology (the PR-1/PR-2 discipline, scripts/cost_update_phase.py /
cost_target_phase.py): compile the EXACT jitted programs on the host
backend and count copy-class HLO instructions
(``copy``/``copy-start``/``copy-done``/``dynamic-update-slice``)
outside fusion bodies — the buffer-allocating set — with the shared
category attribution (utils.classify_copy: "rng" = u32 key/counter
plumbing, "donation_async", "small", "large"). Two granularities:

- ``step``: the full fused train step (fwd+bwd+clip+AdamW+EMA, donated
  state) — what the copy-census CI ceiling pins
  (tests/test_streaming_targets.py);
- ``student_fwd``: the student forward alone (value_and_grad of the
  meta-arch loss), where every device-side RNG consumer lives — the
  granularity that isolates the plan's effect from update-phase and
  donation copies.

The r5 on-chip profile priced the copy/small-op bucket at 14.8% of step
time (21,384 copy-done + 35,400 slice-done trace ops,
PROFILE_r05.json), and the PR-2 census attributed ~98% of the 518
compiled-step copies to RNG-scalar plumbing. This script is the
committed host-side before/after for the engine that removes them; the
on-chip A/B has not been run on the chip.

One JSON line on stdout -> commit as COST_RNG_r08.json.

Usage: JAX_PLATFORMS=cpu python scripts/cost_rng_copies.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import importlib.util

_spec = importlib.util.spec_from_file_location(
    "cost_target_phase", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "cost_target_phase.py")
)
ctp = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ctp)


# the census arch (cost_target_phase.py convention): the copy structure
# under audit — per-layer rng threading, donation aliasing, crop-concat
# copies — is depth/width-independent at this granularity, and vit_test
# keeps the CPU compile seconds-long.
# model.crop_packing is pinned OFF: this artifact (COST_RNG_r08.json)
# is the rng-plan engine's before/after on the two-pass program it was
# committed against; the PR-4 crop-packed engine independently removes
# the two-pass crop-boundary copies from both arms (518 -> 190 legacy /
# 144 -> 96 plan on the packed default, tests/test_streaming_targets.py
# re-pins that ceiling) and would blur the attribution here.
CENSUS_OVERRIDES = [
    "model.crop_packing=false",
    "student.arch=vit_test", "student.patch_size=4",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
    "dino.head_bottleneck_dim=16",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
    "ibot.head_bottleneck_dim=16",
    "optim.scaling_rule=none",
]


def census_cfg(extra=()):
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config

    cfg = get_default_config()
    apply_dot_overrides(cfg, CENSUS_OVERRIDES + list(extra))
    return cfg


def student_fwd_census(cfg, B: int = 4) -> dict:
    """Copy census of the student forward+backward alone (the pass that
    holds every device-side RNG consumer)."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch
    from dinov3_tpu.utils import hlo_copy_census

    meta = SSLMetaArch(cfg)
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, B, seed=0).items()}
    params_abs = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0))

    def loss(student, teacher, rng):
        rng_plan = rngs = None
        if meta.rng_plan:
            rng_plan = meta.build_rng_plan(rng, batch)
        else:
            rngs = {
                "drop_path": jax.random.fold_in(rng, 0),
                "rope": jax.random.fold_in(rng, 1),
                "dropout": jax.random.fold_in(rng, 2),
            }
        total, _ = meta.forward(
            student, {"teacher": teacher}, batch, teacher_temp=0.07,
            state=meta.init_state(), iteration=jnp.zeros((), jnp.int32),
            rngs=rngs, rng_plan=rng_plan,
        )
        return total

    compiled = jax.jit(jax.grad(loss)).lower(
        params_abs["student"], params_abs["teacher"],
        jax.eval_shape(lambda: jax.random.key(0)),
    ).compile()
    return hlo_copy_census(compiled.as_text())


def main():
    rec = {"arch": "vit_test", "granularity": {}}
    arms = {"plan_on": [], "plan_off": ["rng.plan=false"]}
    step = {t: ctp.copy_census(census_cfg(e), B=4) for t, e in arms.items()}
    fwd = {t: student_fwd_census(census_cfg(e), B=4)
           for t, e in arms.items()}
    rec["granularity"]["step"] = step
    rec["granularity"]["student_fwd"] = fwd
    rec["reduction_pct"] = {
        g: round(100.0 * (1.0 - d["plan_on"]["hlo_copy_total"]
                          / max(1, d["plan_off"]["hlo_copy_total"])), 1)
        for g, d in rec["granularity"].items()
    }
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
