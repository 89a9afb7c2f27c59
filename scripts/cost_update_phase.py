"""Bytes-accessed accounting for the weight-update phase: the four-pass
clip -> AdamW -> apply -> EMA chain vs the single-pass fused engine
(train/fused_update.py).

Methodology (stated precisely because it is the committed evidence in
docs/PERFORMANCE.md):

- The CHAIN is accounted at pass granularity: each of its four tree
  passes (per-submodel clip, optax.scale_by_adam + scheduled lr/wd,
  optax.apply_updates, teacher EMA) is compiled as its own XLA program
  and their ``cost_analysis()['bytes accessed']`` are summed. This is
  the granularity the r5 on-chip profile shows the TPU executing the
  phase at — distinct sequential weight-shaped elementwise fusion
  programs with materialized intermediates (``PROFILE_r05.json``
  ``multiply_add``/``multiply_multiply`` fusions inside the 28.5%
  norm/reduce bucket) — and it is what any pass-structured execution
  (separate jits, or a backend that does not fuse across the pass
  chain) pays.
- The FUSED engine is one program: clip norms as one up-front batched
  reduction, then a single tree.map emitting (new_param, new_mu,
  new_nu, new_teacher) per leaf.
- Caveat, measured and worth knowing: when the WHOLE chain is handed to
  XLA as one jit, CSE canonicalizes it to the same HLO as the fused
  engine (verified: identical op counts and bytes on the cpu backend).
  The engine's value is therefore structural — it guarantees the
  single-program form at the StableHLO level instead of relying on the
  backend seeing through four optax tree passes — and the on-chip A/B
  (not yet run) is the measurement that decides what the TPU
  scheduler actually does with each form.

Everything in these programs is weight-shaped (grads, masters, moments,
teacher and nothing else), so the totals ARE the weight-shaped
update-phase traffic. Host-side compile only (cpu backend fine; no
execution — abstract eval_shape + AOT lower/compile).

One JSON line on stdout:

    {"arch": ..., "n_params": ..., "bytes_chain_passes": {...},
     "bytes_chain_total": ..., "bytes_fused": ..., "reduction_pct": ...,
     "floor_bytes": ..., "fused_over_floor": ...}

``floor_bytes``: read g+p+mu+nu+t, write p+mu+nu+t = 9 fp32 passes over
the parameter count, plus the up-front clip-norm read of g = 10.

On a data-parallel mesh the update runs in a cross-replica sharded
form (train/setup.py resolve_update_arm); this script compiles for ONE
device, where every mesh kind's update is the replicated fused engine
measured here.

Usage: JAX_PLATFORMS=cpu python scripts/cost_update_phase.py [arch]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import importlib.util

_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench.py")
)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def _bytes_accessed(fn, args, donate=()) -> float:
    import jax

    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    analysis = compiled.cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0]
    return float(analysis["bytes accessed"])


def measure(cfg) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import (
        build_fused_update,
        build_optimizer,
        build_schedules,
        clip_by_per_submodel_norm,
    )
    from dinov3_tpu.train.fused_update import ema_leaf
    from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch

    meta = SSLMetaArch(cfg)
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 1, seed=0).items()}
    abstract = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0)
    )
    student = abstract["student"]
    schedules = build_schedules(cfg)
    optimizer = build_optimizer(cfg, student, schedules)
    fused = build_fused_update(cfg, student, schedules, ema=True)
    opt_state = jax.eval_shape(optimizer.init, student)
    momentum = jax.ShapeDtypeStruct((), jnp.float32)
    clip = cfg.optim.clip_grad

    passes = {
        "clip": _bytes_accessed(
            lambda g: clip_by_per_submodel_norm(g, clip), (student,)),
        "adamw": _bytes_accessed(
            lambda g, s, p: optimizer.update(g, s, p),
            (student, opt_state, student), donate=(1,)),
        "apply": _bytes_accessed(
            optax.apply_updates, (student, student), donate=(0,)),
        "ema": _bytes_accessed(
            lambda t, s, m: jax.tree.map(
                lambda tt, ss: ema_leaf(tt, ss, m), t, s),
            (student, student, momentum), donate=(0,)),
    }
    bytes_fused = _bytes_accessed(
        lambda g, p, t, s, m: fused(g, p, t, s, m)[:3],
        (student, student, student, opt_state, momentum), donate=(1, 2, 3))

    n_params = sum(
        int(jnp.prod(jnp.asarray(l.shape)))
        for l in jax.tree.leaves(student)
    )
    total = sum(passes.values())
    floor = 10 * 4 * n_params
    return {
        "n_params": n_params,
        "bytes_chain_passes": passes,
        "bytes_chain_total": total,
        "bytes_fused": bytes_fused,
        "reduction_pct": round(100.0 * (1.0 - bytes_fused / total), 1),
        "floor_bytes": floor,
        "fused_over_floor": round(bytes_fused / floor, 3),
    }


def main():
    arch = sys.argv[1] if len(sys.argv) > 1 else "vit_large"
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config

    cfg = get_default_config()
    apply_dot_overrides(cfg, bench.build_step_overrides(arch, 0))
    rec = {"arch": arch}
    rec.update(measure(cfg))
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
