"""Timed A/B sweep of train-step variants on the real chip, with losses.

Each variant runs the full fused ViT-L train step (bench.py config) for a
few steps, printing step time, img/s/chip, and the loss trajectory so
numerics changes show up alongside the speed. Variants share one process
(compile cache reused).

Usage: python scripts/bench_sweep.py [variant ...]
Variants are "name:key=val,key=val" where keys are env knobs understood
below, e.g.  base:DINOV3_FUSED_LN=0  fused:DINOV3_FUSED_LN=1
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_variant(name: str, env: dict, steps=10, warmup=3):
    import jax
    import jax.numpy as jnp

    for k, v in env.items():
        os.environ[k] = v

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup, put_batch

    arch = os.environ.get("BENCH_ARCH", "vit_large")
    per_chip = int(os.environ.get("BENCH_BATCH", "8"))
    n = jax.device_count()
    cfg = get_default_config()
    apply_dot_overrides(cfg, [
        f"student.arch={arch}",
        "student.n_storage_tokens=4",
        "student.drop_path_rate=0.3",
        "optim.scaling_rule=none",
        "parallel.data=-1",
        "compute_precision.param_dtype=bf16",
    ] + list(env.get("_overrides", "").split()))
    B = per_chip * n
    batch_np = make_synthetic_batch(cfg, B, seed=0)
    batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
    t0 = time.perf_counter()
    setup = build_train_setup(cfg, batch)
    dbatch = put_batch(batch, setup.batch_shardings)
    rng = jax.random.key(0)
    state = setup.state
    scalars = setup.scalars(0)
    print(f"[{name}] setup {time.perf_counter() - t0:.1f}s", flush=True)

    losses = []
    t0 = time.perf_counter()
    for i in range(warmup):
        state, metrics = setup.step_fn(state, dbatch, scalars, rng)
        losses.append(float(metrics["total_loss"]))
    print(f"[{name}] warmup {time.perf_counter() - t0:.1f}s", flush=True)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = setup.step_fn(state, dbatch, scalars, rng)
    losses.append(float(metrics["total_loss"]))
    dt = (time.perf_counter() - t0) / steps
    print(f"[{name}] step {dt * 1e3:.2f} ms  {B / dt / n:.2f} img/s/chip  "
          f"losses {['%.4f' % l for l in losses]}", flush=True)
    return B / dt / n


def main():
    specs = sys.argv[1:] or ["fused:DINOV3_FUSED_LN=1", "base:DINOV3_FUSED_LN=0"]
    import jax

    from dinov3_tpu.utils import configure_compile_cache

    configure_compile_cache()
    results = {}
    for spec in specs:
        name, _, kvs = spec.partition(":")
        env = {}
        for kv in kvs.split(","):
            if kv:
                k, _, v = kv.partition("=")
                env[k] = v
        results[name] = run_variant(name, env)
    print({k: round(v, 2) for k, v in results.items()}, flush=True)


if __name__ == "__main__":
    main()
