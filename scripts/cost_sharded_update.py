"""Per-device byte + collective accounting for the cross-replica
sharded update engine (train/fused_update.py make_sharded_update) vs
the replicated fused oracle, on a SIMULATED multi-device mesh.

Methodology (the PR-1/2/3/4 discipline — compile the exact programs on
the host backend, account from their compiled HLO; stated precisely
because this is the committed evidence in docs/PERFORMANCE.md):

- Both arms are compiled at PASS GRANULARITY as standalone update-phase
  programs over ``dp`` simulated CPU devices, taking [dp, *leaf] STACKS
  of per-replica partial gradients (dim 0 sharded over the data axis —
  exactly what the data-parallel backward holds before any grad sync),
  so the grad synchronization collective is INSIDE the measured program
  for both arms instead of hiding in a backward pass this script does
  not compile.
- The REPLICATED arm sums the partials (GSPMD lowers it as the grad
  all-reduce) and runs the fused single-pass engine over the complete
  master/moment/teacher trees on every replica — the pre-PR-5 default.
- The SHARDED arm is ``make_sharded_update_schedule``: the same
  schedule with its collectives spelled out — psum_scatter
  (reduce-scatter) of each leaf's partials, shard-local single-pass
  clip+AdamW+EMA over 1/dp of every leaf (clip norms as shard-local
  partials + ONE small psum), all-gather of the updated student + EMA'd
  teacher. The in-step engine expresses the identical schedule through
  GSPMD "update_shard" annotations; this container's XLA:CPU lowers
  that form as all-reduce + fused dynamic-slice (recorded here under
  ``engine_gspmd_census`` for honesty — it is reduce-scatter's
  pre-rewrite form, which the TPU/GPU collective optimizer rewrites;
  the schedule program is the committed proof of the post-rewrite
  collective set, and tests/test_sharded_update.py pins that it
  computes the identical update).
- ``cost_analysis()['bytes accessed']`` of an SPMD-partitioned module
  is PER-DEVICE (the module is the per-device program).
  ``weight_shaped_bytes`` subtracts the collective result bytes
  (utils.hlo_collective_census) from that total, isolating the
  elementwise master/moment/teacher traffic each replica streams.
- The collective census must show: replicated arm = all_reduce only;
  sharded arm = reduce_scatter + all_gather + the small clip psum
  (all_reduce bytes ~scalar), and ZERO unattributed collectives.

One JSON line on stdout -> commit as COST_SHUP_r10.json.

Usage: JAX_PLATFORMS=cpu python scripts/cost_sharded_update.py \
           [arch] [dp]      (defaults: vit_large 8)
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DP = int(sys.argv[2]) if len(sys.argv) > 2 else 8

# the simulated device count must be pinned before jax initializes
os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += f" --xla_force_host_platform_device_count={DP}"

import importlib.util

_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench.py")
)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def _compiled(fn, args, mesh, in_shardings, out_shardings=None, donate=()):
    import jax

    with mesh:
        return jax.jit(
            fn, in_shardings=in_shardings, out_shardings=out_shardings,
            donate_argnums=donate,
        ).lower(*args).compile()


def _bytes(compiled) -> float:
    analysis = compiled.cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0]
    return float(analysis["bytes accessed"])


def measure(cfg, dp: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
    from dinov3_tpu.parallel.sharding import UPDATE_SHARD_AXES
    from dinov3_tpu.train import (
        build_multiplier_trees,
        build_schedules,
        make_fused_update,
        make_sharded_update,
        make_sharded_update_schedule,
    )
    from dinov3_tpu.train.fused_update import (
        leaf_size,
        padded_flat_size,
        sharded_adam_zeros,
    )
    from dinov3_tpu.train.optimizer import ScheduledAdamWState
    from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.utils import hlo_collective_census

    import flax.linen as nn
    import optax

    mesh = build_mesh(MeshSpec(data=dp))
    set_current_mesh(mesh)
    meta = SSLMetaArch(cfg)
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 1, seed=0).items()}
    student = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0)
    )["student"]
    schedules = build_schedules(cfg)
    lm, wm, isll = build_multiplier_trees(
        student,
        layerwise_decay=cfg.optim.layerwise_decay,
        patch_embed_lr_mult=cfg.optim.patch_embed_lr_mult,
        dino_head_wd_multiplier=cfg.optim.dino_head_wd_multiplier,
    )
    kw = dict(b1=cfg.optim.adamw_beta1, b2=cfg.optim.adamw_beta2,
              clip_grad=cfg.optim.clip_grad, ema=True)
    fused = make_fused_update(schedules, lm, wm, isll, **kw)
    sharded = make_sharded_update(schedules, lm, wm, isll, mesh, **kw)
    schedule = make_sharded_update_schedule(schedules, lm, wm, isll, mesh,
                                            **kw)

    rep = NamedSharding(mesh, P())
    axes = tuple(a for a in UPDATE_SHARD_AXES if a in mesh.shape)
    stacks = NamedSharding(mesh, P(axes))
    gstack = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((dp,) + l.shape, l.dtype), student)
    opt_rep = jax.eval_shape(
        lambda p: ScheduledAdamWState(
            jnp.zeros((), jnp.int32),
            optax.ScaleByAdamState(jnp.zeros((), jnp.int32),
                                   jax.tree.map(jnp.zeros_like, p),
                                   jax.tree.map(jnp.zeros_like, p))),
        student)
    opt_sh = jax.eval_shape(
        lambda p: ScheduledAdamWState(
            jnp.zeros((), jnp.int32),
            optax.ScaleByAdamState(
                jnp.zeros((), jnp.int32),
                nn.meta.unbox(sharded_adam_zeros(p, dp)),
                nn.meta.unbox(sharded_adam_zeros(p, dp)))),
        student)
    momentum = jax.ShapeDtypeStruct((), jnp.float32)
    rep_tree = jax.tree.map(lambda _: rep, student)
    stack_tree = jax.tree.map(lambda _: stacks, gstack)
    opt_rep_sh = jax.tree.map(lambda _: rep, opt_rep)
    opt_sh_sh = ScheduledAdamWState(
        rep, optax.ScaleByAdamState(
            rep,
            jax.tree.map(lambda _: stacks, opt_sh.adam.mu),
            jax.tree.map(lambda _: stacks, opt_sh.adam.nu)))

    def replicated_arm(gs, p, t, s, m):
        g = jax.tree.map(lambda x: jnp.sum(x, 0), gs)  # the grad all-reduce
        return fused(g, p, t, s, m)[:3]

    def sharded_arm(gs, p, t, s, m):
        return schedule(gs, p, t, s, m)[:3]

    def engine_arm(gs, p, t, s, m):
        # the in-step GSPMD-annotation engine, for its structural census
        g = jax.tree.map(lambda x: jnp.sum(x, 0), gs)
        return sharded(g, p, t, s, m)[:3]

    args_rep = (gstack, student, student, opt_rep, momentum)
    args_sh = (gstack, student, student, opt_sh, momentum)
    in_rep = (stack_tree, rep_tree, rep_tree, opt_rep_sh, rep)
    in_sh = (stack_tree, rep_tree, rep_tree, opt_sh_sh, rep)
    c_rep = _compiled(replicated_arm, args_rep, mesh, in_rep,
                      out_shardings=(rep_tree, rep_tree, opt_rep_sh),
                      donate=(1, 2, 3))
    c_sh = _compiled(sharded_arm, args_sh, mesh, in_sh,
                     out_shardings=(rep_tree, rep_tree, opt_sh_sh),
                     donate=(1, 2, 3))
    c_eng = _compiled(engine_arm, args_sh, mesh, in_sh,
                      out_shardings=(rep_tree, rep_tree, opt_sh_sh),
                      donate=(1, 2, 3))

    census_rep = hlo_collective_census(c_rep.as_text())
    census_sh = hlo_collective_census(c_sh.as_text())
    census_eng = hlo_collective_census(c_eng.as_text())
    b_rep, b_sh = _bytes(c_rep), _bytes(c_sh)
    w_rep = b_rep - census_rep["hlo_collective_bytes"]
    w_sh = b_sh - census_sh["hlo_collective_bytes"]

    n_params = sum(leaf_size(l) for l in jax.tree.leaves(student))
    n_padded = sum(padded_flat_size(leaf_size(l), dp)
                   for l in jax.tree.leaves(student))
    return {
        "dp": dp,
        "n_params": n_params,
        "n_padded": n_padded,
        "pad_waste_pct": round(100.0 * (n_padded - n_params) / n_params, 4),
        "bytes_per_device": {"replicated": b_rep, "sharded": b_sh},
        "weight_shaped_bytes_per_device": {
            "replicated": w_rep, "sharded": w_sh},
        "weight_shaped_reduction_pct": round(100.0 * (1.0 - w_sh / w_rep), 1),
        "total_reduction_pct": round(100.0 * (1.0 - b_sh / b_rep), 1),
        "collective_census": {
            "replicated": census_rep, "sharded": census_sh},
        "engine_gspmd_census": census_eng,
    }


def main():
    import jax

    try:
        jax.config.update("jax_num_cpu_devices", DP)
    except AttributeError:
        pass  # XLA_FLAGS set above covers old jaxlibs
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config

    arch = sys.argv[1] if len(sys.argv) > 1 else "vit_large"
    cfg = get_default_config()
    apply_dot_overrides(cfg, bench.build_step_overrides(arch, 0))
    rec = {"arch": arch}
    rec.update(measure(cfg, DP))
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
