"""Bucketed collective engine accounting: the committed evidence
behind COST_BUCKET_r13.json (PR-1..6 discipline — compile the exact
shipped code paths, account from their compiled HLO).

Three instruments, all on the 8-simulated-device CPU mesh:

- **Update-phase twins (ViT-L, compile-only)**: the per-leaf schedule
  (``make_sharded_update_schedule`` — the bitwise oracle; one
  reduce-scatter per leaf, one all-gather per updated student/teacher
  leaf) vs the bucketed schedule (``make_bucketed_update_schedule`` —
  ONE reduce-scatter / all-gather per bucket), both compiled as
  standalone update-phase programs over [dp, *leaf] stacks of
  per-replica partial grads, so the grad sync is INSIDE the measured
  program. The in-step GSPMD-annotation engine
  (``make_bucketed_update``) is censused alongside for honesty
  (``engine_gspmd_census`` — this container's XLA:CPU lowers its
  reduce-scatters in the pre-rewrite all-reduce+slice form; the
  schedule twin is the committed proof of the post-rewrite collective
  set, and tests/test_buckets.py pins that both arms compute the
  BITWISE-identical update).
- **Message-size histogram**: ``utils.hlo_collective_census``'s
  power-of-two ``size_histogram`` of both twins — the per-leaf arm's
  hundreds of latency-bound sub-MiB messages vs the bucketed arm's
  handful of bandwidth-bound >= 64 MiB ones (>= 90% of collective
  bytes, pinned below).
- **Overlap placement**: ``jax.grad`` of the explicit overlap twin
  (``models/streaming.bucketed_stream_scan`` over a ViT-L-shaped bf16
  block stack in equal-sized bucket shards) — the census
  ``by_placement`` column must attribute the forward param all-gather
  to the forward loop body and its transposed grad reduce-scatter to
  the BACKWARD loop body (issued bucket-by-bucket as the backward
  produces each grad, overlappable with the remaining backward
  compute), with zero unattributed collectives.

One JSON record -> COST_BUCKET_r13.json (argv[1], default
./COST_BUCKET_r13.json); also printed to stdout.

Usage: JAX_PLATFORMS=cpu python scripts/cost_buckets.py [out] [dp]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = sys.argv[1] if len(sys.argv) > 1 and not sys.argv[1].startswith(
    "--") else "COST_BUCKET_r13.json"
DP = int(sys.argv[2]) if len(sys.argv) > 2 else 8

os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += f" --xla_force_host_platform_device_count={DP}"

BIG_BIN = 64 * 2 ** 20  # the coalesced-regime floor pinned below


def _log(msg):
    print(f"[cost_buckets] {msg}", file=sys.stderr, flush=True)


def _bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _compiled(fn, args, mesh, in_shardings, out_shardings=None, donate=()):
    import jax

    with mesh:
        return jax.jit(
            fn, in_shardings=in_shardings, out_shardings=out_shardings,
            donate_argnums=donate,
        ).lower(*args).compile()


def _big_bin_fraction(census) -> float:
    """Fraction of the module's collective bytes in >= BIG_BIN bins."""
    hist = census["size_histogram"]
    total = sum(h["bytes"] for h in hist.values())
    big = sum(h["bytes"] for h in hist.values()
              if h["floor_bytes"] >= BIG_BIN)
    return big / max(total, 1)


def update_phase_twins(cfg, dp: int) -> dict:
    """Per-leaf vs bucketed update schedules over the real ViT-L tree."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
    from dinov3_tpu.parallel.sharding import UPDATE_SHARD_AXES
    from dinov3_tpu.train import (
        build_multiplier_trees,
        build_schedules,
        make_bucket_plan,
        make_bucketed_update,
        make_bucketed_update_schedule,
        make_sharded_update_schedule,
    )
    from dinov3_tpu.train.fused_update import (
        bucketed_adam_zeros,
        sharded_adam_zeros,
    )
    from dinov3_tpu.train.optimizer import ScheduledAdamWState
    from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch
    from dinov3_tpu.utils import hlo_collective_census

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
    plan = make_bucket_plan(student, dp, is_last_layer=isll)
    kw = dict(b1=cfg.optim.adamw_beta1, b2=cfg.optim.adamw_beta2,
              clip_grad=cfg.optim.clip_grad, ema=True)
    perleaf = make_sharded_update_schedule(schedules, lm, wm, isll, mesh,
                                           **kw)
    bucketed = make_bucketed_update_schedule(schedules, lm, wm, isll, mesh,
                                             plan, **kw)
    engine = make_bucketed_update(schedules, lm, wm, isll, mesh, plan, **kw)

    rep = NamedSharding(mesh, P())
    axes = tuple(a for a in UPDATE_SHARD_AXES if a in mesh.shape)
    stacks = NamedSharding(mesh, P(axes))
    gstack = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((dp,) + l.shape, l.dtype), student)
    opt_pl = jax.eval_shape(
        lambda p: ScheduledAdamWState(
            jnp.zeros((), jnp.int32),
            optax.ScaleByAdamState(
                jnp.zeros((), jnp.int32),
                nn.meta.unbox(sharded_adam_zeros(p, dp)),
                nn.meta.unbox(sharded_adam_zeros(p, dp)))),
        student)
    opt_bk = jax.eval_shape(
        lambda: ScheduledAdamWState(
            jnp.zeros((), jnp.int32),
            optax.ScaleByAdamState(
                jnp.zeros((), jnp.int32),
                nn.meta.unbox(bucketed_adam_zeros(plan)),
                nn.meta.unbox(bucketed_adam_zeros(plan)))))
    momentum = jax.ShapeDtypeStruct((), jnp.float32)
    rep_tree = jax.tree.map(lambda _: rep, student)
    stack_tree = jax.tree.map(lambda _: stacks, gstack)
    opt_pl_sh = ScheduledAdamWState(
        rep, optax.ScaleByAdamState(
            rep,
            jax.tree.map(lambda _: stacks, opt_pl.adam.mu),
            jax.tree.map(lambda _: stacks, opt_pl.adam.nu)))
    opt_bk_sh = ScheduledAdamWState(
        rep, optax.ScaleByAdamState(
            rep,
            jax.tree.map(lambda _: stacks, opt_bk.adam.mu),
            jax.tree.map(lambda _: stacks, opt_bk.adam.nu)))

    def perleaf_arm(gs, p, t, s, m):
        return perleaf(gs, p, t, s, m)[:3]

    def bucketed_arm(gs, p, t, s, m):
        return bucketed(gs, p, t, s, m)[:3]

    def engine_arm(gs, p, t, s, m):
        # the in-step GSPMD engine (what build_train_setup ships); its
        # grad input is the already-summed tree
        g = jax.tree.map(lambda x: jnp.sum(x, 0), gs)
        return engine(g, p, t, s, m)[:3]

    args_pl = (gstack, student, student, opt_pl, momentum)
    args_bk = (gstack, student, student, opt_bk, momentum)
    in_pl = (stack_tree, rep_tree, rep_tree, opt_pl_sh, rep)
    in_bk = (stack_tree, rep_tree, rep_tree, opt_bk_sh, rep)
    _log(f"compiling per-leaf update twin (dp={dp})...")
    c_pl = _compiled(perleaf_arm, args_pl, mesh, in_pl,
                     out_shardings=(rep_tree, rep_tree, opt_pl_sh),
                     donate=(1, 2, 3))
    _log("compiling bucketed update twin...")
    c_bk = _compiled(bucketed_arm, args_bk, mesh, in_bk,
                     out_shardings=(rep_tree, rep_tree, opt_bk_sh),
                     donate=(1, 2, 3))
    _log("compiling in-step GSPMD bucketed engine...")
    c_eng = _compiled(engine_arm, args_bk, mesh, in_bk,
                      out_shardings=(rep_tree, rep_tree, opt_bk_sh),
                      donate=(1, 2, 3))

    census_pl = hlo_collective_census(c_pl.as_text())
    census_bk = hlo_collective_census(c_bk.as_text())
    census_eng = hlo_collective_census(c_eng.as_text())

    rows = plan.padding_stats()
    payload = sum(r["bytes"] for r in rows)
    return {
        "n_param_leaves": len(jax.tree.leaves(student)),
        "plan": {
            "n_buckets": len(rows),
            "target_bytes": plan.target_bytes,
            "payload_bytes": int(payload),
            "pad_fraction": round(
                sum(r["pad_elems"] for r in rows)
                / max(sum(r["elems"] for r in rows), 1), 6),
            "buckets": rows,
        },
        "collective_census": {
            "per_leaf": census_pl, "bucketed": census_bk},
        "engine_gspmd_census": census_eng,
        "big_bin_fraction": {
            "per_leaf": round(_big_bin_fraction(census_pl), 4),
            "bucketed": round(_big_bin_fraction(census_bk), 4),
        },
    }


def overlap_twin_census(cfg, dp: int, n_buckets: int = 4) -> dict:
    """``jax.grad`` of the explicit overlap twin at ViT-L block shapes:
    bf16 stack in equal bucket shards as a program input; the forward
    gathers ride the loop body one bucket ahead, their transposed grad
    reduce-scatters land in the backward loop body."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.models import build_backbone
    from dinov3_tpu.models.streaming import (
        bucketed_stream_scan,
        pack_stream_buckets,
    )
    from dinov3_tpu.ops.block import SelfAttentionBlock
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
    from dinov3_tpu.parallel.sharding import UPDATE_SHARD_AXES
    from dinov3_tpu.utils import hlo_collective_census

    mesh = build_mesh(MeshSpec(data=dp))
    set_current_mesh(mesh)
    model = build_backbone(cfg)
    kwargs = model._block_kwargs()
    kwargs["drop_path_rate"] = 0.0
    L = model.n_blocks
    D = model.embed_dim
    N = 197

    block = SelfAttentionBlock(**kwargs)
    one_block = jax.eval_shape(
        lambda r: block.init(r, jnp.zeros((1, N, D), jnp.bfloat16)),
        jax.random.key(0))["params"]
    one_block = nn.meta.unbox(one_block)
    stack = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(
            (L,) + tuple(p.shape), jnp.bfloat16), one_block)
    shards = jax.eval_shape(
        lambda s: pack_stream_buckets(s, n_buckets, dp), stack)

    x_abs = jax.ShapeDtypeStruct((2 * dp, N, D), jnp.bfloat16)
    axes = tuple(a for a in UPDATE_SHARD_AXES if a in mesh.shape)

    def loss(bucket_shards, x):
        y = bucketed_stream_scan(bucket_shards, x, mesh=mesh, prefetch=True)
        return jnp.sum(y.astype(jnp.float32))

    _log("compiling grad of the bucketed overlap twin...")
    compiled = _compiled(
        jax.grad(loss), (shards, x_abs), mesh,
        (NamedSharding(mesh, P(None, axes)), NamedSharding(mesh, P(axes[0]))),
    )
    census = hlo_collective_census(compiled.as_text())
    return {
        "n_blocks": L,
        "n_buckets": n_buckets,
        "bucket_shard_shape": list(shards.shape),
        "collective_census": census,
        "note": (
            "explicit overlap twin (models/streaming.bucketed_stream_scan "
            "under jax.grad): the bf16 stack rides as [n_buckets, S/dp] "
            "equal bucket shards; the scan body all-gathers bucket i+1 "
            "under bucket_prefetch while consuming bucket i, and jax's "
            "transpose turns each in-loop gather into an in-loop "
            "reduce-scatter of that bucket's grads — the census "
            "by_placement column attributes it to the BACKWARD loop "
            "body (op_name carries transpose(...)), i.e. the grad sync "
            "is issued as the backward produces each bucket, "
            "overlappable with the remaining backward compute."
        ),
    }


def main():
    import jax

    try:
        jax.config.update("jax_num_cpu_devices", DP)
    except AttributeError:
        pass
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config

    bench = _bench()
    cfg = get_default_config()
    # no scan_layers override: the per-leaf baseline counts (one RS per
    # of the 357 ViT-L leaves, one AG per updated student/teacher leaf)
    # are the unscanned tree's — the cost_sharded_update.py convention
    apply_dot_overrides(cfg, bench.build_step_overrides("vit_large", 0))

    upd = update_phase_twins(cfg, DP)
    pl = upd["collective_census"]["per_leaf"]["by_class"]
    bk = upd["collective_census"]["bucketed"]["by_class"]

    def ops(c, k):
        return c.get(k, {"ops": 0})["ops"]

    # ---- acceptance pins (ISSUE 9) ----
    assert upd["collective_census"]["per_leaf"]["unattributed"] == 0
    assert upd["collective_census"]["bucketed"]["unattributed"] == 0
    rs_before, rs_after = ops(pl, "reduce_scatter"), ops(bk, "reduce_scatter")
    ag_before, ag_after = ops(pl, "all_gather"), ops(bk, "all_gather")
    assert rs_after <= 16, (rs_before, rs_after)
    assert ag_after <= 32, (ag_before, ag_after)
    assert rs_before >= 300 and ag_before >= 600, (rs_before, ag_before)
    assert upd["big_bin_fraction"]["bucketed"] >= 0.90, upd[
        "big_bin_fraction"]

    overlap = overlap_twin_census(cfg, DP)
    oc = overlap["collective_census"]
    rs_pl = oc["by_class"]["reduce_scatter"]["by_placement"]
    ag_pl = oc["by_class"]["all_gather"]["by_placement"]
    assert oc["unattributed"] == 0
    assert rs_pl.get("in-backward-loop", {"ops": 0})["ops"] >= 1, rs_pl
    assert ag_pl.get("in-forward-loop", {"ops": 0})["ops"] >= 1, ag_pl

    rec = {
        "what": ("bucketed collective engine: coalesced update-phase "
                 "reduce-scatter/all-gather + overlap placement"),
        "arch": "vit_large",
        "dp": DP,
        "update_phase": upd,
        "reduce_scatter_ops": {"per_leaf": rs_before, "bucketed": rs_after},
        "all_gather_ops": {"per_leaf": ag_before, "bucketed": ag_after},
        "overlap_twin": overlap,
        "source": "hlo_census of the explicit schedule twins + grad of "
                  "the overlap twin (8 simulated CPU devices, "
                  "compile-only; PR-1..6 discipline)",
    }
    with open(OUT, "w") as f:
        json.dump(rec, f, indent=1)
    _log(f"wrote {OUT}")
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("update_phase", "overlap_twin")}))


if __name__ == "__main__":
    main()
