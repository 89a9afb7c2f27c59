"""Unified parallelism engine accounting: the committed evidence
behind COST_UNIFIED_r18.json (PR-1..6 discipline — compile the exact
shipped code paths, account from their compiled HLO).

The unified arm composes the PR-9 bucket layout with the PR-7 ZeRO-3
layout on a dp×fsdp mesh: the non-block zero3 gathers run as
hierarchy-aware flat buckets (one STAGED all-gather per bucket —
inter tier first, then intra — and one staged grad reduce-scatter per
bucket in the transpose) instead of one collective per leaf, and
``optim.accum_steps`` microbatches the fwd/bwd under a single bucketed
grad-RS per optimizer step. Three instruments, all on the 2×4
(data×fsdp) 8-simulated-device CPU mesh:

- **Gather-phase twins (compile-only)**: the per-leaf zero3 gather
  (one ``all_gather`` per shardable non-block leaf, one transposed
  ``psum_scatter`` per grad leaf — the ``=false`` oracle) vs the
  unified bucket schedule (``make_zero3_gather_schedule``: ONE staged
  AG/RS pair per bucket per tier, scopes ``bucket_ag_inter``/
  ``bucket_ag_intra``/``bucket_rs_intra``/``bucket_rs_inter``), both
  compiled as standalone ``jax.grad`` programs over the real
  non-block subtree so the grad sync is INSIDE the measured program.
- **In-step GSPMD census (honesty)**: the full shipped train step
  under ``build_train_setup`` with the unified arm engaged — the
  census must attribute staged gather collectives on BOTH mesh tiers
  with zero unattributed. This container's XLA:CPU lowers the
  engine's grad reduce-scatters in the pre-rewrite all-reduce+slice
  form (the slice carries the ``bucket_rs_*`` scope in its op_name);
  the schedule twin above is the committed proof of the post-rewrite
  collective set, exactly as for the bucketed update engine
  (``make_bucketed_update_schedule``).
- **Accum sweep**: the same step at ``optim.accum_steps`` ∈ {1,2,4} —
  executed (loss trajectories recorded) and censused; the pin is that
  the bucket collective count DOES NOT grow with accum_steps (the
  gathers hoist outside the microbatch scan as scan constants, so the
  scan-constant transpose sums cotangents in-loop and the staged RS
  fires once per optimizer step).

One JSON record -> COST_UNIFIED_r18.json (argv[1], default
./COST_UNIFIED_r18.json); also printed to stdout. ``--smoke`` runs
the CI-sized variant (vit_test twins, accum {1,2}, same asserts, no
JSON write unless an out path is given explicitly).

Usage: JAX_PLATFORMS=cpu python scripts/cost_unified.py [out] [--smoke]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = "--smoke" in sys.argv
_pos = [a for a in sys.argv[1:] if not a.startswith("--")]
OUT = _pos[0] if _pos else (None if SMOKE else "COST_UNIFIED_r18.json")
DATA, FSDP = 2, 4
DP = DATA * FSDP

os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += f" --xla_force_host_platform_device_count={DP}"

# the SMOL dryrun shape (tests/test_zero3.py convention)
SMOL = [
    "student.arch=vit_test", "student.patch_size=4",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2", "train.batch_size_per_device=2",
    "optim.scaling_rule=none", "train.scan_layers=true",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
    "dino.head_bottleneck_dim=16",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
    "ibot.head_bottleneck_dim=16",
    "train.OFFICIAL_EPOCH_LENGTH=4", "optim.epochs=4",
    "optim.warmup_epochs=1",
    "telemetry.async_metrics=false",
]
MESH_OVR = ["parallel.data=2", "parallel.fsdp=4"]


def _log(msg):
    print(f"[cost_unified] {msg}", file=sys.stderr, flush=True)


def _prune_streamed(tree):
    """Drop the block-stack subtrees the in-scan weight stream owns
    (the ``zero3_streamed_path`` rule) from a nested param dict."""
    if not isinstance(tree, dict):
        return tree
    out = {}
    for k, v in tree.items():
        if k == "blocks" or k.startswith("blocks_") or k == "pipeline":
            continue
        out[k] = _prune_streamed(v)
    return out


def gather_phase_twins(cfg, mesh) -> dict:
    """Per-leaf vs unified-bucket gather schedules over the real
    non-block zero3 subtree: compile ``jax.grad`` of a sum-consume of
    each arm's gathered tree, so the forward gathers AND their
    transposed grad reduce-scatters are inside the measured program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.parallel.sharding import zero3_leaf_spec
    from dinov3_tpu.train.fused_update import (
        make_zero3_bucket_plan,
        make_zero3_gather_schedule,
    )
    from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch
    from dinov3_tpu.utils import hlo_collective_census

    meta = SSLMetaArch(cfg)
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 1, seed=0).items()}
    student = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0)
    )["student"]
    subtree = _prune_streamed(student)
    plan = make_zero3_bucket_plan(subtree, mesh)

    def shardings(tree):
        def leaf(l):
            spec = zero3_leaf_spec(l.shape, (None,) * l.ndim, mesh)
            return NamedSharding(mesh, spec if spec is not None else P())
        return jax.tree.map(leaf, tree)

    in_sh = shardings(subtree)

    def loss_of(gather):
        def loss(tree):
            full = gather(tree)
            # nonlinear consume: a plain sum of a gather reassociates
            # into local-sum + all-reduce under XLA's simplifier, which
            # would erase the very gathers being censused
            return sum(jnp.sum(jnp.sin(l.astype(jnp.float32)))
                       for l in jax.tree.leaves(full))
        return loss

    censuses = {}
    for arm, bucketed in (("per_leaf", False), ("unified", True)):
        g = make_zero3_gather_schedule(plan, mesh, bucketed=bucketed)
        _log(f"compiling {arm} gather twin...")
        with mesh:
            compiled = jax.jit(
                jax.grad(loss_of(g)), in_shardings=(in_sh,),
            ).lower(subtree).compile()
        censuses[arm] = hlo_collective_census(compiled.as_text())

    n_shardable = sum(len(b.members) for b in plan.buckets)
    return {
        "n_nonblock_leaves": plan.n_leaves,
        "n_shardable_leaves": n_shardable,
        "plan": {
            "n_buckets": len(plan.buckets),
            "n_inter": plan.n_inter,
            "n_intra": plan.n_intra,
            "target_bytes": plan.target_bytes,
            "buckets": plan.stats(),
        },
        "collective_census": censuses,
    }


def engine_step(cfg_overrides, accum_steps: int, n_steps: int = 3) -> dict:
    """Build the shipped train step (unified arm), census its compiled
    HLO, and run ``n_steps`` real steps recording the loss trajectory."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup
    from dinov3_tpu.train.setup import put_batch
    from dinov3_tpu.utils import hlo_collective_census

    cfg = get_default_config()
    apply_dot_overrides(
        cfg, SMOL + MESH_OVR + [f"optim.accum_steps={accum_steps}"])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, DP * 2, seed=0).items()}
    setup = build_train_setup(cfg, batch)
    assert setup.zero3 and setup.zero3_buckets, (
        setup.zero3, setup.zero3_buckets)
    assert setup.accum_steps == accum_steps, setup.accum_steps
    dbatch = put_batch(batch, setup.batch_shardings)
    _log(f"compiling unified step (accum_steps={accum_steps})...")
    compiled = setup.step_fn.lower(
        setup.state, dbatch, setup.scalars(0), jax.random.key(0)).compile()
    census = hlo_collective_census(compiled.as_text())
    # the backend lowers the engine's staged grad RS as
    # all-reduce+dynamic-slice; the slice op_name carries the scope, so
    # count scope-stamped grad-sync evidence lines for the record
    txt = compiled.as_text()
    rs_scope_lines = sum(
        txt.count(s) for s in ("bucket_rs_intra", "bucket_rs_inter"))
    losses = []
    state = setup.state
    for i in range(n_steps):
        state, metrics = setup.step_fn(
            state, dbatch, setup.scalars(i), jax.random.key(0))
        losses.append(float(metrics["total_loss"]))
    return {
        "accum_steps": accum_steps,
        "n_buckets": len(setup.zero3_bucket_plan.buckets),
        "loss_trajectory": losses,
        "collective_census": census,
        "grad_rs_scope_lines": rs_scope_lines,
    }


def main():
    import jax

    try:
        jax.config.update("jax_num_cpu_devices", DP)
    except AttributeError:
        pass
    import math

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=DATA, fsdp=FSDP))
    set_current_mesh(mesh)

    cfg = get_default_config()
    if SMOKE:
        apply_dot_overrides(cfg, SMOL + MESH_OVR)
    else:
        # twins at the real ViT-L tree;
        # the head/embed/norm tail is what the unified arm coalesces
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "bench", os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        apply_dot_overrides(cfg, bench.build_step_overrides("vit_large", 0))
        apply_dot_overrides(cfg, MESH_OVR)

    twins = gather_phase_twins(cfg, mesh)
    pl = twins["collective_census"]["per_leaf"]
    un = twins["collective_census"]["unified"]
    nb = twins["plan"]["n_buckets"]

    def scope_ops(c, s):
        return c["by_scope"].get(s, {"ops": 0})["ops"]

    def class_ops(c, k):
        return c["by_class"].get(k, {"ops": 0})["ops"]

    # ---- acceptance pins (ISSUE 14) ----
    assert pl["unattributed"] == 0 and un["unattributed"] == 0
    # coalesced collectives on BOTH mesh tiers, one per bucket per tier
    for s in ("bucket_ag_inter", "bucket_ag_intra",
              "bucket_rs_intra", "bucket_rs_inter"):
        assert scope_ops(un, s) == nb, (s, scope_ops(un, s), nb)
    rs_perleaf = class_ops(pl, "reduce_scatter")
    rs_unified = class_ops(un, "reduce_scatter")
    assert rs_perleaf == twins["n_shardable_leaves"], (
        rs_perleaf, twins["n_shardable_leaves"])
    # one staged RS per bucket per tier <= the per-leaf count collapsed
    assert rs_unified == 2 * nb and nb < twins["n_shardable_leaves"], (
        rs_unified, nb, twins["n_shardable_leaves"])

    accum_values = (1, 2) if SMOKE else (1, 2, 4)
    sweep = [engine_step(SMOL + MESH_OVR, a) for a in accum_values]
    base = sweep[0]["collective_census"]["by_scope"]
    for rec in sweep:
        c = rec["collective_census"]
        # BOTH tiers coalesced in the shipped step, zero unattributed
        assert c["unattributed"] == 0, rec["accum_steps"]
        assert scope_ops(c, "bucket_ag_inter") > 0, rec["accum_steps"]
        assert scope_ops(c, "bucket_ag_intra") > 0, rec["accum_steps"]
        # the bucket collective count does NOT grow with accum_steps
        for s in ("bucket_ag_inter", "bucket_ag_intra"):
            assert c["by_scope"][s]["ops"] == base[s]["ops"], (
                rec["accum_steps"], s)
        # grad-sync scope evidence present in the step program
        assert rec["grad_rs_scope_lines"] > 0, rec["accum_steps"]
        assert all(math.isfinite(v) for v in rec["loss_trajectory"])

    rec = {
        "what": ("unified parallelism engine: zero3 non-block gathers "
                 "as hierarchy-aware staged buckets + microbatched "
                 "gradient accumulation with one bucketed grad-RS per "
                 "optimizer step"),
        "arch": "vit_test" if SMOKE else "vit_large",
        "mesh": {"data": DATA, "fsdp": FSDP},
        "gather_phase": twins,
        "reduce_scatter_ops": {
            "per_leaf": rs_perleaf, "unified": rs_unified,
            "n_buckets": nb},
        "all_gather_ops": {
            "per_leaf": class_ops(pl, "all_gather"),
            "unified": class_ops(un, "all_gather")},
        "accum_sweep": sweep,
        "note": (
            "gather twins are the committed collective-set proof (this "
            "container's XLA:CPU lowers the in-step engine's staged "
            "grad reduce-scatters in the pre-rewrite all-reduce+slice "
            "form; the slice op_name carries the bucket_rs_* scope — "
            "counted under grad_rs_scope_lines); the in-step census "
            "pins both-tier coalesced gathers, zero unattributed, and "
            "accum-invariant bucket collective counts"
        ),
        "source": "hlo_census of the explicit gather schedule twins + "
                  "the shipped build_train_setup step at accum_steps "
                  f"{list(accum_values)} (2x4 data x fsdp simulated "
                  "CPU mesh, steps executed)",
    }
    if OUT:
        with open(OUT, "w") as f:
            json.dump(rec, f, indent=1)
        _log(f"wrote {OUT}")
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("gather_phase", "accum_sweep")}))
    if SMOKE:
        _log("smoke OK: both-tier coalesced, zero unattributed, "
             "accum-invariant bucket collectives")


if __name__ == "__main__":
    main()
