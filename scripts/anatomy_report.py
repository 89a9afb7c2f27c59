"""Step-anatomy artifact: the committed evidence behind ANATOMY_r17.json
— MEASURED per-scope device time with the exposed/overlapped collective
split, for the training arms, on the 8-simulated-device CPU mesh
(ANATOMY_r17.json is a static record: it also holds a "flat" arm, a
per-leaf sharded engine that no longer exists and that this script does
not make).

Where the COST_* artifacts census the compiled HLO (static placement:
"the RS sits inside the backward while-loop"), this one EXECUTES each
arm's program under the jax.profiler and parses the trace through the
shared anatomy plane (telemetry/trace.py + telemetry/anatomy.py):
device time by op category, collective time attributed to named scopes
via the compiled HLO's op_name metadata, measured exposed/overlapped
collective ms per scope, and the measured backward interval — the
dynamic twin of the ``by_placement`` census.

Programs (single-core honesty — this container has ONE CPU core, so a
full ViT-L train step cannot execute in budget; each arm is measured on
the executable program where the arms actually DIFFER, the same twin
discipline as COST_BUCKET_r13 / COST_Z3_r12, but executed, not just
compiled):

- **replicated**: ViT-L dp=8 update phase — stacked per-replica grads
  summed (the implicit grad all-reduce) + the fused replicated update.
- **bucketed (PR 9)**: ``make_bucketed_update_schedule`` — the
  update through ~128 MB buckets (bucket_pack RS / bucket_unpack AG),
  PLUS the executed overlap twin (``jax.grad`` of
  ``bucketed_stream_scan`` at truncated depth): its ledger must show
  bucket-scoped reduce-scatter time INSIDE the measured backward
  interval — consistent with COST_BUCKET_r13.json's static
  ``in-backward-loop`` placement.
- **zero3 (PR 7)**: the executed double-buffered weight-stream twin
  (``jax.grad`` of ``streamed_block_scan``, zero3-sharded stack):
  zero3_prefetch gathers in the measured forward, their transposed
  reduce-scatters in the measured backward.

Plus a tiny end-to-end dryrun (vit_test dp=8) through the REAL trainer
with ``--profile-steps``, exercising the train-loop anatomy wiring
(anatomy.json + "anatomy" span), and the fleet report over its span
stream.

CPU-harness caveat (docs/OBSERVABILITY.md): XLA:CPU runs each simulated
device's thunks sequentially on one worker thread, so measured overlap
fractions here are structural LOWER bounds — the committed numbers pin
attribution, exposure ceilings, and backward-interval placement; the
TPU overlap fractions are not measured yet.

Usage: JAX_PLATFORMS=cpu python scripts/anatomy_report.py [out] [--smoke]
--smoke: dryrun + schema/attribution checks only (the CI tier-1 step).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DP = 8
os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += f" --xla_force_host_platform_device_count={DP}"

OUT = sys.argv[1] if len(sys.argv) > 1 and not sys.argv[1].startswith(
    "--") else "ANATOMY_r17.json"
SMOKE = "--smoke" in sys.argv

TRACED_STEPS = 2
# truncated stream-twin geometry (single-core budget): ViT-L width,
# fewer blocks/tokens — the comm *structure* (scopes, loop placement,
# double buffering) is depth-independent
TWIN_BLOCKS = 4
TWIN_TOKENS = 64
N_BUCKETS = 4

TINY = [
    "student.arch=vit_test", "student.patch_size=4",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2", "train.batch_size_per_device=2",
    "optim.scaling_rule=none", "data.backend=synthetic",
    "optim.epochs=1", "optim.warmup_epochs=0",
    "checkpointing.period=1000000",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
    "dino.head_bottleneck_dim=16",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
    "ibot.head_bottleneck_dim=16",
]


def _log(msg):
    print(f"[anatomy_report] {msg}", file=sys.stderr, flush=True)


def _bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _traced_summary(run_step, compiled, tag: str) -> dict:
    """Execute one warmup + TRACED_STEPS profiled steps of an arm's
    program and parse the window through the shared anatomy plane.
    ``run_step()`` executes ONE step and blocks on its outputs (the
    inter-step host sync is what gives the window its per-step gap
    structure — the same fetch-synced discipline bench.py uses)."""
    import jax

    from dinov3_tpu.telemetry import anatomy_ledger, ledger_summary
    from dinov3_tpu.telemetry.trace import find_trace_file, load_trace

    run_step()  # warmup: ensure no compile lands inside the window
    tdir = tempfile.mkdtemp(
        prefix=f"anatomy_{tag.replace('/', '_')}_", dir="/tmp")
    t0 = time.perf_counter()
    jax.profiler.start_trace(tdir)
    try:
        for _ in range(TRACED_STEPS):
            run_step()
    finally:
        jax.profiler.stop_trace()
    _log(f"{tag}: traced {TRACED_STEPS} steps in "
         f"{time.perf_counter() - t0:.1f}s")
    ledger = anatomy_ledger(
        load_trace(find_trace_file(tdir)),
        hlo_text=compiled.as_text(), n_steps=TRACED_STEPS)
    summary = ledger_summary(ledger)
    shutil.rmtree(tdir, ignore_errors=True)
    # ---- attribution pins, per arm ----
    assert summary["hlo_joined"], tag
    # >= DP, not ==: beyond the 8 tf_XLATfrtCpuClient device threads,
    # XLA:CPU's tf_XLAEigen intra-op pool carries op-annotated events on
    # larger programs (each pool thread spans every step, so per-timeline
    # step windows and attribution stay correct).
    assert summary["n_timelines"] >= DP, (tag, summary["n_timelines"])
    assert summary["unattributed_collective_ms"] == 0.0, (
        tag, summary["unattributed_collective_ms"])
    assert summary["collectives"], f"{tag}: no collective time measured"
    return summary


def _materialize(tree, shardings):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda l, s: jax.device_put(jnp.zeros(l.shape, l.dtype), s),
        tree, shardings)


def update_phase_arms(cfg) -> dict:
    """The two update-phase arms (replicated / bucketed) over the real
    ViT-L tree, executed: the bucketed schedule twin from stacked
    per-replica partial grads, and the replicated fused-update arm."""
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
        make_bucketed_update_schedule,
        make_fused_update,
    )
    from dinov3_tpu.train.fused_update import bucketed_adam_zeros
    from dinov3_tpu.train.optimizer import ScheduledAdamWState
    from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch

    mesh = build_mesh(MeshSpec(data=DP))
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
    plan = make_bucket_plan(student, DP, is_last_layer=isll)
    kw = dict(b1=cfg.optim.adamw_beta1, b2=cfg.optim.adamw_beta2,
              clip_grad=cfg.optim.clip_grad, ema=True)

    rep = NamedSharding(mesh, P())
    axes = tuple(a for a in UPDATE_SHARD_AXES if a in mesh.shape)
    stacks = NamedSharding(mesh, P(axes))
    gstack_abs = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((DP,) + l.shape, l.dtype), student)
    momentum = jnp.float32(0.999)
    rep_tree = jax.tree.map(lambda _: rep, student)
    stack_tree = jax.tree.map(lambda _: stacks, gstack_abs)

    def opt_sharding(opt):
        return ScheduledAdamWState(
            rep, optax.ScaleByAdamState(
                rep,
                jax.tree.map(lambda _: stacks, opt.adam.mu),
                jax.tree.map(lambda _: stacks, opt.adam.nu)))

    def opt_state_of(zeros_fn):
        return jax.eval_shape(
            lambda: ScheduledAdamWState(
                jnp.zeros((), jnp.int32),
                optax.ScaleByAdamState(
                    jnp.zeros((), jnp.int32),
                    nn.meta.unbox(zeros_fn()),
                    nn.meta.unbox(zeros_fn()))))

    fused = make_fused_update(schedules, lm, wm, isll, **kw)
    bucketed = make_bucketed_update_schedule(schedules, lm, wm, isll, mesh,
                                             plan, **kw)

    def repl_arm(gs, p, t, s, m):
        # the replicated arm's grad sync: per-replica partials summed
        # over the stacked (data-sharded) axis = the implicit all-reduce
        g = jax.tree.map(lambda x: jnp.sum(x, 0), gs)
        return fused(g, p, t, s, m)[:3]

    def bucketed_arm(gs, p, t, s, m):
        return bucketed(gs, p, t, s, m)[:3]

    opt_rep = opt_state_of(lambda: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), student))
    opt_rep_sh = ScheduledAdamWState(
        rep, optax.ScaleByAdamState(rep, rep_tree, rep_tree))
    opt_bk = opt_state_of(lambda: bucketed_adam_zeros(plan))

    arms = {
        "replicated": (repl_arm, opt_rep, opt_rep_sh),
        "bucketed": (bucketed_arm, opt_bk, opt_sharding(opt_bk)),
    }
    out = {}
    gstack = _materialize(gstack_abs, stack_tree)
    for name, (fn, opt_abs, opt_sh) in arms.items():
        _log(f"compiling {name} update-phase arm (ViT-L dp={DP})...")
        with mesh:
            compiled = jax.jit(
                fn,
                in_shardings=(stack_tree, rep_tree, rep_tree, opt_sh, rep),
                out_shardings=(rep_tree, rep_tree, opt_sh),
                donate_argnums=(1, 2, 3),
            ).lower(gstack_abs, student, student, opt_abs,
                    jax.ShapeDtypeStruct((), jnp.float32)).compile()
        state = {
            "p": _materialize(student, rep_tree),
            "t": _materialize(student, rep_tree),
            "o": _materialize(opt_abs, opt_sh),
        }

        def run_step(state=state, compiled=compiled):
            p, t, o = compiled(gstack, state["p"], state["t"], state["o"],
                               momentum)
            jax.block_until_ready(p)
            state.update(p=p, t=t, o=o)

        summary = _traced_summary(run_step, compiled, f"update/{name}")
        out[name] = {
            "program": f"ViT-L dp={DP} update-phase twin, executed "
                       f"({TRACED_STEPS} fetch-synced traced steps)",
            "anatomy": summary,
        }
        del state, compiled
    del gstack
    return out


def stream_twin(cfg, which: str) -> dict:
    """Executed weight-stream twin at truncated ViT-L block geometry:
    ``jax.grad`` of the zero3 double-buffered scan (zero3 arm) or of the
    bucket-sharded scan (bucketed arm's overlap program)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.models import build_backbone
    from dinov3_tpu.models.streaming import (
        bucketed_stream_scan,
        cast_stream_leaves,
        make_block_apply,
        pack_stream_buckets,
        streamed_block_scan,
    )
    from dinov3_tpu.ops.block import SelfAttentionBlock
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
    from dinov3_tpu.parallel.sharding import UPDATE_SHARD_AXES, zero3_leaf_spec

    mesh = build_mesh(MeshSpec(data=DP))
    set_current_mesh(mesh)
    model = build_backbone(cfg)
    kwargs = model._block_kwargs()
    kwargs["drop_path_rate"] = 0.0
    L, D, N = TWIN_BLOCKS, model.embed_dim, TWIN_TOKENS

    block = SelfAttentionBlock(**kwargs)
    one_block = nn.meta.unbox(jax.eval_shape(
        lambda r: block.init(r, jnp.zeros((1, N, D), jnp.bfloat16)),
        jax.random.key(0))["params"])
    stack_abs = cast_stream_leaves(jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((L,) + tuple(p.shape), p.dtype),
        one_block), jnp.bfloat16)
    x_abs = jax.ShapeDtypeStruct((2 * DP, N, D), jnp.bfloat16)
    axes = tuple(a for a in UPDATE_SHARD_AXES if a in mesh.shape)
    x_sh = NamedSharding(mesh, P("data"))

    if which == "zero3":
        apply_fn = make_block_apply(kwargs, rope=None)

        def loss(stack_params, x):
            y = streamed_block_scan(apply_fn, stack_params, x, L, mesh)
            return jnp.sum(y.astype(jnp.float32))

        def stack_sharding(p):
            spec = zero3_leaf_spec(p.shape, ("layers",) + (None,) *
                                   (len(p.shape) - 1), mesh)
            return NamedSharding(mesh, spec if spec is not None else P())

        args_abs = (stack_abs, x_abs)
        in_sh = (jax.tree.map(stack_sharding, stack_abs), x_sh)
    else:  # bucketed overlap twin
        shards_abs = jax.eval_shape(
            lambda s: pack_stream_buckets(s, N_BUCKETS, DP), stack_abs)

        def loss(bucket_shards, x):
            y = bucketed_stream_scan(bucket_shards, x, mesh=mesh)
            return jnp.sum(y.astype(jnp.float32))

        args_abs = (shards_abs, x_abs)
        # x rides data-sharded (unlike the census-only twin of
        # tests/test_buckets.py, this one EXECUTES, so x must match).
        in_sh = (NamedSharding(mesh, P(None, axes)), x_sh)

    _log(f"compiling executed {which} stream twin "
         f"(L={L}, N={N}, D={D})...")
    with mesh:
        compiled = jax.jit(jax.grad(loss), in_shardings=in_sh).lower(
            *args_abs).compile()
    args = (_materialize(args_abs[0], in_sh[0]),
            _materialize(x_abs, in_sh[1]))

    def run_step():
        import jax as _jax

        _jax.block_until_ready(compiled(*args))

    summary = _traced_summary(run_step, compiled, f"stream/{which}")
    return {
        "program": f"executed grad of the {which} stream twin "
                   f"(L={L} blocks, N={N} tokens, D={D} — ViT-L width, "
                   f"truncated depth for the single-core budget)",
        "anatomy": summary,
    }


def tiny_dryrun(steps: int = 8, window=(4, 6)) -> dict:
    """End-to-end wiring proof through the REAL trainer: vit_test dp=8,
    --profile-steps window -> the train loop's own emit_step_anatomy
    writes anatomy.json and the "anatomy" span; the fleet report reads
    the run's span stream."""
    from dinov3_tpu.telemetry import fleet_report
    from dinov3_tpu.train.train import main as train_main

    out_dir = tempfile.mkdtemp(prefix="anatomy_dryrun_", dir="/tmp")
    t0 = time.perf_counter()
    train_main([
        "--output-dir", out_dir, "--no-resume",
        "--max-iterations", str(steps),
        "--profile-steps", f"{window[0]},{window[1]}",
    ] + TINY + [f"train.OFFICIAL_EPOCH_LENGTH={steps}"])
    _log(f"dryrun: {steps} steps in {time.perf_counter() - t0:.1f}s")

    ledger_path = os.path.join(out_dir, "trace", "anatomy.json")
    assert os.path.exists(ledger_path), (
        "train-loop anatomy wiring did not write anatomy.json")
    with open(ledger_path) as f:
        ledger = json.load(f)
    assert ledger["schema"] == "anatomy/v1", ledger["schema"]
    assert ledger["n_steps"] == window[1] - window[0] + 1, ledger["n_steps"]
    assert ledger["hlo_joined"] is True
    assert ledger["unattributed_collective_ms"] == 0.0, (
        ledger["unattributed_collective_ms"])

    spans_path = os.path.join(out_dir, "telemetry", "spans.jsonl")
    anatomy_spans = []
    with open(spans_path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("name") == "anatomy":
                anatomy_spans.append(rec)
    assert len(anatomy_spans) == 1, (
        f"expected exactly one anatomy span, got {len(anatomy_spans)}")
    summary = anatomy_spans[0]["summary"]

    fleet = fleet_report(out_dir, anatomy=summary)
    assert fleet["n_hosts"] == 1 and "rank0" in fleet["hosts"]
    assert fleet["hosts"]["rank0"]["straggler_z"] == 0.0  # single host
    assert fleet["verdict"] in ("input-bound", "comm-bound",
                                "compute-bound")
    shutil.rmtree(out_dir, ignore_errors=True)
    return {
        "program": f"vit_test dp={DP} real do_train, --profile-steps "
                   f"{window[0]},{window[1]} (the train-loop wiring path)",
        "anatomy": summary,
        "fleet": fleet,
    }


def main():
    import jax

    from dinov3_tpu.utils import configure_compile_cache

    configure_compile_cache()
    from dinov3_tpu.telemetry.anatomy import round_floats

    dryrun = tiny_dryrun()
    if SMOKE:
        print(json.dumps(round_floats({
            "smoke": "ok",
            "verdict": dryrun["fleet"]["verdict"],
            "n_steps": dryrun["anatomy"]["n_steps"],
            "unattributed_collective_ms":
                dryrun["anatomy"]["unattributed_collective_ms"],
            "scopes": sorted(dryrun["anatomy"]["collectives"]),
        })))
        return

    bench = _bench()
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config

    cfg = get_default_config()
    apply_dot_overrides(cfg, bench.build_step_overrides("vit_large", 0))

    arms = update_phase_arms(cfg)
    arms["zero3"] = stream_twin(cfg, "zero3")
    overlap = stream_twin(cfg, "bucketed")
    arms["bucketed"]["overlap_twin"] = overlap

    # ---- cross-arm acceptance pins (ISSUE 13) ----
    # bucketed update arm: collective time lands in the bucket_* scopes
    assert any(s.startswith("bucket")
               for s in arms["bucketed"]["anatomy"]["collectives"]), (
        arms["bucketed"]["anatomy"]["collectives"])
    # zero3 stream twin: the double-buffered gathers are
    # zero3_prefetch-scoped, and backward-time collective work exists
    z3 = arms["zero3"]["anatomy"]["collectives"]
    assert any(s.startswith("zero3") for s in z3), z3
    assert any(c["inside_backward_frac"] > 0
               for c in z3.values()), z3
    # bucketed overlap twin: measured bucket-scoped reduce-scatter time
    # INSIDE the backward interval — the dynamic twin of
    # COST_BUCKET_r13.json by_placement.in-backward-loop >= 1
    ov = overlap["anatomy"]["collectives"]
    rs_in_bwd = sum(c["ms_per_step"] * c["inside_backward_frac"]
                    for s, c in ov.items() if s.startswith("bucket"))
    assert rs_in_bwd > 0, ov
    with open("COST_BUCKET_r13.json") as f:
        r13 = json.load(f)
    static_bwd = r13["overlap_twin"]["collective_census"][
        "by_placement"].get("in-backward-loop", {"ops": 0})["ops"]
    assert static_bwd >= 1, static_bwd

    rec = round_floats({
        "what": ("step-anatomy ledger: measured per-scope device time, "
                 "exposed/overlapped collective ms, and backward-interval "
                 "placement for the training arms"),
        "arch": "vit_large",
        "dp": DP,
        "traced_steps": TRACED_STEPS,
        "arms": arms,
        "dryrun": dryrun,
        "consistency": {
            "bucketed_rs_inside_backward_ms": rs_in_bwd,
            "cost_bucket_r13_in_backward_loop_ops": static_bwd,
            "note": ("measured bucket-scoped collective time inside the "
                     "measured backward interval > 0, consistent with "
                     "the static census placing >= 1 reduce-scatter "
                     "in-backward-loop (COST_BUCKET_r13.json)"),
        },
        "cpu_harness_caveat": (
            "XLA:CPU executes each simulated device's thunks "
            "sequentially on one worker thread: overlap fractions are "
            "structural lower bounds, exposed-comm is the conservative "
            "ceiling. Attribution, scope split, and backward-interval "
            "placement are exact. TPU overlap: not measured yet."
        ),
        "source": ("executed arm twins + tiny real-trainer dryrun under "
                   "jax.profiler, parsed by telemetry/anatomy.py "
                   f"({DP} simulated CPU devices)"),
    })
    with open(OUT, "w") as f:
        json.dump(rec, f, indent=1)
    _log(f"wrote {OUT}")
    print(json.dumps({
        "arms": {k: {"step_wall_ms": v["anatomy"]["step_wall_ms"]["mean"],
                     "exposed_comm_frac": v["anatomy"]["exposed_comm_frac"],
                     "scopes": sorted(v["anatomy"]["collectives"])}
                 for k, v in arms.items()},
        "dryrun_verdict": dryrun["fleet"]["verdict"],
    }))


if __name__ == "__main__":
    main()
