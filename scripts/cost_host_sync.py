"""Host-sync + memory accounting for the async telemetry engine
(telemetry/): the committed evidence behind COST_HSYNC_r11.json and
MEM_r11.json.

Methodology (the PR-1..5 discipline — measure the exact shipped code
paths, stated precisely because this is the committed evidence in
docs/PERFORMANCE.md):

- **Host-sync A/B (executed)**: the REAL hot loop
  (``train/train.py do_train`` via ``train_main``) runs twice on the
  8-simulated-device CPU mesh with a tiny vit_test program — once on
  the default async arm (metrics -> donated on-device ring, one flush
  per ``telemetry.flush_every`` steps) and once on the per-step-fetch
  oracle (``telemetry.async_metrics=false``). Every blocking
  device->host fetch either arm issues goes through the ONE counted
  funnel (telemetry/host_sync.py blocking_fetch), so
  ``fetches_per_step`` and ``host_blocked_ms_per_step`` are read
  straight off the instrument, not estimated. The claim under test:
  the async hot loop issues <= 1 blocking fetch per flush_every steps
  where the oracle issues 1 per step. Host-blocked ms is
  program-dependent (a tiny model on CPU); the FETCH COUNT is the
  structural, program-independent result. Both arms' span JSONL is
  summarized per phase (mean dispatch/data-wait/flush ms) as the
  phase-attribution record.
- **Memory accounting (ViT-L dp=8 dryrun, compile-only)**: the full
  telemetry step is built ABSTRACTLY (``build_train_setup(...,
  init_state=False)``) on 8 simulated devices — materializing 8
  replicated ViT-L trees in host RAM is exactly what the accounting
  exists to avoid — and per-device bytes-in-use are computed from the
  shardings the partitioner actually assigned (replicated leaves count
  fully per device; the ZeRO-1 sharded adam moments count 1/dp).
  ``compiled.memory_analysis()`` adds XLA's own temp/argument/output
  sizes where the backend exposes them (recorded with a source note
  either way); runtime ``device.memory_stats()`` samples from the
  executed tiny run ride along under ``runtime_samples`` (on this
  container's CPU backend they fall back to live-array walking,
  honestly labelled).

Writes MEM_r11.json (second argv, default ./MEM_r11.json) and prints
the COST_HSYNC record as one JSON line on stdout -> commit as
COST_HSYNC_r11.json.

Usage: JAX_PLATFORMS=cpu python scripts/cost_host_sync.py \
           [steps] [flush_every] [mem_out]   (defaults: 16 8 MEM_r11.json)
"""

from __future__ import annotations

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DP = 8
# the simulated device count must be pinned before jax initializes
os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += f" --xla_force_host_platform_device_count={DP}"

STEPS = int(sys.argv[1]) if len(sys.argv) > 1 else 16
FLUSH_EVERY = int(sys.argv[2]) if len(sys.argv) > 2 else 8
MEM_OUT = sys.argv[3] if len(sys.argv) > 3 else "MEM_r11.json"

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


def _span_summary(spans_path: str) -> dict:
    """Per-phase {count, mean_ms} over one run's span JSONL."""
    agg: dict = {}
    with open(spans_path) as f:
        for line in f:
            rec = json.loads(line)
            if "dur_ms" not in rec:
                continue
            ent = agg.setdefault(rec["name"], {"count": 0, "total_ms": 0.0})
            ent["count"] += 1
            ent["total_ms"] += rec["dur_ms"]
    return {
        name: {"count": ent["count"],
               "mean_ms": round(ent["total_ms"] / ent["count"], 4)}
        for name, ent in agg.items()
    }


def _memory_samples(spans_path: str) -> list:
    with open(spans_path) as f:
        return [json.loads(line) for line in f
                if '"name": "memory"' in line]


def run_hot_loop(async_metrics: bool, out_dir: str) -> dict:
    """One do_train run through the real trainer entry; returns the
    funnel's fetch/blocked-time stats over exactly the loop's fetches."""
    from dinov3_tpu.telemetry import host_sync_stats
    from dinov3_tpu.train.train import main as train_main

    host_sync_stats(reset=True)
    result = train_main([
        "--output-dir", out_dir, "--no-resume",
        "--max-iterations", str(STEPS),
    ] + TINY + [
        f"train.OFFICIAL_EPOCH_LENGTH={STEPS}",
        f"telemetry.flush_every={FLUSH_EVERY}",
        f"telemetry.async_metrics={'auto' if async_metrics else 'false'}",
    ])
    stats = host_sync_stats(reset=True)
    spans = os.path.join(out_dir, "telemetry", "spans.jsonl")
    return {
        "steps": STEPS,
        "flush_every": FLUSH_EVERY,
        "blocking_fetches": stats["fetches"],
        "fetches_per_step": round(stats["fetches"] / STEPS, 4),
        "host_blocked_ms": stats["blocked_ms"],
        "host_blocked_ms_per_step": round(stats["blocked_ms"] / STEPS, 4),
        "final_loss": result["final_loss"],
        "span_summary": _span_summary(spans),
        "_memory_samples": _memory_samples(spans),
    }


def measure_vitl_memory() -> dict:
    """ViT-L dp=8 compile-only memory accounting (see module doc)."""
    import importlib.util

    import jax

    _spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(bench)

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.telemetry.ring import make_ring
    from dinov3_tpu.train import build_train_setup

    cfg = get_default_config()
    apply_dot_overrides(cfg, bench.build_step_overrides("vit_large", 0))
    B = 12 * DP
    batch_np = make_synthetic_batch(cfg, B, seed=0)
    # the setup traces need a subscriptable example (host numpy is fine
    # and never reaches a device); the lowering below uses the abstract
    # ShapeDtypeStruct form so no global batch is ever materialized
    # on the simulated mesh
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch_np.items()}
    setup = build_train_setup(cfg, batch_np, init_state=False)
    plan = setup.telemetry()
    ring_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        make_ring(len(plan.metric_names), plan.ring_len))

    def tree_bytes_per_device(tree, shardings) -> int:
        total = 0
        for leaf, sh in zip(jax.tree.leaves(tree),
                            jax.tree.leaves(shardings)):
            shard_shape = sh.shard_shape(leaf.shape)
            total += math.prod(shard_shape) * leaf.dtype.itemsize
        return total

    state_parts = {
        "params_student": tree_bytes_per_device(
            setup.state.params["student"],
            setup.state_shardings.params["student"]),
        "params_teacher": tree_bytes_per_device(
            setup.state.params["teacher"],
            setup.state_shardings.params["teacher"]),
        "opt_state": tree_bytes_per_device(
            setup.state.opt_state, setup.state_shardings.opt_state),
        "center_state": tree_bytes_per_device(
            setup.state.center_state, setup.state_shardings.center_state),
        "telemetry_ring": tree_bytes_per_device(
            ring_abs, plan.ring_shardings),
    }
    batch_bytes = tree_bytes_per_device(
        batch, setup.batch_shardings)
    state_bytes = sum(state_parts.values())

    scalars = {
        "teacher_temp": jax.ShapeDtypeStruct((), jax.numpy.float32),
        "momentum": jax.ShapeDtypeStruct((), jax.numpy.float32),
    }
    rng = jax.random.key(0)
    print(f"[cost_host_sync] compiling ViT-L dp={DP} telemetry step "
          "(compile-only dryrun)...", file=sys.stderr, flush=True)
    compiled = plan.step_fn.lower(
        setup.state, ring_abs, batch, scalars, rng).compile()
    mem_an = None
    source = "shardings"
    try:
        an = compiled.memory_analysis()
        if an is not None:
            mem_an = {
                k: int(getattr(an, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes",
                          "generated_code_size_in_bytes")
                if hasattr(an, k)
            } or None
            if mem_an:
                source = "shardings+memory_analysis"
    except Exception as e:  # noqa: BLE001 - backend without the analysis
        mem_an = {"error": str(e)[:200]}
    temp = (mem_an or {}).get("temp_size_in_bytes")
    return {
        "arch": "vit_large", "dp": DP, "per_chip_batch": 12,
        "bytes_in_use_per_device": {
            **state_parts,
            "batch": batch_bytes,
            "state_total": state_bytes,
            "total": state_bytes + batch_bytes,
        },
        "peak_bytes_per_device": (
            None if temp is None
            else state_bytes + batch_bytes + int(temp)),
        "xla_memory_analysis": mem_an,
        "source": source,
        "note": (
            "compile-only dryrun on 8 simulated CPU devices: "
            "bytes-in-use from the NamedShardings the partitioner "
            "assigned (replicated leaves full-size per device, ZeRO-1 "
            "adam moments 1/dp); peak adds XLA's temp_size when the "
            "backend reports memory_analysis, else null. XLA:CPU's "
            "temp_size is an UNSCHEDULED upper bound (the TPU memory "
            "scheduler reuses buffers aggressively), so treat peak as "
            "the compile-level bound and re-measure on-chip via "
            "device.memory_stats() (the phO bench records embed it). "
            "Runtime sampling (telemetry/memory.py) is the on-chip "
            "instrument; its CPU fallback samples from the executed "
            "vit_test run are under runtime_samples. The bytes-in-use "
            "split is the ZeRO-3 before-picture: student+teacher fp32 "
            "masters fully replicated (2 x 1.40 GB/device at ViT-L), "
            "adam moments already 1/dp (ROADMAP item 1 shards the "
            "masters next)."
        ),
    }


def main():
    import tempfile

    import jax

    try:
        jax.config.update("jax_num_cpu_devices", DP)
    except AttributeError:
        pass  # XLA_FLAGS set above covers old jaxlibs

    with tempfile.TemporaryDirectory() as td:
        ring_arm = run_hot_loop(True, os.path.join(td, "ring"))
        oracle_arm = run_hot_loop(False, os.path.join(td, "oracle"))
    runtime_samples = ring_arm.pop("_memory_samples")
    oracle_arm.pop("_memory_samples")

    mem = measure_vitl_memory()
    mem["runtime_samples"] = {
        "program": "vit_test dp=8 executed hot loop (async arm)",
        "samples": runtime_samples,
    }
    with open(MEM_OUT, "w") as f:
        json.dump(mem, f, indent=1)
    print(f"[cost_host_sync] wrote {MEM_OUT}", file=sys.stderr)

    rec = {
        "program": "vit_test dp=8, real do_train hot loop, synthetic data",
        "steps_per_flush_claim": (
            "async arm issues <= 1 blocking device->host fetch per "
            "telemetry.flush_every steps; oracle issues 1 per step"),
        "ring": ring_arm,
        "oracle": oracle_arm,
        "fetch_reduction": (
            f"{oracle_arm['blocking_fetches']} -> "
            f"{ring_arm['blocking_fetches']} blocking fetches over "
            f"{STEPS} steps"),
        "mem_artifact": MEM_OUT,
    }
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
