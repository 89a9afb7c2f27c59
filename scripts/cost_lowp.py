"""Low-precision training-arm accounting: the committed evidence
behind COST_LP_r21.json (PR-1..6 discipline — compile the exact
shipped code paths, account from their compiled HLO, execute real
steps for the numerics story).

The fp8/int8 arms (train.low_precision, ops/lowp.py) quantize the
attn/mlp block matmul KERNELS per-tensor with delayed scaling and ride
the ZeRO-3 in-loop weight stream with 1-byte codes: under a lowp arm
the castable kernel leaves stay fsdp-sharded through the stream hook,
``lowp_matmul`` quantizes shard-local and gathers the code tensor
under the SAME ``zero3_stream`` named scope — identical collective
COUNTS, roughly half the streamed kernel BYTES vs the bf16 stream.
Masters, Adam moments, norms/biases and the EMA teacher storage stay
untouched; biases keep the plain bf16 stream.

Three instruments, all on the 2x4 (data x fsdp) 8-simulated-device
CPU mesh with the shipped ``build_train_setup`` step:

- **Streamed-collective census per arm**: compile the full train step
  on each arm and read the ``zero3_stream`` scope from
  ``hlo_collective_census`` — the pins are identical in-loop gather
  counts across arms, streamed bytes reduced >= 1.8x on the quantized
  arms, and zero unattributed collectives (the new ``lowp_amax`` /
  ``lowp_dequant`` scopes attribute their own collectives).
- **Executed loss trajectories per arm**: N real steps per arm from
  the same init seed; the quantized arms must track the bf16
  trajectory within the documented per-step relative tolerance, and
  the setup drift probe (``lowp_drift_probe``) must sit under
  ``train.low_precision.divergence_tol``.
- **bf16 bitwise control**: the default config (no low_precision
  overrides) and an explicit ``arm=bf16`` config (with a different
  amax_history_len, which the bf16 arm must ignore) must produce
  bitwise-identical loss trajectories — the default arm is the PR-16
  program, untouched.

Honesty caveat (docs/PERFORMANCE.md): XLA:CPU emulates fp8/int8 dot
products by upconversion, so this artifact prices BYTES and pins
NUMERICS; the speed story is the phQ on-chip A/B (not yet run).

One JSON record -> COST_LP_r21.json (argv[1], default
./COST_LP_r21.json); also printed to stdout. ``--smoke`` runs the
CI-sized variant (fewer steps, same asserts, no JSON write unless an
out path is given explicitly).

Usage: JAX_PLATFORMS=cpu python scripts/cost_lowp.py [out] [--smoke]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = "--smoke" in sys.argv
_pos = [a for a in sys.argv[1:] if not a.startswith("--")]
OUT = _pos[0] if _pos else (None if SMOKE else "COST_LP_r21.json")
DATA, FSDP = 2, 4
DP = DATA * FSDP
N_STEPS = 3 if SMOKE else 8  # 8 clears the SMOL 4-step LR warmup
# per-step relative loss-trajectory tolerance of the quantized arms vs
# bf16 (tiny vit_test shapes quantize COARSER than ViT-L: per-tensor
# scales over 32-dim kernels; the committed artifact records the
# measured max next to this bound)
LOSS_RTOL = 0.10

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
MESH_OVR = ["parallel.data=2", "parallel.fsdp=4", "parallel.zero3=true"]


def _log(msg):
    print(f"[cost_lowp] {msg}", file=sys.stderr, flush=True)


def arm_step(arm_overrides, n_steps: int, trace: bool = False) -> dict:
    """Build the shipped train step under ``arm_overrides``, census its
    compiled HLO, and run ``n_steps`` real steps recording the loss
    trajectory (same synthetic batch + rng on every arm). With
    ``trace``, re-run two steps under the profiler and join the trace
    against the compiled HLO (telemetry/anatomy.py) — the
    ``unattributed_collective_ms`` pin reads from that ledger."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup
    from dinov3_tpu.train.setup import put_batch
    from dinov3_tpu.utils import hlo_collective_census

    cfg = get_default_config()
    apply_dot_overrides(cfg, SMOL + MESH_OVR + list(arm_overrides))
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, DP * 2, seed=0).items()}
    setup = build_train_setup(cfg, batch)
    assert setup.zero3, "lowp arms ride the zero3 stream"
    dbatch = put_batch(batch, setup.batch_shardings)
    _log(f"compiling step for {list(arm_overrides) or ['<default>']}...")
    compiled = setup.step_fn.lower(
        setup.state, dbatch, setup.scalars(0), jax.random.key(0)).compile()
    txt = compiled.as_text()
    census = hlo_collective_census(txt)
    losses = []
    state = setup.state
    for i in range(n_steps):
        state, metrics = setup.step_fn(
            state, dbatch, setup.scalars(i), jax.random.key(0))
        losses.append(float(metrics["total_loss"]))
    anatomy = None
    if trace:
        import tempfile

        from dinov3_tpu.telemetry import (
            anatomy_ledger,
            find_trace_file,
            ledger_summary,
            load_trace,
        )

        tdir = tempfile.mkdtemp(prefix="cost_lp_trace_", dir="/tmp")
        n_trace = 2
        jax.profiler.start_trace(tdir)
        try:
            for i in range(n_trace):
                state, metrics = setup.step_fn(
                    state, dbatch, setup.scalars(i), jax.random.key(0))
            float(metrics["total_loss"])
        finally:
            jax.profiler.stop_trace()
        summ = ledger_summary(anatomy_ledger(
            load_trace(find_trace_file(tdir)), hlo_text=txt,
            n_steps=n_trace))
        anatomy = {
            "unattributed_collective_ms": summ["unattributed_collective_ms"],
            "collective_scopes": sorted(summ["collectives"]),
        }
    scope = census["by_scope"]
    return {
        "anatomy": anatomy,
        "arm": setup.lowp_arm,
        "drift_probe": setup.lowp_drift,
        "loss_trajectory": losses,
        "stream_scope": scope.get("zero3_stream", {"ops": 0, "bytes": 0}),
        "lowp_scopes": {k: scope[k] for k in ("lowp_amax", "lowp_dequant")
                        if k in scope},
        "unattributed": census["unattributed"],
        "collective_total": census["hlo_collective_total"],
        # engagement proof: the dequant epilogue's named scope stamped
        # into the compiled program's op_names — nonzero on the
        # quantized arms, exactly zero on the inert bf16 default
        "lowp_dequant_scope_lines": txt.count("lowp_dequant"),
        "collective_census": census,
    }


def main():
    import math

    from dinov3_tpu.configs import get_default_config
    from dinov3_tpu.configs.config import lowp_cfg

    tol = lowp_cfg(get_default_config())["divergence_tol"]

    # ---- the three precision arms + the bf16 bitwise control ----
    arms = {
        "bf16": arm_step([], N_STEPS),
        "fp8": arm_step(["train.low_precision.arm=fp8"], N_STEPS,
                        trace=True),
        "int8": arm_step(["train.low_precision.arm=int8"], N_STEPS,
                         trace=True),
    }
    # explicit arm=bf16 with a non-default ring length: the bf16 arm
    # must IGNORE the low_precision block entirely (no rings, no drift
    # probe, the PR-16 program bitwise)
    control = arm_step(
        ["train.low_precision.arm=bf16",
         "train.low_precision.amax_history_len=4"], N_STEPS)

    # ---- acceptance pins (ISSUE 17) ----
    bf16 = arms["bf16"]
    assert bf16["arm"] == "bf16" and bf16["drift_probe"] is None
    assert bf16["lowp_dequant_scope_lines"] == 0
    assert control["loss_trajectory"] == bf16["loss_trajectory"], (
        "bf16 arm is not bitwise-inert",
        control["loss_trajectory"], bf16["loss_trajectory"])
    trajectory_rel = {}
    for name in ("fp8", "int8"):
        rec = arms[name]
        assert rec["arm"] == name
        # zero unattributed collectives: every collective the lowp path
        # adds lands in a registered engine scope
        assert rec["unattributed"] == 0, (name, rec["unattributed"])
        assert bf16["unattributed"] == 0
        # quantized-matmul engagement: the dequant epilogue is IN the
        # compiled program (the has_variable guard makes a silently
        # inert arm a real failure mode — this pin catches it)
        assert rec["lowp_dequant_scope_lines"] > 0, name
        # measured-trace attribution: every collective event of the
        # quantized arm's executed steps joins an HLO op the ledger can
        # place — no unattributed collective time
        assert rec["anatomy"]["unattributed_collective_ms"] == 0, (
            name, rec["anatomy"])
        # identical streamed-gather COUNTS: the code gathers ride the
        # same zero3_stream schedule, one per kernel per use
        assert rec["stream_scope"]["ops"] == bf16["stream_scope"]["ops"], (
            name, rec["stream_scope"], bf16["stream_scope"])
        # streamed BYTES reduced >= 1.8x: 1-byte codes vs the bf16
        # stream on the kernel gathers (biases keep bf16, diluting the
        # ratio below the pure-kernel 2x)
        ratio = bf16["stream_scope"]["bytes"] / max(
            rec["stream_scope"]["bytes"], 1)
        rec["stream_bytes_ratio_vs_bf16"] = round(ratio, 4)
        assert ratio >= 1.8, (name, ratio)
        # the setup drift probe ran and sits under the guardrail gate
        assert rec["drift_probe"] is not None
        assert rec["drift_probe"]["max"] < tol, (name, rec["drift_probe"])
        # quantized loss trajectory tracks bf16 within the documented
        # per-step relative tolerance
        rel = [abs(a - b) / max(abs(b), 1e-9) for a, b in
               zip(rec["loss_trajectory"], bf16["loss_trajectory"])]
        assert all(math.isfinite(r) for r in rel)
        trajectory_rel[name] = float(f"{max(rel):.3g}")
        assert max(rel) < LOSS_RTOL, (name, rel)

    rec = {
        "what": ("fp8/int8 low-precision training arms: per-tensor "
                 "delayed-scaling block-matmul quantization riding the "
                 "zero3 weight stream with 1-byte code gathers"),
        "arch": "vit_test",
        "mesh": {"data": DATA, "fsdp": FSDP},
        "n_steps": N_STEPS,
        "loss_rtol_bound": LOSS_RTOL,
        "trajectory_rel_max": trajectory_rel,
        "divergence_tol": tol,
        "bf16_bitwise_control": True,
        "arms": {k: {kk: vv for kk, vv in v.items()
                     if kk != "collective_census"}
                 for k, v in arms.items()},
        "stream_bytes": {k: arms[k]["stream_scope"]["bytes"]
                         for k in arms},
        "stream_ops": {k: arms[k]["stream_scope"]["ops"] for k in arms},
        "note": (
            "XLA:CPU emulates fp8/int8 dot products by upconversion — "
            "this artifact prices the streamed-collective BYTES and "
            "pins the NUMERICS (trajectories, drift probe, bitwise "
            "bf16 control); the speed story is the phQ on-chip A/B "
            " (not yet run). This container's XLA:CPU also "
            "float-normalizes the bf16 stream's gathers to f32 (the "
            "phW caveat), so the int8 byte ratio here overstates the "
            "on-chip 2x while fp8 lands at ~2x either way; the "
            "identical-count pin and the >=1.8x floor are "
            "backend-independent"),
        "source": ("hlo_census + executed steps of the shipped "
                   "build_train_setup program per precision arm "
                   f"(2x4 data x fsdp simulated CPU mesh, {N_STEPS} "
                   "steps executed per arm)"),
    }
    if OUT:
        with open(OUT, "w") as f:
            json.dump(rec, f, indent=1)
        _log(f"wrote {OUT}")
    print(json.dumps({k: v for k, v in rec.items() if k != "arms"}))
    if SMOKE:
        _log("smoke OK: equal stream counts, >=1.8x streamed-byte "
             "reduction, zero unattributed, trajectories in tolerance, "
             "bf16 arm bitwise-inert")


if __name__ == "__main__":
    main()
