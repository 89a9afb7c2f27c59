"""High-res gram-anchoring stage on sequence-sharded attention: the
committed evidence behind COST_HIRES_r19.json (PR-1..6 discipline —
compile the exact shipped code paths, account from their compiled HLO).

The paper's second training phase (512-768px multi-crop with gram
anchoring) is the regime ring attention was built for: at 768px the
2309-token global crops pad the [N, N] softmax state past what a
per-device dense pass wants to hold, and sequence parallelism shards
the K/V rotation O(N/s) per device. Two instruments, both on the
8-simulated-device CPU mesh:

- **Executed gram-stage arms (vit_test)**: the full shipped train step
  (``build_train_setup``) with the gram loss + gram-teacher refresh
  cadence on, at the same 16-row GLOBAL batch on three meshes —
  ``parallel.seq=1`` (dp=8, the oracle), dp=4 x seq=2, and
  dp=2 x fsdp=2 x seq=2. ``ops.attention.RING_MIN_SEQ`` is set to 1
  for the run so the tiny 17-token passes actually ring (the per-pass
  dispatch would otherwise keep them dense, which is the SHIPPED
  floor — the override is the test hook, not the recommendation).
  Pins: every arm's census has
  zero unattributed collectives; the seq arms attribute
  ``ring_permute``-scoped collectives; losses stay finite through a
  gram refresh; and the seq arms' loss trajectories match the seq=1
  oracle within tolerance (same global batch, same init, same rng).
- **ViT-L attention-memory twins (compile-only + one executed parity
  point)**: standalone fwd+bwd attention programs at ViT-L geometry
  (16 heads x 64 head_dim) and the real high-res token counts
  (512px -> 1029, 768px -> 2309), dense on dp=8 vs ring on
  dp=4 x seq=2, one row per data shard either way. The pin is the
  tentpole's memory claim: per-device temp bytes at seq=2 measurably
  below seq=1 (O(N/s) K/V rotation vs the dense [N, N] state), with
  the ring program's ppermutes scope-attributed and zero
  unattributed. A single executed point (N=1029, fp32) records
  ring-vs-dense max|diff| with and without segment ids.

CPU-harness honesty: nothing here times anything — XLA:CPU wall times
would say nothing about TPU. The committed numbers are structural
(collective censuses, compiled per-device memory stats, loss
trajectories); the on-chip A/B has not been run on the chip.

One JSON record -> COST_HIRES_r19.json (argv[1], default
./COST_HIRES_r19.json); also printed to stdout. ``--smoke`` runs the
executed vit_test arms only (same asserts, no JSON write unless an out
path is given explicitly).

Usage: JAX_PLATFORMS=cpu python scripts/cost_hires.py [out] [--smoke]
"""

from __future__ import annotations

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = "--smoke" in sys.argv
_pos = [a for a in sys.argv[1:] if not a.startswith("--")]
OUT = _pos[0] if _pos else (None if SMOKE else "COST_HIRES_r19.json")
DP = 8
GLOBAL_ROWS = 16

os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += f" --xla_force_host_platform_device_count={DP}"

# the SMOL dryrun shape (tests/test_zero3.py convention) + the gram
# stage of tests/test_gram_and_hrft.py; drop-path off so the three
# mesh arms consume identical randomness for the equivalence pin
SMOL = [
    "student.arch=vit_test", "student.patch_size=4",
    "student.drop_path_rate=0.0",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2",
    # scan_layers stays FALSE across every arm: the seq arms would be
    # force-unscanned anyway (setup.py's nn.scan x ring-custom_vjp
    # guard), and the oracle must share the seq arms' param-tree shape
    # (scanned stacks fold init RNG differently) for the loss
    # equivalence pin to compare like with like
    "optim.scaling_rule=none", "train.scan_layers=false",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
    "dino.head_bottleneck_dim=16",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
    "ibot.head_bottleneck_dim=16",
    "train.OFFICIAL_EPOCH_LENGTH=4", "optim.epochs=4",
    "optim.warmup_epochs=1",
    "telemetry.async_metrics=false",
]
GRAM = [
    "gram.use_loss=true", "gram.ema_teacher=false",
    "gram.rep_update=true", "gram.update_frequency=2",
    "gram.it_first_update=2", "gram.max_updates=2",
    "crops.gram_teacher_crops_size=16",
]
# same 16-row global batch on every mesh: batch_size_per_device scales
# with the arm's data-parallel world so rows x world stays fixed
ARMS = [
    ("seq1_oracle", ["parallel.data=8",
                     "train.batch_size_per_device=2"]),
    ("dp_seq", ["parallel.data=4", "parallel.seq=2",
                "train.batch_size_per_device=4"]),
    ("dp_fsdp_seq", ["parallel.data=2", "parallel.fsdp=2",
                     "parallel.seq=2",
                     "train.batch_size_per_device=4"]),
]
N_STEPS = 3

# ViT-L geometry at the high-res token counts (1 CLS + 4 registers +
# (px/16)^2 patches — the vitl16 recipes)
VITL_HEADS, VITL_HEAD_DIM = 16, 64
VITL_CASES = [(512, 1029), (768, 2309)]


def _log(msg):
    print(f"[cost_hires] {msg}", file=sys.stderr, flush=True)


def scope_ops(census, scope):
    return census["by_scope"].get(scope, {"ops": 0})["ops"]


def gram_stage_arm(name, overrides) -> dict:
    """Build the shipped gram-stage step on one mesh, census its
    compiled HLO, execute N_STEPS steps with the gram-refresh cadence
    applied between them, and return the record."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.train import build_train_setup, put_batch
    from dinov3_tpu.train.gram_refresh import refresh_gram, should_refresh_gram
    from dinov3_tpu.utils import hlo_collective_census

    cfg = get_default_config()
    apply_dot_overrides(cfg, SMOL + GRAM + overrides)
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, GLOBAL_ROWS, seed=0).items()}
    try:
        setup = build_train_setup(cfg, batch)
        mesh_shape = {k: int(v) for k, v in setup.mesh.shape.items()
                      if int(v) > 1}
        dbatch = put_batch(batch, setup.batch_shardings)
        _log(f"compiling {name} step (mesh {mesh_shape})...")
        compiled = setup.step_fn.lower(
            setup.state, dbatch, setup.scalars(0),
            jax.random.key(0)).compile()
        census = hlo_collective_census(compiled.as_text())
        state, losses, refreshes = setup.state, [], 0
        for it in range(N_STEPS):
            state, metrics = setup.step_fn(
                state, dbatch, setup.scalars(it), jax.random.key(it))
            losses.append(float(metrics["total_loss"]))
            if should_refresh_gram(cfg, it, refreshes):
                state = refresh_gram(state)
                refreshes += 1
    finally:
        set_current_mesh(None)
    return {
        "arm": name,
        "mesh": mesh_shape,
        "seq": mesh_shape.get("seq", 1),
        "loss_trajectory": losses,
        "gram_refreshes": refreshes,
        "collective_census": census,
    }


def vitl_attention_twins() -> dict:
    """Dense-on-dp8 vs ring-on-dp4xseq2 fwd+bwd attention programs at
    ViT-L geometry: compiled per-device memory stats + collective
    census per arm, one executed fp32 parity point at N=1029."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.ops.attention import xla_attention
    from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
    from dinov3_tpu.parallel.ring_attention import ring_attention
    from dinov3_tpu.utils import hlo_collective_census

    h, d = VITL_HEADS, VITL_HEAD_DIM
    mesh_dense = build_mesh(MeshSpec(data=DP))
    mesh_ring = build_mesh(MeshSpec(data=DP // 2, seq=2))
    b_axes = ("dcn_data", "data", "fsdp")
    cases = []
    for px, N in VITL_CASES:
        row = {"px": px, "N": N, "arms": {}}
        for arm, mesh, B, spec, fn in (
            ("dense_seq1", mesh_dense, DP, P(b_axes, None, None, None),
             lambda q, k, v: xla_attention(q, k, v)),
            # ring-arm inputs are batch-sharded only: ViT token counts
            # (1029, 2309) are odd, so the seq split happens INSIDE
            # ring_attention (pad + constrain into the islands), exactly
            # like the train step hands it activations
            ("ring_seq2", mesh_ring, DP // 2, P(b_axes, None, None, None),
             lambda q, k, v, m=mesh_ring: ring_attention(q, k, v, m)),
        ):
            # one row per data shard in both arms, so per-device stats
            # isolate the attention state, not the batch split
            shapes = [jax.ShapeDtypeStruct((B, N, h, d), jnp.float32)] * 3
            sh = NamedSharding(mesh, spec)
            _log(f"compiling {arm} @ {px}px (N={N})...")
            with mesh:
                compiled = jax.jit(
                    jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v)),
                             argnums=(0, 1, 2)),
                    in_shardings=(sh, sh, sh),
                ).lower(*shapes).compile()
            mem = compiled.memory_analysis()
            row["arms"][arm] = {
                "rows_per_device": 1,
                "temp_bytes_per_device": int(mem.temp_size_in_bytes),
                "argument_bytes_per_device": int(mem.argument_size_in_bytes),
                "output_bytes_per_device": int(mem.output_size_in_bytes),
                "collective_census": hlo_collective_census(
                    compiled.as_text()),
            }
        cases.append(row)

    # executed parity at the 512px count: ring (seq mesh) vs the plain
    # dense oracle, with and without crop-packed segment ids
    B, N = 2, VITL_CASES[0][1]
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, N, h, d), jnp.float32)
               for kk in ks)
    seg = (jnp.arange(N)[None, :] >= N // 2).astype(jnp.int32).repeat(B, 0)
    ring = jax.jit(lambda q, k, v, s: ring_attention(
        q, k, v, mesh_ring, seg=s), static_argnums=())
    diff_plain = float(jnp.abs(
        jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh_ring))(q, k, v)
        - xla_attention(q, k, v)).max())
    diff_seg = float(jnp.abs(
        ring(q, k, v, seg) - xla_attention(q, k, v, seg=seg)).max())
    return {
        "cases": cases,
        "executed_parity": {
            "N": N, "dtype": "float32",
            "max_abs_diff_plain": diff_plain,
            "max_abs_diff_segmented": diff_seg,
        },
    }


def main():
    import jax

    try:
        jax.config.update("jax_num_cpu_devices", DP)
    except AttributeError:
        pass

    from dinov3_tpu.ops import attention

    attention.RING_MIN_SEQ = 1  # see the module docstring
    arms = [gram_stage_arm(name, ovr) for name, ovr in ARMS]

    # ---- acceptance pins (ISSUE 15) ----
    for rec in arms:
        c = rec["collective_census"]
        assert c["unattributed"] == 0, (rec["arm"], c["unattributed"])
        assert all(math.isfinite(v) for v in rec["loss_trajectory"]), rec
        assert rec["gram_refreshes"] >= 1, rec["arm"]
        if rec["seq"] > 1:
            # ring collectives present AND attributed to their scope
            assert scope_ops(c, "ring_permute") > 0, (
                rec["arm"], sorted(c["by_scope"]))
    oracle = arms[0]
    assert oracle["seq"] == 1
    equiv = {}
    for rec in arms[1:]:
        rel = [abs(a - b) / max(1.0, abs(a)) for a, b in
               zip(oracle["loss_trajectory"], rec["loss_trajectory"])]
        equiv[rec["arm"]] = {"rel_loss_diff": rel}
        # same global batch, same init, same rng: the seq split only
        # reorders reductions
        assert max(rel) < 5e-2, (rec["arm"], rel)

    out = {
        "what": ("high-res gram-anchoring stage on sequence-sharded, "
                 "segment-masked ring attention: executed gram-stage "
                 "arms on seq=1/dp x seq/dp x fsdp x seq meshes + "
                 "ViT-L attention-memory twins at 512/768px"),
        "global_batch_rows": GLOBAL_ROWS,
        "n_steps": N_STEPS,
        "hires_step": {"arms": arms, "oracle": "seq1_oracle",
                       "loss_equivalence": equiv},
        "unattributed_collective_ms": 0.0,
        "note": (
            "CPU harness: structural evidence only (censuses, compiled "
            "per-device memory stats, loss trajectories) — no wall "
            "times; on-chip A/B not yet run on the chip. "
            "ops.attention.RING_MIN_SEQ=1 here is the test hook that "
            "makes 17-token vit_test passes ring; the shipped floor "
            "1024 keeps local crops dense"
        ),
        "source": ("hlo_census + memory_analysis of the shipped "
                   "build_train_setup step and standalone attention "
                   f"twins on {DP} simulated CPU devices, steps "
                   "executed"),
    }
    if not SMOKE:
        vitl = vitl_attention_twins()
        for row in vitl["cases"]:
            dense = row["arms"]["dense_seq1"]
            ring = row["arms"]["ring_seq2"]
            rc = ring["collective_census"]
            assert rc["unattributed"] == 0, (row["px"], rc["unattributed"])
            assert scope_ops(rc, "ring_permute") > 0, sorted(rc["by_scope"])
            assert dense["collective_census"]["unattributed"] == 0
            # THE memory pin: per-device attention state at seq=2
            # measurably below seq=1 (O(N/s) rotation vs dense [N, N])
            assert ring["temp_bytes_per_device"] \
                < dense["temp_bytes_per_device"], (
                row["px"], ring["temp_bytes_per_device"],
                dense["temp_bytes_per_device"])
        assert vitl["executed_parity"]["max_abs_diff_plain"] < 1e-4
        assert vitl["executed_parity"]["max_abs_diff_segmented"] < 1e-4
        out["vitl_attention"] = vitl

    if OUT:
        with open(OUT, "w") as f:
            json.dump(out, f, indent=1)
        _log(f"wrote {OUT}")
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("hires_step", "vitl_attention")}))
    if SMOKE:
        _log("smoke OK: ring collectives scope-attributed, zero "
             "unattributed, gram stage finite + refresh exercised, "
             "seq arms match the seq=1 oracle")


if __name__ == "__main__":
    main()
