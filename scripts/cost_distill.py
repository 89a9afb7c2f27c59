"""Serve-backed multi-student distillation: the committed evidence
behind COST_DISTILL_r22.json (ROADMAP item 2 — compute the 7B teacher
once, fan its features out to every student subgroup).

Under multidistillation every student subgroup used to forward the SAME
frozen teacher over the SAME images inside its own train step: k
subgroups x E epochs = k*E teacher evaluations per unique image. The
serve-backed arm moves the teacher to the host-shared packed AOT engine
(train/distillation.py TeacherServer) behind the content-addressed
feature cache (serve/cache.py), so every unique image is forwarded
EXACTLY ONCE per host — per step, per subgroup, per epoch — and the
train step consumes the precomputed ``teacher_cls``/``teacher_patches``
batch planes through ``get_teacher_output``'s serve arm.

Instruments (all on CPU, structural — no wall times):

- **fan-out dedup**: two student subgroups (vit_test + vit_test_big
  students, one shared vit_test_big teacher) replay a 2-epoch synthetic
  stream through ONE shared TeacherServer
  (multidistillation.shared_teacher_server). Pins: teacher forwards ==
  unique images (forwards per image == 1 regardless of k or epochs; the
  in-step arm pays k*E per image by construction), engine compile count
  == 1 across everything, and the measured cache hit rate equals the
  analytic 1 - 1/(k*E).
- **bitwise loss equivalence**: ``get_teacher_output`` fed precomputed
  planes holding the in-step oracle's OWN backbone features reproduces
  the oracle's teacher targets AND center state bitwise (shared
  ``teacher_targets_from_features`` tail; f32 planes round-trip bf16
  exactly). The serve ENGINE's features vs the in-step forward is a
  tolerance measurement, recorded as max|diff| over the executed step
  losses (bf16 packed program vs in-step program — the on-chip A/B is
  not yet run on the chip).
- **cache hit == miss bitwise**: the replayed epoch's planes are
  array_equal to the first epoch's.
- **attribution**: the teacher-source=serve train step compiles with
  ZERO unattributed collectives (the ``distill_fanout`` scope is in
  utils.HLO_COLLECTIVE_SCOPES), and so does the packed teacher program.

One JSON record -> COST_DISTILL_r22.json (argv[1], default
./COST_DISTILL_r22.json); also printed to stdout. ``--smoke`` runs one
subgroup, one epoch (same pins that apply, no JSON write unless an out
path is given).

Usage: JAX_PLATFORMS=cpu python scripts/cost_distill.py [out] [--smoke]
"""

from __future__ import annotations

import json
import math
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = "--smoke" in sys.argv
_pos = [a for a in sys.argv[1:] if not a.startswith("--")]
OUT = _pos[0] if _pos else (None if SMOKE else "COST_DISTILL_r22.json")

N_STUDENTS = 1 if SMOKE else 2
N_EPOCHS = 1 if SMOKE else 2
BATCHES_PER_EPOCH = 2
ROWS_PER_BATCH = 4

SMOL = [
    "student.patch_size=4", "student.drop_path_rate=0.0",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
    "dino.head_bottleneck_dim=16",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
    "ibot.head_bottleneck_dim=16",
    "train.OFFICIAL_EPOCH_LENGTH=4", "optim.epochs=4",
    "optim.scaling_rule=none",
    "telemetry.async_metrics=false",
]

TEACHER_RECIPE = {
    "student": {"arch": "vit_test_big", "patch_size": 4,
                "drop_path_rate": 0.0},
    "dino": {"head_n_prototypes": 64, "head_hidden_dim": 48,
             "head_bottleneck_dim": 16},
    "ibot": {"head_n_prototypes": 64, "head_hidden_dim": 48,
             "head_bottleneck_dim": 16},
    "crops": {"global_crops_size": 16, "local_crops_size": 8,
              "local_crops_number": 2},
    "optim": {"scaling_rule": "none"},
}

# the k student subgroups (multidistillation spec: one arch each)
STUDENT_ARCHES = [
    ("vit_test", []),
    ("vit_test_big", ["dino.head_hidden_dim=48", "ibot.head_hidden_dim=48"]),
][:N_STUDENTS]


def _log(msg):
    print(f"[cost_distill] {msg}", file=sys.stderr, flush=True)


def _student_cfg(teacher_yaml, arch, extra, source="serve"):
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config

    cfg = get_default_config()
    apply_dot_overrides(cfg, SMOL + [
        f"student.arch={arch}",
        "distillation.enabled=true",
        f"distillation.full_cfg_path={teacher_yaml}",
        f"distillation.teacher_source={source}",
    ] + list(extra))
    return cfg


def _epoch_batches(cfg):
    """The fixed synthetic 'dataset': every epoch replays the SAME
    BATCHES_PER_EPOCH batches (seeded), like a real epoch re-reads the
    same images."""
    from dinov3_tpu.data import make_synthetic_batch

    return [make_synthetic_batch(cfg, ROWS_PER_BATCH, seed=s)
            for s in range(BATCHES_PER_EPOCH)]


def fanout_dedup(teacher_yaml, tparams) -> dict:
    """k student subgroups x E epochs through ONE shared TeacherServer:
    the forwards-per-image and cache-hit-rate measurement."""
    import jax

    from dinov3_tpu.train.multidistillation import (
        _SHARED_TEACHERS,
        shared_teacher_server,
    )

    _SHARED_TEACHERS.clear()
    cfgs = [_student_cfg(teacher_yaml, arch, extra)
            for arch, extra in STUDENT_ARCHES]
    servers = [shared_teacher_server(c, teacher_params=tparams, warn=False)
               for c in cfgs]
    assert all(s is servers[0] for s in servers), "subgroups must share"
    srv = servers[0]

    batches = _epoch_batches(cfgs[0])
    # 2 global crops per image: the dedup unit is the CROP row (each
    # distinct crop is one teacher forward)
    unique = {srv.cache.key(np.asarray(b["global_crops"][i], np.float32),
                            srv.fingerprint)
              for b in batches
              for i in range(b["global_crops"].shape[0])}
    crop_rows = sum(b["global_crops"].shape[0] for b in batches)
    first_pass: dict = {}
    replay_bitwise = True
    for epoch in range(N_EPOCHS):
        for sub, _cfg in enumerate(cfgs):
            for bi, b in enumerate(batches):
                ann = srv.annotate(
                    {"global_crops": np.asarray(b["global_crops"],
                                                np.float32)})
                planes = (ann["teacher_cls"], ann["teacher_patches"])
                if bi in first_pass:
                    replay_bitwise &= all(
                        np.array_equal(x, y)
                        for x, y in zip(first_pass[bi], planes))
                else:
                    first_pass[bi] = planes
    stats = srv.stats()
    _SHARED_TEACHERS.clear()
    images_requested = N_STUDENTS * N_EPOCHS * crop_rows
    return {
        "students": N_STUDENTS,
        "epochs": N_EPOCHS,
        "unique_images": len(unique),
        "images_requested": images_requested,
        "teacher_forwards": stats["teacher_forwards"],
        "forwards_per_unique_image": (
            stats["teacher_forwards"] / len(unique)),
        "in_step_forwards_per_unique_image": N_STUDENTS * N_EPOCHS,
        "forward_reduction_x": N_STUDENTS * N_EPOCHS,
        "compile_count": stats["compile_count"],
        "cache": stats["cache"],
        "cache_hit_rate_analytic": 1.0 - 1.0 / (N_STUDENTS * N_EPOCHS),
        "replay_bitwise": bool(replay_bitwise),
        "engine_census_unattributed": __import__(
            "dinov3_tpu.utils", fromlist=["hlo_collective_census"]
        ).hlo_collective_census(srv.engine.compiled_text())["unattributed"],
    }


def loss_equivalence(teacher_yaml) -> dict:
    """The bitwise pin (oracle features through the serve arm) plus the
    executed-step tolerance measurement (engine features vs in-step)."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.train import build_train_setup, put_batch
    from dinov3_tpu.train.distillation import (
        TeacherServer,
        teacher_feature_example,
    )
    from dinov3_tpu.utils import hlo_collective_census

    arch, extra = STUDENT_ARCHES[0]
    rec = {}
    try:
        # ---- in-step oracle arm
        cfg_o = _student_cfg(teacher_yaml, arch, extra, source="in_step")
        batch = {k: jnp.asarray(v) for k, v in
                 make_synthetic_batch(cfg_o, ROWS_PER_BATCH, seed=0).items()}
        setup_o = build_train_setup(cfg_o, batch)
        meta = setup_o.meta
        frozen = setup_o.state.params["teacher"]
        state0 = meta.init_state()
        temp = 0.05
        masked = meta.masked_rows(batch)
        oracle_out, oracle_state = meta.get_teacher_output(
            frozen, batch, temp, state0, masked=masked)

        # ---- serve arm fed the oracle's OWN features: bitwise
        cls, patches = meta.teacher_backbone_features(frozen, batch)
        sbatch = dict(batch)
        sbatch["teacher_cls"] = jnp.asarray(np.asarray(cls, np.float32))
        sbatch["teacher_patches"] = jnp.asarray(
            np.asarray(patches, np.float32))
        meta.teacher_source = "serve"
        serve_out, serve_state = meta.get_teacher_output(
            frozen, sbatch, temp, state0, masked=masked)
        meta.teacher_source = "in_step"
        bitwise = all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for a, b in ((oracle_out, serve_out),
                         (oracle_state, serve_state))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

        # ---- executed steps: in-step program vs serve program whose
        # planes come from the PACKED ENGINE (bf16 serving tree) — the
        # tolerance measurement, not a bitwise claim
        # (snapshot the frozen teacher FIRST: the executed step donates
        # its state buffers, deleting the device tree)
        frozen_host = jax.device_get(frozen)
        dbatch_o = put_batch(batch, setup_o.batch_shardings)
        _log("executing in-step oracle step...")
        _, m_o = setup_o.step_fn(
            setup_o.state, dbatch_o, setup_o.scalars(0), jax.random.key(0))
        loss_o = float(m_o["total_loss"])
        set_current_mesh(None)

        cfg_s = _student_cfg(teacher_yaml, arch, extra, source="serve")
        srv = TeacherServer(
            cfg_s,
            teacher_params=frozen_host["backbone"], warn=False)
        ex = dict(batch)
        ex.update({k: jnp.asarray(v) for k, v in teacher_feature_example(
            cfg_s, ROWS_PER_BATCH * 2).items()})
        setup_s = build_train_setup(cfg_s, ex)
        # teacher init differs across setups; reuse the ORACLE's frozen
        # teacher tree in both programs so the arms compare like with like
        params_s = dict(setup_s.state.params)
        params_s["teacher"] = frozen_host
        state_s = setup_s.state.replace(params=params_s) \
            if hasattr(setup_s.state, "replace") \
            else setup_s.state._replace(params=params_s)
        ann = srv.annotate(
            {"global_crops": np.asarray(batch["global_crops"], np.float32)})
        sb = dict(batch)
        sb["teacher_cls"] = jnp.asarray(ann["teacher_cls"])
        sb["teacher_patches"] = jnp.asarray(ann["teacher_patches"])
        dbatch_s = put_batch(sb, setup_s.batch_shardings)
        _log("compiling + executing serve-arm step...")
        compiled = setup_s.step_fn.lower(
            state_s, dbatch_s, setup_s.scalars(0),
            jax.random.key(0)).compile()
        census = hlo_collective_census(compiled.as_text())
        _, m_s = compiled(
            state_s, dbatch_s, setup_s.scalars(0), jax.random.key(0))
        loss_s = float(m_s["total_loss"])
        rec = {
            "precomputed_vs_oracle_bitwise": bool(bitwise),
            "executed_step_loss_in_step": loss_o,
            "executed_step_loss_serve_engine": loss_s,
            "engine_vs_in_step_loss_diff": abs(loss_s - loss_o),
            "serve_step_census": census,
        }
    finally:
        set_current_mesh(None)
    return rec


def main():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import yaml

    from dinov3_tpu.models import build_backbone
    from dinov3_tpu.train.distillation import resolve_distillation_cfg

    tmp = tempfile.mkdtemp()
    teacher_yaml = os.path.join(tmp, "teacher.yaml")
    with open(teacher_yaml, "w") as f:
        yaml.safe_dump(TEACHER_RECIPE, f)

    # one frozen teacher weight tree shared by every arm
    any_cfg = _student_cfg(teacher_yaml, *STUDENT_ARCHES[0])
    teacher_cfg = resolve_distillation_cfg(any_cfg)
    tmodel = build_backbone(teacher_cfg, teacher=True)
    tparams = nn.meta.unbox(
        jax.jit(tmodel.init)(jax.random.key(1), jnp.zeros((1, 16, 16, 3)))
    )["params"]

    _log(f"fan-out dedup: {N_STUDENTS} subgroup(s) x {N_EPOCHS} epoch(s)")
    fanout = fanout_dedup(teacher_yaml, tparams)
    _log("loss equivalence arms...")
    equiv = loss_equivalence(teacher_yaml)

    # ---- acceptance pins (ISSUE 18) ----
    assert fanout["forwards_per_unique_image"] == 1.0, fanout
    assert fanout["compile_count"] == 1, fanout
    assert fanout["replay_bitwise"], "cache hit != miss"
    assert fanout["engine_census_unattributed"] == 0, fanout
    assert math.isclose(fanout["cache"]["hit_rate"],
                        fanout["cache_hit_rate_analytic"],
                        abs_tol=1e-9), fanout["cache"]
    assert equiv["precomputed_vs_oracle_bitwise"], equiv
    assert equiv["serve_step_census"]["unattributed"] == 0, \
        equiv["serve_step_census"]
    assert math.isfinite(equiv["executed_step_loss_serve_engine"]), equiv

    out = {
        "what": ("serve-backed multi-student distillation: ONE packed "
                 "AOT teacher forward per unique image fanned out to "
                 "every student subgroup through the content-addressed "
                 "cache, vs k-subgroups x E-epochs in-step forwards"),
        "fanout": fanout,
        "loss_equivalence": equiv,
        "unattributed_collective_ms": 0.0,
        "note": (
            "CPU harness: structural evidence only (forward/compile "
            "counters, censuses, bitwise comparisons) — no wall times. "
            "The bitwise pin feeds the in-step oracle's own features "
            "through the precomputed-targets arm (shared "
            "teacher_targets_from_features tail); the packed engine's "
            "bf16 features vs the in-step forward is the recorded "
            "loss-diff tolerance, not yet priced on the chip."),
        "source": ("TeacherServer/shared_teacher_server counters + "
                   "hlo_census of the teacher_source=serve train step "
                   "and the packed teacher program, steps executed"),
    }
    if OUT:
        with open(OUT, "w") as f:
            json.dump(out, f, indent=1)
        _log(f"wrote {OUT}")
    slim = dict(out)
    slim["loss_equivalence"] = {
        k: v for k, v in equiv.items() if k != "serve_step_census"}
    print(json.dumps(slim))
    if SMOKE:
        _log("smoke OK: forwards/unique image == 1, compile count == 1, "
             "replay bitwise, precomputed targets bitwise vs oracle, "
             "zero unattributed collectives")


if __name__ == "__main__":
    main()
