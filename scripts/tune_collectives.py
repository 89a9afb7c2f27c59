"""Anatomy-driven collective auto-tuner: close the measure->tune loop.

Runs a few profiled steps per candidate through the step-anatomy plane
(telemetry/anatomy.py — the same executed-twin harness as
scripts/anatomy_report.py), searches the collective-schedule knobs, and
commits the winning plan + the FULL per-candidate measurement trail as
``TUNED_r20.json``. ``optim.bucket_mb: auto`` / ``optim.staging_order:
auto`` / ``optim.stream_prefetch: auto`` / ``kernels.ring_min_seq:
auto`` then resolve from the artifact (configs/config.py resolve_*
family) when the live fingerprint (arch, device count, update-shard
size, jax version) matches — and fall back loudly to the hand-set
oracle otherwise.

Objective (telemetry/anatomy.py ``tuning_summary``):
``objective_ms = step_wall_ms.mean + exposed_comm_ms_per_step`` —
exposed collective time counts double, so equal-wall candidates prefer
the schedule that hides more of its communication.

Search (every sweep measures the hand-set oracle too, so tuned-vs-
handset is checkable per arm from the same trail — the
``scripts/perf_gate.py --tuned-vs-handset`` gate):

- ``bucket_mb`` in {32, 64, 128, 256} MiB over the executed ViT-L
  dp=8 bucketed update-phase arm (make_bucket_plan granularity);
- ``staging_order`` over all four "<ag>_<rs>" tier orders of the
  executed unified staged-gather twin (2x4 data x fsdp mesh,
  make_zero3_gather_schedule — the grad RS rides in the transpose);
- ``stream_prefetch`` in {0, 1, 2} over the executed zero3 weight-
  stream twin (jax.grad of streamed_block_scan);
- ``ring_min_seq``: ring-vs-dense attention measured ONCE per
  workload token count (dense on dp=8, ring on dp=4 x seq=2 — same
  device budget, 1 row/device), then every candidate floor's
  objective derived deterministically from the committed table
  (tuning/search.py derive_ring_trail).

During measurement every tuned knob is HAND-SET explicitly — the
tuner never reads the artifact it is writing.

CPU-harness honesty (docs/OBSERVABILITY.md): XLA:CPU runs each
simulated device's thunks sequentially, so measured overlap is a
structural lower bound and exposed-comm a conservative ceiling — the
committed plan optimizes that conservative objective; on-chip
re-derivation has not been run on the chip.

Usage:
  JAX_PLATFORMS=cpu python scripts/tune_collectives.py [out]
  ... --smoke    tiny-arch 2-candidate sweeps; asserts convergence,
                 artifact schema, and resolver round-trip (CI tier-1)
  ... --census   knob census only (tuning/census.py): rc=1 on any
                 untracked optim.*/kernels.* magic number
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DP = 8
os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += f" --xla_force_host_platform_device_count={DP}"

SMOKE = "--smoke" in sys.argv
CENSUS = "--census" in sys.argv
_pos = [a for a in sys.argv[1:] if not a.startswith("--")]
OUT = _pos[0] if _pos else (None if (SMOKE or CENSUS) else "TUNED_r20.json")

# ring workload table: the token counts whose ring-vs-dense cost is
# measured (the candidate floors then partition them); ViT-L head
# geometry (16 heads x 64) — 256 ~ a 224px global crop's patch count,
# 1024 ~ a 448-512px high-res pass
RING_WORKLOADS = (256, 1024)
RING_HEADS, RING_HEAD_DIM = 16, 64

# measurement-time hand-set knobs (== configs/config.py
# TUNED_FALLBACKS): the tuner must never read the artifact it writes
HANDSET_OVR = [
    "optim.bucket_mb=128", "optim.staging_order=inter_intra",
    "optim.stream_prefetch=1", "kernels.ring_min_seq=1024",
]
MESH_OVR = ["parallel.data=2", "parallel.fsdp=4"]


def _log(msg):
    print(f"[tune_collectives] {msg}", file=sys.stderr, flush=True)


_SCRIPT_CACHE: dict = {}


def _load_script(name):
    if name in _SCRIPT_CACHE:
        return _SCRIPT_CACHE[name]
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           f"{name}.py")
        if name != "bench" else
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _SCRIPT_CACHE[name] = mod
    return mod


def _slim(summary: dict, tuning: dict) -> dict:
    """The per-arm committed measurement: enough of the anatomy
    summary for the noise-calibrated perf gate (step_wall_ms stats,
    n_steps, exposed fraction) + the tuner's objective decomposition."""
    return {
        "step_wall_ms": summary["step_wall_ms"],
        "n_steps": summary["n_steps"],
        "exposed_comm_frac": summary["exposed_comm_frac"],
        "exposed_comm_ms_per_step": summary["exposed_comm_ms_per_step"],
        "objective_ms": tuning["objective_ms"],
        "top_exposed_scopes": tuning["top_exposed_scopes"],
    }


def _with_overrides(base_overrides: list, extra: list):
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config

    cfg = get_default_config()
    apply_dot_overrides(cfg, base_overrides + HANDSET_OVR + extra)
    return cfg


def unified_gather_summary(cfg, mesh, order: str) -> dict:
    """Executed staged-bucket gather twin at one staging order: the
    grad of a sin-sum consume over ``make_zero3_gather_schedule``
    (bucketed) on the 2x4 data x fsdp mesh — forward staged AGs and
    their transposed staged grad RS inside the measured program (the
    executed twin of scripts/cost_unified.py gather_phase_twins)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.parallel.sharding import zero3_leaf_spec
    from dinov3_tpu.train.fused_update import (
        make_zero3_bucket_plan,
        make_zero3_gather_schedule,
    )
    from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch

    ar = _load_script("anatomy_report")
    set_current_mesh(mesh)
    meta = SSLMetaArch(cfg)
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, 1, seed=0).items()}
    student = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0)
    )["student"]
    subtree = _load_script("cost_unified")._prune_streamed(student)
    plan = make_zero3_bucket_plan(
        subtree, mesh, target_bytes=meta.zero3_bucket_bytes)

    def shardings(tree):
        def leaf(l):
            spec = zero3_leaf_spec(l.shape, (None,) * l.ndim, mesh)
            return NamedSharding(mesh, spec if spec is not None else P())
        return jax.tree.map(leaf, tree)

    in_sh = shardings(subtree)
    g = make_zero3_gather_schedule(plan, mesh, bucketed=True,
                                   staging_order=order)

    def loss(tree):
        full = g(tree)
        # nonlinear consume: a plain sum reassociates into
        # local-sum + all-reduce and erases the gathers being tuned
        return sum(jnp.sum(jnp.sin(l.astype(jnp.float32)))
                   for l in jax.tree.leaves(full))

    _log(f"compiling unified gather twin (staging_order={order})...")
    with mesh:
        compiled = jax.jit(
            jax.grad(loss), in_shardings=(in_sh,)).lower(subtree).compile()
    args = ar._materialize(subtree, in_sh)

    def run_step():
        jax.block_until_ready(compiled(args))

    return ar._traced_summary(run_step, compiled, f"unified/{order}")


def ring_workload_row(tokens: int) -> dict:
    """One workload row of the ring table: executed fwd+bwd attention
    at ViT-L head geometry — dense on the dp=8 mesh vs ring on the
    dp=4 x seq=2 mesh (same 8-device budget, 1 row per device; odd-N
    padding and the seq split happen INSIDE ring_attention, exactly
    like the train step hands it activations)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.ops.attention import xla_attention
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
    from dinov3_tpu.parallel.ring_attention import ring_attention
    from dinov3_tpu.telemetry import tuning_summary

    ar = _load_script("anatomy_report")
    h, d = RING_HEADS, RING_HEAD_DIM
    row = {"tokens": tokens}
    for arm, mesh, B, fn in (
        ("dense", build_mesh(MeshSpec(data=DP)), DP,
         lambda q, k, v: xla_attention(q, k, v)),
        ("ring", build_mesh(MeshSpec(data=DP // 2, seq=2)), DP // 2,
         None),
    ):
        set_current_mesh(mesh)
        if fn is None:
            def fn(q, k, v, m=mesh):
                return ring_attention(q, k, v, m)
        sh = NamedSharding(mesh, P(("dcn_data", "data", "fsdp"),
                                   None, None, None))
        shapes = [jax.ShapeDtypeStruct((B, tokens, h, d), jnp.float32)] * 3
        _log(f"compiling ring workload {arm} @ N={tokens}...")
        with mesh:
            compiled = jax.jit(
                jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v)),
                         argnums=(0, 1, 2)),
                in_shardings=(sh, sh, sh),
            ).lower(*shapes).compile()
        args = [ar._materialize(s, sh) for s in shapes]

        def run_step():
            jax.block_until_ready(compiled(*args))

        if arm == "ring":
            summary = ar._traced_summary(
                run_step, compiled, f"ring/N{tokens}")
        else:
            # the dense arm has NO collectives (batch-parallel only);
            # trace without the collective-presence assert
            import shutil
            import tempfile
            import time

            from dinov3_tpu.telemetry import anatomy_ledger, ledger_summary
            from dinov3_tpu.telemetry.trace import (
                find_trace_file,
                load_trace,
            )

            run_step()
            tdir = tempfile.mkdtemp(prefix=f"tune_dense_{tokens}_",
                                    dir="/tmp")
            t0 = time.perf_counter()
            jax.profiler.start_trace(tdir)
            try:
                for _ in range(ar.TRACED_STEPS):
                    run_step()
            finally:
                jax.profiler.stop_trace()
            _log(f"dense/N{tokens}: traced {ar.TRACED_STEPS} steps in "
                 f"{time.perf_counter() - t0:.1f}s")
            ledger = anatomy_ledger(
                load_trace(find_trace_file(tdir)),
                hlo_text=compiled.as_text(), n_steps=ar.TRACED_STEPS)
            summary = ledger_summary(ledger)
            shutil.rmtree(tdir, ignore_errors=True)
            assert summary["hlo_joined"]
            assert summary["unattributed_collective_ms"] == 0.0
        tuning = tuning_summary(summary)
        row[arm] = _slim(summary, tuning)
        row[f"{arm}_objective_ms"] = tuning["objective_ms"]
    return row


def measure_bucket_mb(vitl_overrides, mb: int) -> dict:
    ar = _load_script("anatomy_report")
    cfg = _with_overrides(vitl_overrides, [f"optim.bucket_mb={mb}"])
    out = ar.update_phase_arms(cfg, only=("bucketed",))
    return out["bucketed"]["anatomy"]


def measure_stream_prefetch(vitl_overrides, depth: int) -> dict:
    ar = _load_script("anatomy_report")
    cfg = _with_overrides(vitl_overrides,
                          [f"optim.stream_prefetch={depth}"])
    return ar.stream_twin(cfg, "zero3")["anatomy"]


def run_census() -> int:
    from dinov3_tpu.telemetry.anatomy import round_floats
    from dinov3_tpu.tuning import knob_census

    census = knob_census()
    print(json.dumps(round_floats(census), indent=1))
    if not census["ok"]:
        _log(f"census FAILED: unregistered={census['unregistered']} "
             f"stale={census['stale_registry']}")
        return 1
    _log(f"census ok: {census['n_knobs']} knobs accounted for "
         f"({ {k: len(v) for k, v in census['by_kind'].items()} })")
    return 0


def assemble_plan(fingerprint, knob_trails, arms, search_note) -> dict:
    """Round the trails, pick winners from the ROUNDED floats (so
    artifact readers re-derive identical choices), validate, return."""
    from dinov3_tpu.telemetry.anatomy import round_floats
    from dinov3_tpu.tuning import TUNED_SCHEMA, knob_entry, validate_plan

    knobs = {}
    for name, (trail, program, unit, extra) in knob_trails.items():
        knobs[name] = knob_entry(round_floats(trail), name, program,
                                 unit=unit, extra=round_floats(extra))
    doc = {
        "schema": TUNED_SCHEMA,
        "generated_by": "scripts/tune_collectives.py",
        "what": ("measured collective-schedule plan: anatomy-ledger "
                 "objective per candidate, winner re-derivable from "
                 "the committed trail (tuning/plan.py select_best)"),
        "objective": ("objective_ms = step_wall_ms.mean + "
                      "exposed_comm_ms_per_step "
                      "(telemetry/anatomy.py tuning_summary)"),
        "fingerprint": fingerprint,
        "search": search_note,
        "knobs": knobs,
        "arms": round_floats(arms),
        "cpu_harness_caveat": (
            "XLA:CPU executes each simulated device's thunks "
            "sequentially: overlap fractions are structural lower "
            "bounds, exposed-comm a conservative ceiling — the plan "
            "optimizes that conservative objective. Attribution and "
            "scope split are exact. On-chip re-derivation: "
            "a chip run not yet made."),
    }
    return validate_plan(doc)


def smoke() -> None:
    """CI-sized tuner proof on the tiny arch: 2-candidate sweeps,
    schema + convergence + resolver round-trip asserts, artifact to a
    temp path (never the committed one)."""
    import tempfile
    import warnings

    from dinov3_tpu.configs.config import (
        TUNED_FALLBACKS,
        live_tuned_fingerprint,
        resolve_bucket_mb,
        resolve_stream_prefetch,
    )
    from dinov3_tpu.telemetry import tuning_summary
    from dinov3_tpu.tuning import select_best, sweep_knob, trail_row

    ar = _load_script("anatomy_report")
    tiny = list(ar.TINY)

    bucket_cands = (32, 128)
    pf_cands = (0, 1)
    bucket_sums = {}

    def meas_bucket(mb):
        s = measure_bucket_mb(tiny, mb)
        bucket_sums[mb] = s
        return tuning_summary(s)

    pf_sums = {}

    def meas_pf(depth):
        s = measure_stream_prefetch(tiny, depth)
        pf_sums[depth] = s
        return tuning_summary(s)

    bucket_trail = sweep_knob("bucket_mb", bucket_cands, meas_bucket,
                              log=_log)
    pf_trail = sweep_knob("stream_prefetch", pf_cands, meas_pf, log=_log)

    cfg = _with_overrides(tiny, [])
    fp = live_tuned_fingerprint(cfg)
    doc = assemble_plan(
        fp,
        {
            "bucket_mb": (bucket_trail,
                          "vit_test dp=8 bucketed update-phase arm",
                          "MiB", {}),
            "stream_prefetch": (pf_trail,
                                "vit_test zero3 stream twin", None, {}),
        },
        {
            "bucketed": {
                "handset": {"knobs": {"bucket_mb": 128},
                            "anatomy": _slim(
                                bucket_sums[128],
                                tuning_summary(bucket_sums[128]))},
                "tuned": {"knobs": {
                    "bucket_mb": select_best(bucket_trail)},
                    "anatomy": _slim(
                        bucket_sums[select_best(bucket_trail)],
                        tuning_summary(
                            bucket_sums[select_best(bucket_trail)]))},
            },
        },
        {"mode": "smoke", "traced_steps": ar.TRACED_STEPS,
         "candidates": {"bucket_mb": list(bucket_cands),
                        "stream_prefetch": list(pf_cands)}},
    )
    # ---- convergence: the winner is a measured candidate and is
    # re-derivable from the committed (rounded) trail ----
    chosen_mb = doc["knobs"]["bucket_mb"]["chosen"]
    assert chosen_mb in bucket_cands, chosen_mb
    assert chosen_mb == select_best(doc["knobs"]["bucket_mb"]["trail"])
    chosen_pf = doc["knobs"]["stream_prefetch"]["chosen"]
    assert chosen_pf in pf_cands, chosen_pf

    # ---- artifact schema + resolver round-trip ----
    tmp = os.path.join(tempfile.mkdtemp(prefix="tune_smoke_", dir="/tmp"),
                       "TUNED_smoke.json")
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    r1 = resolve_bucket_mb("auto", artifact=tmp, live=fp)
    r2 = resolve_bucket_mb("auto", artifact=tmp, live=fp)
    assert r1 == r2 == chosen_mb, (r1, r2, chosen_mb)
    assert resolve_stream_prefetch(
        "auto", artifact=tmp, live=fp) == chosen_pf
    # stale fingerprint -> loud hand-set fallback
    stale_live = dict(fp, arch="vit_large")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        fb = resolve_bucket_mb("auto", artifact=tmp, live=stale_live)
    assert fb == TUNED_FALLBACKS["bucket_mb"], fb
    assert any("tuned for a different setup" in str(w.message)
               for w in caught), [str(w.message) for w in caught]
    # explicit value stays the oracle
    assert resolve_bucket_mb(64, artifact=tmp, live=fp) == 64

    out = OUT or tmp
    if OUT:
        with open(OUT, "w") as f:
            json.dump(doc, f, indent=1)
    print(json.dumps({
        "smoke": "ok",
        "chosen": {"bucket_mb": chosen_mb, "stream_prefetch": chosen_pf},
        "resolver_round_trip": "bitwise",
        "stale_fallback": fb,
        "artifact": out,
    }))
    _log("smoke OK: convergence + schema + resolver round-trip")


def full() -> None:
    from dinov3_tpu.configs.config import live_tuned_fingerprint
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
    from dinov3_tpu.telemetry import tuning_summary
    from dinov3_tpu.tuning import (
        BUCKET_MB_CANDIDATES,
        RING_MIN_SEQ_CANDIDATES,
        STREAM_PREFETCH_CANDIDATES,
        derive_ring_trail,
        select_best,
        staging_order_candidates,
        sweep_knob,
    )

    ar = _load_script("anatomy_report")
    bench = _load_script("bench")
    vitl = bench.build_step_overrides("vit_large", 0)
    cfg = _with_overrides(vitl, [])
    fp = live_tuned_fingerprint(cfg)
    _log(f"fingerprint: {fp}")

    # ---- plan-invariant arms (measured once; the schedule knobs do
    # not enter their programs) ----
    base_arms = ar.update_phase_arms(cfg, only=("replicated", "flat"))

    # ---- sweeps (each includes its hand-set oracle) ----
    bucket_sums = {}

    def meas_bucket(mb):
        s = measure_bucket_mb(vitl, mb)
        bucket_sums[mb] = s
        return tuning_summary(s)

    pf_sums = {}

    def meas_pf(depth):
        s = measure_stream_prefetch(vitl, depth)
        pf_sums[depth] = s
        return tuning_summary(s)

    bucket_trail = sweep_knob("bucket_mb", BUCKET_MB_CANDIDATES,
                              meas_bucket, log=_log)
    pf_trail = sweep_knob("stream_prefetch", STREAM_PREFETCH_CANDIDATES,
                          meas_pf, log=_log)

    mesh_u = build_mesh(MeshSpec(data=2, fsdp=4))
    st_sums = {}

    def meas_order(order):
        cfg_u = _with_overrides(vitl, MESH_OVR)
        s = unified_gather_summary(cfg_u, mesh_u, order)
        st_sums[order] = s
        return tuning_summary(s)

    st_trail = sweep_knob("staging_order", staging_order_candidates(),
                          meas_order, log=_log)
    set_current_mesh(None)

    # ---- ring workload table (measured once per N; floors derived) --
    ring_rows = [ring_workload_row(n) for n in RING_WORKLOADS]
    set_current_mesh(None)

    from dinov3_tpu.telemetry.anatomy import round_floats

    ring_rows_r = round_floats(ring_rows)
    ring_trail = derive_ring_trail(
        [{"tokens": r["tokens"],
          "ring_objective_ms": r["ring_objective_ms"],
          "dense_objective_ms": r["dense_objective_ms"]}
         for r in ring_rows_r],
        RING_MIN_SEQ_CANDIDATES)

    # ---- tuned-vs-handset arm rows, straight from the sweeps (the
    # handset candidate was measured in every sweep, so both sides of
    # the gate are real measurements of the same program family) ----
    def arm_row(sums, handset_value, chosen_value, knob):
        return {
            "handset": {"knobs": {knob: handset_value},
                        "anatomy": _slim(
                            sums[handset_value],
                            tuning_summary(sums[handset_value]))},
            "tuned": {"knobs": {knob: chosen_value},
                      "anatomy": _slim(
                          sums[chosen_value],
                          tuning_summary(sums[chosen_value]))},
            "same_program": handset_value == chosen_value,
        }

    chosen_mb = select_best(round_floats(bucket_trail))
    chosen_pf = select_best(round_floats(pf_trail))
    chosen_st = select_best(round_floats(st_trail))

    def invariant_arm(summary):
        t = tuning_summary(summary)
        return {"plan_invariant": True,
                "handset": {"knobs": {}, "anatomy": _slim(summary, t)},
                "tuned": {"knobs": {}, "anatomy": _slim(summary, t)}}

    arms = {
        "replicated": invariant_arm(base_arms["replicated"]["anatomy"]),
        "flat": invariant_arm(base_arms["flat"]["anatomy"]),
        "bucketed": arm_row(bucket_sums, 128, chosen_mb, "bucket_mb"),
        "zero3": arm_row(pf_sums, 1, chosen_pf, "stream_prefetch"),
        "unified": arm_row(st_sums, "inter_intra", chosen_st,
                           "staging_order"),
    }

    doc = assemble_plan(
        fp,
        {
            "bucket_mb": (
                bucket_trail,
                f"ViT-L dp={DP} bucketed update-phase arm "
                f"(make_bucket_plan target, executed "
                f"{ar.TRACED_STEPS} traced steps per candidate)",
                "MiB", {}),
            "stream_prefetch": (
                pf_trail,
                "ViT-L zero3 weight-stream twin (jax.grad of "
                "streamed_block_scan at lookahead depth d)",
                None, {}),
            "staging_order": (
                st_trail,
                "executed unified staged-gather twin, 2x4 data x fsdp "
                "mesh (make_zero3_gather_schedule '<ag>_<rs>' order)",
                None, {}),
            "ring_min_seq": (
                ring_trail,
                "derived from the measured ring-vs-dense workload "
                "table (dense dp=8 vs ring dp=4 x seq=2, ViT-L head "
                "geometry): objective(floor) = sum_w (ring if "
                "w.tokens >= floor else dense)",
                "tokens", {"workloads": ring_rows_r}),
        },
        arms,
        {"mode": "full", "traced_steps": ar.TRACED_STEPS,
         "candidates": {
             "bucket_mb": list(BUCKET_MB_CANDIDATES),
             "stream_prefetch": list(STREAM_PREFETCH_CANDIDATES),
             "staging_order": list(staging_order_candidates()),
             "ring_min_seq": list(RING_MIN_SEQ_CANDIDATES)},
         "ring_workload_tokens": list(RING_WORKLOADS)},
    )

    # ---- the acceptance property: tuned >= handset on every arm
    # under the noise-calibrated gate (scripts/perf_gate.py) ----
    pg = _load_script("perf_gate")
    gate = pg.tuned_vs_handset(doc)
    assert gate["passed"], json.dumps(gate, indent=1)

    if OUT:
        with open(OUT, "w") as f:
            json.dump(doc, f, indent=1)
        _log(f"wrote {OUT}")
    print(json.dumps({
        "chosen": {k: v["chosen"] for k, v in doc["knobs"].items()},
        "fingerprint": fp,
        "tuned_vs_handset": {"passed": gate["passed"],
                             "n_arms": gate["n_arms"]},
    }))


def main() -> int:
    if CENSUS:
        return run_census()
    import jax

    from dinov3_tpu.utils import configure_compile_cache

    configure_compile_cache()
    if SMOKE:
        smoke()
    else:
        full()
    return 0


if __name__ == "__main__":
    sys.exit(main())
