"""Elastic topology engine: the chaos harness behind RESHARD_r23.json.

ONE training run is killed and resumed across THREE topologies on the
8-simulated-device CPU mesh — replicated@dp8 -> zero3@dp2xfsdp4 ->
zero3@dp8 — exercising BOTH elastic resume paths of the shipped trainer
(train/train.py do_train + train/setup.py elastic_resume):

- leg 0 -> leg 1 is an in-process resize WITHOUT preemption: the live
  ``TrainState`` is resharded in memory (``parallel/reshard.py``) onto
  the new mesh/arm, no disk round-trip (``--resume-topology memory``);
- leg 1 -> leg 2 is a real preemption: the programmatic
  ``PreemptionHandler.notice()`` kill path drives the final atomic save
  (write-then-finalize marker), the next incarnation restores the
  checkpoint ACROSS the topology change (``--resume-topology disk``).

Pins (asserted, then committed as the record):

- **bitwise loss trajectory**: the stitched 3-topology chaos run's
  per-iteration losses equal the unreshaped replicated@dp8 oracle's
  BITWISE, every iteration (under jax_default_matmul_precision=highest,
  the tests/conftest.py pin discipline). zero3 arms are bitwise vs the
  fused replicated update (tests/test_zero3.py); the bucketed arm is
  deliberately NOT a trajectory leg — its packed Adam update rounds
  last-ulp differently (measured here, reported in the record) — it
  rides the transition instrument below instead.
- **census honesty**: every in-memory transfer compiles to one program
  per leaf-group with EVERY collective attributed to its ``reshard_*``
  scope — zero unattributed, zero leakage into other scopes.
- **in-memory vs disk**: on the same transition, the in-memory
  transfer's execution beats the disk round-trip (atomic save +
  finalize + cross-arm restore) wall-clock; the one-time shape-keyed
  jit compile of the 4 group programs is reported alongside (at the
  vit_test probe size it rivals the tiny disk round-trip — at real
  state sizes the transfer scales with bytes while compile stays
  seconds, and repeats of the same resize pay it once).
- **preemption chain**: the span stream carries the full
  preempt_notice -> preempt_save -> resume_restore chain and the
  preemption-to-resume latency (``since_preempt_s``) for both resume
  paths; step-pitch / straggler z-scores (telemetry/anatomy.py
  fleet_report) are reported per leg, before/after each reshape.

``--smoke`` is the CI variant: oracle + two legs (memory-path resume
only), one A/B transition, same asserts.

Usage: JAX_PLATFORMS=cpu python scripts/cost_reshard.py [out] [--smoke]
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = "--smoke" in sys.argv
_pos = [a for a in sys.argv[1:] if not a.startswith("--")]
OUT = _pos[0] if _pos else (None if SMOKE else "RESHARD_r23.json")
N_DEV = 8

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += \
        f" --xla_force_host_platform_device_count={N_DEV}"

import jax  # noqa: E402
import numpy as np  # noqa: E402

# the bitwise-pin precision discipline (tests/conftest.py): reduction
# order differs across meshes; highest-precision matmuls make the
# cross-topology step bitwise-reproducible on CPU
jax.config.update("jax_default_matmul_precision", "highest")

from dinov3_tpu.configs import load_config  # noqa: E402
from dinov3_tpu.parallel.reshard import (  # noqa: E402
    describe_topology,
    reshard_state,
    topology_of,
)
from dinov3_tpu.telemetry.anatomy import fleet_report  # noqa: E402

# the SMOL dryrun shape (tests/test_zero3.py convention) + synthetic
# data so every incarnation sees the same stream at the same iteration
SMOL = [
    "student.arch=vit_test", "student.patch_size=4",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2", "train.batch_size_per_device=2",
    "optim.scaling_rule=none", "train.scan_layers=true",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
    "dino.head_bottleneck_dim=16",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
    "ibot.head_bottleneck_dim=16",
    "data.backend=synthetic", "optim.warmup_epochs=0",
    # only preemption/final saves: the chaos schedule owns the ckpt dir
    "checkpointing.period=1000",
    # losses recorded+compared on the fp32-probs program (main() pins
    # the same when --record-losses is given on the CLI)
    "compute_precision.probs_dtype=fp32",
]

TOPOLOGIES = {
    "replicated@dp8": ["parallel.data=8", "parallel.zero3=false",
                       "optim.bucketed_collectives=false"],
    "zero3@2x4": ["parallel.data=2", "parallel.fsdp=4",
                  "parallel.zero3=true",
                  "optim.bucketed_collectives=false"],
    "zero3@dp8": ["parallel.data=8", "parallel.zero3=true",
                  "optim.bucketed_collectives=false"],
    "bucketed@dp8": ["parallel.data=8", "parallel.zero3=false",
                     "optim.bucketed_collectives=true"],
}

N_ITERS = 4 if SMOKE else 9
KILLS = [2] if SMOKE else [3, 6]  # iteration counts per killed leg
LEGS = (["replicated@dp8", "zero3@2x4"] if SMOKE
        else ["replicated@dp8", "zero3@2x4", "zero3@dp8"])
RESUME_PATHS = [None, "memory"] if SMOKE else [None, "memory", "disk"]


def build_cfg(topo: str, outdir: str):
    cfg = load_config(None, overrides=SMOL + TOPOLOGIES[topo] + [
        f"train.OFFICIAL_EPOCH_LENGTH={N_ITERS}", "optim.epochs=1"])
    cfg.train.output_dir = outdir
    return cfg


def build_args(outdir: str, losses: str, *, fresh: bool,
               resume_topology: str = "auto"):
    from dinov3_tpu.train.train import get_args_parser

    argv = ["--output-dir", outdir, "--record-losses", losses,
            "--resume-topology", resume_topology]
    if fresh:
        argv.append("--no-resume")
    args = get_args_parser().parse_args(argv)
    args.keep_state = True  # the supervisor handle (do_train result)
    return args


def install_chaos_handler():
    """Patch the trainer's PreemptionHandler with one whose stop-poll
    fires ``notice()`` after a set number of polled iterations — a
    deterministic in-process preemption with the REAL signal-path
    bookkeeping (first-notice clock, preempt span chain, atomic final
    save), minus the test-runner races of a delivered SIGTERM."""
    import dinov3_tpu.run.preemption as prmod

    base = prmod.PreemptionHandler

    class ChaosHandler(base):
        kill_after_steps = None  # set per leg by the harness

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self._polls = 0

        def should_stop(self):
            if type(self).kill_after_steps is not None:
                self._polls += 1
                if self._polls >= type(self).kill_after_steps:
                    self.notice("chaos_kill")
            return super().should_stop()

    prmod.PreemptionHandler = ChaosHandler
    return ChaosHandler


def read_losses(path: str) -> dict:
    rows = {}
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            rows[int(r["iteration"])] = float(r["total_loss"])
    return rows


def span_records(outdir: str) -> list:
    recs = []
    spans = os.path.join(outdir, "telemetry", "spans.jsonl")
    if os.path.exists(spans):
        with open(spans) as f:
            for line in f:
                try:
                    recs.append(json.loads(line))
                except ValueError:
                    continue  # torn trailing line of a killed writer
    return recs


def leg_fleet(recs: list, lo: int, hi: int) -> dict:
    """fleet_report over one leg's iteration window [lo, hi): the
    step-pitch distribution + straggler z-scores before/after each
    reshape (z == 0 on this single-host harness — the schema the
    multi-host fleet fills in)."""
    window = [r for r in recs
              if r.get("iteration") is not None
              and lo <= int(r["iteration"]) < hi]
    rep = fleet_report({"host0": window})
    host = rep["hosts"].get("host0", {})
    return {"step_ms": host.get("step_ms"),
            "straggler_z": host.get("straggler_z"),
            "stragglers": rep["stragglers"],
            "verdict": rep["verdict"]}


def summarize_reshard_report(rep: dict) -> dict:
    return {
        "src": rep["src"], "dst": rep["dst"],
        "same_devices": rep["same_devices"],
        "census_ok": rep["census_ok"],
        "total_wall_ms": rep["total_wall_ms"],
        "total_run_ms": rep["total_run_ms"],
        "total_bytes": rep["total_bytes"],
        "groups": {
            scope: {
                "mode": g["mode"],
                "collectives": {k: v["ops"] for k, v in
                                g["census"]["by_class"].items()},
                "by_scope": {k: v["ops"] for k, v in
                             g["census"]["by_scope"].items()},
                "unattributed": g["census"]["unattributed"],
                "compile_ms": g.get("compile_ms"),
                "run_ms": g.get("run_ms"),
                "bytes": g["bytes"],
            } for scope, g in rep["groups"].items()
        },
        "padding_warnings": rep["padding_warnings"],
    }


def chaos_run(workdir: str) -> dict:
    """The killed-and-resumed run: one loss stream stitched across the
    legs, the preempt span chain, per-leg fleet views."""
    from dinov3_tpu.train.train import do_train

    chaos = install_chaos_handler()
    out = os.path.join(workdir, "chaos")
    os.makedirs(out, exist_ok=True)
    bounds = [0] + KILLS + [N_ITERS]

    legs, live = [], None
    for i, topo in enumerate(LEGS):
        chaos.kill_after_steps = (KILLS[i] - bounds[i]
                                  if i < len(KILLS) else None)
        losses = os.path.join(out, f"losses_leg{i}.jsonl")
        path = RESUME_PATHS[i]
        args = build_args(out, losses, fresh=(i == 0),
                          resume_topology=path or "auto")
        kw = {}
        if path == "memory":
            kw = {"live_state": live["state"], "live_topology":
                  live["topology"]}
        t0 = time.perf_counter()
        res = do_train(build_cfg(topo, out), args, **kw)
        leg_s = time.perf_counter() - t0
        assert res["iterations"] == bounds[i + 1], (
            topo, res["iterations"], bounds[i + 1])
        live = {"state": res["state"], "topology": res["topology"]}
        legs.append({"topology": topo,
                     "desc": describe_topology(res["topology"]),
                     "iterations": [bounds[i], bounds[i + 1]],
                     "resume_path": path, "wall_s": round(leg_s, 3),
                     "losses": losses})
        print(f"[leg {i}] {topo}: iters {bounds[i]}..{bounds[i + 1]} "
              f"(resume={path}, {leg_s:.1f}s)", file=sys.stderr)
    chaos.kill_after_steps = None

    stitched = {}
    for leg in legs:
        stitched.update(read_losses(leg.pop("losses")))
    assert sorted(stitched) == list(range(N_ITERS)), sorted(stitched)

    recs = span_records(out)
    chain = {name: [r for r in recs if r.get("name") == name]
             for name in ("preempt_notice", "preempt_save",
                          "resume_restore")}
    n_kills = len(KILLS)
    assert len(chain["preempt_notice"]) == n_kills, chain
    assert len(chain["preempt_save"]) == n_kills, chain
    # every resumed leg emitted its restore record with the measured
    # preemption-to-resume latency and the path it took
    restores = chain["resume_restore"]
    assert len(restores) == len(LEGS) - 1, restores
    assert [r["path"] for r in restores] == RESUME_PATHS[1:], restores
    assert all("since_preempt_s" in r for r in restores), restores

    fleet = {f"leg{i}:{leg['topology']}":
             leg_fleet(recs, *leg["iterations"])
             for i, leg in enumerate(legs)}
    return {
        "legs": legs,
        "losses": stitched,
        "preempt_chain": {
            k: [{f: r.get(f) for f in
                 ("iteration", "step", "signal", "dur_ms", "path",
                  "since_preempt_s") if f in r} for r in v]
            for k, v in chain.items()},
        "preempt_to_resume_s": [r["since_preempt_s"] for r in restores],
        "fleet": fleet,
    }


def oracle_run(workdir: str) -> dict:
    from dinov3_tpu.train.train import do_train

    out = os.path.join(workdir, "oracle")
    os.makedirs(out, exist_ok=True)
    losses = os.path.join(out, "losses.jsonl")
    res = do_train(build_cfg(LEGS[0], out),
                   build_args(out, losses, fresh=True))
    assert res["iterations"] == N_ITERS
    return {"losses": read_losses(losses), "state": res["state"],
            "topology": res["topology"]}


def transition_ab(workdir: str, live, src_topo) -> list:
    """In-memory reshard vs disk round-trip on the SAME transitions the
    chaos run crossed (+ the bucketed arm conversion in full mode):
    wall clock, per-group censuses, and the value pin (the two paths
    land bitwise-identical states)."""
    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    pairs = [("replicated@dp8", "zero3@2x4")] if SMOKE else [
        ("replicated@dp8", "zero3@2x4"),
        ("zero3@2x4", "zero3@dp8"),
        ("replicated@dp8", "bucketed@dp8"),
    ]
    rows = []
    for src_name, dst_name in pairs:
        cfg = build_cfg(dst_name, workdir)
        import jax.numpy as jnp

        batch = {k: jnp.asarray(v) for k, v in
                 make_synthetic_batch(cfg, 16, seed=0).items()}
        s_dst = build_train_setup(cfg, batch, init_state=True)
        src = live["topology"] if src_name == src_topo else None
        assert src is not None or not SMOKE
        if src is None:
            # chain from the previous row's resharded state
            src, state = prev_dst, prev_state  # noqa: F821
        else:
            state = live["state"]

        t0 = time.perf_counter()
        new_state, rep = reshard_state(state, src, topology_of(s_dst))
        jax.block_until_ready(new_state.params)
        mem_s = time.perf_counter() - t0

        ckdir = tempfile.mkdtemp(dir=workdir)
        ck = Checkpointer(ckdir, async_save=False,
                          bucket_plan=getattr(s_dst, "bucket_plan",
                                              None))
        t0 = time.perf_counter()
        ck.save(int(state.step), state)
        ck.wait_until_finished()
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        disk_state = ck.restore(s_dst.state)
        jax.block_until_ready(disk_state.params)
        restore_s = time.perf_counter() - t0
        ck.close()
        shutil.rmtree(ckdir, ignore_errors=True)

        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_flatten_with_path(new_state)[0],
                jax.tree_util.tree_flatten_with_path(disk_state)[0]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                f"{src_name}->{dst_name}: memory and disk paths "
                f"disagree at {jax.tree_util.keystr(pa)}")

        disk_s = save_s + restore_s
        mem_run_s = rep["total_run_ms"] / 1e3
        rows.append({
            "src": src_name, "dst": dst_name,
            "in_memory": summarize_reshard_report(rep),
            # wall includes the one-time jit compile of the 4 group
            # programs — shape-keyed, amortized across resizes; run is
            # the recurring transfer cost the disk path competes with
            "in_memory_wall_s": round(mem_s, 4),
            "in_memory_run_s": round(mem_run_s, 4),
            "disk": {"save_s": round(save_s, 4),
                     "restore_s": round(restore_s, 4),
                     "total_s": round(disk_s, 4)},
            "memory_vs_disk_speedup": round(disk_s / mem_run_s, 2),
            "paths_bitwise_equal": True,
        })
        print(f"[transition] {src_name} -> {dst_name}: memory "
              f"{mem_s:.2f}s vs disk {disk_s:.2f}s", file=sys.stderr)
        prev_dst, prev_state = topology_of(s_dst), new_state
    return rows


def bucketed_ulp_probe(workdir: str, live) -> dict:
    """Why the bucketed arm is not a bitwise trajectory leg: one step of
    the packed-bucket Adam update vs the replicated fused update from
    the same resharded state — the loss matches, the weights round a
    last-ulp apart (the packed reduction order)."""
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup, put_batch
    import jax.numpy as jnp

    cfg_b = build_cfg("bucketed@dp8", workdir)
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg_b, 16, seed=0).items()}
    s_b = build_train_setup(cfg_b, batch, init_state=True)
    cfg_r = build_cfg("replicated@dp8", workdir)
    s_r = build_train_setup(cfg_r, batch, init_state=True)

    st_b, rep = reshard_state(live["state"], live["topology"],
                              topology_of(s_b))
    assert rep["census_ok"]
    st_r, _ = reshard_state(live["state"], live["topology"],
                            topology_of(s_r))
    it = int(live["state"].step)
    d_b = put_batch(batch, s_b.batch_shardings)
    d_r = put_batch(batch, s_r.batch_shardings)
    st_b2, m_b = s_b.step_fn(st_b, d_b, s_b.scalars(it),
                             jax.random.key(0))
    st_r2, m_r = s_r.step_fn(st_r, d_r, s_r.scalars(it),
                             jax.random.key(0))
    worst, diff_leaves, n = 0.0, 0, 0
    for a, b in zip(jax.tree_util.tree_leaves(st_b2.params),
                    jax.tree_util.tree_leaves(st_r2.params)):
        n += 1
        a, b = np.asarray(a), np.asarray(b)
        if not np.array_equal(a, b):
            diff_leaves += 1
            worst = max(worst, float(np.max(np.abs(
                a.astype(np.float64) - b.astype(np.float64)))))
    return {
        "loss_bitwise": float(m_b["total_loss"]) ==
        float(m_r["total_loss"]),
        "param_leaves_differing": [diff_leaves, n],
        "worst_abs_diff": worst,
    }


def main():
    t_start = time.time()
    workdir = tempfile.mkdtemp(prefix="cost_reshard_")
    try:
        oracle = oracle_run(workdir)
        chaos = chaos_run(workdir)

        # THE pin: the killed-and-resumed run's trajectory is the
        # oracle's, bitwise, across both reshapes and both resume paths
        mismatches = [
            it for it in range(N_ITERS)
            if chaos["losses"][it] != oracle["losses"][it]]
        assert not mismatches, {
            it: (chaos["losses"][it], oracle["losses"][it])
            for it in mismatches}

        transitions = transition_ab(workdir, {
            "state": oracle["state"], "topology": oracle["topology"]},
            LEGS[0])
        for row in transitions:
            assert row["in_memory"]["census_ok"], row
            assert all(g["unattributed"] == 0 for g in
                       row["in_memory"]["groups"].values()), row
            assert row["in_memory_run_s"] < row["disk"]["total_s"], (
                row["src"], row["dst"], row["in_memory_run_s"],
                row["disk"])

        record = {
            "record": "reshard/r23",
            "host": "cpu-sim", "n_devices": N_DEV, "smoke": SMOKE,
            "precision": "highest",
            "topologies": {k: TOPOLOGIES[k] for k in TOPOLOGIES},
            "chaos": {
                "n_iterations": N_ITERS,
                "kills_at": KILLS,
                "legs": chaos["legs"],
                "loss_bitwise_vs_oracle": True,
                "losses": {str(k): repr(v) for k, v in
                           sorted(chaos["losses"].items())},
                "preempt_chain": chaos["preempt_chain"],
                "preempt_to_resume_s": chaos["preempt_to_resume_s"],
                "fleet": chaos["fleet"],
            },
            "transitions": transitions,
        }
        if not SMOKE:
            record["bucketed_ulp_probe"] = bucketed_ulp_probe(
                workdir, {"state": oracle["state"],
                          "topology": oracle["topology"]})
            # the probe is the documented reason bucketed@dp8 rides the
            # transition instrument, not the bitwise trajectory
            assert record["bucketed_ulp_probe"]["loss_bitwise"]
            assert record["bucketed_ulp_probe"]["worst_abs_diff"] < 1e-6
        record["wall_s"] = round(time.time() - t_start, 1)

        print(json.dumps(record, indent=1))
        if OUT:
            with open(OUT, "w") as f:
                json.dump(record, f, indent=1)
                f.write("\n")
            print(f"wrote {OUT}", file=sys.stderr)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    main()
