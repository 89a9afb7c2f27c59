"""Capture a jax.profiler trace of the ViT-L fused train step and print /
emit the per-op-category device-time breakdown — now riding the shared
step-anatomy parser (telemetry/trace.py + telemetry/anatomy.py) instead
of the ad-hoc flat classifier this script used to carry.

The old local ``categorize()`` undercounted matmul/conv (a fusion whose
kind-name carries a dot/conv token — ``convolution_add_fusion`` — was
binned "fusion/elementwise"; PROFILE_r05.json shows 46.3 ms/step of it)
and miscounted ``convert_element_type`` as a convolution (bare ``"conv"
in name`` substring). The shared ``telemetry.anatomy.categorize`` fixes
both; the historical r05 artifact is kept as-is for provenance (its
source trace was never committed — the r17 artifact pins the parser
against the committed ``docs/profiles/PROFILE_r17_trace.json.gz``
instead, tests/test_anatomy.py re-derives it byte-exactly).

Usage:
  python scripts/profile_step.py [outdir]          # capture + parse
  python scripts/profile_step.py --from-trace P    # parse an existing
                                                   # trace file/dir only
Flags: --steps N (traced/assumed step count), --out FILE (write the
machine-readable breakdown JSON), --hlo FILE (join against a compiled
HLO text for named-scope collective attribution).
Env: BENCH_ARCH/BENCH_BATCH/BENCH_RES as in bench.py.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _arg(flag: str, default=None):
    if flag in sys.argv:
        return sys.argv[sys.argv.index(flag) + 1]
    return default


def breakdown(trace_path: str, n_steps: int | None,
              hlo_text: str | None = None) -> dict:
    """One trace file/dir -> the machine-readable breakdown record
    (shared-parser ledger summary + the by-category and top-op views
    the old flat parser printed). Deterministic from the trace alone
    when ``hlo_text`` is None — the property the committed
    PROFILE_r17.json equivalence pin relies on."""
    from dinov3_tpu.telemetry import anatomy_ledger, ledger_summary
    from dinov3_tpu.telemetry.anatomy import round_floats
    from dinov3_tpu.telemetry.trace import find_trace_file, load_trace

    path = find_trace_file(trace_path)
    if path is None:
        raise FileNotFoundError(f"no *.trace.json.gz under {trace_path!r}")
    trace = load_trace(path)
    ledger = anatomy_ledger(trace, hlo_text=hlo_text, n_steps=n_steps)
    summary = ledger_summary(ledger)
    by_name: dict = {}
    for e in trace.op_events(module=ledger["module"]):
        by_name[e.name] = by_name.get(e.name, 0.0) + e.dur / 1e3
    n = max(1, ledger["n_steps"])
    return round_floats({
        "schema": "profile/v2",
        "trace": os.path.basename(path),
        "module": ledger["module"],
        "n_steps": ledger["n_steps"],
        "n_timelines": ledger["n_timelines"],
        "n_device_ops": len(by_name),
        "device_total_ms": summary["device_busy_ms_per_step"] * n,
        "by_category_ms_per_step": dict(sorted(
            summary["device_ms_per_step"].items(), key=lambda kv: -kv[1])),
        "summary": summary,
        "top_ops": [
            {"name": k[:120], "ms_per_step": v / n}
            for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:30]
        ],
    })


def report(rec: dict) -> None:
    total = rec["device_total_ms"]
    n = max(1, rec["n_steps"])
    print(f"\ndevice total {total:.1f} ms over {n} steps "
          f"({total / n:.1f} ms/step)  [{rec['n_timelines']} timelines]")
    print("\n== by category (ms/step) ==")
    for k, v in rec["by_category_ms_per_step"].items():
        print(f"  {k:24s} {v:8.2f}  ({100 * v * n / max(total, 1e-9):5.1f}%)")
    colls = rec["summary"].get("collectives") or {}
    if colls:
        print("\n== collectives by scope (ms/step, exposed | overlap) ==")
        for k, v in sorted(colls.items(),
                           key=lambda kv: -kv[1]["ms_per_step"]):
            print(f"  {k:24s} {v['ms_per_step']:8.2f}  "
                  f"exposed {v['exposed_ms_per_step']:7.2f}  "
                  f"overlap {v['overlap_frac']:5.1%}")
    print("\n== top 30 ops (ms/step) ==")
    for row in rec["top_ops"]:
        print(f"  {row['ms_per_step']:8.3f}  {row['name']}")


def main():
    out = _arg("--out")
    from_trace = _arg("--from-trace")
    hlo_file = _arg("--hlo")
    hlo_text = open(hlo_file).read() if hlo_file else None
    if from_trace:
        rec = breakdown(from_trace, int(_arg("--steps", "0")) or None,
                        hlo_text)
        report(rec)
        if out:
            with open(out, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"\nwrote {out}")
        return

    import jax
    import jax.numpy as jnp

    from dinov3_tpu.utils import configure_compile_cache

    configure_compile_cache()

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup, put_batch

    pos = [a for a in sys.argv[1:] if not a.startswith("--")
           and a not in (_arg("--out"), _arg("--steps"), _arg("--hlo"))]
    outdir = pos[0] if pos else "/tmp/prof_r2"
    arch = os.environ.get("BENCH_ARCH", "vit_large")
    per_chip = int(os.environ.get("BENCH_BATCH", "12"))  # bench.py default
    res = int(os.environ.get("BENCH_RES", "0"))

    n = jax.device_count()
    cfg = get_default_config()
    apply_dot_overrides(cfg, [
        f"student.arch={arch}",
        "student.n_storage_tokens=4",
        "student.drop_path_rate=0.3",
        "optim.scaling_rule=none",
        "parallel.data=-1",
        "compute_precision.param_dtype=bf16",
    ] + ([f"crops.global_crops_size={res}",
          f"crops.local_crops_size={max(96, res // 4)}"] if res else []))
    B = per_chip * n
    batch_np = make_synthetic_batch(cfg, B, seed=0)
    batch = {k: jnp.asarray(v) for k, v in batch_np.items()}

    t0 = time.perf_counter()
    setup = build_train_setup(cfg, batch)
    dbatch = put_batch(batch, setup.batch_shardings)
    rng = jax.random.key(0)
    state = setup.state
    scalars = setup.scalars(0)
    print(f"setup {time.perf_counter() - t0:.1f}s", flush=True)

    t0 = time.perf_counter()
    for _ in range(3):
        state, metrics = setup.step_fn(state, dbatch, scalars, rng)
    float(metrics["total_loss"])
    print(f"warmup(3) {time.perf_counter() - t0:.1f}s", flush=True)

    steps = int(_arg("--steps", "6"))
    t0 = time.perf_counter()
    jax.profiler.start_trace(outdir)
    for _ in range(steps):
        state, metrics = setup.step_fn(state, dbatch, scalars, rng)
    float(metrics["total_loss"])
    jax.profiler.stop_trace()
    dt = (time.perf_counter() - t0) / steps
    print(f"step {dt * 1e3:.1f} ms  ->  {B / dt / n:.1f} img/s/chip",
          flush=True)

    if hlo_text is None:
        # join against the exact program just traced, so collective
        # time lands in named scopes (bucket_*/zero3_*/update_shard)
        try:
            hlo_text = setup.step_fn.lower(
                state, dbatch, scalars, rng).compile().as_text()
        except Exception as e:  # noqa: BLE001 - report still useful bare
            print(f"hlo join skipped: {e}", flush=True)
    rec = breakdown(outdir, steps, hlo_text)
    report(rec)
    if out:
        with open(out, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"\nwrote {out}")


if __name__ == "__main__":
    main()
