"""Cold-compile wall-time table: scanned vs unrolled block stack.

An UNROLLED 512px flash fwd+bwd program compiles many times slower than
the scanned one (one scanned block instead of 24 unrolled ones, ~24x
smaller HLO); the high-res recipes therefore run
``train.scan_layers=true``. This script measures that structural effect:
cold build/lower/compile wall time of the bench-identical step program
on the host CPU backend (XLA's compile of the step is host-side work;
the Mosaic compile of the Pallas kernels is not part of it — on cpu the
attention dispatch takes the XLA arm).

A CPU tool: this parent never imports JAX, and every variant runs in a
fresh child pinned to ``JAX_PLATFORMS=cpu`` with the persistent
compilation cache switched off, so every compile is cold and no child
ever asks for a chip.

Usage:  python scripts/measure_compile_time.py [out.jsonl]
        (env: CT_TIMEOUT per-variant seconds, default 3600)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VARIANTS = [
    # bench-identical high-res point (BENCH_RES=512 BENCH_BATCH=2, see
    # bench.py)
    {"name": "hr512_scan", "res": 512, "batch": 2, "scan": True},
    {"name": "hr512_unrolled", "res": 512, "batch": 2, "scan": False},
    # the default 224px headline program for scale
    {"name": "base224_scan", "res": 0, "batch": 8, "scan": True},
    {"name": "base224_unrolled", "res": 0, "batch": 8, "scan": False},
]

_CHILD = r"""
import json, os, sys, time
sys.path.insert(0, sys.argv[1])
spec = json.loads(sys.argv[2])
import jax
# cold compiles only: no persistent cache in this child
jax.config.update("jax_enable_compilation_cache", False)
import jax.numpy as jnp
from dinov3_tpu.configs import apply_dot_overrides, get_default_config
from dinov3_tpu.data import make_synthetic_batch
from dinov3_tpu.train import build_train_setup, put_batch

t0 = time.perf_counter()
cfg = get_default_config()
overrides = [
    "student.arch=vit_large", "student.n_storage_tokens=4",
    "student.drop_path_rate=0.3", "optim.scaling_rule=none",
    "parallel.data=-1", "compute_precision.param_dtype=bf16",
    f"train.scan_layers={str(spec['scan']).lower()}",
]
if spec["res"]:
    overrides += [f"crops.global_crops_size={spec['res']}",
                  f"crops.local_crops_size={max(96, spec['res'] // 4)}"]
apply_dot_overrides(cfg, overrides)
batch = {k: jnp.asarray(v)
         for k, v in make_synthetic_batch(cfg, spec["batch"], seed=0).items()}
setup = build_train_setup(cfg, batch)
dbatch = put_batch(batch, setup.batch_shardings)
t_build = time.perf_counter() - t0

t1 = time.perf_counter()
lowered = setup.step_fn.lower(setup.state, dbatch, setup.scalars(0),
                              jax.random.key(0))
t_lower = time.perf_counter() - t1

t2 = time.perf_counter()
lowered.compile()
t_compile = time.perf_counter() - t2
print(json.dumps({
    "name": spec["name"], "scan": spec["scan"], "res": spec["res"] or 224,
    "batch": spec["batch"], "build_s": round(t_build, 1),
    "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    "total_s": round(time.perf_counter() - t0, 1),
}))
"""


def main() -> None:
    out_path = sys.argv[1] if len(sys.argv) > 1 else "compile_times.jsonl"
    tmo = float(os.environ.get("CT_TIMEOUT", "3600"))
    # each child is pinned to the CPU through its environment: a child
    # of this tool must never ask for an accelerator
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for spec in VARIANTS:
        print(f"[compile-time] {spec['name']} (timeout {tmo:.0f}s)...",
              flush=True)
        t0 = time.time()
        try:
            r = subprocess.run(
                [sys.executable, "-c", _CHILD, REPO, json.dumps(spec)],
                capture_output=True, text=True, timeout=tmo, env=env,
            )
            if r.returncode == 0 and r.stdout.strip():
                rec = json.loads(r.stdout.strip().splitlines()[-1])
            else:
                rec = {"name": spec["name"], "error":
                       f"rc={r.returncode}: "
                       + (r.stderr or "").strip().splitlines()[-1:]
                       .__str__()}
        except subprocess.TimeoutExpired:
            rec = {"name": spec["name"],
                   "error": f"cold compile exceeded {tmo:.0f}s",
                   "elapsed_s": round(time.time() - t0, 1)}
        rec["backend"] = "cpu-host"
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(f"[compile-time] -> {json.dumps(rec)}", flush=True)


if __name__ == "__main__":
    main()
