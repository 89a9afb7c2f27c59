"""Fixtures of the benchmark's own tests (``pytest benchmark/tests``, run
by hand; not part of the repo's tier-1 suite).

``rehearsal`` points the harness at configurations of test width and
replaces its refusal of anything but a TPU: switches of the TEST. The
harness has no option that shrinks a real run."""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TEST_HEADS = ["dino.head_n_prototypes=256", "ibot.head_n_prototypes=256",
              "dino.head_hidden_dim=64", "ibot.head_hidden_dim=64",
              "dino.head_bottleneck_dim=32", "ibot.head_bottleneck_dim=32"]
TEST_REFERENCE = {"patch_size": 16, "num_heads": 2, "rope_base": 100.0}
TEST_PRETRAIN = {
    "recipe": "configs/train/vitl16_im1k.yaml",
    "overrides": ["data.backend=synthetic", "student.arch=vit_test",
                  "train.batch_size_per_device=4",
                  "crops.local_crops_number=2", "telemetry.flush_every=4",
                  *TEST_HEADS],
    "reference": {**TEST_REFERENCE, "n_local_crops": 2},
    # 2 blocks of width 64, B=4 (this sandbox): the program in bf16 reads up
    # to 0.003 / 0.025 / 0.025 / 0.012 / 0.0002; the reference's forward in
    # fp8 reads grad_norm_gap 0.15 or more, the program's own fp8 path 1.0
    "check": {"loss_rel_gap": 0.01, "loss_terms_gap": 0.08,
              "grad_norm_gap": 0.07, "param_change_gap": 0.05,
              "teacher_change_gap": 0.01},
}
# the serve cell that PERF.md section 7 keeps for later, under the names it
# will have: the harness finds it in the tests' copy of BENCHMARK.json
PARKED_CELL = {"name": "vitl16-serve-closed64", "config": "vitl16-serve",
               "traffic": "serve-mixed-ragged-closed64", "chips": 1,
               "why": "parked"}
PARKED_METRICS = [
    ("end_to_end", "serve_img_per_s", "img/s", None),
    ("per_layer", "serve_pack_wall_ms", "ms", "serve_img_per_s"),
    ("per_layer", "serve_p95_ms", "ms", "serve_img_per_s"),
    ("per_layer", "serve_pad_waste_pct", "%", "serve_img_per_s"),
    ("per_layer", "serve_pack_device_ms", "ms", "serve_img_per_s"),
    ("per_layer", "serve_device_idle_pct", "%", "serve_img_per_s"),
]
TEST_CONFIGS = {
    "vitl16-pretrain": TEST_PRETRAIN,
    "vitl16-serve": {
        "overrides": ["student.arch=vit_test", "student.patch_size=16",
                      "train.scan_layers=true", "serve.rows=2",
                      "serve.max_px=128", "serve.min_px=32"],
        "reference": TEST_REFERENCE,
        "check": {"requests": 3, "cls_rel_l2": 0.015, "pooled_rel_l2": 0.015},
    },
}
TEST_TRAFFIC = {
    "pretrain-steps": {"driver": "train_steps", "pool_batches": 3,
                       "warmup_steps": 2, "traced_steps": 2,
                       "trace_lead_steps": 1, "start_iteration": 1250},
    "serve-mixed-ragged-closed64": {
        "driver": "serve_closed", "callers": 12,
        "bands": [[0.7, [32, 64]], [0.3, [80, 128]]], "grid": 16,
        "pool_images": 24, "sizes_seed": 5, "warmup_packs": 2,
        "traced_packs": 3, "trace_lead_packs": 1},
}


@pytest.fixture
def rehearsal(tmp_path, monkeypatch):
    """``run.main`` on the CPU at test width; returns a function that
    runs one cell and gives back the parsed result line."""
    import jax

    import run

    # the repo's suite runs with the persistent cache off on XLA:CPU
    monkeypatch.setattr(run, "configure_cache", lambda: "(off in tests)")
    cdir, tdir = tmp_path / "configs", tmp_path / "traffic"
    cdir.mkdir()
    tdir.mkdir()
    for name, conf in TEST_CONFIGS.items():
        (cdir / f"{name}.json").write_text(json.dumps(conf))
    for name, mix in TEST_TRAFFIC.items():
        (tdir / f"{name}.json").write_text(json.dumps(mix))
    bench = json.load(open(run.BENCHMARK_JSON))
    bench["workloads"].append(PARKED_CELL)
    for section, name, unit, moves in PARKED_METRICS:
        entry = {"name": name, "unit": unit, "workloads": [PARKED_CELL["name"]]}
        bench[section].append({**entry, "moves": moves} if moves else entry)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(run, "BENCHMARK_JSON", str(tmp_path / "BENCHMARK.json"))
    monkeypatch.setattr(run, "CONFIG_DIR", str(cdir))
    monkeypatch.setattr(run, "TRAFFIC_DIR", str(tdir))
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(run, "HOST_TRACER_LEVEL", 2)  # the CPU's ops are host events
    monkeypatch.setattr(run, "require_devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(
        run, "peaks_for", lambda kind: run.load_json(
            os.path.join(BENCH, "peaks.json"))["devices"]["TPU v5 lite"])

    def go(capsys, workload: str, trace: int, seed: int = 2**31 + 12345,
           seconds: float = 1.0) -> dict:
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go
