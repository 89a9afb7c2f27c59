"""Read, on the chip, the two numbers each limit of the training check is
set from, at the configuration's own size, in ONE process (the step
compiles once, every seed makes a new state): the largest gap that sound
runs of the program give over a dozen seeds or more, and the smallest that
the controls give. No measured window: training's readings need none.

    python3 benchmark/tools/read_limits.py <config> <traffic> <seeds> <control seeds> [first seed]

Controls, each against the float32 reference of the same seed:

- ``ref-int8``, ``ref-fp8``: the reference put in the program's place with
  its blocks' forward pass in int8 / fp8 (``reference/vit_fp32.py``);
- ``program <override>``: the program with a lower-precision path of its
  own switched on (``CONTROL_OVERRIDES``), compiled once more.

One JSON line per reading; a summary of the sound runs' largest and each
control's smallest at the end, also written to
``chiprun_out/benchmark/limits.<config>.json``.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

CONTROL_OVERRIDES = (("student.fp8_enabled=true",),)
REFERENCE_CONTROLS = ("int8", "fp8")


def main(argv: list) -> int:
    import run as harness

    sys.modules.setdefault("run", harness)
    import step_check

    name, traffic, n_seeds, n_control = argv[0], argv[1], int(argv[2]), int(argv[3])
    first = int(argv[4]) if len(argv) > 4 else 2_200_000_000
    conf = harness.load_json(os.path.join(harness.CONFIG_DIR, name + ".json"))
    mix = harness.load_json(os.path.join(harness.TRAFFIC_DIR, traffic + ".json"))
    driver = harness.load_module(harness.DRIVER_DIR, mix["driver"])
    cache = harness.configure_cache()
    devices = harness.require_devices(1)
    harness.log(f"{name}: {n_seeds} seeds, {n_control} control seeds from {first}; "
                f"cache {cache}")
    seeds = [first + 7919 * i for i in range(n_seeds)]
    readings: dict = {"sound": [], **{f"ref-{p}": [] for p in REFERENCE_CONTROLS}}
    references: dict = {}

    def emit(kind: str, seed: int, gaps: dict, **more) -> None:
        readings.setdefault(kind, []).append(gaps)
        print(json.dumps({"config": name, "kind": kind, "seed": seed, **gaps, **more}),
              flush=True)

    rig = driver.Rig(conf, mix, devices, first, harness.SpanRecorder())
    for i, seed in enumerate(seeds):
        rig.start(seed)
        t0 = time.perf_counter()
        program = rig.first_steps()
        t_prog = time.perf_counter() - t0
        rig.free()
        t0 = time.perf_counter()
        references[seed] = rig.reference()
        emit("sound", seed, step_check.gaps(program, references[seed]),
             first_steps_s=round(t_prog, 2), reference_s=round(time.perf_counter() - t0, 2),
             total_loss=[round(r["total_loss"], 4) for r in program["losses"]])
        if i < n_control:
            for p in REFERENCE_CONTROLS:
                emit(f"ref-{p}", seed, step_check.gaps(rig.reference(p), references[seed]))
    for overrides in CONTROL_OVERRIDES if n_control else ():
        kind = "program " + " ".join(overrides)
        del rig
        gc.collect()
        try:
            rig = driver.Rig(conf, mix, devices, first, harness.SpanRecorder(),
                             extra_overrides=overrides)
            for seed in seeds[:n_control]:
                rig.start(seed)
                program = rig.first_steps()
                rig.free()
                emit(kind, seed, step_check.gaps(program, references[seed]))
        except Exception as e:  # a control that crashes has failed; it sets no upper end
            print(json.dumps({"config": name, "kind": kind, "crashed": repr(e)[:500]}),
                  flush=True)
    summary = {"config": name, "seeds": n_seeds, "control_seeds": n_control}
    for kind, rows in readings.items():
        if rows:
            pick = max if kind == "sound" else min
            summary[kind + (" max" if kind == "sound" else " min")] = {
                k: pick(r[k] for r in rows) for k in step_check.NUMBERS}
    print(json.dumps(summary), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"limits.{name}.json"), "w") as f:
        json.dump({"summary": summary, "readings": readings}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
