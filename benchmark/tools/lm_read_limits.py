"""Read, on the chip, the numbers each limit of the decoder's training
check is set from, at the configuration's own size, in ONE process (the
step compiles once, every seed makes a new state): what sound runs of the
program give over the seeds, and what the two controls give. No measured
window.

    python3 benchmark/tools/lm_read_limits.py <config> <traffic> <seeds> <control seeds> [first seed] [controls]

Controls (a comma list; both by default): ``reference/kimi_linear_fp32.py``
put in the program's place with everything the configuration keeps in
float32 lowered to bfloat16 (``bf16``: the nearest precision below the
stated one), and with the last held expert left out (``drop_expert``: a
planted fault), each laid against the float32 reference of the same seed,
weights, tokens and expert choices. Each has to come out not correct by
at least one limit.

One JSON line per reading; a summary at the end, also written to
``chiprun_out/benchmark/limits.<config>.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

CONTROLS = ("bf16", "drop_expert")


def main(argv: list) -> int:
    import run as harness

    sys.modules.setdefault("run", harness)
    import lm_step_check

    name, traffic, n_seeds, n_control = argv[0], argv[1], int(argv[2]), int(argv[3])
    first = int(argv[4]) if len(argv) > 4 else 2_200_000_000
    controls = tuple(argv[5].split(",")) if len(argv) > 5 else CONTROLS
    conf = harness.load_json(os.path.join(harness.CONFIG_DIR, name + ".json"))
    mix = harness.load_json(os.path.join(harness.TRAFFIC_DIR, traffic + ".json"))
    driver = harness.load_module(harness.DRIVER_DIR, mix["driver"])
    cache = harness.configure_cache()
    devices = harness.require_devices(1)
    harness.log(f"{name}: {n_seeds} seeds, {n_control} control seeds from {first}; "
                f"cache {cache}")
    numbers = lm_step_check.UPPER + lm_step_check.LOWER
    readings: dict = {"sound": [], **{c: [] for c in controls}}

    def emit(kind: str, seed: int, gaps: dict, **more) -> None:
        readings[kind].append(gaps)
        print(json.dumps({"config": name, "kind": kind, "seed": seed, **gaps, **more}),
              flush=True)

    rig = driver.Rig(conf, mix, devices, first, harness.SpanRecorder())
    for i in range(n_seeds):
        seed = first + 7919 * i
        rig.start(seed)
        t0 = time.perf_counter()
        program = rig.first_steps()
        t_prog = time.perf_counter() - t0
        rig.free()
        t0 = time.perf_counter()
        reference = rig.reference(keep_host=i < n_control)
        kept = reference.pop("gradient_host", None)
        emit("sound", seed, lm_step_check.gaps(program, reference),
             first_steps_s=round(t_prog, 2), reference_s=round(time.perf_counter() - t0, 2),
             losses=[round(x, 4) for x in program["losses"]],
             worst=lm_step_check.worst_leaves(program, reference))
        for variant in controls if kept is not None else ():
            t0 = time.perf_counter()
            control = rig.reference(variant, against=kept)
            laid = {**reference, "grad_diff_norms": control["grad_diff_norms"]}
            emit(variant, seed, lm_step_check.gaps(control, laid),
                 reference_s=round(time.perf_counter() - t0, 2),
                 worst=lm_step_check.worst_leaves(control, laid))
    summary = {"config": name, "seeds": n_seeds, "control_seeds": n_control}
    for kind, rows in readings.items():
        if rows:
            summary[kind] = {
                k: {"min": min(r[k] for r in rows), "max": max(r[k] for r in rows)}
                for k in numbers}
    print(json.dumps(summary), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"limits.{name}.json"), "w") as f:
        json.dump({"summary": summary, "readings": readings}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
