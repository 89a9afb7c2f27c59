"""Run a cell as the contract's bound rule asks: two sets of runs with the
same seeds in both, each run a process of its own (this parent never
touches JAX), and print every result line, each metric's spread in each
set (interquartile distance as a share of the median,
``statistics.quantiles(values, n=4)``) and how the second set's median
lies to the first's.

    python3 benchmark/tools/two_sets.py <cell> [runs per set=6] [first seed] [--trace-first]

``--trace-first`` makes one ``--trace 1`` run before the sets.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(cell: str, seed: int, seconds: int, trace: int, tag: str) -> dict | None:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cmd = [*bench["command"], "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out_dir, exist_ok=True)
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    with open(os.path.join(out_dir, f"{cell}.{tag}.log"), "w") as f:
        f.write(p.stdout + "\n--- stderr ---\n" + p.stderr[-20000:])
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    info = [ln for ln in lines if "] check " in ln or "window:" in ln
            or "set-up" in ln or "trace:" in ln or "traced stretch" in ln]
    print(f"--- {tag} seed {seed} trace {trace} rc {p.returncode}")
    for ln in info:
        if not any(k in ln for k in ("steps_applied", "state_changed", "nonfinite",
                                     "loss_change", "compile_count")):
            print("   ", ln[:400])
    if p.returncode != 0 or not lines:
        print("    FAILED:", p.stderr[-1500:])
        return None
    print("   ", lines[-1][:3000], flush=True)
    return json.loads(lines[-1])


def spread(values: list) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv: list) -> int:
    flags = [a for a in argv if a.startswith("--")]
    args = [a for a in argv if not a.startswith("--")]
    cell = args[0]
    n = int(args[1]) if len(args) > 1 else 6
    first = int(args[2]) if len(args) > 2 else 3_000_000_001
    seconds = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["run_seconds"]
    if "--trace-first" in flags:
        run(cell, first - 1, seconds, 1, "traced")
    sets = []
    for k in (1, 2):
        results = [run(cell, first + 104729 * i, seconds, 0, f"set{k}.run{i}")
                   for i in range(n)]
        sets.append([r for r in results if r is not None])
    names = sorted(sets[0][0]["metrics"]) if sets[0] else []
    for name in names:
        meds = []
        for k, results in enumerate(sets, 1):
            values = [r["metrics"][name]["value"] for r in results]
            # the first run of the first set is the one that compiles
            if name == "setup_s" and k == 1:
                values = values[1:]
            meds.append(statistics.median(values))
            print(f"{cell} {name} set {k}: median {meds[-1]:.6g}, spread "
                  f"{100 * spread(values):.3f}% of it, values "
                  f"{[round(v, 4) for v in values]}")
        print(f"{cell} {name}: second median / first = {meds[1] / meds[0]:.5f}")
    ok = all(r["correct"] for s in sets for r in s) and all(len(s) == n for s in sets)
    print(f"{cell}: all runs correct and complete: {ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
