"""Noise-aware perf-regression gate over the committed anatomy baseline.

Compares a fresh anatomy/bench record against a committed baseline
artifact (ANATOMY_r17.json by default) and FAILS (rc=1) on step-time or
exposed-comm regressions beyond a calibrated tolerance — the CI teeth
of the step-anatomy plane: a PR that silently de-overlaps a collective
schedule or bloats the step now trips a gate instead of a reviewer's
eyeball.

Tolerance calibration (noise-aware, not a bare percentage): the
baseline's own per-step wall-time spread sets the noise floor —
``tol_rel = clamp(K * cv / sqrt(n), TOL_FLOOR, TOL_CAP)`` where ``cv``
is the baseline window's coefficient of variation (std/mean over its
traced steps) and ``n`` its step count. A quiet baseline gates tightly
(floor 3%), a noisy one gates loosely but never beyond the 8% cap — the
cap guarantees the acceptance property that a 10% step-time regression
ALWAYS fails. Exposed-comm is gated on ABSOLUTE fraction drift
(``+EXPOSED_TOL`` over baseline, default 0.05): a schedule that stops
hiding its comm moves this number by tens of points, and an absolute
gate is immune to tiny-denominator blowups.

Record formats accepted on both sides (auto-detected):
- ANATOMY_r17.json (``arms.<arm>.anatomy`` summaries) — gates every
  arm present in BOTH records;
- a bare ``anatomy-summary/v1`` dict, or a bench.py --trace JSONL
  record carrying one under ``"anatomy"`` — gates as a single arm.

Usage:
  python scripts/perf_gate.py --baseline ANATOMY_r17.json --fresh X.json
  python scripts/perf_gate.py --self-check [--baseline ANATOMY_r17.json]

--self-check (the CI invocation): gates the committed baseline against
ITSELF (must pass — same numbers, zero drift), then against synthetic
perturbations (x1.10 step time, +0.10 exposed fraction — both must
fail). rc=0 only when all three behave.
"""

from __future__ import annotations

import copy
import json
import math
import sys

TOL_FLOOR = 0.03   # tightest step-time gate even on a silent baseline
TOL_CAP = 0.08     # loosest gate ever allowed — keeps 10% regressions failing
NOISE_K = 3.0      # z-like multiplier on the baseline's mean-level noise
EXPOSED_TOL = 0.05  # absolute exposed-comm-fraction drift allowed


def step_time_tolerance(summary: dict) -> float:
    """Relative step-time tolerance calibrated from the baseline
    window's own noise (see module doc)."""
    wall = summary.get("step_wall_ms") or {}
    mean = float(wall.get("mean", 0.0) or 0.0)
    std = float(wall.get("std", 0.0) or 0.0)
    n = max(1, int(summary.get("n_steps", 1) or 1))
    cv = std / mean if mean > 0 else 0.0
    return min(TOL_CAP, max(TOL_FLOOR, NOISE_K * cv / math.sqrt(n)))


def extract_summaries(rec: dict) -> dict:
    """{arm_name: anatomy summary} from any accepted record shape."""
    if "arms" in rec:
        return {arm: blk["anatomy"] for arm, blk in rec["arms"].items()
                if isinstance(blk, dict) and "anatomy" in blk}
    if "anatomy" in rec and isinstance(rec["anatomy"], dict):
        return {"bench": rec["anatomy"]}
    if rec.get("schema") == "anatomy-summary/v1" or "step_wall_ms" in rec:
        return {"record": rec}
    raise ValueError(
        "unrecognized record: expected an ANATOMY artifact ('arms'), a "
        "bench --trace record ('anatomy'), or a bare anatomy summary")


def gate(baseline: dict, fresh: dict) -> dict:
    """Compare two records; returns {passed, checks: [...]} with one
    check row per (arm, metric). Arms present in only one record are
    skipped (reported, not failed — program sets may legitimately
    differ across artifact revisions)."""
    base = extract_summaries(baseline)
    new = extract_summaries(fresh)
    checks = []
    for arm in sorted(base):
        if arm not in new:
            checks.append({"arm": arm, "metric": "presence",
                           "status": "skipped (absent in fresh record)"})
            continue
        b, f = base[arm], new[arm]
        b_ms = float(b["step_wall_ms"]["mean"])
        f_ms = float(f["step_wall_ms"]["mean"])
        tol = step_time_tolerance(b)
        ratio = f_ms / b_ms if b_ms > 0 else math.inf
        ok = ratio <= 1.0 + tol
        checks.append({
            "arm": arm, "metric": "step_wall_ms",
            "baseline": round(b_ms, 3), "fresh": round(f_ms, 3),
            "ratio": round(ratio, 4), "tol_rel": round(tol, 4),
            "status": "ok" if ok else
            f"FAIL: step time regressed {100 * (ratio - 1):.1f}% "
            f"(> {100 * tol:.1f}% noise-calibrated tolerance)",
        })
        b_ex = float(b.get("exposed_comm_frac", 0.0) or 0.0)
        f_ex = float(f.get("exposed_comm_frac", 0.0) or 0.0)
        ok_ex = f_ex <= b_ex + EXPOSED_TOL
        checks.append({
            "arm": arm, "metric": "exposed_comm_frac",
            "baseline": round(b_ex, 4), "fresh": round(f_ex, 4),
            "tol_abs": EXPOSED_TOL,
            "status": "ok" if ok_ex else
            f"FAIL: exposed-comm fraction grew "
            f"{f_ex - b_ex:+.3f} (> +{EXPOSED_TOL} absolute tolerance) — "
            f"the overlap schedule stopped hiding its communication",
        })
    return {
        "passed": all("FAIL" not in c["status"] for c in checks),
        "n_arms": sum(1 for c in checks if c["metric"] == "step_wall_ms"),
        "checks": checks,
    }


def _perturb(rec: dict, *, ms_scale: float = 1.0,
             exposed_add: float = 0.0) -> dict:
    out = copy.deepcopy(rec)
    for s in extract_summaries(out).values():
        s["step_wall_ms"]["mean"] = s["step_wall_ms"]["mean"] * ms_scale
        s["exposed_comm_frac"] = min(
            1.0, float(s.get("exposed_comm_frac", 0.0) or 0.0) + exposed_add)
    return out


def self_check(baseline: dict) -> int:
    """baseline-vs-itself must pass; +10% step time and +0.10 exposed
    fraction must each fail. The acceptance property of ISSUE 13."""
    rows = []
    r0 = gate(baseline, baseline)
    rows.append(("identity", r0["passed"], True))
    r1 = gate(baseline, _perturb(baseline, ms_scale=1.10))
    rows.append(("step_time_x1.10", r1["passed"], False))
    r2 = gate(baseline, _perturb(baseline, exposed_add=0.10))
    rows.append(("exposed_+0.10", r2["passed"], False))
    ok = all(got == want for _, got, want in rows)
    print(json.dumps({
        "self_check": "ok" if ok else "FAIL",
        "n_arms": r0["n_arms"],
        "cases": [{"case": name, "passed": got, "expected_passed": want}
                  for name, got, want in rows],
    }, indent=1))
    return 0 if ok else 1


def _arg(flag: str, default=None):
    if flag in sys.argv:
        return sys.argv[sys.argv.index(flag) + 1]
    return default


def _load(path: str) -> dict:
    with open(path) as f:
        text = f.read().strip()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        # JSONL (bench output): gate the last record
        return json.loads(text.splitlines()[-1])


def main() -> int:
    baseline = _load(_arg("--baseline", "ANATOMY_r17.json"))
    if "--self-check" in sys.argv:
        return self_check(baseline)
    fresh_path = _arg("--fresh")
    if not fresh_path:
        print("usage: perf_gate.py [--baseline B.json] "
              "(--fresh F.json | --self-check)", file=sys.stderr)
        return 2
    result = gate(baseline, _load(fresh_path))
    print(json.dumps(result, indent=1))
    return 0 if result["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
