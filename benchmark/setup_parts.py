"""Set-up, split into named parts: the one helper of the seven ``setup_*``
readers in ``layer_metrics/``.

Set-up is what ``setup_s`` times: from ``run.T_START`` to the start of the
benchmark's own ``dispatch`` span of step ``traffic["warmup_steps"]`` — the
window's first, microseconds after the driver reads ``setup_s``. The program
records what it does in between in its process span log
(``dinov3_tpu/telemetry/spans.py LOG``: ``setup.*`` spans of its own code,
``jit.trace`` / ``jit.lower`` / ``jit.compile`` from JAX's events, by program
name), on ``T_START``'s clock. A checkout whose program keeps no such log
gives no reading (``None``), and the result line leaves the metrics out.

Six timed parts, each the UNION of its spans' intervals inside set-up (a
nested span counts once), made disjoint by precedence: an instant that
several cover belongs to the first of compile/load, lower, the telemetry
plan's trace, other traces, build, import — a program compiled inside
``setup.build`` is compile time, not build time. What no part covers is the
remainder; parts + remainder = the wall. A traced run logs one table: every
part, the remainder, each gap over a second with what ended before it and
what started after it, the ``setup.*`` spans with their self times, and the
ten programs with the most trace + lower + compile seconds.
"""

from __future__ import annotations

import time

# metric -> the spans it reads, in order of precedence (import last: it has
# no span of its own, it ends where ``setup.compile_cache`` starts)
PARTS = (
    ("setup_compile_load_s", "jit.compile"),
    ("setup_lower_s", "jit.lower"),
    ("setup_plan_trace_s", "setup.telemetry_plan"),
    ("setup_jit_trace_s", "jit.trace"),
    ("setup_build_s", "setup.build"),
    ("setup_import_s", None),
)
GAP_S = 1.0      # a stretch of the remainder this long is named in the table
TOP_PROGRAMS = 10


# ---- interval arithmetic on sorted lists of disjoint (a, b)

def union(intervals) -> list:
    out: list = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def minus(keep: list, cut: list) -> list:
    """``keep`` without what ``cut`` covers (both as ``union`` returns)."""
    out = []
    for a, b in keep:
        for c, d in cut:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def total(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def interval(rec: dict, t_start: float, t_end: float) -> tuple:
    """A record's interval, cut at set-up's ends."""
    a = rec["t_mono"]
    return max(a, t_start), min(a + rec["dur_ms"] / 1e3, t_end)


# ---- the split

def split(records: list, t_start: float, t_end: float) -> dict | None:
    """The six parts (seconds, by metric name), the remainder and its
    stretches, and the programs built or loaded, of the set-up
    ``[t_start, t_end]``; ``None`` when the log holds nothing."""
    records = [r for r in records if r["t_mono"] < t_end]
    if not records:
        return None
    import_end = min((r["t_mono"] for r in records
                      if r["name"] == "setup.compile_cache"), default=t_start)
    parts, covered = {}, []
    for metric, name in PARTS:
        mine = union([(t_start, min(import_end, t_end))] if name is None else
                     [interval(r, t_start, t_end) for r in records
                      if r["name"] == name])
        mine = minus(mine, covered)
        parts[metric] = total(mine)
        covered = union(covered + mine)
    gaps = minus([(t_start, t_end)], covered)
    return {"parts": parts, "wall_s": t_end - t_start,
            "remainder_s": total(gaps), "gaps": gaps,
            "programs": sum(r["name"] == "jit.compile" for r in records)}


def setup_end(run) -> float | None:
    """The start of the window's first dispatch, on the host's clock."""
    first = int(run.traffic["warmup_steps"])
    return next((s.t0 for s in run.spans
                 if s.name == "dispatch" and s.step == first), None)


# ---- the table

def program_name(rec_program: str) -> str:
    """``jit(step)`` (lowering, compiling) and ``step`` (tracing) are one
    program."""
    p = str(rec_program)
    return p[4:-1] if p.startswith("jit(") and p.endswith(")") else p


def programs_table(records: list) -> list:
    """``(seconds, name, {kind: [count, seconds]})`` of every program, most
    seconds first. A nested trace's seconds lie inside its caller's."""
    by_name: dict = {}

    def add(name, kind, n, seconds):
        cell = by_name.setdefault(program_name(name), {}).setdefault(
            kind, [0, 0.0])
        cell[0] += n
        cell[1] += seconds

    for r in records:
        if not r["name"].startswith("jit."):
            continue
        kind = r["name"][4:]
        if kind == "compile" and r.get("cached"):
            kind = "load"
        add(r["program"], kind, 1, r["dur_ms"] / 1e3)
        for name, (n, seconds) in r.get("nested", {}).items():
            add(name, "nested trace", n, seconds)
    return sorted(((sum(c[1] for c in kinds.values()), name, kinds)
                   for name, kinds in by_name.items()), reverse=True)


def self_seconds(rec: dict, records: list) -> float:
    a, b = rec["t_mono"], rec["t_mono"] + rec["dur_ms"] / 1e3
    children = union(interval(r, a, b) for r in records
                     if r.get("parent") == rec["id"])
    return (b - a) - total(children)


def neighbours(gap: tuple, named: list) -> tuple:
    """What ended last before the gap, what started first after it and the
    shortest span the gap lies in, of ``named`` = ``(name, t0, t1)``."""
    a, b = gap
    eps = 1e-3
    before = max((n for n in named if n[2] <= a + eps),
                 key=lambda n: n[2], default=("process start",))
    after = min((n for n in named if n[1] >= b - eps),
                key=lambda n: n[1], default=("the window",))
    around = min((n for n in named if n[1] <= a + eps and n[2] >= b - eps),
                 key=lambda n: n[2] - n[1], default=("no span",))
    return before[0], after[0], around[0]


def log_table(log, got: dict, records: list, run, t_start: float) -> None:
    log(f"set-up {got['wall_s']:.2f}s from T_START to the window's first "
        f"dispatch; {len(records)} records in the program's log")
    for metric, _ in PARTS:
        log(f"  {metric:22s} {got['parts'][metric]:9.3f}")
    log(f"  {'remainder':22s} {got['remainder_s']:9.3f}   "
        f"programs built or loaded: {got['programs']}")
    named = [(r["name"] + (f"[{r['program']}]" if "program" in r else ""),
              r["t_mono"], r["t_mono"] + r["dur_ms"] / 1e3) for r in records]
    named += [(f"bench:{s.name}[{s.step}]", s.t0, s.t1) for s in run.spans]
    for a, b in got["gaps"]:
        if b - a >= GAP_S:
            before, after, around = neighbours((a, b), named)
            log(f"  gap {b - a:8.3f}s at +{a - t_start:.2f}s: after {before}, "
                f"before {after}, inside {around}")
    for r in sorted(records, key=lambda r: r["t_mono"]):
        if r["name"].startswith("setup.") or r["name"] == "dispatch":
            log(f"  span {r['name']:24s} at +{r['t_mono'] - t_start:8.2f}s "
                f"{r['dur_ms'] / 1e3:9.3f}s, self "
                f"{self_seconds(r, records):9.3f}s")
    for seconds, name, kinds in programs_table(records)[:TOP_PROGRAMS]:
        log(f"  program {name}: {seconds:.3f}s = " + ", ".join(
            f"{kind} {n} x {s:.3f}s" for kind, (n, s) in sorted(kinds.items())))


# ---- what the readers call

def read(run, metric: str):
    """The reading of ``metric`` (a part's seconds, or ``setup_programs``)
    in this run, or ``None``. The first reader to ask splits the log and
    logs the table; the run keeps the split for the other six."""
    if not hasattr(run, "setup_split"):
        run.setup_split = _read_all(run)
    got = run.setup_split
    if got is None:
        return None
    return got["programs"] if metric == "setup_programs" else got["parts"][metric]


def _read_all(run) -> dict | None:
    from run import T_START, log

    from dinov3_tpu.telemetry import spans

    t0 = time.perf_counter()
    process_log = getattr(spans, "LOG", None)
    t_end = setup_end(run)
    if process_log is None or t_end is None:
        return None
    records = [r for r in process_log.records if r["t_mono"] < t_end]
    got = split(records, T_START, t_end)
    if got is not None:
        log_table(log, got, records, run, T_START)
        c = process_log.counters
        log(f"  the process so far: {c['programs_compiled']} programs built "
            f"or loaded, cache hits {c['cache_hits']}, misses "
            f"{c['cache_misses']}, {c['compile_time_saved_s']:.1f}s of "
            f"compiling saved")
        log(f"  the log dropped {process_log.dropped} records; splitting and "
            f"this table took {(time.perf_counter() - t0) * 1e3:.1f} ms")
    return got
