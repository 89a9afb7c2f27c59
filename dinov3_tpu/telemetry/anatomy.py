"""Step-anatomy ledger: measured device-time attribution for one trace
window, plus the cross-host fleet report over the span JSONL streams.

The repo's overlap claims (ZeRO-3's in-scan weight streams, the
bucketed grad reduce-scatter) were verified only *statically* — the HLO
census places each collective inside/outside the backward while-loop
(``utils.hlo_collective_census`` ``by_placement``). This module is the
dynamic twin: it parses a ``jax.profiler`` trace window
(telemetry/trace.py) into a per-step **anatomy ledger** —

- device time split into op categories (matmul/conv, fusion/
  elementwise, copy/layout, softmax/exp, norm/reduce, collective),
- collective time attributed to the repo's named scopes
  (``bucket_*``/``zero3_*``/``update_shard``/``crop_pack``/
  ``telemetry_ring``/``serve_*``) by joining each trace op event
  against the compiled HLO's ``op_name`` metadata (trace events carry
  the instruction name, scopes live only in the HLO text),
- a **measured-overlap column**: each collective event interval is
  intersected against the union of concurrent non-collective device
  work on its own device timeline — exposed-comm ms and overlapped
  fraction per scope, per step,
- leaf device time by **compute phase** of the step
  (``utils.STEP_PHASES``, forward and backward apart) from the same
  join, with what no phase claims as "unattributed",
- a measured **backward interval** per timeline (the time span of ops
  whose ``op_name`` carries jax's ``transpose(...)`` backward stamp),
  so "the grad-RS sits inside the backward pass" becomes a statement
  about measured timestamps, not just loop nesting.

CPU-harness honesty: XLA:CPU executes each simulated device's thunks
sequentially on one worker thread, so within-timeline overlap is
structurally ~0 there — measured overlap fractions on the CPU harness
are LOWER bounds, and the exposed-comm column is the conservative
ceiling. Placement (backward-interval containment) and attribution are
exact on both backends. See docs/OBSERVABILITY.md.

``fleet_report`` joins the PR-6/PR-11 span JSONL streams
(``telemetry/spans*.jsonl``, schema v1) across hosts into per-host
step-time distributions, straggler z-scores, and an input-bound /
comm-bound / compute-bound verdict per window.
"""

from __future__ import annotations

import bisect
import glob
import json
import math
import os
import re

from dinov3_tpu.telemetry.trace import Trace, find_trace_file, load_trace

SCHEMA = "anatomy/v1"
SUMMARY_SCHEMA = "anatomy-summary/v1"

# op categories, shared with scripts/profile_step.py (whose ad-hoc
# classifier this replaces — see ``categorize``)
CATEGORIES = (
    "matmul/conv", "collective", "softmax/exp", "norm/reduce",
    "copy/layout", "fusion/elementwise", "other",
)

_MATMUL_TOKENS = frozenset(
    ("dot", "conv", "convolution", "einsum", "gemm", "matmul", "cudnn"))
_COPY_TOKENS = frozenset((
    "copy", "transpose", "reshape", "bitcast", "slice", "concatenate",
    "pad", "gather", "scatter", "convert", "dynamic",
))
_COLLECTIVE_KEYS = (
    "all-gather", "all-reduce", "reduce-scatter", "collective",
    "all-to-all", "psum", "permute",
)
_CONTAINER_OPCODES = frozenset(("while", "conditional", "call"))
_COPY_OPCODES = frozenset((
    "copy", "copy-start", "copy-done", "transpose", "reshape", "bitcast",
    "slice", "dynamic-slice", "dynamic-update-slice", "concatenate",
    "pad", "gather", "scatter", "convert",
))

_TOKEN_SPLIT = re.compile(r"[^a-z0-9]+")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?[\w.\-]+\s*\(.*\)\s*->.*\{")
_INST_RE = re.compile(
    r"^(?:ROOT\s+)?%([^\s=]+)\s*=\s*(?:\([^)]*\)|\S+)\s+([\w\-]+)\(")


def categorize(name: str, fusion_dotty: bool | None = None) -> str:
    """Device-op category from the instruction/fusion name.

    Replaces the ad-hoc classifier scripts/profile_step.py carried,
    fixing two miscounts: fusions whose kind-name carries a dot/conv
    token ("convolution_add_fusion") were binned "fusion/elementwise"
    (undercounting matmul/conv), and the bare substring test ``"conv"
    in name`` claimed every ``convert_element_type`` as a convolution.
    Matmul tokens now match on name *components*; ``fusion_dotty=True``
    (from the HLO op index — a fusion whose BODY contains a dot/conv)
    forces matmul/conv even when the kind-name hides it.
    """
    n = name.lower()
    for key in _COLLECTIVE_KEYS:
        if key in n:
            return "collective"
    parts = [p for p in _TOKEN_SPLIT.split(n) if p]
    if fusion_dotty or any(p in _MATMUL_TOKENS for p in parts):
        return "matmul/conv"
    if "softmax" in n or "exponential" in parts or "exp" in parts:
        return "softmax/exp"
    if "norm" in n or "rsqrt" in parts or "reduce" in parts \
            or "reduction" in parts:
        return "norm/reduce"
    if any(p in _COPY_TOKENS for p in parts):
        return "copy/layout"
    if "fusion" in parts:
        return "fusion/elementwise"
    return "other"


# ---------------------------------------------------------------------
# HLO op index: instruction name -> category/scope/placement
# ---------------------------------------------------------------------

def build_op_index(hlo_text: str) -> dict:
    """Parse one compiled HLO module's text into
    ``{instruction_name: info}`` for joining trace op events.

    ``info`` keys: ``opcode``, ``category`` (CATEGORIES), ``scope``
    (collectives only — ``utils.classify_collective_scope`` over the
    instruction line, "other" for model-structure collectives),
    ``coll_class`` (``utils.HLO_COLLECTIVE_CLASSES`` value or None),
    ``placement`` (``utils.hlo_collective_placement`` — while-loop /
    transpose markers in op_name), ``backward`` (op_name carries jax's
    ``transpose(...)`` backward stamp), ``phase`` and ``direction``
    (``utils.classify_step_phase`` of the op_name: the step's compute
    phase, ``utils.STEP_PHASES``, or None; "fwd" | "bwd").

    Fusion instructions are indexed with their called computation's
    body inspected: a fusion calling a computation that contains a
    ``dot``/``convolution`` categorizes as matmul/conv — the
    fusion-absorbs-matmul fix. Instructions inside fusion bodies do not
    execute as separate thunks and are not indexed themselves.
    """
    from dinov3_tpu.utils import (
        classify_collective,
        classify_collective_scope,
        classify_step_phase,
        hlo_collective_placement,
    )

    comp = None
    comp_has_dot: dict = {}
    insts: dict = {}          # name -> (opcode, line, comp)
    fusion_calls: dict = {}   # name -> called computation name
    for raw in hlo_text.splitlines():
        s = raw.strip()
        if _COMP_HEADER_RE.match(s):
            comp = s.split("(")[0].strip().lstrip("%")
            if comp.startswith("ENTRY"):
                comp = comp.split()[-1].lstrip("%")
            continue
        if s == "}":
            comp = None
            continue
        if comp is None or "=" not in s:
            continue
        m = _INST_RE.match(s)
        if not m:
            continue
        name, opcode = m.group(1), m.group(2)
        if opcode in ("dot", "convolution"):
            comp_has_dot[comp] = True
        if "fused" in comp:
            continue  # fusion-body ops never run as separate thunks
        insts[name] = (opcode, s)
        if opcode == "fusion":
            mc = re.search(r"calls=%([\w.\-]+)", s)
            if mc:
                fusion_calls[name] = mc.group(1)

    index: dict = {}
    for name, (opcode, line) in insts.items():
        coll_class = classify_collective(line)
        is_done_half = coll_class is None and re.match(
            r".*(all-gather|all-reduce|reduce-scatter|collective-permute|"
            r"all-to-all)-done$", opcode)
        backward = False
        m = _OP_NAME_RE.search(line)
        if m and "transpose" in m.group(1):
            backward = True
        phase, direction = classify_step_phase(m.group(1) if m else None)
        if coll_class is not None or is_done_half:
            category = "collective"
            scope = classify_collective_scope(line)
            placement = hlo_collective_placement(line)
        elif opcode in ("dot", "convolution"):
            category, scope, placement = "matmul/conv", None, None
        elif opcode == "fusion":
            dotty = bool(comp_has_dot.get(fusion_calls.get(name, ""), False))
            category = categorize(name, fusion_dotty=dotty)
            scope = placement = None
        elif opcode in _COPY_OPCODES:
            category, scope, placement = "copy/layout", None, None
        else:
            category = categorize(name)
            scope = placement = None
        index[name] = {
            "opcode": opcode,
            "category": category,
            "scope": scope,
            "coll_class": coll_class,
            "placement": placement,
            "backward": backward,
            "phase": phase,
            "direction": direction,
        }
    return index


# ---------------------------------------------------------------------
# interval arithmetic (times in us; exact within float)
# ---------------------------------------------------------------------

def merge_intervals(intervals: list) -> list:
    """Sorted union of half-open ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def intersect_length(start: float, end: float, merged: list) -> float:
    """Total length of ``[start, end)`` covered by a merged interval
    union (``merge_intervals`` output)."""
    if end <= start or not merged:
        return 0.0
    starts = [s for s, _ in merged]
    i = max(0, bisect.bisect_right(starts, start) - 1)
    total = 0.0
    while i < len(merged):
        s, e = merged[i]
        if s >= end:
            break
        lo, hi = max(s, start), min(e, end)
        if hi > lo:
            total += hi - lo
        i += 1
    return total


def step_windows(events: list, n_steps: int | None = None) -> list:
    """Split one timeline's op events into per-step windows.

    With ``n_steps`` given (the caller traced a known step range), the
    boundaries are the ``n_steps - 1`` largest idle gaps between
    consecutive events — the host-side inter-step pauses dwarf
    intra-step thunk gaps. Returns ``[(t0, t1), ...]`` half-open
    windows in event-time microseconds; a single window covering
    everything when ``n_steps`` is absent or the timeline is too
    sparse to split."""
    if not events:
        return []
    evs = sorted(events, key=lambda e: e.ts)
    t_end = max(e.end for e in evs)
    if not n_steps or n_steps <= 1 or len(evs) < n_steps:
        return [(evs[0].ts, t_end)]
    gaps = []
    run_end = evs[0].end
    for i in range(1, len(evs)):
        gaps.append((evs[i].ts - run_end, i))
        run_end = max(run_end, evs[i].end)
    cuts = sorted(i for _, i in
                  sorted(gaps, key=lambda g: -g[0])[: n_steps - 1])
    bounds = [evs[0].ts] + [evs[i].ts for i in cuts] + [t_end + 1e-9]
    return [(bounds[k], bounds[k + 1]) for k in range(len(bounds) - 1)]


# ---------------------------------------------------------------------
# the anatomy ledger
# ---------------------------------------------------------------------

def _event_info(event, op_index: dict | None) -> dict:
    """Category/scope/backward/phase attribution for one trace op event:
    exact from the HLO op index when the instruction is found, name
    heuristics otherwise. A collective-looking event MISSING from a
    provided index is scope "unattributed" — the structural-regression
    bucket the artifact pins at zero. ``phase_key``: where the event's
    time goes in the per-step ``phases`` table — "<phase>/<fwd|bwd>",
    "unattributed" for an op under no phase, None for a container
    (``while``/``conditional``/``call``: its body's events are on the
    same timeline and are counted themselves)."""
    info = (op_index or {}).get(event.op_key)
    if info is not None:
        scope = info["scope"]
        if info["opcode"] in _CONTAINER_OPCODES:
            phase_key = None
        elif info["phase"] is None:
            phase_key = "unattributed"
        else:
            phase_key = f"{info['phase']}/{info['direction']}"
        return {"category": info["category"],
                "scope": scope if scope is not None else None,
                "backward": info["backward"],
                "placement": info["placement"],
                "phase_key": phase_key}
    cat = categorize(event.name)
    scope = None
    if cat == "collective":
        scope = "unattributed" if op_index else "unscoped"
    return {"category": cat, "scope": scope, "backward": False,
            "placement": None,
            "phase_key": "unattributed" if op_index else None}


def anatomy_ledger(
    trace: Trace | str,
    hlo_text: str | None = None,
    module: str | None = None,
    n_steps: int | None = None,
) -> dict:
    """Per-step anatomy ledger for one trace window.

    ``trace``: a loaded ``Trace`` or a path/dir (resolved through
    ``find_trace_file``). ``module`` filters op events by hlo_module
    substring (default: the dominant module by device time, when the
    backend annotates one). ``hlo_text``: the compiled module's text —
    enables exact categories, named-scope collective attribution and
    the backward stamp; without it the ledger falls back to name
    heuristics and collective scopes read "unscoped".

    Timelines (devices) are split into ``n_steps`` windows
    independently (each device's ops are sequential on its own
    timeline), then window k aggregates across timelines — so step k's
    row sums every device's k-th execution even when the host
    interleaved their dispatch.
    """
    if isinstance(trace, str):
        path = find_trace_file(trace)
        if path is None:
            raise FileNotFoundError(f"no *.trace.json.gz under {trace!r}")
        trace = load_trace(path)
    if module is None:
        mods = trace.modules()
        module = max(mods, key=mods.get) if mods else None
    events = trace.op_events(module=module)
    op_index = build_op_index(hlo_text) if hlo_text else None
    timelines = trace.timelines(events)

    steps: list = []
    n_windows = max(
        [len(step_windows(evs, n_steps)) for evs in timelines.values()],
        default=0)
    for k in range(n_windows):
        acc_cat = {c: 0.0 for c in CATEGORIES}
        phases: dict = {}
        coll: dict = {}
        busy = 0.0
        backward_ms = 0.0
        t0 = math.inf
        t1 = -math.inf
        tl_busy: list = []
        for evs in timelines.values():
            wins = step_windows(evs, n_steps)
            if k >= len(wins):
                continue
            w0, w1 = wins[k]
            wevs = [e for e in evs if w0 <= e.ts < w1]
            if not wevs:
                continue
            t0 = min(t0, min(e.ts for e in wevs))
            t1 = max(t1, max(e.end for e in wevs))
            infos = [(e, _event_info(e, op_index)) for e in wevs]
            # per-timeline compute union: every non-collective device op
            # counts as work a concurrent collective would hide behind
            compute_union = merge_intervals(
                [(e.ts, e.end) for e, i in infos
                 if i["category"] != "collective"])
            bwd = [(e.ts, e.end) for e, i in infos if i["backward"]]
            bwd_iv = (min(s for s, _ in bwd), max(e for _, e in bwd)) \
                if bwd else None
            if bwd_iv:
                backward_ms += (bwd_iv[1] - bwd_iv[0]) / 1e3
            tb = 0.0
            for e, i in infos:
                acc_cat[i["category"]] += e.dur / 1e3
                tb += e.dur / 1e3
                if i["phase_key"] is not None:
                    phases[i["phase_key"]] = (
                        phases.get(i["phase_key"], 0.0) + e.dur / 1e3)
                if i["category"] != "collective":
                    continue
                scope = i["scope"] or "unscoped"
                ent = coll.setdefault(scope, {
                    "ms": 0.0, "exposed_ms": 0.0, "overlapped_ms": 0.0,
                    "inside_backward_ms": 0.0, "n_events": 0,
                })
                ov = intersect_length(e.ts, e.end, compute_union)
                ent["ms"] += e.dur / 1e3
                ent["overlapped_ms"] += ov / 1e3
                ent["exposed_ms"] += (e.dur - ov) / 1e3
                ent["n_events"] += 1
                if bwd_iv:
                    lo = max(e.ts, bwd_iv[0])
                    hi = min(e.end, bwd_iv[1])
                    if hi > lo:
                        ent["inside_backward_ms"] += (hi - lo) / 1e3
            busy += tb
            tl_busy.append(tb)
        for ent in coll.values():
            ent["overlap_frac"] = (
                ent["overlapped_ms"] / ent["ms"] if ent["ms"] else 0.0)
            ent["inside_backward_frac"] = (
                ent["inside_backward_ms"] / ent["ms"] if ent["ms"] else 0.0)
        exposed_total = sum(c["exposed_ms"] for c in coll.values())
        spread = 0.0
        if tl_busy and max(tl_busy) > 0:
            mean_b = sum(tl_busy) / len(tl_busy)
            spread = (max(tl_busy) - min(tl_busy)) / mean_b if mean_b else 0.0
        steps.append({
            "step": k,
            "wall_ms": (t1 - t0) / 1e3 if t1 > t0 else 0.0,
            "device_busy_ms": busy,
            "device_ms": {c: v for c, v in acc_cat.items() if v > 0},
            # leaf device time by compute phase of the step
            # (utils.STEP_PHASES; empty without the compiled HLO)
            "phases": phases,
            "collectives": coll,
            "exposed_comm_frac": exposed_total / busy if busy else 0.0,
            "backward_ms": backward_ms,
            "device_step_spread": spread,
        })

    unattributed_ms = sum(
        s["collectives"].get("unattributed", {}).get("ms", 0.0)
        for s in steps)
    return {
        "schema": SCHEMA,
        "trace_path": trace.path,
        "module": module,
        "hlo_joined": op_index is not None,
        "n_steps": len(steps),
        "n_timelines": len(timelines),
        "timelines": sorted(timelines),
        "steps": steps,
        "unattributed_collective_ms": unattributed_ms,
    }


def ledger_summary(ledger: dict) -> dict:
    """Flat per-step summary of one ledger — the block bench.py embeds
    in its record and the train loop emits as an ``anatomy`` span."""
    steps = ledger["steps"]
    n = max(1, len(steps))
    walls = [s["wall_ms"] for s in steps]
    mean_wall = sum(walls) / n
    var = sum((w - mean_wall) ** 2 for w in walls) / n if steps else 0.0
    cats: dict = {}
    phases: dict = {}
    coll: dict = {}
    busy = 0.0
    for s in steps:
        busy += s["device_busy_ms"]
        for c, v in s["device_ms"].items():
            cats[c] = cats.get(c, 0.0) + v
        for k, v in s["phases"].items():
            phases[k] = phases.get(k, 0.0) + v
        for scope, ent in s["collectives"].items():
            agg = coll.setdefault(scope, {
                "ms": 0.0, "exposed_ms": 0.0, "overlapped_ms": 0.0,
                "inside_backward_ms": 0.0, "n_events": 0})
            for key in agg:
                agg[key] += ent[key]
    out_coll = {}
    for scope, agg in coll.items():
        out_coll[scope] = {
            "ms_per_step": agg["ms"] / n,
            "exposed_ms_per_step": agg["exposed_ms"] / n,
            "overlap_frac": agg["overlapped_ms"] / agg["ms"]
            if agg["ms"] else 0.0,
            "inside_backward_frac": agg["inside_backward_ms"] / agg["ms"]
            if agg["ms"] else 0.0,
            "n_events": agg["n_events"],
        }
    exposed = sum(a["exposed_ms"] for a in coll.values())
    spreads = [s["device_step_spread"] for s in steps]
    return {
        "schema": SUMMARY_SCHEMA,
        "module": ledger["module"],
        "n_steps": ledger["n_steps"],
        "n_timelines": ledger["n_timelines"],
        "hlo_joined": ledger["hlo_joined"],
        "step_wall_ms": {
            "mean": mean_wall, "std": math.sqrt(var),
            "min": min(walls) if walls else 0.0,
            "max": max(walls) if walls else 0.0,
        },
        "device_ms_per_step": {c: v / n for c, v in cats.items()},
        "device_busy_ms_per_step": busy / n,
        "collectives": out_coll,
        "exposed_comm_ms_per_step": exposed / n,
        "exposed_comm_frac": exposed / busy if busy else 0.0,
        "straggler_spread": sum(spreads) / n if steps else 0.0,
        "unattributed_collective_ms":
            ledger["unattributed_collective_ms"],
        # the phase table needs the compiled HLO's op_names: the
        # name-only path has none, and its summary keeps its old shape
        **({"phases_ms_per_step": {k: v / n for k, v in phases.items()}}
           if ledger["hlo_joined"] else {}),
    }


# ---------------------------------------------------------------------
# fleet report over the span JSONL streams
# ---------------------------------------------------------------------

def load_span_streams(path: str, role: str = "train") -> dict:
    """Load ``telemetry/spans*.jsonl`` streams under ``path`` (an
    output dir or its telemetry/ subdir) into ``{host_id: [records]}``,
    schema-v1 records of ``role`` only. Host ids come from the
    role/rank file naming (``spans[.<role>][.rankN].jsonl``)."""
    tdir = path
    if os.path.isdir(os.path.join(path, "telemetry")):
        tdir = os.path.join(path, "telemetry")
    streams: dict = {}
    for f in sorted(glob.glob(os.path.join(tdir, "spans*.jsonl"))):
        stem = os.path.basename(f)[: -len(".jsonl")]
        parts = stem.split(".")[1:]  # after "spans"
        rank = next((p for p in parts if p.startswith("rank")), "rank0")
        recs = []
        with open(f) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail line of a live writer
                if r.get("v") != 1:
                    continue
                if role and r.get("role", "train") != role:
                    continue
                recs.append(r)
        if recs:
            streams[rank] = streams.get(rank, []) + recs
    return streams


def _dist(xs: list) -> dict:
    n = len(xs)
    if not n:
        return {"n": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0, "std": 0.0}
    ss = sorted(xs)
    mean = sum(xs) / n
    var = sum((x - mean) ** 2 for x in xs) / n
    return {
        "n": n, "mean": mean,
        "p50": ss[min(n - 1, int(0.50 * n))],
        "p95": ss[min(n - 1, int(0.95 * n))],
        "std": math.sqrt(var),
    }


def fleet_report(
    streams: dict | str,
    anatomy: dict | None = None,
    input_bound_frac: float = 0.25,
    exposed_comm_tol: float = 0.25,
    straggler_z: float = 2.0,
) -> dict:
    """Join per-host span streams into the fleet view.

    Per host: the step-time distribution (consecutive ``dispatch`` span
    start deltas — wall-clock step pitch; falls back to summed phase
    durations when a stream has < 2 dispatch spans) and the data-wait
    fraction. Fleet: straggler z-scores of each host's mean step time
    against the fleet distribution (0 when a single host reports —
    the CPU harness), and the bound verdict:

    - **input-bound** when data-wait consumes more than
      ``input_bound_frac`` of the step pitch,
    - else **comm-bound** when a supplied anatomy summary measures an
      exposed-collective fraction above ``exposed_comm_tol``,
    - else **compute-bound**.
    """
    if isinstance(streams, str):
        streams = load_span_streams(streams)
    hosts: dict = {}
    for host, recs in sorted(streams.items()):
        per_phase: dict = {}
        dispatch: list = []
        for r in recs:
            name = r.get("name")
            if name == "dispatch" and r.get("iteration") is not None:
                dispatch.append((int(r["iteration"]), float(r.get("t", 0))))
            if "dur_ms" in r and name:
                per_phase.setdefault(name, []).append(float(r["dur_ms"]))
        dispatch.sort()
        step_ms = [
            (t1 - t0) * 1e3
            for (i0, t0), (i1, t1) in zip(dispatch, dispatch[1:])
            if i1 == i0 + 1 and t1 > t0
        ]
        if not step_ms:
            # degenerate stream: approximate the pitch by the host
            # phases that tile a step
            n = min((len(per_phase.get(p, []))
                     for p in ("dispatch",)), default=0)
            step_ms = [
                sum(per_phase.get(p, [0.0] * n)[i]
                    for p in ("data_wait", "h2d", "dispatch")
                    if i < len(per_phase.get(p, [])))
                for i in range(n)
            ]
        dist = _dist(step_ms)
        data_wait = per_phase.get("data_wait", [])
        dw_mean = sum(data_wait) / len(data_wait) if data_wait else 0.0
        hosts[host] = {
            "step_ms": dist,
            "data_wait_ms_mean": dw_mean,
            "data_wait_frac": dw_mean / dist["mean"] if dist["mean"] else 0.0,
            "n_spans": len(recs),
        }
    means = [h["step_ms"]["mean"] for h in hosts.values()
             if h["step_ms"]["n"]]
    fleet_mean = sum(means) / len(means) if means else 0.0
    fleet_var = (sum((m - fleet_mean) ** 2 for m in means) / len(means)
                 if means else 0.0)
    fleet_std = math.sqrt(fleet_var)
    stragglers = []
    for host, h in hosts.items():
        z = ((h["step_ms"]["mean"] - fleet_mean) / fleet_std
             if fleet_std > 0 and len(means) > 1 else 0.0)
        h["straggler_z"] = z
        if z > straggler_z:
            stragglers.append(host)
    dw_fracs = [h["data_wait_frac"] for h in hosts.values()]
    dw_frac = max(dw_fracs) if dw_fracs else 0.0
    exposed = (anatomy or {}).get("exposed_comm_frac")
    if dw_frac > input_bound_frac:
        verdict = "input-bound"
    elif exposed is not None and exposed > exposed_comm_tol:
        verdict = "comm-bound"
    else:
        verdict = "compute-bound"
    return {
        "schema": "fleet/v1",
        "n_hosts": len(hosts),
        "hosts": hosts,
        "fleet_step_ms": {"mean": fleet_mean, "std": fleet_std},
        "stragglers": stragglers,
        "max_data_wait_frac": dw_frac,
        "exposed_comm_frac": exposed,
        "verdict": verdict,
    }


# ---------------------------------------------------------------------
# train-loop wiring (--profile-steps) + shared artifact plumbing
# ---------------------------------------------------------------------

def round_floats(obj, ndigits: int = 4):
    """Round every float in a JSON-shaped structure — committed
    artifacts and their re-derivation tests round identically, so
    equivalence pins compare exact."""
    if isinstance(obj, float):
        return round(obj, ndigits)
    if isinstance(obj, dict):
        return {k: round_floats(v, ndigits) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [round_floats(v, ndigits) for v in obj]
    return obj


def emit_step_anatomy(
    trace_dir: str,
    hlo_text: str | None = None,
    n_steps: int | None = None,
    module: str | None = None,
    tracer=None,
    cfg=None,
    iteration: int | None = None,
    out_path: str | None = None,
) -> dict | None:
    """Fold a just-stopped profiler window into the telemetry stream:
    parse the newest trace under ``trace_dir`` into a ledger, write the
    full ledger JSON next to it (``anatomy.json`` by default), emit the
    flat summary as an ``anatomy`` span record through ``tracer``, and
    fire the ``warn_exposed_comm`` guardrail against ``cfg``. Returns
    the summary (None when no trace file is found)."""
    path = find_trace_file(trace_dir)
    if path is None:
        return None
    ledger = anatomy_ledger(load_trace(path), hlo_text=hlo_text,
                            module=module, n_steps=n_steps)
    summary = ledger_summary(ledger)
    out_path = out_path or os.path.join(trace_dir, "anatomy.json")
    with open(out_path, "w") as f:
        json.dump(round_floats(ledger), f, indent=1)
    warn = None
    if cfg is not None:
        from dinov3_tpu.configs.config import warn_exposed_comm

        warn = warn_exposed_comm(cfg, summary)
    if tracer is not None:
        import time

        tracer.emit({
            "name": "anatomy",
            "iteration": None if iteration is None else int(iteration),
            "t": round(time.time(), 6),
            "summary": round_floats(summary),
            "ledger_path": out_path,
            **({"warn": warn} if warn else {}),
        })
    return summary
