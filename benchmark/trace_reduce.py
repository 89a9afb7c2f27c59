"""From a profiler trace to the four things the benchmark reads.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. The trace
holds device events only (``run.HOST_TRACER_LEVEL``). The traced window is
given on the host's clock, [t0, t1], together with the moment the host saw
the trace's last device operation end (a fence), which aligns the two
clocks to well under a millisecond. This file computes, inside that window:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the device planes;
- ``window_s``: the length of the traced window;
- ``ops``: device seconds by operation name (first device plane);
- ``gaps``: idle seconds of the first device by the host span that
  covers most of each gap.

What a v5e trace looks like under JAX 0.9 is listed in ``README.md``.
Device planes are named ``/device:TPU:<n>``; their line ``XLA Ops``
holds one event per executed HLO operation. Where a trace has no device
plane (the CPU rehearsal of ``benchmark/tests``) the events that carry an
``hlo_op`` stat on the host plane stand in for it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
NO_SPAN = "(no benchmark span)"


@dataclasses.dataclass
class Reduction:
    busy_s: float
    window_s: float
    ops: dict
    gaps: dict
    n_device_planes: int
    n_op_events: int
    longest_gaps: list = dataclasses.field(default_factory=list)

    def top_ops(self, n: int) -> list:
        """The operations that took most device time, under the names the
        trace gives them (the HLO instruction's text), layouts stripped
        and cut to 120 characters. A ``while`` or a fusion is listed with
        its whole duration, the operations inside it too."""
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        return [[re.sub(r"\{[^{}]*\}", "", k)[:120], v] for k, v in top]

    def top_gaps(self, n: int) -> list:
        return [[k, v] for k, v in sorted(self.gaps.items(), key=lambda kv: -kv[1])[:n]]

    def summary(self) -> str:
        gaps = ", ".join(f"{g * 1e3:.2f} ms at +{t * 1e3:.1f} ms ({n})"
                         for t, g, n in self.longest_gaps)
        return (f"{self.n_device_planes} device plane(s), {self.n_op_events} op "
                f"events, window {self.window_s:.4f}s, busy {self.busy_s:.4f}s; "
                f"longest idle gaps: {gaps}")


def union(intervals: list) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def find_xplane(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def _device_op_events(profile) -> list:
    """[(plane name, [(name, start_ns, end_ns), ...]), ...]."""
    planes = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        # "/device:TPU:0" and not a sub-unit's plane such as "... SparseCore"
        if not plane.name[len(DEVICE_PREFIX):].strip().isdigit():
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                planes.append((plane.name, [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]))
    if planes:
        return planes
    events = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0 and any(k == "hlo_op" for k, _ in e.stats):
                    events.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return [("host-executed ops", events)] if events else []


def reduce_file(path: str, t0: float, t1: float, fence: float,
                spans: list) -> Reduction:
    """``t0``, ``t1``: the traced window on the host's clock (seconds);
    ``fence``: when, on that clock, the host saw the last device operation
    of the trace end; ``spans``: [(name, start, end)] on the same clock."""
    from jax.profiler import ProfileData

    planes = _device_op_events(ProfileData.from_file(path))
    all_ends = [e for _, evs in planes for _, _, e in evs]
    if not all_ends:
        return Reduction(0.0, t1 - t0, {}, {}, 0, 0)
    last = max(all_ends)

    def on_device_clock(t: float) -> float:
        return last + (t - fence) * 1e9

    lo, hi = on_device_clock(t0), on_device_clock(t1)

    busy = []
    for _, evs in planes:
        u = union(_clip([(s, e) for _, s, e in evs], lo, hi))
        busy.append(sum(e - s for s, e in u))
    ops: dict = {}
    gaps: dict = {}
    longest: list = []
    first = planes[0][1]
    for name, s, e in first:
        if e > lo and s < hi:
            ops[name] = ops.get(name, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    u = union(_clip([(s, e) for _, s, e in first], lo, hi))
    edges = [lo] + [t for iv in u for t in iv] + [hi]
    mapped = [(n, on_device_clock(a), on_device_clock(b)) for n, a, b in spans]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        best, best_overlap = NO_SPAN, 0.0
        for n, s, e in mapped:
            overlap = min(e, g1) - max(s, g0)
            if overlap > best_overlap:
                best, best_overlap = n, overlap
        gaps[best] = gaps.get(best, 0.0) + (g1 - g0) / 1e9
        longest.append(((g0 - lo) / 1e9, (g1 - g0) / 1e9, best))
    return Reduction(
        busy_s=sum(busy) / len(busy) / 1e9, window_s=t1 - t0, ops=ops, gaps=gaps,
        n_device_planes=len(planes),
        n_op_events=sum(len(evs) for _, evs in planes),
        longest_gaps=sorted(longest, key=lambda g: -g[1])[:6])


def reduce_dir(directory: str, t0: float, t1: float, fence: float,
               spans: list) -> Reduction:
    return reduce_file(find_xplane(directory), t0, t1, fence, spans)


def listing(path: str, longest: int = 20) -> str:
    """Planes, lines and the longest event names of a trace, as text
    (``tools/list_trace.py``; the README's listing was made with it)."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    rows = []
    for plane in profile.planes:
        lines = list(plane.lines)
        rows.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                rows.append(f"  LINE {line.name!r}: 0 events")
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            rows.append(f"  LINE {line.name!r}: {len(evs)} events, "
                        f"{t0 / 1e6:.3f}..{t1 / 1e6:.3f} ms")
            if plane.name.startswith(DEVICE_PREFIX) or len(lines) < 12:
                total: dict = {}
                for e in evs:
                    total[e.name] = total.get(e.name, 0.0) + e.duration_ns
                for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[:longest]:
                    rows.append(f"      {ns / 1e6:10.3f} ms  {name[:110]}")
    return "\n".join(rows)
