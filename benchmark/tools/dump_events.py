"""Print device events of a profiler trace in full — whole names and every
stat, which ``list_trace.py`` cuts — and write them all out:

    python3 benchmark/tools/dump_events.py <dir-or-file> [<out-dir>]

With ``<out-dir>``: ``events.json.gz`` there (``names``: the distinct
event names of the line ``XLA Ops`` of the first device plane, ``stats``:
the stats of each name's first event, ``events``: ``[name index, start
ns, duration ns]`` for every event in order) and, if it is under 40 MB
gzipped, the ``.xplane.pb`` itself.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHOWN_OPCODES = (" while(", " fusion(", " copy(", " convolution(", " custom-call(")


def _stats(event) -> dict:
    return {str(k): (v if isinstance(v, (int, float, str)) else repr(v))
            for k, v in event.stats}


def main(argv: list) -> int:
    from jax.profiler import ProfileData

    import phase_reduce
    import trace_reduce

    path = argv[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    profile = ProfileData.from_file(path)
    line = next((ln for plane in profile.planes
                 if phase_reduce.is_device_plane(plane.name)
                 for ln in plane.lines if ln.name == trace_reduce.OPS_LINE), None)
    if line is None:
        print(f"no line {trace_reduce.OPS_LINE!r} on a device plane of {path}")
        return 1
    names: dict = {}
    stats: list = []
    events: list = []
    shown = set()
    for e in line.events:
        if e.name not in names:
            names[e.name] = len(names)
            stats.append(_stats(e))
            kind = next((k for k in SHOWN_OPCODES if k in e.name), None)
            if not events or (kind and kind not in shown):
                shown.add(kind)
                print(f"EVENT {len(events)} start {e.start_ns} ns, "
                      f"{e.duration_ns} ns\n  NAME {e.name}")
                for k, v in stats[-1].items():
                    print(f"  STAT {k} = {v}")
        events.append([names[e.name], int(e.start_ns), int(e.duration_ns)])
    print(f"{len(events)} events, {len(names)} distinct names, "
          f"{sum(1 for n in names if 'op_name=' in n)} of them with op_name= in "
          f"the name; stat keys: {sorted({k for s in stats for k in s})}")
    if len(argv) > 1:
        os.makedirs(argv[1], exist_ok=True)
        with gzip.open(os.path.join(argv[1], "events.json.gz"), "wt") as f:
            json.dump({"names": list(names), "stats": stats, "events": events}, f)
        with open(path, "rb") as f:
            packed = gzip.compress(f.read(), 6)
        if len(packed) < 40 << 20:
            with open(os.path.join(argv[1], os.path.basename(path) + ".gz"), "wb") as f:
                f.write(packed)
        print(f"xplane {os.path.getsize(path)} bytes, {len(packed)} gzipped")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
