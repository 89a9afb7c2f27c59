"""Print what a profiler trace holds: ``python3 benchmark/tools/list_trace.py <dir-or-file>``."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list) -> int:
    import trace_reduce

    path = argv[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(trace_reduce.listing(path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
