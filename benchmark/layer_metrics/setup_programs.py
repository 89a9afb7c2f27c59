"""Set-up: ``jit.compile`` records before the window — programs built or
loaded, the driver's "backend compiles N" (``setup_parts.py``). Moves
setup_s."""

import setup_parts


def read(run):
    return setup_parts.read(run, "setup_programs")
