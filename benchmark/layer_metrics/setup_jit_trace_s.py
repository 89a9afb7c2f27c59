"""Set-up: the union of JAX's ``jit.trace`` spans outside compiling, lowering
and the telemetry plan — the step's own trace at its first call, and every
smaller program's (``setup_parts.py``). Moves setup_s."""

import setup_parts


def read(run):
    return setup_parts.read(run, "setup_jit_trace_s")
