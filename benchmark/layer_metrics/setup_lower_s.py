"""Set-up: the union of JAX's ``jit.lower`` spans (jaxpr to MLIR module), less
what compiled inside them (``setup_parts.py``). Moves setup_s."""

import setup_parts


def read(run):
    return setup_parts.read(run, "setup_lower_s")
