"""Set-up: the union of JAX's ``jit.compile`` spans — programs built by the
backend or loaded from the persistent cache (``setup_parts.py``). Moves
setup_s."""

import setup_parts


def read(run):
    return setup_parts.read(run, "setup_compile_load_s")
