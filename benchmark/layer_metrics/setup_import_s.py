"""Set-up: from ``run.T_START`` to the start of the program's
``setup.compile_cache`` span — the interpreter, the benchmark's and JAX's
imports, the cell's files (``setup_parts.py``). Moves setup_s."""

import setup_parts


def read(run):
    return setup_parts.read(run, "setup_import_s")
