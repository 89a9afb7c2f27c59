"""Set-up: the program's ``setup.telemetry_plan`` span — the ``eval_shape`` of
the whole raw step that fixes the metrics ring's columns, the first of the
step's two traces — less what compiled or lowered inside it
(``setup_parts.py``). Moves setup_s."""

import setup_parts


def read(run):
    return setup_parts.read(run, "setup_plan_trace_s")
