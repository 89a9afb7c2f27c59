"""Set-up: the program's ``setup.build`` span (``build_train_setup``: mesh,
meta-arch, abstract state, optimizer, shardings, the jitted step's
construction), less what compiled, lowered or traced inside it
(``setup_parts.py``). Moves setup_s."""

import setup_parts


def read(run):
    return setup_parts.read(run, "setup_build_s")
