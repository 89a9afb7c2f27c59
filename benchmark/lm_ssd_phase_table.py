"""Device time by phase of the ``nemotron_h`` decoder's step, for the
``lm_ssd_*`` readers of ``layer_metrics/``: ``lm_phase_table.py``'s reduction (the same
file format and per-step rule) under the vocabulary
``lm_ssd_phases.json``.

``lm_phase_table`` names its vocabulary file in a module global; this
file runs a copy of that module of its own with the global pointing at
this family's file, so each vocabulary keeps its own cached table (as
``lm_gqa_phase_table.py`` does).

A reader that finds nothing to read (no traced training steps, no trace,
or a program without these scopes, as every commit before PR 48) gets
None and its metric is left out.
"""

from __future__ import annotations

import os

from run import load_module

HERE = os.path.dirname(os.path.abspath(__file__))

_table = load_module(HERE, "lm_phase_table")
_table.LM_PHASES_JSON = os.path.join(HERE, "lm_ssd_phases.json")

metric = _table.metric
