"""Device time by phase of the ``deepseek_v3`` decoder's step, for the
``lm_mla_*`` readers of ``layer_metrics/`` that this family brought (the
older ``lm_mla_ms_per_step`` reads ``lm_phases.json`` through
``lm_phase_table.py`` itself): ``lm_phase_table.py``'s reduction (the same
file format and per-step rule) under the vocabulary
``lm_mla_phases.json``.

``lm_phase_table`` names its vocabulary file in a module global; this
file runs a copy of that module of its own with the global pointing at
this family's file, so each vocabulary keeps its own cached table (as
``lm_gqa_phase_table.py`` does).

A reader that finds nothing to read (no traced training steps, no trace,
or a program without these scopes, as every commit before PR 45) gets
None and its metric is left out.
"""

from __future__ import annotations

import os

from run import load_module

HERE = os.path.dirname(os.path.abspath(__file__))

_table = load_module(HERE, "lm_phase_table")
_table.LM_PHASES_JSON = os.path.join(HERE, "lm_mla_phases.json")

metric = _table.metric
