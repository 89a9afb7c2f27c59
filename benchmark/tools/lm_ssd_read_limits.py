"""``lm_read_limits.py`` for the ``nemotron_h`` decoder's check: the same
procedure (sound seeds, then each control put in the program's place and
laid against the float32 reference of the same seed, weights, tokens and
expert choices) reading ``lm_ssd_step_check.py``'s numbers.

    python3 benchmark/tools/lm_ssd_read_limits.py nemotron3-nano-ep16-pretrain \\
        lm-ssd-pretrain-steps-8k <seeds> <control seeds> [first seed] \\
        [bf16,norm_then_gate,one_group,relu,drop_expert]

The tool it runs names its check module ``lm_step_check``: this file
stands this family's check under that name before it starts.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH, HERE]

import lm_ssd_step_check  # noqa: E402  (it imports the seven numbers' own module first)

sys.modules["lm_step_check"] = lm_ssd_step_check

import lm_read_limits  # noqa: E402

if __name__ == "__main__":
    sys.exit(lm_read_limits.main(sys.argv[1:]))
