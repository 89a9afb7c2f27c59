"""The one general traffic generator. A traffic mix is a data file of
parameters under ``traffic/``; this file turns it and ``--seed`` into
requests. (The banded draw on the patch grid is a copy of
``scripts/bench_serve.py`` ``make_mix``; the original is listed in
``PERF.md`` for a later PR to delete.)

Every seed gets the SAME set of image sizes (drawn once from the mix's
own ``sizes_seed``) in another order and with other pixels: a seed then
changes which requests meet in a pack, not how much work the run holds.
"""

from __future__ import annotations

import numpy as np


def draw_sizes(bands: list, n: int, grid: int, sizes_seed: int) -> list:
    """n (h, w) pairs: a band by its probability, then H and W drawn
    independently on the ``grid``-pixel grid inside the band."""
    rng = np.random.default_rng(int(sizes_seed))
    probs = np.array([p for p, _ in bands], dtype=np.float64)
    out = []
    for b in rng.choice(len(bands), size=n, p=probs / probs.sum()):
        lo, hi = bands[int(b)][1]
        sizes = np.arange(lo, hi + 1, grid)
        out.append((int(rng.choice(sizes)), int(rng.choice(sizes))))
    return out


def image_pool(mix: dict, seed: int, stream: int = 0) -> list:
    """The mix's pool of float32 HWC images: sizes from the mix
    (``stream`` > 0: a disjoint draw, for warm-up), pixels from ``seed``."""
    sizes = draw_sizes(mix["bands"], int(mix["pool_images"]), int(mix["grid"]),
                       int(mix["sizes_seed"]) + stream)
    rng = np.random.default_rng([int(seed) % (1 << 62), 23, stream])
    return [rng.standard_normal((h, w, 3), dtype=np.float32) for h, w in sizes]


def request_order(n_pool: int, seed: int):
    """Endless stream of pool indices: seeded permutation after seeded
    permutation, so every image is sent equally often."""
    rng = np.random.default_rng([int(seed) % (1 << 62), 29])
    while True:
        yield from (int(i) for i in rng.permutation(n_pool))
