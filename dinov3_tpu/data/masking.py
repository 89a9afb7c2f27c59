"""BEiT-style block masking with fixed-capacity padded buffers.

(reference: dinov3_jax/data/masking.py ``MaskingGenerator`` — same block
sampling: repeatedly place log-uniform-aspect rectangles until the target
count is reached, then randomly top up/trim to the exact count
(``complete_mask_randomly``:91-100). On top, this emits the TPU-static
per-image buffers consumed by the meta-arch: token indices, per-token
weights (1/n_masked of the image), and validity (SURVEY.md §7.3).)

How many tokens a batch masks is a constant of the configuration, not of
the draw: one ``sample_ibot_masks`` call masks ``round(N * p)`` of its N
images, image j of them at exactly ``ibot_mask_targets(...)[j]`` tokens
(``block_mask`` tops up or trims to the target, and a target never
exceeds the per-image capacity), whichever images the permutation picks.
``masked_rows_bound`` is therefore not an estimate but the count itself,
rounded up: 1,881 of the 6,272 buffer rows at 64 global crops of 196
tokens, 30 % at every batch size. The train step sizes its batch-wide
compact iBOT buffer from it (train/ssl_meta_arch.py ``masked_rows``).
"""

from __future__ import annotations

import math

import numpy as np


def block_mask(
    rng: np.random.Generator,
    grid: tuple[int, int],
    n_target: int,
    min_aspect: float = 0.3,
    max_attempts: int = 10,
) -> np.ndarray:
    """[H, W] bool mask with approximately n_target True entries."""
    H, W = grid
    mask = np.zeros((H, W), dtype=bool)
    if n_target <= 0:
        return mask
    log_aspect = (math.log(min_aspect), math.log(1.0 / min_aspect))
    count = 0
    for _ in range(max_attempts):
        remaining = n_target - count
        if remaining <= 0:
            break
        # sample a block with area <= remaining
        target_area = rng.uniform(min(4, remaining), max(remaining, 4.01))
        aspect = math.exp(rng.uniform(*log_aspect))
        h = int(round(math.sqrt(target_area * aspect)))
        w = int(round(math.sqrt(target_area / aspect)))
        if h <= 0 or w <= 0 or h > H or w > W:
            continue
        top = rng.integers(0, H - h + 1)
        left = rng.integers(0, W - w + 1)
        region = mask[top: top + h, left: left + w]
        n_new = region.size - region.sum()
        if 0 < n_new:
            mask[top: top + h, left: left + w] = True
            count += n_new
    # exact count: randomly add or remove (reference complete_mask_randomly)
    flat = mask.reshape(-1)
    n_now = int(flat.sum())
    if n_now < n_target:
        off = np.flatnonzero(~flat)
        pick = rng.choice(off, size=n_target - n_now, replace=False)
        flat[pick] = True
    elif n_now > n_target:
        on = np.flatnonzero(flat)
        pick = rng.choice(on, size=n_now - n_target, replace=False)
        flat[pick] = False
    return flat.reshape(H, W)


def ibot_mask_targets(
    n_images: int,
    n_tokens: int,
    capacity: int,
    mask_ratio_min_max: tuple[float, float] = (0.1, 0.5),
    mask_probability: float = 0.5,
) -> list[int]:
    """Masked-token count of each masked image of ONE sampler call.

    ``round(n_images * mask_probability)`` images are masked, with ratios
    spread linearly across [min, max] (reference collate.py:47-65's
    linspaced probabilities), each capped at the per-image ``capacity``.
    ``sample_ibot_masks`` masks exactly these counts; which image gets
    which is the draw's, the list is not.
    """
    lo, hi = mask_ratio_min_max
    n_masked_images = int(round(n_images * mask_probability))
    ratios = np.linspace(lo, hi, max(n_masked_images, 1))[:n_masked_images]
    return [min(int(round(r * n_tokens)), capacity) for r in ratios]


def masked_rows_bound(
    n_images: int,
    n_tokens: int,
    capacity: int,
    mask_ratio_min_max: tuple[float, float] = (0.1, 0.5),
    mask_probability: float = 0.5,
    n_calls: int = 1,
    n_seen: int | None = None,
) -> int:
    """Rows a batch-wide buffer needs to hold every masked token.

    ``n_images`` mask rows (global crops) were made by ``n_calls`` equal
    ``sample_ibot_masks`` calls (one per host: each collates its own
    shard), so the batch masks ``n_calls`` times one call's targets —
    exactly, see the module docstring. ``n_seen``: the consumer holds
    only that many of the images (a gradient-accumulation microbatch,
    which the permutation may have dealt the most-masked images), so the
    bound is the sum of the ``n_seen`` largest targets.

    Rounded up to a multiple of 128: whole (8, 128) tiles in every dtype,
    and an even split over any power-of-two data axis. Never more than
    the per-image buffers' ``n_seen * capacity`` rows.
    """
    if n_images % n_calls:
        raise ValueError(
            f"{n_images} mask rows do not split over {n_calls} sampler calls")
    n_seen = n_images if n_seen is None else n_seen
    targets = sorted(
        ibot_mask_targets(n_images // n_calls, n_tokens, capacity,
                          mask_ratio_min_max, mask_probability) * n_calls,
        reverse=True)
    total = sum(targets[:n_seen])
    return max(1, min(-(-total // 128) * 128, n_seen * capacity))


def sample_ibot_masks(
    rng: np.random.Generator,
    n_images: int,
    n_tokens: int,
    capacity: int,
    grid: tuple[int, int],
    mask_ratio_min_max: tuple[float, float] = (0.1, 0.5),
    mask_probability: float = 0.5,
    random_circular_shift: bool = False,
):
    """Sample per-image block masks and pack fixed-capacity buffers.

    A ``mask_probability`` fraction of images is masked, image j of them
    at exactly ``ibot_mask_targets(...)[j]`` tokens.
    ``random_circular_shift`` rolls each block mask by a random 2-D offset
    (reference config ibot.mask_random_circular_shift) so block positions
    lose their center bias. Returns (masks [N, T] bool, indices [N, C]
    int32, weights [N, C] f32, valid [N, C] bool).
    """
    targets = ibot_mask_targets(
        n_images, n_tokens, capacity, mask_ratio_min_max, mask_probability)
    order = rng.permutation(n_images)
    masks = np.zeros((n_images, n_tokens), dtype=bool)
    indices = np.zeros((n_images, capacity), dtype=np.int32)
    weights = np.zeros((n_images, capacity), dtype=np.float32)
    valid = np.zeros((n_images, capacity), dtype=bool)
    for j, n_target in enumerate(targets):
        img = order[j]
        m2 = block_mask(rng, grid, n_target)
        if random_circular_shift:
            m2 = np.roll(
                m2,
                (int(rng.integers(grid[0])), int(rng.integers(grid[1]))),
                axis=(0, 1),
            )
        m = m2.reshape(-1)
        masks[img] = m
        idx = np.flatnonzero(m)[:capacity]
        k = len(idx)
        if k == 0:
            continue
        indices[img, :k] = idx
        weights[img, :k] = 1.0 / k
        valid[img, :k] = True
    return masks, indices, weights, valid
