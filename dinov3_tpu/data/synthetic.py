"""Synthetic random-data backend, a first-class config option.

The reference's "synthetic data" was a stubbed decoder buried in the real
dataset path (dinov3_jax/data/datasets/decoders.py:31-34 returning random
images); here it is an explicit backend (``data.backend=synthetic``)
producing batches with the exact train-step contract, so smoke runs and
benchmarks need no disk at all (SURVEY.md §4 implication (b)).
"""

from __future__ import annotations

import numpy as np

from dinov3_tpu.configs import ConfigNode
from dinov3_tpu.configs.config import is_lm_arch
from dinov3_tpu.data.masking import sample_ibot_masks


def batch_spec(cfg: ConfigNode, batch_size: int) -> dict:
    """Shapes/dtypes of one host batch (B images per batch; for a token
    decoder, B sequences of ``lm.seq_len`` ids)."""
    B = batch_size
    if is_lm_arch(cfg):
        return {"tokens": ((B, int(cfg.lm.seq_len)), np.int32)}
    p = cfg.student.patch_size
    S = cfg.crops.global_crops_size
    s = cfg.crops.local_crops_size
    n_l = cfg.crops.local_crops_number
    T = (S // p) ** 2
    M = max(1, int(T * cfg.ibot.mask_ratio_min_max[1]))
    spec = {
        "global_crops": ((2 * B, S, S, 3), np.float32),
        "local_crops": ((n_l * B, s, s, 3), np.float32),
        "masks": ((2 * B, T), bool),
        "mask_indices": ((2 * B, M), np.int32),
        "mask_weights": ((2 * B, M), np.float32),
        "mask_valid": ((2 * B, M), bool),
    }
    if cfg.crops.gram_teacher_crops_size:
        G = cfg.crops.gram_teacher_crops_size
        spec["gram_teacher_crops"] = ((2 * B, G, G, 3), np.float32)
    return spec


def make_synthetic_batch(
    cfg: ConfigNode, batch_size: int, seed=0
) -> dict:
    rng = np.random.default_rng(seed)
    spec = batch_spec(cfg, batch_size)
    if is_lm_arch(cfg):
        # ids uniform over the vocabulary the recipe holds (a slice of
        # the published one: the traffic draws from the slice)
        return {"tokens": rng.integers(
            0, int(cfg.lm.vocab_size), spec["tokens"][0], dtype=np.int32)}
    B = batch_size
    p = cfg.student.patch_size
    S = cfg.crops.global_crops_size
    T = (S // p) ** 2
    M = spec["mask_indices"][0][1]

    batch = {
        "global_crops": rng.standard_normal(
            spec["global_crops"][0], dtype=np.float32),
        "local_crops": rng.standard_normal(
            spec["local_crops"][0], dtype=np.float32),
    }
    masks, idx, w, valid = sample_ibot_masks(
        rng, n_images=2 * B, n_tokens=T, capacity=M,
        grid=(S // p, S // p),
        mask_ratio_min_max=tuple(cfg.ibot.mask_ratio_min_max),
        mask_probability=cfg.ibot.mask_sample_probability,
        random_circular_shift=bool(
            cfg.ibot.get("mask_random_circular_shift", False)),
    )
    batch["masks"] = masks
    batch["mask_indices"] = idx
    batch["mask_weights"] = w
    batch["mask_valid"] = valid
    if "gram_teacher_crops" in spec:
        batch["gram_teacher_crops"] = rng.standard_normal(
            spec["gram_teacher_crops"][0], dtype=np.float32)
    return batch


class SyntheticDataset:
    """Iterator over synthetic batches (infinite).

    ``train.cache_dataset`` (reference config key) pregenerates a small
    pool of batches and cycles it, removing per-step host generation cost
    — useful when the host CPU or host->device link is the bottleneck.
    """

    CACHE_POOL = 8

    def __init__(self, cfg: ConfigNode, batch_size: int, seed: int = 0,
                 rank: int = 0, world_size: int = 1, advance: int = 0):
        """``batch_size`` is the per-host (local) batch; hosts draw
        disjoint streams via the (seed, rank, ordinal) RNG key, and
        ``advance`` skips the first n batches (data-stream resume)."""
        self.cfg = cfg
        self.batch_size = batch_size
        self.seed = seed
        self.rank = rank
        self.world_size = world_size
        self.advance = advance
        self.cache = bool(cfg.train.get("cache_dataset", False))

    def _batch(self, i: int) -> dict:
        return make_synthetic_batch(
            self.cfg, self.batch_size, seed=(self.seed, self.rank, i)
        )

    def __iter__(self):
        if self.cache:
            pool = [self._batch(i) for i in range(self.CACHE_POOL)]
            i = self.advance
            while True:
                yield pool[i % len(pool)]
                i += 1
        i = self.advance
        while True:
            yield self._batch(i)
            i += 1
