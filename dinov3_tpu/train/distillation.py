"""Distillation support: frozen pretrained teacher from its own config.

(reference: dinov3_jax/train/ssl_meta_arch.py ``_setup_distillation``
:257-286 — loads the teacher's full config from
``distillation.full_cfg_path``, asserts prototype/patch compatibility,
and builds the teacher backbone + heads from it. The reference never
loaded the weights (``checkpoint_path`` unused) and its meta-arch still
EMA-blended the teacher; here the teacher restores from a framework
checkpoint and is exempt from the EMA by construction.)
"""

from __future__ import annotations

import logging

from dinov3_tpu.configs import ConfigNode, load_config

logger = logging.getLogger("dinov3")


def resolve_distillation_cfg(cfg: ConfigNode) -> ConfigNode:
    """Merged (default <- teacher yaml) config for the frozen teacher."""
    path = cfg.distillation.full_cfg_path
    if not path:
        raise ValueError(
            "distillation.enabled=true requires distillation.full_cfg_path"
        )
    teacher_cfg = load_config(path)
    if not teacher_cfg.ibot.separate_head:
        raise ValueError("distillation teacher must use ibot.separate_head")
    for section in ("dino", "ibot"):
        t = teacher_cfg[section]["head_n_prototypes"]
        s = cfg[section]["head_n_prototypes"]
        if t != s:
            raise ValueError(
                f"{section}.head_n_prototypes mismatch: teacher {t} vs "
                f"student {s} (losses share the prototype space)"
            )
    if teacher_cfg.student.patch_size != cfg.student.patch_size:
        raise ValueError(
            "teacher and student patch_size must match "
            f"({teacher_cfg.student.patch_size} vs {cfg.student.patch_size})"
        )
    logger.info("distillation teacher config: %s", path)
    return teacher_cfg


def load_teacher_params(cfg: ConfigNode, state, state_shardings):
    """Restore the frozen teacher's weights from a framework checkpoint.

    ``distillation.checkpoint_path`` points at a Checkpointer directory of
    the teacher's own pretraining run; its **teacher** branch (the EMA
    weights — the ones DINOv3 evaluates and distills from) is restored
    into this run's ``params["teacher"]`` subtree, sharded per this run's
    layout.
    """
    import jax
    import orbax.checkpoint as ocp

    from dinov3_tpu.checkpoint import pytree_restore_args

    path = cfg.distillation.checkpoint_path
    if not path:
        return state
    with ocp.CheckpointManager(path) as manager:
        step = manager.latest_step()
        if step is None:
            raise FileNotFoundError(f"no teacher checkpoint under {path}")
        target = state.params["teacher"]
        abstract = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            target, state_shardings.params["teacher"],
        )
        # partial restore of the teacher subtree only
        # (checkpoint.pytree_restore_args, as build_model_for_eval does)
        restored = manager.restore(
            step,
            args=ocp.args.Composite(
                state=pytree_restore_args({"params": {"teacher": abstract}})
            ),
        )
    new_params = dict(state.params)
    new_params["teacher"] = restored["state"]["params"]["teacher"]
    logger.info("loaded distillation teacher from %s step %d", path, step)
    return state._replace(params=new_params)


# ---------------- serve-backed teacher (ROADMAP item 2) ----------------


def teacher_feature_example(cfg: ConfigNode, n_rows: int,
                            teacher_cfg: ConfigNode | None = None) -> dict:
    """Zero arrays with the serve-teacher batch-plane shapes —
    ``teacher_cls`` [n_rows, D_t] and ``teacher_patches``
    [n_rows, T, D_t] f32 — enough to trace/shard the train step
    (train.py example batch, batch_specs) without building a
    TeacherServer. ``n_rows`` is the GLOBAL 2B global-crop row count of
    the example. T comes from the student run's global crop size on the
    (assert-shared) patch grid; D_t from the teacher arch."""
    import numpy as np

    from dinov3_tpu.models import build_backbone

    if teacher_cfg is None:
        teacher_cfg = resolve_distillation_cfg(cfg)
    d = int(build_backbone(teacher_cfg, teacher=True).embed_dim)
    p = int(cfg.student.patch_size)
    t = (int(cfg.crops.global_crops_size) // p) ** 2
    return {
        "teacher_cls": np.zeros((n_rows, d), np.float32),
        "teacher_patches": np.zeros((n_rows, t, d), np.float32),
    }


class TeacherServer:
    """The host-shared frozen teacher: ONE packed AOT serve engine plus
    the content-addressed feature cache, in front of every student
    subgroup on this host.

    Under ``distillation.teacher_source=serve`` the train loop routes
    each batch's global crops through :meth:`annotate` instead of
    forwarding the teacher inside the step: a cache miss submits the
    crop to the packed engine (``patch_features=True`` — the iBOT loss
    needs per-token features), a hit replays the stored planes bitwise
    (frozen weights make that safe by construction, serve/cache.py).
    Because the engine + cache are PROCESS-level
    (multidistillation.shared_teacher_server), k co-hosted student
    subgroups iterating the same data pay ONE teacher forward per image
    instead of k, and epoch replays pay zero — the dedup
    COST_DISTILL_r22.json prices. ``teacher_forwards`` counts images
    actually forwarded; ``requests`` counts images asked for."""

    def __init__(self, cfg: ConfigNode, teacher_params=None,
                 ckpt_dir: str | None = None, capacity: int | None = None,
                 warn: bool = True):
        from dinov3_tpu.configs.config import warn_cache_memory
        from dinov3_tpu.serve.cache import FeatureCache, weights_fingerprint
        from dinov3_tpu.serve.engine import (
            PackedServeEngine,
            serve_layout_from_cfg,
        )
        from dinov3_tpu.serve.weights import load_serving_model

        teacher_cfg = resolve_distillation_cfg(cfg)
        # every request is one student-run global crop: pin the serve
        # envelope to exactly that resolution so the auto row budget
        # (2 images/row) never over-allocates the patch plane
        s = int(cfg.crops.global_crops_size)
        teacher_cfg.serve.min_px = s
        teacher_cfg.serve.max_px = s
        model, sparams = load_serving_model(
            teacher_cfg, ckpt_dir=ckpt_dir, params=teacher_params)
        layout = serve_layout_from_cfg(teacher_cfg, model)
        # flush_ms=0: annotate() drains the queue synchronously per
        # batch — there is no latency/throughput deadline to trade
        self.engine = PackedServeEngine(
            model, sparams, layout, flush_ms=0.0, warn=warn,
            patch_features=True)
        self.fingerprint = weights_fingerprint(sparams)
        self.patch_grid = s // int(cfg.student.patch_size)
        cap = int(capacity
                  or cfg.distillation.get("cache_capacity", 4096) or 4096)
        self.cache = FeatureCache(cap)
        if warn:
            c = (cfg.get("serve") or {}).get("cache") or {}
            warn_cache_memory(
                cap, model.embed_dim,
                budget_mb=float(c.get("host_budget_mb", 1024) or 1024),
                axis="distillation teacher feature cache",
                patch_tokens=self.patch_grid ** 2)
        self.requests = 0
        self.teacher_forwards = 0

    def features_for_batch(self, global_crops):
        """(cls [2B, D_t] f32, patches [2B, T, D_t] f32) for one
        batch's global-crop rows — cache hits replayed, misses packed
        through the ONE compiled teacher program (duplicates within the
        batch also forward once)."""
        import numpy as np

        imgs = np.asarray(global_crops, np.float32)
        n = imgs.shape[0]
        d = self.engine.model.embed_dim
        t = self.patch_grid ** 2
        cls = np.zeros((n, d), np.float32)
        patches = np.zeros((n, t, d), np.float32)
        self.requests += n
        by_key: dict = {}
        for i in range(n):
            key = self.cache.key(imgs[i], self.fingerprint)
            val = self.cache.get(key)
            if val is not None:
                cls[i], patches[i] = val[0], val[3]
            else:
                by_key.setdefault(key, []).append(i)
        for rid, (key, rows) in enumerate(by_key.items()):
            self.engine.submit(imgs[rows[0]], request_id=rid)
        keys = list(by_key)
        while self.engine.queue_len:
            for resp in self.engine.flush():
                key = keys[resp.request_id]
                self.cache.put(key, (resp.cls_feature,
                                     resp.pooled_patch_feature,
                                     resp.n_patches, resp.patch_tokens))
                for i in by_key[key]:
                    cls[i] = resp.cls_feature
                    patches[i] = resp.patch_tokens
        self.teacher_forwards += len(by_key)
        return cls, patches

    def annotate(self, batch: dict) -> dict:
        """The batch plus its ``teacher_cls``/``teacher_patches``
        planes — what ``get_teacher_output``'s serve arm consumes."""
        cls, patches = self.features_for_batch(batch["global_crops"])
        out = dict(batch)
        out["teacher_cls"] = cls
        out["teacher_patches"] = patches
        return out

    def stats(self) -> dict:
        """One record for bench/cost harnesses: forward dedup + cache
        behavior + the compile pin."""
        n = self.requests
        return {
            "requests": n,
            "teacher_forwards": self.teacher_forwards,
            "forwards_per_request": (
                round(self.teacher_forwards / n, 4) if n else None),
            "compile_count": self.engine.compile_count,
            "weights_fingerprint": self.fingerprint,
            "cache": self.cache.stats(),
        }
