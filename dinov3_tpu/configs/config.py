"""Config system: YAML + dot-override merging onto a typed-ish node tree.

Plays the role of the reference's OmegaConf stack
(reference: dinov3_jax/configs/config.py:67-146) without the OmegaConf
dependency: the default schema lives in ``ssl_default_config.yaml`` (same key
schema as the reference so its run recipes port over), a run YAML is merged on
top, then CLI ``key.path=value`` overrides. Batch-size-aware lr scaling rules
(``linear_wrt_256`` / ``sqrt_wrt_1024``) match the reference semantics
(reference: dinov3_jax/configs/config.py:43-56).
"""

from __future__ import annotations

import ast
import copy
import os
from pathlib import Path
from typing import Any, Iterable, Mapping

import yaml

_DEFAULT_YAML = Path(__file__).parent / "ssl_default_config.yaml"


class ConfigNode(dict):
    """A dict with attribute access and strict missing-key errors.

    Nested dicts are wrapped lazily so ``cfg.optim.lr`` works. Unlike a
    namespace, it remains a real dict: yaml-serializable, copyable, and
    usable as a pytree-less static argument.
    """

    def __getattr__(self, name: str) -> Any:
        try:
            value = self[name]
        except KeyError as e:
            raise AttributeError(f"config has no key {name!r}") from e
        if isinstance(value, dict) and not isinstance(value, ConfigNode):
            value = ConfigNode(value)
            self[name] = value
        return value

    def __setattr__(self, name: str, value: Any) -> None:
        self[name] = value

    def __deepcopy__(self, memo):
        return ConfigNode(copy.deepcopy(dict(self), memo))

    def to_dict(self) -> dict:
        out = {}
        for k, v in self.items():
            out[k] = v.to_dict() if isinstance(v, ConfigNode) else (
                dict(v) if isinstance(v, dict) else v
            )
        return out


def _wrap(tree: Any) -> Any:
    if isinstance(tree, Mapping):
        return ConfigNode({k: _wrap(v) for k, v in tree.items()})
    return tree


def _merge(base: dict, overlay: Mapping) -> dict:
    """Recursively merge ``overlay`` onto ``base`` (overlay wins)."""
    for k, v in overlay.items():
        if isinstance(v, Mapping) and isinstance(base.get(k), Mapping):
            _merge(base[k], v)
        else:
            base[k] = copy.deepcopy(v) if isinstance(v, (dict, list)) else v
    return base


def _parse_value(text: str) -> Any:
    """Parse a CLI override value with YAML-ish typing."""
    try:
        return yaml.safe_load(text)
    except yaml.YAMLError:
        try:
            return ast.literal_eval(text)
        except (ValueError, SyntaxError):
            return text


# ``section.key``s that went with the code they selected. A recipe or an
# override that still sets one is refused as an unknown key is
# (``load_config``), with what decides the matter now in the message
REMOVED_KEYS = {
    "optim.sharded_update": (
        "the flat per-leaf sharded update engine is gone; on a pure "
        "data-parallel mesh optim.bucketed_collectives chooses between "
        "the bucketed engine (auto/true) and the replicated fused "
        "engine (false)"),
}


def apply_dot_overrides(cfg: ConfigNode, overrides: Iterable[str]) -> ConfigNode:
    """Apply ``a.b.c=value`` overrides in place; numeric components index
    lists.

    Strict against the schema (the reference's OmegaConf ``set_struct``,
    configs/config.py:84): a key path whose parent section or leaf key does
    not already exist raises, so ``optim.lrr=0.1`` cannot silently train
    with the default lr. Prefix with ``+`` (``+extras.tag=v``) to add a
    genuinely new key.
    """
    for item in overrides:
        if "=" not in item:
            raise ValueError(f"override {item!r} is not of the form key.path=value")
        path, _, raw = item.partition("=")
        path = path.strip()
        allow_new = path.startswith("+")
        if allow_new:
            path = path[1:]
        keys = path.split(".")
        node = cfg
        for depth, k in enumerate(keys[:-1]):
            if isinstance(node, list):
                node = node[int(k)]
                continue
            nxt = node.get(k)
            if isinstance(nxt, list):
                node = nxt
                continue
            if not isinstance(nxt, dict):
                if nxt is None and k not in node and not allow_new:
                    raise KeyError(
                        f"override {item!r}: unknown section "
                        f"{'.'.join(keys[:depth + 1])!r} (prefix with '+' "
                        "to add new keys)"
                    )
                if nxt is not None and not allow_new:
                    # optim.lr.x=1 must not silently clobber the scalar
                    # optim.lr into a section
                    raise KeyError(
                        f"override {item!r}: "
                        f"{'.'.join(keys[:depth + 1])!r} is a value, not a "
                        "section (prefix with '+' to replace it with one)"
                    )
                nxt = ConfigNode()
                node[k] = nxt
            elif not isinstance(nxt, ConfigNode):
                nxt = ConfigNode(nxt)
                node[k] = nxt
            node = nxt
        leaf = keys[-1]
        value = _parse_value(raw.strip())
        if isinstance(node, list):
            node[int(leaf)] = value
        else:
            if not allow_new and leaf not in node:
                raise KeyError(
                    f"override {item!r}: unknown key {path!r} ("
                    + REMOVED_KEYS.get(
                        path, "prefix with '+' to add new keys") + ")"
                )
            if (not allow_new and isinstance(node.get(leaf), dict)
                    and not isinstance(value, dict)):
                # the symmetric clobber: optim=5 must not silently wipe
                # the whole optim section
                raise KeyError(
                    f"override {item!r}: {path!r} is a section, not a "
                    "value (prefix with '+' to replace it)"
                )
            node[leaf] = value
    return cfg


# architectures (``student.arch``) that are token decoders trained on a
# next-token loss (models/decoder.py, train/lm_meta_arch.py)
LM_ARCHS = ("kimi_linear", "smallthinker", "qwen3_next", "keye_vl2",
            "lfm2_moe", "deepseek_v3", "nemotron_h")


def is_lm_arch(cfg: ConfigNode) -> bool:
    return str(cfg.student.arch) in LM_ARCHS


def get_default_config() -> ConfigNode:
    with open(_DEFAULT_YAML) as f:
        return _wrap(yaml.safe_load(f))


def load_config(
    config_file: str | os.PathLike | None = None,
    overrides: Iterable[str] = (),
) -> ConfigNode:
    """default yaml <- run yaml <- dot overrides, then lr scaling."""
    cfg = get_default_config().to_dict()
    if config_file:
        with open(config_file) as f:
            run_cfg = yaml.safe_load(f) or {}
        _merge(cfg, run_cfg)
    cfg = _wrap(cfg)
    # Reference recipes use `train.batch_size_per_gpu`; accept it as an alias.
    if "batch_size_per_gpu" in cfg.train:
        cfg.train.batch_size_per_device = cfg.train.pop("batch_size_per_gpu")
    apply_dot_overrides(cfg, overrides)
    # a recipe's keys are merged unchecked, and '+' adds any key
    for path, why in REMOVED_KEYS.items():
        section, _, leaf = path.partition(".")
        if leaf in (cfg.get(section) or {}):
            raise KeyError(f"unknown key {path!r}: {why}")
    apply_scaling_rules_to_cfg(cfg)
    # batch-tiling guardrail: a silent 2.4x cliff is a footgun in a
    # framework whose selling point is TPU-first layout awareness
    # (a decoder's batch is a few long sequences: no image rows to tile)
    if not is_lm_arch(cfg):
        warn_bad_batch_tiling(cfg.train.batch_size_per_device)
    # ... and the same guardrail over the student's OTHER row axes: the
    # local-crop row axis (n_l*B, the two-pass program) or the packed
    # row count (2B + P, the crop-packed program) — 96 rows of 37
    # tokens is precisely the pathology the packing engine removes
    if not is_lm_arch(cfg):
        warn_student_row_tiling(cfg)
    # ... and over the telemetry flush window: metrics rows still in the
    # on-device ring when a run restarts are dropped, so a flush period
    # wider than the checkpoint/eval cadence silently loses exactly the
    # rows around the events one most wants recorded
    warn_telemetry_flush_period(cfg)
    # ... and over the zero3/scan combination: sharded block weights
    # with no scan loop to stream them through
    warn_zero3_no_stream(cfg)
    # ... and over microbatched gradient accumulation: accum_steps that
    # can't tile the batch raise at trace time, and a microbatch can
    # walk the step back into the sublane cliff one slice at a time
    warn_accum_batch_tiling(cfg)
    # ... and over the serve feature cache's worst-case footprint:
    # capacity x per-entry feature bytes vs the host budget, checked at
    # load so an oversized capacity never waits for the LRU to fill
    warn_serve_cache_memory(cfg)
    # ... and over the exposed-comm tolerance the anatomy plane gates
    # on: a tolerance outside (0, 1] makes the measured-overlap
    # guardrail either always-on noise or dead code
    warn_exposed_comm(cfg)
    # ... and over the elastic-resume knobs: a typo'd resume-topology
    # policy or an unusable re-padding tolerance must fail at load, not
    # at the preemption the elastic engine exists to survive (the live
    # re-padding check fires from parallel/reshard.py, which knows the
    # leaf sizes — warn_reshard_padding dual mode)
    warn_reshard_padding(cfg)
    return cfg


def data_parallel_world(cfg: ConfigNode, n_devices: int | None = None) -> int:
    """Number of devices holding independent batch shards.

    Model-parallel axes (tensor, seq, pipe, expert) replicate the batch,
    so they are divided out of the device count. ``n_devices`` overrides
    the global device count (multidistillation subgroup meshes).
    """
    if n_devices is None:
        import jax

        n_devices = jax.device_count()
    replicas = 1
    par = cfg.get("parallel") or {}
    for axis in ("tensor", "seq", "pipe", "expert"):
        replicas *= int(par.get(axis, 1) or 1)
    return max(1, n_devices // replicas)


def global_batch_size(cfg: ConfigNode, n_devices: int | None = None) -> int:
    return cfg.train.batch_size_per_device * data_parallel_world(cfg, n_devices)


def sublane_padding_waste(per_chip_batch: int) -> float:
    """Fraction of wasted sublane rows for a per-chip batch size.

    TPU tiles the sublane axis in units of 8, with a free half-tile for
    a remainder of exactly 4 and sub-tile packing for power-of-two sizes
    below 8 — the model behind the measured B=10 cliff: 10 pads to 16
    (60% waste) and ran 24.22 img/s/chip where B=12 (tiles as 8+4, no
    waste) ran 58.56 and B=8 54.46 (same session; round 5, before PR 1, one v5e chip;
    docs/PERFORMANCE.md). Returns 0.0 for well-tiled sizes.
    """
    b = int(per_chip_batch)
    if b <= 0 or b % 8 in (0, 4) or b in (1, 2, 4):
        return 0.0
    padded = (b // 8 + 1) * 8
    return (padded - b) / b


def nearest_good_batch_sizes(per_chip_batch: int) -> tuple[int, int]:
    """(nearest well-tiled B below-or-equal, nearest above)."""
    b = int(per_chip_batch)
    lo = next(x for x in range(max(b, 1), 0, -1)
              if sublane_padding_waste(x) == 0.0)
    hi = next(x for x in range(max(b, 1), b + 9)
              if sublane_padding_waste(x) == 0.0)
    return lo, hi


def warn_bad_batch_tiling(
    per_chip_batch: int, threshold: float = 0.2, stacklevel: int = 2,
    axis: str = "per-chip batch",
) -> str | None:
    """Warn when a per-chip row count pads >``threshold`` on the sublane
    axis — the measured 2.4x throughput cliff (B=10: 24.22 vs 58.56
    img/s/chip at B=12, same-session A/B; round 5, before PR 1, one v5e chip;
    docs/PERFORMANCE.md). Called at config build (``load_config``) and
    by ``bench.py`` so nobody walks into the cliff silently. Returns the
    warning message, or None when the size tiles fine. ``axis`` names
    the row axis being guarded (the per-chip global batch by default;
    ``warn_student_row_tiling`` reuses this for the local-crop and
    packed row axes).
    """
    waste = sublane_padding_waste(per_chip_batch)
    if waste <= threshold:
        return None
    lo, hi = nearest_good_batch_sizes(per_chip_batch)
    msg = (
        f"{axis} {per_chip_batch} pads {waste:.0%} on the TPU "
        f"sublane axis — the measured-cliff class (B=10 ran "
        f"24.22 img/s/chip vs 58.56 at B=12, same session; "
        f"round 5, before PR 1, one v5e chip). Use "
        f"{lo} or {hi} instead."
    )
    import warnings

    warnings.warn(msg, stacklevel=stacklevel + 1)
    return msg


def crop_packing_wished(cfg: ConfigNode) -> bool:
    """Whether the config ASKS for crop packing (before the meta arch's
    pipeline/convnext/k<2 auto-fallbacks, ssl_meta_arch.py)."""
    cp = (cfg.get("model") or {}).get("crop_packing", "auto")
    if isinstance(cp, str):
        return cp.lower() in ("auto", "true", "on")
    return bool(cp)


def warn_student_row_tiling(
    cfg: ConfigNode, per_chip_batch: int | None = None,
    threshold: float = 0.2, stacklevel: int = 2,
) -> list[str]:
    """Sublane guardrail over the student's crop row axes.

    Two-pass program (``model.crop_packing=false`` or any auto
    fallback): the local-crop row axis ``n_l * B`` — 96 rows of
    37-token sequences at the B=12 default was exactly the
    tiling pathology the original guardrail existed for. Crop-packed
    program: the packed row count ``2B + ceil(n_l*B / k)``
    (ops/packing.py). Returns the warning messages (empty when every
    axis tiles fine).
    """
    from dinov3_tpu.ops.packing import layout_from_cfg

    B = int(per_chip_batch if per_chip_batch is not None
            else cfg.train.batch_size_per_device)
    n_l = int(cfg.crops.local_crops_number)
    layout = layout_from_cfg(cfg, B)
    msgs = []
    if crop_packing_wished(cfg) and layout is not None and layout.k >= 2:
        m = warn_bad_batch_tiling(
            layout.rows_total, threshold, stacklevel + 1,
            axis="packed student row count (2B + ceil(n_l*B/k))")
        if m:
            msgs.append(m)
    else:
        m = warn_bad_batch_tiling(
            n_l * B, threshold, stacklevel + 1,
            axis="local-crop row axis (n_l*B)")
        if m:
            msgs.append(m)
    return msgs


def zero3_wished(cfg: ConfigNode) -> bool:
    """Whether the config ASKS for the ZeRO-3 weight-streaming engine
    (before the setup-time data-axis-size > 1 check).

    ``parallel.zero3``: auto (default) = on when ``parallel.fsdp > 1``
    (an fsdp axis is an explicit request for parameter sharding — zero3
    is how this repo provides it); true = on whenever the data-axis
    product is > 1 (pure data-parallel meshes shard their masters too);
    false = the replicated-masters oracle."""
    par = cfg.get("parallel") or {}
    z = par.get("zero3", "auto")
    if isinstance(z, str):
        zl = z.lower()
        if zl == "auto":
            return int(par.get("fsdp", 1) or 1) > 1
        return zl in ("true", "on", "1")
    return bool(z)


def zero3_stream_wished(cfg: ConfigNode) -> bool:
    """Whether the per-block weight stream (scoped bf16 gathers inside
    the block scan, ops/block.py) should engage: zero3 is wished AND the
    config is model-parallel-free — the stream's materialization
    constraint replicates a block's weights for compute, which would
    also undo a tensor/expert/seq split. Model-parallel zero3 configs
    still run (masters sharded, GSPMD places the gathers), just without
    the scoped stream."""
    if not zero3_wished(cfg):
        return False
    par = cfg.get("parallel") or {}
    return all(
        int(par.get(a, 1) or 1) <= 1
        for a in ("tensor", "seq", "pipe", "expert")
    )


def lowp_cfg(cfg: ConfigNode) -> dict:
    """The resolved ``train.low_precision`` block (ops/lowp.py): ``arm``
    (bf16 = today's bitwise-unchanged path | fp8 | int8),
    ``amax_history_len`` (delayed-scaling ring length),
    ``scale_margin`` (headroom multiplier on the history amax), and
    ``divergence_tol`` (the ``warn_lowp_divergence`` gate). All four are
    registered in the tuning/census.py no-silent-knobs registry.
    Raises on an unknown arm — a typo'd arm must never silently train
    bf16."""
    lp = (cfg.get("train") or {}).get("low_precision") or {}
    arm = str(lp.get("arm", "bf16") or "bf16")
    from dinov3_tpu.ops.lowp import LOWP_ARMS

    if arm not in LOWP_ARMS:
        raise ValueError(
            f"train.low_precision.arm={arm!r}: expected one of {LOWP_ARMS}"
        )
    return {
        "arm": arm,
        "amax_history_len": int(lp.get("amax_history_len", 16) or 16),
        "scale_margin": float(lp.get("scale_margin", 1.0) or 1.0),
        "divergence_tol": float(lp.get("divergence_tol", 0.2) or 0.2),
    }


def warn_lowp_divergence(
    drift: float, tol: float = 0.2, stacklevel: int = 2,
    axis: str = "lowp train matmuls",
) -> str | None:
    """Warn when the measured per-layer lowp-vs-bf16 matmul drift (the
    device-side shadow-matmul probe ``lowp_drift_probe``, ops/lowp.py —
    relative Frobenius error on a sampled layer) exceeds
    ``train.low_precision.divergence_tol`` — a config whose quantized
    matmuls have left the bf16 arm's band refuses to train silently,
    the training-side analogue of ``warn_quant_drift``. Fired at
    training-setup build (train/setup.py) and captured into every bench
    record (bench.py ``lowp_divergence_warning``). Returns the message
    or None when the drift is inside the band."""
    if drift <= tol:
        return None
    msg = (
        f"lowp divergence axis [{axis}]: measured quantized-matmul "
        f"drift {drift:.4g} vs the bf16 shadow exceeds "
        f"train.low_precision.divergence_tol={tol:.4g} — delayed "
        f"scaling cannot represent these kernels at this arm's "
        f"precision. Train this config in bf16 "
        f"(train.low_precision.arm=bf16), raise scale_margin, or raise "
        f"the tolerance only with a pinned loss-trajectory check "
        f"(docs/PERFORMANCE.md low-precision section)."
    )
    import warnings

    warnings.warn(msg, stacklevel=stacklevel + 1)
    return msg


def warn_zero3_padding(
    waste: float, dp: int, threshold: float = 0.01, stacklevel: int = 2,
) -> str | None:
    """Warn when the zero3 master layout leaves > ``threshold`` of the
    master elements replicated — leaves where no free dimension divides
    the shard count ``dp`` (parallel/sharding.py zero3_replicated_waste),
    the layout's per-device overhead over a perfect 1/dp split and the
    analogue of the bucketed engine's ``warn_bucket_padding``.
    Fired at training-setup build (train/setup.py, where the leaf shapes
    and the mesh first coexist) and recorded by ``bench.py``; returns
    the message, or None when the overhead is negligible."""
    if waste <= threshold:
        return None
    msg = (
        f"zero3 master layout: {waste:.1%} of the master elements have "
        f"no dimension divisible by the shard count dp={dp} and stay "
        f"replicated on every device (> {threshold:.0%}) — the "
        f"per-device state saving degrades by that fraction "
        f"(parallel/sharding.py zero3_leaf_spec). Pick a shard count "
        f"that divides the model dims, or set parallel.zero3=false."
    )
    import warnings

    warnings.warn(msg, stacklevel=stacklevel + 1)
    return msg


def warn_zero3_no_stream(cfg: ConfigNode, stacklevel: int = 2) -> str | None:
    """Warn when zero3 is wished but ``train.scan_layers`` is false —
    the block weights are still sharded and gathered at use, but there
    is no scan loop to stream them through, so every block's gather sits
    in the flat unrolled graph with nothing to overlap (the
    double-buffered prefetch story needs the loop). Fired at config
    build (``load_config``)."""
    if not zero3_wished(cfg) or bool(cfg.train.get("scan_layers", False)):
        return None
    msg = (
        "parallel.zero3 is on but train.scan_layers=false: block "
        "weights are sharded but there is no block scan to stream them "
        "through — the per-block all-gathers land in the unrolled "
        "graph with no loop to overlap prefetch against. Set "
        "train.scan_layers=true (the zero3 configs do) or "
        "parallel.zero3=false."
    )
    import warnings

    warnings.warn(msg, stacklevel=stacklevel + 1)
    return msg


def update_shard_padding_waste(leaf_sizes, dp: int) -> float:
    """Fraction of zero-padded lanes the flat per-leaf layout carries.

    A bucket member (train/fused_update.py flatten_update_leaf) is a
    master/moment/teacher leaf flattened and zero-padded to a multiple
    of the data-axis size ``dp``; padded lanes are inert but still cost
    HBM traffic and storage on every replica's 1/dp shard. Per-leaf padding
    is at most ``dp - 1`` elements, so the fraction only matters when a
    model is dominated by tiny leaves or ``dp`` is very large. Returns
    ``padded_extra / total`` (0.0 for an empty tree).
    """
    dp = max(1, int(dp))
    total = extra = 0
    for n in leaf_sizes:
        n = int(n)
        total += n
        extra += (-n) % dp
    return extra / total if total else 0.0


def warn_reshard_padding(
    cfg: ConfigNode | None = None, *, leaf_sizes=None,
    src_dp: int | None = None, dst_dp: int | None = None,
    threshold: float | None = None, stacklevel: int = 2,
) -> list[str]:
    """Guardrail on elastic topology transitions — the axis-labelled,
    dual-mode style of ``warn_exposed_comm``.

    **Config mode** (``load_config``, only ``cfg`` given): validates the
    elastic-resume knobs themselves — ``train.resume_topology`` must
    name a known path and ``train.reshard_padding_tol`` must be a
    usable fraction in (0, 1] — so a typo'd policy fails at load, not
    at the preemption it was meant to survive.

    **Live mode** (``leaf_sizes``/``src_dp``/``dst_dp`` given — fired by
    ``parallel.reshard.reshard_state`` when a transition re-lays-out the
    flat/bucketed/zero3 moment leaves, and recorded into bench/chaos
    JSONs like the PR-9 bucket guardrail): warns when the TARGET
    topology's shard-divisibility zero-padding exceeds the tolerance —
    the resized fleet would stream that padding through its 1/dp update
    shards on every step after the reshape, a permanent tax a one-time
    reshard decision just signed up for.

    Returns the list of messages ([] when clean)."""
    import warnings

    msgs = []
    if leaf_sizes is None:
        assert cfg is not None
        policy = str(cfg.train.get("resume_topology", "auto") or "auto")
        if policy not in ("auto", "memory", "disk"):
            msgs.append(
                f"train.resume_topology={policy!r} is not one of "
                f"auto|memory|disk — the elastic resume would fail at "
                f"the restore it exists to survive; fix the policy "
                f"(train/setup.py elastic_resume)."
            )
        tol = cfg.train.get("reshard_padding_tol", 0.05)
        try:
            tol = float(tol)
            bad = not (0.0 < tol <= 1.0)
        except (TypeError, ValueError):
            bad = True
        if bad:
            msgs.append(
                f"train.reshard_padding_tol={tol!r} is outside (0, 1] — "
                f"the reshard re-padding guardrail is either always-on "
                f"noise or dead code; use a fraction like 0.05."
            )
        for m in msgs:
            warnings.warn(m, stacklevel=stacklevel + 1)
        return msgs
    if threshold is None:
        threshold = (float(cfg.train.get("reshard_padding_tol", 0.05))
                     if cfg is not None else 0.05)
    src_waste = update_shard_padding_waste(leaf_sizes, int(src_dp or 1))
    dst_waste = update_shard_padding_waste(leaf_sizes, int(dst_dp))
    if dst_waste > threshold:
        msgs.append(
            f"reshard flat axis: re-padding the moment leaves from "
            f"dp={src_dp} ({src_waste:.1%} padding) to dp={dst_dp} "
            f"wastes {dst_waste:.1%} of the flattened size "
            f"(> {threshold:.0%}) — every replica of the TARGET "
            f"topology streams that padding through its 1/dp shard on "
            f"every step after the reshape "
            f"(train/fused_update.py flatten_update_leaf). Resize to a "
            f"data-axis size that divides the leaf sizes, or move to a "
            f"model-shaped arm (replicated/zero3) first."
        )
    for m in msgs:
        warnings.warn(m, stacklevel=stacklevel + 1)
    return msgs


def bucketed_collectives_wished(cfg: ConfigNode) -> bool:
    """Whether the config ASKS for the bucketed collective engine
    (before the setup-time data-axis-size > 1 / fused checks).

    ``optim.bucketed_collectives``: auto (default) = on — the coalesced
    schedule is the default whenever the setup-time conditions hold.
    The mesh picks the arm (train/setup.py resolve_update_arm): pure
    data-parallel meshes shard the UPDATE phase by buckets (one
    reduce-scatter + one all-gather per ~128 MiB flat bucket,
    train/fused_update.py make_bucketed_update; needs
    optim.fused_update); zero3 meshes select the UNIFIED arm — the
    non-block subtree gathers of the forward and their transposed grad
    reduce-scatters coalesce into hierarchy-aware gather buckets
    (gather_zero3_bucketed: intra-slice RS then inter-slice AG staging
    on dp×fsdp meshes) while the update stays shard-local zero3 and the
    block stacks keep the per-block in-scan stream. true = insist
    (setup raises if the bucketed arm's conditions cannot hold); false =
    the test oracles: the replicated fused engine on a pure
    data-parallel mesh, the per-leaf gathers on a zero3 mesh."""
    b = (cfg.get("optim") or {}).get("bucketed_collectives", "auto")
    if isinstance(b, str):
        bl = b.lower()
        if bl == "auto":
            return True
        return bl in ("true", "on", "1")
    return bool(b)


def warn_accum_batch_tiling(
    cfg: ConfigNode, per_chip_batch: int | None = None,
    threshold: float = 0.2, stacklevel: int = 2, mesh=None,
) -> list[str]:
    """Guardrails on microbatched gradient accumulation
    (``optim.accum_steps``, train/train_step.py split_microbatches) —
    the axis-labelled style of ``warn_bad_batch_tiling``, fired at
    config build (``load_config``), at training-setup build
    (train/setup.py, where the mesh is known) and recorded by
    ``bench.py``.

    Two failure modes:

    * ``accum_steps`` not dividing the global image batch — the
      semantic microbatch regroup needs equal image subsets, so
      ``split_microbatches`` raises at trace time; warn while the
      config is still editable;
    * a per-chip microbatch (B/accum_steps) that pads >``threshold`` on
      the TPU sublane axis — accumulation quietly walking the step into
      the measured 2.4x ``warn_bad_batch_tiling`` cliff, one microbatch
      at a time.

    Returns the warning messages ([] when accumulation is off or
    tiles fine)."""
    a = int((cfg.get("optim") or {}).get("accum_steps", 1) or 1)
    if a <= 1:
        return []
    b_chip = int(per_chip_batch if per_chip_batch is not None
                 else cfg.train.batch_size_per_device)
    if mesh is not None:
        from dinov3_tpu.parallel.sharding import update_shard_size

        dp = max(1, int(update_shard_size(mesh)))
    else:
        dp = max(1, data_parallel_world(cfg))
    b_global = b_chip * dp
    msgs = []
    if b_global % a:
        msgs.append(
            f"optim.accum_steps axis: accum_steps={a} does not divide "
            f"the global image batch B={b_global} "
            f"(batch_size_per_device={b_chip} x dp={dp}) — the "
            f"microbatch split (train/train_step.py split_microbatches) "
            f"will raise at trace time. Pick accum_steps dividing B, or "
            f"retune the batch."
        )
        import warnings

        warnings.warn(msgs[-1], stacklevel=stacklevel + 1)
        return msgs
    micro_chip = b_global // a // dp if (b_global // a) % dp == 0 \
        else -(-(b_global // a) // dp)
    m = warn_bad_batch_tiling(
        micro_chip, threshold, stacklevel + 1,
        axis=f"per-chip microbatch (B/accum_steps={a})")
    if m:
        msgs.append(m)
    return msgs


def warn_bucket_padding(
    stats, target_bytes: int, threshold: float = 0.05, stacklevel: int = 2,
) -> list[str]:
    """Guardrails on a built bucket plan — the axis-labelled style of
    ``warn_bad_batch_tiling``, fired at training-setup build
    (train/setup.py, where the plan is first assembled) and recorded by
    ``bench.py``.

    ``stats`` is ``BucketPlan.padding_stats()`` (one row per bucket with
    ``elems``/``pad_elems``/``bytes``/``group``). Two failure modes:

    * a bucket whose zero-pad fraction exceeds ``threshold`` (5%) — the
      dp-alignment padding of its member leaves is no longer negligible
      against the bucket payload, so the coalesced reduce-scatter and
      all-gather move mostly zeros;
    * a straggler bucket smaller than 1/8 of the MEDIAN bucket size —
      the greedy leaf→bucket assignment stranded a small bucket whose
      collective is back in the latency-bound regime the engine exists
      to avoid (only meaningful when there are >= 2 buckets to compare).

    Returns the list of messages ([] when the plan is clean)."""
    import warnings

    msgs = []
    for row in stats:
        total = int(row["elems"])
        pad = int(row["pad_elems"])
        frac = pad / total if total else 0.0
        if frac > threshold:
            msgs.append(
                f"bucket flat axis [{row['name']}]: zero-padding the "
                f"member leaves to the data-axis size wastes {frac:.1%} "
                f"of the bucket (> {threshold:.0%}) — the coalesced "
                f"collectives move that padding every step "
                f"(train/fused_update.py make_bucket_plan). Use a "
                f"data-parallel axis that divides the leaf sizes, or "
                f"set optim.bucketed_collectives=false."
            )
    sizes = sorted(int(r["bytes"]) for r in stats)
    if len(sizes) >= 2:
        median = sizes[len(sizes) // 2]
        for row in stats:
            if int(row["bytes"]) * 8 < median:
                msgs.append(
                    f"bucket size axis [{row['name']}]: straggler "
                    f"bucket of {int(row['bytes'])} bytes is smaller "
                    f"than 1/8 of the median bucket ({median} bytes) — "
                    f"its collective is back in the latency-bound "
                    f"small-message regime the bucketed engine exists "
                    f"to avoid (target {target_bytes} bytes, "
                    f"train/fused_update.py BUCKET_TARGET_BYTES). Set "
                    f"optim.bucketed_collectives=false."
                )
    for m in msgs:
        warnings.warn(m, stacklevel=stacklevel + 1)
    return msgs


def warn_telemetry_flush_period(
    cfg: ConfigNode, stacklevel: int = 2,
) -> str | None:
    """Warn when ``telemetry.flush_every`` exceeds the checkpoint period
    or the eval period — the axis-labelled guardrail style of
    ``warn_bad_batch_tiling``.

    The async metrics engine (telemetry/ring.py) holds up to
    ``flush_every`` metric rows on device between flushes; a restart
    drops whatever is still in the ring, and the non-finite 3-strike
    abort is delayed by up to a full window. When the window is wider
    than ``checkpointing.period`` (rows spanning a restart are
    guaranteed droppable) or the eval cadence (an eval's surrounding
    training metrics lag it in the record), the period is almost
    certainly misconfigured. Fired at config build (``load_config``);
    returns the message, or None when the window is fine or the async
    engine is off."""
    from dinov3_tpu.telemetry import telemetry_wished

    if not telemetry_wished(cfg):
        return None
    flush_every = int((cfg.get("telemetry") or {}).get("flush_every", 50))
    offenders = []
    ckpt_period = int(cfg.checkpointing.period)
    if ckpt_period > 0 and flush_every > ckpt_period:
        offenders.append(f"checkpointing.period={ckpt_period}")
    eval_period = int(cfg.evaluation.get("eval_period_iterations", 0) or 0)
    if eval_period > 0 and flush_every > eval_period:
        offenders.append(f"evaluation.eval_period_iterations={eval_period}")
    if not offenders:
        return None
    msg = (
        f"telemetry flush window: telemetry.flush_every={flush_every} "
        f"exceeds {' and '.join(offenders)} — metrics rows still in the "
        f"on-device ring at a restart are dropped, and the non-finite "
        f"abort lags by up to a full window (telemetry/ring.py). Lower "
        f"telemetry.flush_every, or set telemetry.async_metrics=false "
        f"for the per-step-fetch oracle."
    )
    import warnings

    warnings.warn(msg, stacklevel=stacklevel + 1)
    return msg


def anatomy_wished(cfg: ConfigNode) -> bool:
    """Whether the config ASKS for the step-anatomy trace plane
    (telemetry/anatomy.py — parse the ``--profile-steps`` /
    ``bench.py --trace`` profiler window into the per-step ledger).
    ``telemetry.anatomy``: auto/true (default) = parse + emit; false =
    the pre-PR-13 raw-trace-only behaviour (kept as the zero-parse
    oracle, the repo's legacy-path convention)."""
    t = (cfg.get("telemetry") or {}).get("anatomy", "auto")
    if isinstance(t, str):
        return t.lower() in ("auto", "true", "on")
    return bool(t)


def warn_exposed_comm(
    cfg: ConfigNode, summary: dict | None = None, stacklevel: int = 2,
) -> str | None:
    """Warn when a MEASURED anatomy summary shows more exposed
    (non-overlapped) collective time than ``telemetry.exposed_comm_tol``
    allows — the axis-labelled guardrail style of
    ``warn_telemetry_flush_period``, but fired against measurement
    rather than configuration.

    With ``summary`` (a ``ledger_summary`` dict, from the train loop's
    profile window or ``bench.py --trace``): compares the measured
    ``exposed_comm_frac`` — exposed-collective ms over total device-busy
    ms — against the tolerance, naming the worst-exposed scopes so the
    warning points at the schedule that failed to hide its comm.
    Without ``summary`` (the ``load_config`` call): validates that the
    tolerance itself is a sane fraction in (0, 1]. Returns the message,
    or None when within tolerance or the anatomy plane is off."""
    tol = (cfg.get("telemetry") or {}).get("exposed_comm_tol", 0.25)
    try:
        tol = float(tol)
    except (TypeError, ValueError):
        tol = -1.0
    if summary is None:
        if 0.0 < tol <= 1.0:
            return None
        msg = (
            f"exposed-comm tolerance: telemetry.exposed_comm_tol={tol!r} "
            f"is not a fraction in (0, 1] — the anatomy guardrail "
            f"compares measured exposed-collective device time against "
            f"it (telemetry/anatomy.py); set e.g. 0.25."
        )
        import warnings

        warnings.warn(msg, stacklevel=stacklevel + 1)
        return msg
    if not anatomy_wished(cfg):
        return None
    frac = float(summary.get("exposed_comm_frac", 0.0) or 0.0)
    if frac <= tol:
        return None
    scopes = sorted(
        (summary.get("collectives") or {}).items(),
        key=lambda kv: -kv[1].get("exposed_ms_per_step", 0.0),
    )[:3]
    worst = ", ".join(
        f"{name}={ent.get('exposed_ms_per_step', 0.0):.2f}ms/step "
        f"(overlap {ent.get('overlap_frac', 0.0):.0%})"
        for name, ent in scopes if ent.get("exposed_ms_per_step", 0.0) > 0
    ) or "no per-scope breakdown"
    msg = (
        f"exposed comm: measured exposed-collective fraction "
        f"{frac:.1%} of device-busy time exceeds "
        f"telemetry.exposed_comm_tol={tol:g} — the overlap schedule is "
        f"not hiding its communication (worst scopes: {worst}). On the "
        f"CPU harness overlap is a structural lower bound "
        f"(docs/OBSERVABILITY.md); on TPU this means the bucket/stream "
        f"schedule regressed or the step is genuinely comm-bound."
    )
    import warnings

    warnings.warn(msg, stacklevel=stacklevel + 1)
    return msg


def continuous_packing_wished(cfg: ConfigNode) -> bool:
    """Whether the config ASKS for the continuous-packing serve engine
    (serve/engine.py PackedServeEngine). ``serve.continuous_packing``:
    auto/true (default) = the packed engine; false = the naive
    shape-polymorphic oracle arms (``serve.oracle`` picks per_image or
    rectangular)."""
    cp = (cfg.get("serve") or {}).get("continuous_packing", "auto")
    if isinstance(cp, str):
        return cp.lower() in ("auto", "true", "on")
    return bool(cp)


def serve_obs_wished(cfg: ConfigNode) -> bool:
    """Whether the config ASKS for the serving observability plane
    (telemetry/serve_obs.py ServeObserver behind the serve engines).
    ``telemetry.serve_spans``: auto/true (default) = observe; false =
    the blind pre-PR-11 serving path (kept as the zero-overhead
    oracle, the repo's legacy-path convention)."""
    t = (cfg.get("telemetry") or {}).get("serve_spans", "auto")
    if isinstance(t, str):
        return t.lower() in ("auto", "true", "on")
    return bool(t)


def serve_obs_kwargs(cfg: ConfigNode) -> dict:
    """The ``telemetry.serve_*`` block resolved into ServeObserver
    constructor kwargs (defaults mirror ssl_default_config.yaml)."""
    t = cfg.get("telemetry") or {}
    return {
        "window_packs": int(t.get("serve_window_packs", 16) or 16),
        "hist_lo_ms": float(t.get("serve_hist_lo_ms", 1e-2) or 1e-2),
        "hist_hi_ms": float(t.get("serve_hist_hi_ms", 1e5) or 1e5),
        "bins_per_decade": int(
            t.get("serve_hist_bins_per_decade", 16) or 16),
        "mix_alpha": float(t.get("serve_mix_alpha", 0.25) or 0.25),
        "window_deadline_s": float(
            t.get("serve_window_deadline_s", 0.0) or 0.0),
    }


def serve_pad_waste_floor(
    row_tokens: int, patch_size: int, n_prefix: int,
    min_px: int, max_px: int,
) -> dict:
    """Worst-case per-row pad waste over the serve resolution envelope.

    For a square resolution r (a multiple of ``patch_size``) the image
    spans ``L_r = n_prefix + (r/p)^2`` tokens; a row fits
    ``floor(row_tokens / L_r)`` such images and wastes the remainder.
    The floor scans every admissible r in [min_px, max_px] and returns
    the worst ``{"px", "seq_len", "waste"}`` — the waste a traffic mix
    concentrated at that resolution could not pack below, whatever the
    batcher does — plus ``"mean_waste"``, the same floor averaged
    uniformly over the envelope. The build-time guardrail keys on the
    mean (a worst single resolution is an adversarial mix, not a config
    bug); bench_serve.py re-checks each MEASURED mix against its real
    waste. Build-time input to ``warn_serve_pad_waste``."""
    worst = {"px": min_px, "seq_len": 0, "waste": 0.0}
    wastes = []
    for px in range(min_px, max_px + 1, patch_size):
        if px % patch_size:
            continue
        seq = n_prefix + (px // patch_size) ** 2
        if seq > row_tokens:
            continue
        waste = 1.0 - (row_tokens // seq) * seq / row_tokens
        wastes.append(waste)
        if waste > worst["waste"]:
            worst = {"px": px, "seq_len": seq, "waste": waste}
    worst["mean_waste"] = sum(wastes) / len(wastes) if wastes else 0.0
    return worst


def warn_serve_pad_waste(
    pad_waste: float, threshold: float = 0.15, stacklevel: int = 2,
    axis: str = "serve token budget",
) -> str | None:
    """Warn when a serve traffic mix (or the envelope's static floor)
    wastes more than ``threshold`` of the token budget on padding — the
    axis-labelled guardrail style of ``warn_bucket_padding``. Fired at
    engine build (serve/engine.py, with the ``serve_pad_waste_floor``
    envelope scan) and per measured mix by ``scripts/bench_serve.py``
    (recorded in SERVE_r14.json). Returns the message or None."""
    if pad_waste <= threshold:
        return None
    msg = (
        f"serve pad-waste axis [{axis}]: {pad_waste:.1%} of the packed "
        f"token budget is padding (> {threshold:.0%}) — the compiled "
        f"serve step spends that fraction of its FLOPs on masked-out "
        f"tokens. Resize serve.row_tokens / serve.rows to the traffic's "
        f"token distribution, or tighten the serve.min_px..max_px "
        f"envelope (serve/batcher.py)."
    )
    import warnings

    warnings.warn(msg, stacklevel=stacklevel + 1)
    return msg


def serve_quant_wished(cfg: ConfigNode) -> bool:
    """Whether the config ASKS for int8 serving weights
    (serve/quant.py). ``serve.quant.enabled``: OPT-IN — false (default)
    = bf16 serving trees everywhere; true/on = fleet engines quantize
    unless their own overlay says otherwise (``serve.fleet.engines[i]
    .quant`` overrides per engine either way). Opt-in because int8
    trades a measured feature drift for bytes/throughput — the
    ``warn_quant_drift`` guardrail and the SERVE_r16 drift pin make
    that trade visible, but the default stays exact-bf16."""
    q = (cfg.get("serve") or {}).get("quant") or {}
    e = q.get("enabled", False)
    if isinstance(e, str):
        return e.lower() in ("true", "on", "1")
    return bool(e)


def serve_cache_wished(cfg: ConfigNode) -> bool:
    """Whether the fleet builds the content-addressed feature cache
    (serve/cache.py). ``serve.cache.enabled``: auto/true (default) =
    on — frozen weights make caching bitwise-safe, so it follows the
    default-on-where-safe convention; false = every request computes
    (the cache-off oracle path the PR-10 bitwise pin runs under)."""
    c = (cfg.get("serve") or {}).get("cache") or {}
    e = c.get("enabled", "auto")
    if isinstance(e, str):
        return e.lower() in ("auto", "true", "on")
    return bool(e)


def serve_patch_features_wished(cfg: ConfigNode) -> bool:
    """Whether the serve engines extract the per-token patch plane
    (serve/engine.py ServeRing.patch + ServeResponse.patch_tokens).
    ``serve.patch_features``: OPT-IN — false (default) keeps the ring
    at the CLS+pool payload; true/on widens the ring by a
    [depth, R, N, D] f32 plane and every response carries its token
    span. Opt-in because the plane multiplies the per-pack fetch bytes
    by ~row_tokens/segments; the distillation TeacherServer
    (train/distillation.py) forces it on for its own engine regardless
    of this key — the iBOT loss needs tokens, not pools."""
    pf = (cfg.get("serve") or {}).get("patch_features", False)
    if isinstance(pf, str):
        return pf.lower() in ("true", "on", "1")
    return bool(pf)


def distill_teacher_source(cfg: ConfigNode) -> str:
    """Resolved ``distillation.teacher_source`` — where the frozen
    teacher's features come from under distillation:

    - ``in_step`` (default): the teacher backbone forwards INSIDE the
      compiled train step, once per student subgroup per step — the
      bitwise oracle the serve arm is pinned against
      (tests/test_distill_serve.py, COST_DISTILL_r22.json);
    - ``serve``: the host-shared packed AOT teacher engine
      (train/distillation.py TeacherServer) computes CLS+patch features
      ONCE per image, the content-addressed cache absorbs repeats, and
      the train step consumes them as ``teacher_cls``/
      ``teacher_patches`` batch planes (ssl_meta_arch.py
      get_teacher_output precomputed arm, ``distill_fanout`` scope).
    """
    d = cfg.get("distillation") or {}
    ts = str(d.get("teacher_source", "in_step") or "in_step").lower()
    if ts not in ("in_step", "serve"):
        raise ValueError(
            f"distillation.teacher_source={ts!r}: expected in_step|serve")
    return ts


def serve_cache_entry_bytes(embed_dim: int, patch_tokens: int = 0) -> int:
    """Feature payload bytes of ONE cache entry: the CLS and pooled
    [D] float32 vectors, plus the [T, D] f32 patch plane when the
    engine serves per-token features (``patch_tokens`` = T, 0 on the
    default CLS+pool path — serve/cache.py values; keys and LRU
    bookkeeping are O(100) bytes and excluded — the budget guardrail
    is about the feature planes)."""
    return (2 + int(patch_tokens)) * int(embed_dim) * 4


def warn_quant_drift(
    drift: float, tol: float = 0.05, stacklevel: int = 2,
    axis: str = "int8 serving tree",
) -> str | None:
    """Warn when the measured int8 CLS-feature drift vs the bf16 arm
    exceeds ``serve.quant.drift_tol`` — the same
    pin-against-the-wider-dtype discipline bf16 serving was held to
    against fp32 (tests/test_serve.py tolerances). Fired at engine
    build (serve/fleet.py, with the ``quant_feature_drift`` probe) and
    recorded per run in SERVE_r16.json. Returns the message or None."""
    if drift <= tol:
        return None
    msg = (
        f"quant drift axis [{axis}]: measured int8 CLS feature drift "
        f"{drift:.4g} exceeds serve.quant.drift_tol={tol:.4g} — the "
        f"quantized engine's features have left the bf16 arm's "
        f"tolerance band. Serve this model in bf16 "
        f"(serve.quant.enabled=false or the engine overlay's "
        f"quant=false), or raise the tolerance only with a downstream "
        f"quality check (docs/PERFORMANCE.md serving-fleet section)."
    )
    import warnings

    warnings.warn(msg, stacklevel=stacklevel + 1)
    return msg


def warn_cache_memory(
    capacity: int, embed_dim: int, budget_mb: float = 1024.0,
    threshold: float = 1.0, stacklevel: int = 2,
    axis: str = "serve feature cache", patch_tokens: int = 0,
) -> str | None:
    """Warn when the cache's worst-case feature bytes — capacity x
    ``serve_cache_entry_bytes`` — exceed ``threshold`` x the host
    budget (``serve.cache.host_budget_mb``). Fired at fleet build
    (serve/fleet.py), from ``load_config`` so an oversized capacity
    never waits for the LRU to fill before anyone notices, and at
    TeacherServer build (train/distillation.py) with the per-token
    ``patch_tokens`` term — patch entries are ~T/2 x bigger than
    CLS+pool entries. Returns the message or None."""
    entry = serve_cache_entry_bytes(embed_dim, patch_tokens)
    need_mb = int(capacity) * entry / 2**20
    if budget_mb <= 0 or need_mb <= threshold * budget_mb:
        return None
    msg = (
        f"cache memory axis [{axis}]: serve.cache.capacity={capacity} "
        f"x {entry} B/entry (embed_dim {embed_dim}, patch_tokens "
        f"{patch_tokens}) = {need_mb:.0f} MB of feature payload at full "
        f"occupancy, over the serve.cache.host_budget_mb={budget_mb:.0f} "
        f"budget. Lower the capacity or raise the budget "
        f"(serve/cache.py)."
    )
    import warnings

    warnings.warn(msg, stacklevel=stacklevel + 1)
    return msg


def warn_serve_cache_memory(cfg: ConfigNode, stacklevel: int = 2) -> str | None:
    """The ``load_config`` wiring of ``warn_cache_memory``: resolve the
    configured arch's embed_dim (a flax module construction — no
    params) and fire the capacity-vs-budget check when the cache is
    wished. Configs that cannot build a backbone (exotic test configs)
    are skipped — this is a serving guardrail, not a load gate."""
    if not serve_cache_wished(cfg):
        return None
    c = (cfg.get("serve") or {}).get("cache") or {}
    budget_mb = float(c.get("host_budget_mb", 1024) or 1024)
    if budget_mb <= 0:
        return None
    try:
        from dinov3_tpu.models import build_backbone

        embed_dim = build_backbone(cfg, teacher=True).embed_dim
    except Exception:
        return None
    return warn_cache_memory(
        int(c.get("capacity", 4096) or 4096), embed_dim,
        budget_mb=budget_mb, stacklevel=stacklevel + 1)


# kernels.flash_min_seq="auto" resolves against this committed artifact
# (repo root), written by ``python scripts/crossover_attention.py
# CROSSOVER_r19.json`` — the executable threshold definition
# (``recommended_flash_min_seq``: smallest measured N where the Pallas
# flash kernel's fwd+bwd beats dense XLA). The artifact-pin test is
# tests/test_crossover_attention.py.
CROSSOVER_ARTIFACT = Path(__file__).parents[2] / "CROSSOVER_r19.json"

# Sentinel for "flash never won a measured point": an N no real pass
# reaches, so dispatch stays dense everywhere without a special case in
# ops/attention.py (which treats flash_min_seq=0 as "use the baked-in
# FLASH_MIN_SEQ fallback" — the opposite of what a dense-always
# crossover verdict means).
FLASH_NEVER_SEQ = 1 << 30


def resolve_flash_min_seq(value: Any, artifact: Path | None = None) -> int:
    """Resolve ``kernels.flash_min_seq`` to the int the attention modules
    dispatch on. Ints pass through (0 = the ops-layer FLASH_MIN_SEQ
    fallback). "auto" (the default) reads ``recommended_flash_min_seq``
    from the committed crossover artifact: a measured N means flash for
    passes at least that long; null means flash never won a measured
    point, resolved to ``FLASH_NEVER_SEQ`` (dense everywhere). A missing
    or unreadable artifact warns and falls back to 0 so fresh checkouts
    mid-rederivation still build."""
    if value is None or value == "":
        value = "auto"
    if not isinstance(value, str):
        return int(value or 0)
    if value != "auto":
        return int(value)  # "2048"-style override strings
    path = CROSSOVER_ARTIFACT if artifact is None else artifact
    try:
        import json

        with open(path) as f:
            rec = json.load(f)
        n = rec["recommended_flash_min_seq"]
    except Exception as e:  # noqa: BLE001 - degrade to the ops fallback
        import warnings

        warnings.warn(
            f"kernels.flash_min_seq=auto but the crossover artifact "
            f"{path} is unreadable ({e}); falling back to the ops-layer "
            f"FLASH_MIN_SEQ default. Re-derive it with "
            f"scripts/crossover_attention.py.",
            stacklevel=2,
        )
        return 0
    return FLASH_NEVER_SEQ if n is None else int(n)


def warn_seq_padding(
    n_tokens: int, seq: int, threshold: float = 0.02, stacklevel: int = 2,
    axis: str = "global crop tokens",
) -> str | None:
    """Warn when padding a token axis to a multiple of the seq mesh axis
    wastes more than ``threshold`` of the padded length — the CLS +
    register prefix makes N = n_prefix + patches, which is rarely a
    multiple of ``parallel.seq``, and every padded position costs real
    attention FLOPs on every device (ring attention masks them by global
    position but still computes them). Axis-labelled like
    ``warn_bucket_padding``; fired at setup build (train/setup.py) for
    each crop size the step will run, and captured into bench records
    as ``seq_padding_warning`` (bench.py). Returns the message or
    None."""
    if seq <= 1 or n_tokens <= 0:
        return None
    padded = -(-int(n_tokens) // int(seq)) * int(seq)
    waste = (padded - n_tokens) / padded
    if waste <= threshold:
        return None
    msg = (
        f"seq-padding axis [{axis}]: {n_tokens} tokens pad to {padded} "
        f"for parallel.seq={seq} — {waste:.1%} of every attention pass "
        f"is masked padding (> {threshold:.0%}). Pick a crop size whose "
        f"token count (1 + registers + (size/patch)^2) divides the seq "
        f"axis more evenly, or lower parallel.seq for this stage."
    )
    import warnings

    warnings.warn(msg, stacklevel=stacklevel + 1)
    return msg


def apply_scaling_rules_to_cfg(cfg: ConfigNode) -> ConfigNode:
    """Batch-size lr scaling, resolved once at load time.

    Matches the reference rules (dinov3_jax/configs/config.py:43-56):
    ``linear_wrt_256``: lr *= B/256; ``sqrt_wrt_1024``: lr *= 4*sqrt(B/1024);
    skipped entirely when a schedules-v2 block supplies absolute ramps
    (reference:45-46). The scaled value is stored back so schedules are
    built from absolute lr.
    """
    if cfg.get("_lr_scaled") or cfg.get("schedules"):
        return cfg
    rule = cfg.optim.scaling_rule
    if rule == "linear_wrt_256":
        cfg.optim.lr = cfg.optim.lr * global_batch_size(cfg) / 256.0
    elif rule == "sqrt_wrt_1024":
        cfg.optim.lr = cfg.optim.lr * 4.0 * (global_batch_size(cfg) / 1024.0) ** 0.5
    elif rule in (None, "", "none"):
        pass
    else:
        raise ValueError(f"unknown scaling rule {rule!r}")
    cfg["_lr_scaled"] = True
    return cfg


def setup_job(cfg: ConfigNode) -> None:
    """Create the output dir, dump the resolved config, seed python RNGs.

    (reference: dinov3_jax/configs/config.py:110-146 — unlike the reference's
    ``fix_random_seeds`` we seed numpy too, since the masking generator uses
    numpy RNG; SURVEY.md §2.9.8.)
    """
    import random

    import numpy as np

    out = Path(cfg.train.output_dir)
    out.mkdir(parents=True, exist_ok=True)
    dump = {k: v for k, v in cfg.to_dict().items() if not k.startswith("_")}
    with open(out / "config.yaml", "w") as f:
        yaml.safe_dump(dump, f, sort_keys=False)
    random.seed(cfg.train.seed)
    np.random.seed(cfg.train.seed)
