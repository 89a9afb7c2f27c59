"""Knob census: every ``optim.*`` / ``kernels.*`` numeric constant
must be accounted for — tuned from the TUNED_* artifact, resolved
from a committed crossover measurement, or carrying a documented
justification — so no magic number rides in the schedule config
untracked (the tuner satellite's "no silent knobs" guarantee, pinned
by tests/test_tuning.py and run in CI via
``scripts/tune_collectives.py --census``).

Three kinds:

- ``tuned``: searched by scripts/tune_collectives.py against the
  step-anatomy objective; the default is "auto" and the numeric
  magic lives ONLY in configs/config.py TUNED_FALLBACKS (the
  hand-set oracle the resolver degrades to).
- ``crossover``: resolved from a dedicated committed measurement
  artifact (the resolve_flash_min_seq pattern).
- ``justified``: a training-recipe or kernel-shape constant that is
  NOT a latency knob — the entry documents why it is exempt from
  tuning.

The census walks the DEFAULT config (ssl_default_config.yaml): every
key under ``optim``/``kernels`` whose default is numeric (bools
excluded — they are mode switches, not magnitudes) must appear here;
an unregistered numeric key fails the census. Registered tuned/
crossover keys are checked even when their default is the "auto"
string (their magic number lives in the fallback).
"""

from __future__ import annotations

# section.key -> {kind, why, resolver?, artifact?}
KNOB_REGISTRY: dict = {
    # ---- tuned (TUNED_r20.json, scripts/tune_collectives.py) ----
    "optim.bucket_mb": {
        "kind": "tuned", "resolver": "resolve_bucket_mb",
        "artifact": "TUNED_r20.json",
        "why": "bucket payload target of the greedy leaf packing — "
               "swept against the measured step objective",
    },
    "optim.staging_order": {
        "kind": "tuned", "resolver": "resolve_staging_order",
        "artifact": "TUNED_r20.json",
        "why": "tier-release order of the hierarchy-aware staged "
               "gathers — all four orders swept",
    },
    "optim.stream_prefetch": {
        "kind": "tuned", "resolver": "resolve_stream_prefetch",
        "artifact": "TUNED_r20.json",
        "why": "gather-lookahead depth of the explicit weight "
               "streams — depths 0/1/2 swept",
    },
    "kernels.ring_min_seq": {
        "kind": "tuned", "resolver": "resolve_ring_min_seq",
        "artifact": "TUNED_r20.json",
        "why": "ring-dispatch token floor — derived from the measured "
               "ring-vs-dense workload table",
    },
    # ---- crossover (dedicated committed measurement) ----
    "kernels.flash_min_seq": {
        "kind": "crossover", "resolver": "resolve_flash_min_seq",
        "artifact": "CROSSOVER_r19.json",
        "why": "flash-vs-dense sequence crossover, measured by "
               "scripts/crossover_attention.py",
    },
    # ---- justified (documented non-latency constants) ----
    "optim.epochs": {
        "kind": "justified",
        "why": "training-recipe length (paper schedule), not a "
               "latency knob"},
    "optim.weight_decay": {
        "kind": "justified",
        "why": "cosine weight-decay start (reference recipe)"},
    "optim.weight_decay_end": {
        "kind": "justified",
        "why": "cosine weight-decay end (reference recipe)"},
    "optim.lr": {
        "kind": "justified",
        "why": "base learning rate before scaling_rule (reference "
               "recipe)"},
    "optim.warmup_epochs": {
        "kind": "justified",
        "why": "LR warmup length (reference recipe)"},
    "optim.min_lr": {
        "kind": "justified",
        "why": "cosine floor (reference recipe)"},
    "optim.schedule_trunc_extra": {
        "kind": "justified",
        "why": "schedule truncation margin (reference recipe)"},
    "optim.clip_grad": {
        "kind": "justified",
        "why": "global grad-norm clip (reference recipe; numerics, "
               "not latency)"},
    "optim.freeze_last_layer_epochs": {
        "kind": "justified",
        "why": "DINO last-layer freeze window (reference recipe)"},
    "optim.patch_embed_lr_mult": {
        "kind": "justified",
        "why": "per-group LR multiplier (reference recipe)"},
    "optim.dino_head_wd_multiplier": {
        "kind": "justified",
        "why": "per-group WD multiplier (reference recipe)"},
    "optim.layerwise_decay": {
        "kind": "justified",
        "why": "layerwise LR decay base (reference recipe)"},
    "optim.adamw_beta1": {
        "kind": "justified",
        "why": "AdamW moment coefficient (reference recipe)"},
    "optim.adamw_beta2": {
        "kind": "justified",
        "why": "AdamW moment coefficient (reference recipe)"},
    "optim.accum_steps": {
        "kind": "justified",
        "why": "gradient-accumulation factor — a memory/batch choice "
               "made by the launch config, not a tunable latency "
               "constant (its cost story is COST_UNIFIED_r18.json)"},
    "kernels.flash_block_q": {
        "kind": "justified",
        "why": "pallas flash kernel query-tile cap — hardware tile "
               "alignment (MXU/VMEM), changed only with the kernel"},
    "kernels.flash_block_kv": {
        "kind": "justified",
        "why": "pallas flash kernel key/value-tile cap — hardware "
               "tile alignment (MXU/VMEM), changed only with the "
               "kernel"},
    # ---- train.low_precision (ops/lowp.py, PR 17) ----
    "train.low_precision.arm": {
        "kind": "justified",
        "why": "precision-arm mode switch (bf16|fp8|int8), not a "
               "magnitude — its cost story is COST_LP_r21.json and "
               "the phQ on-chip A/B (not yet run)"},
    "train.low_precision.amax_history_len": {
        "kind": "justified",
        "why": "delayed-scaling amax ring length — the Transformer "
               "Engine default (16); a numerics-stability window, "
               "not a latency knob (the ring is a few f32 scalars "
               "per kernel)"},
    "train.low_precision.scale_margin": {
        "kind": "justified",
        "why": "headroom multiplier on the history amax — overflow "
               "insurance for between-step weight drift (numerics, "
               "not latency); 1.0 = trust the one-step-delayed amax"},
    "train.low_precision.divergence_tol": {
        "kind": "justified",
        "why": "warn_lowp_divergence gate on the setup drift probe — "
               "an alerting threshold (rel. Frobenius), not a "
               "schedule constant"},
}

# Dotted entries ("train.low_precision") walk nested config nodes — the
# census covers sub-blocks without sweeping every train.* key into it.
CENSUS_SECTIONS = ("optim", "kernels", "train.low_precision")


def _is_numeric(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def knob_census(cfg=None) -> dict:
    """Walk the default config's ``optim``/``kernels`` sections and
    classify every numeric constant against KNOB_REGISTRY. Returns
    ``{"ok": bool, "entries": [...], "unregistered": [...],
    "stale_registry": [...]}`` — ``unregistered`` are numeric keys
    with no registry entry (the failure the census exists to catch),
    ``stale_registry`` are registry entries whose key no longer
    exists in the config (a renamed/removed knob must leave the
    registry too)."""
    if cfg is None:
        from dinov3_tpu.configs import get_default_config

        cfg = get_default_config()
    entries = []
    unregistered = []
    seen = set()
    present_sections = []
    for section in CENSUS_SECTIONS:
        node = cfg
        for part in section.split("."):
            node = (node.get(part) or {}) if node else {}
        if node:
            present_sections.append(section)
        for key in node:
            value = node.get(key)
            name = f"{section}.{key}"
            reg = KNOB_REGISTRY.get(name)
            if reg is None:
                if _is_numeric(value):
                    unregistered.append({"knob": name, "default": value})
                continue
            seen.add(name)
            if not reg.get("why"):
                unregistered.append(
                    {"knob": name, "default": value,
                     "error": "registered without a justification"})
                continue
            entry = {"knob": name, "default": value,
                     "kind": reg["kind"], "why": reg["why"]}
            for opt in ("resolver", "artifact"):
                if opt in reg:
                    entry[opt] = reg[opt]
            entries.append(entry)
    # staleness is scoped to the sections the given config actually
    # carries: a partial/shadow config (tests census just optim+kernels)
    # must not read the other sections' registry entries as stale
    stale = sorted(
        name for name in set(KNOB_REGISTRY) - seen
        if any(name.startswith(s + ".") for s in present_sections))
    return {
        "ok": not unregistered and not stale,
        "n_knobs": len(entries),
        "by_kind": {
            kind: sorted(e["knob"] for e in entries if e["kind"] == kind)
            for kind in ("tuned", "crossover", "justified")
        },
        "entries": entries,
        "unregistered": unregistered,
        "stale_registry": stale,
    }
