"""Bytes-accessed accounting for the teacher-target/CE ("target") phase:
materialized [*, K] teacher targets + CE reads vs the streaming
prototype-axis engine (losses/streaming.py) — plus a compiled-HLO copy
census of the full train step (donation/aliasing audit).

Methodology (PR-1 discipline, scripts/cost_update_phase.py): the
MATERIALIZED path is accounted at pass granularity — each pass is
compiled as its own XLA program and their ``cost_analysis()['bytes
accessed']`` summed:

- ``targets``: teacher logits -> materialized [*, K] probability buffers
  (softmax-center or the 3-iteration Sinkhorn), stored in
  ``compute_precision.target_dtype``;
- ``dino_ce``: student CLS logits x the materialized CLS targets ->
  both DINO losses (the logit-einsum CE);
- ``ibot_ce``: student masked-token logits x the materialized masked
  targets -> iBOT loss.

This is the granularity the r5 on-chip profile shows the TPU executing
the phase at (``PROFILE_r05.json``: 10.2% of step time in fp32
``convert_reduce``/``exponential_reduce`` passes over the [*, 65536]
buffers). The STREAMING engine is ONE program computing the same three
losses directly from the logits in a single K-tiled pass — the target
buffer never exists, so the saving is algorithmic, not a fusion
artifact: even a backend that fused the whole materialized phase into
one program would still write+read the [*, K] buffer unless it
re-derived the streaming algebra itself (the online-max rescaled
cross-term accumulation).

The copy census compiles the EXACT jitted train step (with state
donation, compile-only — the jaxlib<=0.4.36 cpu cache-staleness bug is
an execution-time bug, see utils.donation_safe_argnums) and counts HLO
``copy``/``copy-start``/``copy-done``/``dynamic-update-slice``
instructions outside fusion bodies plus any donation warnings, so
donation regressions and layout-churn copies fail CI
(tests/test_streaming_targets.py pins the ceiling).

One JSON line on stdout:

    {"arch": ..., "target_phase": {<centering>: {<target_dtype>: {...}}},
     "copy_census": {...}}

Usage: JAX_PLATFORMS=cpu python scripts/cost_target_phase.py [arch]
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import importlib.util

_spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench.py")
)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def _bytes_accessed(fn, args) -> float:
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    analysis = compiled.cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0]
    return float(analysis["bytes accessed"])


def measure_target_phase(cfg, centering: str, target_dtype) -> dict:
    """Pass-granularity bytes for materialized vs streaming, one
    centering mode and one target storage dtype."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.losses import (
        ibot_loss_from_spec,
        ibot_patch_loss_masked,
        pair_ce_from_spec,
        pair_ce_to_loss,
        sinkhorn_knopp,
        softmax_center_teacher,
    )
    from dinov3_tpu.ops import Policy

    policy = Policy.from_cfg(cfg.compute_precision)
    comp = policy.compute_dtype
    B = int(os.environ.get("COST_BATCH", "12"))
    n_g, n_l = 2, cfg.crops.local_crops_number
    K = cfg.dino.head_n_prototypes
    K_i = cfg.ibot.head_n_prototypes
    from dinov3_tpu.data.masking import masked_rows_bound

    masks = make_synthetic_batch(cfg, 2, seed=0)
    # the step's compact iBOT rows (SSLMetaArch.masked_rows)
    rows_m = masked_rows_bound(
        2 * B, masks["masks"].shape[1], masks["mask_indices"].shape[1],
        tuple(cfg.ibot.mask_ratio_min_max), cfg.ibot.mask_sample_probability)
    k_tile = int((cfg.get("loss") or {}).get("k_tile") or 8192)

    sd = jax.ShapeDtypeStruct
    cls_logits = sd((n_g * B, K), comp)
    masked_logits = sd((rows_m, K_i), comp)
    student_cat = sd((n_g + n_l, B, K), comp)
    student_masked = sd((rows_m, K_i), comp)
    center_d = sd((1, K), jnp.float32)
    center_i = sd((1, K_i), jnp.float32)
    valid = sd((rows_m,), jnp.float32)
    weights = sd((rows_m,), jnp.float32)
    temp = sd((), jnp.float32)

    def make_targets(cls_l, masked_l, v, c_d, c_i, t):
        if centering == "sinkhorn_knopp":
            q_c = sinkhorn_knopp(cls_l, t, storage_dtype=target_dtype)
            q_m = sinkhorn_knopp(masked_l, t, row_weights=v,
                                 storage_dtype=target_dtype)
        else:
            q_c = softmax_center_teacher(cls_l, c_d, t,
                                         storage_dtype=target_dtype)
            q_m = softmax_center_teacher(masked_l, c_i, t,
                                         storage_dtype=target_dtype)
            q_m = q_m * v[:, None].astype(q_m.dtype)
        return q_c, q_m

    q_c_abs, q_m_abs = jax.eval_shape(
        make_targets, cls_logits, masked_logits, valid, center_d,
        center_i, temp)

    def dino_ce(cat, q_c):
        pair = pair_ce_from_spec(
            cat, {"kind": "probs", "probs": q_c.reshape(n_g, B, K)})
        return (pair_ce_to_loss(pair[n_g:], B),
                pair_ce_to_loss(pair[:n_g], B, ignore_diagonal=True))

    def ibot_ce(sm, q_m, w):
        return ibot_patch_loss_masked(sm, q_m, w, n_images=n_g * B)

    def streaming(cat, sm, cls_l, masked_l, v, c_d, c_i, t, w):
        if centering == "sinkhorn_knopp":
            cspec = {"kind": "sinkhorn", "factors": sinkhorn_knopp(
                cls_l, t, storage_dtype=target_dtype, return_factors=True)}
            mspec = {"kind": "sinkhorn", "factors": sinkhorn_knopp(
                masked_l, t, row_weights=v, storage_dtype=target_dtype,
                return_factors=True)}
        else:
            cspec = {"kind": "softmax_center",
                     "logits": cls_l.reshape(n_g, B, K),
                     "center": c_d, "temp": t}
            mspec = {"kind": "softmax_center", "logits": masked_l,
                     "center": c_i, "temp": t}
        pair = pair_ce_from_spec(cat, cspec, k_tile=k_tile)
        ibot = ibot_loss_from_spec(sm, mspec, w, n_images=n_g * B,
                                   k_tile=k_tile)
        return (pair_ce_to_loss(pair[n_g:], B),
                pair_ce_to_loss(pair[:n_g], B, ignore_diagonal=True),
                ibot)

    passes = {
        "targets": _bytes_accessed(
            make_targets,
            (cls_logits, masked_logits, valid, center_d, center_i, temp)),
        "dino_ce": _bytes_accessed(dino_ce, (student_cat, q_c_abs)),
        "ibot_ce": _bytes_accessed(
            ibot_ce, (student_masked, q_m_abs, weights)),
    }
    bytes_streaming = _bytes_accessed(
        streaming,
        (student_cat, student_masked, cls_logits, masked_logits, valid,
         center_d, center_i, temp, weights))
    total = sum(passes.values())
    target_rows = n_g * B + rows_m
    return {
        "K": K, "rows_targets": target_rows, "k_tile": k_tile,
        "bytes_materialized_passes": passes,
        "bytes_materialized_total": total,
        "bytes_streaming": bytes_streaming,
        "reduction_pct": round(100.0 * (1.0 - bytes_streaming / total), 1),
    }


# ---------------- compiled-HLO helpers (copy census + target-buffer
# materialization check) ----------------


def non_fusion_lines(hlo_text: str):
    """Instruction lines outside fused-computation bodies — the
    allocation-relevant set for both the copy census and the [*, K]
    materialization check (shared impl: utils.hlo_non_fusion_lines)."""
    from dinov3_tpu.utils import hlo_non_fusion_lines

    return hlo_non_fusion_lines(hlo_text)


def count_materialized(hlo_text: str, dtype_str: str, last_dim: int,
                       rows: int, include_fusions: bool = False,
                       op_pattern: str | None = None) -> int:
    r"""Count instruction results of shape ``dtype[*, last_dim]`` whose
    leading dims multiply to ``rows`` — the teacher-target buffer
    signature.

    ``include_fusions=False`` counts only buffer-allocating (non-fusion-
    body) instructions. ``include_fusions=True`` scans every op,
    including fusion internals: a program in which NO op anywhere even
    produces a full [rows, K] value of the target dtype provably never
    materializes that buffer, regardless of how the backend fuses — the
    version-robust form of the streaming claim (a tiled engine's
    target-valued ops are all [rows, k_tile]-shaped).

    ``op_pattern`` restricts to specific op kinds, e.g.
    ``r"(exponential|divide)\("`` for target VALUES (softmax/sinkhorn
    probabilities). Distinguishing values matters because a backend may
    legally hoist a one-time fp32 convert of the loop-invariant LOGITS
    out of the K-tile loop (observed on XLA:CPU, which strips the
    optimization barriers guarding against it; the TPU pipeline honors
    them) — a bounded scheduling choice that the bytes-accessed
    accounting already reflects, distinct from materializing the
    targets."""
    pat = re.compile(r"=\s*" + re.escape(dtype_str) + r"\[([\d,]+)\]")
    lines = (hlo_text.splitlines() if include_fusions
             else non_fusion_lines(hlo_text))
    op_re = re.compile(op_pattern) if op_pattern else None
    n = 0
    for line in lines:
        m = pat.search(line)
        if not m:
            continue
        if op_re is not None and not op_re.search(line):
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        if len(dims) >= 2 and dims[-1] == last_dim:
            lead = 1
            for d in dims[:-1]:
                lead *= d
            if lead == rows:
                n += 1
    return n


def copy_census(cfg, B: int = 4) -> dict:
    """Compile the exact jitted train step (donated state) on the host
    backend and count copy-class HLO ops + donation warnings."""
    import warnings

    import jax
    import jax.numpy as jnp

    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import (
        build_fused_update,
        build_optimizer,
        build_schedules,
    )
    from dinov3_tpu.train.ssl_meta_arch import SSLMetaArch
    from dinov3_tpu.train.train_step import TrainState, make_train_step

    meta = SSLMetaArch(cfg)
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, B, seed=0).items()}
    abstract_params = jax.eval_shape(
        lambda r: meta.init_params(r, batch), jax.random.key(0))
    schedules = build_schedules(cfg)
    optimizer = build_optimizer(cfg, abstract_params["student"], schedules)
    fused = build_fused_update(cfg, abstract_params["student"], schedules,
                               ema=not meta.distillation)
    step = make_train_step(meta, optimizer, clip_grad=cfg.optim.clip_grad,
                           fused_update=fused)
    state_abs = TrainState(
        params=abstract_params,
        opt_state=jax.eval_shape(optimizer.init, abstract_params["student"]),
        center_state=jax.eval_shape(meta.init_state),
        step=jax.ShapeDtypeStruct((), jnp.int32),
    )
    batch_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in batch.items()}
    scalars_abs = {"teacher_temp": jax.ShapeDtypeStruct((), jnp.float32),
                   "momentum": jax.ShapeDtypeStruct((), jnp.float32)}
    rng_abs = jax.eval_shape(lambda: jax.random.key(0))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            state_abs, batch_abs, scalars_abs, rng_abs).compile()
    donation_warnings = [str(w.message) for w in caught
                         if "donat" in str(w.message).lower()]
    from dinov3_tpu.utils import hlo_copy_census

    # per-category attribution (rng / donation_async / small / large):
    # a future copy regression names its source instead of only moving
    # the total (utils.classify_copy documents the category heuristics)
    rec = hlo_copy_census(compiled.as_text())
    rec["donation_warnings"] = donation_warnings
    return rec


def main():
    import jax.numpy as jnp

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config

    arch = sys.argv[1] if len(sys.argv) > 1 else "vit_large"
    cfg = get_default_config()
    apply_dot_overrides(cfg, bench.build_step_overrides(arch, 0))
    rec = {"arch": arch, "target_phase": {}}
    for centering in ("sinkhorn_knopp", "softmax_center"):
        rec["target_phase"][centering] = {
            "fp32": measure_target_phase(cfg, centering, None),
            "bf16": measure_target_phase(cfg, centering, jnp.bfloat16),
        }
    # the census compiles the full step: use the test arch so the CPU
    # compile stays seconds-long; the copy structure under audit
    # (donation aliasing, subset-gather copies, loss-phase copies) is
    # arch-independent at this granularity
    census_cfg = get_default_config()
    apply_dot_overrides(census_cfg, [
        "student.arch=vit_test", "student.patch_size=4",
        "crops.global_crops_size=16", "crops.local_crops_size=8",
        "crops.local_crops_number=2",
        "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
        "dino.head_bottleneck_dim=16",
        "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
        "ibot.head_bottleneck_dim=16",
        "optim.scaling_rule=none",
    ])
    rec["copy_census"] = {
        "arch": "vit_test",
        "streaming_on": copy_census(census_cfg),
    }
    apply_dot_overrides(census_cfg, ["loss.streaming_targets=false"])
    rec["copy_census"]["streaming_off"] = copy_census(census_cfg)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
