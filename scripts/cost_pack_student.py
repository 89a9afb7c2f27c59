"""Host-side accounting for the crop-packed single-pass student engine
(ops/packing.py, model.crop_packing): student-phase weight-stream bytes,
row counts, and pad-waste fractions — packed vs the two-pass oracle, at
pass granularity.

Methodology (the PR-1/2/3 discipline, scripts/cost_update_phase.py /
cost_target_phase.py / cost_rng_copies.py): each student pass of the
ORACLE program is compiled as its own XLA fwd+bwd program — the
granularity at which the weight stack actually streams from HBM (one
read per forward, one per backward, per program) — and the PACKED
engine as one program. Three numbers per arm:

- ``weight_stream_bytes``: fp32 master bytes x the number of
  weight-stack streams (2 per program: fwd read + bwd read). This is
  STRUCTURAL: the two-pass oracle streams the ViT-L stack 4x per step
  (global fwd/bwd + local fwd/bwd), the packed engine 2x — the -50%
  that motivates the engine. No backend fusion can merge two separately
  dispatched backbone applications' weight reads.
- ``bytes_accessed``: the compiled programs'
  ``cost_analysis()['bytes accessed']`` summed per arm — the measured
  corroboration (includes activations, so the relative saving is
  smaller than the weight-stream number; stated, not hidden).
- row/pad geometry: 120 token-rows -> 44 at ViT-L B=12, the packed
  token pad-waste fraction, and the 128-lane pad factor of the
  37-token local rows the packing removes (the same padding-cliff
  class as the B=10 sublane guardrail).

Both arms are compiled DETERMINISTIC (no drop-path subsetting): the
subset engine is orthogonal and its cut is priced in the FLOP ledger
(scripts/count_flops.py vitl_subset vs vitl_mask); mixing the two
randomized gathers into this accounting would blur which engine owns
which bytes. The unrolled stack is compiled on every point (the scan
caveat from count_flops.py: cost_analysis counts a scan body once).

One JSON line on stdout -> commit as COST_PACK_r09.json. The on-chip
A/B that measures what the TPU scheduler does with each form has not
been run on the chip (both arms BENCH_PROBS=bf16 BENCH_CENSUS=1).

Usage: JAX_PLATFORMS=cpu python scripts/cost_pack_student.py
Env: COST_ARCH (default vit_large), COST_BATCH (default 12)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bytes_accessed(compiled) -> float:
    analysis = compiled.cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0]
    return float(analysis["bytes accessed"])


def _lane_pad_factor(n: int, lane: int = 128) -> float:
    """Padded-lane fraction of an [., n] attention-score axis."""
    padded = -(-n // lane) * lane
    return (padded - n) / n


def main():
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.utils import configure_compile_cache

    configure_compile_cache()

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.models import build_backbone
    from dinov3_tpu.ops.packing import layout_from_cfg

    arch = os.environ.get("COST_ARCH", "vit_large")
    B = int(os.environ.get("COST_BATCH", "12"))
    cfg = get_default_config()
    apply_dot_overrides(cfg, [
        f"student.arch={arch}", "train.scan_layers=false",
        "optim.scaling_rule=none",
    ])
    module = build_backbone(cfg, teacher=False, param_dtype=jnp.float32)
    S = int(cfg.crops.global_crops_size)
    s = int(cfg.crops.local_crops_size)
    n_l = int(cfg.crops.local_crops_number)
    g_abs = jax.ShapeDtypeStruct((2 * B, S, S, 3), jnp.float32)
    l_abs = jax.ShapeDtypeStruct((n_l * B, s, s, 3), jnp.float32)
    params_abs = jax.eval_shape(
        lambda r: module.init(r, jnp.zeros((1, S, S, 3)))["params"],
        jax.random.key(0))
    param_bytes = sum(
        leaf.size * 4 for leaf in jax.tree.leaves(params_abs))
    layout = layout_from_cfg(cfg, B)

    def out_sum(out):
        total = (jnp.sum(out["x_norm_clstoken"].astype(jnp.float32))
                 + jnp.sum(out["x_norm_patchtokens"].astype(jnp.float32)))
        if "local_cls" in out:
            total = total + jnp.sum(out["local_cls"].astype(jnp.float32))
        return total

    def g_pass(p, g):
        return out_sum(module.apply({"params": p}, g, None,
                                    crop_kind="global", deterministic=True))

    def l_pass(p, l):
        return out_sum(module.apply({"params": p}, l, None,
                                    crop_kind="local", deterministic=True))

    def packed_pass(p, g, l):
        return out_sum(module.apply({"params": p}, g, None,
                                    crop_kind="global", deterministic=True,
                                    local_crops=l))

    programs = {
        "oracle_global": (jax.grad(g_pass), (params_abs, g_abs)),
        "oracle_local": (jax.grad(l_pass), (params_abs, l_abs)),
        "packed": (jax.grad(packed_pass), (params_abs, g_abs, l_abs)),
    }
    measured = {}
    for name, (fn, args) in programs.items():
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        measured[name] = {
            "bytes_accessed": _bytes_accessed(compiled),
            "compile_s": round(time.perf_counter() - t0, 1),
        }
        print(f"[pack] {name}: {measured[name]['bytes_accessed'] / 1e9:.2f} "
              f"GB accessed ({measured[name]['compile_s']}s compile)",
              file=sys.stderr, flush=True)

    oracle_bytes = (measured["oracle_global"]["bytes_accessed"]
                    + measured["oracle_local"]["bytes_accessed"])
    packed_bytes = measured["packed"]["bytes_accessed"]
    # weight-stream structure: fwd read + bwd read per compiled program
    streams_oracle, streams_packed = 2 * 2, 1 * 2
    rows_oracle = 2 * B + n_l * B
    rec = {
        "what": ("crop-packed single-pass student engine accounting: "
                 "fp32 weight-stream bytes (structural: streams x "
                 "param bytes, fwd+bwd per compiled program), measured "
                 "bytes accessed (cost_analysis, host compile, "
                 "deterministic passes, unrolled stack), row/pad "
                 "geometry"),
        "script": "scripts/cost_pack_student.py",
        "date": time.strftime("%Y-%m-%d"),
        "arch": arch, "batch_per_chip": B,
        "param_bytes_fp32": param_bytes,
        "weight_stream": {
            "oracle_streams": streams_oracle,
            "packed_streams": streams_packed,
            "oracle_bytes": streams_oracle * param_bytes,
            "packed_bytes": streams_packed * param_bytes,
            "reduction_pct": round(
                100.0 * (1.0 - streams_packed / streams_oracle), 1),
        },
        "bytes_accessed": {
            "oracle_pass_granularity": oracle_bytes,
            "packed": packed_bytes,
            "reduction_pct": round(
                100.0 * (1.0 - packed_bytes / oracle_bytes), 1),
            "per_program": measured,
        },
        "rows": {
            "oracle": rows_oracle,
            "packed": layout.rows_total,
            "k": layout.k,
            "packed_rows_local": layout.n_packed_rows,
            "seq_global": layout.seq_global,
            "seq_local": layout.seq_local,
        },
        "pad_waste": {
            "packed_token_fraction": round(layout.pad_waste, 4),
            "lane_pad_factor_local_rows": round(
                _lane_pad_factor(layout.seq_local), 3),
            "lane_pad_factor_packed_rows": round(
                _lane_pad_factor(layout.seq_global), 3),
        },
    }
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
