"""Op-level flash-vs-dense attention crossover: the EXECUTABLE
definition of the ``kernels.flash_min_seq`` dispatch threshold.

The full-step high-res programs take minutes to compile; this measures
the SAME dispatch
decision (``dinov3_tpu/ops/attention.py``; config default
``kernels.flash_min_seq: auto`` resolves from THIS script's committed
artifact) with tiny fwd+bwd programs that compile in seconds, at the
token counts the recipes actually produce (224px->201, 512px->1029,
518px->1054, 768px->2309, plus 4096).

The threshold's definition is ``recommended_flash_min_seq``: the
smallest measured N at which the Pallas flash kernel beats dense XLA on
fwd+bwd wall time — dispatch flash for N >= that, dense below (None =
flash never won a measured point; keep dense everywhere). The committed
CROSSOVER_r19.json is this harness's verdict on the current platform
(``configs/config.py resolve_flash_min_seq`` reads it; on the CPU
harness interpret-mode Pallas never wins, so the verdict is null =
dense everywhere). Re-derive on-chip and commit the new
artifact over it — never hand-edit the threshold.

Prints one JSON line per (N, impl) with ms/call, then a crossover
summary with the derived threshold. An out path ending in ``.json``
switches to committed-artifact mode (one JSON document). CPU tests
(tests/test_crossover_attention.py) keep the harness collectable, the
threshold definition pinned, and the committed artifact well-formed.

Usage: python scripts/crossover_attention.py [out.jsonl|out.json]
Env: XOVER_MAX_N (skip cases above N), XOVER_STEPS (20),
     XOVER_WARMUP (3; lower it on interpreted-Pallas CPU runs where a
     single flash call can take seconds),
     XOVER_CASES ("B1xN1,B2xN2,..." overrides the case ladder).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ViT-L geometry: 16 heads x 64 head_dim; B chosen so B*N is roughly
# the 224px global-crop workload (16 seqs x 201 tokens) per call
HEADS, HEAD_DIM = 16, 64
CASES = [(16, 201), (4, 1029), (4, 1054), (2, 2309), (1, 4096)]


def parse_cases(s: str) -> list[tuple[int, int]]:
    """"16x201,4x1029" -> [(16, 201), (4, 1029)]."""
    out = []
    for part in s.split(","):
        b, n = part.lower().split("x")
        out.append((int(b), int(n)))
    return out


def measure_case(B: int, N: int, impl: str, steps: int, warmup: int,
                 heads: int = HEADS, head_dim: int = HEAD_DIM) -> dict:
    """One (B, N, impl) fwd+bwd timing record ({"error": ...} on
    failure — e.g. the Pallas kernel on a CPU backend)."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.ops.attention import xla_attention

    q, k, v = (
        jax.random.normal(jax.random.key(i), (B, N, heads, head_dim),
                          jnp.bfloat16)
        for i in range(3)
    )
    if impl == "pallas":
        from dinov3_tpu.ops.flash_attention import flash_attention

        def fwd(q, k, v):
            return flash_attention(q, k, v)
    else:

        def fwd(q, k, v):
            return xla_attention(q, k, v, probs_dtype=jnp.bfloat16)

    # fwd+bwd like the train step sees it
    f = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fwd(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2),
    ))

    # Synchronize via a value fetch: the fetched scalar forces the
    # whole fwd+bwd chain (same fence as bench.py's measure loop).
    def sync(g):
        return float(jnp.sum(g[0].astype(jnp.float32)))

    try:
        t0 = time.time()
        sync(f(q, k, v))
        compile_s = time.time() - t0
        g = None
        for _ in range(max(warmup, 0)):
            g = f(q, k, v)
        if g is not None:
            sync(g)
        t0 = time.perf_counter()
        for _ in range(steps):
            g = f(q, k, v)
        sync(g)
        ms = (time.perf_counter() - t0) / steps * 1e3
    except Exception as e:  # noqa: BLE001 - record and continue
        return {"B": B, "N": N, "impl": impl, "error": str(e)[:200]}
    return {"B": B, "N": N, "impl": impl, "ms": round(ms, 3),
            "compile_s": round(compile_s, 1)}


def measure_crossover(cases=None, steps: int = 20, warmup: int = 3,
                      emit=None) -> list[dict]:
    """All (case, impl) records; ``emit(rec)`` streams each as it lands
    (JSONL writers)."""
    records = []
    for B, N in (cases if cases is not None else CASES):
        for impl in ("xla", "pallas"):
            rec = measure_case(B, N, impl, steps, warmup)
            records.append(rec)
            if emit:
                emit(rec)
    return records


def crossover_summary(records: list[dict]) -> list[dict]:
    """Per-N xla-vs-flash pairs (cases where both impls measured)."""
    by_key = {(r["B"], r["N"], r["impl"]): r["ms"]
              for r in records if "ms" in r}
    seen, summary = set(), []
    for r in records:
        B, N = r["B"], r["N"]
        if (B, N) in seen:
            continue
        seen.add((B, N))
        a, b = by_key.get((B, N, "xla")), by_key.get((B, N, "pallas"))
        if a and b:
            summary.append({"N": N, "xla_ms": round(a, 3),
                            "flash_ms": round(b, 3),
                            "flash_speedup": round(a / b, 3)})
    return summary


def recommended_flash_min_seq(summary: list[dict]) -> int | None:
    """THE threshold definition: the smallest measured N where the flash
    kernel's fwd+bwd beats dense XLA (flash_speedup >= 1) — dispatch
    flash at N >= this. None = flash never won a measured point (keep
    dense everywhere, i.e. an effectively infinite flash_min_seq)."""
    wins = sorted(row["N"] for row in summary
                  if row["flash_speedup"] >= 1.0)
    return wins[0] if wins else None


def main():
    import jax

    from dinov3_tpu.utils import configure_compile_cache

    configure_compile_cache()

    out_path = sys.argv[1] if len(sys.argv) > 1 else "/tmp/attn_crossover.jsonl"
    cases = CASES
    if os.environ.get("XOVER_CASES"):
        cases = parse_cases(os.environ["XOVER_CASES"])
    if os.environ.get("XOVER_MAX_N"):  # CPU smoke: skip the big cases
        cases = [c for c in cases if c[1] <= int(os.environ["XOVER_MAX_N"])]
    steps = int(os.environ.get("XOVER_STEPS", "20"))
    warmup = int(os.environ.get("XOVER_WARMUP", "3"))

    with open(out_path, "a") as out:
        def emit(rec):
            line = json.dumps(rec)
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()

        records = measure_crossover(cases, steps=steps, warmup=warmup,
                                    emit=emit)
        summary = crossover_summary(records)
        line = json.dumps({
            "crossover": summary,
            "recommended_flash_min_seq": recommended_flash_min_seq(summary),
        })
        print(line, flush=True)
        out.write(line + "\n")

    if out_path.endswith(".json"):
        # committed-artifact mode (CROSSOVER_r19.json): one JSON document
        # the config resolver (configs/config.py resolve_flash_min_seq)
        # and the artifact-pin test read — overwrites the JSONL stream
        # written above with the final combined record.
        doc = {
            "generated_by": "scripts/crossover_attention.py",
            "platform": jax.devices()[0].platform,
            "jax": jax.__version__,
            "heads": HEADS, "head_dim": HEAD_DIM,
            "steps": steps,
            "records": records,
            "crossover": summary,
            "recommended_flash_min_seq": recommended_flash_min_seq(summary),
        }
        with open(out_path, "w") as out:
            json.dump(doc, out, indent=1)
            out.write("\n")


if __name__ == "__main__":
    main()
