"""Small shared utilities: parameter counting, loss recording/comparison,
weight dumps.

(reference: dinov3_jax/utils/utils.py ``count_parameters`` — which
contained a live ``IPython.embed()`` (SURVEY.md §2.9) — and the trainer's
declared-but-unwired verification flags ``--record-ref-losses`` /
``--ref-losses-path`` / ``--dump-fsdp-weights``
(dinov3_jax/train/train.py:63-69, never referenced again). Here they all
function; the loss recorder/comparator is the numerical-parity workflow
the reference intended: record per-iteration losses from a trusted run,
then compare a refactored run against them within a tolerance.)
"""

from __future__ import annotations

import json
import logging
import re
from typing import Mapping

import jax
import numpy as np

logger = logging.getLogger("dinov3")


def count_parameters(params, by_top_level: bool = True) -> dict:
    """{submodule: parameter count} plus a ``total`` entry."""
    out: dict = {}
    if by_top_level and isinstance(params, Mapping):
        for key, sub in params.items():
            out[key] = sum(int(np.prod(x.shape))
                           for x in jax.tree.leaves(sub))
    out["total"] = sum(v for k, v in out.items()) if out else sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(params)
    )
    return out


def format_parameter_counts(counts: dict) -> str:
    width = max(len(k) for k in counts)
    lines = [f"{k:<{width}}  {v / 1e6:10.2f} M" for k, v in counts.items()]
    return "\n".join(lines)


class LossRecorder:
    """Append per-iteration scalar dicts; written as JSON lines."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "w")

    def record(self, iteration: int, metrics: Mapping[str, float]) -> None:
        row = {"iteration": int(iteration)}
        row.update({k: float(v) for k, v in metrics.items()})
        self._f.write(json.dumps(row) + "\n")
        self._f.flush()

    def record_batch(self, iterations, names, rows) -> None:
        """Record one flushed telemetry batch: ``rows[j]`` is the
        ``[M]`` metric vector of ``iterations[j]`` with ``names`` as
        column order (telemetry/ring.py RingReader.flush) — the exact
        per-step values the ring stored, so ``--record-losses`` traces
        are identical under async metrics and the per-step oracle."""
        for it, row in zip(iterations, rows):
            self.record(int(it), dict(zip(names, row)))

    def close(self) -> None:
        self._f.close()


class LossComparator:
    """Compare a run's losses against a recorded file, iteration by
    iteration. ``check`` logs each divergence and returns whether the
    iteration matched; ``summary`` reports the worst deviation."""

    def __init__(self, path: str, rtol: float = 1e-3, atol: float = 1e-4):
        self.rows = {}
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                self.rows[int(row.pop("iteration"))] = row
        self.rtol, self.atol = rtol, atol
        self.worst: tuple = (0.0, None, -1)  # (abs err, key, iteration)
        self.n_checked = 0
        self.n_diverged = 0

    def check(self, iteration: int, metrics: Mapping[str, float]) -> bool:
        ref = self.rows.get(int(iteration))
        if ref is None:
            return True
        self.n_checked += 1
        ok = True
        for key, want in ref.items():
            got = metrics.get(key)
            if got is None:
                continue
            got = float(got)
            err = abs(got - want)
            if err > self.atol + self.rtol * abs(want):
                ok = False
                logger.warning(
                    "loss divergence at iter %d: %s = %.6g, recorded %.6g",
                    iteration, key, got, want,
                )
            if err > self.worst[0]:
                self.worst = (err, key, iteration)
        self.n_diverged += not ok
        return ok

    def check_batch(self, iterations, names, rows) -> bool:
        """Check one flushed telemetry batch (see
        ``LossRecorder.record_batch``); returns whether EVERY row
        matched, logging divergences row by row as ``check`` does."""
        ok = True
        for it, row in zip(iterations, rows):
            ok = self.check(int(it), dict(zip(names, row))) and ok
        return ok

    def summary(self) -> str:
        err, key, it = self.worst
        head = (f"compared {self.n_checked} iterations, "
                f"{self.n_diverged} diverged")
        if key is None:
            return head + "; exact match"
        return head + f"; worst |err| {err:.3g} on {key!r} at iter {it}"


def dump_weights(path: str, params) -> None:
    """Flat ``.npz`` dump of a parameter tree ('/'-joined keys) for offline
    inspection or cross-framework diffing.

    Call from EVERY process of a multi-host run: gathering shards that
    live on other hosts is a collective (all hosts must participate);
    only process 0 writes the file."""
    flat = {}
    for keypath, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in keypath
        )
        if hasattr(leaf, "is_fully_addressable") and not leaf.is_fully_addressable:
            from jax.experimental import multihost_utils

            leaf = multihost_utils.process_allgather(leaf, tiled=True)
        # non-writer hosts only participate in the collective; holding a
        # full unsharded copy of every param would OOM memory-tight hosts
        if jax.process_index() == 0:
            flat[name] = np.asarray(leaf)
    if jax.process_index() == 0:
        np.savez(path, **flat)
        logger.info("dumped %d arrays to %s", len(flat), path)


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call it first thing in
    every entry point (trainer, evals CLI, bench.py,
    scripts/bench_serve.py, chip_smoke.py) and nowhere else.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself
    and this sets NO directory in code — whoever runs the program owns
    the placement. Where it is not, the cache lives at one fixed path
    inside the checkout, ``<repo>/.jax_cache``: the directory is part of
    the cache key's environment, so a path that moves (a temp dir, a
    uid, a pid, a time) never hits. Returns the directory in effect.

    The key INCLUDES each instruction's metadata. JAX's default key
    strips it, so a program that differs from a cached one only by its
    named scopes (``STEP_PHASES``) is served the cached, scope-less
    executable and every trace reader finds no phase. The price: an edit
    that moves a traced source line misses the cache once. File names
    enter the key relative to the checkout, so a checkout at another
    path still finds what the same files compiled.

    Being the first call of every entry point, it is also where the
    process's span log (``telemetry/spans.py``) starts to hear JAX's
    trace / lower / compile events, and its own span
    ``setup.compile_cache`` starts where the process's imports end."""
    import os

    from dinov3_tpu.telemetry import spans

    with spans.LOG.span("setup.compile_cache"):
        spans.listen_to_jax()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True)
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(root + os.sep))
        env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if env_dir:
            return env_dir
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
        return path


def require_accelerator(device: str | None = "tpu") -> dict:
    """Initialise the backend and return the device identity every
    record carries (``platform``, ``device_kind``, ``count``), or raise
    when the run would land on a platform nobody asked for.

    ``device`` is the configured platform (``MODEL.DEVICE``; empty or
    None means the default, ``tpu``). The CPU is used only on an explicit request — a
    configured ``cpu``, or ``JAX_PLATFORMS=cpu`` in the environment
    (tests, shape-only dry runs). With the default and no such request,
    finding no chip is an ERROR, not a CPU run: a trainer that exits 0
    after training on the host, or a CPU number printed under a device
    metric, is the failure this guards against."""
    import os

    wanted = str(device or "tpu")
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        wanted = "cpu"
    if wanted != "tpu":
        jax.config.update("jax_platforms", wanted)
    dev = jax.devices()[0]
    if dev.platform != wanted:
        raise RuntimeError(
            f"wanted a {wanted!r} backend but JAX's default device is "
            f"{dev.platform!r} ({dev.device_kind!r}). Run on the chip, or "
            "ask for the CPU explicitly (JAX_PLATFORMS=cpu or "
            "MODEL.DEVICE=cpu).")
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "count": len(jax.devices())}


# ---------------- compiled-HLO copy census (shared by
# scripts/cost_target_phase.py, scripts/cost_rng_copies.py and
# `bench.py --census`) ----------------

_HLO_COMP_HEADER = None  # compiled lazily (re module import kept local)

_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

HLO_COPY_OPS = ("copy", "copy-start", "copy-done", "dynamic-update-slice")


def hlo_non_fusion_lines(hlo_text: str):
    """Yield instruction lines outside fused-computation bodies.

    Instructions at the top level of any non-fusion computation (ENTRY,
    while bodies, conditionals) allocate real buffers; instructions
    inside a ``%fused_computation...`` body do not — the fusion emits
    only its root. This is the allocation-relevant line set for the copy
    census."""
    import re

    global _HLO_COMP_HEADER
    if _HLO_COMP_HEADER is None:
        _HLO_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?[\w.\-]+\s*\(.*\)\s*->.*\{")
    in_comp = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if _HLO_COMP_HEADER.match(stripped):
            in_comp = stripped.split("(")[0].strip().lstrip("%")
            continue
        if stripped == "}":
            in_comp = None
            continue
        if in_comp is not None and "fused" not in in_comp:
            yield stripped


def _hlo_result_shape(line: str):
    """(dtype_str, elems, bytes) of an instruction's result, or None.

    Tuple-shaped results (async copy pairs) take their first leaf."""
    import re

    m = re.search(r"=\s*\(?([a-z]+\d*)\[([\d,]*)\]", line)
    if not m:
        return None
    dtype, dims = m.group(1), m.group(2)
    if dtype not in _HLO_DTYPE_BYTES:
        return None
    elems = 1
    for d in dims.split(","):
        if d:
            elems *= int(d)
    return dtype, elems, elems * _HLO_DTYPE_BYTES[dtype]


def classify_copy(line: str) -> str:
    """Attribution category for one copy-class HLO instruction.

    - "donation_async": ``copy-start``/``copy-done`` pairs — the async
      copies the runtime schedules around donated/aliased buffers and
      cross-memory DMA. (Heuristic by op kind: plain ``copy`` of a
      donated input exists too but is indistinguishable from a layout
      copy in HLO text.)
    - "gather_pack": copies whose op_name metadata places them inside
      the crop-packed engine's pack/unpack assembly (the
      ``crop_pack``/``crop_unpack`` named scopes in
      models/vision_transformer.py _packed_forward, and their
      transposed backward ops, which inherit the scope) — the
      pad/reshape/concat/slice traffic the packing engine introduces,
      attributed so the census ceiling names it instead of silently
      absorbing it.
    - "update_shard": copies inside the bucketed update engine's
      per-leaf flatten/pad/unflatten walk (the ``update_shard_pack``/
      ``update_shard_unpack`` named scopes in
      train/fused_update.py make_bucketed_update) — the leaf-layout
      traffic the cross-replica sharding introduces, named for the same
      reason.
    - "telemetry": the async metrics ring's in-place row writes (the
      ``telemetry_ring`` named scope in telemetry/ring.py write_row —
      one [1, M] metrics-row and one [1] iteration-stamp
      dynamic-update-slice per step), attributed so the telemetry
      step's census ceiling names its own cost instead of absorbing it
      into "small" (tests/test_telemetry.py pins the ceiling).
    - "zero3": copies inside the ZeRO-3 engine's materialization sites
      (the ``zero3_gather``/``zero3_stream``/``zero3_prefetch`` named
      scopes — ssl_meta_arch._zero3_gather_params, ops/block.py
      _zero3_stream_trans_in, models/streaming.py) — the layout traffic
      weight streaming introduces, named so the census ceiling
      attributes it instead of absorbing it into "small"/"large".
    - "bucket": copies inside the bucketed collective engine's
      concat/slice walk (the ``bucket_pack``/``bucket_unpack`` named
      scopes in train/fused_update.py make_bucketed_update, and the
      ``bucket_gather``/``bucket_prefetch``/``bucket_stream`` scopes of
      the overlap twin in models/streaming.py) — the leaf→bucket
      assembly traffic coalescing introduces, named for the same reason
      as "update_shard".
    - "serve": copies inside the serve engine's plane assembly,
      per-segment extraction, and donated output ring (the
      ``serve_pack``/``serve_extract``/``serve_ring`` named scopes in
      models/vision_transformer.py packed_feature_forward and
      serve/engine.py make_serve_step, plus the ``serve_dequant``
      int8->bf16 weight expansion scope of quantized engines,
      serve/quant.py) — the token/feature-plane traffic continuous
      packing introduces, attributed so the serve step's census
      ceiling names it (scripts/bench_serve.py pins zero
      unattributed).
    - "rng": u32 results of <= 8 elements — threefry key/counter
      plumbing (keys are u32[2]/u32[4]; fold_in intermediates scalar).
    - "small": any other result of <= 1024 elements (scalar metrics,
      index vectors, centers).
    - "large": activation/weight-shaped copies (> 1024 elements) — a
      structural regression when a new class of these appears.
    """
    if "copy-start" in line or "copy-done" in line:
        return "donation_async"
    if "crop_pack" in line or "crop_unpack" in line:
        return "gather_pack"
    if "update_shard_pack" in line or "update_shard_unpack" in line:
        return "update_shard"
    if "telemetry_ring" in line:
        return "telemetry"
    if ("zero3_gather" in line or "zero3_stream" in line
            or "zero3_prefetch" in line):
        return "zero3"
    if ("bucket_pack" in line or "bucket_unpack" in line
            or "bucket_gather" in line or "bucket_prefetch" in line
            or "bucket_stream" in line):
        return "bucket"
    if ("serve_pack" in line or "serve_extract" in line
            or "serve_ring" in line or "serve_dequant" in line):
        return "serve"
    shp = _hlo_result_shape(line)
    if shp is None:
        return "small"
    dtype, elems, _ = shp
    if dtype == "u32" and elems <= 8:
        return "rng"
    return "small" if elems <= 1024 else "large"


def hlo_copy_census(hlo_text: str) -> dict:
    """Copy-class op counts + bytes + per-category attribution for one
    compiled HLO module (non-fusion lines only — the buffer-allocating
    set). Categories: see ``classify_copy``."""
    import re

    counts = {op: 0 for op in HLO_COPY_OPS}
    by_cat: dict = {}
    bytes_total = 0
    for line in hlo_non_fusion_lines(hlo_text):
        for op in HLO_COPY_OPS:
            if re.search(r"=\s*\S+\s+" + re.escape(op) + r"\(", line):
                counts[op] += 1
                break
        else:
            continue
        cat = classify_copy(line)
        shp = _hlo_result_shape(line)
        nbytes = shp[2] if shp else 0
        ent = by_cat.setdefault(cat, {"ops": 0, "bytes": 0})
        ent["ops"] += 1
        ent["bytes"] += nbytes
        bytes_total += nbytes
    return {
        "hlo_copy_ops": counts,
        "hlo_copy_total": sum(counts.values()),
        "hlo_copy_bytes": bytes_total,
        "by_category": by_cat,
    }


# ---------------- compiled-HLO collective census (shared by the
# scripts/cost_*.py censuses and `bench.py --census`) ----------------

# collective op kinds the census attributes; anything else that smells
# like a collective lands in "unattributed" — a structural regression
# when it appears (the engines' census tests pin it at 0)
HLO_COLLECTIVE_CLASSES = {
    "all-reduce": "all_reduce",
    "reduce-scatter": "reduce_scatter",
    "all-gather": "all_gather",
    "collective-permute": "ppermute",
    "all-to-all": "all_to_all",
}

# collective-looking op kinds OUTSIDE the attributed set: their
# appearance classifies as "unattributed" (a stray the ceiling names)
_HLO_COLLECTIVE_UNATTRIBUTED = ("collective-broadcast", "ragged-all-to-all")


def classify_collective(line: str) -> str | None:
    """Attribution class for one HLO instruction line, or None when the
    line is not a collective (or is the ``-done`` half of an async pair,
    which is counted at its ``-start``).

    Classes: "all_reduce" (the replicated engine's grad sync),
    "reduce_scatter" (the sharded engine's grad sync — each replica
    receives the summed 1/dp shard), "all_gather" (updated params back
    to every replica), "ppermute" (ring/pipeline transfers),
    "all_to_all" (resharding), "unattributed" (any other collective —
    a stray the census ceiling must name).

    Matching is by opcode token (the name followed by "(", preceded by
    whitespace or a closing bracket) rather than by result-type parsing,
    so tuple-typed async forms (``all-reduce-start`` et al.) classify on
    every backend's text format. Longest names are tested first so
    ``all-reduce`` can never claim a ``reduce-scatter`` line.
    """
    import re

    if "=" not in line:
        return None
    names = sorted(
        list(HLO_COLLECTIVE_CLASSES) + list(_HLO_COLLECTIVE_UNATTRIBUTED),
        key=len, reverse=True,
    )
    for base in names:
        esc = re.escape(base)
        if re.search(r"[\s)]" + esc + r"-done\(", line):
            return None  # async pair's -done half: counted at -start
        if re.search(r"[\s)]" + esc + r"(-start)?\(", line):
            return HLO_COLLECTIVE_CLASSES.get(base, "unattributed")
    return None


# named-scope markers -> attribution category for collectives: the
# engine scopes (zero3 weight streaming, the bucketed update's flat
# pack, crop packing) wrap their materialization/collective sites, and
# the GSPMD-inserted collectives inherit the scope in their op_name
# metadata — so the census can say WHICH engine asked for each
# collective, not just its opcode class. Order matters: first match
# wins (prefetch before stream — the prefetch scope nests inside the
# stream program).
HLO_COLLECTIVE_SCOPES = (
    # the unified zero3 x bucketed engine's hierarchy-aware staged
    # schedule (parallel/sharding.py hier_gather_bucket): ag_inter =
    # the slow-tier shard gather, ag_intra = the fast-tier broadcast of
    # the assembled segments; rs_intra/rs_inter = the hand-written
    # custom_vjp backward (fast-tier volume reduction first, then the
    # shrunk cotangent over the slow links). Listed FIRST: these scopes
    # never nest under another engine scope, but a first-match table
    # must put the most specific markers before zero3_gather's
    ("bucket_ag_inter", "bucket_ag_inter"),
    ("bucket_ag_intra", "bucket_ag_intra"),
    ("bucket_rs_intra", "bucket_rs_intra"),
    ("bucket_rs_inter", "bucket_rs_inter"),
    ("zero3_prefetch", "zero3_prefetch"),
    ("zero3_stream", "zero3_stream"),
    ("zero3_gather", "zero3_gather"),
    # train.low_precision (ops/lowp.py): lowp_amax = the delayed-scaling
    # history advance + the activations' current-scale amax (under zero3
    # each is a tiny all-reduce-max over a sharded master); lowp_dequant
    # = the dequantize epilogue after each quantized matmul (normally
    # collective-free — listed so any reshard GSPMD hangs there is
    # attributed, not "other"). The quantized WEIGHT gathers themselves
    # ride the zero3_stream scope above on purpose: same collective
    # sites as the bf16 stream, 1-byte payloads.
    ("lowp_amax", "lowp_amax"),
    ("lowp_dequant", "lowp_dequant"),
    # the bucketed collective engine (train/fused_update.py
    # make_bucketed_update + the overlap twin in models/streaming.py):
    # pack = the coalesced grad reduce-scatter site, unpack = the
    # one-all-gather-per-bucket param/teacher re-materialization,
    # prefetch/gather/stream = the double-buffered bucket gather scan
    ("bucket_prefetch", "bucket_prefetch"),
    ("bucket_stream", "bucket_stream"),
    ("bucket_gather", "bucket_gather"),
    ("bucket_pack", "bucket_pack"),
    ("bucket_unpack", "bucket_unpack"),
    ("update_shard", "update_shard"),
    ("crop_pack", "gather_pack"),
    ("crop_unpack", "gather_pack"),
    # ring attention (parallel/ring_attention.py): ring_permute = the
    # rotating K/V(+segment) chunk ppermutes of the forward and of the
    # custom_vjp's second ring pass (where the dk/dv accumulators
    # co-rotate); ring_merge = the island boundary — any reshard GSPMD
    # inserts to feed the seq-sharded islands. ring_permute first: the
    # permute scope nests inside the boundary scope.
    ("ring_permute", "ring_permute"),
    ("ring_merge", "ring_merge"),
    # serve-backed distillation fan-out (serve/engine.py patch-plane
    # ring write; ssl_meta_arch.py get_teacher_output's precomputed
    # arm): the teacher_cls/teacher_patches batch planes enter the step
    # replicated-per-host and GSPMD reshards them onto the batch axes —
    # those copies/collectives belong to the fan-out, not "other"
    ("distill_fanout", "distill_fanout"),
    # the elastic-topology engine (parallel/reshard.py): one scope per
    # train-state leaf-group, wrapping the WHOLE per-group program —
    # the arm-layout conversion (flat <-> model <-> bucketed moment
    # reshapes) and the src->dst sharding constraint — so every
    # collective a live mesh/arm transition inserts is attributed to
    # the group that moved, and the zero-unattributed pin holds across
    # reshard censuses exactly as it does for train steps
    ("reshard_params", "reshard_params"),
    ("reshard_mu", "reshard_mu"),
    ("reshard_nu", "reshard_nu"),
    ("reshard_rest", "reshard_rest"),
    ("telemetry_ring", "telemetry"),
)


# the compute phases of one training step, as ``jax.named_scope`` names
# (train/train_step.py, train/ssl_meta_arch.py, telemetry/ring.py open
# them through ``step_phase``). A scope is metadata: it reaches the
# compiled program only as the ``op_name`` of each instruction, where
# the trace readers find it (telemetry/anatomy.py for the operator's
# ``--profile-steps`` window; benchmark/phase_reduce.py keeps its own
# copy of the names in benchmark/phases.json). The engine scopes of
# ``HLO_COLLECTIVE_SCOPES`` nest INSIDE these and stay what they were.
STEP_PHASES = (
    "teacher_backbone",   # the frozen teacher's backbone forward
    "teacher_targets",    # teacher heads, masked gather, centering, specs
    "student_backbone",   # every student backbone apply; bwd = its transpose
    "student_heads",      # masked gather + iBOT head + DINO head
    "losses",             # compute_losses (dino/ibot/koleo/gram inside)
    "gram_teacher",       # get_gram_teacher_output
    "update",             # clip + AdamW + EMA (fused or optax), lowp rings
    "rng_plan",           # the step-wide RNG plan's draws
    "telemetry_ring",     # the metrics row's write into the ring
    # the next-token step of a decoder (models/decoder.py); the update
    # stays ``update``
    "lm_embed",           # the token embedding's gather
    "kda_mixer",          # a KDA layer's mixer (inner: kda_core)
    "mla_mixer",          # a latent-attention layer's mixer (inner:
                          # mla_rope, mla_core)
    "swa_mixer",          # a grouped-query layer with a window and rotary
    "full_attn_mixer",    # ... with neither (inner of both: gqa_core)
    "gdn_mixer",          # a Gated DeltaNet layer's mixer (inner: gdn_core)
    "gated_attn_mixer",   # a grouped-query layer with q/k norms, a partial
                          # rotary and an output gate (inner: gqa_core)
    "dsa_mixer",          # a grouped-query layer over the keys a learned
                          # indexer selects (inner: dsa_index, dsa_select,
                          # dsa_core, dsa_index_loss)
    "sconv_mixer",        # a gated short convolution layer's mixer (inner:
                          # sconv_chain)
    "ssm_mixer",          # a Mamba-2 block's mixer (inner: ssd_core, the
                          # scan alone; ssm_chain, the rest between the
                          # two matmuls)
    "dense_ffn",          # the dense SwiGLU of the leading layers
    "moe_ffn",            # routed + shared experts (inner: moe_route,
                          # moe_experts, moe_shared)
    "lm_head_loss",       # final norm, head and cross-entropy, by blocks
)
# the phases only a decoder's step opens
LM_STEP_PHASES = ("lm_embed", "kda_mixer", "mla_mixer", "swa_mixer",
                  "full_attn_mixer", "gdn_mixer", "gated_attn_mixer",
                  "dsa_mixer", "sconv_mixer", "ssm_mixer", "dense_ffn", "moe_ffn",
                  "lm_head_loss")

_PHASE_WRAPPER = re.compile(r"^(jvp|transpose|checkpoint|remat)\((.*)\)$")


def step_phase(name: str):
    """Open one phase of ``STEP_PHASES``: ``jax.named_scope`` and nothing
    else (no annotation, no counter — the compiled step is the same
    program with or without it)."""
    if name not in STEP_PHASES:
        raise ValueError(f"{name!r} is not in STEP_PHASES {STEP_PHASES}")
    return jax.named_scope(name)


def classify_step_phase(op_name: str | None) -> tuple[str | None, str]:
    """``(phase, direction)`` of one instruction from the VALUE of its
    ``op_name`` metadata (``jit(step)/transpose(jvp(student_backbone))/
    while/body/...``): split on ``/``, each component stripped of its
    ``jvp(`` / ``transpose(`` / ``checkpoint(`` / ``remat(`` wrappers
    and compared for EQUALITY with a phase name. The outermost phase
    wins (``update/bucket_pack`` is ``update``); a ``transpose(`` in or
    before that component makes the direction "bwd" (recomputation under
    remat included), anything else "fwd". No phase: ``(None, "fwd")``.

    Never feed it an instruction's whole text: operands are called
    ``%state_params__student____ibot...`` and only the op_name says
    where an instruction came from."""
    direction = "fwd"
    for comp in (op_name or "").split("/"):
        while True:
            m = _PHASE_WRAPPER.match(comp)
            if m is None:
                break
            if m.group(1) == "transpose":
                direction = "bwd"
            comp = m.group(2)
        if comp in STEP_PHASES:
            return comp, direction
    return None, "fwd"


def classify_collective_scope(line: str) -> str:
    """Named-scope attribution category for one collective HLO line
    (``HLO_COLLECTIVE_SCOPES``), or "other" when no engine scope claims
    it (model-structure collectives: grad all-reduces, loss psums)."""
    for marker, cat in HLO_COLLECTIVE_SCOPES:
        if marker in line:
            return cat
    return "other"


def collective_size_bin(nbytes: int) -> tuple[int, str]:
    """Power-of-two message-size bin for one collective result.

    Returns ``(floor_bytes, label)``: the largest power of two
    <= ``nbytes`` and a human-readable half-open interval label
    ("[64MiB,128MiB)"; zero-byte results bin as ``(0, "0B")``). The
    census histograms collective traffic by these bins — the
    small-message latency-bound regime (hundreds of per-leaf
    collectives under 1 MiB) and the coalesced bucket regime (a few
    >= 64 MiB messages) then read directly off the bin keys.
    """
    n = int(nbytes)
    if n <= 0:
        return 0, "0B"
    floor = 1 << (n.bit_length() - 1)

    def fmt(v: int) -> str:
        for shift, unit in ((30, "GiB"), (20, "MiB"), (10, "KiB")):
            if v >= (1 << shift):
                scaled = v / (1 << shift)
                return (f"{int(scaled)}{unit}" if scaled == int(scaled)
                        else f"{scaled:g}{unit}")
        return f"{v}B"

    return floor, f"[{fmt(floor)},{fmt(floor * 2)})"


def hlo_collective_placement(line: str) -> str:
    """Issue-site placement of one collective HLO instruction, from its
    op_name metadata (the while-loop signal ``hlo_collective_in_loop``
    reads, split by pass direction):

    - "in-backward-loop": inside a compiled loop body AND on the
      transposed (backward) path — jax stamps backward-pass ops with a
      ``transpose(...)`` component in their op_name, which survives
      partitioning. A reduce-scatter here is a grad sync issued as the
      backward loop produces each bucket/block — overlappable with the
      remaining backward compute.
    - "in-forward-loop": inside a loop body on the forward path (the
      per-block / per-bucket weight-stream gathers).
    - "at-barrier": outside any loop — a whole-tree materialization or
      an update-phase collective issued after both passes complete
      (nothing left to overlap it with).
    """
    import re

    m = re.search(r'op_name="([^"]*)"', line)
    op = m.group(1) if m else ""
    if "while" in op:
        return "in-backward-loop" if "transpose" in op else "in-forward-loop"
    return "at-barrier"


def hlo_collective_in_loop(line: str) -> bool:
    """Whether a collective instruction executes inside a compiled loop
    body (the block scan / K-tile scan): jax stamps loop-body ops with a
    ``while`` component in their op_name metadata (``.../while/body/...``,
    ``jvp(while)``, ``transpose(jvp(while))``), which survives into the
    partitioned HLO — the placement signal behind the weight-stream and
    prefetch-overlap columns (an all-gather inside the block loop is a
    per-block stream gather; outside, a whole-tree materialization)."""
    import re

    m = re.search(r'op_name="([^"]*)"', line)
    return bool(m and "while" in m.group(1))


def hlo_collective_census(hlo_text: str) -> dict:
    """Collective op counts + result bytes per class for one compiled
    HLO module (non-fusion lines; ``-start``/plain forms counted once,
    ``-done`` halves skipped).

    Result bytes are the PER-DEVICE output of each collective — for an
    all-reduce that is the full buffer, for a reduce-scatter the 1/dp
    shard, for an all-gather the re-assembled full buffer — so the
    by-class byte totals read directly as the per-device collective
    traffic story of the module. Classes: see ``classify_collective``.

    Beyond ``by_class``, the census attributes every collective to the
    engine named scope that asked for it (``by_scope``,
    ``classify_collective_scope``) and records the weight-stream /
    prefetch-overlap story of the all-gathers (``prefetch_overlap``):
    how many gathers run inside loop bodies (the per-block stream),
    how many of those were issued AHEAD of their consuming block (the
    ``zero3_prefetch`` scope — the double-buffered schedule), and how
    many are issued at use (``zero3_stream``; overlap is then the async
    scheduler's job). The zero3 acceptance pins read these columns.

    Two further columns (the bucketed-collective acceptance pins,
    COST_BUCKET_r13.json):

    - ``size_histogram`` (top-level, and a per-class copy inside each
      ``by_class`` entry): count + bytes per power-of-two message-size
      bin (``collective_size_bin``). The per-leaf schedules show
      hundreds of sub-MiB entries; the bucketed engine a handful of
      >= 64 MiB ones — each bin entry carries its ``floor_bytes`` so
      pins read thresholds without parsing labels.
    - ``by_placement`` (top-level + per class): ops/bytes per issue
      site (``hlo_collective_placement``) — in-backward-loop /
      in-forward-loop / at-barrier. The overlap-scheduled engine's grad
      reduce-scatters attribute to the backward loop body; the per-leaf
      update-phase schedule is all at-barrier.
    """
    by_class: dict = {}
    by_scope: dict = {}
    by_placement: dict = {}
    size_histogram: dict = {}
    ag_in_loop_ops = ag_in_loop_bytes = 0
    ag_prefetch = ag_at_use = 0
    total_ops = 0
    total_bytes = 0

    def _bump_hist(hist: dict, nbytes: int) -> None:
        floor, label = collective_size_bin(nbytes)
        h = hist.setdefault(
            label, {"floor_bytes": floor, "ops": 0, "bytes": 0})
        h["ops"] += 1
        h["bytes"] += nbytes

    for line in hlo_non_fusion_lines(hlo_text):
        cat = classify_collective(line)
        if cat is None:
            continue
        shp = _hlo_result_shape(line)
        nbytes = shp[2] if shp else 0
        ent = by_class.setdefault(
            cat, {"ops": 0, "bytes": 0,
                  "size_histogram": {}, "by_placement": {}})
        ent["ops"] += 1
        ent["bytes"] += nbytes
        _bump_hist(ent["size_histogram"], nbytes)
        _bump_hist(size_histogram, nbytes)
        placement = hlo_collective_placement(line)
        for tbl in (ent["by_placement"], by_placement):
            p_ent = tbl.setdefault(placement, {"ops": 0, "bytes": 0})
            p_ent["ops"] += 1
            p_ent["bytes"] += nbytes
        scope = classify_collective_scope(line)
        s_ent = by_scope.setdefault(scope, {"ops": 0, "bytes": 0})
        s_ent["ops"] += 1
        s_ent["bytes"] += nbytes
        if cat == "all_gather":
            if hlo_collective_in_loop(line):
                ag_in_loop_ops += 1
                ag_in_loop_bytes += nbytes
            if scope == "zero3_prefetch":
                ag_prefetch += 1
            elif scope == "zero3_stream":
                ag_at_use += 1
        total_ops += 1
        total_bytes += nbytes
    return {
        "hlo_collective_total": total_ops,
        "hlo_collective_bytes": total_bytes,
        "by_class": by_class,
        "by_scope": by_scope,
        "by_placement": by_placement,
        "size_histogram": size_histogram,
        "prefetch_overlap": {
            "all_gather_in_loop_ops": ag_in_loop_ops,
            "all_gather_in_loop_bytes": ag_in_loop_bytes,
            "prefetch_scoped_ops": ag_prefetch,
            "at_use_scoped_ops": ag_at_use,
        },
        "unattributed": by_class.get("unattributed", {"ops": 0})["ops"],
    }
