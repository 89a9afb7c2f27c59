"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip: trainer, the step under
                                    # accumulation, kernels, serve, the
                                    # decoder's next-token trainer, the
                                    # decoders' causal attention cores
                                    # (the kernels beside the plain tiles),
                                    # the third decoder's two cores, the
                                    # fourth's sparse attention, the
                                    # fifth's gated short convolution and
                                    # 64-wide causal core, the routed
                                    # layers' experts' block, the sixth's
                                    # rotary turn and latent core at 16k,
                                    # the seventh's state-space scan, its
                                    # 32 | 2 causal core and un-gated
                                    # experts
    python chip_smoke.py --phases lm   # one chip, that phase alone
    python chip_smoke.py --chips 4  # four chips: ONLY the sharded train
                                    # arms and their one-device comparison

One process. It imports the package and calls the normal entry points
in-process (``dinov3_tpu.train.train.main``, the Pallas kernels,
``PackedServeEngine``); it starts no child process, needs no network,
and writes only under ``chiprun_out/``, its own run directory
``.chip_smoke_run/`` and the compile cache
(``utils.configure_compile_cache``). It FAILS — non-zero exit, no final
``ok`` line — the moment ``jax.devices()[0].platform != "tpu"``, and the
first failing phase ends the run: no phase is wrapped in an ``except``.

Everything it prints before the last line is information (per-step wall
times, compile seconds, peak memory, cache hits), not a benchmark. The
last line of stdout, and nothing after it, is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# what comes back from the chip machine: the summary and the trainer's
# small files. The run directory itself (a ViT-L checkpoint is 5.6 GB)
# stays in the checkout, in a directory .gitignore lists.
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
RUN_DIR = os.path.join(REPO, ".chip_smoke_run")
RECIPE = os.path.join(REPO, "configs", "train", "vitl16_im1k.yaml")
LM_RECIPE = os.path.join(REPO, "configs", "train", "kimi_linear_ep32.yaml")

# What the smoke runs, at the size a user would call real: ViT-L/16 at
# full width and depth on the recipe's 2 global + 8 local crops and its
# 65,536-prototype heads. tests/test_chip_smoke.py rehearses the same
# control flow on the CPU by replacing this table (vit_test width,
# kernels interpreted) — a switch of the TEST, not an option of the
# program: there is no flag or variable that shrinks a real run.
SIZES = {
    # per-chip batch 12: the whole step compiled for a described v5e at
    # B=12 needs 12.3 GiB of the chip's 16 GB (compile-time analysis)
    "train_overrides": ["data.backend=synthetic",
                        "train.batch_size_per_device=12"],
    "train_iters": 6,
    # the decoder's recipe as it stands: published widths, 2 x 8,192
    # tokens a step, 13.9 GiB by compile-time analysis; cold it compiles
    # in about two minutes and a step takes about a second
    "lm_overrides": ["data.backend=synthetic"],
    "lm_iters": 3,
    "lm_timeout_s": 900,
    # four-chip phase: global batch 8 on every arm (2 per chip); the
    # one-chip accumulation phase runs the same batch as 2 microbatches
    "mesh_global_batch": 8,
    "mesh_iters": 3,
    # 768 px ViT-L token count (2304 patches + cls + 4 registers)
    "flash_shape": (2, 2309, 16, 64),
    # [B*201, 1024]: the 224 px ViT-L token rows of a B=12 global pass
    "ln_shape": (12 * 201, 1024),
    # the delta rule at published width: [B, T, heads, d_k = d_v]
    "kda_shape": (1, 1024, 4, 128),
    "kernel_interpret": False,
    # the causal cores at published sizes: [B, T, query heads, key/value
    # heads, q/k width, value width, window]: the 16k decoder's window and
    # global layers, the 8k decoder's latent attention; the (block_q,
    # block_kv) pairs timed on the kernel path beside the shipped 512 x
    # 1,024 (information: why it stays)
    "gqa_shapes": {"window": (1, 16384, 28, 4, 128, 128, 4096),
                   "global": (1, 16384, 28, 4, 128, 128, None)},
    # the third decoder's two cores at published sizes (--phases gdn): the
    # delta rule with ONE decay a head, [B, T, key heads, value heads,
    # d_k = d_v],
    # and the gated attention's causal core (16 query heads on 2 of 256)
    "gdn_shape": (2, 8192, 16, 32, 128),
    "gdn_attn_shapes": {"gated": (2, 8192, 16, 2, 256, 256, None)},
    # the fourth decoder's sparse attention at published sizes (--phases
    # dsa): [B, T, query heads, key/value heads, head width, index heads,
    # index width, keys a query keeps]
    "dsa_shape": (1, 16384, 32, 4, 128, 16, 64, 2048),
    # the fifth decoder's two kernels at published sizes (--phases sconv):
    # the gated short convolution's chain, [B, T, channels], and the causal
    # core at 32 query heads on 8 key/value heads of 64
    "sconv_shape": (4, 8192, 2048),
    "sconv_attn_shapes": {"heads64": (4, 8192, 32, 8, 64, 64, None)},
    # latent attention at published sizes (--phases mla): the plain arm's
    # turn of q [B, T, heads, 128 + 64] beside ONE shared key head of 64;
    # the latent kernel pair, [B, T, heads, rope_theta, the plain tiles fit
    # the chip beside it], at the 8k decoder's two rows (no turn) and the
    # sixth decoder's ONE row of 16,384 tokens (32 heads of 128 | 64 | 128;
    # the tiles would take 18.2 GB there); and the generic pair at the
    # same row with q and k a whole 256 wide: what a head's key written
    # out and q and k padded would cost the KERNELS (a kernel's loss told
    # from a copy's)
    "mla_shape": (1, 16384, 32, 192, 64),
    "mla_latent_shapes": {"mla8k": (2, 8192, 32, None, True),
                          "mla16k": (1, 16384, 32, 1e6, False)},
    "mla_attn_shapes": {"wide16k": (1, 16384, 32, 32, 256, 128, None)},
    # the routed layers' row movement and held experts' block at the six
    # decoder cells' published shapes (--phases moe; moe_rows: the row
    # movement alone): [tokens, choices a token, buffer rows, held experts,
    # D, H, gate, the shares of the buffer routed rows fill] — the cells'
    # measured fills, and a quarter and the whole of the 16k cell's buffer
    "moe_shapes": {
        "smallthinker": (16384, 6, 49152, 16, 2560, 768, "relu",
                         (0.25, 0.55, 1.0)),
        "lfm2": (32768, 4, 32768, 8, 2048, 1536, "silu", (0.8,)),
        "qwen3_next": (16384, 10, 40960, 32, 2048, 512, "silu", (0.26,)),
        "keye_vl2": (16384, 8, 32768, 16, 2048, 768, "silu", (0.5,)),
        "kimi_linear": (16384, 8, 8192, 8, 2304, 1024, "silu", (0.5,)),
        "kanana2": (16384, 6, 49152, 16, 2048, 768, "silu", (0.44,))},
    # the seventh decoder's three kernels at published sizes (--phases
    # ssd): the state-space scan, [B, T, heads, head width, groups, state],
    # the chunks its kernel pair is timed at beside the shipped one, the
    # blocks the two chains around it are timed at, the
    # causal core at SIXTEEN query heads a key/value head, and the un-gated
    # experts' block (a row as "moe_shapes" has them: W1 [2688, 1856], 14.5
    # lane tiles)
    "ssd_shape": (2, 8192, 64, 64, 8, 128),
    "ssd_chunks": (256,),
    # (time block, lane block) pairs Mamba2Mixer's two chains are timed at
    # beside the shipped ones
    "ssd_chain_blocks": ((128, 2048), (512, 2048), (256, 1024), (256, 512)),
    "ssd_attn_shapes": {"gqa16": (2, 8192, 32, 2, 128, 128, None)},
    "ssd_moe_shapes": {
        "nemotron3": (16384, 6, 36864, 8, 2688, 1856, "relu2", (0.17, 0.5))},
    "gqa_shipped_blocks": (512, 1024),
    "gqa_blocks": [(512, 512), (1024, 1024), (256, 1024)],
    "gqa_timeout_s": 1200,
    "serve_overrides": ["student.arch=vit_large", "student.patch_size=16",
                        "train.scan_layers=true"],
    # mixed resolutions inside the default 96..512 px envelope
    "serve_images_hw": [(96, 96), (224, 224), (512, 512), (160, 240),
                        (384, 384), (224, 224), (128, 128), (448, 320)],
}

ONE_CHIP_PHASES = ("trainer", "accum", "kernels", "serve", "lm", "gqa", "gdn",
                   "dsa", "sconv", "moe", "mla", "ssd")
# asked for by name only: a part of a phase above, alone
PART_PHASES = ("moe_rows",)

_T0 = time.time()


def log(msg: str) -> None:
    """One information line: stdout, and appended to
    ``chiprun_out/chip_smoke/summary.log`` (the trainer's own logging
    shares stdout, so the summary file is the short record)."""
    line = f"[chip_smoke +{time.time() - _T0:7.1f}s] {msg}"
    print(line, flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.log"), "a") as f:
        f.write(line + "\n")


def require_tpu() -> dict:
    """The device as JAX reports it; anything but a TPU ends the run."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (jax.devices()[0].platform == "
            f"{dev.platform!r}); this script proves the chip path and "
            "does not run without the chip")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def cache_counts() -> dict:
    """The persistent compilation cache's hits and misses so far, as the
    process's span log counts them (``configure_compile_cache`` registers
    its listeners; information for the log lines only)."""
    from dinov3_tpu.telemetry import spans

    c = spans.LOG.counters
    return {"hits": c["cache_hits"], "misses": c["cache_misses"]}


def peak_bytes() -> list:
    from dinov3_tpu.telemetry.memory import sample_memory

    return [d["peak_bytes_in_use"] for d in sample_memory()["devices"]]


# ------------------------------------------------- the next-token trainer

def phase_lm() -> None:
    """The same entry point on the decoder's recipe at published widths
    (``configs/train/kimi_linear_ep32.yaml``): the next-token step
    compiles, runs a few steps with a finite loss near ln(vocabulary),
    saves its teacher-less state and resumes it for one more step.

    A new step program can hang the chip where every rehearsal passed
    (PERF.md section 6, PR 26), so this phase has a time limit of its
    own: past it the process dumps its stacks and exits non-zero, and
    the chip call ends there instead of at the call's limit."""
    import faulthandler
    import shutil

    from dinov3_tpu.configs import load_config
    from dinov3_tpu.train.train import main as train_main

    run_dir = os.path.join(RUN_DIR, "lm")
    shutil.rmtree(run_dir, ignore_errors=True)
    common = ["--config-file", LM_RECIPE, "--output-dir", run_dir,
              *SIZES["lm_overrides"]]
    n = int(SIZES["lm_iters"])
    cfg = load_config(LM_RECIPE, overrides=SIZES["lm_overrides"])
    want = math.log(int(cfg.lm.vocab_size))
    faulthandler.dump_traceback_later(
        float(SIZES["lm_timeout_s"]), exit=True, file=sys.__stderr__)
    log(f"lm: {n} iterations from scratch, "
        f"{cfg.train.batch_size_per_device} x {cfg.lm.seq_len} tokens a "
        f"chip, time limit {SIZES['lm_timeout_s']}s")
    t0 = time.perf_counter()
    result = train_main(["--no-resume", "--max-iterations", str(n),
                         "--benchmark", str(n - 1), *common])
    losses = result["losses"]
    assert result["iterations"] == n and len(losses) == n, result
    assert all(math.isfinite(x) for x in losses), losses
    assert all(abs(x - want) < 0.5 for x in losses), (losses, want)
    log(f"lm: losses {[round(x, 4) for x in losses]} (ln vocabulary "
        f"{want:.3f}); fenced step times (ms) "
        f"{[round(x, 1) for x in result['step_ms']]}; whole call "
        f"{time.perf_counter() - t0:.1f}s; peak_bytes_in_use "
        f"{peak_bytes()}, cache {cache_counts()}")
    t0 = time.perf_counter()
    resumed = train_main(["--max-iterations", str(n + 1), *common])
    assert resumed["iterations"] == n + 1, resumed["iterations"]
    assert len(resumed["losses"]) == 1, resumed["losses"]
    assert abs(resumed["final_loss"] - want) < 0.5, resumed["final_loss"]
    log(f"lm: resumed at {n}, step {n + 1} loss "
        f"{resumed['final_loss']:.4f}, {time.perf_counter() - t0:.1f}s")
    faulthandler.cancel_dump_traceback_later()
    shutil.rmtree(os.path.join(run_dir, "ckpt"))
    shutil.copytree(run_dir, os.path.join(OUT_DIR, "lm"), dirs_exist_ok=True)


# ---------------------------------------------------------------- trainer

def phase_trainer() -> None:
    """The pretrain entry point on the ViT-L/16 recipe: self-check,
    a few training steps, a checkpoint save and a one-step resume."""
    import shutil

    from dinov3_tpu.train.train import main as train_main

    run_dir = os.path.join(RUN_DIR, "train")
    shutil.rmtree(RUN_DIR, ignore_errors=True)  # this script's own dir
    common = ["--config-file", RECIPE, "--output-dir", run_dir,
              *SIZES["train_overrides"]]
    n = int(SIZES["train_iters"])

    log("trainer: --self-check (two diagnostic steps on one batch)")
    t0 = time.perf_counter()
    checks = train_main(["--self-check", "--no-resume", *common])
    failed = sorted(k for k, v in checks.items()
                    if k.startswith("check/") and not v)
    log(f"trainer: self-check {len(checks) - 1} probes, "
        f"{checks['self_check_failures']} failures, "
        f"{time.perf_counter() - t0:.1f}s, cache {cache_counts()}")
    assert checks["self_check_failures"] == 0, failed

    log(f"trainer: {n} iterations from scratch (--no-resume)")
    t0 = time.perf_counter()
    result = train_main(["--no-resume", "--max-iterations", str(n),
                         "--benchmark", str(n - 1), *common])
    wall = time.perf_counter() - t0
    losses = result["losses"]
    assert result["iterations"] == n, result["iterations"]
    assert len(losses) == n, losses
    assert all(math.isfinite(x) for x in losses), losses
    assert losses[-1] != losses[0], losses
    spans = _read_spans(run_dir)
    names = {s["name"] for s in spans}
    # the default async-telemetry step ran: metrics left the device by
    # ring flushes, never by the per-step fetch of the oracle loop
    assert "metrics_flush" in names and "metrics_fetch" not in names, names
    first_dispatch = next(s["dur_ms"] for s in spans
                          if s["name"] == "dispatch" and s["iteration"] == 0)
    log(f"trainer: losses {[round(x, 4) for x in losses]}")
    log(f"trainer: first dispatch (trace + compile + step 0) "
        f"{first_dispatch / 1e3:.1f}s; fenced step times (ms) "
        f"{[round(x, 1) for x in result['step_ms']]}; "
        f"{result['img_per_sec']:.2f} img/s over the fenced steps; "
        f"whole call {wall:.1f}s")
    log(f"trainer: peak_bytes_in_use per device {peak_bytes()}, "
        f"cache {cache_counts()}")

    log("trainer: resume from the saved checkpoint for one more step")
    t0 = time.perf_counter()
    resumed = train_main(["--max-iterations", str(n + 1), *common])
    assert resumed["iterations"] == n + 1, resumed["iterations"]
    assert len(resumed["losses"]) == 1, resumed["losses"]
    assert math.isfinite(resumed["final_loss"]), resumed["final_loss"]
    log(f"trainer: resumed at {n}, step {n + 1} loss "
        f"{resumed['final_loss']:.4f}, {time.perf_counter() - t0:.1f}s, "
        f"cache {cache_counts()}")
    # bring the small files back; drop the checkpoints
    shutil.rmtree(os.path.join(run_dir, "ckpt"))
    shutil.copytree(run_dir, os.path.join(OUT_DIR, "train"),
                    dirs_exist_ok=True)


def _read_spans(run_dir: str) -> list:
    with open(os.path.join(run_dir, "telemetry", "spans.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------- kernels

def _compiled_has_kernel(fn, *args) -> None:
    if SIZES["kernel_interpret"]:
        return  # CPU rehearsal of the test: no Mosaic call to look for
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"


def _max_err(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def phase_kernels() -> None:
    """Flash attention (plain and segment-masked) and the fused
    layernorm, forward and backward, compiled (``interpret=False``) and
    compared with the repo's own XLA paths. The 224 px step never
    reaches them (N=201 is under ``kernels.flash_min_seq``; the LN
    kernel is opt-in), so this phase is what executes them on the chip."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.ops.attention import xla_attention
    from dinov3_tpu.ops.flash_attention import flash_attention
    from dinov3_tpu.ops.fused_norm import fused_layernorm

    interpret = bool(SIZES["kernel_interpret"])
    B, N, H, D = SIZES["flash_shape"]
    kq, kk, kv, kd, kx, ks = jax.random.split(jax.random.key(0), 6)
    q, k, v, do = (jax.random.normal(key, (B, N, H, D), jnp.bfloat16)
                   for key in (kq, kk, kv, kd))
    # three packed segments per row, the crop-packing mask class
    seg = jnp.broadcast_to((jnp.arange(N) * 3 // N).astype(jnp.int32), (B, N))

    for name, seg_arg in (("flash", None), ("flash_seg", seg)):
        def kern(q, k, v, s=seg_arg):
            return flash_attention(q, k, v, interpret=interpret, seg=s)

        def ref(q, k, v, s=seg_arg):
            return xla_attention(q, k, v, jnp.float32, seg=s)

        def vjp_of(f):
            return jax.jit(lambda q, k, v, do: jax.vjp(f, q, k, v)[1](do))

        fwd_k, fwd_r = jax.jit(kern), jax.jit(ref)
        _compiled_has_kernel(fwd_k, q, k, v)
        _compiled_has_kernel(vjp_of(kern), q, k, v, do)
        t0 = time.perf_counter()
        o_k = jax.block_until_ready(fwd_k(q, k, v))
        g_k = jax.block_until_ready(vjp_of(kern)(q, k, v, do))
        dt = time.perf_counter() - t0
        o_r, g_r = fwd_r(q, k, v), vjp_of(ref)(q, k, v, do)
        # bf16 inputs and outputs, fp32 softmax statistics in both
        # arms: outputs are O(1), so one bf16 ulp (2^-8) of headroom
        # on the forward and a few on the gradient sums
        e_fwd = _max_err(o_k, o_r)
        e_bwd = max(_max_err(a, b) for a, b in zip(g_k, g_r))
        scale = max(float(jnp.max(jnp.abs(x.astype(jnp.float32))))
                    for x in g_r)
        log(f"kernels: {name} {tuple(q.shape)} fwd max|err| {e_fwd:.4f}, "
            f"bwd max|err| {e_bwd:.4f} (grad scale {scale:.2f}), "
            f"first call fwd+bwd {dt:.2f}s")
        assert math.isfinite(e_fwd) and e_fwd <= 2e-2, e_fwd
        assert math.isfinite(e_bwd) and e_bwd <= 2e-2 * max(scale, 1.0), e_bwd

    R, W = SIZES["ln_shape"]
    x = jax.random.normal(kx, (R, W), jnp.bfloat16)
    scale_p = 1.0 + 0.1 * jax.random.normal(ks, (W,), jnp.float32)
    bias_p = 0.1 * jax.random.normal(kd, (W,), jnp.float32)
    dy = jax.random.normal(kq, (R, W), jnp.bfloat16)
    ln_ref = nn.LayerNorm(epsilon=1e-6, dtype=jnp.bfloat16,
                          param_dtype=jnp.float32)

    def ln_kern(x, s, b):
        return fused_layernorm(x, s, b, eps=1e-6, interpret=interpret,
                               force=True)

    def ln_xla(x, s, b):
        return ln_ref.apply({"params": {"scale": s, "bias": b}}, x)

    def ln_vjp(f):
        return jax.jit(lambda x, s, b, dy: jax.vjp(f, x, s, b)[1](dy))

    _compiled_has_kernel(jax.jit(ln_kern), x, scale_p, bias_p)
    _compiled_has_kernel(ln_vjp(ln_kern), x, scale_p, bias_p, dy)
    y_k = jax.block_until_ready(jax.jit(ln_kern)(x, scale_p, bias_p))
    y_r = jax.jit(ln_xla)(x, scale_p, bias_p)
    g_k = jax.block_until_ready(ln_vjp(ln_kern)(x, scale_p, bias_p, dy))
    g_r = ln_vjp(ln_xla)(x, scale_p, bias_p, dy)
    e_fwd = _max_err(y_k, y_r)
    e_dx = _max_err(g_k[0], g_r[0])
    # dscale/dbias sum R rows: compare relative to their magnitude
    e_dp = max(_max_err(a, b) / max(1.0, float(jnp.max(jnp.abs(b))))
               for a, b in zip(g_k[1:], g_r[1:]))
    log(f"kernels: fused_layernorm {(R, W)} fwd max|err| {e_fwd:.4f}, "
        f"dx max|err| {e_dx:.4f}, dscale/dbias rel err {e_dp:.4f}")
    assert e_fwd <= 4e-2 and e_dx <= 4e-2 and e_dp <= 2e-2, (e_fwd, e_dx, e_dp)
    _kda_kernel_row(interpret)


def _kda_kernel_row(interpret: bool) -> None:
    """``ops/kda.py``'s two kernels, ``kda_chunk_fwd`` and
    ``kda_chunk_bwd``, compiled, against the token recurrence and ITS
    gradients. Float32 inputs, so that the gap is the products' own: at
    the decays the published initial values reach (1.6 nats a token) the
    largest gap of the output and of the five gradients, relative to
    each one's largest entry, under 1e-4 (a float32 product lowered as
    ONE bfloat16 pass reads 1e-2; interpret mode cannot see that, the
    chip can); at 30 nats a token on half the channels and at single
    tokens of 200, finite and under 1e-3. Then the activation type of the
    step, bfloat16 q, k, v: their gradients come back bfloat16, packed in
    the kernel a pair of heads a word, within a rounding (2^-8) of the
    recurrence's."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.ops.kda import (
        BACKWARD_KERNEL_NAME,
        KERNEL_NAME,
        kda_chunked,
        kda_recurrent,
    )

    b, t, h, d = SIZES["kda_shape"]
    ks = jax.random.split(jax.random.key(1), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q, k = (unit(jax.random.normal(key, (b, t, h, d))) for key in ks[:2])
    v = jax.random.normal(ks[2], (b, t, h, d))
    published = -1.6 * jax.random.uniform(ks[3], (b, t, h, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    decays = {
        "published": (published, 1e-4),
        "30 nats": (jnp.where(jnp.arange(d) < d // 2, -30.0, published), 1e-3),
        "spikes of 200": (jnp.where(
            (jnp.arange(t) % 7 == 3)[None, :, None, None], -200.0,
            0.1 * published), 1e-3),
    }

    def kern(*x):
        return kda_chunked(*x, q_scale=d ** -0.5,
                           interpret=True if interpret else None)

    def ref(*x):
        return d ** -0.5 * kda_recurrent(*x)

    def out_and_grads(f):
        return jax.jit(lambda *x: (f(*x), *jax.grad(
            lambda *y: jnp.sum(jnp.sin(f(*y))), argnums=(0, 1, 2, 3, 4))(*x)))

    def gaps(x):
        got, want = (out_and_grads(f)(*x) for f in (kern, ref))
        assert [a.dtype for a in got[1:]] == [a.dtype for a in x]
        return [_max_err(a, w) / float(jnp.max(jnp.abs(w.astype(jnp.float32))))
                for a, w in zip(got, want)]

    if not interpret:  # both Mosaic calls are in the gradient's program
        text = out_and_grads(kern).lower(
            q, k, v, published, beta).compile().as_text()
        assert text.count("tpu_custom_call") >= 2 and all(
            name in text for name in (KERNEL_NAME, BACKWARD_KERNEL_NAME)), \
            "the gradient's program lacks a kernel"
    _compiled_has_kernel(jax.jit(kern), q, k, v, published, beta)
    for name, (g, limit) in decays.items():
        found = gaps((q, k, v, g, beta))
        log(f"kernels: kda_chunk_fwd + kda_chunk_bwd {(b, t, h, d)} decay "
            f"{name}: largest relative gap to the recurrence, output "
            f"{found[0]:.2e}, gradients q k v g beta "
            f"{' '.join(f'{x:.2e}' for x in found[1:])}")
        assert all(math.isfinite(x) and x <= limit for x in found), (name, found)
    found = gaps((*(x.astype(jnp.bfloat16) for x in (q, k, v)), published,
                  beta))
    log(f"kernels: kda_chunk_fwd + kda_chunk_bwd bfloat16 q k v: output "
        f"{found[0]:.2e}, gradients q k v (bfloat16) g beta "
        f"{' '.join(f'{x:.2e}' for x in found[1:])}")
    assert all(math.isfinite(x) for x in found) and max(
        found[0], *found[4:]) <= 1e-4 and max(found[1:4]) <= 2 ** -8, found


def _timed(fn, x, n=3):
    """(seconds of the first call, ms a call over ``n`` more, the last
    result), each fenced."""
    import jax

    t0 = time.time()
    jax.block_until_ready(fn(*x))
    first = time.time() - t0
    t0 = time.time()
    for _ in range(n):
        out = fn(*x)
    jax.block_until_ready(out)
    return first, (time.time() - t0) / n * 1e3, out


# ------------------------------------------- the banded grouped-query core

def _dense_causal(t, h, hk, d, w, rows=256):
    """The dense masked softmax of [B, T, h, d] q on hk key/value heads in
    float32: whole rows of keys, k and v repeated for every query head, a
    block of ``rows`` queries at a time so that it fits."""
    import jax
    import jax.numpy as jnp

    def fn(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        k, v = (jnp.repeat(x, h // hk, axis=2) for x in (k, v))
        pad = (-t) % rows
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

        @jax.checkpoint
        def block(args):
            qb, first = args
            z = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
            at = jnp.minimum(first + jnp.arange(rows), t - 1)[:, None]
            key = jnp.arange(t)[None, :]
            seen = key <= at if w is None else (key <= at) & (key > at - w)
            p = jax.nn.softmax(jnp.where(seen, z, -jnp.inf), -1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        blocks = jnp.moveaxis(qp.reshape(q.shape[0], -1, rows, h, d), 1, 0)
        o = jax.lax.map(block, (blocks, jnp.arange(blocks.shape[0]) * rows))
        return jnp.moveaxis(o, 0, 1).reshape(
            q.shape[0], t + pad, h, -1)[:, :t]
    return fn


def _out_and_grads(f):
    """The output and a gradient that weighs every element, on all three
    operands."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda *x: (f(*x), *jax.grad(
        lambda *y: jnp.sum(jnp.sin(f(*y).astype(jnp.float32))),
        argnums=(0, 1, 2))(*x)))


def _gaps(got, want):
    """The difference's norm over the reference's, a result each."""
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    return [float(jnp.linalg.norm(f32(a) - f32(r)) / jnp.linalg.norm(f32(r)))
            for a, r in zip(got, want)]


def _said(xs) -> str:
    return " ".join(f"{x:.3e}" for x in xs)


def phase_gqa(shapes: str = "gqa_shapes", with_tiles: bool = True) -> None:
    """``ops/attention.py causal_blockwise_attention`` as the decoders'
    layers call it, at the published head sizes and whole contexts: the
    window and the global grouped-query core of the ``smallthinker``
    family and the latent attention core of ``kimi_linear`` (q and k
    wider than v, no groups, no window). Each row: the path the entry
    point takes there (``causal_attention_path``'s words), the KERNEL
    path's output and three gradients against the dense masked softmax in
    float32 (whole rows of keys, k and v repeated for every query head, a
    block of queries at a time so that it fits) and against the plain
    tiles; forward alone and forward + backward timed on both paths; then
    the kernel path at other block sizes. ``with_tiles`` False leaves the
    plain tiles out, where they do not fit the chip (32 ungrouped heads of
    192 | 128 at 16,384 tokens: 18.2 GB, my chip run, PR 45).

    A new kernel can hang the chip where every rehearsal passed (PERF.md
    section 6, PR 26): the phase has a time limit of its own."""
    import faulthandler

    import jax
    import jax.numpy as jnp

    from dinov3_tpu.ops import causal_attention as kernels
    from dinov3_tpu.ops.attention import causal_tiles

    interpret = bool(SIZES["kernel_interpret"])
    faulthandler.dump_traceback_later(
        float(SIZES["gqa_timeout_s"]), exit=True, file=sys.__stderr__)

    bq, bkv = SIZES["gqa_shipped_blocks"]
    for name, (b, t, h, hk, d, dv, w) in SIZES[shapes].items():
        ks = jax.random.split(jax.random.key(2), 3)
        x = tuple(jax.random.normal(key, shape, jnp.bfloat16)
                  for key, shape in zip(ks, (
                      (b, t, h, d), (b, t, hk, d), (b, t, hk, dv))))
        path, why = kernels.causal_attention_path(
            tuple(a.shape for a in x), w, interpret or None, bq, bkv)
        log(f"gqa: {name} core {(b, t, h, hk, d, dv)} window {w}: the entry "
            f"point takes the {path} ({why})")
        assert path == "kernel", (name, path, why)

        def kernel(*a, bq=bq, bkv=bkv, w=w, d=d):
            return kernels.kernel_attention(
                *a, d ** -0.5, w, bq, bkv, interpret)

        def tiles(*a, w=w):
            return causal_tiles(*a, bq, bkv, jnp.float32, w)

        _compiled_has_kernel(_out_and_grads(kernel), *x)
        found = {}
        for path, fn in (("kernel", kernel), ("tiles", tiles))[:1 + with_tiles]:
            first_f, ms_f, _ = _timed(jax.jit(fn), x)
            first, ms, found[path] = _timed(_out_and_grads(fn), x)
            log(f"gqa: {name} core, {path}: first calls {first_f:.1f}s and "
                f"{first:.1f}s, forward {ms_f:.1f} ms, forward + backward "
                f"{ms:.1f} ms")
        with jax.default_matmul_precision("highest"):
            want = _out_and_grads(_dense_causal(t, h, hk, d, w))(*x)
        log(f"gqa: {name} core: norm of the difference over the norm, "
            f"output and gradients q k v: kernel to the dense masked softmax "
            f"{_said(_gaps(found['kernel'], want))}" + (
                f"; tiles to it {_said(_gaps(found['tiles'], want))}; kernel "
                f"to tiles {_said(_gaps(found['kernel'], found['tiles']))}"
                if with_tiles else ""))
        for got in found.values():
            assert all(g <= 2e-2 for g in _gaps(got, want)), name
        for obq, obkv in SIZES["gqa_blocks"]:
            first, ms, _ = _timed(_out_and_grads(functools.partial(
                kernel, bq=obq, bkv=obkv)), x)
            log(f"gqa: {name} core, kernel at blocks {obq} x {obkv}: first "
                f"call {first:.1f}s, forward + backward {ms:.1f} ms")
    faulthandler.cancel_dump_traceback_later()


# ------------------------------------- the third decoder's two cores

def phase_gdn() -> None:
    """The two cores of the ``qwen3_next`` family stand-alone at published
    sizes. The delta rule as ``GDNMixer`` calls it — ``ops/kda.py
    kda_chunked`` with q and k at the key heads and ONE log decay a value
    head and token (a gate of rank 3), at decays as large as the family's
    initial values give (16 x softplus(1) = 21 nats a token on the fastest
    head): the path ``kda_path`` takes there, the scalar-gate kernels
    against the plain scan fed q and k repeated and the gate broadcast
    over the key channels, output and five gradients (the scan's dq and dk
    summed over a key head's value heads, its dg over the channels), both
    timed. (Against the token recurrence itself: the ``kernels`` phase, on
    a row short enough for its gradient.) Then the gated attention's
    causal core through ``phase_gqa``'s rows."""
    import faulthandler

    import jax
    import jax.numpy as jnp

    from dinov3_tpu.ops import kda

    interpret = bool(SIZES["kernel_interpret"])
    faulthandler.dump_traceback_later(
        float(SIZES["gqa_timeout_s"]), exit=True, file=sys.__stderr__)
    b, t, hk, h, d = SIZES["gdn_shape"]
    path, why = kda.kda_path(d, d, interpret=interpret or None,
                             gate_heads=(hk, h))
    log(f"gdn: delta rule {(b, t, hk, h, d)} at a scalar gate: the entry "
        f"point takes the {path} ({why})")
    assert path == "kernel" and "scalar" in why, (path, why)
    ks = jax.random.split(jax.random.key(3), 5)
    unit = lambda x: (x / jnp.linalg.norm(  # noqa: E731
        x.astype(jnp.float32), axis=-1, keepdims=True)).astype(jnp.bfloat16)
    q, k = (unit(jax.random.normal(key, (b, t, hk, d))) for key in ks[:2])
    v = jax.random.normal(ks[2], (b, t, h, d), jnp.bfloat16)
    rate = jnp.linspace(0.05, 16.0, h)[None, None, :]       # A, a head
    g = -rate * jax.nn.softplus(1.0 + jax.random.normal(ks[3], (b, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    x = (q, k, v, g, beta)

    def core(scan):
        def fn(q, k, v, g, beta):
            if scan:
                q, k, g = kda._per_channel(q, k, v, g)
                return kda._scan_forward(q, k, v, g, beta, kda.CHUNK, d ** -0.5)
            return kda.kda_chunked(q, k, v, g, beta, q_scale=d ** -0.5,
                                   interpret=True if interpret else None)
        return jax.jit(lambda *a: (fn(*a), *jax.grad(
            lambda *y: jnp.sum(jnp.sin(fn(*y))), argnums=(0, 1, 2, 3, 4))(*a)))

    found = {}
    for name, fn in (("kernel", core(False)), ("scan", core(True))):
        first, ms, found[name] = _timed(fn, x)
        log(f"gdn: delta rule, {name}: first call {first:.1f}s, forward + "
            f"backward {ms:.1f} ms")
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    gaps = [float(jnp.linalg.norm(f32(a) - f32(r)) / jnp.linalg.norm(f32(r)))
            for a, r in zip(found["kernel"], found["scan"])]
    log("gdn: delta rule: norm of the difference over the norm, kernel to "
        "scan, output and gradients q k v g beta "
        + " ".join(f"{x:.3e}" for x in gaps))
    assert all(math.isfinite(x) for x in gaps) and gaps[0] <= 1e-4 \
        and max(gaps[1:4]) <= 2e-2 and max(gaps[4:]) <= 1e-3, gaps
    faulthandler.cancel_dump_traceback_later()
    phase_gqa("gdn_attn_shapes")


def phase_sconv() -> None:
    """The ``lfm2_moe`` family's two kernels stand-alone at published
    sizes. The gated short convolution's chain (``ops/mixer_chains.py
    gated_short_conv``: y = C * conv3(B * u) of one ``[B, T, 3 C]`` plane)
    on the path ``mixer_chain_path`` takes there, the kernel pair against
    the plain XLA chain, output and both gradients, both timed; then the
    causal core at heads of 64 (two key/value heads a lane group) through
    ``phase_gqa``'s rows."""
    import faulthandler

    import jax
    import jax.numpy as jnp

    from dinov3_tpu.models.decoder import causal_depthwise_conv
    from dinov3_tpu.ops import mixer_chains as mc

    interpret = bool(SIZES["kernel_interpret"])
    faulthandler.dump_traceback_later(
        float(SIZES["gqa_timeout_s"]), exit=True, file=sys.__stderr__)
    b, t, c = SIZES["sconv_shape"]
    path, why = mc.mixer_chain_path(t, (c,), (), jnp.bfloat16,
                                    interpret=interpret or None)
    log(f"sconv: chain {(b, t, c)}: the entry point takes the {path} ({why})")
    assert path == "kernel", (path, why)
    ks = jax.random.split(jax.random.key(4), 2)
    x = (jax.random.normal(ks[0], (b, t, 3 * c), jnp.bfloat16),
         jax.random.uniform(ks[1], (3, c), jnp.float32, -3 ** -0.5, 3 ** -0.5))

    def plain(plane, taps):
        gate, mid, u = (plane[..., i * c:(i + 1) * c].astype(jnp.float32)
                        for i in range(3))
        return (mid * causal_depthwise_conv(gate * u, taps)).astype(plane.dtype)

    def kernel(plane, taps):
        return mc.gated_short_conv(plane, taps,
                                   interpret=True if interpret else None)

    def out_and_grads(f):
        return jax.jit(lambda *a: (f(*a), *jax.grad(
            lambda *y: jnp.sum(jnp.sin(f(*y).astype(jnp.float32))),
            argnums=(0, 1))(*a)))

    found = {}
    for name, fn in (("kernel", kernel), ("plain", plain)):
        first, ms, found[name] = _timed(out_and_grads(fn), x)
        log(f"sconv: chain, {name}: first call {first:.1f}s, forward + "
            f"backward {ms:.2f} ms")
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    gaps = [float(jnp.linalg.norm(f32(a) - f32(r)) / jnp.linalg.norm(f32(r)))
            for a, r in zip(found["kernel"], found["plain"])]
    log("sconv: chain: norm of the difference over the norm, kernel to "
        "plain, output and gradients plane taps "
        + " ".join(f"{g:.3e}" for g in gaps))
    assert all(math.isfinite(g) for g in gaps) and max(gaps) <= 3e-3, gaps
    faulthandler.cancel_dump_traceback_later()
    phase_gqa("sconv_attn_shapes")


def phase_ssd() -> None:
    """The ``nemotron_h`` family's three kernels stand-alone at published
    sizes. The state-space scan (``ops/ssd.py ssd_chunked``: u, B and C in
    one joined plane, dt > 0 and ONE rate a head, at rates as large as the
    family's initial values give: A up to 16, dt up to 0.1 and a few
    steps near 1) on the path ``ssd_path`` takes there, the kernel pair
    against the plain scan, output and the three gradients (the plane's
    by its u, B and C parts), both timed, then the pair at other chunks;
    the two chains around the scan (``_ssd_chains``); the causal core at 32 | 2 heads of 128 through ``phase_gqa``'s rows;
    the un-gated experts' block at 2688 x 1856 through ``phase_moe``'s."""
    import faulthandler

    import jax
    import jax.numpy as jnp

    from dinov3_tpu.ops import ssd

    interpret = bool(SIZES["kernel_interpret"])
    faulthandler.dump_traceback_later(
        float(SIZES["gqa_timeout_s"]), exit=True, file=sys.__stderr__)
    b, t, h, p, g, n = SIZES["ssd_shape"]
    sizes = (h, p, g, n)
    path, why = ssd.ssd_path(*sizes, t, jnp.bfloat16, interpret or None)
    log(f"ssd: scan {(b, t, h, p, g, n)}: the entry point takes the {path} "
        f"({why})")
    assert path == "kernel", (path, why)
    ks = jax.random.split(jax.random.key(8), 3)
    xbc = jax.random.normal(ks[0], (b, t, h * p + 2 * g * n), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) * 1.5 - 4.0)
    a = -jnp.linspace(1.0, 16.0, h)
    x = (xbc, dt, a)
    weigh = jax.random.normal(ks[2], (b, t, h * p))

    def out_and_grads(f):
        # a LINEAR weight: y is tens here, and sin of a bfloat16 y turns
        # the output's rounding into percents of the gradient, on either
        # path
        return jax.jit(lambda *x: (f(*x), *jax.grad(
            lambda *y: jnp.sum(weigh * f(*y).astype(jnp.float32)),
            argnums=(0, 1, 2))(*x)))

    def core(which, chunk=ssd.KERNEL_CHUNK):
        def fn(xbc, dt, a):
            if which == "scan":
                return ssd._scan_forward(xbc, dt, a, *sizes,
                                         ssd.PLAIN_CHUNK).astype(xbc.dtype)
            return ssd.kernel_scan(xbc, dt, a, (*sizes, chunk), interpret)
        return fn

    found = {}
    for name, fn in (("kernel", core("kernel")), ("scan", core("scan"))):
        first, fwd, _ = _timed(jax.jit(fn), x)
        _, ms, found[name] = _timed(out_and_grads(fn), x)
        log(f"ssd: scan, {name}: first call {first:.1f}s, forward {fwd:.2f} "
            f"ms, forward + backward {ms:.2f} ms")
    inner, gn = h * p, g * n
    parts = lambda r: (r[0], r[1][..., :inner],  # noqa: E731
                       r[1][..., inner:inner + gn], r[1][..., inner + gn:],
                       r[2], r[3])
    gaps = _gaps(parts(found["kernel"]), parts(found["scan"]))
    log("ssd: scan: norm of the difference over the norm, kernel to plain "
        "scan, output and gradients u B C dt a " + _said(gaps))
    assert all(math.isfinite(v) for v in gaps) and max(gaps) <= 2e-2, gaps
    for chunk in SIZES["ssd_chunks"]:
        fn = core("kernel", chunk)
        _, fwd, _ = _timed(jax.jit(fn), x)
        _, ms, out = _timed(out_and_grads(fn), x)
        log(f"ssd: scan, kernel at chunks of {chunk}: forward {fwd:.2f} ms, "
            f"forward + backward {ms:.2f} ms; to the plain scan "
            + _said(_gaps(parts(out), parts(found["scan"]))))
    _ssd_chains(b, t, h, p, g, n, interpret)
    faulthandler.cancel_dump_traceback_later()
    # (the plain tiles at this shape are minutes of compiling for a path
    # that does not ship: the dense masked softmax is the oracle)
    phase_gqa("ssd_attn_shapes", with_tiles=False)
    phase_moe(shapes="ssd_moe_shapes")


def _ssd_chains(b, t, h, p, g, n, interpret) -> None:
    """``Mamba2Mixer``'s two chains stand-alone (``ops/mixer_chains.py
    ssm_conv_silu`` and ``ssm_gate_norm``) on in_proj's lane-tiled plane
    [z | xBC | dt | zeros]: each kernel pair against the plain chain of
    ``models/decoder.py`` written out, output and every gradient, both
    timed forward and forward + backward with the GB/s of the bytes a pass
    has to move (every operand read once, every result written once,
    bfloat16), then the pair at other time and lane blocks."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.models.decoder import causal_depthwise_conv
    from dinov3_tpu.ops import mixer_chains as mc

    inner, joined = h * p, h * p + 2 * g * n
    wide = -(-(inner + joined + h) // mc.LANES) * mc.LANES
    path, why = mc.ssm_chain_path(t, inner, joined, g, jnp.bfloat16,
                                  interpret=interpret or None)
    log(f"ssd: chains {(b, t)} x {inner} | {joined} | {h} in a plane of "
        f"{wide}: the mixer takes the {path} ({why})")
    assert path == "kernel", (path, why)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    ks = jax.random.split(jax.random.key(9), 9)
    draw = lambda k, *shape: jax.random.normal(  # noqa: E731
        ks[k], shape, jnp.float32)
    plane, xbc, y = (draw(k, b, t, w).astype(jnp.bfloat16)
                     for k, w in enumerate((wide, joined, inner)))
    rows = 2 * b * t / 1e6   # MB a bfloat16 lane of every token

    def conv(block=mc.SSM_TIME_BLOCK, lanes=None):
        return lambda plane, taps, bias: mc.ssm_conv_silu(
            plane, taps, bias, first=inner, block=block, lanes=lanes,
            interpret=interpret)

    def plain_conv(plane, taps, bias):
        return jax.nn.silu(causal_depthwise_conv(
            f32(plane[..., inner:inner + joined]), taps) + bias
        ).astype(plane.dtype)

    def norm(block=mc.SSM_TIME_BLOCK, lanes=None):
        return lambda y, xbc, plane, skip, scale: mc.ssm_gate_norm(
            y, xbc, plane, jnp.repeat(skip, p), scale, g, 1e-5, block=block,
            lanes=lanes, interpret=interpret)

    def plain_norm(y, xbc, plane, skip, scale):
        v = f32(y).reshape(b, t, h, p) + skip[:, None] * f32(
            xbc[..., :inner]).reshape(b, t, h, p)
        v = v.reshape(b, t, inner) * jax.nn.silu(f32(plane[..., :inner]))
        v = v.reshape(b, t, g, inner // g)
        v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True) + 1e-5)
        return (v.reshape(b, t, inner) * scale).astype(y.dtype)

    # (name, the pair at given blocks, the plain chain, operands, the
    # gradients' names, MB forward and forward + backward)
    chains = (
        ("conv", conv, plain_conv,
         (plane, 0.5 * draw(3, 4, joined), 0.5 * draw(4, joined)),
         "plane taps bias", 2 * joined * rows, 5 * joined * rows),
        ("norm", norm, plain_norm,
         (y, xbc, plane, 1 + 0.5 * draw(5, h), 1 + 0.3 * draw(6, inner)),
         "y xbc plane D scale", 4 * inner * rows, 11 * inner * rows))
    for name, pair, plain, x, grads, mb_fwd, mb_both in chains:
        weigh = draw(7, *plain(*x).shape)

        def out_and_grads(f, n_args=len(x), weigh=weigh):
            return jax.jit(lambda *a: (f(*a), *jax.grad(
                lambda *c: jnp.sum(weigh * f32(f(*c))),
                argnums=tuple(range(n_args)))(*a)))

        def timed(fn, said):
            first, fwd, _ = _timed(jax.jit(fn), x)
            _, ms, out = _timed(out_and_grads(fn), x)
            log(f"ssd: chain {name}, {said}: first call {first:.1f}s, forward "
                f"{fwd:.2f} ms ({mb_fwd / fwd:.0f} GB/s of {mb_fwd:.0f} MB), "
                f"forward + backward {ms:.2f} ms ({mb_both / ms:.0f} GB/s of "
                f"{mb_both:.0f} MB)")
            return out

        got = timed(pair(), "kernel")
        want = timed(plain, "plain")
        gaps = _gaps(got, want)
        log(f"ssd: chain {name}: norm of the difference over the norm, kernel "
            f"to plain chain, output and gradients {grads} " + _said(gaps))
        assert all(math.isfinite(v) for v in gaps) and max(gaps) <= 1e-2, gaps
        for block, lanes in SIZES["ssd_chain_blocks"]:
            out = timed(pair(block, lanes),
                        f"kernel at blocks of {block} x {lanes}")
            assert max(_gaps(out, want)) <= 1e-2, (name, block, lanes)


def phase_mla() -> None:
    """Latent attention stand-alone at published sizes. The plain arm's
    interleaved rotary turn (``ops/rope.py rope_apply_interleaved``) of
    every query head's last 64 channels and of the ONE shared key head,
    bfloat16 ends, against a complex multiplication in float32, forward and
    forward + backward timed. Then the latent kernel pair
    (``ops/causal_attention.py latent_attention``) as ``MLAMixer`` calls
    it — q, kvb and the shared key as the projections leave them, q's
    rope channels turned in the kernels — at both latent cells' shapes:
    the path ``latent_attention_path`` takes there, output and the
    gradients of q, kvb and kpe against the dense masked softmax in
    float32 (the key written out a head, the turn by
    ``rope_apply_interleaved``) and, where they fit, against the plain
    arm (the key repeated, the plain tiles), forward and forward +
    backward timed. Last the generic pair through ``phase_gqa``'s rows
    at heads of 256 | 128: the kernels alone on operands already padded."""
    import faulthandler

    import jax
    import jax.numpy as jnp

    from dinov3_tpu.ops import causal_attention as kernels
    from dinov3_tpu.ops.attention import causal_tiles
    from dinov3_tpu.ops.rope import (
        rope_apply_interleaved,
        rope_apply_pairs,
        token_rope_pair_sincos,
    )

    b, t, h, d, rope = SIZES["mla_shape"]
    ks = jax.random.split(jax.random.key(8), 2)
    x = (jax.random.normal(ks[0], (b, t, h, d), jnp.bfloat16),
         jax.random.normal(ks[1], (b, t, 1, rope), jnp.bfloat16))

    def turn(q, kpe):
        table = token_rope_pair_sincos(t, rope, 1e6)
        return (rope_apply_interleaved(q, *table),
                rope_apply_interleaved(kpe, *table))

    def by_complex(q, kpe):
        rates = 1e6 ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
        angle = jnp.arange(t, dtype=jnp.float32)[:, None] * rates
        phase = jax.lax.complex(jnp.cos(angle), jnp.sin(angle))[None, :, None]

        def one(z):
            lead, z = z[..., :z.shape[-1] - rope], z[..., -rope:]
            z = z.astype(jnp.float32)
            z = jax.lax.complex(z[..., 0::2], z[..., 1::2]) * phase
            return jnp.concatenate(
                [lead.astype(jnp.float32), z.real, z.imag], -1)
        return one(q), one(kpe)

    def out_and_grads(f):
        return jax.jit(lambda *a: (*f(*a), *jax.grad(lambda *y: sum(
            jnp.sum(jnp.sin(o.astype(jnp.float32))) for o in f(*y)),
            argnums=(0, 1))(*a)))

    first_f, ms_f, _ = _timed(jax.jit(turn), x)
    first, ms, got = _timed(out_and_grads(turn), x)
    log(f"mla: turn {(b, t, h, d)} + {(b, t, 1, rope)}: first calls "
        f"{first_f:.1f}s and {first:.1f}s, forward {ms_f:.2f} ms, forward + "
        f"backward {ms:.2f} ms")
    want = out_and_grads(by_complex)(*x)
    gaps = _gaps(got, want)
    log("mla: turn: norm of the difference over the norm, to the complex "
        "multiplication, q kpe and gradients q kpe " + _said(gaps))
    # (bfloat16 ends: one rounding of the output, one of each gradient)
    assert all(math.isfinite(g) for g in gaps) and max(gaps) <= 1e-2, gaps

    interpret = bool(SIZES["kernel_interpret"])
    faulthandler.dump_traceback_later(
        float(SIZES["gqa_timeout_s"]), exit=True, file=sys.__stderr__)
    # the latent entry's own blocks (the test's table names smaller ones)
    bq, bkv = SIZES.get("mla_blocks", (kernels.LATENT_BLOCK_Q,
                                       kernels.LATENT_BLOCK_KV))
    nope, dv = d - rope, 128
    for name, (b, t, h, theta, with_tiles) in SIZES["mla_latent_shapes"].items():
        path, why = kernels.latent_attention_path(
            t, h, (nope, rope, dv), interpret or None, bq, bkv)
        log(f"mla: {name} latent core {(b, t, h, nope, rope, dv)} theta "
            f"{theta}: the mixer takes the {path} ({why})")
        assert path == "kernel", (name, path, why)
        ks = jax.random.split(jax.random.key(9), 3)
        x = tuple(jax.random.normal(key, (b, t, w), jnp.bfloat16)
                  for key, w in zip(ks, (h * d, h * (nope + dv), rope)))
        table = None if theta is None else token_rope_pair_sincos(t, rope, theta)

        def kernel(q, kvb, kpe, table=table, theta=theta):
            if table is not None:
                kpe = rope_apply_pairs(kpe, *table)
            return kernels.latent_attention(q, kvb, kpe, theta, bq, bkv, interpret)

        def a_key_a_head(core, b=b, t=t, h=h, table=table):
            """``MLAMixer``'s plain arm around ``core(q, k, v)``."""
            def fn(q, kvb, kpe):
                q, kvb = q.reshape(b, t, h, d), kvb.reshape(b, t, h, nope + dv)
                kpe = kpe[:, :, None, :]
                if table is not None:
                    q = rope_apply_interleaved(q, *table)
                    kpe = rope_apply_interleaved(kpe, *table)
                k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
                    kpe, (b, t, h, rope))], axis=-1)
                return core(q, k, kvb[..., nope:]).reshape(b, t, h * dv)
            return fn

        tiles = a_key_a_head(lambda *a: causal_tiles(  # (at ITS blocks)
            *a, *SIZES["gqa_shipped_blocks"], jnp.float32, None))
        _compiled_has_kernel(_out_and_grads(kernel), *x)
        found = {}
        for path, fn in (("kernel", kernel), ("tiles", tiles))[:1 + with_tiles]:
            first_f, ms_f, _ = _timed(jax.jit(fn), x)
            first, ms, found[path] = _timed(_out_and_grads(fn), x)
            log(f"mla: {name} latent core, {path}: first calls {first_f:.1f}s "
                f"and {first:.1f}s, forward {ms_f:.1f} ms, forward + backward "
                f"{ms:.1f} ms")
        with jax.default_matmul_precision("highest"):
            want = _out_and_grads(a_key_a_head(_dense_causal(
                t, h, h, d, None)))(*(a.astype(jnp.float32) for a in x))
        log(f"mla: {name} latent core: norm of the difference over the norm, "
            f"output and gradients q kvb kpe: kernel to the dense masked "
            f"softmax {_said(_gaps(found['kernel'], want))}" + (
                f"; tiles to it {_said(_gaps(found['tiles'], want))}; kernel "
                f"to tiles {_said(_gaps(found['kernel'], found['tiles']))}"
                if with_tiles else ""))
        for got in found.values():
            assert all(g <= 2e-2 for g in _gaps(got, want)), name
    faulthandler.cancel_dump_traceback_later()
    phase_gqa("mla_attn_shapes", with_tiles=False)


def _moe_lists(n, k, cap, sizes, form):
    """``ops/routed_rows.py``'s lists as a layer's routing would make them:
    ``sum(sizes)`` of the ``n * k`` (token, choice) pairs at random, dealt
    to the held experts by ``sizes`` and kept in pair order inside an
    expert's group, then the pairs routed elsewhere in pair order."""
    import jax.numpy as jnp
    import numpy as np

    from dinov3_tpu.ops.routed_rows import row_lists

    rng = np.random.default_rng(11)
    n_here = int(np.sum(sizes))
    here = rng.permutation(n * k)[:n_here]
    groups = np.split(here, np.cumsum(sizes)[:-1])
    away = np.setdiff1d(np.arange(n * k), here)
    order = np.concatenate([np.sort(g) for g in groups] + [away])[:cap]
    kept = jnp.arange(cap) < n_here
    return row_lists(jnp.asarray(order, jnp.int32), kept, n, k, form), kept


def _moe_rows(name, n, k, cap, d, fill, sizes) -> None:
    """One routed layer's row movement ALONE: dispatch, combine and the
    transpose of each, in each form of ``ops/routed_rows.py`` and as the
    parent of PR 47 wrote them (a fill-mode ``take`` under a row mask, a
    float32 ``.at[].add``, JAX's own transposition), ms a call and the
    GB/s of the rows each must at least read and write; the forms'
    results compared with the parent's."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.ops import routed_rows as rr

    ks = jax.random.split(jax.random.key(8), 4)
    x = jax.random.normal(ks[0], (n, d), jnp.bfloat16)
    dy = jax.random.normal(ks[1], (n, d), jnp.bfloat16)
    plain, kept = _moe_lists(n, k, cap, sizes, "scatter")
    # what the experts' block leaves past the last group: zeros
    out = jnp.where(kept[:, None], jax.random.normal(ks[2], (cap, d)), 0.0)
    d_rows = jnp.where(kept[:, None], jax.random.normal(
        ks[3], (cap, d)), 0.0).astype(jnp.bfloat16)
    token = plain.token

    def parent_dispatch(x):
        return jnp.where(kept[:, None], jnp.take(x, token, axis=0), 0)

    def parent_combine(out):
        return jnp.zeros((n, d), jnp.float32).at[token].add(out).astype(
            jnp.bfloat16)

    interpret = bool(SIZES["kernel_interpret"])
    forms = {"parent": (parent_dispatch, parent_combine)}
    for form in ("sorted", "scatter"):
        lists, _ = _moe_lists(n, k, cap, sizes, form)
        forms[form] = (
            functools.partial(rr.dispatch_rows, lists=lists,
                              interpret=interpret),
            functools.partial(rr.combine_rows, lists=lists, n_tokens=n,
                              dtype=jnp.bfloat16, interpret=interpret))
    pick = rr.combine_form(n, cap, d, interpret or None)
    log(f"moe_rows: {name} N {n} K {k} cap {cap} D {d} fill {fill}: "
        f"{n * k / cap:.1f} pairs a buffer row, the layer's combine takes "
        f"the {pick}")
    gb = {"dispatch": 2 * cap * d * 2, "combine": cap * d * 4 + n * d * 2,
          "combine^T": 2 * cap * d * 2, "dispatch^T": cap * d * 2 + n * d * 2}
    found = {}
    for form, (dispatch, combine) in forms.items():
        t = lambda f: jax.jit(  # noqa: E731
            lambda a, ct: jax.vjp(f, a)[1](ct)[0])
        calls = {"dispatch": (jax.jit(dispatch), (x,)),
                 "combine": (jax.jit(combine), (out,)),
                 "combine^T": (t(combine), (out, dy)),
                 "dispatch^T": (t(dispatch), (x, d_rows))}
        said, total = [], 0.0
        for what, (fn, args) in calls.items():
            first, ms, found[form, what] = _timed(fn, args, n=5)
            total += ms
            said.append(f"{what} {ms:.3f} ms ({gb[what] / ms / 1e6:.0f} GB/s, "
                        f"first {first:.1f}s)")
        log(f"moe_rows: {name} fill {fill}, {form}: " + ", ".join(said)
            + f"; all four {total:.3f} ms")
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    for (form, what), got in found.items():
        want = found["parent", what]
        if what == "combine^T":  # the parent's is float32 of the same rows
            assert got.dtype == jnp.float32, (form, got.dtype)
        # (the rows past the last group: the parent masks them, the forms
        # leave them to the block)
        if what == "dispatch":
            got = jnp.where(kept[:, None], got, 0)
        if what == "combine^T":
            got, want = (jnp.where(kept[:, None], a, 0) for a in (got, want))
        gap = float(jnp.linalg.norm(f32(got) - f32(want))
                    / jnp.linalg.norm(f32(want)))
        assert gap <= 1e-2, (name, form, what, gap)
    log(f"moe_rows: {name} fill {fill}: every form within 1e-2 of the "
        "parent's by norm, all four")


def phase_moe(block: bool = True, shapes: str = "moe_shapes") -> None:
    """The routed layers of ``ops/ffn.py RoutedExpertsFFN`` stand-alone at
    the six decoder cells' published shapes and measured fills, a shape at
    a time: its row movement (``_moe_rows``; ``--phases moe_rows`` stops
    there), then its held experts' block (``ops/grouped_matmul.py``): the kernels
    against the ``lax.ragged_dot`` form, forward and forward + backward
    timed on both, the two ``ragged_dot`` calls of the forward timed alone
    (do they cost by the buffer or by the rows?), output and the four
    gradients compared, and the rows past the last group read back as
    exact zeros out of a buffer that holds 1e6 there."""
    import faulthandler

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dinov3_tpu.ops import grouped_matmul as gm

    interpret = bool(SIZES["kernel_interpret"])
    faulthandler.dump_traceback_later(
        float(SIZES["gqa_timeout_s"]), exit=True, file=sys.__stderr__)

    def group_sizes(held, fill, cap):
        share = np.random.default_rng(7).uniform(0.8, 1.2, held)
        sizes = np.floor(share / share.sum() * fill * cap).astype(np.int32)
        sizes[0] += int(fill * cap) - sizes.sum()
        return sizes

    for name, (n, k, cap, held, d, hid, gate, fills) in SIZES[shapes].items():
        fill = fills[min(1, len(fills) - 1)]  # the measured fill
        _moe_rows(name, n, k, cap, d, fill, group_sizes(held, fill, cap))
        if not block:
            continue
        path, why = gm.grouped_matmul_path(cap, d, hid, jnp.bfloat16,
                                           interpret=interpret or None,
                                           gate=gate)
        tm = gm.row_tile(cap)
        log(f"moe: {name} {(cap, held, d, hid)} {gate}: the layer takes the "
            f"{path} ({why}), {tm} rows a visit")
        assert path == "kernel", (path, why)
        ks = jax.random.split(jax.random.key(6), 5)
        w12 = jax.random.normal(  # an un-gated expert's first matrix: H wide
            ks[0], (held, d, hid if gate == gm.UNGATED else 2 * hid)) * d ** -0.5
        w3 = jax.random.normal(ks[1], (held, hid, d)) * hid ** -0.5
        ct = jax.random.normal(ks[2], (cap, d))
        for fill in fills:
            sizes = group_sizes(held, fill, cap)
            kept = jnp.arange(cap) < int(sizes.sum())
            rows = jnp.where(kept[:, None], jax.random.normal(
                ks[3], (cap, d)), 1e6).astype(jnp.bfloat16)
            w_rows = jnp.where(kept, jax.random.uniform(ks[4], (cap,)), 1e6)
            sizes = jnp.asarray(sizes)

            def kernel(rows, w12, w3, w_rows, sizes, tm=tm, gate=gate):
                return gm.experts_block(rows, w12, w3, w_rows, sizes, gate,
                                        tm, interpret)

            def ragged(rows, w12, w3, w_rows, sizes, gate=gate):
                kept = jnp.arange(rows.shape[0]) < jnp.sum(sizes)
                return gm.ragged_experts_block(
                    jnp.where(kept[:, None], rows, 0), w12, w3,
                    jnp.where(kept, w_rows, 0.0), sizes, kept, gate)

            def both_passes(f):
                return jax.jit(lambda *a: (f(*a[:5]), *jax.grad(
                    lambda *y: jnp.sum(f(*y, a[4]) * a[5]),
                    argnums=(0, 1, 2, 3))(*a[:4])))

            x = (rows, w12, w3, w_rows, sizes)
            found = {}
            for which, fn in (("kernel", kernel), ("ragged_dot", ragged)):
                first, fwd, _ = _timed(jax.jit(fn), x)
                _, ms, found[which] = _timed(both_passes(fn), x + (ct,))
                log(f"moe: {name} fill {fill}, {which}: first call "
                    f"{first:.1f}s, forward {fwd:.2f} ms, forward + backward "
                    f"{ms:.2f} ms")
            if fill == fills[min(1, len(fills) - 1)]:
                for other in (t for t in (128, 256, 512)
                              if t != tm and cap % t == 0):
                    _, ms, _ = _timed(both_passes(functools.partial(
                        kernel, tm=other)), x + (ct,))
                    log(f"moe: {name} fill {fill}, kernel at {other} rows a "
                        f"visit: forward + backward {ms:.2f} ms")
            alone = []
            for left, right in ((rows, w12), (jnp.pad(
                    found["kernel"][0].astype(jnp.bfloat16),
                    ((0, 0), (0, max(hid - d, 0))))[:, :hid], w3)):
                _, ms, _ = _timed(jax.jit(lambda a, b, n: jax.lax.ragged_dot(
                    a, b.astype(a.dtype), n,
                    preferred_element_type=jnp.float32)), (left, right, sizes))
                alone.append(ms)
            log(f"moe: {name} fill {fill}: the forward's two ragged_dot calls "
                f"alone {alone[0]:.2f} and {alone[1]:.2f} ms")
            f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
            gaps = [float(jnp.linalg.norm(f32(a) - f32(r))
                          / jnp.linalg.norm(f32(r)))
                    for a, r in zip(found["kernel"], found["ragged_dot"])]
            tail = max(float(jnp.max(jnp.abs(jnp.where(
                kept.reshape((-1,) + (1,) * (a.ndim - 1)), 0, f32(a)))))
                for a in (found["kernel"][i] for i in (0, 1, 4)))
            log(f"moe: {name} fill {fill}: norm of the difference over the "
                "norm, kernel to ragged_dot, output and gradients rows w12 "
                "w3 w_rows " + " ".join(f"{g:.3e}" for g in gaps)
                + f"; largest value past the last group {tail:.1e}")
            assert all(math.isfinite(g) for g in gaps) and max(gaps) <= 2e-2 \
                and tail == 0.0, (gaps, tail)
    faulthandler.cancel_dump_traceback_later()


def phase_dsa() -> None:
    """The ``keye_vl2`` family's sparse attention stand-alone at published
    sizes (``ops/sparse_index.py``, ``ops/causal_attention.py``): the
    exact selection of every query's ``topk`` keys (every count right),
    the causal kernel pair UNDER that selection against a masked softmax
    over whole rows (a block of queries at a time), output and three
    gradients, and the index loss with its closed-form gradient by its
    kernel (fed the log-sum-exp the core hands on) against the plain
    strips; each timed. The selection is made INSIDE the program that uses it, as the
    step makes it."""
    import faulthandler

    import jax
    import jax.numpy as jnp

    from dinov3_tpu.ops import sparse_index
    from dinov3_tpu.ops.attention import causal_blockwise_attention
    from dinov3_tpu.ops.causal_attention import (
        causal_attention_path,
        index_loss_tiles,
        kernel_attention,
        kernel_attention_selected,
    )

    interpret = bool(SIZES["kernel_interpret"])
    faulthandler.dump_traceback_later(
        float(SIZES["gqa_timeout_s"]), exit=True, file=sys.__stderr__)
    b, t, h, hk, d, hi, di, topk = SIZES["dsa_shape"]
    ks = jax.random.split(jax.random.key(5), 7)
    bf = lambda key, shape: jax.random.normal(key, shape, jnp.bfloat16)  # noqa: E731
    q, k, v = bf(ks[0], (b, t, h, d)), bf(ks[1], (b, t, hk, d)), bf(ks[2], (b, t, hk, d))
    qi, ki = bf(ks[3], (b, t, hi, di)), bf(ks[4], (b, t, di))
    a = jax.random.normal(ks[5], (b, t, hi)) * (hi * di) ** -0.5
    do = bf(ks[6], (b, t, h, d))

    select = jax.jit(lambda qi, ki, a: sparse_index.select_thresholds(
        qi, ki, a, topk=topk))
    first, ms, (thr, last) = _timed(select, (qi, ki, a))
    log(f"dsa: selection {(b, t, hi, di)} keep {topk}: first call "
        f"{first:.1f}s, {ms:.1f} ms")
    plane_of = jax.jit(lambda qi, ki, a, thr, last: sparse_index.selection_plane(
        qi, ki, a, thr, last, topk=topk))
    first, ms, (plane, excess) = _timed(plane_of, (qi, ki, a, thr, last))
    kept = int(jnp.sum(plane.astype(jnp.int32)))
    want = b * sum(min(i + 1, topk) for i in range(t))
    log(f"dsa: selected plane: first call {first:.1f}s, {ms:.1f} ms; kept "
        f"{kept} pairs of {want} wanted, {int(excess)} queries miscounted")
    assert kept == want and int(excess) == 0, (kept, want, int(excess))

    bq, bkv = SIZES["gqa_shipped_blocks"]
    path, why = causal_attention_path(
        (q.shape, k.shape, v.shape), None, interpret or None, bq, bkv)
    log(f"dsa: core {(b, t, h, hk, d)} under a selection: the entry point "
        f"takes the {path} ({why})")
    assert path == "kernel", (path, why)
    def under(plane):
        """The core under ``plane``: (output, the rows' log-sum-exp)."""
        if interpret:
            return lambda q, k, v: kernel_attention_selected(
                q, k, v, plane, d ** -0.5, bq, bkv, True)
        return lambda q, k, v: causal_blockwise_attention(q, k, v, selection=plane)

    # the core's forward pass hands on the rows' log-sum-exp; with it the
    # index loss is one kernel: alone (a layer's forward pass), then with
    # its gradient (the forward rule), against the plain strips
    # (the planes are ARGUMENTS of these programs: closed over, a 268 MB
    # constant costs each compile half a minute)
    first, ms, (_, lse) = _timed(jax.jit(
        lambda q, k, v, plane: under(plane)(q, k, v)), (q, k, v, plane))
    log(f"dsa: core forward, with its rows' log-sum-exp: first call "
        f"{first:.1f}s, {ms:.1f} ms")
    operands = (qi, ki, a, plane, q, k, lse)

    def by_kernel(with_grad):
        return jax.jit(lambda *x: index_loss_tiles(
            *x, with_grad, bq, bkv, interpret))

    first, ms, _ = _timed(by_kernel(False), operands)
    log(f"dsa: index loss alone by the kernel: first call {first:.1f}s, {ms:.1f} ms")
    found, took = {}, {}
    for name, fn in (("kernel", by_kernel(True)), ("strips", jax.jit(
            jax.value_and_grad(lambda *x: sparse_index.index_loss(*x[:6]),
                               argnums=(0, 1, 2))))):
        first, took[name], found[name] = _timed(fn, operands, n=2)
        log(f"dsa: index loss by the {name}: {float(found[name][0]):.4f}; "
            f"first call {first:.1f}s")
    log(f"dsa: the index loss with its gradient {took['kernel']:.1f} ms a layer "
        f"call by the kernel against {took['strips']:.1f} by the strips")
    (loss, grads), (want, want_grads) = found["kernel"], found["strips"]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    gaps = [abs(float(loss) - float(want)) / float(want)] + [
        float(jnp.linalg.norm(f32(g) - f32(w)) / jnp.linalg.norm(f32(w)))
        for g, w in zip(grads, want_grads)]
    log("dsa: index loss: kernel to strips, the loss's relative gap and the "
        "gradients' (q^I, k^I, a) " + " ".join(f"{x:.3e}" for x in gaps))
    assert all(math.isfinite(x) and x <= 2e-2 for x in gaps) and float(loss) > 0, gaps

    def rows(q, k, v):
        """A masked softmax over whole rows, a block of queries at a time."""
        g, block = h // hk, min(512, t)
        kr, vr = (jnp.repeat(x, g, axis=2) for x in (k, v))

        @jax.checkpoint
        def one(xs):
            qb, sel = xs
            z = jnp.einsum("bqhd,bkhd->bhqk", qb, kr,
                           preferred_element_type=jnp.float32) * d ** -0.5
            p = jax.nn.softmax(jnp.where(sel[:, None] != 0, z, -jnp.inf), -1)
            return jnp.einsum("bhqk,bkhd->bqhd", p.astype(vr.dtype), vr)

        cut = lambda x: jnp.moveaxis(x.reshape(  # noqa: E731
            (b, t // block, block) + x.shape[2:]), 1, 0)
        o = jax.lax.map(one, (cut(q), cut(plane)))
        return jnp.moveaxis(o, 0, 1).reshape(b, t, h, d)

    found = {}
    for name, fn in (("kernel", lambda *x: under(plane)(*x)[0]), ("rows", rows)):
        both = jax.jit(lambda q, k, v, fn=fn: (fn(q, k, v), *jax.vjp(
            fn, q, k, v)[1](do)))
        first, ms, found[name] = _timed(both, (q, k, v))
        log(f"dsa: core, {name}: first call {first:.1f}s, forward + "
            f"backward {ms:.1f} ms")
    # ... and with the plane made by the program that hands it on

    def in_program(q, k, v, qi, ki, a):
        made = sparse_index.selection_plane(qi, ki, a, thr, last, topk=topk)[0]
        fn = lambda *x: under(made)(*x)[0]  # noqa: E731
        return (fn(q, k, v), *jax.vjp(fn, q, k, v)[1](do))

    found["kernel, plane in program"] = jax.jit(in_program)(q, k, v, qi, ki, a)
    for name in ("kernel", "kernel, plane in program"):
        gaps = [float(jnp.linalg.norm(f32(x) - f32(r)) / jnp.linalg.norm(f32(r)))
                for x, r in zip(found[name], found["rows"])]
        log(f"dsa: core: norm of the difference over the norm, {name} to whole "
            "rows, output and gradients q k v " + " ".join(f"{x:.3e}" for x in gaps))
        assert all(math.isfinite(x) and x <= 2e-2 for x in gaps), gaps
    # a sequence no longer than topk keeps every causal pair: under that
    # triangle the pair is the dense kernels', bit for bit where one
    # compiler makes both (interpreted, XLA:CPU contracts them differently)
    n = min(t, max(topk, bkv))   # whole blocks
    triangle = jnp.tril(jnp.ones((b, n, n), jnp.int8))
    dense = (lambda q, k, v: kernel_attention(q, k, v, d ** -0.5, None, bq, bkv, True)
             ) if interpret else causal_blockwise_attention
    short = tuple(x[:, :n] for x in (q, k, v))
    pair = [jax.jit(lambda q, k, v, fn=fn: (fn(q, k, v), *jax.vjp(fn, q, k, v)[1](
        do[:, :n])))(*short) for fn in (dense, lambda *x: under(triangle)(*x)[0])]
    gap = max(float(jnp.max(jnp.abs(f32(x) - f32(y)))) for x, y in zip(*pair))
    log(f"dsa: core under the causal triangle at {n} tokens against the dense "
        f"kernels, output and gradients: largest difference {gap:.3e}")
    assert gap <= (1e-2 if interpret else 0.0), gap
    faulthandler.cancel_dump_traceback_later()


# ------------------------------------------------------------------ serve

def phase_serve() -> None:
    """``PackedServeEngine`` on seed-initialised ViT-L weights answers a
    handful of mixed-resolution requests; the embeddings match
    ``OracleServeEngine`` on the same images, with one compile."""
    import numpy as np

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.serve import (
        OracleServeEngine,
        PackedServeEngine,
        load_serving_model,
        serve_layout_from_cfg,
    )

    cfg = get_default_config()
    apply_dot_overrides(cfg, list(SIZES["serve_overrides"]))
    t0 = time.perf_counter()
    model, params = load_serving_model(cfg)  # random init from cfg seed
    layout = serve_layout_from_cfg(cfg)
    packed = PackedServeEngine(model, params, layout, warn=False)
    oracle = OracleServeEngine(model, params, layout, mode="per_image")
    log(f"serve: {cfg.student.arch} rows={layout.rows} "
        f"row_tokens={layout.row_tokens} envelope "
        f"{layout.min_px}..{layout.max_px}px, packed compile "
        f"{packed.compile_s:.1f}s, build {time.perf_counter() - t0:.1f}s")

    rng = np.random.default_rng(0)
    images = [rng.standard_normal((h, w, 3)).astype(np.float32)
              for h, w in SIZES["serve_images_hw"]]

    def answer(engine):
        for i, im in enumerate(images):
            engine.submit(im, request_id=i)
        out, t0 = [], time.perf_counter()
        while engine.queue_len:
            out.extend(engine.flush())
        return {r.request_id: r for r in out}, time.perf_counter() - t0

    got, first_s = answer(packed)
    _, second_s = answer(packed)
    want, _ = answer(oracle)
    assert sorted(got) == sorted(want) == list(range(len(images)))
    worst = 0.0
    for i in got:
        for a, b in ((got[i].cls_feature, want[i].cls_feature),
                     (got[i].pooled_patch_feature,
                      want[i].pooled_patch_feature)):
            assert a.shape == b.shape == (model.embed_dim,), a.shape
            assert np.isfinite(a).all()
            worst = max(worst, float(np.abs(a - b).max())
                        / max(1.0, float(np.abs(b).max())))
    log(f"serve: {len(images)} requests, {packed.packs_run} packs, "
        f"first drain {first_s * 1e3:.0f} ms, second {second_s * 1e3:.0f} "
        f"ms; max rel |packed - oracle| {worst:.4f}; compiles packed "
        f"{packed.compile_count}, oracle {oracle.compile_count}")
    # bf16 weights and activations through 24 blocks in both arms; the
    # packed row pads and masks where the oracle runs each image alone
    assert worst <= 5e-2, worst
    # ONE program whatever the traffic: that is what the layout predicts
    assert packed.compile_count == 1, packed.compile_count


# -------------------------------------------------------- four-chip phase

def _collectives(hlo_text: str) -> dict:
    from dinov3_tpu.utils import hlo_collective_census

    cen = hlo_collective_census(hlo_text)
    return {"by_class": {k: v["ops"] for k, v in cen["by_class"].items()},
            "by_scope": {k: v["ops"] for k, v in cen["by_scope"].items()}}


def _run_mesh_arm(name: str, overrides: list, devices: list,
                  tag: str = "mesh") -> dict:
    """Build the ViT-L/16 setup on ``devices``, run the DEFAULT
    (async-telemetry) step a few times on the seeded global batch, and
    return its per-step metric rows plus where the state lives."""
    import gc

    import jax
    import jax.numpy as jnp

    from dinov3_tpu.configs import load_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup, put_batch

    B = int(SIZES["mesh_global_batch"])
    iters = int(SIZES["mesh_iters"])
    cfg = load_config(RECIPE, overrides=[
        *SIZES["train_overrides"],
        f"train.batch_size_per_device={B // len(devices)}", *overrides])
    batch = {k: jnp.asarray(v)
             for k, v in make_synthetic_batch(cfg, B, seed=0).items()}
    t0 = time.perf_counter()
    setup = build_train_setup(cfg, batch, devices=devices)
    plan = setup.telemetry()
    state, ring = setup.state, plan.init_ring()
    dbatch = put_batch(batch, setup.batch_shardings)
    rng = jax.random.key(cfg.train.seed + 1)
    compiled = plan.step_fn.lower(
        state, ring, dbatch, setup.scalars(0), rng).compile()
    build_s = time.perf_counter() - t0
    step_ms = []
    for i in range(iters):
        t0 = time.perf_counter()
        state, ring = compiled(state, ring, dbatch, setup.scalars(i), rng)
        jax.block_until_ready(state.step)
        step_ms.append((time.perf_counter() - t0) * 1e3)
    its, rows, _ = plan.reader().flush(ring, iters)
    assert list(its) == list(range(iters)), its
    metrics = [dict(zip(plan.metric_names, (float(x) for x in row)))
               for row in rows]
    leaves = jax.tree.leaves(state)
    n_dev = {len(x.sharding.device_set) for x in leaves}
    state_bytes = {d.id: 0 for d in devices}
    for x in leaves:
        for shard in x.addressable_shards:
            state_bytes[shard.device.id] += shard.data.nbytes
    out = {
        "name": name, "mesh": {k: v for k, v in setup.mesh.shape.items()
                               if v > 1},
        "zero3": bool(setup.zero3), "bucketed": bool(setup.bucketed),
        "metrics": metrics, "step_ms": step_ms, "build_s": build_s,
        "state_device_counts": sorted(n_dev),
        "state_bytes_per_device": state_bytes,
        # the allocator's own count; None where the backend has none
        # (the CPU rehearsal) — live-array estimates would mix in
        # whatever else the process keeps on device 0
        "bytes_in_use": {d.id: (d.memory_stats() or {}).get("bytes_in_use")
                         for d in devices},
        "collectives": _collectives(compiled.as_text()),
    }
    log(f"{tag}[{name}]: mesh {out['mesh']} zero3={out['zero3']} "
        f"bucketed={out['bucketed']} build+compile {build_s:.1f}s, "
        f"step ms {[round(x, 1) for x in step_ms]}")
    log(f"{tag}[{name}]: losses "
        f"{[round(m['total_loss'], 4) for m in metrics]}; state leaves on "
        f"{out['state_device_counts']} devices; state bytes/device "
        f"{state_bytes}; bytes_in_use {out['bytes_in_use']}")
    log(f"{tag}[{name}]: collectives {out['collectives']}")
    # free this arm's state before the next arm is built on the same chips
    del state, ring, dbatch, compiled, plan, setup, leaves
    gc.collect()
    return out


def phase_accum() -> None:
    """The step's compact iBOT rows at another shape than the trainer
    phase's: ``optim.accum_steps=2`` puts the compaction and its row
    gather inside the accumulation scan, with the buffer sized for the
    most-masked microbatch (``SSLMetaArch.masked_rows``). A step program
    can hang the chip where every rehearsal passed (PERF.md section 6,
    PR 26), so each shape the gather ships at runs here once."""
    import jax

    arm = _run_mesh_arm("accum2", ["parallel.data=1", "optim.accum_steps=2"],
                        jax.devices()[:1], tag="step")
    assert arm["mesh"] == {} and not arm["zero3"], arm
    for m in arm["metrics"]:
        assert math.isfinite(m["total_loss"]), m
        assert math.isfinite(m["ibot_loss"]), m
        assert m["ibot_rows_overflow"] == 0, m
        assert 0 < m["ibot_rows_fill"] <= 1, m
    log(f"step[accum2]: ibot_rows_fill "
        f"{[round(m['ibot_rows_fill'], 4) for m in arm['metrics']]}, "
        f"overflow 0, ibot_loss "
        f"{[round(m['ibot_loss'], 4) for m in arm['metrics']]}")


def phase_mesh(n_chips: int) -> None:
    """Only the sharded arms and what they are compared with: the same
    seeded global batch on a one-device mesh, on the pure-dp mesh
    (bucketed collectives auto-on) and on ``parallel.fsdp=N`` (ZeRO-3
    auto-on), in this one process."""
    import jax

    devices = jax.devices()[:n_chips]  # main() checked the count
    assert len(devices) == n_chips, (len(devices), n_chips)
    # The sharded arms run scanned blocks, as every zero3 recipe does:
    # the per-block weight gathers then stream inside the scan's loop
    # body, and each compiles in one to two minutes for four chips (the
    # unrolled 24-block dp program takes five). The one-device
    # reference keeps the recipe's unrolled stack — the same math — for
    # memory: compiled for a described v5e, the scanned one-device step
    # at B=8 needs 14.9 GiB of the chip's 16 GB, the unrolled one less
    # than the 12.3 GiB it needs at B=12.
    scan = "train.scan_layers=true"
    one = _run_mesh_arm("one_device", ["parallel.data=1"], devices[:1])
    arms = [
        _run_mesh_arm("dp", ["parallel.data=-1", scan], devices),
        _run_mesh_arm("fsdp", [f"parallel.fsdp={n_chips}", scan], devices),
    ]
    assert arms[0]["bucketed"] and not arms[0]["zero3"], arms[0]
    assert arms[1]["zero3"], arms[1]
    ref = one["metrics"][0]
    for arm in arms:
        assert all(math.isfinite(m["total_loss"]) for m in arm["metrics"])
        # every state leaf lives on all N devices (replicated or sharded)
        assert arm["state_device_counts"] == [n_chips], arm
        per_dev = arm["state_bytes_per_device"]
        assert min(per_dev.values()) > 0.5 * max(per_dev.values()), per_dev
        used = arm["bytes_in_use"]
        if all(v is not None for v in used.values()):
            # each device holds its share, not everything on device 0
            assert min(used.values()) > 0.5 * max(used.values()), used
        got = arm["metrics"][0]
        # First-step loss against the one-device step. The sharded and
        # the one-device programs start from the same seeded init and
        # batch (bitwise: the init leaves are equal on the CPU mesh)
        # and differ in reduction order under bf16 compute. The DINO
        # and iBOT terms are well conditioned: they are means over many
        # tokens of a bf16 computation (ulp 2^-8 = 3.9e-3), and the
        # same comparison at vit_test width on four virtual CPU devices
        # shows 0 and 1.7e-3 relative — so 1e-2. The KoLeo term is not
        # well conditioned at this recipe's init: layerscale 1e-5
        # collapses the CLS features to ~1e-5 apart, and -log of a
        # nearest-neighbour distance that small amplifies last-ulp
        # noise (measured in fp32 on the CPU mesh: the other terms
        # agree to 1e-7 while KoLeo differs by 0.7%; with layerscale 1
        # all terms agree to the last digit). So KoLeo gets an absolute
        # band and the total is reported, not pinned.
        for key in ("dino_global_crops_loss", "dino_local_crops_loss",
                    "ibot_loss"):
            rel = abs(got[key] - ref[key]) / abs(ref[key])
            log(f"mesh[{arm['name']}]: step-0 {key} {got[key]:.6f} vs "
                f"one-device {ref[key]:.6f} (rel {rel:.2e})")
            assert rel <= 1e-2, (arm["name"], key, got[key], ref[key])
        d_koleo = abs(got["koleo_loss"] - ref["koleo_loss"])
        log(f"mesh[{arm['name']}]: step-0 koleo_loss "
            f"{got['koleo_loss']:.4f} vs {ref['koleo_loss']:.4f} "
            f"(abs {d_koleo:.4f}); total {got['total_loss']:.4f} vs "
            f"{ref['total_loss']:.4f}")
        assert d_koleo <= 1.0, (arm["name"], got["koleo_loss"],
                                ref["koleo_loss"])
    # the fsdp arm's state is sharded: a quarter-ish of the dp arm's
    dp_b = max(arms[0]["state_bytes_per_device"].values())
    z3_b = max(arms[1]["state_bytes_per_device"].values())
    log(f"mesh: state bytes/device dp {dp_b} vs fsdp {z3_b}")
    assert z3_b < 0.6 * dp_b, (z3_b, dp_b)


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the sharded train arms and their "
                         "one-device comparison on a four-chip host")
    ap.add_argument("--phases", default=",".join(ONE_CHIP_PHASES),
                    help="one chip: which phases, in this order "
                         f"(default all: {','.join(ONE_CHIP_PHASES)})")
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(phases) - set(ONE_CHIP_PHASES + PART_PHASES))
    if unknown:
        raise SystemExit(f"chip_smoke: unknown phases {unknown}")

    from dinov3_tpu.utils import configure_compile_cache

    cache_dir = configure_compile_cache()
    device = require_tpu()
    if device["count"] != args.chips:
        raise SystemExit(
            f"chip_smoke: --chips {args.chips} but JAX reports "
            f"{device['count']} devices")
    import jax
    import jaxlib

    from dinov3_tpu import native

    log(f"device {device}; jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__}; compile cache {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'in-checkout default'})")
    log(f"native normalize kernel: {native.describe()}")
    log("host env: " + json.dumps({
        k: os.environ.get(k) for k in (
            "TPU_WORKER_HOSTNAMES", "TPU_ACCELERATOR_TYPE",
            "JAX_COORDINATOR_ADDRESS", "JAX_PLATFORMS")}))
    if args.chips == 1:
        run = {"trainer": phase_trainer, "accum": phase_accum,
               "kernels": phase_kernels, "serve": phase_serve,
               "lm": phase_lm, "gqa": phase_gqa, "gdn": phase_gdn,
               "dsa": phase_dsa, "sconv": phase_sconv, "moe": phase_moe,
               "moe_rows": functools.partial(phase_moe, block=False),
               "mla": phase_mla, "ssd": phase_ssd}
        for name in phases:
            run[name]()
    else:
        phase_mesh(args.chips)
    log(f"all phases passed in {time.time() - _T0:.0f}s; "
        f"cache {cache_counts()}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
