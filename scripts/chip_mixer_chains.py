"""The mixers' chains (``ops/mixer_chains.py``) alone on the chip, at the
decoder cells' shapes (the delta-rule mixers' three at 2 x 8,192 tokens of
32 heads of 128; the gated short convolution at 4 x 8,192 x 2,048
channels): each kernel pair against its plain
XLA chain (``models/decoder.py``'s arithmetic), output and every gradient
compared, forward and forward + backward timed, then the kernels at other
time blocks (information: why the shipped block stays).

    chiprun -- python3 scripts/chip_mixer_chains.py [--blocks 64,256] [--only sconv]

One process, no child; every first call of a program under a
``faulthandler`` limit of its own (a program can hang the chip where every
rehearsal passed). Lines go to stdout and to
``chiprun_out/mixer_chains/summary.log``; the last line is
``{"ok": true, ...}``. A time is of that program ALONE: in the step the
same kernels overlap with nothing either, but the plain chains fuse into
their neighbours differently there.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT_DIR = os.path.join(REPO, "chiprun_out", "mixer_chains")
FIRST_CALL_LIMIT_S = 300.0
B, T, D = 2, 8192, 128


def log(msg: str) -> None:
    print(msg, flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.log"), "a") as f:
        f.write(msg + "\n")


def cases():
    """name -> (kernel(block) -> fn, plain fn, argument shapes and types,
    which arguments are differentiated)."""
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.models.decoder import causal_depthwise_conv
    from dinov3_tpu.ops import mixer_chains as mc
    from dinov3_tpu.ops.common import l2_normalize

    bf16, f32 = jnp.bfloat16, jnp.float32
    h = 32                                   # KDA: 32 heads of 128
    hk, hv, r = 16, 32, 2                    # GDN: 16 key, 32 value heads
    per = 2 + 2 * r
    layout = lambda first, n: (first, n, per)  # noqa: E731
    conv_layouts = (layout(0, 1), layout(1, 1), layout(2, r))

    def plain_conv(x, kernel, heads, unit, eps):
        y = jax.nn.silu(causal_depthwise_conv(x.astype(f32), kernel.astype(f32)))
        y = y.reshape(B, T, heads, D)
        return (l2_normalize(y, eps=eps) if unit else y).astype(bf16)

    def plain_gdn_conv(qkvz, wq, wk, wv):
        x = qkvz.reshape(B, T, hk, per * D)
        joined = jnp.concatenate([
            x[..., :D].reshape(B, T, hk * D),
            x[..., D:2 * D].reshape(B, T, hk * D),
            x[..., 2 * D:(2 + r) * D].reshape(B, T, hv * D)], -1)
        y = jax.nn.silu(causal_depthwise_conv(
            joined.astype(f32), jnp.concatenate([wq, wk, wv], -1)))
        unit = lambda u: l2_normalize(  # noqa: E731
            u.reshape(B, T, hk, D), eps=1e-3).astype(bf16)
        return (unit(y[..., :hk * D]), unit(y[..., hk * D:2 * hk * D]),
                y[..., 2 * hk * D:].reshape(B, T, hv, D).astype(bf16))

    def plain_norm(act, eps, gate_of):
        def fn(o, gate, scale):
            ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
            y = o * jax.lax.rsqrt(ms + eps) * scale
            y = y * act(gate_of(gate).astype(f32).reshape(B, T, hv, D))
            return y.reshape(B, T, hv * D).astype(bf16)
        return fn

    def plain_decay(f, a_log, dt_bias):
        return -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            (f.astype(f32) + dt_bias).reshape(B, T, h, D))

    def plain_gated_conv(x, w):
        c = x.shape[-1] // 3
        gate, mid, u = (x[..., i * c:(i + 1) * c].astype(f32) for i in range(3))
        return (mid * causal_depthwise_conv(gate * u, w)).astype(bf16)

    plane, rows32 = ((B, T, h * D), bf16), ((B, T, hv, D), f32)
    qkvz = ((B, T, hk * per * D), bf16)
    taps = lambda n: ((4, n * D), f32)  # noqa: E731
    return {
        "kda conv_silu_norm (unit norm)": (
            lambda blk: lambda x, w: mc.conv_silu_norm(
                x, (w,), (mc.IN_ORDER,), (True,), D, block=blk)[0],
            lambda x, w: plain_conv(x, w, h, True, 1e-12),
            [plane, taps(h)], (0, 1)),
        "kda conv_silu_norm (v: no norm)": (
            lambda blk: lambda x, w: mc.conv_silu_norm(
                x, (w,), (mc.IN_ORDER,), (False,), D, block=blk)[0],
            lambda x, w: plain_conv(x, w, h, False, 1e-12),
            [plane, taps(h)], (0, 1)),
        "kda log_decay": (
            lambda blk: lambda f, a, bias: mc.log_decay(f, a, bias, block=blk),
            plain_decay, [plane, ((h,), f32), ((h * D,), f32)], (0, 1, 2)),
        "kda gated_rms_norm (sigmoid)": (
            lambda blk: lambda o, g, s: mc.gated_rms_norm(
                o, g, s, mc.IN_ORDER, "sigmoid", 1e-5, block=blk),
            plain_norm(jax.nn.sigmoid, 1e-5, lambda g: g),
            [rows32, plane, ((D,), f32)], (0, 1, 2)),
        "gdn conv_silu_norm (q, k, v of the grouping)": (
            lambda blk: lambda x, wq, wk, wv: mc.conv_silu_norm(
                x, (wq, wk, wv), conv_layouts, (True, True, False), D,
                eps=1e-3, block=blk),
            plain_gdn_conv, [qkvz, taps(hk), taps(hk), taps(hv)],
            (0, 1, 2, 3)),
        "gdn gated_rms_norm (silu, z of the grouping)": (
            lambda blk: lambda o, g, s: mc.gated_rms_norm(
                o, g, s, layout(2 + r, r), "silu", 1e-6, block=blk),
            plain_norm(jax.nn.silu, 1e-6, lambda g: g.reshape(
                B, T, hk, per * D)[..., (2 + r) * D:]),
            [rows32, qkvz, ((D,), f32)], (0, 1, 2)),
        "sconv gated_short_conv ([B ; C ; u] of 2048)": (
            lambda blk: lambda x, w: mc.gated_short_conv(x, w, block=blk),
            plain_gated_conv, [((4, T, 3 * 2048), bf16), ((3, 2048), f32)],
            (0, 1)),
    }


def _timed(fn, args, n=5):
    import jax

    faulthandler.dump_traceback_later(FIRST_CALL_LIMIT_S, exit=True)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    faulthandler.cancel_dump_traceback_later()
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return first, (time.perf_counter() - t0) / n * 1e3, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", default="",
                    help="other time blocks to time the kernels at")
    ap.add_argument("--only", default="",
                    help="run the cases whose name holds this word")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dinov3_tpu.ops import mixer_chains as mc
    from dinov3_tpu.utils import configure_compile_cache

    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU ({dev.platform}): the kernels are compiled")
    log(f"device {dev.device_kind}; time block {mc.TIME_BLOCK}; "
        f"{B} x {T} tokens, heads of {D}")
    others = [int(b) for b in args.blocks.split(",") if b]
    f32 = jnp.float32
    nbytes = lambda tree: sum(  # noqa: E731
        x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    worst = 0.0
    for name, (kernel, plain, shapes, wrt) in cases().items():
        if args.only not in name:
            continue
        keys = jax.random.split(jax.random.key(len(name)), len(shapes) + 1)
        x = [jax.random.normal(k, s, f32).astype(d)
             for k, (s, d) in zip(keys, shapes)]
        out_shape = jax.eval_shape(plain, *x)
        cts = jax.tree.map(
            lambda s: jax.random.normal(keys[-1], s.shape, f32).astype(s.dtype),
            out_shape)

        def both(fn):
            def run(x, cts):
                out, vjp = jax.vjp(
                    lambda *d: fn(*(d[wrt.index(i)] if i in wrt else x[i]
                                    for i in range(len(x)))),
                    *(x[i] for i in wrt))
                return out, vjp(cts)
            return jax.jit(run)

        # bytes a pass must move: the chain's operands and results once
        fwd_bytes = nbytes(x) + nbytes(out_shape)
        bwd_bytes = fwd_bytes + nbytes(cts) + nbytes([x[i] for i in wrt])
        found = {}
        for label, fn in (("kernel", kernel(mc.TIME_BLOCK)), ("plain", plain)):
            first, fwd_ms, _ = _timed(jax.jit(fn), x)
            first_b, both_ms, found[label] = _timed(both(fn), (x, cts))
            log(f"{name}: {label}: forward {fwd_ms:.3f} ms "
                f"({fwd_bytes / fwd_ms / 1e6:.0f} GB/s of the required "
                f"{fwd_bytes / 1e6:.0f} MB), forward + backward {both_ms:.3f} "
                f"ms ({(fwd_bytes + bwd_bytes) / both_ms / 1e6:.0f} GB/s of "
                f"{(fwd_bytes + bwd_bytes) / 1e6:.0f} MB); first calls "
                f"{first:.1f} s, {first_b:.1f} s")
        gaps = [float(jnp.linalg.norm(a.astype(f32) - w.astype(f32))
                      / jnp.maximum(jnp.linalg.norm(w.astype(f32)), 1e-30))
                for a, w in zip(jax.tree.leaves(found["kernel"]),
                                jax.tree.leaves(found["plain"]))]
        log(f"{name}: norm of the difference over the norm, kernel to plain, "
            "outputs then gradients: " + " ".join(f"{g:.2e}" for g in gaps))
        assert all(np.isfinite(g) for g in gaps) and max(gaps) <= 2e-3, gaps
        worst = max(worst, max(gaps))
        for blk in others:
            _, fwd_ms, _ = _timed(jax.jit(kernel(blk)), x)
            _, both_ms, _ = _timed(both(kernel(blk)), (x, cts))
            log(f"{name}: kernel at a block of {blk}: forward {fwd_ms:.3f} "
                f"ms, forward + backward {both_ms:.3f} ms")
    print(json.dumps({"ok": True, "device": dev.device_kind,
                      "worst_gap": worst}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
