"""sha256 of the Mosaic modules of ``ops/causal_attention.py``'s GENERIC
pair at the shapes the cells send it, compiled for a described v5e (no chip):
the yardstick "the other cells' kernels did not change" (PERF.md section 6,
PR 46).

    JAX_PLATFORMS=cpu python scripts/causal_kernel_sha.py

For each case the backward's program (the forward rule and the backward
kernel) is compiled, each ``tpu_custom_call``'s serialized module is parsed
and printed WITHOUT debug locations (a moved or renamed Python line changes
the locations and nothing the chip runs), and hashed. Run it in two
checkouts and compare.
"""

from __future__ import annotations

import base64
import hashlib
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# [B, T, query heads, q/k width], key/value heads, value width, window
CASES = {
    "window": ((1, 16384, 28, 128), 4, 128, 4096),    # smallthinker-ep4
    "global": ((1, 16384, 28, 128), 4, 128, None),
    "gated": ((2, 8192, 16, 256), 2, 256, None),      # qwen3-next-ep16
    "heads64": ((4, 8192, 32, 64), 8, 64, None),      # lfm2-ep8
    "selected": ((1, 16384, 32, 128), 4, 128, None),  # keye-vl2-ep8
}


def module_shas(text: str) -> list:
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    out = []
    for m in re.finditer(r'"custom_call_config":\{"body":"([^"]*)"', text):
        with mlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            asm = ir.Module.parse(base64.b64decode(m.group(1))).operation.get_asm(
                enable_debug_info=False)
        out.append(hashlib.sha256(asm.encode()).hexdigest())
    return out


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dinov3_tpu.ops import causal_attention as kernels

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    for name, (q, hk, dv, window) in CASES.items():
        k, v, do = q[:2] + (hk, q[3]), q[:2] + (hk, dv), q[:3] + (dv,)
        args = [spec(q), spec(k), spec(v)]
        if name == "selected":
            args.append(spec((q[0], q[1], q[1]), jnp.int8))

            def fwd(*x):
                return kernels.kernel_attention_selected(
                    *x, q[3] ** -0.5, 512, 1024, False)[0]
        else:
            def fwd(*x, window=window):
                return kernels.kernel_attention(
                    *x, q[3] ** -0.5, window, 512, 1024, False)

        def bwd(*x, fwd=fwd):
            *x, do = x
            return jax.vjp(lambda *y: fwd(*y, *x[3:]), *x[:3])[1](do)

        text = jax.jit(bwd).lower(*args, spec(do)).compile().as_text()
        print(name, *module_shas(text), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
