"""Explicit double-buffered ZeRO-3 weight stream over a block stack —
the census/schedule twin of the GSPMD streaming engine.

The default engine (parallel.zero3, train/setup.py) expresses weight
streaming through sharding annotations: the scanned block stack enters
``nn.scan`` sharded over the data axes and each block's weights are
all-gathered inside the compiled while body at use (ops/block.py
``_zero3_stream_trans_in``). WHERE the partitioner places those gathers
relative to the consuming block's compute — and whether the gather of
block i+1 overlaps block i — is then the backend scheduler's decision,
invisible in the annotation-level program.

``streamed_block_scan`` below is the same schedule written EXPLICITLY,
the convention ``make_bucketed_update_schedule`` keeps for the
bucketed update engine: a ``lax.scan`` whose carry holds the NEXT block's
already-gathered weights — iteration i issues the gather of block i+1
(named scope ``zero3_prefetch``) before running block i's compute on the
weights gathered one iteration earlier, so the compiled HLO contains the
literal double-buffered gather schedule: every in-loop all-gather except
the priming one is issued a full block of compute ahead of its consumer.
scripts/cost_zero3.py compiles this program for the committed
prefetch-overlap census (the ``prefetch_overlap`` columns of
``utils.hlo_collective_census``), and the stack it streams is the bf16
pre-cast form (``cast_stream_leaves``), so the census prices the bf16
stream the engine asks for rather than whatever dtype placement the
backend's simplifier chose. tests/test_zero3.py pins both its numerics
(bitwise vs a per-block oracle loop) and its census shape.

Liveness is the double-buffer invariant: exactly TWO gathered block
weight sets exist at any point of the forward (current + prefetched),
1/dp of everything else — the "free after use" half of the SimpleFSDP
pattern falls out of the scan carry being overwritten each iteration.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from dinov3_tpu.ops.block import stream_castable_path
from dinov3_tpu.parallel.sharding import STAGING_ORDER


def cast_stream_leaves(stack_params: Any, dtype) -> Any:
    """Cast the bf16-streamable leaves (attn/mlp matmul weights — the
    shared ``stream_castable_path`` rule) of a stacked block-param tree
    to the stream dtype, leaving fp32-consumed leaves (norm scales,
    layerscale, MoE router) untouched. Shard-local and elementwise:
    applied BEFORE the scan so the loop constant — and therefore every
    in-loop gather — is in the stream dtype by construction."""
    import jax.tree_util as jtu

    def leaf(path, p):
        if (hasattr(p, "dtype") and stream_castable_path(path)
                and jnp.issubdtype(p.dtype, jnp.floating)):
            if isinstance(p, jax.ShapeDtypeStruct):
                # abstract (compile-only accounting) form
                return jax.ShapeDtypeStruct(p.shape, dtype)
            return p.astype(dtype)
        return p

    return jtu.tree_map_with_path(leaf, stack_params)


# Gather lookahead of both stream scans: the classic double buffer
# (gather i+1 under block i's compute). Two gathered weight sets live at
# once; each extra depth keeps one more block's weights on the device.
STREAM_PREFETCH = 1


def prefetch_depth(prefetch: bool | int) -> int:
    """Normalize a ``prefetch`` argument to an integer lookahead
    depth: ``False``/0 = gather at use, ``True``/1 = the classic
    double buffer (gather i+1 under block i's compute), ``d >= 2`` = a
    ``d``-deep gather pipeline (the carry holds ``d`` gathered sets —
    liveness grows one block's weights per extra depth)."""
    depth = int(prefetch)
    if depth < 0:
        raise ValueError(f"prefetch depth must be >= 0, got {depth}")
    return depth


def streamed_block_scan(
    block_apply: Callable,
    stack_params: Any,
    x: jnp.ndarray,
    n_blocks: int,
    mesh=None,
    prefetch: bool | int = STREAM_PREFETCH,
):
    """Run ``n_blocks`` blocks over ``x`` with an explicit
    ``prefetch``-deep buffered weight stream.

    ``block_apply(block_params, x) -> x``: one block's pure apply (e.g.
    a bound ``SelfAttentionBlock.apply``). ``stack_params``: pytree of
    ``[n_blocks, ...]`` leaves, sharded over the data axes on non-layer
    dims (the zero3 layout — the per-block slice is then shard-local
    and only the materialization moves bytes). ``prefetch`` is the
    integer lookahead depth (``prefetch_depth``): depth 1 (= the old
    ``True``) is the double-buffered schedule — gather i+1 under block
    i's compute, scope ``zero3_prefetch``; depth ``d`` issues block
    i+d's gather there, giving the scheduler ``d`` blocks of compute to
    hide each gather under at the price of ``d`` live gathered weight
    sets. Depth 0 (= the old ``False``) gathers each block at use
    (scope ``zero3_stream``) — the A/B control for the overlap census.
    The gathers are pure movement, so every depth is bitwise-identical
    in values; only the wire schedule changes.
    """
    if mesh is None:
        from dinov3_tpu.parallel.context import get_current_mesh

        mesh = get_current_mesh()
    from dinov3_tpu.parallel.sharding import constrain_replicated

    depth = prefetch_depth(prefetch)

    def gather_block(i, scope):
        def leaf(p):
            s = jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False)
            return constrain_replicated(s, mesh) if mesh is not None else s

        with jax.named_scope(scope):
            return jax.tree.map(leaf, stack_params)

    if depth == 0:
        def body_at_use(x, i):
            return block_apply(gather_block(i, "zero3_stream"), x), None

        x, _ = jax.lax.scan(body_at_use, x, jnp.arange(n_blocks))
        return x

    # prime the buffer: blocks [0, depth) gathered before the loop
    buf0 = tuple(
        gather_block(jnp.asarray(min(j, n_blocks - 1)), "zero3_gather")
        for j in range(depth))

    def body(carry, i):
        x, buf = carry
        # issue block i+depth's gather BEFORE block i's compute — no
        # data dependency between them, so the scheduler can run the
        # gather under the next ``depth`` blocks of compute (the tail
        # iterations re-gather the final block into dead carry slots:
        # ``depth`` wasted gathers per pass, the price of a
        # static-shape buffer)
        w_next = gather_block(
            jnp.minimum(i + depth, n_blocks - 1), "zero3_prefetch")
        x = block_apply(buf[0], x)
        return (x, buf[1:] + (w_next,)), None

    (x, _), _ = jax.lax.scan(body, (x, buf0), jnp.arange(n_blocks))
    return x


def make_block_apply(block_kwargs: dict, rope=None, seg=None) -> Callable:
    """A deterministic single-block apply for the streamed scan:
    ``apply(block_params, x)`` binds ``SelfAttentionBlock`` with the
    model's own kwargs (pass-granularity convention of the cost
    scripts: eval-mode, no drop-path randomness)."""
    from dinov3_tpu.ops.block import SelfAttentionBlock

    block = SelfAttentionBlock(**block_kwargs)

    def apply(block_params, x):
        return block.apply(
            {"params": block_params}, x, rope, True, None, seg)

    return apply


def pack_stream_buckets(stack_params: Any, n_buckets: int, dp: int):
    """Coalesce the streamable block weights into ``n_buckets``
    equal-sized flat buckets aligned to the block-scan structure.

    ``stack_params``: pytree of stacked ``[n_blocks, ...]`` leaves (pass
    it through ``cast_stream_leaves`` first so the buckets carry the
    bf16 stream form). Bucket ``b`` holds blocks ``[b*g, (b+1)*g)``
    (``g = n_blocks / n_buckets``, which must divide): the streamable
    leaves (``ops/block.py stream_bucket_leaves`` — the same selection
    the per-block ZeRO-3 stream gathers) of those blocks, flattened and
    concatenated in tree order, zero-padded to a multiple of ``dp``.
    Every bucket is the same size (each leaf contributes ``g`` equal
    block slices), so the bucket axis scans — the double-buffer
    convention of ``streamed_block_scan`` lifts from per-block gathers
    to per-bucket gathers unchanged. Returns ``[n_buckets, S_pb]``.
    """
    from dinov3_tpu.ops.block import stream_bucket_leaves

    leaves = stream_bucket_leaves(stack_params)
    if not leaves:
        raise ValueError("stack has no streamable (attn/mlp) leaves")
    n_blocks = leaves[0][1].shape[0]
    if n_blocks % n_buckets:
        raise ValueError(
            f"n_buckets={n_buckets} must divide n_blocks={n_blocks} "
            f"(equal buckets are what makes the bucket axis scannable)"
        )
    g = n_blocks // n_buckets
    dtype = leaves[0][1].dtype
    rows = []
    for b in range(n_buckets):
        flat = jnp.concatenate([
            leaf[b * g:(b + 1) * g].reshape(-1).astype(dtype)
            for _, leaf in leaves
        ])
        rows.append(jnp.pad(flat, (0, (-flat.size) % max(1, dp))))
    return jnp.stack(rows)


def bucketed_stream_scan(
    bucket_shards: jnp.ndarray,
    x: jnp.ndarray,
    mesh=None,
    prefetch: bool | int = STREAM_PREFETCH,
    consume_fn: Callable | None = None,
    hierarchical: bool = False,
    staging_order: str = STAGING_ORDER,
):
    """The BUCKETED forward weight-gather schedule, written explicitly —
    ``streamed_block_scan``'s double-buffer convention lifted from
    per-block gathers to per-bucket gathers, as a shard_map island so
    the compiled HLO contains the literal per-bucket ``all_gather``
    (and, under ``jax.grad``, its transpose ``psum_scatter`` inside the
    BACKWARD while loop — the overlap-placement evidence
    ``utils.hlo_collective_placement`` classifies and
    tests/test_buckets.py censuses: param gathers ride the forward
    loop, the coalesced grad reduce-scatter of bucket *i* is issued as
    backward leaves bucket *i*'s consume, under bucket *i-1*'s backward
    compute).

    ``bucket_shards``: ``[n_buckets, S_pb]`` from ``pack_stream_buckets``
    (dim 1 sharded over the data axes by the in_spec). ``prefetch`` is
    the integer lookahead depth (``prefetch_depth``; booleans map to
    0/1): depth ``d >= 1`` gathers bucket i+d under bucket i's consume
    (scope ``bucket_prefetch``, priming gathers ``bucket_gather``);
    depth 0 gathers at use (scope ``bucket_stream``) — the A/B
    control. ``consume_fn(w_full, x) -> x`` consumes one gathered
    bucket; the default is a cheap reduction coupling every weight
    element into ``x`` (pass-granularity convention of the cost
    scripts — the census prices the collective schedule, not the block
    math).

    ``hierarchical=True`` replaces each flat all-gather with the
    unified engine's STAGED schedule on a dp×fsdp mesh, the tiers
    released per ``staging_order``'s AG half (parallel/sharding.py
    ``split_staging_order``; the default moves 1/dp shards over the
    slow inter links first, then intra, scopes ``bucket_ag_inter``/
    ``bucket_ag_intra`` — the RS half rides the autodiff transpose
    here), followed by an index-order-restoring reshape so the
    consumed vector is BITWISE the flat gather's device-order concat:
    the options change the wire schedule, never the numerics. With one
    present mesh tier it degrades to the flat gather unchanged.
    """
    if mesh is None:
        from dinov3_tpu.parallel.context import get_current_mesh

        mesh = get_current_mesh()
    from jax.sharding import PartitionSpec as P

    from dinov3_tpu.parallel.sharding import (
        UPDATE_SHARD_AXES,
        hierarchy_axes,
        split_staging_order,
    )

    axes = tuple(a for a in UPDATE_SHARD_AXES if a in mesh.shape)
    inter, intra = hierarchy_axes(mesh)
    staged = bool(hierarchical and inter and intra)
    ag_first, _ = split_staging_order(staging_order)
    depth = prefetch_depth(prefetch)
    n_buckets = int(bucket_shards.shape[0])
    if consume_fn is None:
        def consume_fn(w, x):
            return x + jnp.mean(w).astype(x.dtype) * x

    def body(shards, x):
        def gather(i, scope):
            s = jax.lax.dynamic_index_in_dim(shards, i, 0, keepdims=False)
            if staged:
                # staged gather, then restore flat device order: the
                # flat tiled gather concats inter-major (device-id
                # order), i.e. a [n_inter, n_intra, cols] raveling —
                # inter-first stacks [n_intra, n_inter, cols] and
                # swaps; intra-first lands inter-major directly
                if ag_first == "inter":
                    with jax.named_scope("bucket_ag_inter"):
                        g = jax.lax.all_gather(s, inter, tiled=False)
                    with jax.named_scope("bucket_ag_intra"):
                        g = jax.lax.all_gather(g, intra, tiled=False)
                    with jax.named_scope(scope):
                        return jnp.swapaxes(g, 0, 1).reshape(-1)
                with jax.named_scope("bucket_ag_intra"):
                    g = jax.lax.all_gather(s, intra, tiled=False)
                with jax.named_scope("bucket_ag_inter"):
                    g = jax.lax.all_gather(g, inter, tiled=False)
                with jax.named_scope(scope):
                    return g.reshape(-1)
            with jax.named_scope(scope):
                return jax.lax.all_gather(s, axes, tiled=True)

        if depth == 0:
            def at_use(x, i):
                return consume_fn(gather(i, "bucket_stream"), x), None

            x, _ = jax.lax.scan(at_use, x, jnp.arange(n_buckets))
            return x

        # prime the buffer: buckets [0, depth) gathered before the loop
        buf0 = tuple(
            gather(jnp.asarray(min(j, n_buckets - 1)), "bucket_gather")
            for j in range(depth))

        def step(carry, i):
            x, buf = carry
            # issue bucket i+depth's gather BEFORE consuming bucket i —
            # the streamed_block_scan lookahead, per bucket
            w_next = gather(
                jnp.minimum(i + depth, n_buckets - 1), "bucket_prefetch")
            x = consume_fn(buf[0], x)
            return (x, buf[1:] + (w_next,)), None

        (x, _), _ = jax.lax.scan(step, (x, buf0), jnp.arange(n_buckets))
        return x

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axes), P()),
        out_specs=P(),
        check_vma=False,
    )(bucket_shards, x)
