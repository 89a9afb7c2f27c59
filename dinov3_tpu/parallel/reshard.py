"""In-memory live resharding across (mesh, arm) topologies.

A production fleet resizes and preempts: dp=8 today is dp=4 after a
maintenance drain and dp=16 after a capacity grant, and the surviving
processes should not round-trip a multi-GB ZeRO-3 opt state through
disk to change layout. "Memory-efficient array redistribution through
portable collective communication" (arXiv 2112.01075) shows a mesh
reshape is a short program of collectives; GSPMD (arXiv 2105.04663)
already speaks the spec-to-spec form — an input committed to the source
``NamedSharding`` constrained to the target ``NamedSharding`` lowers to
exactly that collective program. This module packages the whole train
state that way:

- ``TopologyDesc`` names one side of a transition: mesh + opt-state arm
  (replicated / bucketed / zero3 / unified) + the state's
  ``NamedSharding`` tree (+ the ``BucketPlan`` when the arm needs one).
  ``topology_of(setup)`` derives it from a ``TrainSetup``.
- ``reshard_state(state, src, dst)`` moves a live ``TrainState`` from
  ``src`` to ``dst`` as ONE jitted collective program per leaf-group
  (params / adam-mu / adam-nu / rest), each under its own ``reshard_*``
  named scope so the PR-13 anatomy census attributes every inserted
  collective (``unattributed`` pinned 0, no "other" leakage). Arm
  changes (model-shaped <-> bucketed moment layouts, including dp
  changes that re-pad the buckets' flat members) convert INSIDE the
  same program — reshape/pad/slice are free riders on the data movement.
- When the target mesh is a different device set (a true resize, e.g.
  dp=8 -> dp=4 on half the devices), no single XLA program can span
  both device assignments: the engine stages the arm conversion on the
  source mesh (still scoped + censused) and ships each leaf-group with
  one batched ``jax.device_put`` — still no disk round-trip.

The disk path (checkpoint.py) remains the oracle: both paths produce
bitwise-identical states (tests/test_reshard.py), which is exactly what
makes the in-memory engine safe to trust after a live resize.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any

import jax
import jax.numpy as jnp

from dinov3_tpu.parallel.sharding import replicated, update_shard_size

# the reshard scope vocabulary — one scope per leaf-group, registered in
# utils.HLO_COLLECTIVE_SCOPES so the anatomy ledger and the census
# attribute every reshard collective (docs/PARALLELISM.md)
RESHARD_SCOPES = (
    "reshard_params", "reshard_mu", "reshard_nu", "reshard_rest",
)

# opt-state arms (train/setup.py resolve_update_arm) and their
# adam-moment storage layout:
#   model  — param-shaped mu/nu (replicated arm; zero3/unified differ
#            only in PLACEMENT, which the shardings carry)
#   bucket — {bucket_name: flat [S_b]} dicts (optim.bucketed_collectives)
ARM_LAYOUT = {
    "replicated": "model",
    "zero3": "model",
    "unified": "model",
    "bucketed": "bucket",
}


@dataclasses.dataclass(frozen=True)
class TopologyDesc:
    """One side of a topology transition: mesh + arm + state placement.

    ``shardings`` is the full ``TrainState``-shaped ``NamedSharding``
    tree (``TrainSetup.state_shardings``); ``student_like`` the abstract
    student param tree (shapes only — the model-shaped canonical the
    moment-layout conversions pivot through); ``bucket_plan`` the
    ``BucketPlan`` when ``arm == "bucketed"``.
    """

    mesh: Any
    arm: str
    dp: int
    shardings: Any
    student_like: Any
    bucket_plan: Any = None

    def device_ids(self) -> tuple[int, ...]:
        return tuple(d.id for d in self.mesh.devices.flat)


def topology_of(setup) -> TopologyDesc:
    """Derive the ``TopologyDesc`` of a built ``TrainSetup`` (the state
    may be concrete or abstract — only shapes/dtypes are read)."""
    student = setup.state.params["student"]
    like = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype), student)
    return TopologyDesc(
        mesh=setup.mesh,
        arm=setup.arm,
        dp=update_shard_size(setup.mesh),
        shardings=setup.state_shardings,
        student_like=like,
        bucket_plan=getattr(setup, "bucket_plan", None),
    )


def describe_topology(t: TopologyDesc) -> dict:
    """JSON-able summary (the checkpoint sidecar + report rows)."""
    return {
        "mesh": {a: int(s) for a, s in t.mesh.shape.items() if int(s) > 1},
        "arm": t.arm,
        "dp": int(t.dp),
        "n_devices": int(t.mesh.devices.size),
    }


# ---- moment-layout conversion (traced; rides inside the programs) ----


def _moments_to_model(m, src: TopologyDesc):
    """Arm storage layout -> the model-shaped canonical."""
    from dinov3_tpu.train.fused_update import unflatten_update_leaf

    if ARM_LAYOUT[src.arm] == "model":
        return m
    # through the per-leaf flat padded intermediate
    flat = src.bucket_plan.buckets_to_flat_tree(dict(m))
    return jax.tree.map(unflatten_update_leaf, flat, src.student_like)


def _moments_from_model(m, dst: TopologyDesc):
    """Model-shaped canonical -> ``dst``'s arm storage layout."""
    from dinov3_tpu.train.fused_update import flatten_update_leaf

    if ARM_LAYOUT[dst.arm] == "model":
        return m
    flat = jax.tree.map(lambda x: flatten_update_leaf(x, dst.dp), m)
    return dst.bucket_plan.flat_tree_to_buckets(flat)


def moments_convert_needed(src: TopologyDesc, dst: TopologyDesc) -> bool:
    """Whether the adam moments change STORAGE layout (not just
    placement) across the transition. The bucket layout depends on dp
    (the members' zero padding) and on the plan itself."""
    sk, dk = ARM_LAYOUT[src.arm], ARM_LAYOUT[dst.arm]
    if sk != dk:
        return True
    if sk == "bucket":
        return (src.dp != dst.dp
                or src.bucket_plan is not dst.bucket_plan
                and [b.name for b in src.bucket_plan.buckets]
                != [b.name for b in dst.bucket_plan.buckets])
    return False


def _convert_moments(m, src: TopologyDesc, dst: TopologyDesc):
    return _moments_from_model(_moments_to_model(m, src), dst)


# ---- leaf-group split / join ----


def _split_groups(state, src: TopologyDesc, dst: TopologyDesc):
    """The four leaf-groups of a transition, each ``(scope, src_tree,
    dst_sharding_tree, convert_fn|None)``. The lowp rings ride the rest
    group only when both sides carry matching rings; otherwise they are
    dropped here and reseeded (or left None) by the caller."""
    adam = state.opt_state.adam
    convert = (
        (lambda m: _convert_moments(m, src, dst))
        if moments_convert_needed(src, dst) else None
    )
    sh = dst.shardings
    lowp_ok = _lowp_compatible(state, sh)
    rest = state._replace(
        params=(),
        opt_state=state.opt_state._replace(
            adam=adam._replace(mu=(), nu=())),
        lowp=state.lowp if lowp_ok else None,
    )
    rest_sh = sh._replace(
        params=(),
        opt_state=sh.opt_state._replace(
            adam=sh.opt_state.adam._replace(mu=(), nu=())),
        lowp=sh.lowp if lowp_ok else None,
    )
    return [
        ("reshard_params", state.params, sh.params, None),
        ("reshard_mu", adam.mu, sh.opt_state.adam.mu, convert),
        ("reshard_nu", adam.nu, sh.opt_state.adam.nu, convert),
        ("reshard_rest", rest, rest_sh, None),
    ]


def _lowp_compatible(state, dst_shardings) -> bool:
    like = getattr(dst_shardings, "lowp", None)
    have = getattr(state, "lowp", None)
    if like is None or have is None:
        return False
    a = [p for p, _ in jax.tree_util.tree_flatten_with_path(have)[0]]
    b = [p for p, _ in jax.tree_util.tree_flatten_with_path(like)[0]]
    return a == b


def _join_groups(outs) -> Any:
    """Reassemble the four group outputs into one ``TrainState``."""
    params, mu, nu, rest = (
        outs["reshard_params"], outs["reshard_mu"],
        outs["reshard_nu"], outs["reshard_rest"],
    )
    return rest._replace(
        params=params,
        opt_state=rest.opt_state._replace(
            adam=rest.opt_state.adam._replace(mu=mu, nu=nu)),
    )


# ---- the engine ----


def _tree_bytes(tree) -> int:
    return sum(
        int(x.size) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(tree)
    )


def _census_ok(census: dict, scope: str) -> bool:
    """Every collective attributed to this group's scope: nothing
    unattributed, nothing leaking into "other" or a foreign scope."""
    return (census["unattributed"] == 0
            and set(census["by_scope"]) <= {scope})


def reshard_state(
    state,
    src: TopologyDesc,
    dst: TopologyDesc,
    *,
    donate: bool = False,
    with_census: bool = True,
    tracer=None,
):
    """Move a live ``TrainState`` from ``src`` to ``dst`` in memory.

    Returns ``(new_state, report)``. ``report`` carries per-group mode
    ("jit" when one collective program covers the transfer, "transfer"
    when the device sets differ and the group ships via ``device_put``),
    wall/compile times, byte counts, and — on jit groups with
    ``with_census`` — the compiled HLO collective census with the
    zero-unattributed pin pre-checked (``census_ok``).

    ``donate=True`` donates the source buffers to the jitted programs
    (halves peak memory — the production setting; the default keeps the
    input state alive for callers that still read it). A tracer, when
    given, receives one ``reshard`` span record per group plus a
    summary record — the same JSONL stream the train loop's phase spans
    live in, so preemption/resize timelines read off one file.
    """
    from dinov3_tpu.utils import hlo_collective_census

    same_devices = src.device_ids() == dst.device_ids()
    groups = _split_groups(state, src, dst)
    outs: dict[str, Any] = {}
    report: dict[str, Any] = {
        "schema": "reshard/v1",
        "src": describe_topology(src),
        "dst": describe_topology(dst),
        "same_devices": bool(same_devices),
        "groups": {},
        "padding_warnings": [],
    }
    if (moments_convert_needed(src, dst)
            and ARM_LAYOUT[dst.arm] == "bucket"):
        # the target re-pads the buckets' flat members to ITS dp — a
        # permanent per-step tax the one-time reshard signs up for;
        # gate it (configs/config.py warn_reshard_padding live mode)
        from dinov3_tpu.configs.config import warn_reshard_padding

        report["padding_warnings"] = warn_reshard_padding(
            leaf_sizes=[
                int(math.prod(x.shape))
                for x in jax.tree.leaves(src.student_like)
            ],
            src_dp=src.dp, dst_dp=dst.dp,
        )
    for scope, tree, dst_sh, convert in groups:
        t0 = time.perf_counter()
        if same_devices:
            out, row = _jit_group(
                tree, dst_sh, scope, convert,
                donate=donate, with_census=with_census,
                census_fn=hlo_collective_census,
            )
        else:
            out, row = _transfer_group(
                tree, dst_sh, scope, convert, src,
                with_census=with_census,
                census_fn=hlo_collective_census,
            )
        row["bytes"] = _tree_bytes(out)
        row["wall_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        outs[scope] = out
        report["groups"][scope] = row
        if tracer is not None:
            tracer.emit({
                "name": "reshard", "group": scope, "mode": row["mode"],
                "t": round(time.time(), 6), "dur_ms": row["run_ms"],
                "bytes": row["bytes"],
            })
    new_state = _join_groups(outs)
    new_state = _finish_lowp(new_state, state, dst)
    report["total_run_ms"] = round(
        sum(r["run_ms"] for r in report["groups"].values()), 3)
    report["total_wall_ms"] = round(
        sum(r["wall_ms"] for r in report["groups"].values()), 3)
    report["total_bytes"] = sum(
        r["bytes"] for r in report["groups"].values())
    report["census_ok"] = all(
        r.get("census_ok", True) for r in report["groups"].values())
    if tracer is not None:
        tracer.emit({
            "name": "reshard", "group": "total",
            "mode": "jit" if same_devices else "transfer",
            "t": round(time.time(), 6),
            "dur_ms": report["total_run_ms"],
            "bytes": report["total_bytes"],
            "src": report["src"], "dst": report["dst"],
        })
    return new_state, report


def _jit_group(tree, dst_sh, scope, convert, *, donate, with_census,
               census_fn):
    """One jitted collective program: src layout in, dst layout out,
    every inserted collective under ``scope``."""

    def prog(t):
        with jax.named_scope(scope):
            if convert is not None:
                t = convert(t)
            return jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(x, s),
                t, dst_sh)

    fn = jax.jit(
        prog,
        out_shardings=dst_sh,
        donate_argnums=(0,) if donate else (),
    )
    t0 = time.perf_counter()
    lowered = fn.lower(tree)
    compiled = lowered.compile()
    compile_ms = (time.perf_counter() - t0) * 1e3
    row: dict[str, Any] = {"mode": "jit",
                           "compile_ms": round(compile_ms, 3)}
    if with_census:
        census = census_fn(compiled.as_text())
        row["census"] = {
            "by_class": {k: v["ops"]
                         for k, v in census["by_class"].items()},
            "by_scope": {k: v["ops"]
                         for k, v in census["by_scope"].items()},
            "unattributed": census["unattributed"],
        }
        row["census_ok"] = _census_ok(census, scope)
    t1 = time.perf_counter()
    out = compiled(tree)
    jax.block_until_ready(out)
    row["run_ms"] = round((time.perf_counter() - t1) * 1e3, 3)
    return out, row


def _transfer_group(tree, dst_sh, scope, convert, src: TopologyDesc, *,
                    with_census, census_fn):
    """Different device sets (a true resize): stage any arm conversion
    as a scoped program on the SOURCE mesh (replicated staging layout),
    then ship the group with one batched ``device_put`` — in memory,
    across device sets, no single-program requirement."""
    row: dict[str, Any] = {"mode": "transfer", "compile_ms": 0.0}
    if convert is not None:
        rep = replicated(src.mesh)

        def stage(t):
            with jax.named_scope(scope):
                t = convert(t)
                return jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(x, rep), t)

        fn = jax.jit(stage)
        t0 = time.perf_counter()
        compiled = fn.lower(tree).compile()
        row["compile_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        if with_census:
            census = census_fn(compiled.as_text())
            row["census"] = {
                "by_class": {k: v["ops"]
                             for k, v in census["by_class"].items()},
                "by_scope": {k: v["ops"]
                             for k, v in census["by_scope"].items()},
                "unattributed": census["unattributed"],
            }
            row["census_ok"] = _census_ok(census, scope)
        tree = compiled(tree)
    t1 = time.perf_counter()
    out = jax.device_put(tree, dst_sh)
    jax.block_until_ready(out)
    row["run_ms"] = round((time.perf_counter() - t1) * 1e3, 3)
    return out, row


def _finish_lowp(new_state, old_state, dst: TopologyDesc):
    """Reseed the lowp amax rings when ``dst`` expects rings the source
    could not supply (arm enabled mid-run, or ``amax_history_len``
    changed) — same rule the checkpoint restore uses."""
    like = getattr(dst.shardings, "lowp", None)
    if like is None:
        return new_state._replace(lowp=None)
    if new_state.lowp is not None:
        return new_state
    # shardings carry no shapes, so the engine cannot rebuild rings the
    # source never had — the checkpoint restore path (which reseeds
    # from config-shaped abstract rings) covers that transition
    raise ValueError(
        "reshard into a lowp-armed topology from a source without "
        "matching amax rings: restore through the checkpoint path "
        "(which reseeds rings), or carry a source state whose lowp "
        "ring structure matches the target's")
