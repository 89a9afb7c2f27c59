"""Async, sharded, multi-host checkpointing on orbax CheckpointManager.

(reference: dinov3_jax/checkpointer/checkpointer.py used a synchronous
``PyTreeCheckpointer`` with hand-rolled step-dir discovery and a retention
helper that never deleted anything (SURVEY.md §2.7, §2.9.3). Here orbax's
``CheckpointManager`` provides all of it natively: integer step dirs,
``max_to_keep`` + ``keep_period`` retention, async save overlapping the
next train steps, and sharded restore directly into ``NamedSharding``-
placed arrays on every host.)
"""

from __future__ import annotations

import logging
from typing import Any

import jax
import orbax.checkpoint as ocp

from dinov3_tpu.train.train_step import TrainState

logger = logging.getLogger("dinov3")


def _adapt_opt_leaf(stored, like):
    """One Adam-moment leaf: checkpoint layout -> ``state_like`` layout.

    The bucketed arm's on-disk layout (train/fused_update.py,
    ``optim.bucketed_collectives``) stores mu/nu per leaf as flat arrays
    zero-padded to a multiple of the data-axis size; the other arms
    store them param-shaped. Both directions are lossless: flat -> full
    drops the (inert, exactly-zero) padding; full -> flat re-adds zeros.
    Returns a numpy array in ``like``'s shape.
    """
    import numpy as np

    v = np.asarray(stored)
    if v.shape == tuple(like.shape):
        return v
    n_like = 1
    for d in like.shape:
        n_like *= int(d)
    if v.ndim == 1 and v.size >= n_like:
        # per-leaf flat checkpoint -> model layout
        return v[:n_like].reshape(like.shape)
    if len(like.shape) == 1 and v.size <= like.shape[0]:
        # model-layout checkpoint -> per-leaf flat layout
        flat = v.reshape(-1)
        return np.pad(flat, (0, int(like.shape[0]) - flat.size))
    raise ValueError(
        f"cannot adapt opt-state leaf of shape {v.shape} to {like.shape}"
    )


def _reseed_lowp_rings(restored, lowp_like):
    """Fresh amax-history rings for a cross-arm restore — a bf16-arm (or
    pre-lowp) checkpoint resuming into a quantized ``train.low_precision``
    run, or a changed ``amax_history_len``. Seeded from the RESTORED
    masters, the same rule fresh setups use
    (``ops.lowp.lowp_history_init``), so the first H steps quantize
    against the actual restored weights rather than stale or zero amax;
    placed onto the like-rings' shardings."""
    from dinov3_tpu.ops.lowp import lowp_history_init

    H = int(jax.tree.leaves(lowp_like)[0].shape[-1])
    fresh = {
        k: lowp_history_init(restored.params[k]["backbone"], H)
        for k in ("student", "teacher")
    }

    def put(v, like):
        sharding = getattr(like, "sharding", None)
        return jax.device_put(v, sharding) if sharding is not None else v

    return jax.tree.map(put, fresh, lowp_like)


def _bucketed_moments(state, plan) -> bool:
    """True when ``state``'s adam moments are in ``plan``'s bucket layout
    (the ``optim.bucketed_collectives`` engine,
    train/fused_update.py make_bucketed_update): a dict keyed by bucket
    name instead of the per-leaf / param-shaped trees every other arm
    carries. The on-disk format is ALWAYS per-leaf, so the bucketed arm
    converts at this boundary in both directions."""
    if plan is None:
        return False
    adam = getattr(getattr(state, "opt_state", None), "adam", None)
    mu = getattr(adam, "mu", None)
    try:
        return sorted(dict(mu).keys()) == sorted(plan.names)
    except (TypeError, ValueError):
        return False


def _flat_moment_abstract(plan):
    """Per-leaf flat padded abstract moments (one ``[padded_flat_size]``
    array a leaf) for ``plan``'s student tree — the layout bucketed moments
    persist as. Plain ShapeDtypeStructs, no sharding: the restore path
    stages them addressably and re-places them bucket-by-bucket."""
    import numpy as np

    leaves = [None] * plan.n_leaves
    for b in plan.buckets:
        for m in b.members:
            leaves[m.index] = jax.ShapeDtypeStruct(
                (m.padded,), np.dtype(b.dtype)
            )
    return jax.tree.unflatten(plan.treedef, leaves)


def _moments_to_flat(state, plan):
    """Bucket-layout state -> same state with per-leaf flat moments (the
    on-disk layout). Pure index permutation (BucketPlan layout comment),
    bitwise lossless."""
    adam = state.opt_state.adam._replace(
        mu=plan.buckets_to_flat_tree(dict(state.opt_state.adam.mu)),
        nu=plan.buckets_to_flat_tree(dict(state.opt_state.adam.nu)),
    )
    return state._replace(
        opt_state=state.opt_state._replace(adam=adam)
    )


def _opt_moment_shapes(state_like):
    """The mu leaf-shape list of ``state_like``'s opt state, or None when
    the state does not carry the scheduled-adamw ``adam.mu`` subtree."""
    adam = getattr(getattr(state_like, "opt_state", None), "adam", None)
    mu = getattr(adam, "mu", None)
    if mu is None:
        return None
    return [tuple(l.shape) for l in jax.tree.leaves(mu)]


def _replace_opt_moments(state_abstract, stored_mu, stored_nu):
    """Swap the abstract mu/nu subtrees for ones in the CHECKPOINT's
    shapes (metadata leaves -> plain ShapeDtypeStructs, no sharding: the
    stored layout has no placement in this run's mesh; orbax restores
    them addressable and ``restore`` adapts + re-places them)."""
    import numpy as np

    def abs_leaf(m):
        return jax.ShapeDtypeStruct(
            tuple(m.shape), np.dtype(getattr(m, "dtype", np.float32))
        )

    adam = state_abstract.opt_state.adam._replace(
        mu=jax.tree.map(abs_leaf, stored_mu),
        nu=jax.tree.map(abs_leaf, stored_nu),
    )
    return state_abstract._replace(
        opt_state=state_abstract.opt_state._replace(adam=adam)
    )


def pytree_restore_args(item, **kw):
    """``ocp.args.PyTreeRestore`` restoring exactly the paths present in
    ``item`` (orbax's ``partial_restore``)."""
    return ocp.args.PyTreeRestore(item, partial_restore=True, **kw)


def item_metadata_tree(manager, step: int, name: str = "state"):
    """Tree of a checkpoint item's metadata.

    A manager that has not saved in THIS process has no handler
    registered for ``name`` yet and reports the item's metadata as None
    (resume flows hit this); fall back to a throwaway manager with an
    explicit ``StandardCheckpointHandler`` registration, which resolves
    metadata without touching the caller's manager or the checkpoint.
    Returns None when the checkpoint holds no metadata for ``name``."""
    meta = manager.item_metadata(step)[name]
    if meta is None:
        reader = ocp.CheckpointManager(
            manager.directory,
            item_handlers={name: ocp.StandardCheckpointHandler()},
        )
        try:
            meta = reader.item_metadata(step)[name]
        finally:
            reader.close()
    return None if meta is None else meta.tree


class Checkpointer:
    def __init__(
        self,
        directory: str,
        max_to_keep: int = 3,
        keep_every: int | None = None,
        async_save: bool = True,
        process_group: tuple[int, ...] | None = None,
        sync_prefix: str | None = None,
        bucket_plan: Any = None,
    ):
        """``process_group``: restrict orbax's cross-host barriers to these
        process indices (multidistillation subgroups checkpoint disjoint
        students concurrently; a global barrier would interleave/deadlock
        across groups). ``sync_prefix`` keys the group's barriers apart.

        ``bucket_plan``: the run's ``BucketPlan`` when the bucketed
        collective engine is on (``TrainSetup.bucket_plan``); the train
        loop assigns it after setup (the plan needs the traced abstract
        params, the checkpointer must exist before them to announce the
        resume step). With a plan set, bucket-layout adam moments are
        converted to the per-leaf flat layout on save and back on
        restore, so on-disk checkpoints stay arm-independent."""
        import os

        self.bucket_plan = bucket_plan

        directory = os.path.abspath(directory)
        extra = {}
        create = True
        if process_group is not None:
            extra["multiprocessing_options"] = ocp.options.MultiprocessingOptions(
                primary_host=min(process_group),
                active_processes=set(process_group),
                barrier_sync_key_prefix=sync_prefix,
            )
            # orbax refuses create=True with active_processes
            os.makedirs(directory, exist_ok=True)
            create = False
        # A one-host subgroup in a multi-host runtime cannot use orbax at
        # all: its jax.Array handler refuses fully-addressable arrays
        # ("host local"), and the numpy/scalar type handlers hardcode
        # ``multihost.process_index() == 0`` for their writes
        # (orbax _src/serialization/type_handlers.py:143,217,271,334,382)
        # — a group whose primary is any other process silently writes an
        # empty checkpoint. Use a plain npz-per-step local backend there;
        # the group state is single-host by construction so no
        # coordination is needed.
        self._local = (
            process_group is not None and len(process_group) == 1
            and jax.process_count() > 1
        )
        self._directory = directory
        self._max_to_keep = max_to_keep
        self._keep_every = keep_every
        if self._local:
            self.manager = None
            return
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            keep_period=keep_every,
            enable_async_checkpointing=async_save,
            create=create,
            **extra,
        )
        self.manager = ocp.CheckpointManager(directory, options=options)

    # -------- local npz backend (one-host subgroups) --------

    # A step is resumable only once this marker exists: every byte of the
    # payload was flushed BEFORE the marker was written (write-then-
    # finalize), so a save interrupted at any point — mid-payload,
    # mid-rename, mid-marker — leaves a directory that latest_step()
    # refuses to announce, and resume falls back to the previous
    # finalized step instead of a truncated one.
    FINALIZED = "FINALIZED"

    def _local_steps(self) -> list[int]:
        import os

        if not os.path.isdir(self._directory):
            return []
        return sorted(
            int(d) for d in os.listdir(self._directory)
            if d.isdigit()
            # only this backend's layout: a pre-upgrade orbax step dir
            # must not be announced as resumable...
            and os.path.exists(os.path.join(self._directory, d, "state.npz"))
            # ...and only FINALIZED saves: an interrupted/truncated save
            # never wrote the marker
            and os.path.exists(
                os.path.join(self._directory, d, self.FINALIZED))
        )

    def _local_save(self, step: int, state: TrainState) -> bool:
        import os
        import shutil

        import numpy as np

        flat = jax.tree_util.tree_flatten_with_path(state)[0]
        arrays = {
            jax.tree_util.keystr(path): np.asarray(leaf)
            for path, leaf in flat
        }
        tmp = os.path.join(self._directory, f"tmp.{step}")
        final = os.path.join(self._directory, str(step))
        os.makedirs(tmp, exist_ok=True)
        np.savez(os.path.join(tmp, "state.npz"), **arrays)
        # finalize order: payload flushed -> marker -> rename. A kill at
        # any point leaves either a tmp.* dir (never discovered) or a
        # digit dir whose marker vouches for a complete payload.
        with open(os.path.join(tmp, self.FINALIZED), "w") as f:
            f.write(str(step))
            f.flush()
            os.fsync(f.fileno())
        if os.path.isdir(final):  # overwrite-save of the same step
            shutil.rmtree(final)
        os.rename(tmp, final)
        # retention: newest max_to_keep survive, plus every keep_every-th
        steps = self._local_steps()
        for s in steps[: -self._max_to_keep or None]:
            if self._keep_every and s % self._keep_every == 0:
                continue
            shutil.rmtree(os.path.join(self._directory, str(s)),
                          ignore_errors=True)
        return True

    def _local_restore(self, state_like, step: int, subtree: str = ""):
        import os

        import numpy as np

        with np.load(
            os.path.join(self._directory, str(step), "state.npz")
        ) as z:
            flat = jax.tree_util.tree_flatten_with_path(state_like)
            leaves = []
            for path, like in flat[0]:
                key = subtree + jax.tree_util.keystr(path)
                v = z[key]
                if v.dtype.kind == "V":
                    # npz stores ml_dtypes (bfloat16, fp8) as raw void
                    # records; the bytes are intact — reinterpret with the
                    # like-leaf's dtype
                    v = v.view(np.dtype(like.dtype))
                if (".opt_state" in key
                        and tuple(v.shape) != tuple(
                            getattr(like, "shape", v.shape))):
                    # sharded <-> replicated update-engine layouts
                    # (_adapt_opt_leaf): flat padded moments round-trip
                    # losslessly against param-shaped ones
                    v = _adapt_opt_leaf(v, like)
                if isinstance(like, jax.Array):
                    v = jax.device_put(v, like.sharding)
                leaves.append(v)
        return jax.tree_util.tree_unflatten(flat[1], leaves)

    # -------- save --------

    def save(self, step: int, state: TrainState,
             topology: dict | None = None) -> bool:
        """Async save; returns True if a save was started.

        ``topology``: JSON-able (mesh, arm) descriptor of the saving run
        (``parallel.reshard.describe_topology``) — written as a
        ``topology.json`` sidecar at the checkpoint root so an elastic
        resume can decide between the in-memory reshard path and the
        disk path, and so ``scripts/cost_reshard.py`` can report the
        transition it crossed. The on-disk STATE stays arm-independent
        regardless (per-leaf moment layout); the sidecar is advisory.
        """
        if _bucketed_moments(state, self.bucket_plan):
            # persist the per-leaf layout so any arm restores this
            # checkpoint (pure permutation, bitwise)
            state = _moments_to_flat(state, self.bucket_plan)
        if topology is not None:
            self._write_topology(step, topology)
        if self._local:
            saved = self._local_save(step, state)
        else:
            saved = self.manager.save(
                step,
                args=ocp.args.Composite(state=ocp.args.StandardSave(state)),
            )
        if saved:
            logger.info("checkpoint save started at step %d", step)
        return saved

    def _write_topology(self, step: int, topology: dict) -> None:
        import json
        import os

        if jax.process_index() != 0 and not self._local:
            return
        os.makedirs(self._directory, exist_ok=True)
        path = os.path.join(self._directory, "topology.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(dict(topology, step=int(step)), f, indent=1)
        os.replace(tmp, path)

    def saved_topology(self) -> dict | None:
        """The (mesh, arm) sidecar of the most recent save, or None for
        pre-elastic checkpoints that never wrote one."""
        import json
        import os

        path = os.path.join(self._directory, "topology.json")
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    # -------- restore --------

    def latest_step(self) -> int | None:
        """Newest FINALIZED step, or None.

        Both backends honor write-then-finalize discovery: the local-npz
        backend requires its ``FINALIZED`` marker (``_local_steps``); the
        orbax backend re-checks ``manager.all_steps()`` newest-first and
        skips any step whose directory fails the structural readability
        probe (``_orbax_step_readable``) — orbax's own tmp-dir atomic
        rename covers the common interruption, but a save killed during
        finalization (or a truncated copy/transfer) can leave a
        digit-named directory missing its item payload or metadata, and
        ``manager.latest_step()`` would happily announce it. Resume then
        lands on the newest step that can actually be restored.
        """
        if self._local:
            steps = self._local_steps()
            return steps[-1] if steps else None
        for step in sorted(self.manager.all_steps(), reverse=True):
            if self._orbax_step_readable(int(step)):
                return int(step)
        return None

    def _orbax_step_readable(self, step: int) -> bool:
        import os

        root = os.path.join(self._directory, str(step))
        if not os.path.isdir(root):
            return False
        try:
            if not ocp.utils.is_checkpoint_finalized(root):
                return False
        except ValueError:
            # orbax raises on tmp-suffixed/unfinalized layouts
            return False
        # the "state" item payload must exist and be non-empty — an
        # interrupted composite save can finalize the step dir before
        # the item directory has content
        item = os.path.join(root, "state")
        if not os.path.isdir(item) or not os.listdir(item):
            return False
        # metadata must PARSE: a truncated payload loses its manifest /
        # _METADATA and the readers raise. A checkpoint without
        # metadata (None) stays permissive — the structural checks
        # above already ran.
        try:
            item_metadata_tree(self.manager, step)
        except Exception:
            return False
        return True

    def restore(self, state_like: TrainState, step: int | None = None) -> TrainState:
        """Restore into the sharding/structure of ``state_like``.

        ``state_like`` may be the freshly initialized (sharded) state: each
        leaf is restored directly to its ``NamedSharding`` placement, no
        host-side detour (multi-host safe).

        Checkpoints cross update-engine arms in both directions: a
        replicated-arm checkpoint (param-shaped adam moments) restores
        into the bucketed arm's on-disk layout (per-leaf flat padded
        moments) and vice versa — the moment leaves are
        detected by shape against the stored metadata, restored in their
        STORED layout, and adapted losslessly (``_adapt_opt_leaf``) onto
        ``state_like``'s placement. The adapting path stages the moments
        addressably before re-placing them, so it is a single-host
        convenience; same-arm restores keep the direct sharded path.

        The ZeRO-3 arm (``parallel.zero3``) keeps every leaf in its
        MODEL shape — only the ``NamedSharding`` placement differs — so
        replicated <-> zero3 restores are pure re-placements (orbax
        restores each leaf straight into ``state_like``'s sharding; the
        local-npz backend ``device_put``s per leaf) and need no shape
        adaptation at all; per-leaf flat <-> zero3 crossings ride
        the same ``_adapt_opt_leaf`` flat/full path as flat <->
        replicated. Round-trips and resume determinism across the arms
        are pinned in tests/test_zero3.py and tests/test_ckpt_zero3.py.

        The bucketed arm (``optim.bucketed_collectives``) carries its
        moments as {bucket_name: flat} dicts — a different TREE, not
        just different shapes — but persists them per-leaf (``save``
        above): one flat padded array a leaf, whatever the plan.
        Restoring INTO a bucketed run restores
        against the per-leaf on-disk layout first (riding the same
        ``_adapt_opt_leaf`` machinery when the checkpoint came from a
        replicated/zero3 arm) and re-buckets at the end
        (``_rebucket_moments`` — pure permutation + per-bucket
        device_put). Pinned in tests/test_buckets.py.

        Checkpoints also cross ``train.low_precision`` arms: the lowp
        amax-history rings (``TrainState.lowp``) restore directly when
        the checkpoint carries matching rings; a bf16-arm / pre-lowp
        checkpoint restoring into a quantized run (or an
        ``amax_history_len`` change) gets FRESH rings reseeded from the
        restored masters (``_reseed_lowp_rings``); a lowp checkpoint
        restoring into a bf16 run discards the on-disk rings
        (``state_like.lowp is None``; orbax insists every stored subtree
        is requested, so the rings are requested abstractly from the
        stored metadata and dropped). Pinned in tests/test_lowp.py.
        """
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError("no checkpoint found")
        lowp_like = getattr(state_like, "lowp", None)
        if lowp_like is not None and self._lowp_reseed_needed(
                state_like, step):
            restored = self._restore_arms(
                state_like._replace(lowp=None), step)
            restored = restored._replace(
                lowp=_reseed_lowp_rings(restored, lowp_like))
            logger.info(
                "restored checkpoint at step %d (no matching lowp rings "
                "on disk; amax histories reseeded from the restored "
                "masters)", step)
            return restored
        if lowp_like is None:
            stored_lowp = self._stored_lowp_abstract(step)
            if stored_lowp is not None:
                # lowp checkpoint into a bf16 run: request the rings
                # abstractly (orbax refuses a request tree missing a
                # stored subtree) and drop them — the bf16 arm carries
                # no scaling state
                return self._restore_arms(
                    state_like._replace(lowp=stored_lowp), step
                )._replace(lowp=None)
        return self._restore_arms(state_like, step)

    def _stored_lowp_abstract(self, step: int):
        """Abstract (shape/dtype) tree of the lowp amax rings stored at
        ``step``, or None when the save carried none. The local npz
        backend reads only requested keys, so it never needs this."""
        if self._local:
            return None
        try:
            meta = item_metadata_tree(self.manager, step)["lowp"]
        except (KeyError, TypeError, AttributeError):
            return None
        if meta is None:
            return None
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(tuple(l.shape), l.dtype), meta)

    def _lowp_reseed_needed(self, state_like, step: int) -> bool:
        """True when ``state_like`` carries lowp rings but the checkpoint
        has none (bf16-arm / pre-lowp save) or their shapes differ
        (``amax_history_len`` changed across the restore)."""
        import numpy as np

        like_flat = jax.tree_util.tree_flatten_with_path(state_like.lowp)[0]
        if self._local:
            import os

            with np.load(
                os.path.join(self._directory, str(step), "state.npz")
            ) as z:
                for path, leaf in like_flat:
                    key = ".lowp" + jax.tree_util.keystr(path)
                    if key not in z.files or tuple(z[key].shape) != tuple(
                            leaf.shape):
                        return True
            return False
        try:
            meta = item_metadata_tree(self.manager, step)
            stored_flat = jax.tree_util.tree_flatten_with_path(
                meta["lowp"])[0]
        except (KeyError, TypeError, AttributeError):
            return True
        like_shapes = [(jax.tree_util.keystr(p), tuple(l.shape))
                       for p, l in like_flat]
        stored_shapes = [
            (jax.tree_util.keystr(p), tuple(getattr(l, "shape", ())))
            for p, l in stored_flat]
        return stored_shapes != like_shapes

    def _restore_arms(self, state_like: TrainState, step: int) -> TrainState:
        bucketed = _bucketed_moments(state_like, self.bucket_plan)
        if bucketed:
            # the like-state in the per-leaf ON-DISK layout; re-bucketed
            # after the restore below
            state_like_disk = state_like._replace(
                opt_state=state_like.opt_state._replace(
                    adam=state_like.opt_state.adam._replace(
                        mu=_flat_moment_abstract(self.bucket_plan),
                        nu=_flat_moment_abstract(self.bucket_plan),
                    )
                )
            )
        else:
            state_like_disk = state_like
        if self._local:
            restored = self._local_restore(state_like_disk, step)
            if bucketed:
                restored = self._rebucket_moments(restored, state_like)
            logger.info("restored checkpoint at step %d (local npz)", step)
            return restored
        abstract = jax.tree.map(
            # the flat moment stand-ins are already abstract (and have
            # no sharding for orbax to convert)
            lambda x: (x if isinstance(x, jax.ShapeDtypeStruct)
                       else ocp.utils.to_shape_dtype_struct(x)),
            state_like_disk,
        )
        adapt = False
        like_shapes = _opt_moment_shapes(state_like_disk)
        if like_shapes is not None:
            try:
                meta = item_metadata_tree(self.manager, step)
                stored_mu = meta["opt_state"]["adam"]["mu"]
                stored_nu = meta["opt_state"]["adam"]["nu"]
                stored_shapes = [tuple(l.shape)
                                 for l in jax.tree.leaves(stored_mu)]
            except (KeyError, TypeError, AttributeError):
                # metadata unresolvable: same-arm restores still work;
                # a true cross-arm restore will fail loudly at
                # shape-intersection time below
                stored_shapes = like_shapes
            if stored_shapes != like_shapes:
                abstract = _replace_opt_moments(abstract, stored_mu, stored_nu)
                adapt = True
        restored = self.manager.restore(
            step,
            args=ocp.args.Composite(state=ocp.args.StandardRestore(abstract)),
        )["state"]
        if adapt:
            adam_like = state_like_disk.opt_state.adam

            def put(stored, like):
                v = _adapt_opt_leaf(stored, like)
                sharding = getattr(like, "sharding", None)
                return (jax.device_put(v, sharding)
                        if sharding is not None else jax.numpy.asarray(v))

            adam = restored.opt_state.adam._replace(
                mu=jax.tree.map(put, restored.opt_state.adam.mu,
                                adam_like.mu),
                nu=jax.tree.map(put, restored.opt_state.adam.nu,
                                adam_like.nu),
            )
            restored = restored._replace(
                opt_state=restored.opt_state._replace(adam=adam)
            )
        if bucketed:
            restored = self._rebucket_moments(restored, state_like)
            logger.info(
                "restored checkpoint at step %d (opt moments re-bucketed "
                "from the per-leaf on-disk layout%s)", step,
                ", cross-arm adapted" if adapt else "")
            return restored
        if adapt:
            logger.info(
                "restored checkpoint at step %d (opt-state layout adapted "
                "across update-engine arms)", step)
            return restored
        logger.info("restored checkpoint at step %d", step)
        return restored

    def _rebucket_moments(self, restored, state_like):
        """Per-leaf flat moments (the on-disk layout, possibly just
        cross-arm adapted above) -> ``state_like``'s bucket layout and
        placement. Host-side concat + per-bucket device_put — the same
        single-host staging convenience as the cross-arm adapt path."""
        import numpy as np

        plan = self.bucket_plan
        adam_like = state_like.opt_state.adam

        def put_buckets(flat_tree, like_m):
            like_m = dict(like_m)
            buckets = plan.flat_tree_to_buckets(
                jax.tree.map(np.asarray, flat_tree)
            )
            out = {}
            for name in plan.names:
                sharding = getattr(like_m[name], "sharding", None)
                out[name] = (
                    jax.device_put(buckets[name], sharding)
                    if sharding is not None
                    else jax.numpy.asarray(buckets[name])
                )
            return out

        adam = restored.opt_state.adam._replace(
            mu=put_buckets(restored.opt_state.adam.mu, adam_like.mu),
            nu=put_buckets(restored.opt_state.adam.nu, adam_like.nu),
        )
        return restored._replace(
            opt_state=restored.opt_state._replace(adam=adam)
        )

    def wait_until_finished(self) -> None:
        if self._local:
            return
        self.manager.wait_until_finished()

    def restore_params_only(
        self, state_like: TrainState, step: int | None = None
    ) -> TrainState:
        """Restore only ``params`` (fresh optimizer/centers/step) — the
        high-res-adapt / fine-tune entry (reference hrft.checkpoint_path,
        ssl_default_config.yaml)."""
        import orbax.checkpoint as ocp

        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError("no checkpoint found")
        if self._local:
            params = self._local_restore(
                state_like.params, step, subtree=".params"
            )
            logger.info(
                "restored params-only checkpoint at step %d (local npz)", step
            )
            return state_like._replace(params=params)
        abstract = jax.tree.map(
            ocp.utils.to_shape_dtype_struct, state_like.params
        )
        restored = self.manager.restore(
            step,
            args=ocp.args.Composite(
                state=pytree_restore_args({"params": abstract})
            ),
        )
        logger.info("restored params-only checkpoint at step %d", step)
        return state_like._replace(params=restored["state"]["params"])

    def close(self) -> None:
        if self._local:
            return
        self.manager.wait_until_finished()
        self.manager.close()
