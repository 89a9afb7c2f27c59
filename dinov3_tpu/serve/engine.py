"""The serve engines: one compiled fixed-shape step vs shape-keyed jit.

``PackedServeEngine`` (the default, ``serve.continuous_packing``) runs
every pack through ONE ahead-of-time compiled program — the batcher
(batcher.py) absorbs all shape raggedness on the host, so after the
single build-time compile the replay never traces again (compile count
pinned at 1 in tests/test_serve.py and SERVE_r14.json). Its output
planes live in a donated on-device ring (the PR-6 telemetry-ring
pattern, ``serve_ring`` named scope): the step writes each pack's
[R, S, D] CLS/pooled planes in place at a rotating slot, and the host
reads one slot back per pack through the counted ``blocking_fetch``
funnel (telemetry/host_sync.py) — so the host-blocked time per request
in the bench records is measured, not estimated.

``OracleServeEngine`` (behind ``serve.continuous_packing=false``) is
the naive reference: the per-batch-shape re-jit the repo's eval path
had before this engine. Two modes — ``per_image`` (one forward per
request, the feature-equivalence oracle) and ``rectangular`` (requests
grouped by resolution per flush window, batch rows padded to a
power of two to bound the shape census) — both reading features off the
standard ``__call__`` forward. Packed-vs-oracle feature equivalence is
pinned within bf16 tolerance in tests/test_serve.py.
"""

from __future__ import annotations

import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from dinov3_tpu.serve.batcher import ContinuousBatcher, PackPlan, ServeLayout
from dinov3_tpu.serve.quant import dequantize_tree, is_quantized_tree
from dinov3_tpu.serve.types import ServeRequest, ServeResponse


class ServeRing(NamedTuple):
    """Donated output planes: [depth, R, S, D] f32 CLS and pooled-patch
    features, plus a [depth, 4] per-pack stats row (token occupancy,
    segment count, pad tokens, step stamp — SERVE_STATS_FIELDS) the
    observability plane reads in the SAME fetch as the features, so
    device-side serve stats cost zero extra blocking syncs. Depth 2 =
    double buffering — slot t is fetched while the buffers for slot t+1
    are already owned by the next dispatch.

    ``patch`` is the per-token plane ([depth, R, N, D] f32): the full
    patch-normed rows of the packed forward, written only when the
    engine is built with ``patch_features=True`` (the serve-backed
    distillation teacher needs per-token features for the iBOT loss,
    not just the mean pool). With patch features off the plane is
    zero-width ([depth, R, 0, D]) so the ring's pytree structure — and
    with it the donation contract of the ONE compiled program — is
    identical across both arms."""

    cls: jnp.ndarray
    pooled: jnp.ndarray
    patch: jnp.ndarray
    stats: jnp.ndarray


# field order of the ServeRing.stats row — shared with the observer
# (telemetry/serve_obs.py) and the host/device agreement census
# (scripts/obs_report.py)
SERVE_STATS_FIELDS = ("tokens_used", "n_segments", "pad_tokens", "stamp")


def make_serve_ring(depth: int, rows: int, n_slots: int, embed_dim: int,
                    patch_tokens: int = 0):
    shape = (depth, rows, n_slots, embed_dim)
    return ServeRing(cls=jnp.zeros(shape, jnp.float32),
                     pooled=jnp.zeros(shape, jnp.float32),
                     patch=jnp.zeros((depth, rows, patch_tokens, embed_dim),
                                     jnp.float32),
                     stats=jnp.zeros((depth, len(SERVE_STATS_FIELDS)),
                                     jnp.float32))


def make_serve_step(model, n_slots: int, patch_features: bool = False):
    """The jitted serve step: packed planes -> per-segment features,
    written in place into the donated ring at ``slot``.

    Extraction (``serve_extract`` scope): each segment's CLS row is
    gathered from the cls-normed plane at its host-recorded position;
    the pooled patch feature is a masked mean over the patch-normed
    plane (one [R, S, N] x [R, N, D] einsum — no per-segment slicing,
    so the program stays fixed-shape whatever the segment layout).

    The stats row (``serve_ring`` scope) is computed from the SAME seg
    planes the forward consumed — occupancy/segment counts as the
    device saw them, not as the host planned them — and written beside
    the features, so the observability plane's one-fetch discipline
    holds (ISSUE 11 tentpole (b): stats ride the existing ring fetch).
    ``stamp`` is the host's pack counter echoed through the device, the
    freshness check that the fetched slot belongs to the pack the host
    thinks it does."""

    def step(params, ring, patches, coords, prefix_idx, seg, cls_index,
             slot, stamp):
        # int8 trees (serve/quant.py QuantLeaf) expand to bf16 INSIDE
        # the compiled program — dequant is fused ahead of the matmuls,
        # the host holds only codes + scales, and the census attributes
        # any expansion copies to "serve" (utils.classify_copy). A
        # dense tree passes through untouched.
        with jax.named_scope("serve_dequant"):
            params = dequantize_tree(params)
        out = model.apply({"params": params}, patches, coords, prefix_idx,
                          seg, method="packed_feature_forward")
        with jax.named_scope("serve_extract"):
            cls_rows = out["cls_rows"].astype(jnp.float32)
            patch_rows = out["patch_rows"].astype(jnp.float32)
            cls = jnp.take_along_axis(cls_rows, cls_index[..., None], axis=1)
            is_patch = (prefix_idx < 0) & (seg >= 0)
            sel = ((seg[:, None, :] == jnp.arange(n_slots)[None, :, None])
                   & is_patch[:, None, :]).astype(jnp.float32)
            pooled = jnp.einsum("rsn,rnd->rsd", sel, patch_rows)
            counts = sel.sum(-1)
            pooled = pooled / jnp.maximum(counts, 1.0)[..., None]
        patch_plane = ring.patch
        if patch_features:
            # distillation fan-out: the full patch-normed rows land in
            # the ring beside the CLS/pooled planes — the SAME forward,
            # the same one-fetch discipline, just a wider payload. The
            # scope attributes any GSPMD copies/reshards this write
            # induces to the fan-out in the collective census.
            with jax.named_scope("distill_fanout"):
                patch_plane = jax.lax.dynamic_update_slice(
                    ring.patch, patch_rows[None], (slot, 0, 0, 0))
        with jax.named_scope("serve_ring"):
            tokens_used = (seg >= 0).sum().astype(jnp.float32)
            n_segments = (counts > 0).sum().astype(jnp.float32)
            budget = jnp.float32(seg.shape[0] * seg.shape[1])
            stats_row = jnp.stack([
                tokens_used, n_segments, budget - tokens_used,
                stamp.astype(jnp.float32)])
            ring = ServeRing(
                cls=jax.lax.dynamic_update_slice(
                    ring.cls, cls[None], (slot, 0, 0, 0)),
                pooled=jax.lax.dynamic_update_slice(
                    ring.pooled, pooled[None], (slot, 0, 0, 0)),
                patch=patch_plane,
                stats=jax.lax.dynamic_update_slice(
                    ring.stats, stats_row[None], (slot, 0)),
            )
        return ring

    return step


class PackedServeEngine:
    """Continuous-packing engine: ragged traffic, one compiled program."""

    def __init__(self, model, params, layout: ServeLayout,
                 flush_ms: float = 10.0, ring_depth: int = 2,
                 warn: bool = True, patch_features: bool = False):
        from dinov3_tpu.configs.config import (
            serve_pad_waste_floor,
            warn_serve_pad_waste,
        )

        self.model = model
        self.params = params
        self.layout = layout
        # int8 trees carry QuantLeaf kernels (serve/quant.py); the arm
        # label and dtype ride every bench record (_fleet_summary)
        self.weights_dtype = "int8" if is_quantized_tree(params) else "bf16"
        self.arm = ("packed_int8" if self.weights_dtype == "int8"
                    else "packed")
        self.batcher = ContinuousBatcher(layout, flush_ms=flush_ms)
        self.ring_depth = int(ring_depth)
        # per-token feature serving (serve.patch_features / the
        # distillation TeacherServer): the ring grows a [depth, R, N, D]
        # patch plane and every response carries its token span
        self.patch_features = bool(patch_features)
        self._slot = 0
        self._ring = make_serve_ring(
            self.ring_depth, layout.rows, layout.max_segments_per_row,
            model.embed_dim,
            patch_tokens=layout.row_tokens if self.patch_features else 0)
        if warn:
            floor = serve_pad_waste_floor(
                layout.row_tokens, layout.patch_size, layout.n_prefix,
                layout.min_px, layout.max_px)
            # key on the envelope MEAN: the worst single resolution is
            # an adversarial mix (reported in floor["waste"] and pinned
            # per measured mix by bench_serve.py), not a config bug
            warn_serve_pad_waste(
                floor["mean_waste"],
                axis=f"serve row budget over the {layout.min_px}.."
                     f"{layout.max_px}px envelope (uniform mix; worst "
                     f"single resolution {floor['px']}px wastes "
                     f"{floor['waste']:.0%})")
        # the one compile: AOT lower + compile at build, so serving can
        # never silently re-trace (a mismatched plane shape is an error,
        # not a second program)
        step = make_serve_step(model, layout.max_segments_per_row,
                               patch_features=self.patch_features)
        jitted = jax.jit(step, donate_argnums=(1,))
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (self.params, self._ring) + self._abstract_planes())
        t0 = time.perf_counter()
        self._compiled = jitted.lower(*abstract).compile()
        self.compile_s = time.perf_counter() - t0
        self._compile_count = 1
        self.packs_run = 0
        self.last_pad_waste: float | None = None
        self._waste_used = 0
        self._waste_total = 0
        # observability hook (telemetry/serve_obs.ServeObserver or
        # None): admission + per-pack phase timings flow through it;
        # the engine itself never blocks on its account
        self.observer = None

    def _abstract_planes(self):
        L = self.layout
        p = L.patch_size
        return (
            jnp.zeros((L.rows, L.row_tokens, p, p, L.in_chans), jnp.float32),
            jnp.zeros((L.rows, L.row_tokens, 2), jnp.float32),
            jnp.zeros((L.rows, L.row_tokens), jnp.int32),
            jnp.zeros((L.rows, L.row_tokens), jnp.int32),
            jnp.zeros((L.rows, L.max_segments_per_row), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        )

    @property
    def compile_count(self) -> int:
        return self._compile_count

    def compiled_text(self) -> str:
        """Optimized HLO of the one serve program (census input)."""
        return self._compiled.as_text()

    @property
    def mean_pad_waste(self) -> float | None:
        """Padding fraction over ALL packs since the last reset — the
        deployment-relevant number. ``last_pad_waste`` is one pack's,
        and the trailing pack of a drained queue is usually partial."""
        if not self._waste_total:
            return None
        return 1.0 - self._waste_used / self._waste_total

    def reset_pad_stats(self) -> None:
        self._waste_used = 0
        self._waste_total = 0

    # ---------------- serving ----------------

    def submit(self, image, request_id: int, arrival_s: float = 0.0,
               slo: str = "default") -> None:
        req = ServeRequest(
            request_id=request_id, image=np.asarray(image, np.float32),
            arrival_s=arrival_s, slo=slo)
        self.batcher.admit(req)
        if self.observer is not None:
            h, w = req.hw
            self.observer.on_admit(request_id, slo,
                                   self.layout.seq_len(h, w), h, w)

    @property
    def queue_len(self) -> int:
        return self.batcher.queue_len

    def should_flush(self, now: float) -> bool:
        return self.batcher.should_flush(now)

    def flush_deadline(self):
        return self.batcher.flush_deadline()

    def flush(self) -> list[ServeResponse]:
        """Run ONE pack off the queue (callers loop while queue_len)."""
        t0 = time.perf_counter()
        plan = self.batcher.next_pack()
        if plan is None:
            return []
        placement_ms = (time.perf_counter() - t0) * 1e3
        return self.run_pack(plan, placement_ms=placement_ms)

    def run_pack(self, plan: PackPlan,
                 placement_ms: float | None = None) -> list[ServeResponse]:
        from dinov3_tpu.telemetry.host_sync import blocking_fetch

        planes = plan.planes
        slot = self._slot
        self._slot = (slot + 1) % self.ring_depth
        stamp = self.packs_run
        t_disp0 = time.perf_counter()
        self._ring = self._compiled(
            self.params, self._ring,
            jnp.asarray(planes["patches"]),
            jnp.asarray(planes["coords"]),
            jnp.asarray(planes["prefix_idx"]),
            jnp.asarray(planes["seg"]),
            jnp.asarray(planes["cls_index"]),
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(stamp, jnp.int32),
        )
        t_disp1 = time.perf_counter()
        self.packs_run += 1
        self.last_pad_waste = plan.pad_waste
        self._waste_used += plan.tokens_used
        self._waste_total += self.layout.token_budget
        # ONE blocking fetch per pack — the stats row rides it, so the
        # observability plane adds zero device syncs (funnel-pinned in
        # tests/test_obs.py and the OBS artifact). The patch plane, when
        # served, rides the SAME fetch: a wider payload, not a second
        # sync.
        fetch = (self._ring.cls[slot], self._ring.pooled[slot],
                 self._ring.stats[slot])
        if self.patch_features:
            fetch = fetch + (self._ring.patch[slot],)
        fetched = blocking_fetch(fetch)
        cls, pooled, stats = fetched[:3]
        patch_plane = fetched[3] if self.patch_features else None
        t_fetch1 = time.perf_counter()
        out = []
        npfx = self.layout.n_prefix
        for pl in plan.placements:
            patch_tokens = None
            if patch_plane is not None:
                # the request's tokens are the contiguous packed span
                # [offset + n_prefix, offset + n_prefix + n_patches)
                # of its row (batcher.py plane layout)
                a = pl.offset + npfx
                patch_tokens = np.asarray(
                    patch_plane[pl.row, a:a + pl.n_patches])
            out.append(ServeResponse(
                request_id=pl.request.request_id,
                cls_feature=np.asarray(cls[pl.row, pl.slot]),
                pooled_patch_feature=np.asarray(pooled[pl.row, pl.slot]),
                n_patches=pl.n_patches,
                patch_tokens=patch_tokens,
                arrival_s=pl.request.arrival_s,
                slo=pl.request.slo,
            ))
        if self.observer is not None:
            t_done = time.perf_counter()
            dev_ms = (t_fetch1 - t_disp1) * 1e3
            self.observer.on_pack(
                plan.placement_summary(),
                {"placement": placement_ms,
                 "dispatch": (t_disp1 - t_disp0) * 1e3,
                 # device compute is fenced by the ring fetch: this is
                 # the dispatch-return -> fetch-return wall (== the
                 # host-blocked fetch here, where nothing runs between)
                 "device": dev_ms,
                 "fetch": dev_ms,
                 "extract": (t_done - t_fetch1) * 1e3},
                device_stats=dict(zip(SERVE_STATS_FIELDS,
                                      (float(v) for v in stats))),
                tokens_used=plan.tokens_used)
        return out


class OracleServeEngine:
    """Naive serving oracle: shape-polymorphic jit dispatch.

    Shares the batcher's admission/flush-deadline policy (so latency
    replays are apples-to-apples) but executes by re-jitting per batch
    shape — ``compile_count`` reads the jit cache and grows with the
    traffic's shape diversity, which is exactly the pathology the
    packed engine removes."""

    def __init__(self, model, params, layout: ServeLayout,
                 flush_ms: float = 10.0, mode: str = "rectangular",
                 patch_features: bool = False):
        if mode not in ("per_image", "rectangular"):
            raise ValueError(
                f"serve.oracle={mode!r}: expected per_image|rectangular")
        self.model = model
        self.params = params
        self.layout = layout
        self.mode = mode
        self.arm = f"oracle_{mode}"
        self.patch_features = bool(patch_features)
        self.batcher = ContinuousBatcher(layout, flush_ms=flush_ms)
        self.packs_run = 0
        self.last_pad_waste = 0.0
        self._waste_used = 0
        self._waste_total = 0
        self.observer = None

        def feats(p, x):
            out = model.apply({"params": p}, x, crop_kind="global",
                              deterministic=True)
            patches = out["x_norm_patchtokens"].astype(jnp.float32)
            return (out["x_norm_clstoken"].astype(jnp.float32),
                    patches.mean(1),
                    patches if self.patch_features else None)

        self._feat = jax.jit(feats)

    @property
    def compile_count(self) -> int:
        return self._feat._cache_size()

    def submit(self, image, request_id: int, arrival_s: float = 0.0,
               slo: str = "default") -> None:
        req = ServeRequest(
            request_id=request_id, image=np.asarray(image, np.float32),
            arrival_s=arrival_s, slo=slo)
        self.batcher.admit(req)
        if self.observer is not None:
            h, w = req.hw
            self.observer.on_admit(request_id, slo,
                                   self.layout.seq_len(h, w), h, w)

    @property
    def queue_len(self) -> int:
        return self.batcher.queue_len

    def should_flush(self, now: float) -> bool:
        return self.batcher.should_flush(now)

    def flush_deadline(self):
        return self.batcher.flush_deadline()

    def flush(self) -> list[ServeResponse]:
        from dinov3_tpu.telemetry.host_sync import blocking_fetch

        t_place0 = time.perf_counter()
        reqs = self.batcher.drain()
        if not reqs:
            return []
        self.packs_run += 1
        out: list[ServeResponse] = []
        if self.mode == "per_image":
            groups = [[r] for r in reqs]
        else:
            by_hw: dict = {}
            for r in reqs:
                by_hw.setdefault(r.hw, []).append(r)
            groups = list(by_hw.values())
        placement_ms = (time.perf_counter() - t_place0) * 1e3
        used = padded = 0
        dispatch_ms = fetch_ms = 0.0
        t_run0 = time.perf_counter()
        for group in groups:
            B = len(group)
            Bp = 1 << (B - 1).bit_length() if self.mode == "rectangular" else B
            x = np.zeros((Bp,) + group[0].image.shape, np.float32)
            for i, r in enumerate(group):
                x[i] = r.image
            t0 = time.perf_counter()
            pending = self._feat(self.params, jnp.asarray(x))
            t1 = time.perf_counter()
            cls, pooled, patches = blocking_fetch(pending)
            dispatch_ms += (t1 - t0) * 1e3
            fetch_ms += (time.perf_counter() - t1) * 1e3
            seq = self.layout.seq_len(*group[0].hw)
            used += B * seq
            padded += Bp * seq
            for i, r in enumerate(group):
                out.append(ServeResponse(
                    request_id=r.request_id, cls_feature=cls[i],
                    pooled_patch_feature=pooled[i],
                    n_patches=seq - self.layout.n_prefix,
                    patch_tokens=(np.asarray(patches[i])
                                  if patches is not None else None),
                    arrival_s=r.arrival_s, slo=r.slo))
        self.last_pad_waste = 1.0 - used / padded if padded else 0.0
        self._waste_used += used
        self._waste_total += padded
        if self.observer is not None:
            t_done = time.perf_counter()
            self.observer.on_pack(
                [(r.request_id, r.slo, self.layout.seq_len(*r.hw))
                 for r in reqs],
                {"placement": placement_ms, "dispatch": dispatch_ms,
                 # the oracle has no packed stats plane; device time is
                 # the whole grouped run minus response assembly
                 "device": (t_done - t_run0) * 1e3 - dispatch_ms,
                 "fetch": fetch_ms,
                 "extract": None},
                device_stats=None, tokens_used=used, token_budget=padded)
        return out

    @property
    def mean_pad_waste(self) -> float | None:
        if not self._waste_total:
            return None
        return 1.0 - self._waste_used / self._waste_total

    def reset_pad_stats(self) -> None:
        self._waste_used = 0
        self._waste_total = 0


# ---------------- config-level construction ----------------


def serve_layout_from_cfg(cfg, model=None) -> ServeLayout:
    """serve.* config block -> static layout. ``row_tokens=auto`` sizes
    each row to hold TWO max-envelope images: bin-packing remainders
    shrink with bin size (uniform-envelope mean waste roughly halves vs
    a one-max-image row — serve_pad_waste_floor reports both), and the
    trainer's crop-packing rows set the same 2-crops-per-row precedent
    (ops/packing.py). Larger rows pack tighter still but pay O(row²)
    dense attention per pack; 2x is the elbow."""
    s = cfg.get("serve") or {}
    st = cfg.student
    p = int(st.patch_size)
    n_prefix = 1 + int(st.get("n_storage_tokens", 0) or 0)
    max_px = int(s.get("max_px", 512) or 512)
    rt = s.get("row_tokens", "auto")
    if rt in (None, "auto") or (isinstance(rt, str) and rt.lower() == "auto"):
        row_tokens = 2 * (n_prefix + (max_px // p) ** 2)
    else:
        row_tokens = int(rt)
    return ServeLayout(
        rows=int(s.get("rows", 4) or 4),
        row_tokens=row_tokens,
        n_prefix=n_prefix,
        max_segments_per_row=int(s.get("max_segments_per_row", 8) or 8),
        patch_size=p,
        in_chans=int(st.get("in_chans", 3) or 3),
        normalize=str(st.get("pos_embed_rope_normalize_coords", "separate")),
        min_px=int(s.get("min_px", 96) or 96),
        max_px=max_px,
    )


def build_serve_engine(cfg, params=None, ckpt_dir: str | None = None,
                       warn: bool = True):
    """The config-level entry: checkpoint (any opt-state arm) or params
    -> bf16 serving tree -> the configured engine arm."""
    from dinov3_tpu.configs.config import continuous_packing_wished
    from dinov3_tpu.serve.weights import load_serving_model

    from dinov3_tpu.configs.config import serve_patch_features_wished

    model, sparams = load_serving_model(cfg, ckpt_dir=ckpt_dir,
                                        params=params)
    layout = serve_layout_from_cfg(cfg, model)
    s = cfg.get("serve") or {}
    flush_ms = float(s.get("flush_ms", 10.0) or 10.0)
    patch_features = serve_patch_features_wished(cfg)
    if continuous_packing_wished(cfg):
        return PackedServeEngine(
            model, sparams, layout, flush_ms=flush_ms,
            ring_depth=int(s.get("ring_depth", 2) or 2), warn=warn,
            patch_features=patch_features)
    return OracleServeEngine(
        model, sparams, layout, flush_ms=flush_ms,
        mode=str(s.get("oracle", "rectangular") or "rectangular"),
        patch_features=patch_features)
