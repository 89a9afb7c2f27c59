"""From a profiler trace to device seconds by phase of the training step.

The program opens a ``jax.named_scope`` around each compute phase of its
step (``dinov3_tpu/utils.py`` ``STEP_PHASES``); a scope reaches the
compiled program only as the ``op_name`` metadata of each instruction
(``jit(telemetry_step)/transpose(jvp(student_backbone))/while/body/...``).
This file finds that value for every event of the device's line ``XLA
Ops`` and sums device time by (phase, direction). It imports nothing of
the program: the names it knows are ``phases.json``'s, the benchmark's
own copy.

**Where an event's op_name comes from.** On a v5e under JAX 0.9 an event
of ``XLA Ops`` has three stats (``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier``) and its name is the
instruction's text WITHOUT its ``metadata={...}`` (my chip run, PR 25). But
the profile carries the programs it saw: the plane ``/host:metadata``
holds one entry per executed module, named like the module's events on
the line ``XLA Modules`` (``jit_telemetry_step(<fingerprint>)``), with the
module's serialized ``HloProto`` as its one stat. ``jax.profiler.
ProfileData`` does not expose that plane's entries, so ``module_op_names``
walks the protobuf wire format itself (five messages, two fields each)
down to every instruction's ``name`` and ``metadata.op_name``. An event is
joined by the ``%name`` its text starts with, inside the module whose
``XLA Modules`` event covers it. The XLA:CPU profile has the same plane
(entries ``<module>(<program_id>)``; its events carry ``hlo_op``,
``hlo_module`` and ``program_id``), so the CPU rehearsal reads the same
way. A name that does hold ``metadata={op_name="..."}`` (another runtime,
a trace written by hand) is taken at its word first.

Only the VALUE of op_name is read: split on ``/``, each component
stripped of its ``jvp(`` / ``transpose(`` / ``checkpoint(`` / ``remat(``
wrappers and compared for equality with a phase name; the outermost phase
wins; a ``transpose(`` in or before it makes the direction ``bwd``. Never
the instruction's text: its operands are called
``%state_params__student____ibot...``.

**No time is counted twice.** A ``while``, ``conditional`` or ``call``
event contains its body's events on the same line. Every event is counted
with its SELF time: its duration less the time its direct children cover.
A container's self time (the loop's own control between two body
operations) goes to the container's phase, so the sum over all phases and
the unattributed rest is the busy union that ``trace_reduce`` computes. An
event with no op_name of its own (compiler-made copies, loop counters)
takes the phase of the innermost event that encloses it in time; what
is still nameless, and what has an op_name that holds no phase, is
*unattributed*.

**Per step without a clock.** Every step in the trace file runs the same
program (``trace_lead_steps`` + ``traced_steps`` of the traffic file), so
the sums over the WHOLE file divided by that count are per-step means.
``LayerRun`` carries no path: the trace is the newest ``.xplane.pb`` under
``run.TRACE_DIR``.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import json
import os
import re

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES_JSON = os.path.join(HERE, "phases.json")

METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
_OP_NAME_IN_TEXT = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=(]+)\s*=")
_LAYOUT = re.compile(r"\{[^{}]*\}")
# one ``name(...)`` wrapper that a transformation puts around a component
# of an op_name; ``transpose`` is the backward pass's
_WRAPPER = re.compile(r"^(jvp|transpose|checkpoint|remat)\((.*)\)$")
FWD, BWD = "fwd", "bwd"


# ---- the vocabulary

@dataclasses.dataclass(frozen=True)
class Vocabulary:
    phases: frozenset
    inner: dict            # phase -> the scopes directly named inside it
    metrics: dict          # metric -> [[phase, direction], ...]
    unattributed_metric: str

    @classmethod
    @functools.cache
    def load(cls) -> "Vocabulary":
        with open(PHASES_JSON) as f:
            raw = json.load(f)
        return cls(
            phases=frozenset(raw["phases"]),
            inner={k: frozenset(v) for k, v in raw["inner"].items()},
            metrics=raw["metrics"], unattributed_metric=raw["unattributed_metric"])

    def classify(self, op_name: str | None) -> tuple:
        """``(phase | None, direction, inner scope | None)``."""
        direction, phase = FWD, None
        for comp in (op_name or "").split("/"):
            while True:
                m = _WRAPPER.match(comp)
                if m is None:
                    break
                if phase is None and m.group(1) == "transpose":
                    direction = BWD
                comp = m.group(2)
            if phase is None:
                if comp in self.phases:
                    phase = comp
            elif comp in self.inner.get(phase, ()):
                return phase, direction, comp
        return (phase, direction, None) if phase else (None, FWD, None)


# ---- the programs a profile carries (protobuf wire format, read by hand)

def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in a profile: not a protobuf this reads")


def _first(buf, number: int):
    return next((v for n, v in _fields(buf) if n == number), None)


def module_op_names(hlo_proto) -> dict:
    """instruction name -> op_name, from a serialized ``HloProto``
    (``hlo_module`` = 1; ``HloModuleProto.computations`` = 3;
    ``HloComputationProto.instructions`` = 2; ``HloInstructionProto.name``
    = 1, ``.metadata`` = 7; ``OpMetadata.op_name`` = 2)."""
    out = {}
    module = _first(hlo_proto, 1)
    for n, computation in _fields(module if module is not None else b""):
        if n != 3:
            continue
        for m, instruction in _fields(computation):
            if m != 2:
                continue
            name = metadata = None
            for k, v in _fields(instruction):
                if k == 1:
                    name = v
                elif k == 7:
                    metadata = v
            op_name = _first(metadata, 2) if metadata is not None else None
            if name is not None and op_name is not None and len(op_name):
                out[bytes(name).decode()] = bytes(op_name).decode()
    return out


def profile_modules(path: str) -> dict:
    """``{entry name: {instruction name: op_name}}`` for every module in
    the plane ``/host:metadata`` of the ``.xplane.pb`` at ``path``
    (``XSpace.planes`` = 1; ``XPlane.name`` = 2, ``.event_metadata`` = 4,
    a map entry with ``value`` = 2; ``XEventMetadata.name`` = 2, ``.stats``
    = 5; ``XStat.bytes_value`` = 6)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    try:
        for n, plane in _fields(space):
            if n != 1 or bytes(_first(plane, 2) or b"").decode() != METADATA_PLANE:
                continue
            for m, entry in _fields(plane):
                if m != 4:
                    continue
                meta = _first(entry, 2)
                if meta is None:
                    continue
                name = bytes(_first(meta, 2) or b"").decode()
                for k, stat in _fields(meta):
                    proto = _first(stat, 6) if k == 5 else None
                    if proto is not None:
                        out[name] = module_op_names(proto)
    except (ValueError, IndexError, UnicodeDecodeError) as e:
        # a layout this walk does not know: no op_names, so no phases, and
        # the readers leave their metrics out — they do not end a traced run
        from run import log

        log(f"phases: the programs in {path} could not be read ({e!r})")
        return {}
    return out


# ---- the table

@dataclasses.dataclass
class PhaseTable:
    """Device seconds over the whole trace file. ``seconds``: self time by
    ``(phase, direction, inner scope | None)``; ``unattributed``: self
    time under no phase, by operation name."""

    seconds: dict
    unattributed: dict
    steps: int
    n_events: int
    n_names: int           # distinct operations
    n_with_op_name: int    # of them, those an op_name was found for

    @property
    def unattributed_s(self) -> float:
        return sum(self.unattributed.values())

    @property
    def total_s(self) -> float:
        return sum(self.seconds.values()) + self.unattributed_s

    def phase_s(self, phase: str, direction: str) -> float:
        return sum(v for (p, d, _), v in self.seconds.items()
                   if p == phase and d == direction)

    def rows(self) -> list:
        """``[label, ms a step, share of all device time]``, largest first:
        every (phase, direction), the inner scopes under their phase."""
        total, out = self.total_s or 1.0, []
        by_pd: dict = {}
        for (p, d, inner), v in self.seconds.items():
            ent = by_pd.setdefault((p, d), [0.0, {}])
            ent[0] += v
            if inner:
                ent[1][inner] = ent[1].get(inner, 0.0) + v
        for (p, d), (v, inners) in sorted(by_pd.items(), key=lambda kv: -kv[1][0]):
            out.append([f"{p} {d}", v / self.steps * 1e3, v / total])
            out.extend([f"  {p} {d} / {i}", w / self.steps * 1e3, w / total]
                       for i, w in sorted(inners.items(), key=lambda kv: -kv[1]))
        out.append(["(unattributed)", self.unattributed_s / self.steps * 1e3,
                    self.unattributed_s / total])
        return out

    def top_unattributed(self, n: int) -> list:
        top = sorted(self.unattributed.items(), key=lambda kv: -kv[1])[:n]
        return [[_LAYOUT.sub("", k)[:160], v / self.steps * 1e3] for k, v in top]


def is_device_plane(name: str) -> bool:
    """``/device:TPU:0`` and not a sub-unit's plane such as ``/device:TPU:0
    SparseCore`` (the rule of ``trace_reduce``)."""
    return name.startswith(trace_reduce.DEVICE_PREFIX) and \
        name[len(trace_reduce.DEVICE_PREFIX):].strip().isdigit()


def _op_lines(profile) -> list:
    """``[[(name, start ns, end ns, instruction, module entry), ...], ...]``:
    the line ``XLA Ops`` of each device plane, each event with the entry of
    ``/host:metadata`` whose ``XLA Modules`` event covers it; or — a trace
    without a device plane, the CPU rehearsal's — each line of the host
    plane, cut to the events that carry an ``hlo_op`` stat (the stand-in
    ``trace_reduce`` takes too)."""
    lines = []
    for plane in profile.planes:
        if not is_device_plane(plane.name):
            continue
        by_name = {line.name: line for line in plane.lines}
        if trace_reduce.OPS_LINE not in by_name:
            continue
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in (by_name[MODULES_LINE].events
                                   if MODULES_LINE in by_name else ()))
        starts = [m[0] for m in modules]
        events = []
        for e in by_name[trace_reduce.OPS_LINE].events:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            module = modules[i][2] if i >= 0 and e.start_ns < modules[i][1] else None
            inst = _INSTRUCTION.match(e.name)
            events.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                           inst.group(1) if inst else e.name.lstrip("%"), module))
        lines.append(events)
    if lines:
        return lines
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            events = []
            for e in line.events:
                stats = dict(e.stats) if e.duration_ns > 0 else {}
                if "hlo_op" in stats:
                    events.append((
                        e.name, e.start_ns, e.start_ns + e.duration_ns, stats["hlo_op"],
                        f"{stats.get('hlo_module')}({stats.get('program_id')})"))
            if events:
                lines.append(events)
    return lines


def reduce_profile(profile, modules: dict, steps: int, vocab: Vocabulary) -> PhaseTable:
    """``profile``: a ``ProfileData``; ``modules``: ``profile_modules`` of
    the same file."""
    seconds: dict = {}
    unattributed: dict = {}
    known: dict = {}   # (event name, module) -> key, or None where nameless
    n_events = 0

    def key_of(name, instruction, module):
        ident = (name, module)
        if ident not in known:
            m = _OP_NAME_IN_TEXT.search(name)
            op_name = m.group(1) if m else modules.get(module, {}).get(instruction)
            known[ident] = None if op_name is None else vocab.classify(op_name)
        return known[ident]

    def close(entry):
        _, key, name, dur, covered = entry
        self_s = max(dur - covered, 0.0) / 1e9
        if key is not None and key[0] is not None:
            seconds[key] = seconds.get(key, 0.0) + self_s
        else:
            unattributed[name] = unattributed.get(name, 0.0) + self_s

    for events in _op_lines(profile):
        n_events += len(events)
        stack: list = []   # [end, key, name, duration, time its children cover]
        for name, start, end, instruction, module in sorted(
                events, key=lambda t: (t[1], -t[2])):
            while stack and stack[-1][0] <= start:
                close(stack.pop())
            key = key_of(name, instruction, module)
            if stack:
                parent = stack[-1]
                parent[4] += min(end, parent[0]) - start
                if key is None:          # nameless: the enclosing event's phase
                    key = parent[1]
            stack.append([end, key, name, end - start, 0.0])
        while stack:
            close(stack.pop())
    return PhaseTable(seconds=seconds, unattributed=unattributed, steps=steps,
                      n_events=n_events, n_names=len(known),
                      n_with_op_name=sum(1 for k in known.values() if k is not None))


def reduce_file(path: str, steps: int) -> PhaseTable:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), profile_modules(path), steps,
                          Vocabulary.load())


# ---- what the readers of layer_metrics/ call

def newest_xplane(root: str) -> str | None:
    files = glob.glob(os.path.join(root, "*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=1)
def _table_once(path: str, mtime: float, steps: int, device_ms) -> PhaseTable | None:
    """Reduced and logged once a run, whichever reader asks first."""
    from run import log

    table = reduce_file(path, steps)
    log(f"phases: {table.n_events} events of {path}, {steps} steps; "
        f"{table.n_with_op_name} of {table.n_names} distinct operations have an op_name")
    summed = {p for sums in Vocabulary.load().metrics.values() for p, _ in sums}
    if not any(p in summed for p, _, _ in table.seconds):
        log("phases: no operation carries a phase that a metric of phases.json "
            "sums: a program without the scopes (the parent of PR 25 has "
            "telemetry_ring alone), or an executable from a cache that was "
            "filled without them; the phase metrics are left out")
        return None
    for label, ms, share in table.rows():
        log(f"phases: {label:<44s} {ms:10.3f} ms/step {share * 100:7.2f} %")
    per_step = table.total_s / steps * 1e3
    if device_ms:
        log(f"phases: all phases + unattributed = {per_step:.3f} ms/step against "
            f"train_device_ms_per_step {device_ms:.3f}: "
            f"{(per_step / device_ms - 1) * 100:+.2f} %")
    for name, ms in table.top_unattributed(12):
        log(f"phases: unattributed {ms:8.3f} ms/step  {name}")
    return table


def table(run) -> PhaseTable | None:
    """The phase table of the traced training run ``run`` (a ``LayerRun``),
    or None where there is nothing to read: no traced training steps, no
    trace, or a trace in which no operation carries a phase."""
    traced = run.counters.get("train_steps_traced")
    if not traced or run.trace is None:
        return None
    from run import TRACE_DIR

    path = newest_xplane(TRACE_DIR)
    if path is None:
        return None
    steps = int(traced) + int(run.traffic.get("trace_lead_steps", 0))
    device_ms = run.trace.busy_s / traced * 1e3 if run.trace.busy_s > 0 else None
    return _table_once(path, os.path.getmtime(path), steps, device_ms)


def metric(run, name: str) -> float | None:
    """The per-layer metric ``name`` as ``phases.json`` defines it: ms a
    step over the phases and directions it sums, or — the unattributed
    metric — percent of all device time under no phase."""
    t = table(run)
    if t is None:
        return None
    vocab = Vocabulary.load()
    if name == vocab.unattributed_metric:
        return 100.0 * t.unattributed_s / t.total_s
    return sum(t.phase_s(p, d) for p, d in vocab.metrics[name]) / t.steps * 1e3
