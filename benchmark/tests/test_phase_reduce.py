"""``phase_reduce`` on a small trace in the layout a v5e trace has under JAX
0.9 — event names without metadata, the modules' ``HloProto`` in the plane
``/host:metadata``, a ``while`` event with its body's events inside it —
and the CPU rehearsal of a traced pretrain run printing the six phase
metrics. The trace is written here in protobuf wire format by a dozen
lines, so that the reader's own walk of that format is what is tested."""

from __future__ import annotations

import json

import pytest

MS = 10**9  # picoseconds


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def msg(*fields) -> bytes:
    """``(number, int | bytes | str)`` pairs -> one serialized message."""
    out = bytearray()
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            raw = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(raw)) + raw
    return bytes(out)


def hlo_proto(module: str, op_names: dict) -> bytes:
    instructions = [
        msg((1, name), (2, "fusion"), *([(7, msg((1, "op"), (2, op))),] if op else []))
        for name, op in op_names.items()]
    computation = msg((1, "main"), *[(2, i) for i in instructions])
    return msg((1, msg((1, module), (3, computation))))


def plane(name: str, lines=(), event_names=(), protos=()) -> bytes:
    """``lines``: ``[(line name, [(metadata id, start ps, duration ps)])]``;
    ``event_names``: ``{metadata id: name}``; ``protos``: ``[(id, entry
    name, HloProto)]`` (the metadata plane's entries)."""
    fields = [(2, name)]
    for line_name, events in lines:
        fields.append((3, msg((2, line_name), (3, 1000), *[
            (4, msg((1, mid), (2, start), (3, dur))) for mid, start, dur in events])))
    for mid, ev_name in dict(event_names).items():
        fields.append((4, msg((1, mid), (2, msg((1, mid), (2, ev_name))))))
    for mid, entry, proto in protos:
        fields.append((4, msg((1, mid), (2, msg(
            (1, mid), (2, entry), (5, msg((1, 1), (6, proto))))))))
    if protos:
        fields.append((5, msg((1, 1), (2, msg((1, 1), (2, "Hlo Proto"))))))
    return msg(*fields)


STEP = "jit_telemetry_step(123)"
OTHER = "jit_convert_element_type(9)"
NAMES = {
    1: "%fusion.1 = bf16[8,128]{1,0:T(8,128)} fusion(bf16[8,128] %p0), kind=kOutput",
    2: "%while.2 = (s32[]{:T(128)}, f32[8]{0:T(1024)}) while(%tuple.1), body=%b",
    3: "%add.3 = f32[8]{0:T(1024)} add(f32[8] %state_params__student____ibot, %b)",
    4: "%copy.4 = f32[8]{0:T(1024)} copy(%c)",
    5: "%copy-done.5 = f32[8]{0:T(1024)S(1)} copy-done(%copy-start.5)",
    6: "%fusion.6 = f32[8]{0:T(1024)} fusion(%student_backbone_w), kind=kLoop",
    7: STEP,
    8: OTHER,
}
STEP_OPS = {
    "fusion.1": "jit(telemetry_step)/jvp(teacher_backbone)/blocks_0/dot_general",
    "while.2": "jit(telemetry_step)/transpose(jvp(losses))/ibot_loss/while",
    "add.3": "jit(telemetry_step)/transpose(jvp(losses))/ibot_loss/while/body/add",
    "copy.4": None,          # compiler-made, inside the while
    "copy-done.5": None,     # compiler-made, at the top level
    "fusion.6": "jit(telemetry_step)/update/bucket_pack/mul",
}
# another module has an instruction of the same name under another phase
OTHER_OPS = {"fusion.1": "jit(convert)/jvp(student_backbone)/convert_element_type"}


def one_step(t0: int) -> list:
    """Ops of one 10 ms step from ``t0`` (ps): the while covers 2..8 ms and
    holds add.3 (2.5..4.5) and copy.4 (5..7)."""
    return [(1, t0, 2 * MS), (2, t0 + 2 * MS, 6 * MS),
            (3, t0 + int(2.5 * MS), 2 * MS), (4, t0 + 5 * MS, 2 * MS),
            (5, t0 + 8 * MS, 1 * MS), (6, t0 + 9 * MS, 1 * MS)]


@pytest.fixture
def xplane(tmp_path):
    ops = one_step(0) + one_step(20 * MS) + [(1, 40 * MS, 1 * MS)]
    modules = [(7, 0, 10 * MS), (7, 20 * MS, 10 * MS), (8, 40 * MS, 1 * MS)]
    space = msg(
        (1, plane("/device:TPU:0", lines=[("XLA Modules", modules), ("XLA Ops", ops),
                                          ("Steps", [])], event_names=NAMES)),
        (1, plane("/device:TPU:0 SparseCore")),
        (1, plane("/host:metadata", protos=[
            (123, STEP, hlo_proto("jit_telemetry_step", STEP_OPS)),
            (9, OTHER, hlo_proto("jit_convert_element_type", OTHER_OPS))])),
        (1, plane("/host:CPU")))
    d = tmp_path / "cell" / "plugins" / "profile" / "2026_09_27"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(space)
    return str(tmp_path)


def test_the_profiles_own_programs(xplane):
    import phase_reduce

    modules = phase_reduce.profile_modules(phase_reduce.newest_xplane(xplane))
    assert set(modules) == {STEP, OTHER}
    assert modules[STEP] == {k: v for k, v in STEP_OPS.items() if v}
    assert modules[OTHER] == OTHER_OPS


def test_self_time_by_phase(xplane):
    import phase_reduce

    t = phase_reduce.reduce_file(phase_reduce.newest_xplane(xplane), steps=2)
    assert t.n_events == 13
    ms = {k: v / t.steps * 1e3 for k, v in t.seconds.items()}
    assert ms[("teacher_backbone", "fwd", None)] == pytest.approx(2.0)
    # the while's own 2 ms + add.3's 2 ms + the nameless copy.4's 2 ms that
    # it encloses: nothing counted twice, nothing lost
    assert ms[("losses", "bwd", "ibot_loss")] == pytest.approx(6.0)
    assert ms[("update", "fwd", None)] == pytest.approx(1.0)
    # the other module's fusion.1 is its own instruction (1 ms over 2 steps)
    assert ms[("student_backbone", "fwd", None)] == pytest.approx(0.5)
    assert set(ms) == {("teacher_backbone", "fwd", None), ("update", "fwd", None),
                       ("losses", "bwd", "ibot_loss"), ("student_backbone", "fwd", None)}
    # the top-level compiler-made copy is all that is left
    assert [n for n, _ in t.top_unattributed(5)] == [
        "%copy-done.5 = f32[8] copy-done(%copy-start.5)"]
    assert t.unattributed_s / t.steps * 1e3 == pytest.approx(1.0)
    assert t.total_s / t.steps * 1e3 == pytest.approx(10.5)
    assert t.phase_s("losses", "bwd") == pytest.approx(0.012)
    labels = [r[0] for r in t.rows()]
    assert labels[0] == "losses bwd" and labels[1] == "  losses bwd / ibot_loss"
    assert labels[-1] == "(unattributed)"


def test_an_operand_that_holds_a_phases_name_is_not_read(xplane):
    """add.3's operand is ``%state_params__student____ibot`` and fusion.6's
    ``%student_backbone_w``: only the op_name's value decides."""
    import phase_reduce

    t = phase_reduce.reduce_file(phase_reduce.newest_xplane(xplane), steps=2)
    assert t.phase_s("student_backbone", "bwd") == 0.0
    assert t.phase_s("student_heads", "fwd") == 0.0


def test_a_name_with_its_metadata_is_taken_at_its_word():
    import phase_reduce

    vocab = phase_reduce.Vocabulary.load()
    assert vocab.classify(
        "jit(step)/transpose(jvp(student_backbone))/while/body/checkpoint/"
        "rematted_computation/blk/tanh") == ("student_backbone", "bwd", None)
    assert vocab.classify("jit(step)/jvp(losses)/dino_loss/while/body/mul") == (
        "losses", "fwd", "dino_loss")
    assert vocab.classify("jit(step)/jit(update)/mul") == (None, "fwd", None)
    assert vocab.classify(None) == (None, "fwd", None)


class _Run:
    def __init__(self, counters, traffic, trace):
        self.counters, self.traffic, self.trace = counters, traffic, trace


class _Reduction:
    busy_s = 0.020


def test_the_six_metrics_and_nothing_where_no_phase_is(xplane, tmp_path, monkeypatch, capsys):
    import phase_reduce
    import run

    monkeypatch.setattr(run, "TRACE_DIR", xplane)
    phase_reduce._table_once.cache_clear()
    r = _Run({"train_steps_traced": 1}, {"trace_lead_steps": 1}, _Reduction())
    got = {name: phase_reduce.metric(r, name) for name in (
        "train_teacher_ms_per_step", "train_student_fwd_ms_per_step",
        "train_student_bwd_ms_per_step", "train_heads_losses_ms_per_step",
        "train_update_ms_per_step", "train_unattributed_pct")}
    assert got == {
        "train_teacher_ms_per_step": pytest.approx(2.0),
        "train_student_fwd_ms_per_step": pytest.approx(0.5),
        "train_student_bwd_ms_per_step": 0.0,
        "train_heads_losses_ms_per_step": pytest.approx(6.0),
        "train_update_ms_per_step": pytest.approx(1.0),
        "train_unattributed_pct": pytest.approx(100 * 1.0 / 10.5)}
    out = capsys.readouterr().out
    assert out.count("all phases + unattributed = 10.500 ms/step") == 1  # logged once
    # a serve run, an untraced run: nothing to read
    assert phase_reduce.metric(_Run({}, {}, _Reduction()), "train_update_ms_per_step") is None
    assert phase_reduce.metric(r.__class__(r.counters, r.traffic, None),
                               "train_update_ms_per_step") is None
    # a program without the scopes: None, not 0, and one line that says so
    d = tmp_path / "bare" / "cell" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    # ... but for telemetry_ring, which the program had before it had phases
    bare = {k: (v.replace("teacher_backbone", "t").replace("losses", "l")
                .replace("update", "telemetry_ring") if v else v)
            for k, v in STEP_OPS.items()}
    (d / "vm.xplane.pb").write_bytes(msg(
        (1, plane("/device:TPU:0", lines=[("XLA Modules", [(7, 0, 10 * MS)]),
                                          ("XLA Ops", one_step(0))], event_names=NAMES)),
        (1, plane("/host:metadata", protos=[
            (123, STEP, hlo_proto("jit_telemetry_step", bare))]))))
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "bare"))
    phase_reduce._table_once.cache_clear()
    assert all(phase_reduce.metric(r, n) is None for n in got)
    assert capsys.readouterr().out.count("no operation carries a phase that") == 1
    phase_reduce._table_once.cache_clear()


def test_rehearsal_prints_the_six_phase_metrics(rehearsal, capsys):
    """A traced pretrain run on the CPU at test width: the six metrics are on
    the result line beside the old ones (information: a CPU is no device)."""
    import phase_reduce

    phase_reduce._table_once.cache_clear()
    out = rehearsal(capsys, "vitl16-pretrain", trace=1)
    assert out["correct"] is True, out
    six = ["train_teacher_ms_per_step", "train_student_fwd_ms_per_step",
           "train_student_bwd_ms_per_step", "train_heads_losses_ms_per_step",
           "train_update_ms_per_step", "train_unattributed_pct"]
    assert set(six) <= set(out["metrics"]), out["metrics"]
    for name in six:
        print(name, out["metrics"][name])
    assert all(out["metrics"][n]["value"] > 0 for n in six)
    assert {"train_device_ms_per_step", "train_host_ms_per_step",
            "train_device_idle_pct"} <= set(out["metrics"])
    assert out["metrics"]["train_unattributed_pct"]["value"] < 100
    json.dumps(out)
