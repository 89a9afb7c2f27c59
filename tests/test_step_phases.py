"""The training step's compute phases (utils.STEP_PHASES): named scopes
that reach the compiled program as op_name metadata and nothing else.

(a) the default step's compiled text holds every phase the default
    configuration reaches, and the student backbone's backward stamp;
(b) with the phase helper patched to a no-op the optimized HLO, its
    metadata removed, is the same text: tracing costs nothing when
    nobody reads it;
(c) ``classify_step_phase`` on the op_name forms the compiler emits;
(d) the benchmark's copy of the vocabulary (benchmark/phases.json) names
    only phases the program has;
(e) the persistent compile cache, under ``configure_compile_cache``'s
    settings, never serves a scope-less executable to a scoped program;
and the operator's trace window (telemetry/spans.py): tracer levels by
platform, the monotonic start on every span, the fence on profile_stop.
"""

import contextlib
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from dinov3_tpu.utils import (
    LM_STEP_PHASES,
    STEP_PHASES,
    classify_step_phase,
    step_phase,
)
from test_fused_update import smol_cfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what the default configuration reaches: no Gram teacher before the
# gram phase of a recipe (gram.use_loss=false), and none of the phases
# only a decoder's next-token step opens (tests/test_lm_decoder.py)
REACHED = tuple(p for p in STEP_PHASES
                if p != "gram_teacher" and p not in LM_STEP_PHASES)


def _compiled_step_text() -> str:
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    # stochastic depth on, so that the RNG plan has something to draw
    cfg = smol_cfg(["student.drop_path_rate=0.1"])
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, 4, seed=0).items()}
    setup = build_train_setup(cfg, batch, devices=jax.devices()[:1],
                              init_state=False)
    plan = setup.telemetry()
    args = (setup.state, jax.eval_shape(plan.init_ring), batch,
            setup.scalars(0), jax.random.key(0))
    with setup.mesh:
        return plan.step_fn.lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def step_text():
    return _compiled_step_text()


@pytest.fixture(scope="module")
def step_text_without_phases():
    import dinov3_tpu.train.ssl_meta_arch as arch
    import dinov3_tpu.train.train_step as train_step

    mp = pytest.MonkeyPatch()
    try:
        for module in (arch, train_step):
            mp.setattr(module, "step_phase",
                       lambda name: contextlib.nullcontext())
        return _compiled_step_text()
    finally:
        mp.undo()


def _op_names(text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', text)


# ---------------- (a) ----------------

def test_compiled_step_holds_every_reached_phase(step_text):
    found = {classify_step_phase(n) for n in _op_names(step_text)}
    phases = {p for p, _ in found}
    assert phases - {None} == set(REACHED), phases
    assert "transpose(jvp(student_backbone))" in step_text
    # forward and backward of what is differentiated, forward only of
    # what is not
    for phase in ("student_backbone", "student_heads", "losses"):
        assert {(phase, "fwd"), (phase, "bwd")} <= found, phase
    for phase in ("teacher_backbone", "teacher_targets", "update",
                  "rng_plan", "telemetry_ring"):
        assert (phase, "fwd") in found and (phase, "bwd") not in found, phase
    # the inner loss scopes, under ``losses``
    for inner in ("dino_loss", "ibot_loss", "koleo_loss"):
        assert any(f"losses)/{inner}/" in n or f"losses/{inner}/" in n
                   for n in _op_names(step_text)), inner


def test_step_phase_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="STEP_PHASES"):
        step_phase("student_backbon")


# ---------------- (b) ----------------

def _without_metadata(text: str) -> list:
    """The instruction lines of a module's text, ``metadata={...}``
    removed (op_name and the stack-frame index are all a scope changes)."""
    body = re.sub(r",?\s*metadata=\{[^{}]*\}", "", text)
    # computation headers and instructions; not the tables of file names
    # and stack frames that the metadata indexes
    return [ln for ln in body.splitlines()
            if ln.lstrip().startswith(("%", "ROOT %", "ENTRY %"))]


def test_phases_are_metadata_only(step_text, step_text_without_phases):
    # the control: only telemetry/ring.py's own scope is left
    assert {classify_step_phase(n)[0] for n in _op_names(
        step_text_without_phases)} == {None, "telemetry_ring"}
    a = _without_metadata(step_text)
    b = _without_metadata(step_text_without_phases)
    assert len(a) == len(b)
    differing = [(x, y) for x, y in zip(a, b) if x != y]
    assert not differing, differing[:3]


# ---------------- (c) ----------------

@pytest.mark.parametrize("op_name, want", [
    # forward
    ("jit(telemetry_step)/jvp(student_backbone)/DinoVisionTransformer/"
     "blocks_1/attn/dot_general", ("student_backbone", "fwd")),
    # backward
    ("jit(telemetry_step)/transpose(jvp(student_backbone))/"
     "DinoVisionTransformer/blocks_1/norm2/add_any",
     ("student_backbone", "bwd")),
    # under while/body, inside an inner loss scope
    ("jit(telemetry_step)/transpose(jvp(losses))/ibot_loss/while/body/"
     "dynamic_slice", ("losses", "bwd")),
    # recomputation under remat, on the backward path
    ("jit(step)/transpose(jvp(student_backbone))/while/body/closed_call/"
     "checkpoint/rematted_computation/blk/tanh", ("student_backbone", "bwd")),
    ("jit(step)/checkpoint(jvp(student_backbone))/blk/tanh",
     ("student_backbone", "fwd")),
    # a collective scope nested in update: the outermost phase wins
    ("jit(telemetry_step)/update/bucket_pack/reduce_scatter",
     ("update", "fwd")),
    # a name that merely HOLDS a phase's name is not that phase
    ("jit(telemetry_step)/jvp()/state_params__student____ibot_head/"
     "student_backbone_features/add", (None, "fwd")),
    ("jit(telemetry_step)/jit(update)/mul", (None, "fwd")),
    # no op_name at all
    (None, (None, "fwd")),
    ("", (None, "fwd")),
])
def test_classify_step_phase(op_name, want):
    assert classify_step_phase(op_name) == want


# ---------------- (d) ----------------

def test_benchmark_vocabulary_is_the_programs():
    with open(os.path.join(REPO, "benchmark", "phases.json")) as f:
        bench = json.load(f)
    named = set(bench["phases"]) | set(bench["inner"])
    named |= {p for sums in bench["metrics"].values() for p, _ in sums}
    assert named <= set(STEP_PHASES), named - set(STEP_PHASES)
    assert all(d in ("fwd", "bwd")
               for sums in bench["metrics"].values() for _, d in sums)


# ---------------- (e) ----------------

_CACHE_SCRIPT = textwrap.dedent("""
    import contextlib, json, sys
    import jax, jax.numpy as jnp
    from dinov3_tpu.utils import configure_compile_cache, step_phase

    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    hits = []
    jax.monitoring.register_event_listener(
        lambda event, **_: hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)

    def make(scope):
        # both programs come from these lines: they differ by the scope only
        def f(x):
            with scope("student_backbone"):
                return jnp.tanh(x) * 2.0
        return f

    x = jnp.ones((8, 8))
    out = {"cache_dir": cache_dir}
    for label, scope in (("plain", lambda name: contextlib.nullcontext()),
                         ("scoped", step_phase), ("scoped_again", step_phase)):
        jax.clear_caches()
        before = len(hits)
        text = jax.jit(make(scope)).lower(x).compile().as_text()
        out[label] = {"hit": len(hits) > before,
                      "scope": "student_backbone" in text}
    print(json.dumps(out))
""")


def test_cache_never_serves_a_scope_less_executable(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=REPO)
    proc = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["cache_dir"] == str(tmp_path / "cache")
    assert os.listdir(tmp_path / "cache"), "the cache was not written"
    assert out["plain"] == {"hit": False, "scope": False}
    # the scoped twin of a cached program: a miss, and it carries the scope
    assert out["scoped"] == {"hit": False, "scope": True}
    # found again, scope and all
    assert out["scoped_again"] == {"hit": True, "scope": True}


# ---------------- the operator's trace window ----------------

@pytest.mark.parametrize("platform, host_level", [("tpu", 0), ("cpu", None)])
def test_profile_window_tracer_levels_and_fence(tmp_path, monkeypatch,
                                                platform, host_level):
    """On a TPU the window is opened with the host and Python tracers
    off; on the CPU with the profiler's defaults. Every span carries
    its monotonic start; profile_stop carries the fence."""
    from dinov3_tpu.telemetry.spans import SpanTracer

    calls = {}

    class Dev:
        pass

    dev = Dev()
    dev.platform = platform
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: calls.update(
            dir=d, options=profiler_options))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.update(stopped=True))
    defaults = jax.profiler.ProfileOptions()
    tracer = SpanTracer(str(tmp_path), profile_steps=(1, 2),
                        profile_dir=str(tmp_path / "trace"))
    state = type("S", (), {"params": {"w": jnp.ones(())}})()
    import time

    t_before = time.perf_counter()
    for it in range(3):
        tracer.profile_step_begin(it)
        with tracer.span("dispatch", it):
            pass
        tracer.profile_step_end(it, state)
    tracer.close()
    assert calls["dir"] == str(tmp_path / "trace") and calls["stopped"]
    got = calls["options"]
    if host_level is None:
        assert got.host_tracer_level == defaults.host_tracer_level
        assert got.python_tracer_level == defaults.python_tracer_level
    else:
        assert got.host_tracer_level == 0 and got.python_tracer_level == 0
    spans = [json.loads(ln) for ln in open(tracer.spans_path)]
    by_name = {s["name"]: s for s in spans}
    assert {"profile_start", "dispatch", "profile_stop"} <= set(by_name)
    for s in spans:
        if s["name"] in ("dispatch", "profile_start"):
            assert t_before <= s["t_mono"] <= time.perf_counter()
    stop = by_name["profile_stop"]
    assert by_name["profile_start"]["t_mono"] <= stop["fence_mono"]
    assert stop["fence_mono"] <= time.perf_counter()
    # the spans of the window lie before the fence on the same clock
    inside = [s for s in spans if s["name"] == "dispatch"
              and s["iteration"] in (1, 2)]
    assert inside and all(s["t_mono"] <= stop["fence_mono"] for s in inside)
