"""Device time by phase of the decoder's next-token step, for the
``lm_*`` readers of ``layer_metrics/``: ``lm_phases.json`` made into a
``phase_reduce.Vocabulary``, the newest trace reduced by
``phase_reduce.reduce_profile`` — the same reduction, file format and
per-step rule as the SSL step's phase metrics, another vocabulary.

A reader that finds nothing to read (no traced training steps, no
trace, or a program without these scopes, as every commit before PR 27)
gets None and its metric is left out.
"""

from __future__ import annotations

import functools
import json
import os

import phase_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
LM_PHASES_JSON = os.path.join(HERE, "lm_phases.json")


@functools.cache
def _raw() -> dict:
    with open(LM_PHASES_JSON) as f:
        return json.load(f)


@functools.cache
def vocabulary() -> phase_reduce.Vocabulary:
    raw = _raw()
    return phase_reduce.Vocabulary(
        phases=frozenset(raw["phases"]),
        inner={k: frozenset(v) for k, v in raw["inner"].items()},
        metrics=raw["metrics"], unattributed_metric=raw["unattributed_metric"])


@functools.lru_cache(maxsize=1)
def _table_once(path: str, mtime: float, steps: int, device_ms):
    from jax.profiler import ProfileData

    from run import log

    vocab = vocabulary()
    table = phase_reduce.reduce_profile(
        ProfileData.from_file(path), phase_reduce.profile_modules(path), steps, vocab)
    summed = {p for sums in vocab.metrics.values() for p, _ in sums}
    if not any(p in summed for p, _, _ in table.seconds):
        log("lm phases: no operation carries a phase that a metric of "
            "lm_phases.json sums; the lm_* phase metrics are left out")
        return None
    for label, ms, share in table.rows():
        log(f"lm phases: {label:<44s} {ms:10.3f} ms/step {share * 100:7.2f} %")
    per_step = table.total_s / steps * 1e3
    if device_ms:
        log(f"lm phases: all phases + unattributed = {per_step:.3f} ms/step "
            f"against train_device_ms_per_step {device_ms:.3f}: "
            f"{(per_step / device_ms - 1) * 100:+.2f} %")
    for name, ms in table.top_unattributed(12):
        log(f"lm phases: unattributed {ms:8.3f} ms/step  {name}")
    return table


def table(run):
    traced = run.counters.get("train_steps_traced")
    if not traced or run.trace is None:
        return None
    from run import TRACE_DIR

    path = phase_reduce.newest_xplane(TRACE_DIR)
    if path is None:
        return None
    steps = int(traced) + int(run.traffic.get("trace_lead_steps", 0))
    device_ms = run.trace.busy_s / traced * 1e3 if run.trace.busy_s > 0 else None
    return _table_once(path, os.path.getmtime(path), steps, device_ms)


def metric(run, name: str):
    """ms a step over the phases and directions ``lm_phases.json`` sums
    under ``name``; an inner scope's ms a step (``inner_metrics``); or the
    unattributed share of all device time, percent."""
    t = table(run)
    if t is None:
        return None
    vocab, raw = vocabulary(), _raw()
    if name == vocab.unattributed_metric:
        return 100.0 * t.unattributed_s / t.total_s
    if name in raw["inner_metrics"]:
        phase, inner = raw["inner_metrics"][name]
        return sum(v for (p, _, i), v in t.seconds.items()
                   if p == phase and i == inner) / t.steps * 1e3
    return sum(t.phase_s(p, d) for p, d in vocab.metrics[name]) / t.steps * 1e3
