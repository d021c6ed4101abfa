"""The reduction of the program's spans and op scopes: synthetic events
whose answers are known, a hand-encoded trace file for the scope paths,
the recorded TPU trace (a program without spans) and a trace recorded on
the CPU for the reading of the profiler's file."""
import json
import struct
from pathlib import Path

import pytest

from harness import spans, trace
from harness.spans import NO_SPAN, ScopedEvent

DATA = Path(__file__).resolve().parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"
LOGD = "jit(traced)/vmap()/while/body/repro.integrator/repro.logdensity/mul:"


def _ev(plane, line, name, start, dur, scope=""):
    return ScopedEvent(plane, line, name, float(start), float(dur), scope)


def synthetic():
    """One 100 ns call of the program: set-up 2-30 holds the fingerprint
    4-10 and an eager JAX call 14-28 whose op runs 19-21; dispatch 30-40
    (an op 30-31); the program 40-60 runs two ops with an idle gap 45-50
    inside it; collect 60-98 waits in a copy 62-80, then an op 85-90."""
    py = "python3"
    return [
        _ev(HOST, py, "bench.run_chains", 0, 100),
        _ev(HOST, py, "repro.run_chains", 2, 96),
        _ev(HOST, py, "repro.run_chains.setup", 2, 28),
        _ev(HOST, py, "repro.program.fingerprint", 4, 6),
        _ev(HOST, py, "PjitFunction(ravel)", 14, 14),
        _ev(HOST, py, "repro.run_chains.dispatch", 30, 10),
        _ev(HOST, py, "repro.run_chains.collect", 60, 38),
        _ev(HOST, py, "np.asarray(jax.Array)", 62, 18),
        _ev(HOST, py, "bench.host", 100, 5),
        _ev(DEV, "XLA Ops", "ravel", 19, 2),
        _ev(DEV, "XLA Ops", "fusion", 30, 1),
        _ev(DEV, "XLA Ops", "fused_bernoulli_logpdf", 40, 5, LOGD),
        _ev(DEV, "XLA Ops", "while", 50, 10, "jit(traced)/vmap()/while:"),
        _ev(DEV, "XLA Ops", "fusion", 52, 4,
            "jit(traced)/vmap()/repro.logdensity/add:"),
        _ev(DEV, "XLA Ops", "copy", 85, 5),
        _ev(DEV, "XLA Modules", "jit_traced(1)", 40, 20),
    ]


def test_idle_split_at_span_boundaries():
    idle = spans.reduce_program(synthetic())["idle_by_span"]
    want = {NO_SPAN: 4, "repro.run_chains.setup": 20,
            "repro.program.fingerprint": 6, "repro.run_chains.dispatch": 9,
            "repro.run_chains.collect": 33}
    assert idle == {k: pytest.approx(v * 1e-9) for k, v in want.items()}


def test_jax_events_nested_in_a_span_are_credited_to_the_span():
    # the idle gap 21-30 has its middle in the eager JAX call, which the
    # breakdown names; the program's set-up span owns it here
    gaps = dict(trace.reduce_events(synthetic())["breakdown"]["idle_gaps"])
    assert gaps["PjitFunction(ravel)"] == pytest.approx(9e-9)
    assert "repro.run_chains.setup" not in gaps
    idle = spans.reduce_program(synthetic())["idle_by_span"]
    assert "PjitFunction(ravel)" not in idle
    assert "np.asarray(jax.Array)" not in idle


def test_idle_by_span_sums_to_the_idle_outside_programs():
    events = synthetic()
    r = trace.reduce_events(events, top=1000)
    outside = sum(v for k, v in r["breakdown"]["idle_gaps"]
                  if not k.startswith("in-program "))
    assert outside == pytest.approx(72e-9)
    idle = spans.reduce_program(events)["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(outside, rel=1e-12)


def test_span_seconds_and_scope_busy():
    r = spans.reduce_program(synthetic())
    assert r["span_s"]["repro.run_chains.collect"] == {
        "wall_s": pytest.approx(38e-9), "count": 1}
    assert set(r["span_s"]) == {
        "repro.run_chains", "repro.run_chains.setup",
        "repro.program.fingerprint", "repro.run_chains.dispatch",
        "repro.run_chains.collect"}
    # 40-45 and 52-56; the integrator's scope holds only the first
    assert r["scope_busy_s"] == {"repro.logdensity": pytest.approx(9e-9),
                                 "repro.integrator": pytest.approx(5e-9)}


def test_recorded_tpu_trace_without_program_spans():
    rows = json.loads((DATA / "gaussian_fleet_tpu.json").read_text())
    events = [ScopedEvent(*row) for row in rows]
    r = spans.reduce_program(events)
    assert r["span_s"] == {} and r["scope_busy_s"] == {}
    base = trace.reduce_events(events, top=1000)
    outside = sum(v for k, v in base["breakdown"]["idle_gaps"]
                  if not k.startswith("in-program "))
    assert r["idle_by_span"] == {NO_SPAN: pytest.approx(outside)}


# a serialized XSpace, written field by field
def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _int(num, v):
    return _varint(num << 3) + _varint(v)


def _msg(num, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, metas):
    """metas: event name -> {stat name: str, or ("ref", name), or float}."""
    stat_ids = {}
    for stats in metas.values():
        for k, v in stats.items():
            stat_ids.setdefault(k, len(stat_ids) + 1)
            if isinstance(v, tuple):
                stat_ids.setdefault(v[1], len(stat_ids) + 1)
    line = _int(1, 1) + _msg(2, "XLA Ops") + _msg(
        4, _int(1, 1) + _int(2, 1000) + _int(3, 500))
    out = _int(1, 7) + _msg(2, name) + _msg(3, line)
    for i, (ev, stats) in enumerate(metas.items(), 1):
        body = _int(1, i) + _msg(2, ev) + _msg(4, ev.split(" ")[0])
        for k, v in stats.items():
            if isinstance(v, float):
                st = _int(1, stat_ids[k]) + _varint(2 << 3 | 1) \
                    + struct.pack("<d", v)
            elif isinstance(v, tuple):
                st = _int(1, stat_ids[k]) + _int(7, stat_ids[v[1]])
            else:
                st = _int(1, stat_ids[k]) + _msg(5, v)
            body += _msg(5, st)
        out += _msg(4, _int(1, i) + _msg(2, body))
    for k, i in stat_ids.items():
        out += _msg(5, _int(1, i) + _msg(2, _int(1, i) + _msg(2, k)))
    return _msg(1, out)


def test_op_scopes_reads_the_event_metadata():
    fusion = "%fusion.3 = f32[8] fusion(f32[8] %a), kind=kLoop"
    buf = _plane(HOST, {"wrapped_sine": {"tf_op": "jit(f)/sin:"}}) \
        + _plane(DEV, {
            fusion: {"flops": 8.0, "tf_op": LOGD,
                     "hlo_category": ("ref", "loop fusion")},
            "%copy.1 = f32[8] copy(f32[8] %b)": {"flops": 0.0},
            "%while.2 = (f32[]) while()": {"tf_op": ("ref", "jit(f)/w:")}})
    assert spans.op_scopes(buf) == {DEV: {
        fusion: LOGD, "%while.2 = (f32[]) while()": "jit(f)/w:"}}
    assert spans.op_scopes(buf, stat="hlo_category") == {
        DEV: {fusion: "loop fusion"}}


def test_reads_a_profiler_file(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.core.program import call_span
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    trace.start_trace(str(tmp_path))
    with TraceAnnotation("bench.run_chains"), call_span("repro.run_chains"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = spans.events_from_xspace(trace.find_xspace(str(tmp_path)))
    names = [e.name for e in events
             if e.name.startswith(("bench.", "repro."))]
    assert names == ["bench.run_chains", "repro.run_chains"]
