"""The trace reduction on small traces: synthetic events whose answers are
known, a trace recorded on a TPU v5e (two ``run_chains`` jobs of 64
chains of a 10,000-dimensional standard normal under static HMC, the
fused Pallas leapfrog: the device's op and module events and the Python
thread's host events, each op named by its HLO instruction), and a trace
recorded on the CPU for the reading of the profiler's file."""
import json
from pathlib import Path

import pytest

from harness import trace
from harness.trace import Event

DATA = Path(__file__).resolve().parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def synthetic():
    """One 100 ns call; ops busy 10-40 and 60-80 (a while op holding two
    ops); the host waits on a copy from 40 to 60; a module runs 55-95."""
    return [
        _ev(HOST, "python3", "bench.run_chains", 0, 100),
        _ev(HOST, "python3", "np.asarray(jax.Array)", 35, 25),
        _ev(HOST, "python3", "bench.host", 100, 5),
        _ev(DEV, "XLA Ops", "%while.3 = (f32[]) while(..)", 10, 30),
        _ev(DEV, "XLA Ops", "%fused_leapfrog.12 = (f32[64]) custom-call()",
            12, 10),
        _ev(DEV, "XLA Ops", "%fusion.7 = f32[] fusion()", 25, 5),
        _ev(DEV, "XLA Ops", "%fused_leapfrog.13 = (f32[64]) custom-call()",
            60, 20),
        _ev(DEV, "XLA Modules", "jit_traced(1)", 55, 40),
    ]


def test_synthetic_window_busy_and_self_time():
    r = trace.reduce_events(synthetic())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["op_s"]["while"] == pytest.approx(15e-9)
    assert r["op_s"]["fused_leapfrog"] == pytest.approx(30e-9)
    assert r["op_launches"]["fused_leapfrog"] == 2
    assert trace.kernel_time(r, "fused_leapfrog") == (
        pytest.approx(30e-9), 2)
    assert r["calls"] == [{"name": "run_chains",
                           "wall_s": pytest.approx(100e-9),
                           "busy_s": pytest.approx(50e-9)}]


def test_synthetic_idle_gaps_by_owner():
    gaps = dict(trace.reduce_events(synthetic())["breakdown"]["idle_gaps"])
    # 0-10 and 80-100 outside a module, 40-60 in the copy wait
    assert gaps["bench.run_chains"] == pytest.approx(10e-9)
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(20e-9)
    assert gaps["in-program jit_traced(1)"] == pytest.approx(20e-9)
    assert sum(gaps.values()) == pytest.approx(50e-9)


def test_op_name():
    assert trace.op_name("%fused_bernoulli_logpdf.10 = f32[64,1,1] "
                         "custom-call(f32[64,256,128] %a)") \
        == "fused_bernoulli_logpdf"
    assert trace.op_name("%copy-start.3 = (f32[64]) copy-start()") \
        == "copy-start"


def test_no_call_span_is_an_error():
    with pytest.raises(RuntimeError):
        trace.reduce_events([e for e in synthetic()
                             if e.name != "bench.run_chains"])


def test_recorded_tpu_trace():
    events = [Event(*row) for row in
              json.loads((DATA / "gaussian_fleet_tpu.json").read_text())]
    r = trace.reduce_events(events)
    calls = [e for e in events if e.name == "bench.run_chains"]
    assert r["window_s"] * 1e9 == pytest.approx(
        max(e.start_ns + e.dur_ns for e in calls)
        - min(e.start_ns for e in calls))
    # one vmapped launch per transition: 2 jobs x (100 + 100)
    leapfrog = [e for e in events if e.name == "fused_leapfrog"]
    assert len(leapfrog) == 400
    assert trace.kernel_time(r, "fused_leapfrog") == (
        pytest.approx(sum(e.dur_ns for e in leapfrog) * 1e-9), 400)
    # busy lies between the leaf ops' time and the programs' time
    modules = [e for e in events if e.line == trace.MODULES_LINE]
    assert sum(e.dur_ns for e in leapfrog) * 1e-9 < r["busy_s"] \
        <= sum(e.dur_ns for e in modules) * 1e-9
    assert [c["name"] for c in r["calls"]] == ["run_chains"] * 2
    assert len(r["breakdown"]["idle_gaps"]) == 10
    every = dict(trace.reduce_events(events, top=1000)["breakdown"]
                 ["idle_gaps"])
    assert sum(every.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_reads_a_profiler_file(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    trace.start_trace(str(tmp_path))
    with TraceAnnotation("bench.run_chains"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = trace.events_from_xspace(trace.find_xspace(str(tmp_path)))
    assert [e.name for e in events if e.name.startswith("bench.")] \
        == ["bench.run_chains"]
