"""Profiler trace of a short steady window, and its reduction to metrics.

The run wraps each call into the program in a ``TraceAnnotation`` named
``bench.<call>`` and its own bookkeeping in ``bench.host``. The
reduction:

* window: from the first call span's start to the last one's end;
* busy: the union of the device's op intervals (line ``XLA Ops``) inside
  the window, per chip, averaged over the chips used;
* op time: each op's self time (its duration less that of the ops nested
  in it, as a ``while`` holds its body), summed by name; the name is the
  HLO instruction's (``%fused_leapfrog.12 = ...`` gives
  ``fused_leapfrog``);
* idle gaps: the window less the busy union. A gap inside a program's
  execution (line ``XLA Modules``) goes to ``in-program <module>``; any
  other to the innermost span of the Python thread's host line that
  covers its middle (the benchmark's spans and JAX's own, such as
  ``np.asarray(jax.Array)``).

``events_from_xspace`` turns the profiler's file into plain tuples and
``reduce_events`` works on those alone, so the reduction can be checked on
a small recorded trace without JAX.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, NamedTuple, Tuple

SPAN_PREFIX = "bench."
# the span of the benchmark's own, which is not a call into the program
NOT_CALLS = ("bench.host",)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"(\.\d+)+$")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name \
        and "CUSTOM" not in name


def start_trace(log_dir: str) -> None:
    """Start the profiler with Python function tracing off: it would add
    an event per Python call and slow the host path being measured."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def events_from_xspace(path: str) -> List[Event]:
    """Device op and module events and host events of a trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out, names = [], {}
    for plane in pd.planes:
        dev = is_device_plane(plane.name)
        if not (dev or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            ops = dev and line.name == OPS_LINE
            for e in line.events:
                # an op's name is its whole HLO instruction; keep one
                # short copy of each so a long trace fits in memory
                name = e.name
                if ops:
                    name = names.setdefault(name, op_name(name))
                out.append(Event(plane.name, line.name, name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def find_xspace(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {found}")
    return found[0]


def op_name(name: str) -> str:
    """``%fused_leapfrog.12 = (f32[..]) custom-call(..)`` -> the HLO name
    without its numeric suffix."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def _union(intervals) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def _covered(busy, lo, hi) -> float:
    return sum(e - s for s, e in _clip(busy, lo, hi))


def _self_times(ops: List[Event]) -> Dict[str, float]:
    """Self time by op name; an op nested in another is subtracted from
    its parent."""
    order = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    self_ns = [e.dur_ns for e in order]
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].start_ns + order[stack[-1]].dur_ns \
                <= e.start_ns:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e.dur_ns
        stack.append(i)
    out: Dict[str, float] = {}
    for e, s in zip(order, self_ns):
        k = op_name(e.name)
        out[k] = out.get(k, 0.0) + max(s, 0.0)
    return out


def _innermost(events: List[Event]):
    """Non-overlapping (start, end, name) pieces of a host line, each given
    to the innermost event covering it."""
    bounds = sorted({t for e in events
                     for t in (e.start_ns, e.start_ns + e.dur_ns)})
    order = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    pieces, active, k = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(order) and order[k].start_ns <= a:
            active.append(order[k])
            k += 1
        active = [e for e in active if e.start_ns + e.dur_ns > a]
        if active:
            pieces.append((a, b, active[-1].name))
    return pieces


def _owner(pieces, starts, t: float, default: str) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and pieces[i][0] <= t < pieces[i][1]:
        return pieces[i][2]
    return default


def reduce_events(events: List[Event], top: int = 10) -> Dict:
    """Window, busy share, op time by name and idle gaps by owner.

    Returns seconds throughout. ``calls`` lists, per call span, its name,
    wall seconds and the device-busy seconds inside it (per chip, averaged
    over the chips)."""
    host = [e for e in events if not is_device_plane(e.plane)]
    spans = [e for e in host if e.name.startswith(SPAN_PREFIX)]
    calls = [e for e in spans if e.name not in NOT_CALLS]
    if not calls:
        raise RuntimeError("the trace holds no call span")
    lo = min(e.start_ns for e in calls)
    hi = max(e.start_ns + e.dur_ns for e in calls)
    thread = (spans[0].plane, spans[0].line)
    py = [e for e in host if (e.plane, e.line) == thread and e.dur_ns > 0]
    pieces = _innermost(py)
    piece_starts = [p[0] for p in pieces]

    dev = [e for e in events if is_device_plane(e.plane)]
    ops = [e for e in dev if e.line == OPS_LINE]
    planes = sorted({e.plane for e in ops})
    if not planes:
        raise RuntimeError("the trace holds no device op")
    busy_by_plane = {
        p: _union(_clip([(e.start_ns, e.start_ns + e.dur_ns)
                         for e in ops if e.plane == p], lo, hi))
        for p in planes}
    busy_ns = sum(_covered(b, lo, hi) for b in busy_by_plane.values()) \
        / len(planes)

    in_window = [e for e in ops if lo <= e.start_ns < hi]
    op_ns = _self_times(in_window)
    launches: Dict[str, int] = {}
    for e in in_window:
        k = op_name(e.name)
        launches[k] = launches.get(k, 0) + 1

    gaps_ns: Dict[str, float] = {}
    for p, busy in busy_by_plane.items():
        mods = sorted((e.start_ns, e.start_ns + e.dur_ns, e.name)
                      for e in dev if e.plane == p and e.line == MODULES_LINE)
        mod_starts = [m[0] for m in mods]
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                mid = 0.5 * (prev + s)
                mod = _owner(mods, mod_starts, mid, "")
                name = (f"in-program {mod}" if mod else
                        _owner(pieces, piece_starts, mid, "(no host span)"))
                gaps_ns[name] = gaps_ns.get(name, 0.0) \
                    + (s - prev) / len(planes)
            prev = max(prev, e)

    call_rows = []
    for c in calls:
        c_lo, c_hi = c.start_ns, c.start_ns + c.dur_ns
        inside = sum(_covered(b, c_lo, c_hi)
                     for b in busy_by_plane.values()) / len(planes)
        call_rows.append({"name": c.name[len(SPAN_PREFIX):],
                          "wall_s": c.dur_ns * 1e-9,
                          "busy_s": inside * 1e-9})

    def top_list(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "chips": len(planes),
        "op_s": {k: v * 1e-9 for k, v in op_ns.items()},
        "op_launches": launches,
        "calls": call_rows,
        "breakdown": {"device_ops": top_list(op_ns),
                      "idle_gaps": top_list(gaps_ns)},
    }


def kernel_time(reduced: Dict, kernel: str):
    """(seconds, launches) of the ops named ``kernel``."""
    return (reduced["op_s"].get(kernel, 0.0),
            reduced["op_launches"].get(kernel, 0))
