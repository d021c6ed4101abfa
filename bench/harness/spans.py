"""The program's own spans and op scopes in a profiler trace.

The program writes host spans named ``repro.`` (``repro.core.program.span``:
``repro.run_chains`` and its ``setup``, ``dispatch`` and ``collect``,
``repro.program.fingerprint``, ``repro.program.build``) on the thread that
calls it, and names scopes of its device code with ``jax.named_scope``
(``repro.logdensity``, ``repro.integrator``). ``reduce_program`` reduces a
traced window of calls (found as ``trace.reduce_events`` finds it) to:

* ``idle_by_span``: the idle gaps that are not inside a program (as
  ``trace.reduce_events``'s idle gaps decide it, by a gap's middle), split
  exactly at the program's span boundaries, each piece given to the
  innermost ``repro.`` span covering it; JAX's own host events are
  ignored. Idle time outside every ``repro.`` span goes to ``NO_SPAN``,
  so the values sum to the gaps that are not ``in-program``;
* ``span_s``: wall seconds and count of each ``repro.`` span name that
  starts in the window;
* ``scope_busy_s``: per scope in ``SCOPES``, the device-busy seconds (the
  union of op intervals, per chip, averaged over the chips, as
  ``busy_s``) of the ops whose scope path holds it.

An op's scope path is the ``tf_op`` stat of its event's metadata (the
op's ``op_name`` in the HLO, such as
``jit(traced)/vmap()/while/body/repro.integrator/repro.logdensity/...``);
a fusion carries its root op's. ``jax.profiler.ProfileData`` does not
expose metadata stats, so ``op_scopes`` reads them from the serialized
trace. On a program without these spans and scopes the keys hold nothing
of the program: ``idle_by_span`` only ``NO_SPAN``, the others nothing.

Nothing in ``run.py`` calls this module yet: wiring it in takes edits to
``trace.py`` and the metric readers (``PERF.md``, section 7).
"""
from __future__ import annotations

import bisect
from typing import Dict, List, NamedTuple, Tuple

from harness.trace import (MODULES_LINE, NOT_CALLS, OPS_LINE, SPAN_PREFIX,
                           _clip, _covered, _innermost, _owner, _union,
                           is_device_plane, op_name)

PROGRAM_PREFIX = "repro."
SCOPES = ("repro.logdensity", "repro.integrator")
NO_SPAN = "(none)"


class ScopedEvent(NamedTuple):
    """``trace.Event`` with a device op's scope path (``""`` elsewhere)."""
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    scope: str = ""


def events_from_xspace(path: str) -> List[ScopedEvent]:
    """``trace.events_from_xspace``'s events, each device op with its
    scope path."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        scopes = op_scopes(f.read())
    out, names = [], {}
    for plane in ProfileData.from_file(path).planes:
        dev = is_device_plane(plane.name)
        if not (dev or plane.name.startswith("/host:")):
            continue
        plane_scopes = scopes.get(plane.name, {})
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            ops = dev and line.name == OPS_LINE
            for e in line.events:
                name, scope = e.name, ""
                if ops:
                    scope = plane_scopes.get(name, "")
                    name = names.setdefault(name, op_name(name))
                out.append(ScopedEvent(plane.name, line.name, name,
                                       float(e.start_ns),
                                       float(e.duration_ns), scope))
    return out


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of the protobuf message in ``buf[lo:hi]``: an
    int for a varint, (start, end) for a length-delimited field; fixed
    width fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield num, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield num, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode()


def op_scopes(buf: bytes, stat: str = "tf_op",
              planes=is_device_plane) -> Dict[str, Dict[str, str]]:
    """Per plane whose name ``planes`` accepts, each event name's string
    stat ``stat`` from the event metadata of a serialized XSpace.

    Fields read (``tsl/profiler/protobuf/xplane.proto``): XSpace.planes 1;
    XPlane.name 2, event_metadata 4, stat_metadata 5 (map entries: key 1,
    value 2); XEventMetadata.name 2, stats 5; XStat.metadata_id 1,
    str_value 5, ref_value 7 (the name of a stat metadata entry);
    XStatMetadata.name 2. A plane's lines, which hold its events, are
    skipped unread."""
    buf = memoryview(buf)
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f in (4, 5) and planes(name):
                entry = dict(_fields(buf, *v))
                if 2 not in entry:
                    continue
                if f == 4:
                    metas.append(entry[2])
                else:
                    sm = dict(_fields(buf, *entry[2]))
                    if 2 in sm:
                        stat_names[entry.get(1, 0)] = _text(buf, sm[2])
        if not planes(name):
            continue
        want = {k for k, v in stat_names.items() if v == stat}
        table = out.setdefault(name, {})
        for meta in metas:
            ev_name = value = None
            for f, v in _fields(buf, *meta):
                if f == 2:
                    ev_name = _text(buf, v)
                elif f == 5:
                    st = dict(_fields(buf, *v))
                    if st.get(1) in want:
                        value = (_text(buf, st[5]) if 5 in st
                                 else stat_names.get(st.get(7)))
            if ev_name is not None and value:
                table.setdefault(ev_name, value)
    return out


def _split(pieces, starts, lo: float, hi: float):
    """(owner, ns) of the interval lo-hi cut at the boundaries of the
    non-overlapping ``pieces``; a part no piece covers goes to NO_SPAN."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    t = lo
    while t < hi and i < len(pieces):
        a, b, name = pieces[i]
        i += 1
        if b <= t:
            continue
        if a >= hi:
            break
        if a > t:
            yield NO_SPAN, a - t
            t = a
        end = min(b, hi)
        yield name, end - t
        t = end
    if t < hi:
        yield NO_SPAN, hi - t


def reduce_program(events) -> Dict:
    """``idle_by_span``, ``span_s`` and ``scope_busy_s`` of the window
    that ``trace.reduce_events`` reduces, in seconds."""
    host = [e for e in events if not is_device_plane(e.plane)]
    spans = [e for e in host if e.name.startswith(SPAN_PREFIX)]
    calls = [e for e in spans if e.name not in NOT_CALLS]
    if not calls:
        raise RuntimeError("the trace holds no call span")
    lo = min(e.start_ns for e in calls)
    hi = max(e.start_ns + e.dur_ns for e in calls)
    thread = (spans[0].plane, spans[0].line)
    program = [e for e in host if (e.plane, e.line) == thread
               and e.dur_ns > 0 and e.name.startswith(PROGRAM_PREFIX)]
    pieces = _innermost(program)
    starts = [p[0] for p in pieces]

    dev = [e for e in events if is_device_plane(e.plane)]
    ops = [e for e in dev if e.line == OPS_LINE]
    planes = sorted({e.plane for e in ops})
    if not planes:
        raise RuntimeError("the trace holds no device op")

    def busy(plane, keep=lambda e: True):
        return _union(_clip([(e.start_ns, e.start_ns + e.dur_ns) for e in ops
                             if e.plane == plane and keep(e)], lo, hi))

    idle_ns: Dict[str, float] = {}
    for p in planes:
        mods = sorted((e.start_ns, e.start_ns + e.dur_ns, e.name)
                      for e in dev if e.plane == p and e.line == MODULES_LINE)
        mod_starts = [m[0] for m in mods]
        prev = lo
        for s, e in busy(p) + [(hi, hi)]:
            if s > prev and not _owner(mods, mod_starts,
                                       0.5 * (prev + s), ""):
                for owner, ns in _split(pieces, starts, prev, s):
                    idle_ns[owner] = idle_ns.get(owner, 0.0) \
                        + ns / len(planes)
            prev = max(prev, e)

    span_s: Dict[str, Dict] = {}
    for e in program:
        if lo <= e.start_ns < hi:
            row = span_s.setdefault(e.name, {"wall_s": 0.0, "count": 0})
            row["wall_s"] += e.dur_ns * 1e-9
            row["count"] += 1

    scope_busy_s = {}
    for scope in SCOPES:
        ns = sum(_covered(busy(p, lambda e: scope in e.scope), lo, hi)
                 for p in planes) / len(planes)
        if ns:
            scope_busy_s[scope] = ns * 1e-9
    return {"idle_by_span": {k: v * 1e-9 for k, v in idle_ns.items()},
            "span_s": span_s, "scope_busy_s": scope_busy_s}
