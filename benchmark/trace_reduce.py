"""Reduction of a JAX profiler trace to device metrics.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` into a plain structure (:func:`load`), so the
reduction itself runs on recorded data without JAX (the tests feed it a
trace recorded on the chip).

- busy: the union of the intervals in which an op ran on a device
  (the device plane's ``XLA Ops`` line), clipped to a window;
- module time: device time per XLA module (the ``XLA Modules`` line);
- collective time: ops whose name is a collective's;
- idle gaps: the holes in the busy union inside the window, each labelled
  with the innermost host span open over it.

Host spans and device events share the profiler's clock. The harness's own
``TraceAnnotation`` marks the window in the trace; :func:`clock_offset`
turns host ``perf_counter`` seconds into trace nanoseconds with it.
"""

import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|psum|send|recv", re.IGNORECASE)


def load(path):
    """{plane name: {line name: [(name, start_ns, dur_ns)]}} of a trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events
            )
    return out


def device_planes(trace):
    """Device plane names, ordered by device id."""
    found = [(int(m.group(2)), name) for name in trace
             for m in [DEVICE_PLANE.match(name)] if m]
    return [name for _, name in sorted(found)]


def find_host_event(trace, name):
    """(start_ns, end_ns) of the first host event called ``name``."""
    for plane, lines in trace.items():
        if DEVICE_PLANE.match(plane):
            continue
        for events in lines.values():
            for ev_name, start, dur in events:
                if ev_name == name:
                    return start, start + dur
    return None


def clock_offset(trace, marker, marker_start_s):
    """Trace ns at host perf_counter 0, from a marker annotation whose
    host start was ``marker_start_s``."""
    found = find_host_event(trace, marker)
    if found is None:
        return None
    return found[0] - marker_start_s * 1e9


def _clip(events, lo, hi):
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [tuple(x) for x in merged]


def busy_ns(trace, plane, windows):
    """Union of op intervals on ``plane`` inside each (lo, hi) window."""
    ops = trace.get(plane, {}).get(OPS_LINE, [])
    total = 0.0
    for lo, hi in windows:
        total += sum(e - s for s, e in
                     union((s, e) for _, s, e in _clip(ops, lo, hi)))
    return total


def op_time_ns(trace, plane, windows, line=OPS_LINE, match=None):
    """{name: device ns} of the events of one line inside the windows,
    optionally only names that ``match`` (a compiled regex) finds."""
    out = {}
    events = trace.get(plane, {}).get(line, [])
    for lo, hi in windows:
        for name, s, e in _clip(events, lo, hi):
            if match is None or match.search(name):
                out[name] = out.get(name, 0.0) + (e - s)
    return out


def idle_gaps(trace, plane, window, spans, top=10, min_ns=0.0):
    """The longest holes in the busy union inside ``window``, each as
    (label, ns). ``spans``: host spans (name, start_ns, end_ns) already on
    the trace clock; a gap is labelled with the shortest span covering its
    middle, or ``"no host span"``."""
    lo, hi = window
    ops = trace.get(plane, {}).get(OPS_LINE, [])
    busy = union((s, e) for _, s, e in _clip(ops, lo, hi))
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    gaps = sorted((g for g in gaps if g[1] - g[0] > min_ns),
                  key=lambda g: g[0] - g[1])[:top]
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(se - ss, name) for name, ss, se in spans if ss <= mid <= se]
        out.append((min(cover)[1] if cover else "no host span", e - s))
    return out
