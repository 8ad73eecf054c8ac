"""Give the device's idle time in a profiler trace of the window to the
solve path's own host spans (the ``tw.*`` spans of DESIGN.md §14).

``idle_by_span``: device-idle seconds inside the window, each idle
interval given to the innermost ``tw.*`` span open at that time on the
host line that carries ``tw.step`` (the scheduler's driver thread), or to
``"none"`` where no such span is open.  A span still open when the trace
stops is not in the trace (the profiler writes a host span when it
ends), so the time of a step the window's close cuts reads ``"none"``.

It takes the planes of ``jax.profiler.ProfileData`` as
``bench/trace_reduce.py`` does, clips to its ``bench.window`` span and
averages over the chips traced.  Nothing in ``bench/run.py`` calls it
yet.
"""
from __future__ import annotations

import bisect

from bench.trace_reduce import DEVICE_PREFIX, OPS_LINE, WINDOW_SPAN, _union

DRIVER_SPAN = "tw.step"
NONE = "none"


def _window(planes):
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in planes if plane.name.startswith("/host:")
             for ln in plane.lines for ev in ln.events
             if ev.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span in the "
                           f"trace, found {len(spans)}")
    return spans[0]


def _busy(planes, w0, w1):
    """Per TPU plane, the union of its ``XLA Ops`` intervals clipped to
    the window."""
    out = []
    for plane in planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for ln in plane.lines:
            if ln.name == OPS_LINE:
                out.append(_union(
                    [(max(ev.start_ns, w0),
                      min(ev.start_ns + ev.duration_ns, w1))
                     for ev in ln.events
                     if min(ev.start_ns + ev.duration_ns, w1)
                     > max(ev.start_ns, w0)]))
                break
    if not out:
        raise RuntimeError(f"the trace has no TPU plane with an {OPS_LINE!r}"
                           " line")
    return out


def _driver_spans(planes):
    """The ``tw.*`` events of the host line with the most ``tw.step``
    events, as (start, end, name); empty where no line has one."""
    best, most = [], 0
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in ln.events if ev.name.startswith("tw.")]
            steps = sum(1 for _s, _e, name in evs if name == DRIVER_SPAN)
            if steps > most:
                best, most = evs, steps
    return best


def _tiles(spans):
    """Sorted, disjoint (start, end, name) pieces of the time the spans
    cover, each named for the innermost span open in it.  Spans of one
    thread nest; one that outlasts its parent is cut at the parent's
    end."""
    tiles, stack, t = [], [], None          # stack: [end, name]

    def close_until(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                tiles.append((t, end, name))
                t = end

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        close_until(s)
        if stack:
            if s > t:
                tiles.append((t, s, stack[-1][1]))
            e = min(e, stack[-1][0])
        stack.append([e, name])
        t = s
    close_until(float("inf"))
    return tiles


def idle_by_span(planes) -> dict:
    """Device-idle seconds in the window per innermost driver span."""
    planes = list(planes)          # ProfileData's planes iterate once
    w0, w1 = _window(planes)
    devices = _busy(planes, w0, w1)
    tiles = _tiles(_driver_spans(planes))
    starts = [s for s, _e, _n in tiles]
    out = {NONE: 0.0}
    for merged in devices:
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            covered = 0.0
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while i < len(tiles) and tiles[i][0] < b:
                s, e, name = tiles[i]
                c = min(e, b) - max(s, a)
                if c > 0:
                    out[name] = out.get(name, 0.0) + c
                    covered += c
                i += 1
            out[NONE] += (b - a) - covered
    k = len(devices)
    return {name: ns * 1e-9 / k for name, ns in out.items()}
