"""Reduce a JAX profiler trace (``.xplane.pb``) of the timed window to the
numbers the per-layer metrics read.

- The window is the host span ``bench.window`` that ``bench/run.py``
  opens around the timed traffic; device events are clipped to it.
- Device time comes from each TPU plane's ``XLA Ops`` line: busy time is
  the union of the op intervals (averaged over the chips used), idle is
  the window less that.
- ``device_ops``: device seconds per op name, largest first; an op whose
  span holds other ops (a loop around its body) counts only its own time.
- ``sort_s``: device seconds of the XLA sort ops (``sort`` in the HLO op
  name: the exact dedup of ``core/dedup.py`` and ``engine.expand_chunk``).
- ``wavefront``: the fused Pallas wavefront kernel's calls (ops whose
  name holds ``wavefront_pallas`` in the compiled program:
  ``wavefront_pallas.<i>``, or ``vmap_jit_wavefront_pallas__.<i>`` as a
  lone program names it): device seconds, call
  count, and the HBM bytes those calls must move, from the shape of each
  call's result (``wavefront_bytes``).
- ``idle_gaps``: the longest gaps between device ops inside the window,
  each labelled by the host span that covers most of it (the
  benchmark's own ``bench.*`` spans and JAX's runtime spans).
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"

WAVEFRONT = "wavefront_pallas"
# the kernel's result: int32 feasibility, (lanes, n, rows, 128)
_RESULT = re.compile(r"s32\[(\d+),(\d+),(\d+),128\]")


def wavefront_bytes(lanes: int, n: int, rows: int) -> int:
    """HBM bytes one call of the fused wavefront kernel must move, read
    once or written once (``kernels/wavefront/kernel.py``): per lane the
    adjacency (n, W), k, the allowed mask (W), rows x 128 states of W
    words and their valid flags, and the (n, rows x 128) int32
    feasibility it writes; W = ceil(n / 32) words of 4 bytes."""
    w = -(-n // 32)
    states = rows * 128
    return 4 * lanes * (n * w + 1 + w + states * w + states + n * states)


def kernel_shape(ev):
    """(lanes, n, rows) from the kernel call's result shape, found in the
    event's name or stats; None where the trace does not give it."""
    for text in [ev.name] + [str(v) for _k, v in ev.stats]:
        m = _RESULT.search(text)
        if m:
            return tuple(int(x) for x in m.groups())
    return None


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(clipped):
    """(start, end, own ns, event) for each (start, end, event): its length
    less what the events nested inside it cover, so that an op whose span
    holds others (a while loop around its body) counts only its own
    time."""
    out, stack = [], []          # stack: [start, end, child ns, event]
    for s, e, ev in sorted(clipped, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            ps, pe, child, pev = stack.pop()
            out.append((ps, pe, pe - ps - child, pev))
        if stack and e <= stack[-1][1]:
            stack[-1][2] += e - s
        stack.append([s, e, 0, ev])
    out.extend((ps, pe, pe - ps - child, pev)
               for ps, pe, child, pev in reversed(stack))
    return out


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    return files[0]


def reduce_dir(trace_dir: str) -> dict:
    return reduce_file(find_xplane(trace_dir))


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def reduce_planes(planes) -> dict:
    host, devices = [], []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if ops:
                devices.append(list(ops[0].events))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.start_ns
                             + ev.duration_ns) for ev in ln.events)
    spans = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span in the "
                           f"trace, found {len(spans)}")
    w0, w1 = spans[0]
    window_s = (w1 - w0) * 1e-9
    if not devices:
        raise RuntimeError("the trace has no TPU plane with an "
                           f"{OPS_LINE!r} line")

    per_op, sort_ns, wave_ns, wave_calls, wave_bytes = {}, 0.0, 0.0, 0, 0
    busy_ns, gaps = 0.0, []
    for events in devices:
        clipped = []
        for ev in events:
            s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns,
                                              w1)
            if e > s:
                clipped.append((s, e, ev))
        for s, e, own, ev in _self_times(clipped):
            per_op[ev.name] = per_op.get(ev.name, 0.0) + own
            hlo = str(_stat(ev, "long_name") or _stat(ev, "hlo_op")
                      or ev.name)
            if "sort" in ev.name or " sort(" in hlo:
                sort_ns += own
            if WAVEFRONT in ev.name:
                shape = kernel_shape(ev)
                if shape is None:
                    raise RuntimeError(f"no result shape for {ev.name}")
                wave_ns += own
                wave_calls += 1
                # a call clipped by the window moves its share of bytes
                wave_bytes += wavefront_bytes(*shape) * (e - s) \
                    / ev.duration_ns
        merged = _union([(s, e) for s, e, _ev in clipped])
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    k = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_label(host, s, e), (e - s) * 1e-9] for s, e in gaps[:10]]
    ops = sorted(([name, ns * 1e-9 / k] for name, ns in per_op.items()),
                 key=lambda x: -x[1])
    return {"window_s": window_s, "busy_s": busy_ns * 1e-9 / k,
            "device_ops": ops[:10], "idle_gaps": idle,
            "sort_s": sort_ns * 1e-9 / k,
            "wavefront": {"s": wave_ns * 1e-9 / k, "calls": wave_calls,
                          "bytes": wave_bytes}}


def _label(host, s, e) -> str:
    """The host span that covers most of [s, e), the window's own span
    left out; ``"no host span"`` where none does."""
    best, cover = "no host span", 0
    for name, hs, he in host:
        if name == WINDOW_SPAN:
            continue
        c = min(he, e) - max(hs, s)
        if c > cover:
            best, cover = name, c
    return best
