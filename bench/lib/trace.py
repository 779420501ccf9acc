"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes under
``<dir>/plugins/profile/<time>/``, read with ``jax.profiler.ProfileData``.

* Device planes are those named ``/device:TPU:<n>``.  Their ``XLA Ops`` line
  holds one event per operation run on the chip; ``XLA Modules`` one per
  program (a jitted function appears as ``jit_<name>(...)``).
* Busy time is the union of the op intervals of a device plane; the idle
  share is 1 - busy / window, averaged over the devices used.
* Host spans are the benchmark's own ``TraceAnnotation``s on the host
  plane's threads, on the same clock as the device events.  Each idle gap
  of the device is labelled with the innermost host span that covers its
  middle, or ``host`` where none does.

Host spans are those whose name starts with ``bench.``; other host events
(the runtime's threads, the Python tracer) are left out.  Only the window
between the first and the last host span named by ``window_span`` counts,
so that the profiler's own start and stop stay out.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
import re
from collections import defaultdict

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Device:
    name: str
    ops: list            # (name, start_ns, end_ns)
    modules: list        # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: list
    spans: list          # host (name, start_ns, end_ns)
    t0: int
    t1: int

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9


def _clip(events, t0, t1):
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if b > t0 and a < t1]


def read(path: str, window_span: str = "bench.window") -> Trace:
    """Read the trace under directory (or file) ``path``."""
    from jax.profiler import ProfileData
    files = ([path] if os.path.isfile(path) else
             glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                       recursive=True))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {path}, "
                         f"found {len(files)}")
    pd = ProfileData.from_file(files[0])
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                target = {"XLA Ops": ops, "XLA Modules": modules}.get(
                    line.name)
                if target is None:
                    continue
                for ev in line.events:
                    target.append((ev.name, int(ev.start_ns),
                                   int(ev.start_ns + ev.duration_ns)))
            devices.append(Device(plane.name, ops, modules))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)))
    win = [s for s in spans if s[0] == window_span]
    if not win:
        raise ValueError(f"no host span {window_span!r} in the trace")
    t0, t1 = min(s[1] for s in win), max(s[2] for s in win)
    spans = [s for s in spans if s[0] != window_span]
    for d in devices:
        d.ops = _clip(d.ops, t0, t1)
        d.modules = _clip(d.modules, t0, t1)
    return Trace(devices, _clip(spans, t0, t1), t0, t1)


def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for a, b in sorted((a, b) for _, a, b in intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_s(tr: Trace) -> float:
    """Seconds in which an op ran, averaged over the devices in the trace."""
    if not tr.devices:
        return 0.0
    tot = sum(sum(b - a for a, b in union(d.ops)) for d in tr.devices)
    return tot / len(tr.devices) / 1e9


def op_seconds(tr: Trace, match) -> tuple[float, int]:
    """Total device seconds and count of the ops whose name ``match``
    accepts, summed over devices."""
    s, n = 0, 0
    for d in tr.devices:
        for name, a, b in d.ops:
            if match(name):
                s += b - a
                n += 1
    return s / 1e9, n


def module_seconds(tr: Trace, match) -> tuple[float, int]:
    """Busy device seconds inside the programs whose name ``match``
    accepts (the union of their ops), and how many such programs ran."""
    s, n = 0, 0
    for d in tr.devices:
        mods = [m for m in d.modules if match(m[0])]
        n += len(mods)
        busy = union(d.ops)
        starts = [x for x, _ in busy]
        for _, a, b in mods:
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(busy) and busy[i][0] < b:
                x, y = busy[i]
                if y > a:
                    s += min(b, y) - max(a, x)
                i += 1
    return s / 1e9, n


def short_name(op: str) -> str:
    """``%fusion.255 = bf16[...] fusion(...), ...`` -> ``%fusion.255
    fusion``: the instruction's name and its opcode."""
    name = op.split(" = ", 1)
    m = re.search(r" ([a-z][\w-]*)\(", " " + name[-1].split(" ", 1)[-1])
    return f"{name[0]} {m.group(1)}" if len(name) == 2 and m else op[:80]


def top_ops(tr: Trace, k: int = 10) -> list:
    """The ``k`` ops that took most device time (seconds, averaged over
    devices), by short name.  A loop's op (``while``) spans its body's ops,
    which are listed too."""
    acc = defaultdict(int)
    for d in tr.devices:
        for name, a, b in d.ops:
            acc[short_name(name)] += b - a
    nd = max(len(tr.devices), 1)
    return [[n, v / 1e9 / nd] for n, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def _segments(spans):
    """Split time into segments labelled by the innermost host span (the
    latest-started one still open); returns (starts, ends, labels)."""
    points = sorted({p for _, a, b in spans for p in (a, b)})
    by_start = sorted(spans, key=lambda s: s[1])
    open_, i, starts, ends, labels = [], 0, [], [], []
    for a, b in zip(points, points[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            s = by_start[i]
            heapq.heappush(open_, (-s[1], s[2], s[0]))
            i += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        live = [o for o in open_ if o[1] > a]
        if live:
            starts.append(a)
            ends.append(b)
            labels.append(min(live)[2])
    return starts, ends, labels


def idle_gaps(tr: Trace, k: int = 10) -> list:
    """Idle device time grouped by the innermost host span covering each
    gap's middle; the ``k`` largest groups, in seconds averaged over
    devices."""
    acc = defaultdict(int)
    starts, ends, labels = _segments(tr.spans)
    for d in tr.devices:
        busy = union(d.ops)
        edges = [tr.t0] + [x for ab in busy for x in ab] + [tr.t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) // 2
            j = bisect.bisect_right(starts, mid) - 1
            label = labels[j] if j >= 0 and mid < ends[j] else "host"
            acc[label] += b - a
    nd = max(len(tr.devices), 1)
    return [[n, v / 1e9 / nd] for n, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]
