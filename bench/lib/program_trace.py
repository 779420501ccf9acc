"""The program's own host spans in a traced run, and what they add up to.

The program marks the layer boundaries of its serving tick and its
collaborative round with spans named ``coca.*`` (``src/repro/obs.py``
lists them), each a ``TraceAnnotation`` whose integer counters come back
as the event's stats.  They share the profiler's trace, and its clock, with
the device events and the benchmark's ``bench.*`` spans, which
``bench/lib/trace.py`` reduces; this module reads the ``coca.*`` ones from
the same ``.xplane.pb``: the one ``bench/run.py`` writes under
``<checkout>/bench_out/trace`` and reads before it deletes it.

Spans are clipped to the traced window (``Trace.t0`` to ``Trace.t1``).  A
program without such spans gives none, and every reader of them then
returns ``None``.  The device's busy time comes from the reduction's own
union of op intervals (``ctx.lib.union``).
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import statistics
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
TRACE_DIR = ROOT / "bench_out" / "trace"
PREFIX = "coca."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int           # ns, on the device events' clock
    end: int
    counters: dict

    @property
    def ns(self) -> int:
        return self.end - self.start


class SpanIndex:
    """Spans in start order, indexed by name for containment queries."""

    def __init__(self, spans: list):
        self.all = sorted(spans, key=lambda s: (s.start, -s.end))
        self._by_name: dict = {}
        for s in self.all:
            self._by_name.setdefault(s.name, []).append(s)
        self._starts = {n: [s.start for s in v]
                        for n, v in self._by_name.items()}

    def named(self, name: str) -> list:
        return self._by_name.get(name, [])

    def within(self, parent: Span, name: str) -> list:
        """Spans called ``name`` that lie inside ``parent``'s interval."""
        starts, out = self._starts.get(name, []), []
        i = bisect.bisect_left(starts, parent.start)
        for s in self._by_name.get(name, [])[i:]:
            if s.start > parent.end:
                break
            if s.end <= parent.end:
                out.append(s)
        return out

    def self_ns(self, parent: Span, child: str) -> int:
        """``parent``'s duration less its ``child`` spans."""
        return parent.ns - sum(c.ns for c in self.within(parent, child))


def _parse(path: str) -> list:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    a = int(ev.start_ns)
                    out.append((ev.name, a, a + int(ev.duration_ns),
                                {k: int(v) for k, v in ev.stats}))
    return out


@functools.lru_cache(maxsize=1)
def _clipped(path: str, mtime_ns: int, size: int, t0: int,
             t1: int) -> SpanIndex:
    return SpanIndex([Span(n, max(a, t0), min(b, t1), c)
                      for n, a, b, c in _parse(path) if b > t0 and a < t1])


def read(tr, path: Path = TRACE_DIR) -> SpanIndex:
    """The ``coca.*`` spans of the trace under ``path`` clipped to
    ``tr``'s window; one parse serves every reader of a run."""
    files = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        return SpanIndex([])
    st = os.stat(files[0])
    return _clipped(files[0], st.st_mtime_ns, st.st_size, tr.t0, tr.t1)


def median_ms(values_ns) -> float | None:
    return statistics.median(values_ns) / 1e6 if values_ns else None


ALLOC = ("coca.round.aca", "coca.round.cut")


def round_parts(sp: SpanIndex) -> list:
    """Host nanoseconds of each vectorised round (a ``coca.round`` that
    holds its ``coca.round.sync``), by part: ``aca``, ``cut`` and ``stack``
    summed over the round's spans of those names, and ``host``, the round
    less its sync.  An ``aca`` or ``cut`` span outside every round (a
    caller that cut the tables before ``step(tables=...)``) counts toward
    the next round, in its part and in ``host``."""
    rounds = sp.named("coca.round")
    starts = [r.start for r in rounds]
    before = {n: [0] * len(rounds) for n in ALLOC}
    for n in ALLOC:
        for s in sp.named(n):
            j = bisect.bisect_right(starts, s.start) - 1
            if j >= 0 and rounds[j].end >= s.end:
                continue                     # inside round j
            if j + 1 < len(rounds):
                before[n][j + 1] += s.ns
    out = []
    for i, r in enumerate(rounds):
        syncs = sp.within(r, "coca.round.sync")
        if not syncs:
            continue
        part = {n.rsplit(".", 1)[1]: sum(c.ns for c in sp.within(r, n))
                for n in (*ALLOC, "coca.round.stack")}
        for n in ALLOC:
            part[n.rsplit(".", 1)[1]] += before[n][i]
        part["host"] = (r.ns - sum(c.ns for c in syncs)
                        + sum(before[n][i] for n in ALLOC))
        out.append(part)
    return out


def overlap_ns(xs: list, ys: list) -> int:
    """Total overlap of two sorted lists of disjoint intervals."""
    i = j = tot = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            tot += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def host_idle_share(tr, sp: SpanIndex, names: tuple, union) -> float | None:
    """% of the traced window in which no op ran on the device while the
    host was inside a span named in ``names``, averaged over the devices;
    ``union`` merges ``(name, start, end)`` intervals (the device's ops
    into its busy time).  ``None`` where the trace has no device or no
    such span.

    The spans' ``*.sync`` children are not taken out.  Host spans and
    device ops sit on two clocks aligned to about a millisecond, so the
    idle on either side of a sync's edges, which lie next to device work,
    moves with the alignment; a tick or a round begins and ends with the
    device idle, so the idle inside the whole span does not."""
    host = union([(n, s.start, s.end) for n in names for s in sp.named(n)])
    win = tr.t1 - tr.t0
    if not tr.devices or not host or win <= 0:
        return None
    host_ns = sum(b - a for a, b in host)
    idle = [host_ns - overlap_ns(host, union(d.ops)) for d in tr.devices]
    return 100.0 * sum(idle) / len(idle) / win
