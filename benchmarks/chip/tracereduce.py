"""Reduce a profiler trace (``.xplane.pb``) to busy time, idle gaps and
per-program device time.

Device planes are ``/device:TPU:<n>``; on them the ``XLA Ops`` line holds
every operation the chip ran and ``XLA Modules`` one event per program run.
Host spans are the benchmark's own ``bench.*`` annotations on the host
plane, on the same clock.  Everything is in nanoseconds until the readers
turn it into their units.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict
from pathlib import Path


@dataclasses.dataclass
class Line:
    name: str
    events: list[tuple[str, int, int]]  # (name, start_ns, end_ns), sorted by start


@dataclasses.dataclass
class Trace:
    planes: dict[str, list[Line]]

    def lines(self, plane_prefix: str, line_name: str) -> dict[str, Line]:
        """``{plane: line}`` for every plane starting with ``plane_prefix``
        that has a line named ``line_name``."""
        out = {}
        for pname, lines in self.planes.items():
            if pname.startswith(plane_prefix):
                for ln in lines:
                    if ln.name == line_name:
                        out[pname] = ln
        return out

    def host_spans(self, prefix: str = "bench.") -> list[tuple[str, int, int]]:
        spans = []
        for pname, lines in self.planes.items():
            if pname.startswith("/host:"):
                for ln in lines:
                    spans.extend(e for e in ln.events if e[0].startswith(prefix))
        return sorted(spans, key=lambda e: e[1])


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes: dict[str, list[Line]] = {}
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]
            evs.sort(key=lambda e: e[1])
            lines.append(Line(line.name, evs))
        planes[plane.name] = lines
    return Trace(planes)


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def union(intervals) -> list[tuple[int, int]]:
    """Merge overlapping [start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals, lo: int, hi: int) -> int:
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle stretches of [lo, hi) between the merged busy intervals."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans) -> list[tuple[int, int, str]]:
    """Flatten properly nested spans into non-overlapping ``(start, end,
    name)`` segments, each named by the innermost span covering it."""
    out: list[tuple[int, int, str]] = []
    stack: list[tuple[str, int]] = []  # (name, end)
    cur = 0
    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            if end > cur:
                out.append((cur, end, top))
            cur = max(cur, end)
        if stack and s > cur:
            out.append((cur, s, stack[-1][0]))
        cur = max(cur, s)
        stack.append((name, e))
    while stack:
        top, end = stack.pop()
        if end > cur:
            out.append((cur, end, top))
        cur = max(cur, end)
    return out


def attribute(gap_list, spans, other: str = "host:other") -> dict[str, int]:
    """Idle ns by what the host was doing: each part of a gap goes to the
    innermost host span covering it, and to ``other`` where none does."""
    out: dict[str, int] = defaultdict(int)
    segs = innermost(spans)
    j = 0
    for g0, g1 in sorted(gap_list):
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        covered, k = 0, j
        while k < len(segs) and segs[k][0] < g1:
            a, b = max(segs[k][0], g0), min(segs[k][1], g1)
            if b > a:
                out[segs[k][2]] += b - a
                covered += b - a
            k += 1
        if g1 - g0 > covered:
            out[other] += g1 - g0 - covered
    return dict(out)


def time_by_name(events, lo: int, hi: int) -> dict[str, int]:
    """Summed (clipped) duration of the events of each name."""
    out: dict[str, int] = defaultdict(int)
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[name] += e - s
    return dict(out)
