"""The program's own spans in a profiler trace, and what
``tools/idle_by_span.py`` reduces them to.

The runtime opens ``gr:`` spans (``repro.core.timeline.SPAN_PREFIX``) around
its launches, lane elements and host reads while a JAX profiler trace runs,
on the clock of the device's events.  ``load`` reads them from an
``.xplane.pb`` beside what ``trace.load`` reads, each with its host thread
and its event stats (``uid``, ``lane``, ``nbytes``, ``device``); a runtime
without them gives an empty list.  Everything else is arithmetic over the
events, tested without a chip.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import trace as T

# The spans of copies between host and device or between devices: a lane's
# upload, device-to-device copy, spill and reload, and a host read's D2H or
# restore from a host tier.
COPIES = ("h2d", "d2d", "evict", "reload", "d2h", "tier_restore")
# Device work a lane or a host read is doing, preferred when a device gap is
# put down to a span; waits are the last choice.
DEVICE_WORK = COPIES + ("kernel", "dispatch", "block")
WAITS = ("host_wait", "lane_wait", "sync")


@dataclass(frozen=True)
class ProgramSpan(T.Event):
    """A span of the program, named without its prefix: ``line`` names its
    host thread (plane and line index: line names repeat), ``stats`` are its
    event stats."""
    line: str = ""
    stats: Tuple[Tuple[str, object], ...] = ()


def program_prefix() -> Optional[str]:
    """The prefix of the program's spans; None for a runtime that has
    none."""
    try:
        from repro.core.timeline import SPAN_PREFIX
    except ImportError:
        return None
    return SPAN_PREFIX


def load(path: str) -> List[ProgramSpan]:
    """The program's spans in an ``.xplane.pb``, sorted by start."""
    prefix = program_prefix()
    if prefix is None:
        return []
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append(ProgramSpan(
                        e.name[len(prefix):], e.start_ns, e.duration_ns,
                        f"{plane.name}/{i}", tuple(e.stats)))
    out.sort(key=lambda e: e.start_ns)
    return out


def overlap_ns(a: Sequence[Tuple[float, float]],
               b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_in_copy_ns(ops: Iterable[T.Event], spans: Sequence[ProgramSpan],
                    t0: float, t1: float) -> float:
    """Device-idle time in [t0, t1] during which a copy span is open on any
    host thread."""
    copies = [s for s in spans if s.name in COPIES]
    return overlap_ns(T.idle_gaps(ops, t0, t1),
                      T.merged(T.clipped(copies, t0, t1)))


def span_table(spans: Sequence[ProgramSpan], t0: float, t1: float
               ) -> Dict[str, Tuple[int, float]]:
    """Per span name: how many lie wholly inside [t0, t1], and their mean
    duration in microseconds."""
    by: Dict[str, List[float]] = {}
    for s in spans:
        if s.start_ns >= t0 and s.end_ns <= t1:
            by.setdefault(s.name, []).append(s.dur_ns)
    return {k: (len(v), sum(v) / len(v) * 1e-3) for k, v in sorted(by.items())}


# ----------------------------------------------------------------------
# Idle time by program span
# ----------------------------------------------------------------------

def _rank(span: ProgramSpan) -> int:
    return 0 if span.name in DEVICE_WORK else 2 if span.name in WAITS else 1


def program_label(open_: Sequence[ProgramSpan]) -> str:
    """What the program was doing at an instant, given the spans open then
    on any thread: of each thread's innermost span, the one of the most
    device-like kind (then the shortest), written with the spans of its
    thread that enclose it (``h2d>block``); ``-`` if none is open."""
    if not open_:
        return "-"
    inner: Dict[str, ProgramSpan] = {}
    for s in open_:
        if s.line not in inner or s.dur_ns < inner[s.line].dur_ns:
            inner[s.line] = s
    chosen = min(inner.values(), key=lambda s: (_rank(s), s.dur_ns))
    outer = [s for s in open_ if s.line == chosen.line
             and s.start_ns <= chosen.start_ns and s.end_ns >= chosen.end_ns]
    outer.sort(key=lambda s: (s.start_ns, -s.dur_ns))
    return ">".join(s.name for s in outer)


def idle_by_span(ops: Iterable[T.Event], trace: T.Trace,
                 spans: Sequence[ProgramSpan], t0: float, t1: float
                 ) -> Dict[Tuple[str, str], float]:
    """Device-idle seconds inside [t0, t1] by (benchmark label, program
    label).  Each idle gap is cut at every start and end of a program or
    benchmark span, so that the same spans are open all through a piece;
    each piece is put down to the program spans open at its midpoint on any
    thread (``program_label``) and to the benchmark span around it
    (``trace.gap_label``)."""
    live = [s for s in spans if s.end_ns > t0 and s.start_ns < t1]
    edges = sorted({x for s in live + trace.spans
                    for x in (s.start_ns, s.end_ns)})
    by: Dict[Tuple[str, str], float] = {}
    open_: List[ProgramSpan] = []
    nxt = k = 0
    for a, b in T.idle_gaps(ops, t0, t1):
        while k < len(edges) and edges[k] <= a:
            k += 1
        cuts = [a]
        while k < len(edges) and edges[k] < b:
            cuts.append(edges[k])
            k += 1
        cuts.append(b)
        for p, q in zip(cuts, cuts[1:]):
            mid = 0.5 * (p + q)
            while nxt < len(live) and live[nxt].start_ns <= mid:
                open_.append(live[nxt])
                nxt += 1
            open_ = [s for s in open_ if s.end_ns >= mid]
            key = (T.gap_label((p, q), trace.spans), program_label(open_))
            by[key] = by.get(key, 0.0) + (q - p) * 1e-9
    return by
