"""Split a profile's device idle time among the program's own spans.

The program writes each of its trace spans into the profile as a host
annotation named ``repro.<span>`` (``repro.hop``, ``repro.ingest``,
``repro.fence``, ...) while the profile is captured.  Each device's idle
intervals inside ``bench.window`` (the ones ``xplane.reduce_planes``
names by ``bench.*`` span) are split here by exact overlap among the
innermost program spans open over them: innermost is the latest start
among the spans, on any host thread, that cover the instant.  Idle time
under no program span goes to ``none``.  Totals are averaged over the
devices, as ``busy_s`` is, so they sum to ``window_s - busy_s``.
"""
from __future__ import annotations

import collections
import heapq

import xplane

PREFIX = "repro."
NONE = "none"


def _innermost(spans, lo: int, hi: int) -> list[tuple[int, int, str]]:
    """Segments ``(start, end, name)`` that tile ``[lo, hi]``, each named
    by the innermost of ``spans`` ((start, end, name)) open over it."""
    cuts = {lo, hi}
    for a, b, _ in spans:
        cuts.update(x for x in (a, b) if lo < x < hi)
    pts = sorted(cuts)
    order = sorted(spans)
    open_ = []          # heap of (-start, end, name): latest start on top
    i = 0
    out = []
    for x, y in zip(pts, pts[1:]):
        while i < len(order) and order[i][0] <= x:
            a, b, n = order[i]
            heapq.heappush(open_, (-a, b, n))
            i += 1
        while open_ and open_[0][1] <= x:
            heapq.heappop(open_)
        name = open_[0][2] if open_ else NONE
        if out and out[-1][2] == name and out[-1][1] == x:
            out[-1] = (out[-1][0], y, name)
        else:
            out.append((x, y, name))
    return out


def _idle(ops, lo: int, hi: int) -> list[tuple[int, int]]:
    """The gaps between the union of ``ops`` ((start, end)) in [lo, hi]."""
    busy = xplane._union([(max(a, lo), min(b, hi)) for a, b in ops
                          if min(b, hi) > max(a, lo)])
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def _split(idle, segments) -> collections.Counter:
    """Overlap of each named segment with the idle intervals (both
    sorted, the segments disjoint)."""
    out = collections.Counter()
    j = 0
    for a, b in idle:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, n = segments[k]
            out[n] += min(b, e) - max(a, s)
            k += 1
    return out


def _planes(planes):
    host, devices = [], {}
    for pname, lines in planes:
        if pname == xplane.HOST_PLANE:
            for _, events in lines:
                host += [(s, s + d, n) for n, s, d in events]
        elif pname.startswith(xplane.DEVICE_PREFIX):
            for lname, events in lines:
                if lname == xplane.OPS_LINE:
                    devices[pname] = [(s, s + d) for _, s, d in events]
    win = [(a, b) for a, b, n in host if n == xplane.WINDOW]
    if not win or not devices:
        raise ValueError("trace holds no bench.window span or no device ops")
    lo, hi = win[0]
    spans = [(a, b, n) for a, b, n in host
             if n.startswith(PREFIX) and b > lo and a < hi]
    return lo, hi, spans, devices


def idle_by_program_span(planes) -> list[list]:
    """``[[name, seconds], ...]``, largest first, for ``planes`` in
    ``xplane.reduce_planes``' form."""
    lo, hi, spans, devices = _planes(planes)
    segments = _innermost(spans, lo, hi)
    total = collections.Counter()
    for ops in devices.values():
        total.update(_split(_idle(ops, lo, hi), segments))
    n = len(devices)
    return [[name, t * 1e-9 / n] for name, t in total.most_common()]


def span_counts(planes) -> dict[str, int]:
    """How many of each program span start inside ``bench.window``."""
    lo, hi, spans, _ = _planes(planes)
    return dict(collections.Counter(n for a, _, n in spans if lo <= a < hi))

