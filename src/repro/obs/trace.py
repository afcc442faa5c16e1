"""Per-hop trace spans: one bounded ring, bridged into the JAX profile.

The streaming hop is a pipeline (pack -> dispatch -> fence -> fetch ->
detector -> push_fold) and the ROADMAP's async-overlap work will be
judged by *where inside the hop* the time goes, not by one aggregate
number.  ``Tracer`` records lightweight spans into a bounded ring (O(1)
memory over unbounded uptime, same discipline as the metrics registry)
and exports them as Chrome trace-event JSON — load the file at
``ui.perfetto.dev`` (or ``chrome://tracing``) to see every hop's phase
breakdown on a timeline.

While a JAX profile is being captured (``jax.profiler.start_trace``),
every span the ring records is also written into the profile as a
``jax.profiler.TraceAnnotation`` named ``<process_name>.<span name>``
(``repro.hop``, ``repro.ingest``, ...), opened and closed live at the
span's own boundaries, so the program's spans share the device trace's
clock and can name the device's idle gaps.  The gate is
``TraceAnnotation.is_enabled()``, checked where a span opens: with no
profile running a span costs that one check and builds no annotation.

Recording APIs:

* ``with tracer.span("resize"):`` — the context-manager form (lifecycle
  work: ingest, resize, rebalance, prime_batch, prewarm, LM prefill).
  The body may add args to the dict it yields.
* ``tracer.add`` / ``tracer.add_batch`` — record completed spans into
  the ring from stamps the caller already holds.  They cannot reach a
  profile after the fact, so a caller that records this way (the hop hot
  path) brackets the same boundaries live with ``annotate`` and
  ``handoff``.

Timestamps are monotonic (``perf_counter``, or the clock the caller
passes) relative to the tracer's epoch, exported in microseconds as the
trace-event spec requires.  Consecutive phases share boundary stamps, so
the exported spans tile their parent ``hop`` span exactly (the tests
assert >= 95% coverage).
"""
from __future__ import annotations

import collections
import contextlib
import json
import threading
import time

from jax.profiler import TraceAnnotation as _Annotation


class Tracer:
    """Bounded span recorder; disabled mode is a near-free no-op."""

    def __init__(self, capacity: int = 65536, enabled: bool = True,
                 process_name: str = "repro") -> None:
        self.enabled = enabled
        self.process_name = process_name
        self._prefix = process_name + "."
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._epoch = time.perf_counter()
        self.dropped = 0  # spans evicted from the ring (uptime > capacity)

    @property
    def capacity(self) -> int:
        return self._events.maxlen or 0

    def __len__(self) -> int:
        return len(self._events)

    # -- the profile bridge --------------------------------------------------

    def annotate(self, name: str, **args):
        """Open span ``name``'s annotation in the profile being captured
        and return it; None (and no object built) when no profile is
        being captured or the tracer is off."""
        if not (self.enabled and _Annotation.is_enabled()):
            return None
        ann = _Annotation(self._prefix + name, **args)
        ann.__enter__()
        return ann

    def handoff(self, ann, name: str, **args):
        """One boundary between consecutive phases: close ``ann`` (None:
        nothing open) and open ``name``'s annotation in its place."""
        close(ann)
        return self.annotate(name, **args)

    # -- recording -----------------------------------------------------------

    def add(self, name: str, t0: float, dur_s: float, **args) -> None:
        """Record a completed span into the ring: ``t0`` is a clock
        stamp, ``dur_s`` its duration.  One deque append — cheap enough
        for several calls per hop."""
        if not self.enabled:
            return
        ev = self._events
        if len(ev) == ev.maxlen:
            self.dropped += 1
        ev.append((name, t0 - self._epoch, dur_s, threading.get_ident(), args))

    def add_batch(self, spans) -> None:
        """Record several completed spans into the ring in one call.

        The hop hot path stamps every phase with consecutive clock reads
        and hands them all over at once — one python call per hop instead
        of one per phase.  ``spans`` is an iterable of ``(name, t0, dur_s,
        args_dict)`` tuples.
        """
        if not self.enabled:
            return
        ev = self._events
        epoch = self._epoch
        tid = threading.get_ident()
        maxlen = ev.maxlen
        for name, t0, dur_s, args in spans:
            if len(ev) == maxlen:
                self.dropped += 1
            ev.append((name, t0 - epoch, dur_s, tid, args))

    @contextlib.contextmanager
    def span(self, name: str, clock=time.perf_counter, **args):
        """Context-managed span, stamped with ``clock``; yields its args
        dict, which the body may extend.  Body exceptions still close the
        span."""
        if not self.enabled:
            yield args
            return
        ann = self.annotate(name)
        t0 = clock()
        try:
            yield args
        finally:
            t1 = clock()
            if ann is not None:
                if args:
                    ann.set_metadata(**args)
                close(ann)
            self.add(name, t0, t1 - t0, **args)

    def instant(self, name: str, clock=time.perf_counter, **args) -> None:
        """Zero-duration marker (joins, detections, compiles, ...)."""
        close(self.annotate(name, **args))
        self.add(name, clock(), 0.0, **args)

    def reset(self) -> None:
        self._events.clear()
        self.dropped = 0

    # -- reporting -----------------------------------------------------------

    def spans(self, name: str | None = None) -> list[dict]:
        """Retained spans as dicts (seconds, tracer-epoch-relative)."""
        return [
            {"name": n, "t0": t0, "dur_s": dur, "tid": tid, "args": args}
            for n, t0, dur, tid, args in self._events
            if name is None or n == name
        ]

    def export_chrome(self, path=None, last: int | None = None):
        """Chrome trace-event JSON: a list when ``path`` is None, else
        written to ``path`` (``{"traceEvents": [...]}`` object form) and
        the event count returned.  ``last`` keeps only the trailing N
        spans — bench artifacts stay small without truncating the ring.

        Spans export as ``ph: "X"`` complete events (microsecond ``ts`` +
        ``dur``), which Perfetto nests by containment per thread.
        """
        events = list(self._events)
        if last is not None:
            events = events[-last:]
        tids = {}
        out = []
        for name, t0, dur, tid, args in events:
            tids.setdefault(tid, len(tids))
            ev = {
                "name": name,
                "ph": "X",
                "ts": t0 * 1e6,
                "dur": dur * 1e6,
                "pid": 0,
                "tid": tids[tid],
            }
            if args:
                ev["args"] = args
            out.append(ev)
        meta = [
            {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
             "args": {"name": self.process_name}},
        ]
        if path is None:
            return meta + out
        with open(path, "w") as f:
            json.dump({"traceEvents": meta + out, "displayTimeUnit": "ms"},
                      f)
            f.write("\n")
        return len(out)


def close(ann) -> None:
    """Close an annotation from ``Tracer.annotate`` (None: nothing open)."""
    if ann is not None:
        ann.__exit__(None, None, None)


def _dur(e: dict) -> float:
    return e["dur"] if "dur" in e else e["dur_s"]


def _start(e: dict) -> float:
    return e["ts"] if "dur" in e else e["t0"]


def _intervals(events: list[dict], names) -> list[tuple[float, float]]:
    """(start, end) of every span named in ``names``, in input units
    (Chrome events: microseconds; ``Tracer.spans()`` dicts: seconds)."""
    return [
        (_start(e), _start(e) + _dur(e))
        for e in events
        if e["name"] in names and ("dur" in e or "dur_s" in e)
    ]


def _union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge intervals into a disjoint sorted union."""
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _measure(iv: list[tuple[float, float]]) -> float:
    return sum(b - a for a, b in iv)


def _intersect(xs: list[tuple[float, float]],
               ys: list[tuple[float, float]]) -> float:
    """Total overlap between two disjoint sorted interval unions."""
    tot, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            tot += b - a
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def coverage(events: list[dict], parent: str = "hop",
             phases: tuple[str, ...] = ("pack", "dispatch", "fence",
                                        "fetch", "detector", "push_fold"),
             mode: str = "tile") -> float:
    """Fraction of ``parent`` span wall time covered by phase spans.

    Operates on exported Chrome events (or ``Tracer.spans()`` dicts with
    ``dur_s``).  ``mode="tile"`` (the synchronous invariant) ratios
    summed durations: the hop phases are stamped back-to-back, so
    anything under the 0.95 acceptance floor means a phase went missing
    from the instrumentation — but under the async plane it double
    counts, because hop N+1's pack/dispatch legitimately overlap hop N's
    fence span (the ratio can exceed 1.0).  ``mode="overlap"`` is the
    overlap-aware invariant: the measure of the *union* of phase
    intervals clipped to the union of parent intervals, over the parent
    union's measure — overlap never double counts and a missing phase
    still drops it below the floor.
    """
    if mode == "tile":
        tot = sum(_dur(e) for e in events if e["name"] == parent)
        cov = sum(_dur(e) for e in events if e["name"] in phases)
        return cov / tot if tot else 0.0
    assert mode == "overlap", mode
    par = _union(_intervals(events, (parent,)))
    phs = _union(_intervals(events, phases))
    tot = _measure(par)
    return _intersect(phs, par) / tot if tot else 0.0


def overlap_stats(events: list[dict],
                  busy: tuple[str, ...] = ("fence", "fetch"),
                  hidden_under: tuple[str, ...] = ("pack", "detector"),
                  ) -> dict[str, float]:
    """Union-interval account of how much host work hid under device
    compute — the async plane's acceptance measure.

    ``busy`` spans (the wait on the device and the result copy,
    including queue wait at retire) merge into one busy union; every
    ``hidden_under`` span's overlap with that union counts as hidden.
    Returns totals in the input's
    time unit (seconds for ``Tracer.spans()`` dicts, microseconds for
    exported Chrome events) plus the unit-free ``hidden_frac`` and
    ``utilization`` (busy fraction of the overall span extent).
    """
    busy_u = _union(_intervals(events, busy))
    host_iv = _intervals(events, hidden_under)
    host_u = _union(host_iv)
    host_total = _measure(host_u)
    hidden = _intersect(host_u, busy_u)
    everything = _union(_intervals(
        events, {e["name"] for e in events if "dur" in e or "dur_s" in e}
    ))
    extent = (everything[-1][1] - everything[0][0]) if everything else 0.0
    busy_total = _measure(busy_u)
    return {
        "busy_total": busy_total,
        "host_total": host_total,
        "hidden": hidden,
        "hidden_frac": hidden / host_total if host_total else 0.0,
        "extent": extent,
        "utilization": busy_total / extent if extent else 0.0,
    }
