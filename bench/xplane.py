"""Reduce a profiler trace (``.xplane.pb``) to busy time, op times and gaps.

The traced slice is the host span ``bench.window``.  On each device plane
(``/device:TPU:<n>``) the events of the ``XLA Ops`` line are the
operations that ran; their union is the device's busy time.  Idle gaps are
the rest of the slice, each named by the innermost ``bench.*`` host span
that holds the gap's middle: what the benchmark's host thread was doing
while the device waited.  Device and host events share the profiler's
clock.
"""
from __future__ import annotations

import collections

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"


def _union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_name(event_name: str) -> str:
    """``%fusion.12 = s32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ")[0].lstrip("%").strip()


def reduce_planes(planes) -> dict:
    """``planes``: iterable of (plane name, [(line name, [(name, start_ns,
    duration_ns), ...]), ...]).  Returns seconds throughout."""
    host_spans = []
    devices = {}
    for pname, lines in planes:
        if pname == HOST_PLANE:
            for _, events in lines:
                host_spans += [(s, s + d, n) for n, s, d in events
                               if n.startswith("bench.")]
        elif pname.startswith(DEVICE_PREFIX):
            for lname, events in lines:
                if lname == OPS_LINE:
                    devices[pname] = [(s, s + d, n) for n, s, d in events]
    win = [(a, b) for a, b, n in host_spans if n == WINDOW]
    if not win or not devices:
        raise ValueError("trace holds no bench.window span or no device ops")
    lo, hi = win[0]
    inner = [(a, b, n) for a, b, n in host_spans if n != WINDOW]
    op_time = collections.Counter()
    busy = []
    gaps = []
    for pname, evs in sorted(devices.items()):
        iv = []
        for a, b, n in evs:
            a2, b2 = max(a, lo), min(b, hi)
            if b2 > a2:
                iv.append((a2, b2))
                op_time[op_name(n)] += (b2 - a2) * 1e-9
        u = _union(iv)
        busy.append(sum(b - a for a, b in u) * 1e-9)
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                holders = [(sa, n) for sa, sb, n in inner if sa <= mid < sb]
                name = max(holders)[1] if holders else "bench.other"
                gaps.append([name, (b - a) * 1e-9])
    window_s = (hi - lo) * 1e-9
    gaps.sort(key=lambda g: -g[1])
    by_activity = collections.Counter()
    for name, s in gaps:
        by_activity[name] += s
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy_per_device_s": busy,
        "op_time_s": sum(op_time.values()),
        "device_ops": [[n, t] for n, t in op_time.most_common()],
        "idle_gaps": gaps,
        "idle_by_activity": [[n, t] for n, t in by_activity.most_common()],
        "n_devices": len(devices),
    }


def read_planes(path: str):
    """The planes of an ``.xplane.pb`` in ``reduce_planes``' form."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for pl in pd.planes:
        if pl.name != HOST_PLANE and not pl.name.startswith(DEVICE_PREFIX):
            continue
        lines = []
        for ln in pl.lines:
            if pl.name.startswith(DEVICE_PREFIX) and ln.name != OPS_LINE:
                continue
            lines.append((ln.name, [(e.name, e.start_ns, e.duration_ns)
                                    for e in ln.events]))
        out.append((pl.name, lines))
    return out


def reduce(path: str) -> dict:
    return reduce_planes(read_planes(path))
