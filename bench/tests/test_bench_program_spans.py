"""The split of device idle time among the program's ``repro.*`` spans, on
a profile recorded on a TPU v5e and on hand-made planes."""
import pytest

from bench_testkit import BENCH

import program_spans
import xplane

FIXTURE = BENCH / "tests" / "data" / "tpu_v5e_hop_spans.xplane.pb"
PHASES = ("pack", "dispatch", "fence", "fetch", "detector", "push_fold")


def _modules(path):
    """(start, end) of the device's ``XLA Modules`` events, by name."""
    from jax.profiler import ProfileData

    out = {}
    for pl in ProfileData.from_file(str(path)).planes:
        if pl.name.startswith(xplane.DEVICE_PREFIX):
            for ln in pl.lines:
                if ln.name == "XLA Modules":
                    for e in ln.events:
                        out.setdefault(e.name.split("(")[0], []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return out


def test_recorded_tpu_hops_nest_their_phases():
    """One ``push_audio_batch`` and three hop steps at 4,096 slots,
    profiled on one v5e with the program's spans bridged in."""
    planes = xplane.read_planes(str(FIXTURE))
    spans = sorted((s, s + d, n[len("repro."):])
                   for _, evs in dict(planes)[xplane.HOST_PLANE]
                   for n, s, d in evs if n.startswith("repro."))
    assert sum(n == "ingest" for *_, n in spans) == 1
    hops = [(a, b) for a, b, n in spans if n == "hop"]
    steps = _modules(FIXTURE)["jit_kws_hop_step"]
    assert len(hops) == len(steps) == 3
    for (lo, hi), (m0, m1) in zip(hops, sorted(steps)):
        inside = {n: (a, b) for a, b, n in spans
                  if lo <= a and b <= hi and n != "hop"}
        assert sorted(inside) == sorted(PHASES)
        order = [inside[p] for p in PHASES]
        assert all(x[1] <= y[0] for x, y in zip(order, order[1:]))
        # the device runs the step between its launch and the fence's end
        assert inside["dispatch"][0] <= m0 and m1 <= inside["fence"][1]
    assert program_spans.span_counts(planes) == {
        "repro." + n: 3 for n in ("hop",) + PHASES} | {"repro.ingest": 1}
    split = program_spans.idle_by_program_span(planes)
    red = xplane.reduce_planes(planes)
    assert sum(t for _, t in split) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert split[0][0] == "repro.ingest"


def _planes(ops, host, ops2=None, host2=()):
    dev = [("/device:TPU:0", [("XLA Ops", ops)])]
    if ops2 is not None:
        dev.append(("/device:TPU:1", [("XLA Ops", ops2)]))
    return dev + [("/host:CPU", [("python", host), ("pump", list(host2))])]


def test_one_gap_is_split_exactly_between_two_spans():
    ops = [("%a = f()", 0, 100), ("%b = g()", 900, 100)]    # idle 100-900
    host = [("bench.window", 0, 1000), ("repro.hop", 0, 1000),
            ("repro.fence", 50, 350), ("repro.fetch", 400, 300)]
    got = dict(program_spans.idle_by_program_span(_planes(ops, host)))
    assert got == pytest.approx({"repro.fence": 300e-9,
                                 "repro.fetch": 300e-9,
                                 "repro.hop": 200e-9})


def test_idle_under_no_program_span_is_none():
    ops = [("%a = f()", 100, 100)]
    host = [("bench.window", 0, 1000), ("bench.push", 0, 1000),
            ("repro.ingest", 300, 200)]
    got = dict(program_spans.idle_by_program_span(_planes(ops, host)))
    # 0-100 and 200-300 and 500-1000 lie under no program span
    assert got == pytest.approx({"none": 700e-9, "repro.ingest": 200e-9})


def test_the_split_sums_to_the_window_less_busy():
    """Two devices, spans on two host threads, nested and overlapping; the
    later start is the inner span whichever thread it is on."""
    ops = [("%a = f()", 120, 80), ("%b = g()", 500, 150)]
    ops2 = [("%a = f()", 130, 300)]
    host = [("bench.window", 100, 800), ("repro.hop", 110, 600),
            ("repro.pack", 110, 40), ("repro.dispatch", 150, 20),
            ("repro.fence", 170, 400), ("repro.hop", 650, 300),
            ("repro.pack", 650, 100)]
    pump = [("repro.ingest", 300, 120), ("repro.ingest", 880, 50)]
    planes = _planes(ops, host, ops2, pump)
    split = program_spans.idle_by_program_span(planes)
    red = xplane.reduce_planes(planes)
    assert sum(t for _, t in split) == pytest.approx(
        red["window_s"] - red["busy_s"])
    got = dict(split)
    # device 0 idles 100-120, 200-500 and 650-900; device 1 100-130 and
    # 430-900.  Ingest (300-420 on the pump thread, started inside the
    # fence) holds 120 of device 0's idle time, and 880-900 on both
    assert got["repro.ingest"] == pytest.approx((120 + 20 + 20) / 2e9)
    assert got["repro.fence"] == pytest.approx((100 + 80 + 140) / 2e9)
    assert got["none"] == pytest.approx((10 + 10) / 2e9)
    assert program_spans.span_counts(planes) == {
        "repro.hop": 2, "repro.pack": 2, "repro.dispatch": 1,
        "repro.fence": 1, "repro.ingest": 2}
