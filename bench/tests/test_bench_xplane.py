"""The trace reducer, on a trace recorded on a TPU v5e and on hand-made
planes."""
import pytest

from bench_testkit import BENCH

import xplane

FIXTURE = BENCH / "tests" / "data" / "tpu_v5e_4steps.xplane.pb"


def test_recorded_tpu_trace():
    """Four hop steps at 4,096 slots, profiled on one v5e; the slice is
    the span of the four ``bench.step_batch`` annotations."""
    planes = xplane.read_planes(str(FIXTURE))
    names = [p for p, _ in planes]
    assert "/device:TPU:0" in names and "/host:CPU" in names
    host = dict(planes)["/host:CPU"]
    steps = [e for _, evs in host for e in evs if e[0] == "bench.step_batch"]
    assert len(steps) == 4
    lo = min(s for _, s, _ in steps)
    hi = max(s + d for _, s, d in steps)
    host.append(("slice", [("bench.window", lo, hi - lo)]))
    r = xplane.reduce_planes(planes)
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # the jitted step runs about 2.2 ms per hop on the device
    assert 4 * 1.5e-3 < r["busy_s"] < 4 * 3e-3
    assert r["op_time_s"] >= r["busy_s"] * 0.999
    assert sum(t for _, t in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert {n for n, _ in r["idle_gaps"]} <= {"bench.step_batch",
                                               "bench.other"}
    assert r["device_ops"][0][1] >= r["device_ops"][-1][1]


def _planes(ops, host):
    return [("/device:TPU:0", [("XLA Ops", ops)]),
            ("/host:CPU", [("python", host)])]


def test_union_clip_and_gap_names():
    ops = [("%a = f()", 100, 50), ("%b = g()", 120, 50),   # overlap: 100-170
           ("%a = f()", 300, 100),                         # clipped at 350
           ("%c = h()", 0, 20)]                            # before the slice
    host = [("bench.window", 50, 300), ("bench.step_batch", 60, 100),
            ("bench.push", 200, 50), ("bench.account", 210, 10)]
    r = xplane.reduce_planes(_planes(ops, host))
    assert r["window_s"] == pytest.approx(300e-9)
    assert r["busy_s"] == pytest.approx((70 + 50) * 1e-9)
    # 50-100 (step_batch), 170-300 (mid 235: push), none after 350
    assert [n for n, _ in r["idle_gaps"]] == ["bench.push",
                                              "bench.step_batch"]
    assert [t for _, t in r["idle_gaps"]] == pytest.approx([130e-9, 50e-9])
    assert dict(r["device_ops"])["a"] == pytest.approx(100e-9)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce_planes(_planes([("%a = f()", 0, 1)], []))
