"""Observability plane: bounded instruments, traces, events, and their
integration with the streaming runtime.

Pins the contracts the always-on deployment depends on:

* histogram quantile estimates stay within the log-linear error bound
  against exact percentiles, for every shape of latency distribution;
* metrics memory is constant over 10k hops of join/close/resize churn
  (the unbounded-list leak this plane replaced cannot come back);
* device-phase timing is fenced — the jitted step's execution cost lands
  in the ``fence`` span, not wherever results happen to be forced;
* the spans reach a JAX profile only while one is being captured: with
  none, the hop path checks the gate once per span boundary and builds
  no annotation; with one, the hop's annotations nest in phase order;
* empty summaries report NaN, never a fabricated 0.0, and the report
  renders them as "—";
* sid reuse retires the first tenant's counters instead of clobbering;
* a dead shard inflates ``shard_summary``'s imbalance;
* ``_charge_scaled`` scales every *runtime* ledger field, so a grown
  EnergyLedger can't silently drop a counter from streaming accounting;
* the JSONL event log records every lifecycle event even when the human
  log mirror is rate-limited down to a handful of lines.
"""
from __future__ import annotations

import dataclasses
import json
import math

import jax
import numpy as np
import pytest

from repro.core.energy import EnergyLedger
from repro.launch.report import _num
from repro.models import kws
from repro.obs import (
    EventLog,
    Histogram,
    MetricsRegistry,
    Observability,
    Reservoir,
    Tracer,
    coverage,
)
from repro.obs import trace as trace_mod
from repro.stream import StreamScheduler, plan_stream
from repro.stream.metrics import StreamMetrics, _charge_scaled
from repro.utils.logging import RateLimiter


@pytest.fixture(scope="module")
def smoke():
    spec = kws.build_kws_smoke_spec()
    params = kws.init_kws_params(jax.random.PRNGKey(0), spec)
    weights, thresholds = kws.export_kws(params, spec)
    return spec, weights, thresholds


@pytest.fixture(scope="module")
def plan(smoke):
    return plan_stream(smoke[0], hop_frames=2)


# -- histogram ----------------------------------------------------------------


def _distributions():
    rng = np.random.default_rng(0)
    return {
        "lognormal": rng.lognormal(-6.0, 1.5, 5000),
        "uniform": rng.uniform(1e-4, 5e-1, 5000),
        "exponential": rng.exponential(2e-3, 5000) + 1e-6,
        "bimodal": np.concatenate(
            [rng.normal(1e-3, 1e-4, 2500), rng.normal(3e-2, 3e-3, 2500)]
        ).clip(1e-6),
    }


def test_histogram_quantile_error_bound():
    """Estimates stay within the log-linear bucket bound of the exact
    order statistics: each power-of-two range splits into ``lin`` linear
    sub-buckets, so the estimate must land within relative error 2/lin
    of the samples bracketing the target rank (a quantile that falls in
    a gap between modes is bracketed, not interpolated — interpolating
    across empty mass is a choice no bounded sketch can reproduce)."""
    for name, dist in _distributions().items():
        h = Histogram(name)
        for v in dist:
            h.record(v)
        srt = np.sort(dist)
        for q in (0.5, 0.95, 0.99, 0.999):
            rank = q * (len(srt) - 1)
            lo = float(srt[math.floor(rank)])
            hi = float(srt[math.ceil(rank)])
            est = h.quantile(q)
            bound = 2.0 / h.lin
            assert lo * (1 - bound) <= est <= hi * (1 + bound), (
                name, q, est, lo, hi
            )


def test_histogram_record_many_matches_record():
    rng = np.random.default_rng(1)
    vals = rng.lognormal(-5, 2, 2000)
    a, b = Histogram("a"), Histogram("b")
    for v in vals:
        a.record(v)
    b.record_many(vals)
    assert a.count == b.count and a.min == b.min and a.max == b.max
    assert a.sum == pytest.approx(b.sum)
    for q in (0.01, 0.5, 0.9, 0.99, 0.999):
        assert a.quantile(q) == b.quantile(q)


def test_histogram_empty_and_clamping():
    h = Histogram("h", lo=1e-3, hi=1.0)
    assert math.isnan(h.quantile(0.5))
    assert "p50" not in h.snapshot()  # strict JSON: no NaN in snapshots
    h.record(1e-9)   # underflow
    h.record(100.0)  # overflow
    # extremes are exact even though the samples clamped into edge buckets
    assert h.quantile(0.0) == 1e-9
    assert h.quantile(1.0) == 100.0
    assert h.min == 1e-9 and h.max == 100.0


def test_histogram_memory_is_fixed():
    h = Histogram("h")
    before = h.nbytes
    for v in np.random.default_rng(2).uniform(1e-6, 1e3, 20000):
        h.record(v)
    assert h.nbytes == before


# -- reservoir ----------------------------------------------------------------


def test_reservoir_exact_until_wrap():
    r = Reservoir(8)
    for i in range(8):
        r.record(float(i))
    assert not r.saturated  # exactly full still holds every sample
    assert sorted(r.values().tolist()) == [float(i) for i in range(8)]
    r.record(8.0)
    assert r.saturated
    assert len(r.values()) == 8  # last-N window, O(1) memory
    r.reset()
    assert r.count == 0 and not r.saturated


# -- registry -----------------------------------------------------------------


def test_registry_get_or_create_and_type_guard():
    reg = MetricsRegistry()
    c = reg.counter("hops")
    c.inc()
    assert reg.counter("hops") is c and c.value == 1
    reg.gauge("occ").set(3.5)
    reg.histogram("lat").record(0.5)
    with pytest.raises(TypeError):
        reg.histogram("hops")
    snap = reg.snapshot()
    assert snap["hops"] == 1 and snap["occ"] == 3.5
    json.loads(reg.to_json())  # strict JSON round-trips


# -- rate limiter + event log -------------------------------------------------


def test_rate_limiter_suppression_accounting():
    rl = RateLimiter(min_interval_s=10.0)
    ok, suppressed = rl.allow("join", now=0.0)
    assert ok and suppressed == 0
    for t in (1.0, 2.0, 3.0):
        ok, _ = rl.allow("join", now=t)
        assert not ok
    ok, _ = rl.allow("close", now=3.0)  # independent per key
    assert ok
    ok, suppressed = rl.allow("join", now=11.0)
    assert ok and suppressed == 3  # the dropped count surfaces


def test_event_log_writes_every_event_mirror_limited(tmp_path):
    """All 100 events reach the JSONL sink; the human log mirror is
    rate-limited to the first line per kind inside the interval."""
    import io
    import logging

    path = tmp_path / "events.jsonl"
    ev = EventLog(path=str(path), mirror_interval_s=3600.0)
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    logger = logging.getLogger("repro.obs.events")
    logger.addHandler(handler)
    try:
        for i in range(100):
            ev.emit("join", sid=i)
    finally:
        logger.removeHandler(handler)
    ev.close()
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(recs) == 100
    assert [r["seq"] for r in recs] == list(range(100))
    assert all(r["event"] == "join" for r in recs)
    assert buf.getvalue().count("join sid=") == 1


def test_event_log_ring_and_counts(tmp_path):
    ev = EventLog(capacity=4, mirror=False)
    for i in range(10):
        ev.emit("resize", new=i)
    ev.emit("close", sid=0)
    assert len(ev) == 4 and ev.seq == 11  # ring bounded, count exact
    assert ev.counts() == {"resize": 3, "close": 1}
    assert ev.tail(1)[0]["event"] == "close"


# -- tracer -------------------------------------------------------------------


def test_tracer_spans_and_chrome_export(tmp_path):
    tr = Tracer()
    t0 = 0.0
    tr.add_batch((
        ("pack", t0, 0.2, {"n": 4}),
        ("fence", 0.2, 0.7, {}),
        ("hop", t0, 0.9, {"n": 4}),
    ))
    with tr.span("resize", old=2, new=4):
        pass
    assert len(tr) == 4
    events = tr.export_chrome()
    names = [e["name"] for e in events]
    assert names[0] == "process_name"  # metadata record
    assert {"pack", "fence", "hop", "resize"} <= set(names)
    path = tmp_path / "trace.json"
    n = tr.export_chrome(path=str(path))
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) == n + 1
    hop = next(e for e in doc["traceEvents"] if e["name"] == "hop")
    assert hop["ph"] == "X" and hop["dur"] == pytest.approx(0.9e6)
    assert coverage(events, phases=("pack", "fence")) == pytest.approx(1.0)


def test_tracer_bounded_and_disabled():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.add("hop", float(i), 0.1)
    assert len(tr) == 4 and tr.dropped == 6
    off = Tracer(enabled=False)
    off.add("hop", 0.0, 0.1)
    with off.span("hop"):
        pass
    assert len(off) == 0


# -- metrics: bounded memory, NaN, sid reuse, shards, energy ------------------


def test_metrics_constant_memory_over_10k_steps(plan):
    """The leak fix: 10k hops of step + resize + join/close churn retain
    exactly as much memory as 2k hops."""
    m = StreamMetrics(plan, max_retained=64, reservoir=256)
    tr = Tracer(capacity=512)

    def hop(i):
        m.on_step(8, plan.frames_per_hop, 1e-3, host_pack_s=1e-4,
                  dispatch_s=2e-4, fence_s=5e-4, fetch_s=1e-4,
                  detector_s=1e-4)
        if i % 7 == 0:
            m.on_resize(8 << (i % 3))
        sid = i % 1000
        m.on_join(sid)
        m.on_close(sid)
        tr.add("hop", float(i), 1e-3)

    for i in range(2000):
        hop(i)
    footprint_2k = m.footprint_bytes()
    trace_2k = len(tr)
    for i in range(2000, 10000):
        hop(i)
    assert m.footprint_bytes() == footprint_2k
    assert len(tr) == trace_2k == tr.capacity
    assert len(m.capacity_events) <= 64 and m.resize_count == 1429
    assert len(m.streams) <= 64 + 1
    # exact totals survive the bounded retention
    assert m.steps == 10000 and m.streams_total == 10000
    assert m.latency_estimated  # reservoirs wrapped long ago...
    s = m.summary()
    assert s["latency_estimated"] == 1.0
    assert s["step_ms_p50"] == pytest.approx(1.0, rel=2.0 / 32)
    # ...and the histograms still cover every sample ever recorded
    assert m._wall_hist.count == 10000


def test_metrics_empty_summary_nan_not_zero(plan):
    m = StreamMetrics(plan)
    s = m.summary()
    for key in ("step_ms_p50", "step_ms_p95", "step_ms_p99", "step_ms_p999",
                "host_pack_ms_p50", "host_pack_ms_p95"):
        assert math.isnan(s[key]), key
    # non-latency aggregates legitimately start at zero
    assert s["samples_pushed"] == 0.0 and s["steps"] == 0.0
    assert set(m.phase_summary()) == {"pack", "dispatch", "fence", "fetch",
                                      "detector"}
    for p, d in m.phase_summary().items():
        assert math.isnan(d["ms_p50"]) and d["share_of_wall"] == 0.0, p


def test_report_renders_nan_and_missing_as_dash():
    assert _num({"x": float("nan")}, "x", ".3f") == "—"
    assert _num({}, "x", ".3f") == "—"
    assert _num({"x": 0.0}, "x", ".3f") == "0.000"  # measured zero is real


def test_sid_reuse_retires_first_tenant(plan):
    m = StreamMetrics(plan)
    m.on_join(5)
    m.on_detection(5)
    m.on_close(5, frames_out=7)
    first = m.streams[5]
    m.on_join(5)  # sid reused by a new tenant
    assert m.streams[5] is not first
    assert m.streams[5].detections == 0
    assert list(m.retired) == [first] and m.retired_total == 1
    assert first.detections == 1 and first.frames_out == 7
    assert m.streams_total == 2 and m.detections_total == 1


def test_closed_streams_evict_oldest_but_stay_inspectable(plan):
    m = StreamMetrics(plan, max_retained=4)
    for sid in range(10):
        m.on_join(sid)
        m.on_close(sid, frames_out=sid)
    assert set(m.streams) == {6, 7, 8, 9}  # most recent stay inspectable
    assert m.streams[9].frames_out == 9
    assert m.closed_total == 10


def test_shard_summary_dead_shard_inflates_imbalance(plan):
    m = StreamMetrics(plan, n_shards=4)
    for _ in range(5):
        m.on_step(12, plan.frames_per_hop, 1e-3, shard_counts=[4, 4, 4, 0])
    s = m.shard_summary()
    assert s["per_shard"][3]["stream_hops"] == 0
    assert s["per_shard"][0]["mean_occupancy"] == pytest.approx(4.0)
    # mean counts the dead shard: 4 / (12/4) = 4/3
    assert s["imbalance"] == pytest.approx(4.0 / 3.0)
    assert s["fleet_stream_hops"] == 60


def test_charge_scaled_covers_grown_ledger_fields():
    @dataclasses.dataclass
    class GrownLedger(EnergyLedger):
        dram_bits: int = 0  # a field EnergyLedger doesn't have today

    src = GrownLedger(dram_bits=7)
    src.charge_mac_op(10, 20, 30, 40)
    dst = GrownLedger()
    _charge_scaled(dst, src, 3)
    assert dst.dram_bits == 21  # runtime-generic: the new field scales too
    assert dst.macs == 30 and dst.phys_macs == 60
    assert dst.sa_decisions == 90 and dst.cycles == 120


def test_begin_window_resets_latency_not_lifecycle(plan):
    m = StreamMetrics(plan)
    m.on_join(0)
    for _ in range(3):
        m.on_step(4, plan.frames_per_hop, 1e-3)
    macs_before = m.ledger.macs
    m.begin_window()
    s = m.summary()
    assert s["steps"] == 0.0 and math.isnan(s["step_ms_p50"])
    assert s["streams"] == 1.0  # lifecycle survives
    assert m.ledger.macs == macs_before  # energy stays cumulative


def test_latency_estimated_flips_after_reservoir_wrap(plan):
    m = StreamMetrics(plan, reservoir=16)
    for _ in range(16):
        m.on_step(1, plan.frames_per_hop, 2e-3)
    assert not m.latency_estimated
    assert m.summary()["step_ms_p50"] == pytest.approx(2.0)  # exact
    m.on_step(1, plan.frames_per_hop, 2e-3)
    assert m.latency_estimated
    # the lazily-backfilled histogram covers all 17 samples
    assert m._wall_hist.count == 17
    assert m.summary()["step_ms_p50"] == pytest.approx(2.0, rel=2.0 / 32)


# -- scheduler integration: fencing, coverage, lifecycle ----------------------


def _stream_rounds(sched, n_streams, rounds, rng, warm: int = 4):
    """Prime + ``warm`` hops (compile lands here), then open a fresh
    metrics window and run ``rounds`` steady-state hops."""
    plan = sched.plan
    need = plan.prime_samples + (warm + rounds + 1) * plan.hop_samples
    audio = rng.integers(0, 256, (n_streams, need)).astype(np.uint8)
    sids = [sched.add_stream() for _ in range(n_streams)]
    pos = plan.prime_samples + (warm + 1) * plan.hop_samples
    sched.push_audio_batch(sids, list(audio[:, :pos]))
    sched.drain()
    sched.metrics.begin_window()
    sched.push_audio_batch(sids, list(audio[:, pos:]))
    sched.drain()
    return sids


def test_device_phase_dominates_at_large_batch(smoke):
    """The fencing regression: ``block_until_ready`` sits at the device
    span boundary, so the jitted step's execution cost lands between the
    dispatch stamp and the device stamp.  If the fence is removed, the
    wait silently moves to wherever results are first forced (the
    detector's host copy) and the device-side share collapses to enqueue
    time.  The CPU backend splits execution between "inside the dispatch
    call" and "behind the fence" at the whim of the scheduler, so the
    assertion pools dispatch+device — that sum is fence-bounded and
    load-stable where the individual split is not."""
    spec, weights, thresholds = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=128,
                            initial_capacity=128, min_capacity=128,
                            emit_logits=False)
    _stream_rounds(sched, 128, 8, np.random.default_rng(0))
    ps = sched.metrics.phase_summary()
    m = sched.metrics.summary()
    assert m["steps"] >= 4
    devside = (ps["fence"]["share_of_wall"] + ps["fetch"]["share_of_wall"]
               + ps["dispatch"]["share_of_wall"])
    assert devside > ps["pack"]["share_of_wall"]
    assert devside > ps["detector"]["share_of_wall"]
    assert devside > 0.5, ps  # execution, not host work, owns the hop
    # per hop too: launch plus the wait on the device dominate packing
    assert (ps["dispatch"]["ms_p50"] + ps["fence"]["ms_p50"]
            > m["host_pack_ms_p50"])


def test_trace_spans_cover_hop_wall(smoke):
    spec, weights, thresholds = smoke
    obs = Observability.create(mirror_events=False)
    sched = StreamScheduler(spec, weights, thresholds, capacity=8,
                            initial_capacity=8, min_capacity=8, obs=obs)
    _stream_rounds(sched, 8, 6, np.random.default_rng(1))
    events = obs.trace.export_chrome()
    names = {e["name"] for e in events}
    assert {"hop", "pack", "dispatch", "fence", "fetch", "detector",
            "push_fold", "prime_batch", "ingest"} <= names
    assert coverage(events) >= 0.95
    # phase stamps are consecutive: each hop is tiled exactly
    hops = [e for e in events if e["name"] == "hop"]
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in hops)


@pytest.fixture
def fake_profiler(monkeypatch):
    """Stands in for ``jax.profiler.TraceAnnotation``: counts the gate's
    checks and the annotations built, and logs each one opened and
    closed."""

    class FakeAnnotation:
        enabled = False
        checks = 0
        made = 0
        log: list = []

        @classmethod
        def is_enabled(cls):
            cls.checks += 1
            return cls.enabled

        def __init__(self, name, **args):
            type(self).made += 1
            self.name, self.args = name, args

        def __enter__(self):
            self.log.append(("open", self.name, dict(self.args)))
            return self

        def __exit__(self, *exc):
            self.log.append(("close", self.name, dict(self.args)))

        def set_metadata(self, **args):
            self.args.update(args)

    monkeypatch.setattr(trace_mod, "_Annotation", FakeAnnotation)
    return FakeAnnotation


def _primed(smoke, n_hops, seed):
    """A 4-stream scheduler primed and past its first (compiling) hop,
    with ``n_hops`` more hops buffered in every inbox."""
    spec, weights, thresholds = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=4,
                            initial_capacity=4, min_capacity=4)
    plan = sched.plan
    sids = [sched.add_stream() for _ in range(4)]
    n = plan.prime_samples + (1 + n_hops) * plan.hop_samples
    audio = np.random.default_rng(seed).integers(0, 256, (4, n))
    sched.push_audio_batch(sids, list(audio.astype(np.uint8)))
    assert sched.step_batch() is not None
    return sched, sids


def test_hop_path_without_a_profile_builds_no_annotation(smoke,
                                                        fake_profiler):
    sched, _ = _primed(smoke, 5, seed=4)
    fake_profiler.checks = 0
    assert sched.drain() == 5
    assert fake_profiler.made == 0 and fake_profiler.log == []
    # one check per boundary: hop+pack, dispatch, fence, fetch, detector,
    # push_fold (the hop's close needs none)
    assert fake_profiler.checks == 6 * 5
    assert len(sched.obs.trace.spans("hop")) == 6  # the ring records all


def test_hop_annotations_open_in_nesting_order(smoke, fake_profiler):
    sched, sids = _primed(smoke, 0, seed=5)
    fake_profiler.enabled = True
    plan = sched.plan
    chunk = np.full(plan.hop_samples, 7, np.uint8)
    sched.push_audio_batch(sids + sids[:1], [chunk] * 5)
    assert fake_profiler.log == [
        ("open", "repro.ingest", {}),
        ("close", "repro.ingest",
         {"chunks": 5, "samples": 5 * plan.hop_samples, "coalesced": 1,
          "wrapped": 0}),
    ]
    fake_profiler.log.clear()
    assert sched.step_batch() is not None
    seq = sched.obs.trace.spans("hop")[-1]["args"]["hop"]
    phases = ("pack", "dispatch", "fence", "fetch", "detector", "push_fold")
    want = [("open", "repro.hop")]
    for p in phases:
        want += [("open", "repro." + p), ("close", "repro." + p)]
    want.append(("close", "repro.hop"))
    assert [(k, n) for k, n, _ in fake_profiler.log] == want
    assert {a["hop"] for _, _, a in fake_profiler.log} == {seq}
    # the ring's spans of that hop carry the same sequence number
    ring = [s for s in sched.obs.trace.spans() if s["args"].get("hop") == seq]
    assert sorted(s["name"] for s in ring) == sorted(phases + ("hop",))


def test_scheduler_event_log_lifecycle(smoke, tmp_path):
    spec, weights, thresholds = smoke
    path = tmp_path / "events.jsonl"
    obs = Observability.create(event_path=str(path), mirror_events=False)
    sched = StreamScheduler(spec, weights, thresholds, capacity=8,
                            initial_capacity=2, min_capacity=2, obs=obs)
    sids = _stream_rounds(sched, 6, 4, np.random.default_rng(2))
    for sid in sids:
        sched.close_stream(sid)
    obs.events.close()
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    kinds = {r["event"] for r in recs}
    assert {"join", "mass_join", "resize", "close"} <= kinds
    assert sum(r["event"] == "join" for r in recs) == 6
    assert sum(r["event"] == "close" for r in recs) == 6
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs)
    resize = next(r for r in recs if r["event"] == "resize")
    assert resize["old"] < resize["new"]  # the pool grew under the joins


def test_metrics_summary_bit_compatible_with_reservoir(plan):
    """While the reservoir holds every sample, summary quantiles are
    np.percentile over the full sample list — bit-identical to the old
    unbounded implementation."""
    rng = np.random.default_rng(3)
    walls = rng.uniform(5e-4, 5e-3, 200)
    packs = rng.uniform(1e-5, 1e-4, 200)
    fences = walls - packs
    m = StreamMetrics(plan)
    for w, p, f in zip(walls, packs, fences):
        m.on_step(4, plan.frames_per_hop, float(w), host_pack_s=float(p),
                  fence_s=float(f))
    s = m.summary()
    assert s["step_ms_p50"] == float(np.percentile(walls, 50) * 1e3)
    assert s["step_ms_p95"] == float(np.percentile(walls, 95) * 1e3)
    assert s["step_ms_p999"] == float(np.percentile(walls, 99.9) * 1e3)
    assert s["host_pack_ms_p50"] == float(np.percentile(packs, 50) * 1e3)
    assert m.phase_summary()["fence"]["ms_p50"] == float(
        np.percentile(fences, 50) * 1e3
    )
    assert s["latency_estimated"] == 0.0
