"""Concurrency suite for the async execution plane (stream.async_plane).

The async scheduler's whole claim is that it changes WHEN host work runs
— never WHAT it computes.  This suite proves it:

  * interleaving property test: random schedules of ragged pushes / hop
    steps / joins / closes / peeks / drains (through grows, shrinks and —
    sharded — rebalances) executed on the synchronous and asynchronous
    schedulers with a controllable fake clock must produce bit-identical
    results: close logits/frames/samples, detection events, detector
    hysteresis state, peeks, and the event-log lifecycle;
  * race stress test: N producer threads feed the ingest pump while hops
    are in flight — no sample lost, duplicated, or torn (the arena's
    monotone ``samples_in`` reconciles exactly against pushes, closes
    reconcile against the offline executor on the full byte stream, and
    the seqlock generation guard never admits a torn read);
  * drain/close with a hop in flight retires the future and runs the
    ghost end-of-stream flush (regression vs the offline executor);
  * trace invariants: under overlap the old "spans tile the hop" sum
    double counts wall time, so ``coverage(mode="overlap")`` uses
    interval unions; the fence ∩ pack(N+1) overlap is *reported*
    (``overlap_stats``), not flagged, and each hop's interleaved phase
    spans are joined by the ``hop`` sequence number they carry.

Event-log note: with the ingest pump enabled, push *timing* (and hence
``mass_join`` batching granularity) is inherently racy, so the
deterministic property tests run with ``use_pump=False`` (pushes land
synchronously, schedules are exactly reproducible); the pump gets its
own stress + error-surfacing coverage.
"""
import dataclasses
import faulthandler
import threading

import jax
import numpy as np
import pytest

from repro.core import compiler, executor
from repro.models import kws
from repro.obs import Observability, coverage, overlap_stats
from repro.stream import AsyncStreamScheduler, StreamScheduler

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container without dev extras: seeded sweep still runs
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def smoke():
    spec = kws.build_kws_smoke_spec()
    params = kws.init_kws_params(jax.random.PRNGKey(0), spec)
    weights, thresholds = kws.export_kws(params, spec)
    prog = compiler.compile_model(spec, weights, thresholds)
    return spec, weights, thresholds, prog


def _offline(prog, x):
    return executor.Executor(prog).run(x[:, None]).output.ravel()


_prog_cache: dict[int, object] = {}


def _offline_n(smoke, codes: np.ndarray) -> np.ndarray:
    """Offline-executor logits for an utterance of ANY length: the
    compiled program's input geometry is static, so recompile the same
    spec/weights at ``len(codes)`` (cached per length) and run it —
    the oracle a stream closed after ``len(codes)`` samples must match."""
    spec, weights, thresholds, _prog = smoke
    n = len(codes)
    prog = _prog_cache.get(n)
    if prog is None:
        prog = compiler.compile_model(
            dataclasses.replace(spec, in_len=n), weights, thresholds)
        _prog_cache[n] = prog
    return _offline(prog, codes)


def _audio(sid: int, pos: int, n: int) -> np.ndarray:
    """Deterministic per-(sid, position) sample codes: any schedule that
    feeds stream ``sid`` its samples in order feeds identical bytes, so
    sync/async runs and the offline oracle all see the same stream."""
    idx = np.arange(pos, pos + n, dtype=np.uint64)
    return ((idx * 2654435761 + sid * 97003) % 251).astype(np.uint8)


class FakeClock:
    """Controllable monotone clock for deterministic hop stamps: every
    read ticks by ``tick`` (so span ordering mirrors call ordering
    exactly), and tests can ``advance`` it arbitrarily."""

    def __init__(self, tick: float = 1e-4) -> None:
        self.t = 0.0
        self.tick = tick

    def __call__(self) -> float:
        self.t += self.tick
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# Interleaving property test: sync == async for arbitrary schedules
# ---------------------------------------------------------------------------

_MAX_STREAMS = 8


def _run_schedule(cls, smoke, ops, **kw):
    """Interpret one schedule on a fresh scheduler; returns the full
    observable fingerprint (close results, peeks, detector digests,
    lifecycle events)."""
    spec, weights, thresholds, _prog = smoke
    obs = Observability.create(mirror_events=False)
    hop_cap = 64  # per-sid feed ceiling, in hops (bounds the inbox)
    kwargs = dict(capacity=_MAX_STREAMS, initial_capacity=2, min_capacity=2,
                  obs=obs, clock=FakeClock())
    if cls is AsyncStreamScheduler:
        kwargs["use_pump"] = False  # deterministic landing (see module doc)
    kwargs.update(kw)
    sched = cls(spec, weights, thresholds, **kwargs)
    hop = sched.plan.hop_samples
    limit = hop * hop_cap
    if sched._inbox_samples < limit:  # pragma: no cover - config guard
        limit = sched._inbox_samples
    fed: dict[int, int] = {}
    live: list[int] = []
    fingerprints: dict[int, tuple] = {}
    peeks: list[tuple] = []
    for op in ops:
        kind = op[0]
        if kind == "join":
            if len(live) < _MAX_STREAMS:
                sid = sched.add_stream()
                live.append(sid)
                fed[sid] = 0
        elif kind == "push" and live:
            sid = live[op[1] % len(live)]
            n = op[2] * (hop // 2 + 1)  # ragged: never a hop multiple
            if fed[sid] + n <= limit:
                sched.push_audio_batch([sid], [_audio(sid, fed[sid], n)])
                fed[sid] += n
        elif kind == "step":
            sched.step_batch()
        elif kind == "drain":
            sched.drain()
        elif kind == "peek" and live:
            sid = live[op[1] % len(live)]
            peeks.append((sid, sched.peek(sid).tobytes()))
        elif kind == "close" and live:
            sid = live.pop(op[1] % len(live))
            fingerprints[sid] = _close_fp(sched.close_stream(sid))
    sched.drain()
    digests = {
        sid: sched._detector.state_digest(sched._streams[sid].slot)
        for sid in live
    }
    for sid in list(live):
        fingerprints[sid] = _close_fp(sched.close_stream(sid))
    if isinstance(sched, AsyncStreamScheduler):
        assert sched.in_flight == 0
        sched.shutdown()
    return {
        "fp": fingerprints,
        "peeks": peeks,
        "fed": fed,
        "digests": digests,
        "events": obs.events.tail(),
        "resizes": sched.metrics.resize_count,
        "rebalances": sched.metrics.rebalances,
    }


def _close_fp(r) -> tuple:
    return (
        r.logits.tobytes(), r.frames, r.samples,
        tuple((d.cls, d.frame, d.score) for d in r.events),
    )


def _lifecycle(events, kinds=("join", "detection", "close")):
    """Per-sid ordered lifecycle + the global resize/rebalance/mass_join
    sequences — the event-log facts that must survive the async plane
    (global detection-vs-join interleaving is schedule-timing, per-sid
    ordering and barrier-pinned sequences are semantics)."""
    per_sid: dict[int, list] = {}
    for rec in events:
        if rec["event"] in kinds and "sid" in rec:
            per_sid.setdefault(rec["sid"], []).append(
                (rec["event"],
                 tuple(sorted((k, v) for k, v in rec.items()
                              if k in ("cls", "frame", "score", "frames",
                                       "samples", "events"))))
            )
    resizes = [(r["old"], r["new"]) for r in events if r["event"] == "resize"]
    mass = [r["n"] for r in events if r["event"] == "mass_join"]
    counts: dict[str, int] = {}
    for rec in events:
        counts[rec["event"]] = counts.get(rec["event"], 0) + 1
    return per_sid, resizes, mass, counts


def _assert_equiv(smoke, ops, **kw):
    sync = _run_schedule(StreamScheduler, smoke, ops, **kw)
    asyn = _run_schedule(AsyncStreamScheduler, smoke, ops, **kw)
    assert sync["fed"] == asyn["fed"]  # the interpreter fed both alike
    assert sync["fp"] == asyn["fp"], "close results diverged"
    assert sync["peeks"] == asyn["peeks"], "peeks diverged"
    assert sync["digests"] == asyn["digests"], "detector state diverged"
    assert sync["resizes"] == asyn["resizes"]
    assert sync["rebalances"] == asyn["rebalances"]
    assert _lifecycle(sync["events"]) == _lifecycle(asyn["events"])
    return sync


def _seeded_schedule(seed: int, n_ops: int = 60) -> list[tuple]:
    rng = np.random.default_rng(seed)
    ops: list[tuple] = [("join",), ("join",)]
    kinds = ["push", "push", "push", "step", "join", "close", "peek",
             "drain"]
    for _ in range(n_ops):
        k = kinds[int(rng.integers(0, len(kinds)))]
        ops.append((k, int(rng.integers(0, 64)), int(rng.integers(1, 4))))
    ops += [("drain",)]
    return ops


def test_interleaving_property_seeded(smoke):
    """Seeded schedule sweep (always runs, even without hypothesis):
    sync == async == offline through joins, ragged pushes, closes, peeks
    and at least one grow + one shrink."""
    spec, _w, _t, _prog = smoke
    grew = shrank = False
    checked_offline = 0
    for seed in range(4):
        sync = _assert_equiv(smoke, _seeded_schedule(seed, n_ops=50))
        # every closed stream that saw audio also matches the offline
        # executor on the exact bytes it was fed (the whole-utterance
        # program compiles at any length — bit-exactness end-to-end)
        for sid, n in sync["fed"].items():
            if n == 0:
                continue
            ref = _offline_n(smoke, _audio(sid, 0, n))
            got = np.frombuffer(sync["fp"][sid][0], np.int64)
            np.testing.assert_array_equal(got, ref)
            checked_offline += 1
        resizes = [(r["old"], r["new"]) for r in sync["events"]
                   if r["event"] == "resize"]
        grew = grew or any(new > old for old, new in resizes)
        shrank = shrank or any(new < old for old, new in resizes)
    assert grew and shrank, "sweep never exercised grow+shrink barriers"
    assert checked_offline > 0, "no stream was long enough for the oracle"


if HAVE_HYPOTHESIS:
    _op = st.one_of(
        st.tuples(st.just("push"), st.integers(0, 63), st.integers(1, 3)),
        st.tuples(st.just("step"), st.just(0), st.just(0)),
        st.tuples(st.just("join"), st.just(0), st.just(0)),
        st.tuples(st.just("close"), st.integers(0, 63), st.just(0)),
        st.tuples(st.just("peek"), st.integers(0, 63), st.just(0)),
        st.tuples(st.just("drain"), st.just(0), st.just(0)),
    )

    @settings(max_examples=10, deadline=None)
    @given(ops=st.lists(_op, min_size=6, max_size=40))
    def test_interleaving_property_hypothesis(smoke, ops):
        """Hypothesis-driven schedules (shrinks the failing schedule to a
        minimal op list on mismatch).  Skipped where hypothesis isn't
        installed; the seeded sweep above always runs."""
        _assert_equiv(smoke, [("join",), ("join",)] + list(ops) +
                      [("drain",)])


# ---------------------------------------------------------------------------
# Race stress: producer threads vs in-flight hops
# ---------------------------------------------------------------------------

@pytest.mark.timeout(300)
def test_ingest_pump_race_stress(smoke):
    """4 producer threads push ragged chunks through the pump while the
    main thread keeps hops in flight.  Every sample must land exactly
    once and untorn: the arena's monotone per-slot ``samples_in``
    reconciles against what each producer pushed, the close-time logits
    reconcile against the offline executor on the full byte stream, and
    lock-free seqlock readers never observe an inconsistent window."""
    faulthandler.dump_traceback_later(240, exit=True)
    try:
        spec, weights, thresholds, prog = smoke
        n_threads, sids_per, chunks_per = 4, 2, 30
        n = n_threads * sids_per
        sched = AsyncStreamScheduler(
            spec, weights, thresholds, capacity=n, initial_capacity=n,
            min_capacity=n, inbox_samples=8192,
            obs=Observability.create(mirror_events=False),
        )
        sids = [sched.add_stream() for _ in range(n)]
        pushed = {sid: 0 for sid in sids}

        def producer(t: int) -> None:
            rng = np.random.default_rng(1000 + t)
            mine = sids[t * sids_per:(t + 1) * sids_per]
            for _ in range(chunks_per):
                for sid in mine:
                    k = int(rng.integers(20, 180))
                    sched.push_audio_batch(
                        [sid], [_audio(sid, pushed[sid], k)]
                    )
                    pushed[sid] += k  # thread-local sid: no write race

        stop = threading.Event()
        violations: list = []

        def checker() -> None:
            arena = sched._arena
            while not stop.is_set():
                wr, rd = arena.read_consistent(
                    lambda: (arena.wr.copy(), arena.rd.copy())
                )
                fill = wr - rd
                if (fill < 0).any() or (fill > arena.capacity_samples).any():
                    violations.append((wr, rd))  # torn read admitted
                    return

        threads = [threading.Thread(target=producer, args=(t,))
                   for t in range(n_threads)]
        chk = threading.Thread(target=checker, daemon=True)
        for th in threads:
            th.start()
        chk.start()
        while any(th.is_alive() for th in threads):
            sched.step_batch()  # keep hops in flight under the pushes
        for th in threads:
            th.join()
        stop.set()
        chk.join(timeout=30)
        sched.drain()  # flushes the pump, retires in-flight hops
        assert not violations, "seqlock admitted a torn read"
        assert sched._arena.generation % 2 == 0  # no writer left open
        # exact reconcile: monotone per-slot counters vs producer truth
        for sid in sids:
            slot = sched._streams[sid].slot
            assert int(sched._arena.samples_in[slot]) == pushed[sid], sid
        # content reconcile: the flushed stream == offline on the exact
        # byte sequence — samples landed once, in order, untorn
        for sid in sids:
            r = sched.close_stream(sid)
            assert r.samples == pushed[sid]
            np.testing.assert_array_equal(
                r.logits, _offline_n(smoke, _audio(sid, 0, pushed[sid])))
        sched.shutdown()
    finally:
        faulthandler.cancel_dump_traceback_later()


def test_pump_surfaces_push_errors(smoke):
    """A pumped push to an unknown sid fails on the worker thread; the
    error surfaces at the next flush (and the pump keeps working)."""
    spec, weights, thresholds, _prog = smoke
    sched = AsyncStreamScheduler(
        spec, weights, thresholds, capacity=2, initial_capacity=2,
        min_capacity=2, obs=Observability.create(mirror_events=False),
    )
    sid = sched.add_stream()
    sched.push_audio(9999, np.zeros(8, np.uint8))  # unknown sid
    with pytest.raises(KeyError, match="9999"):
        sched.flush_ingest()
    sched.push_audio(sid, _audio(sid, 0, 64))
    sched.flush_ingest()  # error was consumed; valid pushes still land
    assert int(sched._arena.samples_in[sched._streams[sid].slot]) == 64
    sched.shutdown()


def test_arena_seqlock_parity(smoke):
    """Failed (validated-out) arena ops leave the generation untouched;
    successful mutations bump it by exactly 2 (odd only mid-write)."""
    from repro.stream import RingArena
    arena = RingArena(2, 16)
    g0 = arena.generation
    assert g0 % 2 == 0
    with pytest.raises(MemoryError):
        arena.push(0, np.zeros(32, np.uint8))  # overflow: rejected clean
    assert arena.generation == g0
    arena.push(0, np.zeros(8, np.uint8))
    assert arena.generation == g0 + 2
    out = arena.read_consistent(lambda: arena.fill_of(0))
    assert out == 8


# ---------------------------------------------------------------------------
# drain()/close with a hop in flight
# ---------------------------------------------------------------------------

def test_drain_retires_inflight_hops(smoke):
    """``drain()`` must flush the pump and retire in-flight futures:
    after it, nothing is unfolded and peeks match the offline prefix."""
    spec, weights, thresholds, prog = smoke
    sched = AsyncStreamScheduler(
        spec, weights, thresholds, capacity=2, initial_capacity=2,
        min_capacity=2, obs=Observability.create(mirror_events=False),
    )
    sid = sched.add_stream()
    plan = sched.plan
    total = plan.prime_samples + 5 * plan.hop_samples
    sched.push_audio(sid, _audio(sid, 0, total))
    sched.flush_ingest()
    sched.step_batch()  # primes + dispatches hop 1 — stays in flight
    assert sched.in_flight == 1
    hops = sched.drain()
    assert sched.in_flight == 0
    assert hops >= 4  # the remaining buffered hops all executed
    np.testing.assert_array_equal(
        sched.peek(sid), _offline_n(smoke, _audio(sid, 0, total)))
    sched.shutdown()


def test_close_with_hop_in_flight_matches_offline(smoke):
    """Regression for the drain/teardown contract: closing a stream
    while its hop is still executing must retire the future, fold it,
    then run the ghost end-of-stream flush — byte-identical to the
    offline executor over everything pushed (including a sub-hop
    tail)."""
    spec, weights, thresholds, prog = smoke
    sched = AsyncStreamScheduler(
        spec, weights, thresholds, capacity=2, initial_capacity=2,
        min_capacity=2, obs=Observability.create(mirror_events=False),
    )
    sid = sched.add_stream()
    plan = sched.plan
    total = plan.prime_samples + 3 * plan.hop_samples + 7  # ragged tail
    sched.push_audio(sid, _audio(sid, 0, total))
    sched.flush_ingest()
    sched.step_batch()
    sched.step_batch()
    assert sched.in_flight >= 1  # a hop really is mid-air
    r = sched.close_stream(sid)
    assert sched.in_flight == 0
    assert r.samples == total
    np.testing.assert_array_equal(
        r.logits, _offline_n(smoke, _audio(sid, 0, total)))
    sched.shutdown()


# ---------------------------------------------------------------------------
# Trace invariants under overlap
# ---------------------------------------------------------------------------

def test_coverage_overlap_mode_synthetic():
    """Pinned interval math: overlapping phases double count under the
    tile invariant but union-coverage stays exact, and ``overlap_stats``
    reports the host∩device overlap."""
    spans = [
        # hop 1: pack 0-1, fence 1-9 (retired late), fold 9-10
        {"name": "hop", "t0": 0.0, "dur_s": 10.0},
        {"name": "pack", "t0": 0.0, "dur_s": 1.0},
        {"name": "fence", "t0": 1.0, "dur_s": 8.0},
        {"name": "detector", "t0": 9.0, "dur_s": 1.0},
        # hop 2's pack+dispatch run INSIDE hop 1's fence span
        {"name": "hop", "t0": 2.0, "dur_s": 12.0},
        {"name": "pack", "t0": 2.0, "dur_s": 1.0},
        {"name": "fence", "t0": 3.0, "dur_s": 10.0},
        {"name": "detector", "t0": 13.0, "dur_s": 1.0},
    ]
    tile = coverage(spans, phases=("pack", "fence", "detector"))
    assert tile == pytest.approx(22.0 / 22.0)
    ov = coverage(spans, phases=("pack", "fence", "detector"),
                  mode="overlap")
    assert ov == pytest.approx(1.0)  # unions: no double count, no gap
    stats = overlap_stats(spans)
    # hop2 pack [2,3] ⊂ fence union [1,13]; hop1 detector [9,10] too
    assert stats["hidden"] == pytest.approx(2.0)
    assert stats["host_total"] == pytest.approx(4.0)
    assert stats["hidden_frac"] == pytest.approx(0.5)
    assert stats["utilization"] == pytest.approx(12.0 / 14.0)
    # a missing phase still sinks union coverage below the floor
    gappy = [s for s in spans if s["name"] != "fence"]
    assert coverage(gappy, phases=("pack", "fence", "detector"),
                    mode="overlap") < 0.5


def test_async_trace_overlap_invariants(smoke):
    """Deterministic (fake-clock) async run: each hop's phases still
    tile its own span, union coverage holds the 95% floor, and the
    device ∩ pack(N+1) overlap is reported as hidden wall — the PR 6
    tile assert's overlap-aware replacement."""
    spec, weights, thresholds, _prog = smoke
    obs = Observability.create(mirror_events=False)
    sched = AsyncStreamScheduler(
        spec, weights, thresholds, capacity=4, initial_capacity=4,
        min_capacity=4, obs=obs, clock=FakeClock(), use_pump=False,
        inbox_samples=1 << 13,
    )
    plan = sched.plan
    sids = [sched.add_stream() for _ in range(4)]
    total = plan.prime_samples + 16 * plan.hop_samples
    sched.push_audio_batch(sids, [_audio(s, 0, total) for s in sids])
    sched.drain()
    spans = obs.trace.spans()
    assert coverage(spans) >= 0.95  # per-hop tiling still holds
    ov = coverage(spans, mode="overlap")
    assert 0.95 <= ov <= 1.0 + 1e-9, ov
    stats = overlap_stats(spans)
    # pipelined: every pack but the first ran under an in-flight device
    # span, every fold but the last did too — reported, not flagged
    assert stats["hidden"] > 0.0
    assert stats["hidden_frac"] >= 0.8, stats
    assert sched.metrics.overlap_summary()["hidden_frac"] >= 0.8
    # the synchronous scheduler's trace reports no hidden wall
    obs2 = Observability.create(mirror_events=False)
    sync = StreamScheduler(
        spec, weights, thresholds, capacity=4, initial_capacity=4,
        min_capacity=4, obs=obs2, clock=FakeClock(),
        inbox_samples=1 << 13,
    )
    sids = [sync.add_stream() for _ in range(4)]
    sync.push_audio_batch(sids, [_audio(s, 0, total) for s in sids])
    sync.drain()
    assert sync.metrics.overlap_summary()["hidden_ms"] == 0.0
    assert coverage(obs2.trace.spans(), mode="overlap") >= 0.95


def test_async_hop_spans_join_by_sequence(smoke):
    """Under the pipeline, hop N+1's pack and dispatch fall inside hop N's
    span; the ``hop`` arg still groups every phase with its own hop, and
    each group's phases tile that hop's span (shared stamps)."""
    spec, weights, thresholds, _prog = smoke
    obs = Observability.create(mirror_events=False)
    sched = AsyncStreamScheduler(
        spec, weights, thresholds, capacity=4, initial_capacity=4,
        min_capacity=4, obs=obs, clock=FakeClock(), use_pump=False,
        inbox_samples=1 << 13,
    )
    plan = sched.plan
    sids = [sched.add_stream() for _ in range(4)]
    total = plan.prime_samples + 8 * plan.hop_samples
    sched.push_audio_batch(sids, [_audio(s, 0, total) for s in sids])
    assert sched.drain() == 8
    spans = obs.trace.spans()
    phases = ("pack", "dispatch", "fence", "fetch", "detector", "push_fold")
    by_hop: dict[int, dict] = {}
    for sp in spans:
        if "hop" in sp["args"]:
            by_hop.setdefault(sp["args"]["hop"], {})[sp["name"]] = sp
    assert sorted(by_hop) == list(range(1, 9))
    for seq, group in by_hop.items():
        assert set(group) == {*phases, "hop"}, seq
        assert sum(group[p]["dur_s"] for p in phases) == pytest.approx(
            group["hop"]["dur_s"])
        assert group["fetch"]["args"]["bytes"] > 0
    # hop 2 was packed while hop 1 was in flight
    hop1 = by_hop[1]["hop"]
    assert by_hop[2]["pack"]["t0"] < hop1["t0"] + hop1["dur_s"]
    assert coverage(spans, phases=phases, mode="overlap") >= 0.95
    sched.shutdown()


def test_ingest_span_runs_on_the_pump_thread(smoke):
    spec, weights, thresholds, _prog = smoke
    obs = Observability.create(mirror_events=False)
    sched = AsyncStreamScheduler(spec, weights, thresholds, capacity=4,
                                 obs=obs)
    sids = [sched.add_stream() for _ in range(2)]
    sched.push_audio_batch(sids, [_audio(s, 0, 300) for s in sids])
    sched.flush_ingest()
    ingest = obs.trace.spans("ingest")
    assert [sp["args"] for sp in ingest] == [
        {"chunks": 2, "samples": 600, "coalesced": 0, "wrapped": 0}]
    assert ingest[0]["tid"] != threading.get_ident()
    sched.shutdown()


# ---------------------------------------------------------------------------
# Sharded epoch barriers (runs on the CI multi-device leg)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >=2 devices (multi-device CI leg)")
def test_async_sharded_rebalance_barrier(smoke):
    """Cross-shard rebalance as an epoch barrier: skewed closes under a
    mesh trigger a migration on both schedulers at the same boundary,
    and every surviving stream stays bit-exact through it."""
    from repro.launch.mesh import make_stream_mesh
    spec, weights, thresholds, prog = smoke
    mesh = make_stream_mesh()
    S = jax.device_count()
    n = 2 * S

    def run(cls, **kw):
        sched = cls(spec, weights, thresholds, capacity=2 * n,
                    initial_capacity=n, min_capacity=S, mesh=mesh,
                    obs=Observability.create(mirror_events=False), **kw)
        plan = sched.plan
        sids = [sched.add_stream() for _ in range(n)]
        half = plan.prime_samples + 3 * plan.hop_samples
        for sid in sids:
            sched.push_audio(sid, _audio(sid, 0, half))
        sched.drain()
        # close every stream on shards 0..S/2 (least-loaded placement
        # spreads consecutive sids over the shards) -> skew -> migrate
        low = [sid for sid in sids
               if sched._streams[sid].slot // sched.shard_capacity < S // 2]
        assert len(low) == n // 2
        out = {sid: _close_fp(sched.close_stream(sid)) for sid in low}
        survivors = [sid for sid in sids if sid not in out]
        for sid in survivors:
            sched.push_audio(sid, _audio(sid, half, 2 * plan.hop_samples))
        sched.drain()
        out.update({sid: _close_fp(sched.close_stream(sid))
                    for sid in survivors})
        if isinstance(sched, AsyncStreamScheduler):
            sched.shutdown()
        return out, sched.metrics.rebalances

    sync_out, sync_reb = run(StreamScheduler)
    asyn_out, asyn_reb = run(AsyncStreamScheduler, use_pump=False)
    assert sync_out == asyn_out
    assert sync_reb == asyn_reb >= 1, "rebalance barrier never exercised"
    # offline oracle over the full fed stream for one migrated survivor
    sid = max(asyn_out)
    n_fed = asyn_out[sid][2]
    np.testing.assert_array_equal(
        np.frombuffer(asyn_out[sid][0], np.int64),
        _offline_n(smoke, _audio(sid, 0, n_fed)))


# ---------------------------------------------------------------------------
# LM engine: double-buffered decode
# ---------------------------------------------------------------------------

def test_engine_async_decode_bit_exact():
    """``Engine.step_async`` (device-resident token feedback, one-tick
    deferred host copy) produces token-identical outputs to the
    synchronous tick loop, through slot refills and shutdown drain."""
    from repro.configs.base import get_arch
    from repro.models import api
    from repro.serve.engine import Engine, Request

    cfg = get_arch("qwen3-0.6b", smoke=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))

    def run(async_mode):
        eng = Engine(cfg, params, batch_slots=2, max_seq=32,
                     obs=Observability.create(mirror_events=False))
        for i in range(5):
            eng.submit(Request(rid=i,
                               prompt=np.arange(6, dtype=np.int32) + i,
                               max_new_tokens=3))
        done = (eng.run_until_drained_async() if async_mode
                else eng.run_until_drained())
        assert not eng._pending
        return {r.rid: list(r.out_tokens) for r in done}

    sync, asyn = run(False), run(True)
    assert sync == asyn and set(sync) == set(range(5))
