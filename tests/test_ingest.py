"""Vectorized ingest plane (stream.state.RingArena + scheduler hot path):
arena push/pop/pack semantics incl. wraparound and boundary validation,
batched pushes == sequential pushes, the slot-vectorized detector ==
the per-stream state machine, scheduler sid errors, and the property-style
bit-exactness sweep (random ragged float/u8 chunks, B in {1, 8, 64},
across one grow + one shrink) against the offline executor."""
import jax
import numpy as np
import pytest

from repro.core import compiler, executor
from repro.models import kws
from repro.stream import (
    AudioFrontend,
    BatchedDetector,
    DetectorConfig,
    PosteriorDetector,
    RingArena,
    StreamScheduler,
    plan_stream,
    quantize_pcm,
)
from repro.stream.detector import _softmax


@pytest.fixture(scope="module")
def smoke():
    spec = kws.build_kws_smoke_spec()
    params = kws.init_kws_params(jax.random.PRNGKey(0), spec)
    weights, thresholds = kws.export_kws(params, spec)
    prog = compiler.compile_model(spec, weights, thresholds)
    return spec, weights, thresholds, prog


def _offline(prog, x):
    return executor.Executor(prog).run(x[:, None]).output.ravel()


# ---------------------------------------------------------------------------
# RingArena semantics
# ---------------------------------------------------------------------------

def test_arena_push_pop_wraparound():
    arena = RingArena(3, 7)  # tiny so pointers lap the region many times
    rng = np.random.default_rng(0)
    fed = {s: [] for s in range(3)}
    drained = {s: [] for s in range(3)}
    for i in range(40):
        slot = i % 3
        free = 7 - arena.fill_of(slot)
        chunk = rng.integers(
            0, 256, min(free, int(rng.integers(1, 5)))
        ).astype(np.uint8)
        arena.push(slot, chunk)
        fed[slot].append(chunk)
        n = min(arena.fill_of(slot), int(rng.integers(1, 6)))
        drained[slot].append(arena.pop(slot, n))
    for s in range(3):
        drained[s].append(arena.pop(s, arena.fill_of(s)))
        np.testing.assert_array_equal(
            np.concatenate(fed[s]), np.concatenate(drained[s])
        )
    assert arena.fill().tolist() == [0, 0, 0]
    # monotonic counters, wrapped storage
    assert (arena.rd == arena.wr).all() and (arena.wr > 7).all()


def test_arena_over_underflow():
    arena = RingArena(2, 4)
    arena.push(0, np.ones(3, np.uint8))
    with pytest.raises(MemoryError):
        arena.push(0, np.ones(2, np.uint8))
    with pytest.raises(MemoryError):
        arena.pop(0, 4)
    with pytest.raises(MemoryError):
        arena.pack_hops(np.array([0, 1]), 2)  # slot 1 holds nothing
    assert arena.fill_of(0) == 3  # failed ops leave the arena intact


def test_arena_push_boundary_validation():
    """Satellite: malformed audio is rejected AT the push boundary with a
    clear error, not silently widened like the old (n, 1) int32 rings."""
    arena = RingArena(2, 16)
    with pytest.raises(ValueError, match=r"out of u8 range"):
        arena.push(0, np.array([0, 300], np.int32))
    with pytest.raises(ValueError, match=r"out of u8 range"):
        arena.push(0, np.array([-1, 5], np.int64))
    with pytest.raises(TypeError, match=r"float PCM or integer u8"):
        arena.push(0, np.array([True, False]))
    with pytest.raises(ValueError, match=r"unique"):
        arena.push_batch(np.array([1, 1]), [np.ones(1, np.uint8)] * 2)
    assert arena.fill().tolist() == [0, 0]  # nothing landed
    # in-range non-uint8 integers are fine (offline clips arrive as such)
    arena.push(0, np.array([0, 128, 255], np.int64))
    assert arena.pop(0, 3).tolist() == [0, 128, 255]
    # the arena stores u8, 4x smaller than the old int32 rings
    assert arena.data.dtype == np.uint8
    assert arena.pack_hops(np.array([], np.int64), 4).dtype == np.int32


def test_arena_push_batch_matches_sequential():
    """One vectorized quantize+scatter == per-stream pushes, with float
    PCM and u8 codes mixed in the same call and per-slot gains honored."""
    rng = np.random.default_rng(1)
    a = RingArena(5, 64)
    b = RingArena(5, 64)
    for arena in (a, b):
        arena.set_gain(2, 0.5)
        arena.set_gain(4, 2.0)
    chunks = [
        rng.integers(0, 256, 7).astype(np.uint8),
        rng.uniform(-1.2, 1.2, 9),                      # float64, clips
        rng.uniform(-1, 1, 5).astype(np.float32),       # gain 0.5
        np.zeros(0, np.uint8),                          # empty is legal
        rng.uniform(-1, 1, 11),                         # gain 2.0
    ]
    a.push_batch(np.arange(5), chunks)
    for slot, c in enumerate(chunks):
        b.push(slot, c)
    np.testing.assert_array_equal(a.data, b.data)
    assert a.fill().tolist() == b.fill().tolist() == [7, 9, 5, 0, 11]
    np.testing.assert_array_equal(
        a.peek(2), quantize_pcm(chunks[2], 0.5).astype(np.int32)
    )


def test_arena_pack_hops_gathers_and_consumes():
    arena = RingArena(4, 8)
    arena.push_batch(
        np.array([0, 2, 3]),
        [np.full(6, 9, np.uint8), np.arange(5, dtype=np.uint8),
         np.full(3, 7, np.uint8)],
    )
    ready = np.nonzero(arena.ready_mask(4))[0]
    assert ready.tolist() == [0, 2]
    out = arena.pack_hops(ready, 4)
    assert out.shape == (4, 4) and out.dtype == np.int32
    assert out[0].tolist() == [9, 9, 9, 9]
    assert out[2].tolist() == [0, 1, 2, 3]
    assert out[1].tolist() == out[3].tolist() == [0, 0, 0, 0]  # masked rows
    assert arena.fill().tolist() == [2, 0, 1, 3]  # hop consumed
    # wrapped second hop continues seamlessly
    arena.push(2, np.array([5, 6, 7], np.uint8))
    np.testing.assert_array_equal(arena.pack_hops(np.array([2]), 4)[2],
                                  [4, 5, 6, 7])


def _ref_code(x, gain: float) -> int:
    """One sample's u8 code, by hand: float PCM through the offset-binary
    quantizer in float64 (round half to even), integers as they are."""
    if isinstance(x, float):
        v = min(max(x * gain, -1.0), 1.0) * 127.0
        return min(max(round(v) + 128, 0), 255)
    return int(x)


class _RefArena:
    """Per-sample python twin of RingArena's push side: sample i of a
    chunk lands at column ``(wr + i) % cap`` of its slot's row."""

    def __init__(self, slots: int, cap: int) -> None:
        self.cap = cap
        self.data = [[0] * cap for _ in range(slots)]
        self.rd, self.wr = [0] * slots, [0] * slots
        self.samples_in, self.chunks_in = [0] * slots, [0] * slots
        self.total_samples_in = self.total_chunks_in = 0

    def push(self, slot: int, chunk: np.ndarray, gain: float) -> bool:
        wraps = self.wr[slot] % self.cap + chunk.size > self.cap
        row, wr = self.data[slot], self.wr[slot]
        for i, x in enumerate(chunk.tolist()):
            row[(wr + i) % self.cap] = _ref_code(x, gain)
        self.wr[slot] += chunk.size
        self.samples_in[slot] += chunk.size
        self.chunks_in[slot] += 1
        self.total_samples_in += chunk.size
        self.total_chunks_in += 1
        return wraps


_PUSH_DTYPES = (np.uint8, np.int64, np.float32, np.float64)


@pytest.mark.parametrize("cap,seed", [(1, 0), (2, 1), (3, 2), (5, 3),
                                      (8, 4), (13, 5)])
def test_arena_push_batch_matches_per_sample_reference(cap, seed):
    """Property: push_batch lands every sample exactly where a per-sample
    ring would — u8, int64 and float32/float64 chunks mixed in one call
    with per-slot gains, chunks that cross the row end, fill exactly the
    free space, equal the whole capacity, or are empty — and keeps the
    pointers, counters and totals the reference keeps, leaving the
    seqlock generation even after every call."""
    n_slots = 6
    rng = np.random.default_rng(seed)
    arena, ref = RingArena(n_slots, cap), _RefArena(n_slots, cap)
    gains = rng.choice([1.0, 0.5, 2.0, 0.3], n_slots)
    for slot, g in enumerate(gains.tolist()):
        arena.set_gain(slot, g)
    seen = set()
    for _ in range(80):
        k = int(rng.integers(1, n_slots + 1))
        slots = rng.choice(n_slots, k, replace=False)
        chunks, want_wrapped = [], 0
        for slot in slots.tolist():
            free = cap - (ref.wr[slot] - ref.rd[slot])
            kind = int(rng.integers(4))
            n = (0 if kind == 0 or free == 0 else free if kind == 1
                 else int(rng.integers(1, free + 1)))
            dtype = _PUSH_DTYPES[int(rng.integers(len(_PUSH_DTYPES)))]
            if np.dtype(dtype).kind == "f":
                c = rng.uniform(-1.3, 1.3, n).astype(dtype)
            else:
                c = rng.integers(0, 256, n).astype(dtype)
            chunks.append(c)
            wraps = ref.push(slot, c, float(gains[slot]))
            want_wrapped += wraps
            seen.add(np.dtype(dtype).name)
            for case, hit in (("empty", n == 0), ("wrap", wraps),
                              ("exact_free", 0 < n == free),
                              ("whole_cap", n == cap),
                              ("whole_cap_wraps", n == cap and wraps)):
                if hit:
                    seen.add(case)
        assert arena.push_batch(slots, chunks) == want_wrapped
        assert arena.generation % 2 == 0
        np.testing.assert_array_equal(arena.data, np.array(ref.data))
        assert arena.wr.tolist() == ref.wr
        assert arena.samples_in.tolist() == ref.samples_in
        assert arena.chunks_in.tolist() == ref.chunks_in
        assert arena.total_samples_in == ref.total_samples_in
        assert arena.total_chunks_in == ref.total_chunks_in
        # consume a random amount so later pushes start mid-row
        for slot in range(n_slots):
            m = int(rng.integers(0, ref.wr[slot] - ref.rd[slot] + 1))
            want = [ref.data[slot][(ref.rd[slot] + i) % cap]
                    for i in range(m)]
            assert arena.pop(slot, m).tolist() == want
            ref.rd[slot] += m
    want = {"empty", "exact_free", "whole_cap", "uint8", "int64", "float32",
            "float64"}
    if cap > 1:  # a one-sample row has no end to cross
        want |= {"wrap", "whole_cap_wraps"}
    assert seen >= want


@pytest.mark.parametrize("bad", ["duplicate", "overflow", "dtype", "range"])
def test_arena_rejected_push_leaves_arena_untouched(bad):
    """A push rejected at the boundary — even one whose float chunks
    were already quantized — lands nothing, bumps no counter and leaves
    the seqlock generation where it was."""
    arena = RingArena(4, 8)
    arena.push_batch(np.array([0, 1]), [np.arange(6, dtype=np.uint8),
                                        np.arange(3, dtype=np.uint8)])
    arena.pop(0, 4)
    before = (arena.data.copy(), arena.rd.copy(), arena.wr.copy(),
              arena.samples_in.copy(), arena.chunks_in.copy(),
              arena.total_samples_in, arena.total_chunks_in,
              arena.generation)
    slots = np.array([0, 1, 2])
    chunks = [np.full(5, 9, np.uint8),                  # wraps slot 0's row
              np.linspace(-1, 1, 4),                    # float, quantized
              np.array([1, 2], np.int64)]
    err = {"duplicate": ValueError, "overflow": MemoryError,
           "dtype": TypeError, "range": ValueError}[bad]
    if bad == "duplicate":
        slots = np.array([0, 1, 0])
    elif bad == "overflow":
        chunks[1] = np.zeros(6)                         # 5 free in slot 1
    elif bad == "dtype":
        chunks[2] = np.array([True, False])
    else:
        chunks[2] = np.array([7, 256], np.int64)
    with pytest.raises(err):
        arena.push_batch(slots, chunks)
    after = (arena.data, arena.rd, arena.wr, arena.samples_in,
             arena.chunks_in, arena.total_samples_in, arena.total_chunks_in,
             arena.generation)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    assert arena.push_batch(np.array([0]), [chunks[0]]) == 1  # then lands


def test_frontend_facade_over_shared_arena():
    """The per-stream AudioFrontend API is a view of one shared arena."""
    arena = RingArena(3, 32)
    f1 = AudioFrontend(arena=arena, slot=1)
    f1.push(np.array([1, 2, 3], np.uint8))
    assert len(f1) == 3 and f1.samples_in == 3
    assert arena.fill().tolist() == [0, 3, 0]
    np.testing.assert_array_equal(f1.peek_all(), [1, 2, 3])
    np.testing.assert_array_equal(f1.pop(2), [1, 2])
    assert f1.pop_all().tolist() == [3] and len(f1) == 0
    # standalone construction still works (private 1-row arena)
    f2 = AudioFrontend()
    f2.push(np.zeros(4, np.uint8))
    assert len(f2) == 4


# ---------------------------------------------------------------------------
# BatchedDetector == PosteriorDetector
# ---------------------------------------------------------------------------

def test_batched_detector_matches_per_stream():
    """The slot-vectorized state machine is bit-identical to one
    PosteriorDetector per stream: same events (frame/cls/score), same
    hysteresis/refractory behavior, window longer than numpy's pairwise
    threshold to pin the summation-order contract."""
    cfg = DetectorConfig(smooth_frames=5, on_threshold=0.3,
                         off_threshold=0.15, refractory_frames=4)
    n_cls, n_streams = 12, 3
    batched = BatchedDetector(8, n_cls, cfg)
    slots = np.array([1, 4, 6])
    refs = [PosteriorDetector(i, cfg) for i in range(n_streams)]
    rng = np.random.default_rng(5)
    got: dict[int, list] = {i: [] for i in range(n_streams)}
    for frame in range(60):
        posts = np.stack([_softmax(rng.normal(0, 6, n_cls))
                          for _ in range(n_streams)])
        frames = np.full(n_streams, frame)
        rows, cls, score = batched.update_batch(slots, frames, posts)
        for r, c, sc in zip(rows, cls, score):
            got[int(r)].append((frame, int(c), float(sc)))
        for i, ref in enumerate(refs):
            ref.update_posterior(frame, posts[i])
    fired_any = False
    for i, ref in enumerate(refs):
        want = [(e.frame, e.cls, e.score) for e in ref.events]
        assert got[i] == want  # bitwise: scores compare exactly
        fired_any |= bool(want)
    assert fired_any  # the random walk actually exercised the machine


def test_batched_detector_remap_carries_state():
    """apply_remap moves a slot's window/hold/refractory state with it —
    continuing on the new slot equals an uninterrupted reference."""
    cfg = DetectorConfig(smooth_frames=3, on_threshold=0.3,
                         off_threshold=0.15, refractory_frames=4)
    n_cls = 12
    batched = BatchedDetector(4, n_cls, cfg)
    ref = PosteriorDetector(0, cfg)
    rng = np.random.default_rng(9)
    events = []
    for frame in range(30):
        if frame == 11:  # mid-run shrink: slot 3 -> 1
            batched.apply_remap({3: 1, 0: 0}, 2)
        slot = 3 if frame < 11 else 1
        post = _softmax(rng.normal(0, 6, n_cls))
        rows, cls, score = batched.update_batch(
            np.array([slot]), np.array([frame]), post[None, :]
        )
        if rows.size:
            events.append((frame, int(cls[0]), float(score[0])))
        ref.update_posterior(frame, post)
    assert events == [(e.frame, e.cls, e.score) for e in ref.events]
    assert events  # state machine fired across the remap


# ---------------------------------------------------------------------------
# Scheduler: sid errors + batched API
# ---------------------------------------------------------------------------

def test_push_audio_unknown_sid_raises_keyerror(smoke):
    """Satellite: pushing to an unknown/ended sid raises KeyError naming
    the live sid set, on both the scalar and the batched entry point."""
    spec, weights, thresholds, _ = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=4)
    a, b = sched.add_stream(), sched.add_stream()
    with pytest.raises(KeyError, match=r"unknown.*sid 99.*2 live.*0.*1"):
        sched.push_audio(99, np.zeros(8, np.uint8))
    sched.push_audio(b, np.zeros(8, np.uint8))
    sched.close_stream(b)  # ended: its sid must now be rejected too
    with pytest.raises(KeyError, match=r"sid 1"):
        sched.push_audio(b, np.zeros(8, np.uint8))
    with pytest.raises(KeyError, match=r"sid 1"):
        sched.push_audio_batch([a, b], [np.zeros(4, np.uint8)] * 2)
    with pytest.raises(KeyError):
        sched.close_stream(b)
    assert len(sched._streams[a].frontend) == 0  # batch push was atomic


def test_step_batch_columnar_matches_step_tuples(smoke):
    """HopBatch (the zero-collation hot-path result) carries exactly what
    the tuple-per-stream step() API reports."""
    spec, weights, thresholds, _ = smoke
    a = StreamScheduler(spec, weights, thresholds, capacity=4)
    b = StreamScheduler(spec, weights, thresholds, capacity=4)
    rng = np.random.default_rng(21)
    clips = rng.integers(0, 256, (3, 600)).astype(np.uint8)
    for sched in (a, b):
        sids = [sched.add_stream() for _ in range(3)]
        sched.push_audio_batch(sids, list(clips))
    outs = a.run_until_starved()
    hops = []
    while True:
        hb = b.step_batch()
        if hb is None:
            break
        hops.append(hb)
    flat = [
        (int(sid), int(fr), hb.logits[r])
        for hb in hops
        for r, (sid, fr) in enumerate(zip(hb.sids, hb.frames))
    ]
    assert len(outs) == len(flat)
    for (sid_a, fr_a, lg_a, _), (sid_b, fr_b, lg_b) in zip(outs, flat):
        assert (sid_a, fr_a) == (sid_b, fr_b)
        np.testing.assert_array_equal(lg_a, lg_b)
    m = b.metrics.summary()
    assert m["host_pack_ms_p50"] >= 0.0
    assert m["step_ms_p50"] >= m["host_pack_ms_p50"]
    ps = b.metrics.phase_summary()
    assert ps["fence"]["ms_p50"] + ps["fetch"]["ms_p50"] > 0.0


def test_push_audio_batch_coalesces_duplicate_sids(smoke):
    """Satellite: a sid appearing multiple times in one batch coalesces
    (arrival order, float/u8 dtypes preserved per chunk) instead of
    tripping RingArena.push_batch's unique-slots ValueError — and the
    result is bit-identical to sequential pushes."""
    spec, weights, thresholds, _ = smoke
    a = StreamScheduler(spec, weights, thresholds, capacity=4)
    b = StreamScheduler(spec, weights, thresholds, capacity=4)
    rng = np.random.default_rng(33)
    f0 = rng.uniform(-1.0, 1.0, 37)                    # float PCM
    u1 = rng.integers(0, 256, 21).astype(np.uint8)     # u8 codes
    u0 = rng.integers(0, 256, 13).astype(np.uint8)
    f0b = rng.uniform(-1.0, 1.0, 9).astype(np.float32)
    for sched in (a, b):
        s0, s1 = sched.add_stream(), sched.add_stream()
    a.push_audio_batch([s0, s1, s0, s0], [f0, u1, u0, f0b])
    for sid, chunk in ((s0, f0), (s1, u1), (s0, u0), (s0, f0b)):
        b.push_audio(sid, chunk)
    np.testing.assert_array_equal(a._arena.data, b._arena.data)
    assert a._arena.fill().tolist() == b._arena.fill().tolist()
    # chunk accounting stays arrival-accurate through the coalesce
    assert a._arena.chunks_in.tolist() == b._arena.chunks_in.tolist()
    assert a._arena.total_chunks_in == b._arena.total_chunks_in == 4
    # and the streams compute identically from here
    outs_a, outs_b = a.run_until_starved(), b.run_until_starved()
    assert len(outs_a) == len(outs_b)
    for (sa, fa, la, _), (sb, fb, lb, _) in zip(outs_a, outs_b):
        assert (sa, fa) == (sb, fb)
        np.testing.assert_array_equal(la, lb)
    # malformed dtypes are still rejected on the coalesce path
    with pytest.raises(TypeError, match=r"float PCM or integer u8"):
        a.push_audio_batch([s0, s0], [np.array([True]), np.array([False])])


def test_ingest_span_counts_chunks_wrapped_at_row_end(smoke):
    """The ``ingest`` span's ``wrapped`` arg is the number of chunks that
    crossed their arena row's end: 0 for a push that fits, exactly the
    crossing chunks after the streams' read pointers moved mid-row."""
    spec, weights, thresholds, _ = smoke
    plan = plan_stream(spec)
    sched = StreamScheduler(spec, weights, thresholds, capacity=4,
                            inbox_samples=plan.prime_samples
                            + 2 * plan.hop_samples)
    arena = sched._arena
    cap = arena.capacity_samples
    rng = np.random.default_rng(21)
    sids = [sched.add_stream() for _ in range(3)]
    fill = [cap, plan.prime_samples + plan.hop_samples, 7]
    sched.push_audio_batch(
        sids, [rng.integers(0, 256, n).astype(np.uint8) for n in fill])
    assert sched.obs.trace.spans("ingest")[-1]["args"]["wrapped"] == 0
    sched.drain()  # primes and consumes whole hops: rd moves mid-row
    slots = np.array([sched._streams[s].slot for s in sids])
    start = arena.wr[slots] % cap
    free = cap - arena.fill()[slots]
    want = int((start + free > cap).sum())
    assert want == 2  # the two primed streams; the third never moved
    chunks = [rng.uniform(-1, 1, free[0]),              # float PCM
              rng.integers(0, 256, free[1]).astype(np.uint8),
              rng.integers(0, 256, free[2]).astype(np.int64)]
    sched.push_audio_batch(sids, chunks)
    span = sched.obs.trace.spans("ingest")[-1]
    assert span["args"] == {"chunks": 3, "samples": int(free.sum()),
                            "coalesced": 0, "wrapped": want}
    for slot, c in zip(slots.tolist(), chunks):
        got = arena.peek(slot)[-c.size:]
        if c.dtype.kind == "f":
            c = quantize_pcm(c)
        np.testing.assert_array_equal(got, c)


def test_push_counters_fold_without_per_sid_python(smoke):
    """Satellite: push-side counters accumulate in slot-indexed arena
    arrays and fold into the metrics at hop boundaries (fleet totals) and
    at close (per-stream) — the push path never walks per-sid counter
    objects."""
    spec, weights, thresholds, _ = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=4)
    plan = sched.plan
    sids = [sched.add_stream() for _ in range(3)]
    n = plan.prime_samples + 2 * plan.hop_samples
    rng = np.random.default_rng(8)
    clips = rng.integers(0, 256, (3, n)).astype(np.uint8)
    sched.push_audio_batch(sids, list(clips))          # 1 chunk each
    sched.push_audio(sids[0], clips[0][:5])            # +1 chunk, +5 samples
    assert sched.metrics.summary()["samples_pushed"] == 0.0  # no hop yet
    sched.drain()
    m = sched.metrics.summary()
    assert m["samples_pushed"] == float(3 * n + 5)
    assert m["chunks_pushed"] == 4.0
    res = sched.close_stream(sids[0])
    c = sched.metrics.streams[sids[0]]
    assert c.samples_in == n + 5 and c.chunks_in == 2
    assert res.samples == n + 5


def test_step_batch_profile_has_no_per_sid_python(smoke):
    """Satellite: the steady-state hop's python call count must not scale
    with the number of streams — profile one hop at B=4 and B=32 and
    demand identical call counts (any per-sid loop would add ~B calls)."""
    import cProfile
    import pstats

    spec, weights, thresholds, _ = smoke

    def profile_one_hop(B):
        cfg = DetectorConfig(on_threshold=2.0)  # nothing ever fires
        sched = StreamScheduler(spec, weights, thresholds, capacity=B,
                                initial_capacity=B, min_capacity=B,
                                detector_cfg=cfg)
        plan = sched.plan
        rng = np.random.default_rng(B)
        sids = [sched.add_stream() for _ in range(B)]
        warm = plan.prime_samples + plan.hop_samples
        audio = rng.integers(0, 256, (B, warm + plan.hop_samples)
                             ).astype(np.uint8)
        sched.push_audio_batch(sids, list(audio[:, :warm]))
        sched.drain()  # primes + traces the jitted step at this capacity
        sched.push_audio_batch(sids, list(audio[:, warm:]))
        prof = cProfile.Profile()
        prof.enable()
        batch = sched.step_batch()
        prof.disable()
        assert batch is not None and batch.sids.size == B
        stats = pstats.Stats(prof)
        for (_, _, name), (_, nc, *_rest) in stats.stats.items():
            # nothing that smells per-sid may appear at all
            assert "_require" not in name and "fill_of" not in name, name
        return sum(nc for (_, nc, *_r) in stats.stats.values())

    assert profile_one_hop(4) == profile_one_hop(32)


# ---------------------------------------------------------------------------
# Property-style bit-exactness sweep: ragged mixed-dtype chunks, elastic pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_streams,emit", [(1, True), (8, True), (64, False)])
def test_random_chunks_bitexact_across_grow_and_shrink(smoke, n_streams, emit):
    """Feed random-sized chunks (1..hop*3 samples, float PCM and u8 codes
    mixed, batched and scalar pushes mixed) through the arena path while
    the elastic pool grows once and shrinks once; every finalized logit
    must equal one whole-clip offline run."""
    spec, weights, thresholds, prog = smoke
    rng = np.random.default_rng(100 + n_streams)
    # float PCM is the source of truth; the offline run eats the codes the
    # arena's quantizer produces, so both paths see identical u8 streams
    pcm = rng.uniform(-1.1, 1.1, (n_streams, spec.in_len))
    codes = quantize_pcm(pcm)
    want = {j: _offline(prog, codes[j]) for j in range(n_streams)}

    cap0 = max(1, n_streams // 4)
    sched = StreamScheduler(
        spec, weights, thresholds, capacity=n_streams,
        initial_capacity=cap0, min_capacity=1, emit_logits=emit,
        inbox_samples=1024,  # small inbox: arena pointers wrap in-run
    )
    hop = sched.plan.hop_samples
    # first quarter joins early; the rest join mid-run to force a grow
    joined = [sched.add_stream() for _ in range(cap0)]
    pos = {j: 0 for j in joined}
    round_i = 0
    while any(p < spec.in_len for p in pos.values()):
        if round_i == 2 and len(joined) < n_streams:
            for j in range(len(joined), n_streams):
                assert sched.add_stream() == j
                joined.append(j)
                pos[j] = 0
        live = [j for j in joined if pos[j] < spec.in_len]
        sids, chunks = [], []
        for j in live:
            n = int(rng.integers(1, hop * 3 + 1))
            lo, hi = pos[j], min(pos[j] + n, spec.in_len)
            # mix dtypes: float PCM chunks and u8 code chunks interleave
            chunk = pcm[j, lo:hi] if rng.random() < 0.5 else codes[j, lo:hi]
            pos[j] = hi
            if rng.random() < 0.3:
                sched.push_audio(j, chunk)  # scalar path
            else:
                sids.append(j)
                chunks.append(chunk)
        if sids:
            sched.push_audio_batch(sids, chunks)
        sched.run_until_starved()
        round_i += 1
    grew_to = sched.capacity
    assert grew_to == n_streams or n_streams == 1
    # close three quarters -> the pool shrinks; survivors then flush too
    survivors = joined[-max(1, n_streams // 4):]
    for j in joined:
        if j in survivors:
            continue
        np.testing.assert_array_equal(sched.close_stream(j).logits, want[j])
    assert sched.capacity <= grew_to
    if n_streams > 1:
        assert sched.capacity < grew_to  # actually shrank
    for j in survivors:
        np.testing.assert_array_equal(sched.close_stream(j).logits, want[j])
    caps = [c for _, c in sched.metrics.capacity_events]
    if n_streams > 1:
        assert max(caps) == n_streams and caps[-1] < n_streams
