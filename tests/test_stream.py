"""Streaming runtime (repro.stream): golden equivalence with the offline
executor, ring-buffer wraparound, mid-batch join/leave, the in-jit
finalization tail (per-hop logits == offline prefix), elastic slot-pool
resize boundaries, detector hysteresis, and the batched Pallas kernels."""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiler, executor
from repro.core.cnn_spec import CNN1DSpec, Conv1DSpec, FCSpec, GAPSpec
from repro.kernels import ops, ref
from repro.models import kws
from repro.stream import (
    DetectorConfig,
    FrameRing,
    PosteriorDetector,
    StreamScheduler,
    StreamState,
    plan_stream,
)
from repro.stream.detector import _softmax

RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def smoke():
    spec = kws.build_kws_smoke_spec()
    params = kws.init_kws_params(jax.random.PRNGKey(0), spec)
    weights, thresholds = kws.export_kws(params, spec)
    prog = compiler.compile_model(spec, weights, thresholds)
    return spec, weights, thresholds, prog


def _offline(prog, x):
    return executor.Executor(prog).run(x[:, None]).output.ravel()


def _clip(spec, seed):
    return np.random.default_rng(seed).integers(
        0, 256, (spec.in_len,)
    ).astype(np.uint8)


# ---------------------------------------------------------------------------
# Plan geometry
# ---------------------------------------------------------------------------

def test_plan_steady_state_geometry(smoke):
    spec, *_ = smoke
    plan = plan_stream(spec)
    # hop = prod(stride*pool) per final frame; KWS: 8*1 * 1*2 * 1*2 * 1*2
    assert plan.hop_samples == 64 and plan.frames_per_hop == 1
    n_in = plan.hop_samples
    for st in plan.convs:
        assert st.n_in == n_in
        assert st.n_conv * st.stride == st.n_in
        assert st.n_conv % st.pool == 0
        assert 0 <= st.phase < st.pool
        n_in = st.n_out
    # larger hops scale every stage linearly
    plan4 = plan_stream(spec, hop_frames=4)
    assert plan4.hop_samples == 256 and plan4.frames_per_hop == 4


def test_plan_flush_geometry(smoke):
    """The static finalization-tail counts must match both the count model
    and what a real numpy flush emits from the steady state."""
    spec, weights, thresholds, _ = smoke
    for hf in (1, 4):
        plan = plan_stream(spec, hop_frames=hf)
        f_in = 0
        for st in plan.convs:
            assert st.flush_in == f_in
            avail = st.tail + f_in + st.pad
            want = (avail - st.k) // st.stride + 1 if avail >= st.k else 0
            assert st.flush_conv == want
            assert st.flush_out == (st.phase + st.flush_conv) // st.pool
            f_in = st.flush_out
        # a primed stream's ghost flush emits exactly flush_out final frames
        st0 = StreamState(plan, weights, thresholds)
        st0.advance(_clip(spec, 9)[: plan.prime_samples + plan.hop_samples])
        ghost = st0.clone()
        emitted = ghost.advance(np.zeros((0,), np.int32), flush=True)
        assert emitted.shape[0] == plan.convs[-1].flush_out


# ---------------------------------------------------------------------------
# Ring buffer
# ---------------------------------------------------------------------------

def test_frame_ring_wraparound():
    ring = FrameRing(7, 3)
    total_in, total_out = [], []
    for i in range(25):  # pointers lap the 7-slot region multiple times
        f = np.full((2, 3), i)
        ring.push(f)
        total_in.append(f)
        got = ring.pop(2 if i % 2 else 1)
        total_out.append(got)
        if i % 2 == 0:
            total_out.append(ring.pop(1))
    np.testing.assert_array_equal(
        np.concatenate(total_in), np.concatenate(total_out)
    )
    assert len(ring) == 0
    assert ring.wr == ring.rd == 50  # monotonic counters, wrapped storage


def test_frame_ring_over_underflow():
    ring = FrameRing(4, 1)
    ring.push(np.ones((3, 1)))
    with pytest.raises(MemoryError):
        ring.push(np.ones((2, 1)))
    with pytest.raises(MemoryError):
        ring.pop(4)
    assert len(ring) == 3  # failed ops leave the ring intact


def test_stream_state_rings_wrap(smoke):
    """Tiny ring slack forces every hist ring to wrap many times; the
    results must not change."""
    spec, weights, thresholds, prog = smoke
    plan = plan_stream(spec)
    x = _clip(spec, 1)
    big = StreamState(plan, weights, thresholds)
    small = StreamState(plan, weights, thresholds, ring_slack=plan.hop_samples)
    for st in (big, small):
        for i in range(0, spec.in_len, 160):
            st.advance(x[i : i + 160])
        st.advance(np.zeros((0,), np.int32), flush=True)
    np.testing.assert_array_equal(big.logits(), small.logits())
    np.testing.assert_array_equal(big.logits(), _offline(prog, x))


# ---------------------------------------------------------------------------
# Golden equivalence: streaming == offline executor
# ---------------------------------------------------------------------------

def test_stream_matches_offline_full_clip(smoke):
    spec, weights, thresholds, prog = smoke
    plan = plan_stream(spec)
    x = _clip(spec, 2)
    st = StreamState(plan, weights, thresholds)
    i = 0
    for sz in itertools.cycle([37, 200, 111, 64, 5]):  # ragged chunks
        st.advance(x[i : i + sz])
        i += sz
        if i >= spec.in_len:
            break
    st.advance(x[i:] if i < spec.in_len else np.zeros((0,), np.int32),
               flush=True)
    np.testing.assert_array_equal(st.logits(), _offline(prog, x))


@pytest.mark.parametrize("k", [3, 5])
def test_stream_kernel_narrower_than_stride_matches_offline(k):
    """With k < stride the next window can start past the frames that
    have arrived: ragged chunks and the flush carry that skip instead of
    underflowing the ring (every prefix peek == offline executor)."""
    spec = CNN1DSpec(
        in_len=700, in_channels=1, in_bits=4, name="narrow",
        layers=(
            Conv1DSpec(1, 8, k=k, stride=8, pad=1, in_bits=4, in_offset=8,
                       name="l0"),
            Conv1DSpec(8, 8, k=5, stride=1, pad=2, pool=2, name="b1"),
            GAPSpec(8, name="gap"),
            FCSpec(8, 8, in_bits=8, name="fc1"),
            FCSpec(8, kws.N_CLASSES, out_raw=True, name="fc2"),
        ),
    )
    params = kws.init_kws_params(jax.random.PRNGKey(k), spec)
    weights, thresholds = kws.export_kws(params, spec)
    x = np.random.default_rng(k).integers(0, 16, (spec.in_len,)).astype(
        np.uint8)
    st = StreamState(plan_stream(spec), weights, thresholds)
    i = 0
    for sz in itertools.cycle([37, 13, 64, 5, 90]):
        st.advance(x[i : i + sz])
        i = min(i + sz, spec.in_len)
        prog_i = compiler.compile_model(
            dataclasses.replace(spec, in_len=i), weights, thresholds)
        np.testing.assert_array_equal(st.peek_logits(), _offline(prog_i,
                                                                 x[:i]))
        if i == spec.in_len:
            break


@pytest.mark.parametrize("prefix", [320, 520, 648])
def test_stream_peek_matches_offline_prefix(smoke, prefix):
    """Per-frame logits contract: peek after audio[:L] == offline run on
    audio[:L] (same weights, shorter program)."""
    spec, weights, thresholds, _ = smoke
    x = _clip(spec, 3)
    spec_l = kws.build_kws_spec(in_len=prefix, width=16)
    prog_l = compiler.compile_model(spec_l, weights, thresholds)
    st = StreamState(plan_stream(spec), weights, thresholds)
    st.advance(x[: prefix - 100])
    st.advance(x[prefix - 100 : prefix])
    np.testing.assert_array_equal(
        st.peek_logits(), _offline(prog_l, x[:prefix])
    )
    assert not st.flushed  # peek is non-destructive
    st.advance(x[prefix:], flush=True)  # stream continues normally


# ---------------------------------------------------------------------------
# Scheduler: continuous batching, join/leave mid-batch
# ---------------------------------------------------------------------------

def test_scheduler_join_leave_mid_batch(smoke):
    spec, weights, thresholds, prog = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=3,
                            hop_frames=1, emit_logits=False)
    clips = {j: _clip(spec, 10 + j) for j in range(4)}
    want = {j: _offline(prog, clips[j]) for j in range(4)}

    a = sched.add_stream()
    b = sched.add_stream()
    sched.push_audio(a, clips[0][:500])
    sched.push_audio(b, clips[1][:200])  # b is a straggler
    sched.run_until_starved()

    # c joins while a/b are mid-flight
    c = sched.add_stream()
    sched.push_audio(c, clips[2])
    sched.push_audio(a, clips[0][500:])
    sched.run_until_starved()

    # a leaves; its slot is recycled by d mid-run
    res_a = sched.close_stream(a)
    np.testing.assert_array_equal(res_a.logits, want[0])
    d = sched.add_stream()
    sched.push_audio(d, clips[3])
    sched.push_audio(b, clips[1][200:])
    sched.run_until_starved()

    for sid, j in ((b, 1), (c, 2), (d, 3)):
        res = sched.close_stream(sid)
        np.testing.assert_array_equal(res.logits, want[j])
    assert sched.active == []


def test_scheduler_peek_matches_offline_prefix(smoke):
    spec, weights, thresholds, _ = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=2,
                            emit_logits=False)
    x = _clip(spec, 20)
    prefix = 520
    spec_l = kws.build_kws_spec(in_len=prefix, width=16)
    off = _offline(compiler.compile_model(spec_l, weights, thresholds),
                   x[:prefix])
    sid = sched.add_stream()
    sched.push_audio(sid, x[:prefix])
    sched.run_until_starved()  # leaves a sub-hop remainder in the inbox
    np.testing.assert_array_equal(sched.peek(sid), off)


def test_scheduler_capacity_enforced(smoke):
    spec, weights, thresholds, _ = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=1)
    sched.add_stream()
    with pytest.raises(MemoryError):
        sched.add_stream()


# ---------------------------------------------------------------------------
# In-jit finalization tail: per-hop logits == offline executor on the prefix
# ---------------------------------------------------------------------------

def test_scheduler_hop_logits_match_offline_prefix(smoke):
    """Each hop's emitted logits (computed on-device by the fused
    finalization tail) equal an offline executor run over exactly the
    samples consumed so far."""
    spec, weights, thresholds, _ = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=2)
    plan = sched.plan
    x = _clip(spec, 40)
    sid = sched.add_stream()
    sched.push_audio(sid, x[: spec.in_len // 2])
    outs = sched.run_until_starved()
    assert len(outs) >= 2
    for hop_i in (0, len(outs) - 1):  # first and latest hop boundaries
        consumed = plan.prime_samples + (hop_i + 1) * plan.hop_samples
        spec_l = kws.build_kws_spec(in_len=consumed, width=16)
        prog_l = compiler.compile_model(spec_l, weights, thresholds)
        np.testing.assert_array_equal(
            outs[hop_i][2], _offline(prog_l, x[:consumed])
        )


def test_scheduler_peek_on_hop_boundary_uses_device_tail(smoke):
    """peek() with an empty inbox reads the in-jit tail and must agree with
    the logits emitted at the last hop."""
    spec, weights, thresholds, _ = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=2)
    plan = sched.plan
    x = _clip(spec, 41)
    sid = sched.add_stream()
    sched.push_audio(sid, x[: plan.prime_samples + 2 * plan.hop_samples])
    outs = sched.run_until_starved()
    assert len(outs) == 2 and len(sched._streams[sid].frontend) == 0
    np.testing.assert_array_equal(sched.peek(sid), outs[-1][2])


def test_scheduler_peek_cached_across_masked_steps(smoke):
    """A stream idle at a hop boundary keeps peeking its own last logits
    (served from the emit cache) while OTHER streams advance — masked
    rows ride through later finalizations unchanged."""
    spec, weights, thresholds, _ = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=2)
    plan = sched.plan
    a, b = sched.add_stream(), sched.add_stream()
    xa, xb = _clip(spec, 43), _clip(spec, 44)
    sched.push_audio(a, xa[: plan.prime_samples + 2 * plan.hop_samples])
    sched.push_audio(b, xb[: plan.prime_samples + 2 * plan.hop_samples])
    outs = sched.run_until_starved()
    want_a = [o[2] for o in outs if o[0] == a][-1]
    # only b advances now; a sits masked at its hop boundary
    sched.push_audio(b, xb[plan.prime_samples + 2 * plan.hop_samples :
                           plan.prime_samples + 4 * plan.hop_samples])
    sched.run_until_starved()
    np.testing.assert_array_equal(sched.peek(a), want_a)
    # and a freshly primed stream (no emit step yet) still peeks exactly
    c_sched = StreamScheduler(spec, weights, thresholds, capacity=2)
    c = c_sched.add_stream()
    c_sched.push_audio(c, xa[: c_sched.plan.prime_samples])
    c_sched.step()  # primes c; nothing ready -> no emit
    spec_l = kws.build_kws_spec(in_len=c_sched.plan.prime_samples, width=16)
    prog_l = compiler.compile_model(spec_l, weights, thresholds)
    np.testing.assert_array_equal(
        c_sched.peek(c), _offline(prog_l, xa[: c_sched.plan.prime_samples])
    )


def test_scheduler_pallas_hop_logits_match_jnp(smoke):
    """The pallas step + fused classifier-tail kernel emit the same per-hop
    logits as the jnp reference path."""
    spec, weights, thresholds, _ = smoke
    x = _clip(spec, 42)
    outs = {}
    for backend in ("jnp", "pallas"):
        sched = StreamScheduler(spec, weights, thresholds, capacity=2,
                                hop_frames=4, backend=backend)
        sid = sched.add_stream()
        sched.push_audio(sid, x)
        outs[backend] = sched.run_until_starved()
    assert len(outs["jnp"]) == len(outs["pallas"]) >= 1
    for a, b in zip(outs["jnp"], outs["pallas"]):
        assert a[:2] == b[:2]
        np.testing.assert_array_equal(a[2], b[2])


# ---------------------------------------------------------------------------
# Elastic slot pool: grow/shrink resize boundaries are bit-exact
# ---------------------------------------------------------------------------

def test_scheduler_elastic_capacity_lifecycle(smoke):
    spec, weights, thresholds, _ = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=4)
    assert sched.capacity == 2 and sched.max_capacity == 4
    sids = [sched.add_stream() for _ in range(4)]  # forces a 2 -> 4 grow
    assert sched.capacity == 4
    with pytest.raises(MemoryError):
        sched.add_stream()  # ceiling still enforced
    for sid in sids:
        sched.close_stream(sid)
    assert sched.capacity == 2  # pool shrank back
    assert sched.metrics.summary()["resizes"] >= 2.0


def test_scheduler_grow_shrink_bitexact(smoke):
    """A stream fed across a 4->8 grow and an 8->4 shrink produces per-hop
    and flushed logits bit-identical to a fixed-capacity run."""
    spec, weights, thresholds, prog = smoke
    clips = {j: _clip(spec, 60 + j) for j in range(8)}
    want = {j: _offline(prog, clips[j]) for j in range(8)}
    el = StreamScheduler(spec, weights, thresholds, capacity=8,
                         initial_capacity=4)
    fx = StreamScheduler(spec, weights, thresholds, capacity=8,
                         initial_capacity=8, min_capacity=8)  # pinned pool

    def lockstep(stage):
        a = el.run_until_starved()
        b = fx.run_until_starved()
        assert len(a) == len(b), stage
        for ea, eb in zip(a, b):
            assert ea[:2] == eb[:2], stage
            np.testing.assert_array_equal(ea[2], eb[2])
        return a

    # 4 streams fit the elastic pool's initial capacity exactly
    for sched in (el, fx):
        sids = [sched.add_stream() for _ in range(4)]
        assert sids == list(range(4))
        for j in range(4):
            sched.push_audio(j, clips[j][:300])
    lockstep("warm")
    assert el.capacity == 4

    # 4 more join -> elastic pool grows 4 -> 8 with streams 0..3 mid-flight
    for sched in (el, fx):
        for j in range(4, 8):
            assert sched.add_stream() == j
            sched.push_audio(j, clips[j][:600] if j >= 6 else clips[j])
        for j in range(4):
            sched.push_audio(j, clips[j][300:])
    lockstep("grow")
    assert el.capacity == 8

    # streams 0..5 leave -> pool shrinks 8 -> 4, relocating the survivors
    # (sids 6/7) out of the doomed upper slots
    for sched in (el, fx):
        for j in range(6):
            res = sched.close_stream(j)
            np.testing.assert_array_equal(res.logits, want[j])
    assert el.capacity == 4 and fx.capacity == 8
    assert {el._streams[j].slot for j in (6, 7)} <= {0, 1, 2, 3}

    # survivors keep streaming across the shrink boundary, then flush
    for sched in (el, fx):
        for j in (6, 7):
            sched.push_audio(j, clips[j][600:])
    lockstep("shrink")
    for sched in (el, fx):
        for j in (6, 7):
            res = sched.close_stream(j)
            np.testing.assert_array_equal(res.logits, want[j])
    grows = [c for _, c in el.metrics.capacity_events]
    assert 8 in grows and 4 in grows  # both directions actually happened


# ---------------------------------------------------------------------------
# Batched Pallas kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "b,l,cin,cout,k,stride,pad",
    [
        (3, 40, 8, 16, 3, 1, 1),
        (8, 32, 24, 40, 3, 1, 1),
        (5, 66, 16, 20, 5, 2, 2),
    ],
)
def test_bnn_conv1d_batched_kernel(b, l, cin, cout, k, stride, pad):
    x = jnp.array(RNG.integers(0, 2, (b, l, cin)), jnp.uint32)
    w = jnp.array(RNG.integers(-1, 2, (k, cin, cout)), jnp.int32)
    raw = ops.bnn_conv1d_batched(x, w, stride=stride, pad=pad)
    np.testing.assert_array_equal(
        np.asarray(raw),
        np.asarray(ref.ref_bnn_conv1d_batched(x, w, stride, pad)),
    )


def test_classifier_tail_kernel_matches_oracle():
    """Fused GAP-saturate + fc cascade kernel vs StreamState.logits math."""
    rng = np.random.default_rng(11)
    b, c, h_dim, ncls = 5, 24, 40, 12
    gap = rng.integers(0, 400, (b, c)).astype(np.int32)  # exceeds 255 ceiling
    w1 = rng.integers(-1, 2, (c, h_dim)).astype(np.int32)
    w2 = rng.integers(-1, 2, (h_dim, ncls)).astype(np.int32)
    thr1 = rng.integers(-5, 6, (h_dim,)).astype(np.float64)
    flip1 = rng.integers(0, 2, (h_dim,)).astype(bool)
    # numpy oracle: int64 math, float64 compare (StreamState.logits)
    h = np.minimum(gap.astype(np.int64), 255)
    raw = h @ w1.astype(np.int64)
    ge = raw >= thr1[None, :]
    h = np.where(flip1[None, :], ~ge, ge).astype(np.int64)
    want = h @ w2.astype(np.int64)
    got = ops.classifier_tail(
        jnp.asarray(gap),
        (jnp.asarray(w1), jnp.asarray(w2)),
        (jnp.asarray(thr1, jnp.float32), jnp.zeros((ncls,), jnp.float32)),
        (jnp.asarray(flip1, jnp.int32), jnp.zeros((ncls,), jnp.int32)),
        out_raw=(False, True),
    )
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))


def test_scheduler_pallas_backend_matches_offline(smoke):
    spec, weights, thresholds, prog = smoke
    x = _clip(spec, 30)
    sched = StreamScheduler(spec, weights, thresholds, capacity=2,
                            hop_frames=4, backend="pallas",
                            emit_logits=False)
    sid = sched.add_stream()
    sched.push_audio(sid, x)
    sched.run_until_starved()
    res = sched.close_stream(sid)
    np.testing.assert_array_equal(res.logits, _offline(prog, x))


# ---------------------------------------------------------------------------
# Streaming energy: measured ledger charges, all Table-I components
# ---------------------------------------------------------------------------

def test_stream_energy_ledger_covers_all_components(smoke):
    """Each hop charges the executor's EnergyLedger from the static plan:
    the summary must carry real (non-zero) SA/SRAM/controller components,
    not just e_mac, and scale linearly with hops executed."""
    spec, weights, thresholds, _ = smoke
    sched = StreamScheduler(spec, weights, thresholds, capacity=2)
    plan = sched.plan
    sid = sched.add_stream()
    x = _clip(spec, 70)
    sched.push_audio(sid, x[: plan.prime_samples + 4 * plan.hop_samples])
    outs = sched.run_until_starved()
    assert len(outs) == 4
    e = sched.metrics.energy_summary()
    for k in ("e_mac_uj", "e_sa_uj", "e_sram_uj", "e_ctrl_uj"):
        assert e[k] > 0.0, k
    assert e["energy_uj"] == pytest.approx(
        e["e_mac_uj"] + e["e_sa_uj"] + e["e_sram_uj"] + e["e_ctrl_uj"]
    )
    assert e["tops_per_w_equiv"] > 0
    # 4 hops, 4 finalizations: per-inference energy is the per-hop charge
    assert e["uj_per_inference"] == pytest.approx(e["energy_uj"] / 4)
    # the conv-cascade MAC count must match the plan's static budget
    from repro.stream import plan_hop_ledger
    hop = plan_hop_ledger(plan)
    assert hop.macs == plan.macs_per_hop()
    # another 2 hops scale every component linearly
    sched.push_audio(
        sid, x[plan.prime_samples + 4 * plan.hop_samples :
               plan.prime_samples + 6 * plan.hop_samples]
    )
    sched.run_until_starved()
    e2 = sched.metrics.energy_summary()
    assert e2["energy_uj"] == pytest.approx(e["energy_uj"] * 6 / 4)


def test_stream_energy_tail_only_when_finalizing(smoke):
    """With emit_logits off the classifier tail is never executed, so its
    fc MACs must not be charged."""
    spec, weights, thresholds, _ = smoke
    runs = {}
    for emit in (True, False):
        sched = StreamScheduler(spec, weights, thresholds, capacity=2,
                                emit_logits=emit)
        sid = sched.add_stream()
        x = _clip(spec, 71)
        sched.push_audio(
            sid, x[: sched.plan.prime_samples + 2 * sched.plan.hop_samples]
        )
        sched.run_until_starved()
        runs[emit] = sched.metrics
    on, off = runs[True], runs[False]
    fc_macs_per_hop = on.plan.fc_macs()
    assert on.ledger.macs - off.ledger.macs == 2 * fc_macs_per_hop
    assert off.finalizations == 0 and on.finalizations == 2
    assert off.energy_summary()["uj_per_inference"] == 0.0


# ---------------------------------------------------------------------------
# Detector hysteresis
# ---------------------------------------------------------------------------

def _logit(cls: int, strength: float = 30.0, n: int = 12) -> np.ndarray:
    z = np.zeros(n)
    z[cls] = strength
    return z


def test_detector_fires_once_per_utterance():
    cfg = DetectorConfig(smooth_frames=2, on_threshold=0.6,
                         off_threshold=0.4, refractory_frames=5)
    det = PosteriorDetector(0, cfg)
    events = []
    for f in range(10):  # sustained keyword: must fire exactly once
        e = det.update(f, _logit(3))
        if e:
            events.append(e)
    assert [e.cls for e in events] == [3]
    assert events[0].score >= cfg.on_threshold


def test_detector_refractory_blocks_refire():
    cfg = DetectorConfig(smooth_frames=1, on_threshold=0.6,
                         off_threshold=0.4, refractory_frames=8)
    det = PosteriorDetector(0, cfg)
    assert det.update(0, _logit(2)) is not None
    # dips below off-threshold immediately, but refractory still holds
    assert det.update(1, _logit(11)) is None
    assert det.update(2, _logit(2)) is None  # inside refractory: no refire
    # silence until refractory expires, then a new utterance fires again
    for f in range(3, 9):
        assert det.update(f, _logit(11)) is None
    e = det.update(9, _logit(5))
    assert e is not None and e.cls == 5


def test_detector_no_fire_before_window_full():
    # a confident-wrong first frame (right after priming) must not bypass
    # the smoother just because the window is still partial
    cfg = DetectorConfig(smooth_frames=4, on_threshold=0.6,
                         off_threshold=0.4, refractory_frames=4)
    det = PosteriorDetector(0, cfg)
    assert det.update(0, _logit(3)) is None
    assert det.update(1, _logit(11)) is None
    assert det.events == []


def test_detector_smoothing_suppresses_single_frame_glitch():
    cfg = DetectorConfig(smooth_frames=4, on_threshold=0.6,
                         off_threshold=0.4, refractory_frames=4)
    det = PosteriorDetector(0, cfg)
    for f in range(4):
        assert det.update(f, _logit(11)) is None
    # one glitch frame inside a 4-frame window: smoothed posterior ~0.25
    assert det.update(4, _logit(6)) is None
    assert det.update(5, _logit(11)) is None
    assert det.events == []


def test_detector_update_posterior_matches_update():
    """Feeding device-computed posteriors must drive the state machine
    exactly like feeding raw logits (the scheduler's per-hop path)."""
    cfg = DetectorConfig(smooth_frames=2, on_threshold=0.4,
                         off_threshold=0.2, refractory_frames=3)
    via_logits = PosteriorDetector(0, cfg)
    via_post = PosteriorDetector(0, cfg)
    rng = np.random.default_rng(13)
    for f in range(40):
        z = rng.normal(0, 8, 12)
        ea = via_logits.update(f, z)
        eb = via_post.update_posterior(f, _softmax(z))
        assert (ea is None) == (eb is None)
    assert [(e.cls, e.frame) for e in via_logits.events] == [
        (e.cls, e.frame) for e in via_post.events
    ]
    assert via_logits.events  # the random walk actually fired


def test_detector_hysteresis_rearm_requires_decay():
    cfg = DetectorConfig(smooth_frames=1, on_threshold=0.6,
                         off_threshold=0.4, refractory_frames=2)
    det = PosteriorDetector(0, cfg)
    assert det.update(0, _logit(1)) is not None
    # posterior stays above off_threshold long past refractory: still held
    for f in range(1, 10):
        assert det.update(f, _logit(1)) is None
    # decays -> re-arms -> new event
    assert det.update(10, _logit(11)) is None
    e = det.update(11, _logit(1))
    assert e is not None and e.frame == 11
