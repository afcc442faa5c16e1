"""Residual networks through the streaming runtime: residual joins through
skip rings and standalone pools, on the jnp hop step and the numpy path,
held to the plain whole-clip forward (``models.rawres.offline_logits``) at
every prefix — hop logits, close logits after a flush, peeks, batched
priming, the steady-state interchange and pool resizes in mid-stream."""
import numpy as np
import pytest

from repro.core import compiler, executor
from repro.core.cnn_spec import (
    CNN1DSpec,
    Conv1DSpec,
    FCSpec,
    GAPSpec,
    PoolSpec,
    ResBlock1DSpec,
)
from repro.models import rawres
from repro.stream import StreamScheduler, StreamState, plan_stream
from repro.stream.state import PRIME_GROUP, JoinStage, PoolStage, prime_batch


def _tail_pool_spec() -> CNN1DSpec:
    """Identity and projection blocks, then a standalone pool that no
    conv follows, so the prime leaves its window part full."""
    return CNN1DSpec(
        in_len=512, in_channels=1, in_bits=8, name="res_tail_pool",
        layers=(
            Conv1DSpec(1, 4, k=6, stride=2, pad=2, pool=2, in_bits=8,
                       in_offset=128, name="conv0"),
            ResBlock1DSpec(4, 4, name="b1"),
            ResBlock1DSpec(4, 8, proj=True, name="b2"),
            PoolSpec(8, 3, name="pool"),
            GAPSpec(8),
            FCSpec(8, 12, in_bits=8, out_raw=True, name="fc"),
        ),
    )


@pytest.fixture(scope="module")
def small():
    spec = rawres.build_m34res_small_spec()
    w, t = rawres.random_weights(spec, 3)
    return spec, w, t


@pytest.fixture(scope="module")
def tail_pool():
    spec = _tail_pool_spec()
    w, t = rawres.random_weights(spec, 5)
    return spec, w, t


def _clip(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.uint8)


def _hop_logits(spec, w, t, clips, chunk=13, capacity=4, **kw):
    """Feed every clip in ``chunk``-sample pieces, stepping as they land;
    returns {sid: {hops done: logits}} and the scheduler."""
    sch = StreamScheduler(spec, w, t, capacity=capacity, **kw)
    for j in range(len(clips)):
        assert sch.add_stream() == j
    got = {j: {} for j in range(len(clips))}
    n = max(c.size for c in clips)
    for a in range(0, n, chunk):
        for j, c in enumerate(clips):
            if a < c.size:
                sch.push_audio(j, c[a:a + chunk])
        for sid, _, lg, _ in sch.run_until_starved():
            got[sid][len(got[sid]) + 1] = lg
    return got, sch


# ---------------------------------------------------------------------------
# The spec and the plan
# ---------------------------------------------------------------------------

def test_m34res_spec_is_table_1():
    spec = rawres.build_m34res_spec()
    assert [c for _, c in spec.trace_shapes()[:1]] == [48]
    shapes = spec.trace_shapes()
    pools = [shapes[i][0] for i, L in enumerate(spec.layers)
             if isinstance(L, PoolSpec)]
    assert shapes[0][0] == 2000 and pools == [500, 125, 31]
    blocks = [L for L in spec.layers if isinstance(L, ResBlock1DSpec)]
    assert len(blocks) == 16 and 1 + 2 * len(blocks) == 33
    assert [L.cout for L in blocks if L.proj] == [96, 192, 384]
    assert spec.total_weights == 4_058_880


def test_plan_streams_blocks_through_skip_rings(small):
    spec, *_ = small
    plan = plan_stream(spec)
    assert not plan.plain
    joins = [st for st in plan.stages if isinstance(st, JoinStage)]
    assert len(joins) == 4 and [j.proj for j in joins] == [False, False,
                                                           True, False]
    for j in joins:
        # the block's two k=3 convs lag one frame each
        assert j.tail == 2 and j.n_out == j.n_in
        assert j.flush_in == j.tail + plan.stages[j.src].flush_in
    pools = [st for st in plan.stages if isinstance(st, PoolStage)]
    assert len(pools) == 1 and pools[0].n_out * 2 == pools[0].n_in


def test_tail_pool_has_a_phase(tail_pool):
    spec, *_ = tail_pool
    plan = plan_stream(spec)
    pool = plan.stages[-1]
    assert isinstance(pool, PoolStage) and pool.phase > 0


# ---------------------------------------------------------------------------
# The numpy path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("which", ["small", "tail_pool"])
def test_stream_state_matches_offline_prefixes(which, small, tail_pool):
    spec, w, t = small if which == "small" else tail_pool
    plan = plan_stream(spec)
    x = _clip(700, 11)
    st = StreamState(plan, w, t, ring_slack=plan.hop_samples)
    done = 0
    for n in (plan.prime_samples - 3, plan.prime_samples + 5, 301, 517,
              700):
        st.advance(x[done:n])
        done = n
        np.testing.assert_array_equal(
            st.peek_logits(), rawres.offline_logits(spec, w, t, x[:n]))


@pytest.mark.parametrize("b", [1, 3, 8, PRIME_GROUP * 2 + 3])
def test_prime_batch_matches_per_stream(b, small, tail_pool):
    """Row for row the per-stream warm-up, in one group or in several
    primed side by side."""
    for spec, w, t in (small, tail_pool):
        plan = plan_stream(spec)
        codes = np.stack([_clip(plan.prime_samples, 40 + j)
                          for j in range(b)])
        got = prime_batch(plan, w, t, codes)
        for j in range(b):
            st = StreamState(plan, w, t)
            st.advance(codes[j])
            want = st.export_steady()
            for i in range(len(plan.stages)):
                np.testing.assert_array_equal(got["tails"][i][j],
                                              want["tails"][i])
                np.testing.assert_array_equal(got["pendings"][i][j],
                                              want["pendings"][i])
            np.testing.assert_array_equal(got["gap"][j], want["gap"])
            assert got["frames"] == st.frames


def test_export_import_steady_round_trip_mid_stream(tail_pool):
    spec, w, t = tail_pool
    plan = plan_stream(spec)
    x = _clip(plan.prime_samples + 9 * plan.hop_samples, 12)
    cut = plan.prime_samples + 4 * plan.hop_samples
    whole = StreamState(plan, w, t)
    whole.advance(x[:cut])
    moved = StreamState(plan, w, t)
    moved.import_steady(**whole.export_steady(), frames=whole.frames)
    for st in (whole, moved):
        st.advance(x[cut:])
    np.testing.assert_array_equal(moved.peek_logits(), whole.peek_logits())
    np.testing.assert_array_equal(
        moved.peek_logits(), rawres.offline_logits(spec, w, t, x))


# ---------------------------------------------------------------------------
# The scheduler (jnp hop step)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("which", ["small", "tail_pool"])
def test_scheduler_hop_and_close_logits_match_offline(which, small,
                                                      tail_pool):
    spec, w, t = small if which == "small" else tail_pool
    plan = plan_stream(spec)
    clips = [_clip(plan.prime_samples + n, 20 + n) for n in (96, 131, 160)]
    got, sch = _hop_logits(spec, w, t, clips)
    for j, c in enumerate(clips):
        hops = (c.size - plan.prime_samples) // plan.hop_samples
        assert sorted(got[j]) == list(range(1, hops + 1))
        for i, lg in got[j].items():
            n = plan.prime_samples + i * plan.hop_samples
            np.testing.assert_array_equal(
                lg, rawres.offline_logits(spec, w, t, c[:n]))
        res = sch.close_stream(j)  # flushes the leftover sub-hop samples
        np.testing.assert_array_equal(
            res.logits, rawres.offline_logits(spec, w, t, c))


def test_scheduler_peek_matches_offline(small):
    spec, w, t = small
    plan = plan_stream(spec)
    x = _clip(plan.prime_samples + 5 * plan.hop_samples + 3, 31)
    sch = StreamScheduler(spec, w, t, capacity=2)
    sid = sch.add_stream()
    sch.push_audio(sid, x[:plan.prime_samples + 2 * plan.hop_samples])
    sch.run_until_starved()
    # on a hop boundary: the in-jit ghost flush of the last emit
    n = plan.prime_samples + 2 * plan.hop_samples
    np.testing.assert_array_equal(
        sch.peek(sid), rawres.offline_logits(spec, w, t, x[:n]))
    # leftover sub-hop samples: the numpy fallback over the imported state
    sch.push_audio(sid, x[n:])
    sch.run_until_starved()
    np.testing.assert_array_equal(
        sch.peek(sid), rawres.offline_logits(spec, w, t, x))


def test_scheduler_finalize_matches_stream_state_peek(tail_pool):
    """The jitted ghost flush (masked-out rows included) equals the numpy
    clone-and-flush on every primed slot."""
    spec, w, t = tail_pool
    plan = plan_stream(spec)
    sch = StreamScheduler(spec, w, t, capacity=4)
    clips = [_clip(plan.prime_samples + k * plan.hop_samples, 50 + k)
             for k in (1, 3, 6)]
    for c in clips:
        sch.push_audio(sch.add_stream(), c)
    sch.run_until_starved()
    logits, _ = sch._model.finalize(tuple(sch._tails),
                                    tuple(sch._pendings), sch._gap)
    host = sch._host_state()
    for sid, c in enumerate(clips):
        s = sch._streams[sid]
        want = sch._extract_slot(s, host).peek_logits()
        np.testing.assert_array_equal(np.asarray(logits[s.slot]), want)
        np.testing.assert_array_equal(
            want, rawres.offline_logits(spec, w, t, c))


def test_scheduler_resize_mid_stream_is_bit_exact(small):
    """Streams riding a 2 -> 4 grow and a 4 -> 2 shrink emit the hop and
    close logits of a pinned pool, and of the offline forward."""
    spec, w, t = small
    plan = plan_stream(spec)
    clips = [_clip(plan.prime_samples + 12 * plan.hop_samples, 70 + j)
             for j in range(4)]
    el = StreamScheduler(spec, w, t, capacity=4, initial_capacity=2)
    fx = StreamScheduler(spec, w, t, capacity=4, min_capacity=4)
    half = plan.prime_samples + 5 * plan.hop_samples

    def lockstep():
        a, b = el.run_until_starved(), fx.run_until_starved()
        assert [x[:2] for x in a] == [x[:2] for x in b]
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x[2], y[2])

    for sch in (el, fx):
        for j in range(2):
            sch.add_stream()
            sch.push_audio(j, clips[j][:half])
    lockstep()
    for sch in (el, fx):  # two more join: 2 -> 4 with 0, 1 mid-stream
        for j in range(2, 4):
            sch.add_stream()
            sch.push_audio(j, clips[j][:half])
        for j in range(2):
            sch.push_audio(j, clips[j][half:])
    lockstep()
    assert el.capacity == 4
    for j in range(3):  # a quarter left: 4 -> 2 with sid 3 mid-stream
        got = el.close_stream(j).logits
        np.testing.assert_array_equal(got, fx.close_stream(j).logits)
        n = clips[j].size if j < 2 else half
        np.testing.assert_array_equal(
            got, rawres.offline_logits(spec, w, t, clips[j][:n]))
    assert el.capacity == 2
    for sch in (el, fx):
        sch.push_audio(3, clips[3][half:])
    lockstep()
    got = el.close_stream(3).logits
    np.testing.assert_array_equal(got, fx.close_stream(3).logits)
    np.testing.assert_array_equal(
        got, rawres.offline_logits(spec, w, t, clips[3]))


def test_scheduler_tenant_pool_runs_residual_blocks(small):
    """Two tenants' blocks (projections and gains gathered per tenant
    block) each match their own offline forward."""
    spec, w0, t0 = small
    w1, t1 = rawres.random_weights(spec, 4)
    plan = plan_stream(spec)
    sch = StreamScheduler(spec, w0, t0, capacity=4, max_models=2,
                          tenant_block=2)
    sch.register_model("other", w1, t1)
    x = _clip(plan.prime_samples + 4 * plan.hop_samples, 9)
    a = sch.add_stream()
    b = sch.add_stream(model="other")
    for sid in (a, b):
        sch.push_audio(sid, x)
    sch.run_until_starved()
    np.testing.assert_array_equal(sch.peek(a),
                                  rawres.offline_logits(spec, w0, t0, x))
    np.testing.assert_array_equal(sch.peek(b),
                                  rawres.offline_logits(spec, w1, t1, x))


# ---------------------------------------------------------------------------
# Who refuses the new layer kinds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["pallas", "megakernel"])
def test_kernel_backends_refuse_blocks_and_pools(backend, small):
    spec, w, t = small
    with pytest.raises(ValueError, match="backend='jnp'"):
        StreamScheduler(spec, w, t, capacity=2, backend=backend)


def test_isa_compiler_and_executor_refuse_blocks(small):
    spec, w, t = small
    with pytest.raises(ValueError, match="residual"):
        compiler.compile_model(spec, w, t)
    plain = rawres.build_m34res_spec(in_len=256, widths=(16,), blocks=(0,),
                                     k0=8, stride0=2, pad0=3, pool=2)
    prog = compiler.compile_model(plain, *rawres.random_weights(plain, 1))
    prog.spec = spec  # a program whose network holds a block
    with pytest.raises(ValueError, match="residual"):
        executor.Executor(prog)


def test_res_block_spec_validates_its_shape():
    with pytest.raises(ValueError, match="projection"):
        ResBlock1DSpec(4, 8)
    with pytest.raises(ValueError, match="length"):
        ResBlock1DSpec(4, 4, k=3, pad=0)
    b = ResBlock1DSpec(4, 8, proj=True)
    assert b.kind == "res" and b.out_len(37) == 37
    assert b.weights == 3 * 4 * 8 + 3 * 8 * 8 + 4 * 8
