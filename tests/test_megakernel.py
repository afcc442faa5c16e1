"""Hop megakernel (kernels/hop_megakernel.py) oracle suite.

The fused-cascade backend must be bit-exact with the per-stage paths
(``jnp``, ``pallas``) and the offline executor across randomized plan
geometries — strides, pools, pool phases, bit-serial first layers, flush
geometry — including across elastic resize boundaries and on 1/2/8-shard
meshes; and its per-hop device-dispatch count must match the static
accounting (``_BatchedModel.dispatches_per_hop``) exactly.

Multi-shard cases need a forced multi-device host (see
tests/test_stream_sharded.py); they skip on a 1-device host.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiler, executor
from repro.core.cnn_spec import CNN1DSpec, Conv1DSpec, FCSpec, GAPSpec
from repro.kernels import dispatch, ops, ref
from repro.launch.mesh import make_stream_mesh
from repro.models import kws
from repro.stream import StreamScheduler

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as hyp_st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - hypothesis is optional
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


def _clip(spec, seed):
    return np.random.default_rng(seed).integers(
        0, 256, (spec.in_len,)
    ).astype(np.uint8)


def _mesh(n):
    if jax.device_count() < n:
        pytest.skip(
            f"needs {n} devices (XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n})"
        )
    return make_stream_mesh(n)


# ---------------------------------------------------------------------------
# Randomized plan geometries
# ---------------------------------------------------------------------------

def _random_spec(seed: int) -> CNN1DSpec | None:
    """A small random streamable spec: bit-serial first layer with random
    k/stride/pad, 1-2 tail conv blocks with random k/pad/pool (so pool
    phases, tails, and flush geometry all vary), GAP, binary fc, raw fc.
    Returns None when no hop_frames reaches a steady state (rare)."""
    rng = np.random.default_rng(seed)
    k0 = int(rng.integers(3, 13))
    s0 = int(rng.choice([2, 4, 8]))
    c0 = int(rng.choice([4, 8]))
    bits0 = int(rng.choice([4, 8]))
    layers = [
        Conv1DSpec(1, c0, k=k0, stride=s0, pad=int(rng.integers(0, k0)),
                   in_bits=bits0, in_offset=1 << (bits0 - 1),
                   name="l0"),
    ]
    cin = c0
    for j in range(int(rng.integers(1, 3))):
        k = int(rng.choice([3, 5]))
        cout = int(rng.choice([4, 8]))
        layers.append(
            Conv1DSpec(cin, cout, k=k, stride=1,
                       pad=int(rng.integers(0, k // 2 + 1)),
                       pool=int(rng.choice([1, 2, 2, 4])),  # isa: pow2 only
                       name=f"b{j + 1}")
        )
        cin = cout
    layers += [
        GAPSpec(cin, name="gap"),
        FCSpec(cin, 8, in_bits=8, name="fc1"),
        FCSpec(8, kws.N_CLASSES, out_raw=True, name="fc2"),
    ]
    spec = CNN1DSpec(in_len=int(rng.integers(500, 900)), in_channels=1,
                     in_bits=layers[0].in_bits, layers=tuple(layers),
                     name=f"rand{seed}")
    from repro.stream.state import plan_stream
    for hf in (1, 2, 3, 4, 6, 8, 12):
        try:
            plan = plan_stream(spec, hop_frames=hf)
        except ValueError:
            continue
        if spec.in_len >= plan.prime_samples + 3 * plan.hop_samples:
            return spec, hf
    return None


def _check_random_geometry(seed: int) -> None:
    """One randomized geometry: megakernel hop logits + peeks == jnp;
    peeks == offline executor on all pushed audio, the last hop's logits
    == offline executor on the prefix the hops consumed."""
    built = _random_spec(seed)
    if built is None:
        pytest.skip(f"seed {seed}: no steady-state hop geometry")
    spec, hf = built
    params = kws.init_kws_params(jax.random.PRNGKey(seed), spec)
    weights, thresholds = kws.export_kws(params, spec)
    # codes must fit the first layer's bit-serial precision: paths that
    # decompose into planes mask to in_bits, the dense path subtracts the
    # offset from the raw value — they agree iff codes < 2**in_bits
    x = np.random.default_rng(1000 + seed).integers(
        0, 1 << spec.in_bits, (spec.in_len,)
    ).astype(np.uint8)
    outs = {}
    for backend in ("jnp", "megakernel"):
        s = StreamScheduler(spec, weights, thresholds, capacity=2,
                            hop_frames=hf, backend=backend)
        a, b = s.add_stream(), s.add_stream()
        s.push_audio(a, x)
        s.push_audio(b, x[: int(0.7 * spec.in_len)])
        hops = s.run_until_starved()
        outs[backend] = (hops, np.asarray(s.peek(a)), np.asarray(s.peek(b)),
                         s.plan)
    hj, pja, pjb, plan = outs["jnp"]
    hm, pma, pmb, _ = outs["megakernel"]
    assert len(hj) == len(hm) >= 2
    for u, v in zip(hj, hm):
        assert u[:2] == v[:2]
        np.testing.assert_array_equal(u[2], v[2])
    np.testing.assert_array_equal(pja, pma)
    np.testing.assert_array_equal(pjb, pmb)
    # a peek covers all audio pushed so far, sub-hop inbox leftovers
    # included: the offline executor on the whole clip
    np.testing.assert_array_equal(
        pma, _offline(compiler.compile_model(spec, weights, thresholds), x))
    # the fused emit tail of stream a's last hop against the offline
    # executor on the exact prefix the hops consumed
    a_hops = [u for u in hm if u[0] == 0]
    consumed = plan.prime_samples + len(a_hops) * plan.hop_samples
    spec_l = dataclasses.replace(spec, in_len=consumed)
    prog_l = compiler.compile_model(spec_l, weights, thresholds)
    np.testing.assert_array_equal(a_hops[-1][2],
                                  _offline(prog_l, x[:consumed]))


@pytest.mark.parametrize("seed", [*range(5), 3870])  # 3870: k < stride
def test_megakernel_random_geometry_oracle(seed):
    _check_random_geometry(seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=5, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(seed=hyp_st.integers(min_value=5, max_value=10_000))
    def test_megakernel_hypothesis_geometry_oracle(seed):
        """Property form of the randomized-geometry oracle: any drawn seed
        (→ any streamable random geometry) must be fused-vs-reference
        bit-exact.  Runs only where hypothesis is installed; the seeded
        parametrization above always runs."""
        _check_random_geometry(seed)


# ---------------------------------------------------------------------------
# Smoke-spec equivalence: all three backends, per-stage pallas included
# ---------------------------------------------------------------------------

def test_megakernel_matches_all_backends(smoke):
    """Fused hop + fused emit tail + standalone finalize peek, against both
    per-stage backends on the KWS smoke spec."""
    spec, weights, thresholds, _ = smoke
    x = _clip(spec, 42)
    outs = {}
    for backend in ("jnp", "pallas", "megakernel"):
        s = StreamScheduler(spec, weights, thresholds, capacity=2,
                            hop_frames=4, backend=backend)
        a, b = s.add_stream(), s.add_stream()
        s.push_audio(a, x)
        s.push_audio(b, x[:600])
        hops = s.run_until_starved()
        outs[backend] = (hops, np.asarray(s.peek(a)), np.asarray(s.peek(b)))
    for backend in ("pallas", "megakernel"):
        hj, pja, pjb = outs["jnp"]
        hk, pka, pkb = outs[backend]
        assert len(hj) == len(hk) >= 1, backend
        for u, v in zip(hj, hk):
            assert u[:2] == v[:2], backend
            np.testing.assert_array_equal(u[2], v[2])
        np.testing.assert_array_equal(pja, pka)
        np.testing.assert_array_equal(pjb, pkb)


def test_megakernel_full_clip_matches_offline(smoke):
    """Close-out logits through the megakernel backend equal the offline
    executor on the whole clip."""
    spec, weights, thresholds, prog = smoke
    s = StreamScheduler(spec, weights, thresholds, capacity=2,
                        backend="megakernel")
    x = _clip(spec, 7)
    sid = s.add_stream()
    s.push_audio(sid, x)
    s.run_until_starved()
    res = s.close_stream(sid)
    np.testing.assert_array_equal(res.logits, _offline(prog, x))


def test_megakernel_grow_shrink_bitexact(smoke):
    """Streams fed across 4->8 grow and 8->4 shrink boundaries through the
    megakernel backend emit hop logits bit-identical to a pinned-capacity
    jnp scheduler (resize = pure pad/slice of fused-kernel state)."""
    spec, weights, thresholds, _ = smoke
    clips = {j: _clip(spec, 80 + j) for j in range(8)}
    el = StreamScheduler(spec, weights, thresholds, capacity=8,
                         initial_capacity=4, backend="megakernel")
    fx = StreamScheduler(spec, weights, thresholds, capacity=8,
                         initial_capacity=8, min_capacity=8, backend="jnp")

    def lockstep(stage):
        a, b = el.run_until_starved(), fx.run_until_starved()
        assert len(a) == len(b), stage
        for ea, eb in zip(a, b):
            assert ea[:2] == eb[:2], stage
            np.testing.assert_array_equal(ea[2], eb[2])

    sids_e = [el.add_stream() for _ in range(3)]
    sids_f = [fx.add_stream() for _ in range(3)]
    for j in range(3):
        el.push_audio(sids_e[j], clips[j][:400])
        fx.push_audio(sids_f[j], clips[j][:400])
    lockstep("pre-grow")
    sids_e += [el.add_stream() for _ in range(3)]  # forces 4 -> 8 grow
    sids_f += [fx.add_stream() for _ in range(3)]
    for j in range(6):
        el.push_audio(sids_e[j], clips[j][400:])
        fx.push_audio(sids_f[j], clips[j][400:])
    lockstep("post-grow")
    for j in range(5):  # occupancy 6 -> 1 triggers the 8 -> 4 shrink
        el.close_stream(sids_e[j])
        fx.close_stream(sids_f[j])
    assert el.capacity < 8  # shrank (elastic), fx stays pinned at 8
    el.push_audio(sids_e[5], clips[6])
    fx.push_audio(sids_f[5], clips[6])
    lockstep("post-shrink")


# ---------------------------------------------------------------------------
# Sharded meshes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", (1, 2, 8))
def test_megakernel_sharded_matches_unsharded(smoke, n_shards):
    """One fused launch per shard: the mesh megakernel scheduler is
    bit-exact with the single-device jnp scheduler."""
    spec, weights, thresholds, _ = smoke
    mesh = _mesh(n_shards)
    x = {j: _clip(spec, 90 + j) for j in range(8)}
    outs = {}
    for backend, m in (("jnp", None), ("megakernel", mesh)):
        s = StreamScheduler(spec, weights, thresholds, capacity=8,
                            initial_capacity=8, min_capacity=8,
                            hop_frames=2, backend=backend, mesh=m)
        sids = [s.add_stream() for _ in range(8)]
        for j, sid in enumerate(sids):
            s.push_audio(sid, x[j][: 600 + 64 * (j % 3)])
        hops = s.run_until_starved()
        outs[backend] = (hops, [np.asarray(s.peek(sid)) for sid in sids])
    def by_sid(hops):
        d = {}
        for sid, frame, logits, _post in hops:
            d.setdefault(sid, []).append((frame, logits))
        return d

    hj, pj = outs["jnp"]
    hm, pm = outs["megakernel"]
    assert len(hj) == len(hm) >= 1
    dj, dm = by_sid(hj), by_sid(hm)
    assert dj.keys() == dm.keys()
    for sid in dj:  # per-stream hop sequences match; cross-shard emit
        assert len(dj[sid]) == len(dm[sid])  # order may differ
        for (fa, la), (fb, lb) in zip(dj[sid], dm[sid]):
            assert fa == fb
            np.testing.assert_array_equal(la, lb)
    for a, b in zip(pj, pm):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Dispatch accounting: static per-hop figure == traced pallas_call count
# ---------------------------------------------------------------------------

def _traced_dispatches(sched, emit: bool) -> int:
    """pallas_calls captured by one fresh trace of the hop step."""
    m = sched._model
    plan = sched.plan
    B = sched.capacity
    args = (
        jnp.zeros((B, plan.hop_samples), jnp.int32),
        jnp.zeros((B,), bool),
        tuple(jnp.zeros((B, st.tail, st.cin), jnp.int32)
              for st in plan.convs),
        tuple(jnp.zeros((B, st.phase, st.cout), jnp.int32)
              for st in plan.convs),
        jnp.zeros((B, plan.gap_channels), jnp.int32),
    )
    jax.clear_caches()  # a jit cache hit would trace (and count) nothing
    with dispatch.counting() as traced:
        jax.eval_shape(lambda *a: m._step(*a, emit=emit), *args)
    return traced()


@pytest.mark.parametrize("backend", ("jnp", "pallas", "megakernel"))
def test_dispatches_per_hop_matches_trace(smoke, backend):
    """The static accounting surfaced in metrics/BENCH must equal the
    launches actually traced through kernels.dispatch — and the megakernel
    hits the fused target: ONE launch per hop, emit included."""
    spec, weights, thresholds, _ = smoke
    s = StreamScheduler(spec, weights, thresholds, capacity=2,
                        hop_frames=2, backend=backend)
    for emit in (False, True):
        static = s._model.dispatches_per_hop(emit)
        assert _traced_dispatches(s, emit) == static
    assert s._model.dispatches_per_hop(True) <= 2 or backend != "megakernel"
    if backend == "megakernel":
        assert s._model.dispatches_per_hop(True) == 1
    if backend == "jnp":
        assert s._model.dispatches_per_hop(True) == 0


def test_metrics_surface_dispatch_counts(smoke):
    """StreamMetrics carries the per-hop figure + running total into
    summary(), and the dispatch trace span is annotated with it."""
    spec, weights, thresholds, _ = smoke
    s = StreamScheduler(spec, weights, thresholds, capacity=2,
                        backend="megakernel")
    sid = s.add_stream()
    s.push_audio(sid, _clip(spec, 3))
    hops = s.run_until_starved()
    assert len(hops) >= 2
    summ = s.metrics.summary()
    assert summ["device_dispatches_per_hop"] == 1.0
    assert summ["device_dispatches_total"] == float(s.metrics.steps)
    dispatch_spans = s.obs.trace.spans("dispatch")
    assert dispatch_spans and all(
        sp["args"].get("dispatches") == 1 for sp in dispatch_spans
    )


# ---------------------------------------------------------------------------
# Satellite: single-launch bit-serial first layer (per-stage fallback path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits,stride,pad", [(8, 8, 9), (4, 2, 0), (2, 4, 3)])
def test_bitserial_batched_single_dispatch(bits, stride, pad):
    """ops.bitserial_conv1d_batched accumulates every bit plane inside ONE
    pallas launch and matches the plane-looped reference exactly."""
    rng = np.random.default_rng(5)
    b, l, cin, cout, k = 3, 75, 2, 5, 7
    x = jnp.asarray(rng.integers(0, 1 << bits, (b, l, cin)), jnp.uint32)
    w = jnp.asarray(rng.integers(-1, 2, (k, cin, cout)), jnp.int32)
    offset = 1 << (bits - 1)
    jax.clear_caches()
    with dispatch.counting() as traced:
        got = ops.bitserial_conv1d_batched(
            x, w, bits=bits, offset=offset, stride=stride, pad=pad,
            interpret=True,
        )
    assert traced() == 1  # not `bits` separate launches
    for r in range(b):
        want = ref.ref_bitserial_conv1d(x[r], w, bits, offset=offset,
                                        stride=stride, pad=pad)
        np.testing.assert_array_equal(np.asarray(got[r]), np.asarray(want))
