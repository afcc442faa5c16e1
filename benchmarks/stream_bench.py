"""Streaming runtime throughput: in-jit finalization vs the host-peek and
full re-run baselines, steady-state batch sweep, elastic-pool churn, and
the mesh-sharded 1k-stream sweep.

The offline path answers "what does this stream say now?" by re-running the
whole utterance through the executor — the cost a deployment would pay per
emitted frame without incremental state.  The streaming scheduler instead
advances all B streams one hop with a single batched step that *includes*
finalization: the fused tail (ghost flush + classifier kernel) emits every
active slot's executor-exact logits on-device, so steady-state hop latency
IS hop-to-logits latency.  Reported:

  * steady-state hop latency p50/p95, frames/sec and measured silicon-
    equivalent uJ/inference at B in {8, 64, 256} (every slot active,
    per-hop logits on), with the hop's host pack and its wait on the
    device (``host_pack_ms_p50`` / ``fence_ms_p50`` per config)
  * before/after vs the previous committed BENCH_stream.json at B=8
  * the host-pack microbench at B=1024: the pre-arena per-slot ring walk
    (one python pop per stream per hop) vs ``RingArena.pack_hops``'s one
    vectorized gather — the ``host_pack_ms`` field CI asserts on, with
    the before/after reduction recorded
  * a join/leave churn scenario against the elastic slot pool: staggered
    arrivals/departures, pool resizes counted, hop latency under churn
  * the async-overlap scenario at the largest sweep batch: the whole
    timed load preloaded into an oversized arena, then one open-loop
    ``drain()`` on the sync scheduler vs the double-buffered
    ``AsyncStreamScheduler`` — pack+detector time hidden under device
    spans measured from the fenced trace (``overlap`` in the artifact;
    acceptance floor: >=90% hidden at the non-smoke B=256)
  * the skewed-churn scenario: leaves concentrated onto one shard, steady
    capacity with vs without the cross-shard rebalance plane — the
    rebalanced pool must shrink to within 2x of the balanced floor
    ``S * next_pow2(ceil(active/S))`` where the no-rebalance pool stays
    pinned at the fullest shard's count (``skewed_churn`` in the
    artifact, asserted by the multi-device CI leg)
  * the offline re-run baseline frames/sec and the speedup
  * the mesh-sharded sweep: >=1024 concurrent streams on one logical slot
    pool spanning 1, 2 and 8 shards of a forced multi-device host
    (XLA_FLAGS=--xla_force_host_platform_device_count=8 — set below when
    this module owns jax initialization), acceptance floor: some
    multi-shard config beats the single-device pool at the same total
    stream count

Writes BENCH_stream.json next to the repo root so the perf trajectory of
streams/sec is tracked across PRs.  ``STREAM_BENCH_SMOKE=1`` shrinks every
round count for CI.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time

if "jax" not in sys.modules:  # pragma: no cover - import-order dependent
    # must land before jax initializes; inert when the operator set their own
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )

import jax
import numpy as np

from benchmarks.common import row
from repro.core import compiler
from repro.core.executor import Executor
from repro.data import gscd
from repro.launch.mesh import make_stream_mesh
from repro.models import kws
from repro.obs import (
    EventLog,
    MetricsRegistry,
    Observability,
    Tracer,
    coverage,
    overlap_stats,
)
from repro.stream import (
    AsyncStreamScheduler,
    FrameRing,
    RingArena,
    StreamScheduler,
    plan_stream,
)
from repro.stream.metrics import StreamMetrics
from repro.stream.scheduler import _next_pow2

SMOKE = os.environ.get("STREAM_BENCH_SMOKE", "") not in ("", "0")

BATCH_SWEEP = (8,) if SMOKE else (8, 64, 256)
HOP_FRAMES = 2            # matches the BENCH_stream.json trajectory
WARM_ROUNDS = 1 if SMOKE else 2
TIMED_ROUNDS = 2 if SMOKE else 20
CHURN_STREAMS = 8 if SMOKE else 24
CHURN_CAP = 32
TENANT_KS = (1, 2, 4, 8)  # pool sizes swept at fixed total streams
TENANT_TOTAL = 16            # fixed across K; same total in smoke + full
TENANT_ROUNDS = 2 if SMOKE else 10
LM_ELASTIC_SLOTS = (4, 8, 16)  # slot-pool ceilings for the LM decode split
LM_ELASTIC_WAVES = 2
SHARD_TOTAL = 1024        # the ROADMAP "1k+ concurrent streams" target
SHARD_CONFIGS = (1, 2, 8)
SHARD_TIMED_ROUNDS = 2 if SMOKE else 6
# at 1k streams the per-hop python packing loop is the serial floor; a
# bigger hop amortizes it so the device-side speedup is what gets measured
SHARD_HOP_FRAMES = 8

_OUT = pathlib.Path(__file__).resolve().parent.parent / "BENCH_stream.json"


def _steady(spec, weights, thresholds, n_streams: int, mesh=None,
            warm_rounds: int = WARM_ROUNDS, timed_rounds: int = TIMED_ROUNDS,
            chunk_hops: int = 4, hop_frames: int = HOP_FRAMES,
            backend: str = "jnp",
            obs: Observability | None = None) -> dict[str, object]:
    """All slots active, per-hop logits on: the always-on steady state.

    Quantiles come from the scheduler's own bounded metrics plane:
    ``begin_window()`` after warm-up opens a fresh measurement window, so
    ``summary()`` / ``phase_summary()`` report exactly the steady-state
    rounds (exact order statistics while the reservoir holds every
    sample; ``latency_estimated`` flags the histogram fallback).
    """
    sched = StreamScheduler(
        spec, weights, thresholds, capacity=n_streams,
        initial_capacity=n_streams, min_capacity=n_streams,
        hop_frames=hop_frames, emit_logits=True, mesh=mesh, obs=obs,
        backend=backend,
    )
    plan = sched.plan
    chunk = plan.hop_samples * chunk_hops
    need = plan.prime_samples + plan.hop_samples + (
        warm_rounds + timed_rounds
    ) * chunk
    rng = np.random.default_rng(0)
    audio = rng.integers(0, 256, (n_streams, need)).astype(np.uint8)
    sids = [sched.add_stream() for _ in range(n_streams)]

    # prime + trace the jitted step outside the timed region; results are
    # consumed columnar (sched.drain) — the per-stream tuple collation of
    # run_until_starved is exactly the per-slot python the vectorized
    # ingest plane removed, so the bench measures the hot path itself
    pos = plan.prime_samples + plan.hop_samples
    sched.push_audio_batch(sids, list(audio[:, :pos]))
    sched.drain()
    for r in range(warm_rounds):
        sched.push_audio_batch(sids, list(audio[:, pos : pos + chunk]))
        sched.drain()
        pos += chunk

    sched.metrics.begin_window()
    t0 = time.perf_counter()
    for r in range(timed_rounds):
        sched.push_audio_batch(sids, list(audio[:, pos : pos + chunk]))
        sched.drain()
        pos += chunk
    wall = time.perf_counter() - t0

    m = sched.metrics.summary()
    phases = sched.metrics.phase_summary()
    frames = sched.metrics.frames_total()
    energy = sched.metrics.energy_summary()
    return {
        "hop_ms_p50": m["step_ms_p50"],
        "hop_ms_p95": m["step_ms_p95"],
        "hop_ms_p99": m["step_ms_p99"],
        "hop_ms_p999": m["step_ms_p999"],
        "host_pack_ms_p50": m["host_pack_ms_p50"],
        "fence_ms_p50": phases["fence"]["ms_p50"],
        "fence_ms_p95": phases["fence"]["ms_p95"],
        "fence_ms_p99": phases["fence"]["ms_p99"],
        "fetch_ms_p50": phases["fetch"]["ms_p50"],
        "latency_estimated": m["latency_estimated"],
        # the fenced per-phase split of the hop (pack / dispatch / fence /
        # fetch / detector): quantiles + each phase's share of hop wall
        "phases": {
            p: {k: d[k] for k in ("ms_p50", "ms_p95", "ms_p99", "ms_p999",
                                  "share_of_wall")}
            for p, d in phases.items()
        },
        "frames_per_sec": frames / wall,
        "stream_hops_per_sec": frames / plan.frames_per_hop / wall,
        "audio_sec_per_wall_sec": frames * plan.samples_per_frame
        / gscd.SR / wall,
        "uj_per_inference": energy["uj_per_inference"],
        # per-shard pallas_call count for one emit hop (0 = plain XLA)
        "device_dispatches_per_hop": m["device_dispatches_per_hop"],
        "backend": backend,
    }


def _obs_overhead(spec, hop_ms_p50: float, n_streams: int = 256,
                  rounds: int = 2000) -> dict[str, float]:
    """Cost of the instrumentation itself, against the <=2% acceptance
    bound.

    Replays exactly what one hop adds to the hot path — one ``on_step``
    (reservoir records, ledger charge) plus the seven ``add_batch`` ring
    appends — with no device work, so the measured per-hop cost is pure
    observability overhead.  The timed region starts *after* the latency
    reservoirs have wrapped, so it measures the saturated regime (ring
    write + live histogram record per series — the most expensive the
    instrumentation ever gets over unbounded uptime).  Compared against
    the measured steady-state hop p50 at the same batch size.
    """
    plan = plan_stream(spec, hop_frames=HOP_FRAMES)
    metrics = StreamMetrics(plan, registry=MetricsRegistry())
    tr = Tracer()

    def hop() -> None:
        metrics.on_step(n_streams, plan.frames_per_hop, 4e-3,
                        host_pack_s=4e-4, dispatch_s=6e-4, fence_s=2.4e-3,
                        fetch_s=2e-4, detector_s=4e-4)
        tr.add_batch((
            ("pack", 0.0, 4e-4, {"n": n_streams}),
            ("dispatch", 0.0, 6e-4, {}),
            ("fence", 0.0, 2.4e-3, {}),
            ("fetch", 0.0, 2e-4, {}),
            ("detector", 0.0, 4e-4, {}),
            ("push_fold", 0.0, 1e-4, {}),
            ("hop", 0.0, 4e-3, {"n": n_streams}),
        ))

    for _ in range(metrics._wall_res.capacity + 8):  # wrap the reservoirs
        hop()
    assert metrics.latency_estimated
    t0 = time.perf_counter()
    for _ in range(rounds):
        hop()
    per_hop_ms = (time.perf_counter() - t0) / rounds * 1e3
    frac = per_hop_ms / hop_ms_p50 if hop_ms_p50 else 0.0
    return {
        "instrument_ms_per_hop": per_hop_ms,
        "hop_ms_p50": hop_ms_p50,
        "overhead_frac": frac,
        "within_2pct": float(frac <= 0.02),
    }


def _host_pack_micro(hop_samples: int, n_streams: int = 1024,
                     rounds: int = 8) -> dict[str, float]:
    """Host-side hop packing in isolation, before vs after the arena.

    "Before" reconstructs the PR-3 packing loop: one per-stream ring
    object (u8 codes as (n, 1) int32 — the old AudioFrontend layout) and
    one python pop per stream per hop, scattered row by row into the
    batched step input.  "After" is the shared RingArena's one-shot
    ``pack_hops`` gather.  Same data, same output, no device work — this
    isolates exactly the serial floor the ingest refactor removes.
    """
    rng = np.random.default_rng(7)
    need = (rounds + 1) * hop_samples
    codes = rng.integers(0, 256, (n_streams, need)).astype(np.uint8)

    rings = [FrameRing(need, 1, np.int32) for _ in range(n_streams)]
    for i, r in enumerate(rings):
        r.push(codes[i].astype(np.int32)[:, None])
    t0 = time.perf_counter()
    for _ in range(rounds):
        audio = np.zeros((n_streams, hop_samples), np.int32)
        for i, r in enumerate(rings):
            audio[i] = r.pop(hop_samples)[:, 0]
    t_before = (time.perf_counter() - t0) / rounds
    check_before = audio.sum()

    arena = RingArena(n_streams, need)
    arena.push_batch(np.arange(n_streams), list(codes))
    slots = np.arange(n_streams)
    t0 = time.perf_counter()
    for _ in range(rounds):
        audio = arena.pack_hops(slots, hop_samples)
    t_after = (time.perf_counter() - t0) / rounds
    assert audio.sum() == check_before  # same final hop, both paths
    return {
        "streams": float(n_streams),
        "hop_samples": float(hop_samples),
        "host_pack_ms_before": t_before * 1e3,
        "host_pack_ms_after": t_after * 1e3,
        "reduction": t_before / t_after,
    }


def _churn(spec, weights, thresholds,
           obs: Observability | None = None) -> dict[str, float]:
    """Bursty arrivals/departures against the elastic slot pool."""
    sched = StreamScheduler(
        spec, weights, thresholds, capacity=CHURN_CAP,
        hop_frames=HOP_FRAMES, emit_logits=True, obs=obs,
    )
    rng = np.random.default_rng(1)
    clips = [
        gscd.sample(rng, int(c), n=spec.in_len)
        for c in rng.integers(0, gscd.N_CLASSES, CHURN_STREAMS)
    ]
    pending = list(range(CHURN_STREAMS))
    live: dict[int, int] = {}  # sid -> clip index
    pos: dict[int, int] = {}
    t0 = time.perf_counter()
    while pending or live:
        # a burst of arrivals every round (2 at a time)
        for _ in range(2):
            if pending and len(live) < CHURN_CAP:
                j = pending.pop(0)
                sid = sched.add_stream()
                live[sid] = j
                pos[sid] = 0
        for sid, j in list(live.items()):
            n = int(rng.integers(160, 512))
            sched.push_audio(sid, clips[j][pos[sid] : pos[sid] + n])
            pos[sid] += n
        sched.run_until_starved()
        for sid, j in list(live.items()):
            if pos[sid] >= spec.in_len:
                sched.close_stream(sid)
                del live[sid], pos[sid]
    wall = time.perf_counter() - t0
    m = sched.metrics.summary()
    caps = [c for _, c in sched.metrics.capacity_events]
    return {
        "streams": float(CHURN_STREAMS),
        "wall_s": wall,
        "hop_ms_p50": m["step_ms_p50"],
        "resizes": m["resizes"],
        "peak_capacity": float(max(caps)) if caps else float(sched.capacity),
        "final_capacity": float(sched.capacity),
    }


def _overlap_async(spec, weights, thresholds) -> dict[str, object]:
    """Async execution plane vs the sync scheduler, open-loop at the
    largest sweep batch.

    The whole timed load is preloaded (``inbox_samples`` sized to hold
    it), then one ``drain()`` consumes it: on the async plane every hop's
    pack for N+1 and the deferred detector fold for N ride inside hop N's
    (resp. N+1's) device window, so the pipeline is the steady state the
    whole time — no closed-loop push/step alternation in the timed
    region.  Overlap comes from the fenced trace spans
    (``overlap_stats``: pack+detector time inside the union of device
    spans); the acceptance bar is >=90% hidden at the non-smoke B=256.
    Both schedulers consume identical audio and the async plane is
    bit-exact by tests/test_async.py, so the throughput delta is pure
    scheduling.
    """
    B = BATCH_SWEEP[-1]
    hops = 12 if SMOKE else 48
    plan = plan_stream(spec, hop_frames=HOP_FRAMES)
    warm = plan.prime_samples + 2 * plan.hop_samples
    need = warm + hops * plan.hop_samples
    rng = np.random.default_rng(11)
    audio = rng.integers(0, 256, (B, need)).astype(np.uint8)

    out: dict[str, object] = {"batch": B, "hops": hops}
    for label, cls in (("sync", StreamScheduler),
                       ("async", AsyncStreamScheduler)):
        sched = cls(
            spec, weights, thresholds, capacity=B, initial_capacity=B,
            min_capacity=B, hop_frames=HOP_FRAMES, emit_logits=True,
            inbox_samples=need,
            obs=Observability.create(mirror_events=False),
        )
        sids = [sched.add_stream() for _ in range(B)]
        sched.push_audio_batch(sids, list(audio[:, :warm]))
        sched.drain()
        # fresh span window + metrics window: only the open-loop timed
        # drain below contributes to the overlap measurement
        sched.obs.trace.reset()
        sched.metrics.begin_window()
        sched.push_audio_batch(sids, list(audio[:, warm:]))
        t0 = time.perf_counter()
        sched.drain()
        wall = time.perf_counter() - t0
        frames = sched.metrics.frames_total()
        stats = overlap_stats(sched.obs.trace.spans())
        out[label] = {
            "wall_s": wall,
            "stream_hops_per_sec": frames / plan.frames_per_hop / wall,
            "hidden_ms": stats["hidden"] * 1e3,
            "hidden_frac": stats["hidden_frac"],
            "utilization": stats["utilization"],
            "host_ms": stats["host_total"] * 1e3,
            "device_busy_ms": stats["busy_total"] * 1e3,
            # the scheduler's own per-hop accounting of the same overlap
            "metrics": sched.metrics.overlap_summary(),
        }
        if hasattr(sched, "shutdown"):
            sched.shutdown()
    a, s = out["async"], out["sync"]
    out.update(
        # the fields the multi-device CI leg asserts on, promoted to the
        # top of the split
        hidden_ms=a["hidden_ms"],
        hidden_frac=a["hidden_frac"],
        utilization=a["utilization"],
        speedup_vs_sync=a["stream_hops_per_sec"] / s["stream_hops_per_sec"],
        hidden_target_met=bool(a["hidden_frac"] >= 0.9),
    )
    return out


def _skewed_churn(spec, weights, thresholds,
                  events: EventLog | None = None) -> dict[str, object] | None:
    """Leaves skewed onto one shard: shrink floor with vs without the
    cross-shard rebalance plane.

    Every stream joins, then every tenant off shard 0 leaves — the
    churn-unlucky shape that pinned the PR 3 pool at ``S *
    _next_pow2(fullest shard)`` because rows could not cross devices.
    The survivors keep streaming a few hops so the migrate-on-idle
    rebalance (and the shrink it unpins) actually executes; recorded is
    each pool's steady capacity next to the balanced floor ``S *
    _next_pow2(ceil(active / S))`` the acceptance criterion bounds
    against (rebalanced capacity <= 2x that floor).  Returns None on a
    1-device host, like ``_sharded_sweep``.
    """
    if jax.device_count() < 2:
        return None
    S = min(8, jax.device_count())
    mesh = make_stream_mesh(S)
    total = 8 * S
    rng = np.random.default_rng(3)
    out: dict[str, object] = {}
    for label, thr in (("no_rebalance", None), ("rebalance", 1)):
        obs = None
        if events is not None:
            # the shared bench-wide event log: this scenario is where the
            # rebalance lifecycle records come from
            obs = Observability(registry=MetricsRegistry(), trace=Tracer(),
                                events=events)
        sched = StreamScheduler(
            spec, weights, thresholds, capacity=total,
            initial_capacity=total, min_capacity=S,
            hop_frames=HOP_FRAMES, mesh=mesh, rebalance_threshold=thr,
            obs=obs,
        )
        plan = sched.plan
        warm = plan.prime_samples + 2 * plan.hop_samples
        tail = 4 * plan.hop_samples
        audio = rng.integers(0, 256, (total, warm + tail)).astype(np.uint8)
        sids = [sched.add_stream() for _ in range(total)]
        sched.push_audio_batch(sids, list(audio[:, :warm]))
        sched.drain()
        survivors = [
            sid for sid in sids
            if sched._streams[sid].slot < sched.shard_capacity
        ]
        for sid in sids:
            if sid not in survivors:
                sched.close_stream(sid)
        sched.push_audio_batch(survivors,
                               list(audio[survivors][:, warm:]))
        sched.drain()
        m = sched.metrics.summary()
        out[label] = {
            "steady_capacity": float(sched.capacity),
            "rebalances": m["rebalances"],
            "rows_migrated": m["rows_migrated"],
        }
        active = len(survivors)
    floor = S * _next_pow2(-(-active // S))
    out.update(
        shards=S, total_streams=total, active_after_churn=active,
        floor_capacity=float(floor),
        # the acceptance criterion: rebalanced steady capacity within 2x
        # of the balanced floor while the pinned pool cannot get there
        rebalance_within_2x_floor=bool(
            out["rebalance"]["steady_capacity"] <= 2 * floor
        ),
        pinned_capacity_ratio=(
            out["no_rebalance"]["steady_capacity"]
            / out["rebalance"]["steady_capacity"]
        ),
    )
    return out


def _multi_tenant(spec, weights, thresholds) -> dict[str, object]:
    """K tenant models, one megakernel launch: the fused weight pool vs
    K independent single-tenant schedulers at the SAME total stream
    count.

    The baseline is what a deployment without the pool would run: one
    scheduler per model, each advancing ``total/K`` streams with its own
    (smaller) batched hop — K host packs, K dispatches, K detector
    passes per round.  The fused pool advances all ``total`` streams in
    ONE batched hop whose kernels gather each slot-block's weight planes
    by the per-slot model index, so its launches/hop are K-independent
    (recorded per K from the megakernel's static accounting, which
    tests/test_multitenant.py pins to the traced count).  The acceptance
    bar asserted by the multi-device CI leg: fused hop throughput >= 2x
    the K-separate-schedulers baseline at K=4.
    """
    total = TENANT_TOTAL
    plan = plan_stream(spec, hop_frames=HOP_FRAMES)
    tb = max(1, total // max(TENANT_KS))
    # K complete variants of the same geometry (distinct init seeds);
    # variant 0 is the schedulers' default model
    names = [f"tenant{i}" for i in range(max(TENANT_KS))]
    variants = {names[0]: (weights, thresholds)}
    for i, name in enumerate(names[1:], start=1):
        p = kws.init_kws_params(jax.random.PRNGKey(100 + i), spec)
        variants[name] = kws.export_kws(p, spec)
    chunk = plan.hop_samples * 4
    need = plan.prime_samples + plan.hop_samples + (2 + TENANT_ROUNDS) * chunk
    rng = np.random.default_rng(13)
    audio = rng.integers(0, 256, (total, need)).astype(np.uint8)

    def drive(scheds, sid_lists, rounds):
        """Lockstep rounds over one-or-K schedulers; returns wall s."""
        pos = [plan.prime_samples + plan.hop_samples] * len(scheds)
        for j, (s, sids) in enumerate(zip(scheds, sid_lists)):
            rows = audio[j * len(sids) : (j + 1) * len(sids)]
            s.push_audio_batch(sids, list(rows[:, : pos[j]]))
            s.drain()
        for r in range(2 + rounds):  # 2 warm rounds, then timed
            if r == 2:
                for s in scheds:
                    s.metrics.begin_window()
                t0 = time.perf_counter()
            for j, (s, sids) in enumerate(zip(scheds, sid_lists)):
                rows = audio[j * len(sids) : (j + 1) * len(sids)]
                s.push_audio_batch(sids, list(rows[:, pos[j] : pos[j] + chunk]))
                s.drain()
                pos[j] += chunk
        return time.perf_counter() - t0

    per_k: dict[str, dict[str, object]] = {}
    for K in TENANT_KS:
        # fused pool: one scheduler, round-robin tenant binding
        fused = StreamScheduler(
            spec, weights, thresholds, capacity=total,
            initial_capacity=total, min_capacity=total,
            hop_frames=HOP_FRAMES, emit_logits=True,
            max_models=max(K, 2), tenant_block=tb,
        )
        for name in names[1:K]:
            fused.register_model(name, *variants[name])
        # block-contiguous binding (total/K streams per tenant): the
        # tenant-aware placement packs each tenant's streams into whole
        # blocks either way; contiguous joins keep the round deterministic
        # variant 0 rides the ctor default model (pool row 0)
        sids = [fused.add_stream(
                    model=names[t] if (t := (i * K) // total) else None)
                for i in range(total)]
        wall_f = drive([fused], [sids], TENANT_ROUNDS)
        hops_f = TENANT_ROUNDS * 4 * total
        mf = fused.metrics.summary()
        fence_f = fused.metrics.phase_summary()["fence"]
        # the same load on K independent single-tenant schedulers
        scheds, sid_lists = [], []
        for k in range(K):
            s = StreamScheduler(
                spec, *variants[names[k]], capacity=total // K,
                initial_capacity=total // K, min_capacity=total // K,
                hop_frames=HOP_FRAMES, emit_logits=True,
            )
            scheds.append(s)
            sid_lists.append([s.add_stream() for _ in range(total // K)])
        wall_b = drive(scheds, sid_lists, TENANT_ROUNDS)
        # launches/hop from the pooled megakernel's static accounting at
        # this K (pure python, no compile) — must not move with K
        mk = StreamScheduler(
            spec, weights, thresholds, capacity=4, hop_frames=HOP_FRAMES,
            backend="megakernel", max_models=max(K, 2), tenant_block=2,
        )
        per_k[str(K)] = {
            "hop_ms_p50": mf["step_ms_p50"],
            "host_pack_ms_p50": mf["host_pack_ms_p50"],
            "fence_ms_p50": fence_f["ms_p50"],
            "stream_hops_per_sec": hops_f / wall_f,
            "dispatches_per_emit_hop": mk._model.dispatches_per_hop(True),
            "dispatches_per_steady_hop": mk._model.dispatches_per_hop(False),
            "baseline": {
                "schedulers": K,
                "streams_each": total // K,
                "stream_hops_per_sec": hops_f / wall_b,
                "wall_s": wall_b,
            },
            "speedup_vs_separate": wall_b / wall_f,
        }
    emit_counts = {c["dispatches_per_emit_hop"] for c in per_k.values()}
    k4 = per_k.get("4", {})
    return {
        "total_streams": total,
        "hop_frames": HOP_FRAMES,
        "tenant_block": tb,
        "per_k": per_k,
        "launches_k_independent": len(emit_counts) == 1,
        "speedup_at_k4": k4.get("speedup_vs_separate"),
        # the multi-device CI leg's acceptance bar (full runs only)
        "k4_target_met": bool((k4.get("speedup_vs_separate") or 0.0) >= 2.0),
    }


def _lm_elastic(events) -> dict[str, object]:
    """LM decode on the shared slot pool: tokens/s under grow/shrink churn.

    The serving engine rides the same ``repro.runtime.SlotPool`` as the
    streaming scheduler; this split measures continuous-batching decode
    throughput at slot-pool ceilings {4, 8, 16}.  Each config starts the
    pool at 2 slots and feeds waves of mixed-length requests: admission
    doubles capacity up to the ceiling (``lm_resize`` grow, emitted by the
    pool), the short tail finishing and the end-of-wave drain shrink it
    back (``lm_resize`` shrink) — so every timed wave crosses at least one
    grow and one shrink mid-decode.  Throughput is generated tokens over
    wall; resize lifecycle counts come from the pool's own event stream
    (landing in the shared lifecycle JSONL artifact).
    """
    from repro.configs.base import get_arch
    from repro.models import api
    from repro.serve.engine import Engine, Request

    cfg = get_arch("qwen3-0.6b", smoke=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    max_new = 4 if SMOKE else 8
    configs: dict[str, dict] = {}

    def wave(eng, rid0: int, n_req: int) -> tuple[int, int]:
        for i in range(n_req):
            eng.submit(Request(
                rid=rid0 + i,
                prompt=np.arange(6, dtype=np.int32) + rid0 + i,
                # alternate short/long so finishes skew occupancy and the
                # shrink path runs while the long half still decodes
                max_new_tokens=2 if i % 2 else max_new,
            ))
        done = eng.run_until_drained_async()
        return rid0 + n_req, sum(len(r.out_tokens) for r in done)

    for slots in LM_ELASTIC_SLOTS:
        obs = Observability(registry=MetricsRegistry(), trace=Tracer(),
                            events=events)
        eng = Engine(cfg, params, batch_slots=2, max_seq=64, obs=obs,
                     max_slots=slots, min_slots=2)
        # untimed warm wave: compiles decode at every pow-2 capacity the
        # elastic pool visits, so the timed waves measure the runtime,
        # not jit
        rid, _ = wave(eng, 0, 2 * slots)
        seq0 = events.seq
        tokens = 0
        t0 = time.perf_counter()
        for _ in range(LM_ELASTIC_WAVES):
            rid, t = wave(eng, rid, 2 * slots)
            tokens += t
        wall = time.perf_counter() - t0
        resizes = [e for e in events.tail()
                   if e["event"] == "lm_resize" and e["seq"] >= seq0]
        grew = [e for e in resizes if e["new"] > e["old"]]
        shrank = [e for e in resizes if e["new"] < e["old"]]
        configs[str(slots)] = {
            "tokens": tokens,
            "wall_s": wall,
            "tokens_per_sec": tokens / wall,
            "requests": rid,
            "resizes_grow": len(grew),
            "resizes_shrink": len(shrank),
            "peak_capacity": max((e["new"] for e in grew), default=2),
            "final_capacity": eng.slots,
        }
    return {
        "arch": "qwen3-0.6b (smoke)",
        "min_slots": 2,
        "waves": LM_ELASTIC_WAVES,
        "max_new_tokens": max_new,
        "configs": configs,
    }


def _sharded_sweep(spec, weights, thresholds) -> dict[str, object] | None:
    """>=1024 streams on one logical pool across 1/2/8 shards.

    The same total stream count runs against a single-device pool and
    against mesh-sharded pools, so the aggregate streams/s comparison
    isolates what sharding the slot-pool batch axis buys.  Returns None
    on a 1-device host (e.g. another suite initialized jax before this
    module could force 8 host devices) so a degraded run never clobbers
    a committed multi-device sweep.
    """
    if jax.device_count() < 2:
        return None
    shards = [s for s in SHARD_CONFIGS if s <= jax.device_count()]
    configs: dict[str, dict[str, float]] = {}
    configs_per_stage: dict[str, dict[str, float]] = {}
    configs_fused: dict[str, dict[str, float]] = {}
    for s in shards:
        mesh = make_stream_mesh(s) if s > 1 else None
        kw = dict(mesh=mesh, warm_rounds=1, timed_rounds=SHARD_TIMED_ROUNDS,
                  chunk_hops=2, hop_frames=SHARD_HOP_FRAMES)
        # the committed trajectory row (plain-XLA backend), the per-stage
        # kernel path (before: one launch per stage), and the fused
        # megakernel (after: ONE launch per shard per hop, emit included)
        configs[str(s)] = _steady(spec, weights, thresholds, SHARD_TOTAL,
                                  **kw)
        configs_per_stage[str(s)] = _steady(
            spec, weights, thresholds, SHARD_TOTAL, backend="pallas", **kw
        )
        configs_fused[str(s)] = _steady(
            spec, weights, thresholds, SHARD_TOTAL, backend="megakernel",
            **kw
        )
    single = configs.get("1", {}).get("stream_hops_per_sec")
    multi = [
        c["stream_hops_per_sec"] for k, c in configs.items() if int(k) > 1
    ]
    f_single = configs_fused.get("1", {}).get("stream_hops_per_sec")
    f_multi = [c["stream_hops_per_sec"] for k, c in configs_fused.items()
               if int(k) > 1]
    top = str(max(shards))
    return {
        "total_streams": SHARD_TOTAL,
        "devices": jax.device_count(),
        "hop_frames": SHARD_HOP_FRAMES,
        "configs": configs,
        # the before/after device-ms split of the fusion: per-stage
        # kernel launches vs the hop megakernel, same pool, same mesh
        "configs_per_stage": configs_per_stage,
        "configs_fused": configs_fused,
        "fused_vs_per_stage_fence_p50": (
            configs_per_stage[top]["fence_ms_p50"]
            / configs_fused[top]["fence_ms_p50"]
            if configs_fused[top]["fence_ms_p50"] else None
        ),
        "best_single_stream_hops_per_sec": single,
        "best_multi_stream_hops_per_sec": max(multi) if multi else None,
        "multi_vs_single": (max(multi) / single) if multi and single else None,
        "fused_multi_vs_single": (
            max(f_multi) / f_single if f_multi and f_single else None
        ),
    }


def run() -> list[str]:
    spec = kws.build_kws_smoke_spec()
    params = kws.init_kws_params(jax.random.PRNGKey(0), spec)
    weights, thresholds = kws.export_kws(params, spec)
    prog = compiler.compile_model(spec, weights, thresholds)
    prev = json.loads(_OUT.read_text()) if _OUT.exists() else {}

    # ---- offline baseline: full re-run per emitted frame --------------------
    rng = np.random.default_rng(0)
    clip = gscd.sample(rng, 0, n=spec.in_len)
    ex = Executor(prog)
    ex.run(clip[:, None])  # warm caches
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        ex.run(clip[:, None])
    t_rerun = (time.perf_counter() - t0) / reps
    # every new frame on every stream would pay one full re-run
    baseline_fps = BATCH_SWEEP[0] / t_rerun

    # ---- shared observability artifacts ------------------------------------
    # one event log across every scenario (steady joins, churn
    # join/close/resize, skewed-churn rebalance) -> the lifecycle JSONL
    # artifact; one tracer on the B=8 steady config -> the Chrome trace
    suffix = "_smoke" if SMOKE else ""
    trace_path = _OUT.with_name(f"BENCH_stream_trace{suffix}.json")
    events_path = _OUT.with_name(f"BENCH_stream_events{suffix}.jsonl")
    events = EventLog(path=str(events_path), mirror=False, mode="w")

    def _obs() -> Observability:
        return Observability(registry=MetricsRegistry(), trace=Tracer(),
                             events=events)

    steady_obs = _obs()

    # ---- steady-state sweep + host-pack micro + churn + sharded sweep ------
    sweep = {
        b: _steady(spec, weights, thresholds, b,
                   obs=steady_obs if b == BATCH_SWEEP[0] else None)
        for b in BATCH_SWEEP
    }
    trace_events = steady_obs.trace.export_chrome()
    span_coverage = coverage(trace_events)
    n_trace = steady_obs.trace.export_chrome(path=str(trace_path))
    obs_over = _obs_overhead(spec, sweep[BATCH_SWEEP[-1]]["hop_ms_p50"],
                             n_streams=BATCH_SWEEP[-1],
                             rounds=200 if SMOKE else 2000)
    pack_plan = plan_stream(spec, hop_frames=SHARD_HOP_FRAMES)
    host_pack = _host_pack_micro(pack_plan.hop_samples,
                                 rounds=2 if SMOKE else 8)
    churn = _churn(spec, weights, thresholds, obs=_obs())
    overlap = _overlap_async(spec, weights, thresholds)
    multi_tenant = _multi_tenant(spec, weights, thresholds)
    sharded = _sharded_sweep(spec, weights, thresholds)
    sharded_skipped = sharded is None
    if sharded_skipped:
        # carry the previously committed multi-device sweep through, but
        # mark it stale in the artifact itself — this run never saw it
        sharded = prev.get("sharded")
        if sharded is not None:
            sharded = {**sharded, "carried_from_prior_run": True}
    skewed = _skewed_churn(spec, weights, thresholds, events=events)
    skewed_skipped = skewed is None
    if skewed_skipped:
        skewed = prev.get("skewed_churn")
        if skewed is not None:
            skewed = {**skewed, "carried_from_prior_run": True}
    lm_elastic = _lm_elastic(events)
    events.flush()
    event_counts = events.counts()
    events.close()

    # ---- per-hop device-dispatch accounting (static, plan + backend) -------
    def _disp(backend: str) -> dict[str, int]:
        s = StreamScheduler(spec, weights, thresholds, capacity=2,
                            hop_frames=SHARD_HOP_FRAMES, backend=backend)
        return {"emit": s._model.dispatches_per_hop(True),
                "steady": s._model.dispatches_per_hop(False)}

    disp = {b: _disp(b) for b in ("jnp", "pallas", "megakernel")}
    device_dispatches = {
        # per-shard pallas_call launches for one hop, by backend; "emit"
        # includes the ghost flush + classifier tail.  The fused target
        # from the megakernel issue is <= 2 launches per emit hop.
        "per_hop_emit": {b: d["emit"] for b, d in disp.items()},
        "per_hop_steady": {b: d["steady"] for b, d in disp.items()},
        "fused_target": 2,
        "fused_target_met": disp["megakernel"]["emit"] <= 2,
    }

    b0 = sweep[BATCH_SWEEP[0]]
    speedup = b0["frames_per_sec"] / baseline_fps
    prev_p50 = prev.get("step_ms_p50")
    # None -> null: keeps the committed artifact strict-JSON when there is
    # no prior BENCH_stream.json to compare against
    hop_speedup = (prev_p50 / b0["hop_ms_p50"]) if prev_p50 else None

    payload = {
        "n_streams": BATCH_SWEEP[0],
        "hop_frames": HOP_FRAMES,
        "smoke": SMOKE,
        "frames_per_sec": b0["frames_per_sec"],
        "frame_latency_ms": 1e3 / b0["frames_per_sec"],
        "step_ms_p50": b0["hop_ms_p50"],
        "step_ms_p95": b0["hop_ms_p95"],
        "step_ms_p99": b0["hop_ms_p99"],
        "step_ms_p999": b0["hop_ms_p999"],
        "latency_estimated": b0["latency_estimated"],
        # the fenced per-phase hop breakdown at B=8 (pack / dispatch /
        # device / detector quantiles + share of hop wall) — CI asserts
        # these fields exist and the phase names match the trace spans
        "phases": b0["phases"],
        "trace": {
            "artifact": trace_path.name,
            "events": n_trace,
            "span_coverage": span_coverage,
        },
        "event_log": {
            "artifact": events_path.name,
            "counts": event_counts,
        },
        # instrumentation hot-path cost vs the <=2% of hop-p50 bound
        "obs_overhead": obs_over,
        "audio_sec_per_wall_sec": b0["audio_sec_per_wall_sec"],
        "baseline_rerun_s": t_rerun,
        "baseline_frames_per_sec": baseline_fps,
        "speedup_vs_rerun": speedup,
        "prev_step_ms_p50": prev_p50,
        "hop_speedup_vs_prev": hop_speedup,
        # host-side per-hop packing at B=1024: the field CI asserts on
        # (vectorized arena gather), with the pre-arena per-slot loop and
        # the reduction recorded next to it
        "host_pack_ms": host_pack["host_pack_ms_after"],
        "host_pack": host_pack,
        "sweep": {str(b): sweep[b] for b in BATCH_SWEEP},
        "churn": churn,
        # async execution plane vs sync at the largest sweep batch,
        # open-loop: hidden_ms / utilization are what CI asserts on
        "overlap": overlap,
        # per-hop launch counts by backend + the fused <=2 target (CI
        # asserts fused_target_met on the multi-device leg)
        "device_dispatches": device_dispatches,
        # K tenant models on one batched dispatch: per-K hop p50 +
        # launches/hop + speedup vs K separate schedulers (CI asserts
        # the >=2x bar at K=4 on the committed full-run artifact)
        "multi_tenant": multi_tenant,
        # the LM engine on the same shared SlotPool: decode tokens/s at
        # slot ceilings {4,8,16} under grow/shrink churn (lm_resize
        # lifecycle asserted by CI from the shared event log)
        "lm_elastic": lm_elastic,
        "sharded": sharded,
        # shrink-floor capacity with vs without the cross-shard rebalance
        # plane under one-shard-skewed leave churn (CI asserts on this)
        "skewed_churn": skewed,
    }
    # smoke runs park their (low-round, noisy) numbers next to the real
    # artifact so they can never corrupt the committed perf trajectory
    out_path = _OUT.with_name("BENCH_stream_smoke.json") if SMOKE else _OUT
    out_path.write_text(json.dumps(payload, indent=2) + "\n")

    out = [
        row("stream.frames_per_sec", f"{b0['frames_per_sec']:.1f}",
            f"B={BATCH_SWEEP[0]} streams, per-hop logits on"),
        row("stream.hop_ms_p50", f"{b0['hop_ms_p50']:.3f}",
            "steady-state hop -> finalized logits"),
        row("stream.hop_ms_p99", f"{b0['hop_ms_p99']:.3f}",
            f"p999 {b0['hop_ms_p999']:.3f}; "
            f"{'exact' if not b0['latency_estimated'] else 'histogram est'}"),
        row("stream.host_pack_ms_b1024", f"{host_pack['host_pack_ms_after']:.3f}",
            f"arena gather; per-slot loop was "
            f"{host_pack['host_pack_ms_before']:.3f}"),
        row("stream.host_pack_reduction", f"{host_pack['reduction']:.1f}",
            f"{'PASS' if host_pack['reduction'] >= 5 else 'FAIL'} "
            "(floor 5x, B=1024)"),
        row("stream.uj_per_inference", f"{b0['uj_per_inference']:.4f}",
            "measured ledger: mac+sa+sram+ctrl"),
    ]
    for p in ("pack", "dispatch", "fence", "fetch", "detector"):
        ph = b0["phases"][p]
        out.append(row(f"stream.phase_{p}_ms_p50", f"{ph['ms_p50']:.3f}",
                       f"p99 {ph['ms_p99']:.3f}, "
                       f"{ph['share_of_wall']*100:.1f}% of hop wall"))
    out.extend([
        row("stream.trace_coverage", f"{span_coverage:.3f}",
            f"{'PASS' if span_coverage >= 0.95 else 'FAIL'} (floor 0.95); "
            f"{n_trace} spans -> {trace_path.name}"),
        row("stream.obs_overhead_pct", f"{obs_over['overhead_frac']*100:.3f}",
            f"{'PASS' if obs_over['within_2pct'] else 'FAIL'} (<=2% of hop "
            f"p50 at B={BATCH_SWEEP[-1]}; "
            f"{obs_over['instrument_ms_per_hop']*1e3:.1f} us/hop)"),
        row("stream.event_log", f"{sum(event_counts.values())}",
            ", ".join(f"{k}={v}" for k, v in sorted(event_counts.items()))
            + f" -> {events_path.name}"),
    ])
    for b in BATCH_SWEEP[1:]:
        out.append(row(f"stream.hop_ms_p50_b{b}",
                       f"{sweep[b]['hop_ms_p50']:.3f}",
                       f"B={b}, {sweep[b]['frames_per_sec']:.0f} frames/s"))
    if prev_p50:
        out.append(row("stream.hop_p50_vs_prev", f"{hop_speedup:.2f}",
                       "x prior committed BENCH_stream.json"))
    for s, c in sorted(lm_elastic["configs"].items(),
                       key=lambda kv: int(kv[0])):
        out.append(row(
            f"stream.lm_elastic_s{s}", f"{c['tokens_per_sec']:.1f}",
            f"LM decode tok/s, slot ceiling {s}; grow {c['resizes_grow']} "
            f"shrink {c['resizes_shrink']}, peak cap {c['peak_capacity']}",
        ))
    if sharded_skipped:
        out.append(row(
            "stream.sharded", "SKIP",
            "1 device visible; run this suite alone (or set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8); prior sweep kept",
        ))
    if sharded is not None:
        for s, c in sorted(sharded["configs"].items(),
                           key=lambda kv: int(kv[0])):
            out.append(row(f"stream.sharded_x{s}",
                           f"{c['stream_hops_per_sec']:.1f}",
                           f"stream-hops/s, {sharded['total_streams']} streams, "
                           f"hop p50 {c['hop_ms_p50']:.1f} ms"))
        ratio = sharded["multi_vs_single"]
        if ratio is not None and not sharded_skipped:
            out.append(row(
                "stream.sharded_speedup", f"{ratio:.2f}",
                f"{'PASS' if ratio > 1.0 else 'FAIL'} "
                "(multi-shard > single device, same total streams)",
            ))
        fused = sharded.get("configs_fused") or {}
        for s, c in sorted(fused.items(), key=lambda kv: int(kv[0])):
            ps = sharded["configs_per_stage"][s]
            out.append(row(
                f"stream.fused_x{s}", f"{c['stream_hops_per_sec']:.1f}",
                f"megakernel stream-hops/s; fence p50 "
                f"{c['fence_ms_p50']:.1f} ms vs per-stage "
                f"{ps['fence_ms_p50']:.1f} ms, "
                f"{c['device_dispatches_per_hop']:.0f} vs "
                f"{ps['device_dispatches_per_hop']:.0f} launches/hop",
            ))
        fvp = sharded.get("fused_vs_per_stage_fence_p50")
        if fvp is not None and not sharded_skipped:
            out.append(row(
                "stream.fused_vs_per_stage", f"{fvp:.2f}",
                f"{'PASS' if fvp > 1.0 else 'FAIL'} (fused hop fence p50 "
                "faster than per-stage launches, same pool)",
            ))
        fms = sharded.get("fused_multi_vs_single")
        if fms is not None and not sharded_skipped:
            out.append(row(
                "stream.fused_sharded_speedup", f"{fms:.2f}",
                "megakernel multi-shard vs single, same total streams",
            ))
    if skewed_skipped:
        out.append(row(
            "stream.skewed_churn", "SKIP",
            "1 device visible; prior scenario kept" if skewed is not None
            else "1 device visible",
        ))
    if skewed is not None:
        reb = skewed["rebalance"]
        pin = skewed["no_rebalance"]
        out.append(row(
            "stream.skewed_churn_capacity",
            f"{reb['steady_capacity']:.0f}",
            f"{'PASS' if skewed['rebalance_within_2x_floor'] else 'FAIL'} "
            f"(<= 2x floor {skewed['floor_capacity']:.0f}; pinned pool "
            f"stuck at {pin['steady_capacity']:.0f}, "
            f"{reb['rows_migrated']:.0f} rows migrated)",
        ))
    out.extend([
        row("stream.realtime_factor", f"{b0['audio_sec_per_wall_sec']:.1f}",
            "audio-sec per wall-sec"),
        row("stream.baseline_rerun_fps", f"{baseline_fps:.1f}",
            "full re-run per frame"),
        row("stream.speedup_vs_rerun", f"{speedup:.1f}",
            f"{'PASS' if speedup >= 2 else 'FAIL'} (floor 2x)"),
        row("stream.churn_resizes", f"{churn['resizes']:.0f}",
            f"elastic pool peak {churn['peak_capacity']:.0f} -> "
            f"final {churn['final_capacity']:.0f}"),
        row("stream.churn_hop_ms_p50", f"{churn['hop_ms_p50']:.3f}",
            f"{CHURN_STREAMS} streams join/leave, cap {CHURN_CAP}"),
        row("stream.overlap_hidden_pct",
            f"{overlap['hidden_frac']*100:.1f}",
            f"{'PASS' if overlap['hidden_target_met'] else 'FAIL'} "
            f"(>=90% pack+detector hidden under device, "
            f"B={overlap['batch']} open-loop, "
            f"{overlap['hidden_ms']:.1f} ms hidden)"),
        row("stream.overlap_speedup", f"{overlap['speedup_vs_sync']:.2f}",
            f"async vs sync stream-hops/s at B={overlap['batch']}; "
            f"device util {overlap['utilization']*100:.1f}%"),
        *[
            row(f"stream.tenant_k{K}",
                f"{c['stream_hops_per_sec']:.1f}",
                f"fused-pool stream-hops/s at {multi_tenant['total_streams']}"
                f" streams; hop p50 {c['hop_ms_p50']:.2f} ms, "
                f"{c['dispatches_per_emit_hop']:.0f} launches/emit-hop, "
                f"{c['speedup_vs_separate']:.2f}x vs {K} separate")
            for K, c in sorted(
                ((int(k), c) for k, c in multi_tenant["per_k"].items())
            )
        ],
        row("stream.tenant_speedup_k4",
            f"{multi_tenant['speedup_at_k4']:.2f}",
            f"{'PASS' if multi_tenant['k4_target_met'] else 'FAIL'} "
            "(fused pool >= 2x K=4 separate schedulers, same total "
            "streams; launches/hop K-independent: "
            f"{multi_tenant['launches_k_independent']})"),
        row("stream.dispatches_per_emit_hop",
            f"{device_dispatches['per_hop_emit']['megakernel']}",
            f"{'PASS' if device_dispatches['fused_target_met'] else 'FAIL'} "
            f"(fused target <= {device_dispatches['fused_target']}; "
            f"per-stage pallas "
            f"{device_dispatches['per_hop_emit']['pallas']}, jnp "
            f"{device_dispatches['per_hop_emit']['jnp']})"),
        row("stream.artifact", out_path.name,
            "perf trajectory" if not SMOKE else "smoke numbers, kept apart"),
    ])
    return out


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for line in run():
        print(line, flush=True)
