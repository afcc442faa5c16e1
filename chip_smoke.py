#!/usr/bin/env python3
"""Bring-up smoke run of the always-on KWS streaming runtime on a TPU.

    python chip_smoke.py             # one chip: four runs at the paper's width
    python chip_smoke.py --chips 4   # the mesh-sharded slot pool, four chips

One chip: the paper's network (``kws.build_kws_spec()``, width 64, 1 s
windows, 8-bit input) with seeded random weights serves 256 concurrent
streams.  Each stream gets 1 s of seeded synthetic audio
(``repro.data.gscd``), pushed as ragged 10-100 ms chunks through
``push_audio_batch``; the scheduler steps until starved after each round of
chunks, then every stream is closed.  This runs once per hop backend
(``jnp``, ``pallas``, ``megakernel``, all compiled, never interpreted) and
once through ``AsyncStreamScheduler`` on ``jnp`` with donated buffers.
Every run's per-hop logits and close logits must equal the ``jnp`` run's
bit for bit, and four streams' close logits must equal the offline
``Executor``.

``--chips 4`` runs only the mesh phase: 1,024 streams on a 4-shard
``make_stream_mesh`` pool (256 per shard) for each backend, checked per
stream against the same audio through a one-device scheduler, plus a check
that the slot state really is spread over the four devices.

The timings printed are smoke timings, not benchmark numbers.  Any failure
exits non-zero; the last line of a passing run is one JSON object naming
the device.  There is no CPU mode: without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_STREAMS = 256
HOP_FRAMES = 2
CHUNK_MS = (10, 100)
N_EXECUTOR = 4
BACKENDS = ("jnp", "pallas", "megakernel")


class SmokeFailure(AssertionError):
    """A run disagreed with its reference."""


@dataclasses.dataclass
class Run:
    """What one scheduler run emitted, keyed by stream id."""

    label: str
    hops: dict[int, list[np.ndarray]]   # per-hop emitted logits, in order
    closes: dict[int, np.ndarray]       # close (flushed) logits
    steps: int                          # batched hop steps
    launches_per_hop: int               # pallas_calls per emit hop
    compile_s: float
    wall_s: float
    new_jit_entries: int                # step cache entries added by traffic

    def line(self) -> str:
        stream_hops = sum(len(v) for v in self.hops.values())
        return (f"run {self.label}: streams={len(self.closes)} "
                f"steps={self.steps} stream_hops={stream_hops} "
                f"launches_per_hop={self.launches_per_hop} "
                f"new_jit_entries={self.new_jit_entries} "
                f"compile_s={self.compile_s:.2f} wall_s={self.wall_s:.2f} "
                "(smoke timings, not benchmark numbers)")


def make_model(spec, seed: int):
    """Seeded random weights through the normal export path."""
    import jax

    from repro.models import kws

    params = kws.init_kws_params(jax.random.PRNGKey(seed), spec)
    return kws.export_kws(params, spec)


def make_traffic(n_streams: int, n_samples: int, seed: int,
                 sample_rate: int = 16000):
    """Seeded clips, one per stream, and each clip cut into ragged chunks
    of ``CHUNK_MS`` milliseconds."""
    from repro.data import gscd

    rng = np.random.default_rng(seed)
    clips = [gscd.sample(rng, int(rng.integers(gscd.N_CLASSES)), n_samples)
             for _ in range(n_streams)]
    lo, hi = (ms * sample_rate // 1000 for ms in CHUNK_MS)
    chunks = []
    for clip in clips:
        cuts = np.cumsum(rng.integers(lo, hi + 1, n_samples // lo + 1))
        chunks.append(np.split(clip, cuts[cuts < n_samples]))
    return clips, chunks


def stream_run(spec, weights, thresholds, chunks, *, backend: str,
               interpret: bool, mesh=None, async_plane: bool = False
               ) -> Run:
    """Drive one scheduler through the whole traffic: join every stream,
    push chunk rounds with ``push_audio_batch``, step until starved after
    each round, close every stream."""
    from repro.stream import AsyncStreamScheduler, StreamScheduler

    n = len(chunks)
    cls = AsyncStreamScheduler if async_plane else StreamScheduler
    sched = cls(spec, weights, thresholds, capacity=n, initial_capacity=n,
                min_capacity=n, hop_frames=HOP_FRAMES, backend=backend,
                interpret=interpret, mesh=mesh)
    try:
        sids = [sched.add_stream() for _ in range(n)]
        t0 = time.perf_counter()
        sched.warm(sched.capacity)
        compile_s = time.perf_counter() - t0
        entries = sched._jit_entries()
        hops: dict[int, list[np.ndarray]] = {sid: [] for sid in sids}
        t0 = time.perf_counter()
        for r in range(max(len(c) for c in chunks)):
            live = [j for j, c in enumerate(chunks) if r < len(c)]
            sched.push_audio_batch([sids[j] for j in live],
                                   [chunks[j][r] for j in live])
            for sid, _frame, logits, _det in sched.run_until_starved():
                hops[sid].append(logits)
        closes = {sid: sched.close_stream(sid).logits for sid in sids}
        wall_s = time.perf_counter() - t0
        label = backend + (" async" if async_plane else "")
        if mesh is not None:
            label += f" mesh={sched.n_shards}"
            _check_spread(sched, mesh)
        return Run(label, hops, closes, sched.metrics.steps,
                   sched._model.dispatches_per_hop(True), compile_s, wall_s,
                   sched._jit_entries() - entries)
    finally:
        if async_plane:
            sched.shutdown()


def _check_spread(sched, mesh) -> None:
    """The sharded slot state must hold one equal block per device, not
    land everything on one device."""
    shards = sched._gap.addressable_shards
    devices = {s.device for s in shards}
    rows = {s.data.shape[0] for s in shards}
    want = sched.capacity // sched.n_shards
    if len(devices) != mesh.size or rows != {want}:
        raise SmokeFailure(
            f"slot state not spread over the mesh: {len(devices)} devices, "
            f"rows per shard {sorted(rows)}, want {mesh.size} x {want}")


def check_same(ref: Run, run: Run) -> None:
    """Per stream, the same hop logits in the same order and the same
    close logits, bit for bit."""
    if ref.hops.keys() != run.hops.keys():
        raise SmokeFailure(f"{run.label}: stream ids differ from {ref.label}")
    for sid, want in ref.hops.items():
        got = run.hops[sid]
        if len(got) != len(want) or not all(
                np.array_equal(a, b) for a, b in zip(want, got)):
            raise SmokeFailure(
                f"{run.label}: stream {sid} hop logits differ from "
                f"{ref.label}")
        if not np.array_equal(ref.closes[sid], run.closes[sid]):
            raise SmokeFailure(
                f"{run.label}: stream {sid} close logits differ from "
                f"{ref.label}")


def check_executor(spec, weights, thresholds, clips, run: Run,
                   n: int = N_EXECUTOR) -> None:
    """Close logits of the first ``n`` streams against the offline
    executor on the whole clip."""
    from repro.core import compiler, executor
    from repro.models import kws

    prog = compiler.compile_model(
        spec, weights, thresholds,
        rotate_hints=kws.ROTATE_HINTS, rowsplit_hints=kws.ROWSPLIT_HINTS,
    )
    for sid, clip in list(zip(run.closes, clips))[:n]:
        want = executor.Executor(prog).run(clip[:, None]).output.ravel()
        if not np.array_equal(run.closes[sid], want):
            raise SmokeFailure(
                f"{run.label}: stream {sid} close logits differ from the "
                f"offline executor: {run.closes[sid]} vs {want}")


def stream_phase(spec, *, n_streams: int = N_STREAMS, seed: int = 0,
                 interpret: bool = False, report=print) -> list[Run]:
    """The one-chip phase: every backend plus the async plane on the
    same traffic, each checked against the ``jnp`` run, and the ``jnp``
    run checked against the offline executor."""
    weights, thresholds = make_model(spec, seed)
    clips, chunks = make_traffic(n_streams, spec.in_len, seed)
    runs = []
    for backend, async_plane in [(b, False) for b in BACKENDS] + [
            ("jnp", True)]:
        run = stream_run(spec, weights, thresholds, chunks, backend=backend,
                         interpret=interpret, async_plane=async_plane)
        report(run.line())
        if runs:
            check_same(runs[0], run)
        runs.append(run)
    check_executor(spec, weights, thresholds, clips, runs[0])
    report(f"check: {len(runs)} runs bit-exact with jnp over {n_streams} "
           f"streams; {N_EXECUTOR} streams bit-exact with the executor")
    return runs


def mesh_phase(spec, *, n_shards: int = 4, per_shard: int = N_STREAMS,
               seed: int = 0, interpret: bool = False, report=print
               ) -> list[Run]:
    """The four-chip phase: the sharded pool for each backend against one
    device on the same audio, grouped by stream id."""
    from repro.launch.mesh import make_stream_mesh

    weights, thresholds = make_model(spec, seed)
    _, chunks = make_traffic(n_shards * per_shard, spec.in_len, seed)
    ref = stream_run(spec, weights, thresholds, chunks, backend="jnp",
                     interpret=interpret)
    report(ref.line())
    mesh = make_stream_mesh(n_shards)
    runs = [ref]
    for backend in BACKENDS:
        run = stream_run(spec, weights, thresholds, chunks, backend=backend,
                         interpret=interpret, mesh=mesh)
        report(run.line())
        check_same(ref, run)
        runs.append(run)
    report(f"check: {len(BACKENDS)} sharded runs bit-exact with one device "
           f"over {n_shards * per_shard} streams")
    return runs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phase; 4: only the mesh phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import kws

    kind = devices[0].device_kind
    print(f"jax {jax.__version__} platform={platform} device_kind={kind} "
          f"device_count={len(devices)}", flush=True)
    print(f"compile cache: {use_compile_cache()}", flush=True)
    spec = kws.build_kws_spec()
    report = lambda s: print(s, flush=True)  # noqa: E731
    if args.chips == 4:
        mesh_phase(spec, report=report)
    else:
        stream_phase(spec, report=report)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
