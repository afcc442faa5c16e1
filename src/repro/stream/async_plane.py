"""Async execution plane: overlap ingest, pack, and device compute.

The synchronous ``StreamScheduler.step_batch`` is strictly serial —
push -> pack -> dispatch -> block -> fold — so ~1 ms of host pack and
the detector fold sit on the critical path even though device compute
dominates at scale.  This module is the runtime-level twin of the
paper's flexible ping-pong feature SRAM (§II-C): stage the next tile
while the current one computes.

``AsyncStreamScheduler`` keeps the scheduler's math, state, and slot
machinery byte-for-byte identical and changes only *when* the host-side
stages run:

  * **Ingest pump** — ``push_audio_batch`` enqueues to a daemon thread
    that lands samples in the shared ``RingArena`` (one slice copy per
    chunk) while the main thread packs and dispatches.  Arena mutations
    are serialized by the scheduler's ingest lock and marked by the
    arena's seqlock generation, so lock-free observers can detect (and
    retry past) a torn read instead of consuming one.
  * **Double-buffered hop dispatch** — pack hop N+1 and launch it on
    hop N's *unforced* result futures (JAX async dispatch chains them
    device-side).  With ``donate_buffers`` (default on) the slot-state
    operands are donated to each step, so a restep aliases instead of
    copying tails/pendings.  The fence + fold for hop N run at its
    *retirement*, when hop N+1 is already executing — the pack,
    detector, and metrics work hide under device compute.
  * **Deferred FIFO fold** — retirements apply detector/metrics/event
    results strictly in dispatch order, so every slot sees the exact
    posterior sequence the synchronous schedule would produce:
    detections, hysteresis state, frame counts, and the event log's
    per-stream lifecycle are bit-identical (tests/test_async.py).
  * **Epoch barriers** — elastic resize, cross-shard rebalance,
    mass-join priming, ``peek``, and ``close_stream`` first drain every
    in-flight hop, then remap/prime exactly as the synchronous path
    would, then let the pipeline refill.  ``SlotPlacement``,
    ``ops.remap_slot_rows``, and ``prime_batch`` are untouched; a remap
    can never invalidate an in-flight hop's row indices.

Pipeline depth is 1 by default (classic double buffering); deeper
pipelines only add queue latency before the fold without increasing
overlap, since one hop's compute already hides the next hop's host work.
"""
from __future__ import annotations

import threading

import numpy as np

# the double-buffered in-flight queue, epoch-barrier protocol, and the
# ingest pump are the generic async plane (shared with the LM engine);
# IngestPump is re-exported because this module is its historical home
from repro.runtime.async_plane import InFlightQueue, IngestPump
from repro.stream.detector import Detection
from repro.stream.scheduler import (
    HopBatch,
    StreamResult,
    StreamScheduler,
    _Hop,
)

__all__ = ["AsyncStreamScheduler", "IngestPump"]


class AsyncStreamScheduler(StreamScheduler):
    """``StreamScheduler`` with the async execution plane switched on.

    Drop-in: the constructor, ``push_audio*``, ``step``/``step_batch``,
    ``drain``, ``peek``, ``close_stream`` signatures are unchanged and
    the results are bit-identical to the synchronous scheduler for any
    interleaving of calls.  The differences are operational:

      * ``push_audio_batch`` returns before samples land (the pump
        applies them; push errors surface at the next ``flush``/
        ``drain``/``peek``/``close_stream``);
      * ``step_batch`` may return ``None`` for a hop it *dispatched*
        (still in flight) and returns hop N's results while hop N+1
        executes — results arrive one call later than the sync path,
        in the same order;
      * ``drain()`` is the safe settling point: pump flushed, every
        in-flight hop retired, every ghost end-of-stream flush
        performed before it returns.

    Use ``shutdown()`` (or rely on the daemon pump dying with the
    process) when discarding the scheduler.
    """

    def __init__(self, *args, pipeline_depth: int = 1,
                 use_pump: bool = True, **kwargs) -> None:
        kwargs.setdefault("donate_buffers", True)
        super().__init__(*args, **kwargs)
        assert pipeline_depth >= 1, pipeline_depth
        self._depth = pipeline_depth
        self._inflight = InFlightQueue(self._retire_inflight,
                                       depth=pipeline_depth)
        self._dispatched_total = 0
        # serializes arena/placement/bookkeeping mutations between the
        # main thread (pack/fold/lifecycle) and the pump (push scatter);
        # the device queue itself needs no lock — only the main thread
        # dispatches
        self._lock = threading.RLock()
        # declare the epoch barrier to the slot pool: EVERY structural
        # mutation (grow-on-alloc, shrink-on-close, cross-shard
        # rebalance) drains the pipeline first, on every path, instead of
        # per-call-site overrides
        self._slots.pre_structural = self._pre_structural
        self._pump = IngestPump(self._apply_push) if use_pump else None

    # -- ingest (pumped) -----------------------------------------------------

    def _apply_push(self, sids, chunks) -> None:
        with self._lock:
            StreamScheduler.push_audio_batch(self, sids, chunks)

    def push_audio_batch(self, sids, chunks) -> None:
        if self._pump is None:
            self._apply_push(sids, chunks)
        else:
            self._pump.submit(sids, chunks)

    def push_audio(self, sid: int, audio: np.ndarray) -> None:
        # route the scalar push through the pump too (one-element batch:
        # same arena counters, same quantize math)
        self.push_audio_batch([sid], [audio])

    def flush_ingest(self) -> None:
        """Wait until every submitted push has landed in the arena."""
        if self._pump is not None:
            self._pump.flush()

    # -- pipeline core -------------------------------------------------------

    @property
    def in_flight(self) -> int:
        """Dispatched hops whose fold has not retired yet."""
        return len(self._inflight)

    def _retire_inflight(self, h: _Hop, still_in_flight: bool
                         ) -> HopBatch:
        """Retire function the ``InFlightQueue`` drives: fence on one hop,
        fetch its results and run its deferred fold.  The fence blocks
        OUTSIDE the ingest lock so pushes keep landing while the device
        finishes; the fold itself (detector, metrics, events, emit cache)
        runs under the lock, in FIFO dispatch order."""
        # emit off: no per-hop output future survives donation, so fence
        # the resident state (syncs every queued hop <= now)
        fence_on = h.logits if h.logits is not None else (
            self._tails, self._pendings, self._gap)
        logits_h, post_h = self._fence_fetch(h, fence_on)
        with self._lock:
            return self._fold_hop(h, logits_h, post_h,
                                  fold_hidden=still_in_flight)

    def _retire_one(self) -> HopBatch:
        """Fence on the oldest in-flight hop and run its deferred fold."""
        return self._inflight.retire_oldest()

    def _epoch_barrier(self) -> None:
        """Retire every in-flight hop.  Callers then hold the invariant
        the synchronous scheduler has between steps: all folds applied,
        no future references any slot row — so resize / rebalance /
        priming / teardown remaps run exactly as they do synchronously."""
        self._inflight.barrier()

    def _pre_structural(self) -> None:
        """SlotPool hook: a structural slot mutation is about to run —
        drain the pipeline so a remap never invalidates in-flight row
        indices (the epoch-barrier protocol, declared once)."""
        with self._lock:
            self._epoch_barrier()

    def _advance(self) -> tuple[bool, HopBatch | None]:
        """One pipeline turn: dispatch a hop if any stream is ready, and
        retire the oldest in-flight hop once the pipeline is past its
        depth (or when starved).  Returns ``(dispatched, retired)``."""
        with self._lock:
            if self._skew_dirty or self._unprimed:
                # epoch barrier: drain the pipeline, then rebalance /
                # shrink / prime at the same logical point the sync
                # scheduler would
                self._epoch_barrier()
                self._hop_barriers()
            packed = self._pack_ready()
            if packed is not None:
                h, ready_mask, audio = packed
                was_busy = bool(self._inflight)
                self._dispatch_hop(h, ready_mask, audio)
                if was_busy:
                    # this hop's pack+dispatch ran while an earlier hop
                    # was executing: that host wall is hidden
                    h.hidden_s = h.t_dispatch - h.t0
                self._inflight.push(h)
                self._dispatched_total += 1
            else:
                self._maybe_prewarm()  # starved turn: warm next capacity
        dispatched = packed is not None
        # depth policy (retire at most one per turn): the queue retires
        # once the pipeline is past its depth, or when starved and hops
        # remain to drain
        retired_list = self._inflight.settle(dispatched, max_retire=1)
        return dispatched, (retired_list[0] if retired_list else None)

    # -- public stepping -----------------------------------------------------

    def step_batch(self) -> HopBatch | None:
        """One pipeline turn.  Unlike the sync scheduler, ``None`` can
        mean "hop dispatched, results not retired yet" — callers that
        need everything settled use ``drain()`` (or ``peek``/
        ``close_stream``, which barrier internally)."""
        return self._advance()[1]

    def run_until_starved(self):
        """Step until no stream has a full hop buffered AND every
        dispatched hop has retired; returns the collated tuples."""
        self.flush_ingest()
        out = []
        while True:
            dispatched, retired = self._advance()
            if retired is not None:
                out.extend(self._collate(retired))
            if not dispatched and not self._inflight:
                return out

    def drain(self) -> int:
        """Flush the pump, run the pipeline until starved, and retire
        every in-flight hop; returns hops *dispatched* by this call
        (== hops the sync scheduler would have executed)."""
        self.flush_ingest()
        before = self._dispatched_total
        while True:
            dispatched, _ = self._advance()
            if not dispatched and not self._inflight:
                return self._dispatched_total - before

    # -- epoch-barrier lifecycle overrides -----------------------------------
    #
    # resize and rebalance need NO overrides here: the SlotPool calls
    # ``_pre_structural`` (declared in __init__) before every structural
    # mutation, whichever path reaches it.

    def add_stream(self, *args, **kwargs) -> int:
        with self._lock:  # placement/arena bookkeeping vs pump pushes
            return super().add_stream(*args, **kwargs)

    def register_model(self, *args, **kwargs) -> int:
        with self._lock:
            # pool swap = epoch barrier: an in-flight hop still references
            # the weight row an admission may overwrite (LRU eviction)
            self._epoch_barrier()
            return super().register_model(*args, **kwargs)

    def peek(self, sid: int) -> np.ndarray:
        self.flush_ingest()  # the contract covers "audio pushed so far"
        with self._lock:
            self._epoch_barrier()
            return super().peek(sid)

    def close_stream(self, sid: int) -> StreamResult:
        self.flush_ingest()  # pending pushes for this sid must land
        with self._lock:
            self._epoch_barrier()  # fold in-flight hops, then ghost-flush
            return super().close_stream(sid)

    def detections(self, sid: int) -> list[Detection]:
        """Events recorded so far for ``sid`` (settles the pipeline)."""
        with self._lock:
            self._epoch_barrier()
            return list(self._require(sid).events)

    def shutdown(self) -> None:
        """Settle everything and stop the pump thread."""
        if self._pump is not None:
            self._pump.close()
            self._pump = None
        with self._lock:
            self._epoch_barrier()
