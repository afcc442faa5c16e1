"""Continuous-batching multi-stream scheduler for always-on KWS.

Thousands of concurrent audio streams each produce frames continuously;
the model weights are shared across all of them (one CIM macro, many
users).  This scheduler packs the active streams onto an *elastic* batch
axis and advances them with ONE jitted step per hop:

  * streams join/leave at any time — a free slot is primed from the
    stream's first ``prime_samples`` (generic numpy path in state.py) and
    from then on rides the static-shape batched step;
  * streams whose inbox holds less than a hop are masked out of the step
    (their state passes through untouched), so stragglers never force a
    re-trace — continuous batching, not synchronized batching;
  * **the ingest plane is struct-of-arrays** (``state.RingArena``): every
    stream's inbox is one row of a shared uint8 sample arena, so the
    steady-state hop packs all ready inboxes with ONE vectorized gather
    (``pack_hops``), readiness is one compare, audio lands via one
    scatter (``push_audio_batch``), and detection advances through the
    slot-vectorized ``BatchedDetector`` — zero per-slot python anywhere
    on the hop hot path (``step_batch``; the tuple-per-stream ``step``
    API survives as a thin collation wrapper);
  * the slot pool grows and shrinks at power-of-two sizes: a resize
    pads/slices the batched ring state along the batch axis and lets jit
    re-trace at the new static shape, so bursty arrivals are absorbed
    without provisioning for the peak and results stay bit-exact across
    the resize boundary;
  * the batched step runs one of three hop backends: ``jnp`` (plain XLA
    einsums, the default and the reference), ``pallas`` (the per-stage
    popcount kernels of kernels/bnn_conv1d) or ``megakernel`` (the whole
    hop in one kernel, kernels/hop_megakernel).  Off the TPU the Pallas
    kernels run interpreted; on a TPU they compile to Mosaic kernels.

**Mesh sharding (one pool, whole mesh).**  Pass ``mesh`` (see
``launch.mesh.make_stream_mesh``) and the batch axis of every piece of
per-stream state — conv tails, pool pendings, GAP counters — shards over
the mesh's ``"data"`` axis while the (tiny) model weights replicate: the
software analogue of the paper's one-large-macro argument (§II-A), one
logical slot pool spanning every device instead of one pool per device.
``SlotPlacement`` (state.py) keeps each stream's row inside one shard's
contiguous block and performs the elastic pow-2 resize *per shard*, so
grow/shrink never reshuffles rows across devices and a sharded run is
bit-exact with the single-device scheduler (tests/test_stream_sharded.py).
With no mesh (or a 1-device mesh) every code path collapses to the
single-device behavior.

**Cross-shard rebalance (migrate-on-idle).**  Resizes never move rows
across devices, so churn that leaves one shard crowded would pin the
whole pool's shrink floor at that shard's tenant count.  At hop
boundaries, when occupancy skew exceeds ``rebalance_threshold``, the
scheduler executes ``SlotPlacement.rebalance()``'s cross-shard (dst,
src) moves: one device-side row gather over the sharded
tails/pendings/GAP state (``ops.remap_slot_rows`` — standalone because
``pallas_call`` is GSPMD-opaque) plus the usual host-side
``remap_rows``/``RingArena.apply_remap`` remap, after which
``_maybe_shrink``'s floor is ``ceil(active / n_shards)`` per shard
instead of the fullest shard's count — the paper's flexible ping-pong
re-layout argument (§II-E) applied to the slot pool.  Migrations are
bit-invisible to the streams riding through them (rows travel
unchanged); ``rebalance_threshold=None`` restores the PR 3 no-migration
behavior.

Per emitted hop the step also runs the *in-jit finalization tail*: a ghost
end-of-stream flush with statically known emission counts (the plan's
``flush_*`` geometry) followed by the fused classifier tail
(kernels/ops.classifier_tail), so every active slot's finalized logits —
the exact logits the offline executor would produce if the utterance ended
now — and softmax posteriors leave the device with the hop itself.  The
host-side ``StreamState.peek_logits`` clone-and-flush survives only as the
exact fallback for mid-hop peeks over leftover sub-hop samples.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.cnn_spec import CNN1DSpec
from repro.kernels import ops
from repro.launch.mesh import dp_axes, dp_size
from repro.obs import Observability
from repro.obs.trace import close as close_annotation
from repro.stream.detector import (
    BatchedDetector,
    Detection,
    DetectorConfig,
    _softmax,
)
from repro.stream.frontend import AudioFrontend, FrontendConfig
from repro.stream.metrics import StreamMetrics
from repro.runtime.pool import SlotPool
# the pow-2 helper moved into the generic runtime with the slot pool; the
# historical name is re-exported because benches/tests import it from here
from repro.runtime.pool import next_pow2 as _next_pow2  # noqa: F401
from repro.stream.state import (
    RingArena,
    StreamPlan,
    StreamState,
    plan_stream,
    prime_batch,
    quantize_pcm,
    remap_rows,
)
from repro.utils.logging import get_logger

log = get_logger("stream")

#: pool row 0 always holds the scheduler's construction weights
DEFAULT_MODEL = "default"

# ---------------------------------------------------------------------------
# Memoized parameter prep (weights dict -> device-ready arrays)
# ---------------------------------------------------------------------------
#
# Building a _BatchedModel converts every layer's ternary weights and SA
# thresholds into device arrays; re-constructing a scheduler over the
# same exported model (K-tenant admission, bench baselines, test
# fixtures) used to redo that prep — and the wp/wn plane packing it
# feeds — from scratch every time.  The cache keys on the *identity* of
# the weights/thresholds dicts plus the plan geometry, holds strong
# references to the keyed dicts (so an id can never be recycled under
# us; an identity check guards the lookup anyway), and is bounded LRU.

_PARAM_CACHE: collections.OrderedDict = collections.OrderedDict()
_PARAM_CACHE_MAX = 64
_param_cache_hits = 0
_param_cache_misses = 0


def prepared_model_params(plan: StreamPlan, weights, thresholds) -> dict:
    """Device-ready per-stage params for one model variant, memoized by
    ``(id(weights), id(thresholds), plan geometry)``.

    Returns ``{"w", "thr", "flip", "fc_w", "fc_thr", "fc_flip"}`` —
    exactly the arrays ``_BatchedModel`` loads — so pool admission,
    scheduler reconstruction, and grow/shrink cycles over an unchanged
    variant never re-run the conversion (or the wp/wn packing derived
    from it downstream).
    """
    global _param_cache_hits, _param_cache_misses
    key = (id(weights), id(thresholds), plan.convs, plan.fcs)
    hit = _PARAM_CACHE.get(key)
    if (hit is not None and hit["weights"] is weights
            and hit["thresholds"] is thresholds):
        _param_cache_hits += 1
        _PARAM_CACHE.move_to_end(key)
        return hit
    _param_cache_misses += 1
    stages = plan.convs
    prep = {
        # strong refs pin the keyed ids for the cache's lifetime
        "weights": weights,
        "thresholds": thresholds,
        "w": [
            jnp.asarray(weights[st.layer_idx].reshape(st.k, st.cin, st.cout),
                        jnp.int32) for st in stages
        ],
        "thr": [jnp.asarray(thresholds[st.layer_idx][0], jnp.float32)
                for st in stages],
        "flip": [jnp.asarray(thresholds[st.layer_idx][1], bool)
                 for st in stages],
        "fc_w": tuple(jnp.asarray(weights[st.layer_idx], jnp.int32)
                      for st in plan.fcs),
        "fc_thr": tuple(jnp.asarray(thresholds[st.layer_idx][0],
                                    jnp.float32) for st in plan.fcs),
        "fc_flip": tuple(jnp.asarray(thresholds[st.layer_idx][1],
                                     jnp.int32) for st in plan.fcs),
    }
    _PARAM_CACHE[key] = prep
    while len(_PARAM_CACHE) > _PARAM_CACHE_MAX:
        _PARAM_CACHE.popitem(last=False)
    return prep


def param_cache_stats() -> dict[str, int]:
    """Hit/miss counters for the memoized parameter prep (tests)."""
    return {
        "hits": _param_cache_hits,
        "misses": _param_cache_misses,
        "size": len(_PARAM_CACHE),
    }


class WeightPool:
    """K complete model variants sharing one plan geometry, one device.

    The pool owns the *host* side of multi-tenancy: which model ids are
    resident, which pool row (0..max_models-1) each occupies, how many
    live streams pin each variant, and LRU admission/eviction.  Row
    indices are stable for a variant's whole residency and the row count
    is FIXED at ``max_models`` from construction, so the device-side
    ``(K, ...)`` weight stacks never change shape — admission is a row
    write, never a retrace.

    Row 0 conventionally holds the scheduler's default model
    (``DEFAULT_MODEL``), admitted at construction and never evicted
    while default-bound streams exist (refcounting covers it like any
    other variant).
    """

    def __init__(self, max_models: int) -> None:
        assert max_models >= 1, max_models
        self.max_models = max_models
        self._index: dict[str, int] = {}
        self._lru: collections.OrderedDict = collections.OrderedDict()
        self._weights: dict[str, dict] = {}
        self._thresholds: dict[str, dict] = {}
        self._refs: dict[str, int] = {}
        self._free = list(range(max_models - 1, -1, -1))  # pop() -> row 0
        self.admits = 0
        self.evictions = 0

    def __contains__(self, model_id: str) -> bool:
        return model_id in self._index

    def __len__(self) -> int:
        return len(self._index)

    def models(self) -> list[tuple[str, int]]:
        """Resident variants as ``(model_id, pool row)``, row order."""
        return sorted(self._index.items(), key=lambda kv: kv[1])

    def index_of(self, model_id: str) -> int:
        return self._index[model_id]

    def refcount(self, model_id: str) -> int:
        return self._refs[model_id]

    def params_for(self, model_id: str):
        """The pool-held (weights, thresholds) host copies."""
        return self._weights[model_id], self._thresholds[model_id]

    def admit(self, model_id: str, weights, thresholds
              ) -> tuple[int, str | None]:
        """Bind a variant to a pool row; returns ``(row, evicted_id)``.

        A resident id is an LRU touch (its stored params stay — the
        caller re-registers, the pool does not re-copy).  When full, the
        least-recently-used variant with NO live streams is evicted;
        if every row is pinned, MemoryError.
        """
        if model_id in self._index:
            self._lru.move_to_end(model_id)
            return self._index[model_id], None
        evicted = None
        if self._free:
            row = self._free.pop()
        else:
            victim = next(
                (m for m in self._lru if self._refs[m] == 0), None
            )
            if victim is None:
                raise MemoryError(
                    f"weight pool full: all {self.max_models} variants "
                    "have live streams; close streams or raise max_models"
                )
            row = self._evict(victim)
            evicted = victim
            self.evictions += 1
        # store the caller's mappings as-is: the memoized prep
        # (prepared_model_params) keys on their identity, so re-admitting
        # the same arrays — here or in another scheduler — never re-packs
        self._weights[model_id] = weights
        self._thresholds[model_id] = thresholds
        self._index[model_id] = row
        self._refs[model_id] = 0
        self._lru[model_id] = None
        self.admits += 1
        return row, evicted

    def _evict(self, model_id: str) -> int:
        row = self._index.pop(model_id)
        del self._weights[model_id]
        del self._thresholds[model_id]
        del self._refs[model_id]
        del self._lru[model_id]
        return row

    def acquire(self, model_id: str) -> int:
        """Pin a variant for one joining stream; returns its row."""
        self._refs[model_id] += 1
        self._lru.move_to_end(model_id)
        return self._index[model_id]

    def release(self, model_id: str) -> None:
        self._refs[model_id] -= 1
        assert self._refs[model_id] >= 0, model_id


@dataclasses.dataclass
class StreamResult:
    """Returned by close_stream: the stream's final, flushed inference."""

    stream_id: int
    logits: np.ndarray        # executor-exact raw logits
    frames: int               # final-conv frames accumulated
    samples: int
    events: list[Detection]


@dataclasses.dataclass
class HopBatch:
    """One batched hop's results in columnar (struct-of-arrays) form —
    what ``step_batch`` returns without ever materializing per-stream
    python objects.  ``detections`` is sparse: one entry per fired event,
    usually empty."""

    sids: np.ndarray                 # (R,) stream ids advanced this hop
    frames: np.ndarray               # (R,) final-conv frame counts after it
    logits: np.ndarray | None        # (R, n_classes) finalized logits
    posteriors: np.ndarray | None    # (R, n_classes) on-device softmax
    detections: list[Detection]


@dataclasses.dataclass
class _Stream:
    sid: int
    slot: int
    frontend: AudioFrontend   # facade over the shared arena row
    events: list[Detection]
    primed: bool = False
    stamp: int = 0  # emit-step from which cached hop logits cover this slot
    model: str = DEFAULT_MODEL  # tenant variant this stream computes with


@dataclasses.dataclass(eq=False)
class _Hop:
    """One hop from pack to fold: the rows it advances, its device result
    futures, the ring's phase stamps and, while a profile is being
    captured, its open profile annotations.  The sync path carries it
    through one ``step_batch``; the async plane queues it in flight
    between dispatch and retirement."""

    seq: int                     # joins the spans of one hop
    ready_slots: np.ndarray
    shard_counts: np.ndarray
    t0: float
    t_pack: float
    hop_ann: object              # profile annotation of the hop, or None
    ann: object                  # the open phase's annotation, or None
    t_dispatch: float = 0.0
    t_fence: float = 0.0
    t_device: float = 0.0
    logits: object = None        # device futures (None with emit off)
    post: object = None
    fetch_bytes: int = 0
    hidden_s: float = 0.0        # host wall of this hop under device work


def _mesh_data_axes(mesh):
    """The mesh's data-parallel axes as a PartitionSpec entry (a tuple of
    axis names is a valid single-dim entry)."""
    return dp_axes(mesh)


class _BatchedModel:
    """Device-resident model + jitted batched hop/finalize for one plan.

    Batch-size polymorphic: every entry point derives B from its operands,
    so the elastic slot pool only pays one re-trace per power-of-two
    capacity it ever visits (jit's shape-keyed cache does the rest).

    With ``mesh`` the weights are replicated across it and the batch axis
    of every operand/result is pinned to the data axes, so GSPMD keeps
    each slot's row resident on its shard through the whole hop (the
    Pallas backend routes through the shard_map entry points in
    kernels/ops.py, which are opaque-kernel-safe).
    """

    def __init__(self, plan: StreamPlan, weights, thresholds,
                 backend: str, interpret: bool | None, mesh=None,
                 donate: bool = False, pool_size: int | None = None,
                 tenant_block: int | None = None,
                 params: dict | None = None) -> None:
        self.plan = plan
        self.backend = backend
        self.interpret = interpret
        self.mesh = mesh
        self.pool_size = pool_size
        self._tenant_block = tenant_block
        prep = params if params is not None else prepared_model_params(
            plan, weights, thresholds
        )
        self._w = list(prep["w"])
        self._thr = list(prep["thr"])
        self._flip = list(prep["flip"])
        self._fc_w = tuple(prep["fc_w"])
        self._fc_thr = tuple(prep["fc_thr"])
        self._fc_flip = tuple(prep["fc_flip"])
        self._fc_raw = tuple(st.out_raw for st in plan.fcs)
        if pool_size is not None:
            # tenant pool: axis 0 stacks K complete variants.  Unfilled
            # rows hold the default model, so the stack SHAPES are fixed
            # at max_models from construction — admitting a variant is a
            # row write (set_model_row), never a retrace.
            stack = lambda t: jnp.stack([t] * pool_size)  # noqa: E731
            self._w = [stack(w) for w in self._w]
            self._thr = [stack(t) for t in self._thr]
            self._flip = [stack(f) for f in self._flip]
            self._fc_w = tuple(stack(w) for w in self._fc_w)
            self._fc_thr = tuple(stack(t) for t in self._fc_thr)
            self._fc_flip = tuple(stack(f) for f in self._fc_flip)
        # offset fold (per tenant row when pooled)
        self._wsum = [
            jnp.sum(w, axis=(1, 2) if pool_size is not None else (0, 1))
            for w in self._w
        ]
        if mesh is not None:
            # one macro, many shards: weights live replicated on every
            # device (the whole (K, ...) pool replicates exactly like
            # the single weight set); only per-stream state is sharded
            put = self._rep_put
            self._w = [put(w) for w in self._w]
            self._thr = [put(t) for t in self._thr]
            self._flip = [put(f) for f in self._flip]
            self._wsum = [put(w) for w in self._wsum]
            self._fc_w = tuple(put(w) for w in self._fc_w)
            self._fc_thr = tuple(put(t) for t in self._fc_thr)
            self._fc_flip = tuple(put(f) for f in self._fc_flip)
            self._baxes = _mesh_data_axes(mesh)
        # with donate=True the slot-state operands (tails, pendings, gap)
        # are donated to each hop: XLA aliases the output state onto the
        # input buffers, so a restep never copies the resident state.  The
        # caller must treat the passed-in state arrays as consumed (the
        # scheduler reassigns them from the step's results immediately).
        # the device profile names the program after the function jitted
        # (``jit_kws_hop_step`` in its XLA Modules line)
        step = functools.partial(self._step)
        step.__name__ = "kws_hop_step"
        self.step = jax.jit(
            step, static_argnames=("emit",),
            donate_argnums=(2, 3, 4) if donate else (),
        )
        self.finalize = jax.jit(self._finalize)

    def _rep_put(self, t: jax.Array) -> jax.Array:
        """Replicate a weight array across the mesh (identity without)."""
        if self.mesh is None:
            return t
        return jax.device_put(t, NamedSharding(self.mesh, P()))

    def _pin(self, x: jax.Array) -> jax.Array:
        """Constrain the leading (batch) axis to the mesh's data sharding."""
        if self.mesh is None:
            return x
        spec = P(self._baxes, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec)
        )

    # -- tenant pool device side ---------------------------------------------

    def set_model_row(self, idx: int, weights, thresholds) -> None:
        """Write one tenant variant into pool row ``idx`` (admission).

        Row updates keep every stacked shape fixed, so the jitted step's
        shape-keyed cache survives; under a mesh the updated stacks
        re-replicate like the originals.  The variant must share the
        plan geometry (same spec/hop) — shapes are asserted by the
        ``.at[idx].set`` writes themselves.
        """
        assert self.pool_size is not None, "not a pooled model"
        assert 0 <= idx < self.pool_size, (idx, self.pool_size)
        prep = prepared_model_params(self.plan, weights, thresholds)
        put = self._rep_put
        for i in range(len(self.plan.convs)):
            self._w[i] = put(self._w[i].at[idx].set(prep["w"][i]))
            self._thr[i] = put(self._thr[i].at[idx].set(prep["thr"][i]))
            self._flip[i] = put(self._flip[i].at[idx].set(prep["flip"][i]))
            self._wsum[i] = put(jnp.sum(self._w[i], axis=(1, 2)))
        self._fc_w = tuple(
            put(w.at[idx].set(v)) for w, v in zip(self._fc_w, prep["fc_w"])
        )
        self._fc_thr = tuple(
            put(t.at[idx].set(v))
            for t, v in zip(self._fc_thr, prep["fc_thr"])
        )
        self._fc_flip = tuple(
            put(f.at[idx].set(v))
            for f, v in zip(self._fc_flip, prep["fc_flip"])
        )

    def _bb(self, b: int) -> int | None:
        """Tenant-aligned batch block for the pooled kernels.

        Placement keeps each ``min(tenant_block, shard_capacity)`` slot
        block single-model, so forcing the kernel's batch block to the
        same size keeps every grid block's weight gather one row.  None
        (backend default) when un-pooled.
        """
        if self.pool_size is None:
            return None
        S = 1 if self.mesh is None else dp_size(self.mesh)
        return min(self._tenant_block, max(1, b // S))

    def _block_gather(self, stack: jax.Array, model_idx: jax.Array
                      ) -> tuple[jax.Array, int]:
        """One weight row per tenant block instead of per slot.

        Placement keeps every block single-model (``_sync_model_rows``),
        so the naive per-slot gather — B full weight copies driving a
        per-example batched matmul — collapses to one gather per block
        and a per-block matmul: tb-fold fewer, tb-fold larger GEMMs.
        Exact: the contractions are int32, so regrouping rows into
        blocks cannot change a single accumulation.
        """
        tb = self._bb(model_idx.shape[0])
        return stack[model_idx.reshape(-1, tb)[:, 0]], tb

    # -- shared conv math ----------------------------------------------------

    def _conv_raw(self, i: int, window: jax.Array, n_conv: int,
                  model_idx: jax.Array | None = None) -> jax.Array:
        """(B, len, Cin) window -> (B, n_conv, Cout) raw popcount diff.
        With ``model_idx`` the weights are the pooled (K, ...) stacks —
        one gather per tenant block inside the kernel, one per-row
        gather on the jnp path."""
        st = self.plan.convs[i]
        w = self._w[i]
        if st.in_bits > 1:
            # bit-serial first layer; offset folds out after accumulation.
            # ONE launch accumulates every bit plane in-kernel (PR 8) —
            # the fallback path no longer pays per-plane dispatch.
            if self.backend == "pallas":
                return ops.bitserial_conv1d_batched_sharded(
                    window.astype(jnp.uint32), w, model_idx,
                    mesh=self.mesh, bits=st.in_bits, offset=st.in_offset,
                    stride=st.stride, pad=0,
                    bb=self._bb(window.shape[0]), interpret=self.interpret,
                )
            xi = window.astype(jnp.int32) - st.in_offset
            taps = [
                xi[:, t : t + (n_conv - 1) * st.stride + 1 : st.stride]
                for t in range(st.k)
            ]
            xs = jnp.stack(taps, axis=1)  # (B, K, n_conv, Cin)
            if model_idx is not None:
                wg, tb = self._block_gather(w, model_idx)
                xg = xs.reshape(-1, tb, *xs.shape[1:])
                return jnp.einsum("gtknc,gkco->gtno", xg, wg).reshape(
                    xs.shape[0], n_conv, -1)
            return jnp.einsum("bknc,kco->bno", xs, w)
        if self.backend == "pallas":
            return ops.bnn_conv1d_batched_sharded(
                window.astype(jnp.uint32), w, model_idx,
                mesh=self.mesh, stride=st.stride, pad=0,
                bb=self._bb(window.shape[0]), interpret=self.interpret,
            )
        taps = [
            window[:, t : t + (n_conv - 1) * st.stride + 1 : st.stride]
            for t in range(st.k)
        ]
        xs = jnp.stack(taps, axis=1).astype(jnp.int32)
        if model_idx is not None:
            wg, tb = self._block_gather(w, model_idx)
            xg = xs.reshape(-1, tb, *xs.shape[1:])
            return jnp.einsum("gtknc,gkco->gtno", xg, wg).reshape(
                xs.shape[0], n_conv, -1)
        return jnp.einsum("bknc,kco->bno", xs, w)

    def _sa(self, i: int, raw: jax.Array,
            model_idx: jax.Array | None = None) -> jax.Array:
        """SA binarization, executor-exact: integer thresholds make the
        float32 compare knife-edge free."""
        if model_idx is not None:
            thr = self._thr[i][model_idx][:, None, :]
            flip = self._flip[i][model_idx][:, None, :]
        else:
            thr = self._thr[i][None, None, :]
            flip = self._flip[i][None, None, :]
        ge = raw.astype(jnp.float32) >= thr
        return jnp.where(flip, ~ge, ge).astype(jnp.int32)

    # -- the hop -------------------------------------------------------------

    def _step(self, audio, mask, tails, pendings, gap, model_idx=None,
              *, emit: bool):
        """One batched hop; with ``emit`` the in-jit finalization tail also
        returns per-slot finalized logits + posteriors.  Shapes static.
        ``model_idx`` ((B,) int32, pooled models only) selects each
        slot's tenant variant — constant per tenant block by placement,
        so the launch count stays K-independent."""
        plan = self.plan
        stages = plan.convs
        if self.backend == "megakernel":
            # the whole cascade — bit-serial layer 0, SA, pool phases,
            # tail/pending carry, GAP, mask merge, and (on emit) the ghost
            # flush + classifier — is ONE fused launch per shard; only the
            # hop input and the updated slot state touch HBM
            audio = audio.reshape(
                audio.shape[0], plan.hop_samples, stages[0].cin
            )
            out = ops.hop_megakernel_sharded(
                audio, mask.astype(jnp.int32), tuple(tails), tuple(pendings),
                gap, tuple(self._w), tuple(self._thr), tuple(self._flip),
                self._fc_w, self._fc_thr, self._fc_flip, model_idx,
                mesh=self.mesh, stages=stages, emit=emit,
                fc_raw=self._fc_raw, bb=self._bb(gap.shape[0]),
                interpret=self.interpret,
            )
            new_tails = tuple(self._pin(t) for t in out[0])
            new_pendings = tuple(self._pin(p) for p in out[1])
            gap2 = self._pin(out[2])
            state = new_tails, new_pendings, gap2
            if not emit:
                return state
            logits = self._pin(out[3])
            post = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            return (*state, logits, post)
        cur = audio.reshape(audio.shape[0], plan.hop_samples, stages[0].cin)
        new_tails, new_pendings = [], []
        for i, st in enumerate(stages):
            with jax.named_scope(f"conv{i}"):
                window = jnp.concatenate([tails[i], cur], axis=1)
                raw = self._conv_raw(i, window, st.n_conv, model_idx)
                new_tails.append(window[:, st.n_conv * st.stride :])
                y = self._sa(i, raw, model_idx)
                if st.pool > 1:
                    frames = (
                        jnp.concatenate([pendings[i], y], axis=1)
                        if st.phase else y
                    )
                    used = st.n_out * st.pool
                    pooled = frames[:, :used].reshape(
                        frames.shape[0], st.n_out, st.pool, st.cout
                    ).max(axis=2)
                    new_pendings.append(frames[:, used:])
                    cur = pooled
                else:
                    new_pendings.append(pendings[i])
                    cur = y
        # saturate at the 8-bit PWB counter ceiling inside the step: the
        # accumulation is monotone non-negative, so incremental clamping
        # equals clamping the int64 total (pwb.gap_counts semantics) and
        # int32 can never wrap on always-on streams
        with jax.named_scope("gap"):
            gap2 = jnp.minimum(gap + cur.sum(axis=1, dtype=jnp.int32), 255)

        m3 = mask[:, None, None]
        new_tails = [
            self._pin(jnp.where(m3, nt, t))
            for nt, t in zip(new_tails, tails)
        ]
        new_pendings = [
            self._pin(jnp.where(m3, np_, p)) if p.shape[1] else p
            for np_, p in zip(new_pendings, pendings)
        ]
        gap2 = self._pin(jnp.where(mask[:, None], gap2, gap))
        state = tuple(new_tails), tuple(new_pendings), gap2
        if not emit:
            return state
        # finalization tail on the merged state: masked-out rows hold their
        # previous (still steady) state, so every primed slot's logits are
        # valid — ready rows are simply the ones the scheduler reads
        logits, post = self._finalize(*state, model_idx)
        return (*state, logits, post)

    # -- in-jit finalization tail --------------------------------------------

    def _finalize(self, tails, pendings, gap, model_idx=None):
        """Logits/posteriors as if every stream ended at this hop boundary.

        A *ghost* end-of-stream flush — statically sized by the plan's
        ``flush_*`` geometry — cascades each layer's right pad through the
        conv stack without touching the live state, then the fused
        classifier tail drains the saturated GAP counts through the fc
        stack.  Bit-exact with ``StreamState.peek_logits()`` on an empty
        inbox (tests/test_stream.py).
        """
        if self.backend == "megakernel":
            logits = self._pin(ops.finalize_megakernel_sharded(
                tuple(tails), tuple(pendings), gap,
                tuple(self._w), tuple(self._thr), tuple(self._flip),
                self._fc_w, self._fc_thr, self._fc_flip, model_idx,
                mesh=self.mesh, stages=self.plan.convs,
                fc_raw=self._fc_raw, bb=self._bb(gap.shape[0]),
                interpret=self.interpret,
            ))
            post = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            return logits, post
        stages = self.plan.convs
        B = gap.shape[0]
        cur = None  # frames flowing down from the layer above's flush
        with jax.named_scope("ghost_flush"):
            for i, st in enumerate(stages):
                pieces = [tails[i]]
                if cur is not None and st.flush_in:
                    pieces.append(cur)
                if st.pad:
                    pad_val = st.in_offset if st.in_bits > 1 else 0
                    pieces.append(
                        self._pin(
                            jnp.full((B, st.pad, st.cin), pad_val, jnp.int32)
                        )
                    )
                if st.flush_conv > 0:
                    window = jnp.concatenate(pieces, axis=1)
                    y = self._sa(i, self._conv_raw(i, window, st.flush_conv,
                                                   model_idx), model_idx)
                else:
                    y = jnp.zeros((B, 0, st.cout), jnp.int32)
                frames = jnp.concatenate([pendings[i], y], axis=1)
                used = st.flush_out * st.pool  # drop-remainder (ref_maxpool1d)
                cur = frames[:, :used].reshape(
                    B, st.flush_out, st.pool, st.cout
                ).max(axis=2)
            gap_f = jnp.minimum(gap + cur.sum(axis=1, dtype=jnp.int32), 255)
        with jax.named_scope("classifier"):
            logits = self._pin(self._classifier(gap_f, model_idx))
            post = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        return logits, post

    def _classifier(self, gap_f: jax.Array,
                    model_idx: jax.Array | None = None) -> jax.Array:
        """Saturated GAP counts (B, C) -> raw logits (B, n_classes)."""
        if self.backend == "pallas":
            return ops.classifier_tail_sharded(
                gap_f, self._fc_w, self._fc_thr, self._fc_flip, model_idx,
                mesh=self.mesh, out_raw=self._fc_raw,
                bb=self._bb(gap_f.shape[0]), interpret=self.interpret,
            )
        h = gap_f
        for j, st in enumerate(self.plan.fcs):
            if model_idx is not None:
                wg, tb = self._block_gather(self._fc_w[j], model_idx)
                hg = h.reshape(-1, tb, h.shape[1])
                raw = jnp.einsum("gtc,gco->gto", hg, wg).reshape(
                    h.shape[0], -1)
                thr = self._fc_thr[j][model_idx]
                flip = self._fc_flip[j][model_idx]
            else:
                raw = h @ self._fc_w[j]
                thr = self._fc_thr[j][None, :]
                flip = self._fc_flip[j][None, :]
            if st.out_raw:
                h = raw
            else:
                ge = raw.astype(jnp.float32) >= thr
                h = jnp.where(flip != 0, ~ge, ge).astype(jnp.int32)
        return h

    def dispatches_per_hop(self, emit: bool) -> int:
        """Static per-shard ``pallas_call`` count for one hop.

        Derived from the plan + backend alone; tests/test_megakernel.py
        asserts it equals the count actually traced through
        ``kernels.dispatch``, so this figure (surfaced per hop by
        ``StreamMetrics`` and BENCH_stream.json) cannot drift from the
        kernels launched.  ``jnp`` lowers to plain XLA: 0 by definition.
        """
        if self.backend == "jnp":
            return 0
        if self.backend == "megakernel":
            return 1  # emit's flush + classifier ride the same launch
        # per-stage pallas: one launch per conv stage (the bit-serial
        # first layer is a single plane-accumulating launch since PR 8),
        # plus — on emit — the ghost flush's conv launches and the fused
        # classifier tail
        n = len(self.plan.convs)
        if emit:
            n += sum(1 for st in self.plan.convs if st.flush_conv > 0) + 1
        return n


class StreamScheduler:
    """Continuous batching over an elastic pool of stream slots.

    ``capacity`` is the *ceiling*: the pool starts at ``initial_capacity``
    (default ``min_capacity``) and doubles on demand up to the ceiling;
    ``close_stream`` halves it once occupancy falls to a quarter (never
    below ``min_capacity`` — set ``min_capacity == capacity`` to pin a
    fixed-size pool).  Each resize is a pure pad/slice of the batched ring
    state, so a stream fed across a resize boundary produces bit-identical
    logits to one fed at a fixed capacity.

    With ``mesh`` the pool spans the mesh: every capacity is ``n_shards *
    per_shard`` rows, a joining stream lands on the least-loaded shard,
    and the elastic resize scales the *per-shard* capacity so rows never
    cross devices (``SlotPlacement``).  ``capacity`` (and, if given,
    ``min_capacity``/``initial_capacity``) must be multiples of the mesh's
    data-axis size.  When leave churn skews occupancy by more than
    ``rebalance_threshold`` tenants between the fullest and emptiest
    shard, the next hop boundary migrates tenants across shards to level
    the pool (and re-checks the shrink); ``None`` disables migration.
    """

    def __init__(
        self,
        spec: CNN1DSpec,
        weights: dict[int, np.ndarray],
        thresholds: dict[int, tuple[np.ndarray, np.ndarray]],
        capacity: int = 8,
        hop_frames: int = 1,
        backend: str = "jnp",
        interpret: bool | None = None,
        detector_cfg: DetectorConfig | None = None,
        emit_logits: bool = True,
        sample_rate: int = 16000,
        initial_capacity: int | None = None,
        min_capacity: int | None = None,
        mesh=None,
        inbox_samples: int | None = None,
        rebalance_threshold: int | None = 1,
        obs: Observability | None = None,
        clock=time.perf_counter,
        donate_buffers: bool = False,
        max_models: int = 1,
        tenant_block: int = 8,
        prewarm: bool = False,
    ) -> None:
        assert backend in ("jnp", "pallas", "megakernel"), backend
        # every hop stamp (metrics, trace spans) reads this clock, so the
        # concurrency suite can drive sync and async schedulers with one
        # controllable fake clock and compare their traces structurally
        self._clock = clock
        self.plan = plan_stream(spec, hop_frames=hop_frames)
        self.weights = {k: np.asarray(v) for k, v in weights.items()}
        self.thresholds = thresholds
        self.mesh = mesh
        if mesh is not None:
            self.n_shards = dp_size(mesh)
            self._baxes = _mesh_data_axes(mesh)
        else:
            self.n_shards = 1
        S = self.n_shards
        self.backend = backend
        self.detector_cfg = detector_cfg or DetectorConfig()
        self.emit_logits = emit_logits
        # the observability plane: bounded metrics registry + hop trace
        # spans + structured lifecycle events (always on, always O(1)
        # memory; pass obs= to share one plane across runtimes or to
        # write an event JSONL / enable the jax.profiler bridge)
        self.obs = obs if obs is not None else Observability.create()
        self.metrics = StreamMetrics(self.plan, sample_rate, n_shards=S,
                                     registry=self.obs.registry)
        # tenant weight pool: with max_models > 1 the device weights are
        # (K, ...) stacks and each stream binds a registered variant at
        # join time; row 0 always holds the construction weights
        assert max_models >= 1, max_models
        self._pool = WeightPool(max_models) if max_models > 1 else None
        self._tenant_block = tenant_block
        if self._pool is not None:
            assert tenant_block >= 1 and tenant_block & (tenant_block - 1) \
                == 0, f"tenant_block {tenant_block} not a power of two"
            self._pool.admit(DEFAULT_MODEL, self.weights, self.thresholds)
        self._params = prepared_model_params(self.plan, weights, thresholds)
        self._model = _BatchedModel(
            self.plan, self.weights, thresholds, backend, interpret, mesh,
            donate=donate_buffers,
            pool_size=max_models if max_models > 1 else None,
            tenant_block=tenant_block, params=self._params,
        )

        # the generic slot-pool plane (repro.runtime): slot<->sid binding,
        # per-shard pow-2 elastic resize, cross-shard rebalance, idle-time
        # prewarm, and the resize/rebalance observability all live there —
        # this scheduler is one SlotPool *client* (the KWS workload), the
        # LM serving engine is another.  The client surface is the
        # device_state/slot_axes/shard/apply_host_remap methods below.
        self._slots = SlotPool(
            self, capacity,
            initial_capacity=initial_capacity,
            min_capacity=min_capacity,
            n_shards=S, mesh=mesh,
            tenant_block=tenant_block if self._pool is not None else None,
            rebalance_threshold=rebalance_threshold,
            obs=self.obs,
            on_resize=self.metrics.on_resize,
            on_rebalance=self.metrics.on_rebalance,
            prewarm=prewarm,
            clock=self._clock,
        )
        cap0 = self._slots.capacity
        # batched state lives device-resident between hops; host copies are
        # made only on join/leave or fallback peeks — never the hot loop
        self._tails = [
            self._shard(jnp.zeros((cap0, st.tail, st.cin), jnp.int32))
            for st in self.plan.convs
        ]
        self._pendings = [
            self._shard(jnp.zeros((cap0, st.phase, st.cout), jnp.int32))
            for st in self.plan.convs
        ]
        self._gap = self._shard(
            jnp.zeros((cap0, self.plan.gap_channels), jnp.int32)
        )
        # the ingest plane: ONE shared sample arena + slot-vectorized
        # detector + slot-indexed bookkeeping vectors, all resized through
        # the same SlotPlacement remap as the device arrays
        base_inbox = (
            inbox_samples if inbox_samples is not None
            else FrontendConfig().capacity_samples
        )
        # whole hops only: keeps primed slots on pack_hops' block-aligned
        # contiguous fast path (see RingArena.rebase)
        hop = self.plan.hop_samples
        self._inbox_samples = -(-base_inbox // hop) * hop
        self._arena = RingArena(cap0, self._inbox_samples)
        self._detector = BatchedDetector(
            cap0, self.plan.fcs[-1].cout, self.detector_cfg
        )
        self._slot_sid = np.full(cap0, -1, np.int64)
        self._primed_mask = np.zeros(cap0, bool)
        self._frames_v = np.zeros(cap0, np.int64)  # frames per slot
        # per-slot tenant rows (pool row 0 = default model); staged to the
        # device with each hop when pooled, remapped with every resize/
        # rebalance like the other slot-indexed vectors
        self._model_idx_v = np.zeros(cap0, np.int32)
        self._model_rows_dirty = False
        self._model_idx_dev = None  # cached device upload of the rows
        self._streams: dict[int, _Stream] = {}
        self._unprimed: set[int] = set()  # empty in steady state
        self._next_sid = 0
        # hop-boundary peeks are served from the last emit step's logits:
        # _finalize covers EVERY primed slot (masked rows hold steady
        # state), so the row stays valid until the slot is rewritten on
        # the host (priming) or remapped (resize)
        self._emit_step = 0
        self._hop_seq = 0  # the last hop packed; joins its phase spans
        self._emit_cache: np.ndarray | None = None
        self._emit_cache_step = -1
        # idle-time jit pre-warm of the next pow-2 capacity (satellite of
        # the tenant-pool PR: grow spikes hide behind starved steps);
        # the dedup set lives here because its key includes emit_logits
        self._warmed: set[tuple[int, bool]] = set()

    # -- elastic slot pool (delegated to repro.runtime.SlotPool) -------------

    @property
    def capacity(self) -> int:
        """Current pool size (<= ``max_capacity``)."""
        return self._slots.capacity

    @property
    def shard_capacity(self) -> int:
        """Current per-shard pool size (== ``capacity`` with no mesh)."""
        return self._slots.shard_capacity

    @property
    def max_capacity(self) -> int:
        """Capacity ceiling the elastic pool doubles toward."""
        return self._slots.max_capacity

    # internal aliases kept for the concurrency suite and subclasses: the
    # pool owns the state; these names predate the runtime extraction
    @property
    def _capacity(self) -> int:
        return self._slots.capacity

    @property
    def _min_capacity(self) -> int:
        return self._slots.min_capacity

    @property
    def _placement(self):
        return self._slots.placement

    @property
    def _skew_dirty(self) -> bool:
        return self._slots.skew_dirty

    @_skew_dirty.setter
    def _skew_dirty(self, v: bool) -> None:
        self._slots.skew_dirty = v

    def _shard(self, x):
        """Settle an array's batch axis onto the mesh's data sharding."""
        if self.mesh is None:
            return x
        spec = P(self._baxes, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    # -- SlotPool client surface (see repro.runtime.pool.SlotPoolClient) ----

    def device_state(self):
        """The per-slot device pytree the pool resizes/remaps: conv tails,
        pool pendings, GAP counters (slot axis 0 everywhere)."""
        return (tuple(self._tails), tuple(self._pendings), self._gap)

    def set_device_state(self, state) -> None:
        tails, pendings, gap = state
        self._tails = list(tails)
        self._pendings = list(pendings)
        self._gap = gap

    def slot_axes(self):
        n = len(self.plan.convs)
        return ((0,) * n, (0,) * n, 0)

    def shard(self, x, axis: int = 0):
        return self._shard(x)

    def apply_host_remap(self, remap: dict[int, int], new_cap: int) -> None:
        """Ride the host-side ingest plane through a slot remap, so a
        stream's inbox/detector/bookkeeping rows stay glued to its slot."""
        self._arena.apply_remap(remap, new_cap)
        self._detector.apply_remap(remap, new_cap)
        self._slot_sid = remap_rows(self._slot_sid, remap, new_cap, fill=-1)
        self._primed_mask = remap_rows(self._primed_mask, remap, new_cap)
        self._frames_v = remap_rows(self._frames_v, remap, new_cap)
        self._model_idx_v = remap_rows(self._model_idx_v, remap, new_cap)
        self._model_rows_dirty = True
        for s in self._streams.values():
            s.slot = remap[s.slot]
            s.frontend._slot = s.slot
        self._emit_cache = None  # cached rows are indexed by old slots

    def warm(self, capacity: int) -> None:
        self._warm_capacity(capacity)

    # -- tenant weight pool --------------------------------------------------

    @property
    def models(self) -> list[tuple[str, int]]:
        """Resident pool variants as ``(model_id, pool row)`` pairs."""
        if self._pool is None:
            return [(DEFAULT_MODEL, 0)]
        return self._pool.models()

    def register_model(self, model_id: str, weights, thresholds) -> int:
        """Admit one tenant variant into the weight pool; returns its row.

        The variant must share the default model's plan geometry (same
        spec, same hop).  Admission writes one row of the device-resident
        ``(K, ...)`` stacks — shapes never change, so the jitted step's
        cache survives.  When the pool is full, the least-recently-used
        variant with NO live streams is evicted (MemoryError when every
        row is pinned).  Re-admitting a resident id is an LRU touch.
        """
        if self._pool is None:
            raise ValueError(
                "single-model scheduler: construct with max_models > 1 "
                "to enable the tenant weight pool"
            )
        if model_id in self._pool:
            row, _ = self._pool.admit(model_id, weights, thresholds)
            return row
        row, evicted = self._pool.admit(model_id, weights, thresholds)
        w, t = self._pool.params_for(model_id)
        self._model.set_model_row(row, w, t)
        if evicted is not None:
            self.metrics.on_model_evict(evicted)
            self.obs.events.emit("model_evict", model=evicted, row=row)
        self.metrics.on_model_admit(model_id)
        self.obs.events.emit("model_admit", model=model_id, row=row,
                             evicted=evicted)
        return row

    def _stream_params(self, s: _Stream):
        """The weights/thresholds the stream's slot computes with."""
        if self._pool is None or s.model == DEFAULT_MODEL:
            return self.weights, self.thresholds
        return self._pool.params_for(s.model)

    def _sync_model_rows(self) -> None:
        """Rebuild the per-slot tenant rows block-uniformly from the live
        streams.  The kernels gather ONE weight row per tenant block, so
        every slot of a block — free slots included — must carry the
        block's bound row: a freed or remapped slot left stale (or reset
        to 0) would steer its whole block to the wrong weights.  Coalesced
        by a dirty flag so joins/closes/resizes pay it once per hop."""
        if self._pool is None or not self._model_rows_dirty:
            return
        v = np.zeros(self._capacity, np.int32)
        tb = min(self._tenant_block, self._placement.shard_capacity)
        for s in self._streams.values():
            b0 = (s.slot // tb) * tb
            v[b0:b0 + tb] = self._pool.index_of(s.model)
        self._model_idx_v = v
        self._model_rows_dirty = False
        self._model_idx_dev = None  # rows changed: next hop re-uploads

    # -- stream lifecycle ----------------------------------------------------

    def add_stream(self, sid: int | None = None,
                   frontend_cfg: FrontendConfig | None = None,
                   model: str | None = None) -> int:
        """Claim a slot for a new stream on the least-loaded shard (growing
        the pool if needed); returns the stream id.  With a tenant pool,
        ``model`` binds the stream to a registered variant (default: the
        construction weights); placement keeps every ``tenant_block``
        slot block single-model, so the batched hop's per-block weight
        gather stays one row."""
        sid = self._next_sid if sid is None else sid
        assert sid not in self._streams, f"stream {sid} already exists"
        if self._pool is not None:
            model_id = DEFAULT_MODEL if model is None else model
            if model_id not in self._pool:
                raise KeyError(
                    f"unknown model {model_id!r}; register_model() first"
                )
            midx = self._pool.acquire(model_id)
        else:
            if model is not None:
                raise ValueError(
                    "model binding needs a tenant pool (max_models > 1)"
                )
            model_id, midx = DEFAULT_MODEL, 0
        try:
            # grow-on-demand alloc (pow-2 doubling to the ceiling) is the
            # pool's; it raises MemoryError when every slot stays busy
            slot = self._slots.alloc(sid, model=model_id)
        except MemoryError:
            if self._pool is not None:
                self._pool.release(model_id)
            raise
        self._next_sid = max(self._next_sid, sid) + 1
        self._streams[sid] = _Stream(
            sid=sid,
            slot=slot,
            frontend=AudioFrontend(frontend_cfg, arena=self._arena,
                                   slot=slot),
            events=[],
            model=model_id,
        )
        self._slot_sid[slot] = sid
        self._model_idx_v[slot] = midx
        self._model_rows_dirty = True  # block fill happens at sync
        self._detector.reset_slot(slot)
        self._unprimed.add(sid)
        self.metrics.on_join(sid)
        self.obs.events.emit("join", sid=sid, slot=slot,
                             shard=slot // self._placement.shard_capacity)
        return sid

    def _require(self, sid: int) -> _Stream:
        s = self._streams.get(sid)
        if s is None:
            live = sorted(self._streams)
            shown = live if len(live) <= 8 else live[:8] + ["..."]
            raise KeyError(
                f"unknown or already-closed stream sid {sid}; "
                f"{len(live)} live sid(s): {shown}"
            )
        return s

    def push_audio(self, sid: int, audio: np.ndarray) -> None:
        s = self._require(sid)
        s.frontend.push(audio)  # arena counts samples_in; folded at close

    def push_audio_batch(self, sids: list[int],
                         chunks: list[np.ndarray]) -> None:
        """Bulk twin of ``push_audio``: one vectorized quantize, then one
        contiguous slice copy per chunk into its slot's arena row (two
        where the chunk crosses the row's end), lands every stream's
        chunk in the shared arena (``RingArena.push_batch``) — the ingest
        half of the zero-per-slot hop path.  Float PCM and u8 chunks may
        be mixed, and a sid may appear multiple times: duplicate-sid
        chunks coalesce in arrival order (float chunks pre-quantized with
        the slot's gain — the exact math the arena would apply — so the
        arena's bytes stay bit-identical to sequential pushes).
        Per-stream ``samples_in`` counters are NOT walked here — the
        arena's vectorized counter is the truth and folds into the
        stream's metrics at close.

        The whole call is one ``ingest`` span (args ``chunks``,
        ``samples``, ``coalesced``: chunks merged into an earlier one of
        the same stream, ``wrapped``: chunks split at their row's end)."""
        arena = self._arena
        with self.obs.trace.span("ingest", clock=self._clock,
                                 chunks=len(sids)) as args:
            before = arena.total_samples_in
            streams = [self._require(sid) for sid in sids]
            slots = np.fromiter((s.slot for s in streams), np.int64,
                                len(streams))
            if np.unique(slots).size != slots.size:
                slots, chunks, extra = self._coalesce_chunks(slots, chunks)
            else:
                extra = None
            wrapped = arena.push_batch(slots, chunks)
            coalesced = 0
            if extra is not None:
                # credit the chunks the coalesce merged away (push_batch
                # counted one per slot) so chunks_in stays arrival-accurate
                coalesced = int(extra.sum())
                arena.chunks_in[slots] += extra
                arena.total_chunks_in += coalesced
            args["samples"] = arena.total_samples_in - before
            args["coalesced"] = coalesced
            args["wrapped"] = wrapped

    def _coalesce_chunks(self, slots: np.ndarray, chunks: list[np.ndarray]
                         ) -> tuple[np.ndarray, list[np.ndarray], np.ndarray]:
        """Merge duplicate-slot chunks into one chunk per slot (arrival
        order preserved).  Float PCM is quantized here with the slot's
        gain — identical to ``RingArena.push_batch``'s vectorized pass —
        so a float chunk followed by a u8 chunk concatenates without the
        dtype of one corrupting the other."""
        merged: dict[int, list[np.ndarray]] = {}
        for slot, chunk in zip(slots.tolist(), chunks):
            c = np.asarray(chunk).reshape(-1)
            if c.dtype.kind == "f":
                c = quantize_pcm(c, self._arena.gain[slot])
            elif c.dtype.kind not in "iu":
                raise TypeError(
                    f"audio must be float PCM or integer u8 codes, "
                    f"got dtype {c.dtype}"
                )
            merged.setdefault(slot, []).append(c)
        out_slots = np.fromiter(merged.keys(), np.int64, len(merged))
        out_chunks = [
            cs[0] if len(cs) == 1 else np.concatenate(cs)
            for cs in merged.values()
        ]
        extra = np.fromiter(
            (len(cs) - 1 for cs in merged.values()), np.int64, len(merged)
        )
        return out_slots, out_chunks, extra

    @property
    def active(self) -> list[int]:
        return sorted(self._streams)

    # -- the batched hop -----------------------------------------------------

    def _prime_ready(self) -> None:
        """Batched mass-join primer: every unprimed stream whose inbox
        holds ``prime_samples`` warms up through ONE vectorized numpy
        advance (``state.prime_batch`` — bit-exact with the per-stream
        ``StreamState`` warm-up) and lands in the slot pool via one
        batched scatter per state array, so a 256-stream mass join costs
        one cascade instead of 256 per-stream numpy warm-ups.  Runs only
        while ``self._unprimed`` is non-empty — never in steady state."""
        prime = self.plan.prime_samples
        sids = sorted(self._unprimed)
        slots = np.fromiter(
            (self._streams[sid].slot for sid in sids), np.int64, len(sids)
        )
        ready = (self._arena.wr[slots] - self._arena.rd[slots]) >= prime
        if not ready.any():
            return
        sids = [sid for sid, r in zip(sids, ready.tolist()) if r]
        with self.obs.trace.span("prime_batch", clock=self._clock,
                                 n=len(sids)):
            slots = slots[ready]
            samples = self._arena.pop_batch(slots, prime)
            # priming consumed a non-hop-multiple; realign the inboxes so
            # every future hop window is one contiguous block
            self._arena.rebase_batch(slots)
            # one vectorized warm-up per tenant model (a single group without
            # a pool): each group's rows land via the same batched scatters
            if self._pool is None:
                groups = [(self.weights, self.thresholds,
                           np.arange(len(sids), dtype=np.int64))]
            else:
                by_model: dict[str, list[int]] = {}
                for j, sid in enumerate(sids):
                    by_model.setdefault(self._streams[sid].model, []).append(j)
                groups = [
                    (*self._stream_params(self._streams[sids[pos[0]]]),
                     np.asarray(pos, np.int64))
                    for pos in by_model.values()
                ]
            for w, t, pos in groups:
                steady = prime_batch(self.plan, w, t, samples[pos])
                gslots = slots[pos]
                jslots = jnp.asarray(gslots)
                for i in range(len(self.plan.convs)):
                    self._tails[i] = self._tails[i].at[jslots].set(
                        jnp.asarray(steady["tails"][i])
                    )
                    if self._pendings[i].shape[1]:
                        self._pendings[i] = self._pendings[i].at[jslots].set(
                            jnp.asarray(steady["pendings"][i])
                        )
                self._gap = self._gap.at[jslots].set(
                    jnp.asarray(steady["gap"].astype(np.int32))
                )
                self._frames_v[gslots] = steady["frames"]
            self._primed_mask[slots] = True
            for sid in sids:
                s = self._streams[sid]
                s.primed = True
                self._unprimed.discard(sid)
                # host wrote the slot: earlier cached logits don't cover it;
                # the NEXT emit step (which includes this write) does
                s.stamp = self._emit_step + 1
        self.obs.events.emit("mass_join", n=len(sids))

    def _clear_slot(self, slot: int) -> None:
        for i in range(len(self.plan.convs)):
            self._tails[i] = self._tails[i].at[slot].set(0)
            if self._pendings[i].shape[1]:
                self._pendings[i] = self._pendings[i].at[slot].set(0)
        self._gap = self._gap.at[slot].set(0)

    def _host_state(self):
        """One bulk device->host view of the batched state (zero-copy on
        CPU, a gather across shards under a mesh); per-slot rows are then
        plain numpy indexing."""
        return (
            [np.asarray(t) for t in self._tails],
            [np.asarray(p) for p in self._pendings],
            np.asarray(self._gap),
        )

    def _extract_slot(self, s: _Stream, host=None) -> StreamState:
        tails, pendings, gap = host if host is not None else self._host_state()
        w, t = self._stream_params(s)
        st = StreamState(self.plan, w, t)
        st.import_steady(
            [t[s.slot] for t in tails],
            [p[s.slot] for p in pendings],
            gap[s.slot],
            int(self._frames_v[s.slot]),
        )
        st.samples_seen = s.frontend.samples_in - len(s.frontend)
        return st

    def _hop_barriers(self) -> None:
        """Hop-boundary housekeeping: rebalance-on-skew (plus the shrink
        the migration may unpin) and the mass-join primer.  The async
        plane only calls this behind an epoch barrier (no hop in flight),
        so a slot remap can never invalidate in-flight row indices."""
        # leave churn since the last hop may have skewed the shards —
        # the pool migrates-on-idle, then re-checks the shrink the
        # migration may have unpinned
        self._slots.hop_barrier()
        if self._unprimed:
            self._prime_ready()  # numpy warm-up, excluded from step timing

    def _pack_ready(self):
        """Pack stage: consume one hop window from every ready slot.
        Returns ``None`` when no stream is ready, else ``(hop, ready_mask,
        audio)``.

        The ring's ``pack`` and ``hop`` spans start at ``t0``, before the
        readiness compare; their profile annotations open once the hop
        has ready streams, so a starved turn leaves none in the profile."""
        hop = self.plan.hop_samples
        t0 = self._clock()
        ready_mask = self._primed_mask & self._arena.ready_mask(hop)
        ready_slots = np.nonzero(ready_mask)[0]
        if ready_slots.size == 0:
            return None
        self._hop_seq += 1
        seq = self._hop_seq
        tr = self.obs.trace
        hop_ann = tr.annotate("hop", hop=seq)
        ann = None if hop_ann is None else tr.annotate("pack", hop=seq)
        audio = self._arena.pack_hops(ready_slots, hop)
        shard_counts = np.bincount(
            ready_slots // self._placement.shard_capacity,
            minlength=self.n_shards,
        )
        # pack phase ends here; staging (jnp.asarray/device_put) and the
        # jitted call itself are the dispatch phase
        t_pack = self._clock()
        ann = tr.handoff(ann, "dispatch", hop=seq)
        h = _Hop(seq=seq, ready_slots=ready_slots, shard_counts=shard_counts,
                 t0=t0, t_pack=t_pack, hop_ann=hop_ann, ann=ann)
        return h, ready_mask, audio

    def _dispatch_hop(self, h: _Hop, ready_mask, audio) -> None:
        """Dispatch stage: stage operands, launch the jitted hop, and
        reassign the resident state from its (still unforced) result
        futures.  Nothing here blocks — JAX's async dispatch returns
        immediately — and with donated buffers the previous state arrays
        are consumed by the call, so they must not be read afterwards.
        Leaves the logits/posterior futures (None with emit off) in ``h``
        and opens its ``fence`` phase."""
        args = (
            self._shard(jnp.asarray(audio)),
            self._shard(jnp.asarray(ready_mask)),
            tuple(self._tails), tuple(self._pendings), self._gap,
        )
        if self._pool is not None:
            self._sync_model_rows()
            if self._model_idx_dev is None:
                # steady state reuses one device copy: the rows only
                # move on join/close/resize, not per hop
                self._model_idx_dev = self._shard(
                    jnp.asarray(self._model_idx_v))
            args = args + (self._model_idx_dev,)
        n_entries = self._jit_entries()
        if self.emit_logits:
            tails, pendings, gap, h.logits, h.post = self._model.step(
                *args, emit=True
            )
        else:
            tails, pendings, gap = self._model.step(*args, emit=False)
        if self._jit_entries() != n_entries:
            # this hop traced a new (capacity, emit) shape — the compile
            # spike idle pre-warming exists to hide (the multi-tenant
            # suite pins the post-grow hop clean when prewarm=True)
            self.obs.trace.instant("compile", clock=self._clock,
                                   capacity=self._capacity)
        self._tails = list(tails)
        self._pendings = list(pendings)
        self._gap = gap
        # dispatch phase ends when the jitted call has returned its
        # futures; the fence (and, under the async plane, the hop's wait
        # in the pipeline) runs from here to the results being ready
        h.t_dispatch = self._clock()
        h.ann = self.obs.trace.handoff(h.ann, "fence", hop=h.seq)

    def _jit_entries(self) -> int:
        """Jit-cache entry count of the batched step."""
        return self._model.step._cache_size()

    def _fence_fetch(self, h: _Hop, fence_on):
        """Fence and fetch stages: block until ``fence_on`` is ready, then
        copy the hop's logits and posteriors to the host (one bulk
        transfer each).  Returns them (None with emit off).

        Without the fence, JAX's async dispatch would let wall time
        measure *enqueue* rather than execution (egregiously so with
        emit_logits off, where nothing else forces a sync)."""
        tr = self.obs.trace
        jax.block_until_ready(fence_on)
        h.t_fence = self._clock()
        h.ann = tr.handoff(h.ann, "fetch", hop=h.seq)
        logits_h = post_h = None
        if h.logits is not None:
            logits_h = np.asarray(h.logits)
            post_h = np.asarray(h.post)
            h.fetch_bytes = logits_h.nbytes + post_h.nbytes
            h.logits = h.post = None
        h.t_device = self._clock()
        h.ann = tr.handoff(h.ann, "detector", hop=h.seq)
        return logits_h, post_h

    def _fold_hop(self, h: _Hop, logits_h, post_h,
                  fold_hidden: bool = False) -> HopBatch:
        """Fold stage: apply one resolved hop's results to the host-side
        planes — emit cache, frame counters, slot-vectorized detector,
        metrics, lifecycle events, trace spans.  The sync path runs it
        inline right after the fence; the async plane defers it to the
        hop's retirement, strictly in FIFO dispatch order, which keeps
        every per-slot sequence (frames, detector state, events)
        bit-identical to the synchronous schedule."""
        ready_slots = h.ready_slots
        if self.emit_logits:
            self._emit_step += 1
            self._emit_cache = logits_h
            self._emit_cache_step = self._emit_step
        self._frames_v[ready_slots] += self.plan.frames_per_hop
        sids = self._slot_sid[ready_slots]
        frames = self._frames_v[ready_slots]
        rows_logits = rows_post = None
        detections: list[Detection] = []
        if self.emit_logits:
            rows_logits = logits_h[ready_slots]
            rows_post = post_h[ready_slots]
            fired, f_cls, f_score = self._detector.update_batch(
                ready_slots, frames, rows_post
            )
            for r, c, sc in zip(fired.tolist(), f_cls.tolist(),
                                f_score.tolist()):
                det = Detection(int(sids[r]), int(c), int(frames[r]),
                                float(sc))
                self._streams[det.stream_id].events.append(det)
                self.metrics.on_detection(det.stream_id)
                self.obs.events.emit("detection", sid=det.stream_id,
                                     cls=det.cls, frame=det.frame,
                                     score=det.score)
                detections.append(det)
        t_detector = self._clock()
        tr = self.obs.trace
        h.ann = tr.handoff(h.ann, "push_fold", hop=h.seq)
        hidden_s = h.hidden_s
        if fold_hidden:
            # a later hop is still executing while this fold runs, so the
            # detector phase is hidden under device compute
            hidden_s += t_detector - h.t_device
        n_disp = self._model.dispatches_per_hop(self.emit_logits)
        model_counts = None
        if self._pool is not None:
            mc = np.bincount(self._model_idx_v[ready_slots],
                             minlength=self._pool.max_models)
            model_counts = {
                m: int(mc[row]) for m, row in self._pool.models()
                if mc[row]
            }
        t0, t_pack, t_dispatch = h.t0, h.t_pack, h.t_dispatch
        t_fence, t_device = h.t_fence, h.t_device
        self.metrics.on_step(
            ready_slots.size, self.plan.frames_per_hop,
            t_detector - t0, host_pack_s=t_pack - t0,
            shard_counts=h.shard_counts.tolist(),
            finalized=self.emit_logits,
            dispatch_s=t_dispatch - t_pack, fence_s=t_fence - t_dispatch,
            fetch_s=t_device - t_fence, detector_s=t_detector - t_device,
            hidden_s=hidden_s, dispatches=n_disp, model_counts=model_counts,
        )
        # fold the arena's push-side counters into the metrics at the hop
        # boundary: two scalar reads, so neither the push path nor this
        # hot path ever walks per-sid counter objects
        self.metrics.on_push_fold(self._arena.total_samples_in,
                                  self._arena.total_chunks_in)
        t_end = self._clock()
        close_annotation(h.ann)
        close_annotation(h.hop_ann)
        # hop trace: on the sync path the stamps are consecutive, so the
        # phase spans tile the hop span exactly (the tests assert >= 95%
        # coverage).  Under the async plane, hop N+1's pack/dispatch
        # spans legitimately overlap hop N's fence span — union-interval
        # coverage (``trace.coverage(mode="overlap")``) accounts for
        # that.  One batched call, seven deque appends — B-independent.
        n_ready = int(ready_slots.size)
        seq = h.seq
        tr.add_batch((
            ("pack", t0, t_pack - t0, {"n": n_ready, "hop": seq}),
            ("dispatch", t_pack, t_dispatch - t_pack,
             {"dispatches": n_disp, "hop": seq}),
            ("fence", t_dispatch, t_fence - t_dispatch, {"hop": seq}),
            ("fetch", t_fence, t_device - t_fence,
             {"bytes": h.fetch_bytes, "hop": seq}),
            ("detector", t_device, t_detector - t_device, {"hop": seq}),
            ("push_fold", t_detector, t_end - t_detector, {"hop": seq}),
            ("hop", t0, t_end - t0, {"n": n_ready, "hop": seq}),
        ))
        return HopBatch(sids=sids, frames=frames, logits=rows_logits,
                        posteriors=rows_post, detections=detections)

    def step_batch(self) -> HopBatch | None:
        """Advance every stream that has a full hop buffered; None when no
        stream is ready.

        This is the steady-state hot path and it contains NO python loop
        over slots: readiness is one vectorized compare over the arena,
        hop packing is one gather (``RingArena.pack_hops``), shard counts
        come from ``np.bincount``, bookkeeping updates are fancy-indexed
        vector ops, and detection advances through the slot-vectorized
        ``BatchedDetector``.  Per-slot python survives only off this path
        (priming, teardown, fallback peeks) and for detections that
        actually fire.

        The body is pack -> dispatch -> fence -> fetch -> fold, each
        stage a method the async plane (``AsyncStreamScheduler``) reuses
        with the fence, fetch and fold deferred to the hop's retirement.
        Each stage is a trace span of the same name (the fold is
        ``detector`` then ``push_fold``), all inside one ``hop`` span.
        """
        self._hop_barriers()
        packed = self._pack_ready()
        if packed is None:
            self._maybe_prewarm()  # starved step = idle; warm the grow
            return None
        h, ready_mask, audio = packed
        self._dispatch_hop(h, ready_mask, audio)
        logits_h, post_h = self._fence_fetch(
            h, (self._tails, self._pendings, self._gap))
        return self._fold_hop(h, logits_h, post_h)

    # -- idle-time jit pre-warm ----------------------------------------------

    def _maybe_prewarm(self) -> None:
        """Compile the NEXT pow-2 capacity's hop while starved, so the
        first hop after a grow pays no compile spike (``prewarm=True``;
        the trace stays free of ``compile`` events across the resize —
        pinned by tests/test_multitenant.py).  The pool picks the target
        capacity and calls back into ``warm``."""
        self._slots.maybe_prewarm()

    def _warm_capacity(self, cap: int) -> None:
        """Run the jitted step once on zero dummies at ``cap`` slots —
        same shapes/dtypes/shardings as a real hop, so jit's shape-keyed
        cache is hot before the resize ever happens."""
        key = (cap, self.emit_logits)
        if key in self._warmed:
            return
        self._warmed.add(key)
        plan = self.plan
        z = lambda shape, dt: self._shard(jnp.zeros(shape, dt))  # noqa: E731
        with self.obs.trace.span("prewarm", clock=self._clock, capacity=cap):
            args = (
                z((cap, plan.hop_samples), jnp.int32),      # pack_hops dtype
                z((cap,), bool),
                tuple(z((cap, st.tail, st.cin), jnp.int32)
                      for st in plan.convs),
                tuple(z((cap, st.phase, st.cout), jnp.int32)
                      for st in plan.convs),
                z((cap, plan.gap_channels), jnp.int32),
            )
            if self._pool is not None:
                args = args + (z((cap,), jnp.int32),)
            out = self._model.step(*args, emit=self.emit_logits)
            jax.block_until_ready(out)
        self.obs.events.emit("prewarm", capacity=cap)

    def step(self) -> list[tuple[int, int, np.ndarray | None, Detection | None]]:
        """Advance every stream that has a full hop buffered.

        Returns one (sid, frame_idx, logits, detection) tuple per advanced
        stream; logits is None when ``emit_logits`` is off.  With
        ``emit_logits`` the logits/posteriors come from the in-jit
        finalization tail — no host-side re-inference per hop.

        This is a compatibility collation of ``step_batch`` — building
        one tuple per stream is inherently O(ready) python, so throughput
        callers (the benchmark's steady loop) should consume the columnar
        ``HopBatch`` directly.
        """
        return self._collate(self.step_batch())

    @staticmethod
    def _collate(batch: HopBatch | None
                 ) -> list[tuple[int, int, np.ndarray | None,
                                 Detection | None]]:
        if batch is None:
            return []
        det_by_sid = {d.stream_id: d for d in batch.detections}
        if batch.logits is None:
            return [
                (int(sid), int(fr), None, None)
                for sid, fr in zip(batch.sids.tolist(), batch.frames.tolist())
            ]
        return [
            (int(sid), int(fr), batch.logits[r].copy(), det_by_sid.get(sid))
            for r, (sid, fr) in enumerate(
                zip(batch.sids.tolist(), batch.frames.tolist())
            )
        ]

    def run_until_starved(self) -> list[tuple[int, int, np.ndarray | None,
                                              Detection | None]]:
        """Step until no stream has a full hop buffered."""
        out = []
        while True:
            r = self.step()
            if not r:
                return out
            out.extend(r)

    def drain(self) -> int:
        """Run ``step_batch`` until starved; returns hops executed.  The
        zero-collation twin of ``run_until_starved`` for callers that read
        results from metrics/peeks instead of per-stream tuples."""
        hops = 0
        while self.step_batch() is not None:
            hops += 1
        return hops

    # -- inspection / teardown ----------------------------------------------

    def peek(self, sid: int) -> np.ndarray:
        """Finalized logits if the stream ended now (inbox included) —
        bit-exact with the offline executor on the audio pushed so far.

        On a hop boundary (empty inbox) this reads the last emit step's
        cached logits — the finalization tail already covered every primed
        slot, so no recompute — or re-runs the in-jit tail when no emit
        covers this slot yet; with leftover sub-hop samples it drops to
        the exact numpy fallback (``StreamState.peek_logits``)."""
        s = self._require(sid)
        if s.primed and len(s.frontend) == 0:
            if (self._emit_cache is not None
                    and s.stamp <= self._emit_cache_step):
                return self._emit_cache[s.slot].copy()
            fargs = (tuple(self._tails), tuple(self._pendings), self._gap)
            if self._pool is not None:
                self._sync_model_rows()
                fargs = fargs + (
                    self._shard(jnp.asarray(self._model_idx_v)),
                )
            logits, _ = self._model.finalize(*fargs)
            return np.asarray(logits[s.slot])
        return self._peek_fallback(s)

    def _peek_fallback(self, s: _Stream) -> np.ndarray:
        if s.primed:
            st = self._extract_slot(s)
        else:
            w, t = self._stream_params(s)
            st = StreamState(self.plan, w, t)
        leftover = s.frontend.peek_all() if len(s.frontend) else None
        return st.peek_logits(leftover)

    def close_stream(self, sid: int) -> StreamResult:
        """Flush (right-pad + drop incomplete pools), free the slot, and
        shrink the pool once occupancy drops to a quarter."""
        s = self._require(sid)
        del self._streams[sid]
        self._unprimed.discard(sid)
        # before the slot is scrubbed
        samples_in = s.frontend.samples_in
        chunks_in = s.frontend.chunks_in
        if s.primed:
            st = self._extract_slot(s)
        else:
            w, t = self._stream_params(s)
            st = StreamState(self.plan, w, t)
        st.advance(s.frontend.pop_all(), flush=True)
        logits = st.logits()
        # one last detector update with the flushed logits (host softmax),
        # through the same slot-vectorized state machine the hops drove
        fired, f_cls, f_score = self._detector.update_batch(
            np.array([s.slot], np.int64), np.array([st.frames], np.int64),
            _softmax(logits)[None, :],
        )
        if fired.size:
            det = Detection(sid, int(f_cls[0]), st.frames, float(f_score[0]))
            s.events.append(det)
            self.metrics.on_detection(sid)
        self._slots.free(s.slot)  # also marks the pool skew-dirty
        if self._pool is not None:
            self._pool.release(s.model)  # unpin; LRU may now evict it
        self._clear_slot(s.slot)  # scrub so the next tenant starts clean
        self._arena.clear_slot(s.slot)
        self._detector.reset_slot(s.slot)
        self._slot_sid[s.slot] = -1
        self._primed_mask[s.slot] = False
        self._frames_v[s.slot] = 0
        self._model_rows_dirty = True  # never zero the slot: its block
        # may still be bound to a tenant; sync rebuilds block-uniformly
        self.metrics.on_close(sid, frames_out=st.frames,
                              samples_in=samples_in, chunks_in=chunks_in)
        self.obs.events.emit("close", sid=sid, frames=st.frames,
                             samples=samples_in, events=len(s.events))
        # a leave can skew the shards; the migration itself waits for the
        # next hop boundary (migrate-on-idle), but the shrink runs now so
        # an emptying pool releases capacity without needing another hop
        self._slots.maybe_shrink()
        return StreamResult(
            stream_id=sid,
            logits=logits,
            frames=st.frames,
            samples=st.samples_seen,
            events=list(s.events),
        )
