"""Public jit'd wrappers around the Pallas kernels.

These handle host-visible concerns the kernels do not: bit-packing, padding
to block multiples (padding = inactive wordlines / unused bitline pairs, so
it is numerically inert), tap-shift view construction, and the
popcount-vs-MXU dispatch heuristic (DESIGN.md §2.4).

Off the TPU the kernels run in the Pallas interpreter (``interpret=None``
resolves through ``default_interpret``).  On a TPU they compile to Mosaic
kernels, whose layouts these wrappers prepare: slots or rows on the tiled
axes, int8 GEMM operands, and the first layer's stride cut into phases.
``tests/test_tpu_compile.py`` compiles them for a v5e ahead of time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.kernels import bnn_conv1d as _conv
from repro.kernels import hop_megakernel as _mega
from repro.kernels import twm_matmul as _mm


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_axis(x, mult, axis):
    return quant.pad_to_multiple(x, mult, axis)


# ---------------------------------------------------------------------------
# Packing / view helpers (host side of the kernel contract)
# ---------------------------------------------------------------------------

def pack_activations(x_bits: jax.Array) -> jax.Array:
    """(..., C) {0,1} -> (..., ceil(C/32)) uint32."""
    x = _pad_axis(x_bits.astype(jnp.uint32), quant.PACK, -1)
    return quant.pack_bits(x, axis=-1)


def pack_weight_planes(w_t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Ternary (Cin, Cout) or (K, Cin, Cout) -> packed planes along Cin."""
    pos, neg = quant.ternary_planes(w_t)
    axis = -2
    pos = _pad_axis(pos, quant.PACK, axis)
    neg = _pad_axis(neg, quant.PACK, axis)
    return quant.pack_bits(pos, axis=axis), quant.pack_bits(neg, axis=axis)


def shifted_strided_views(
    x_packed: jax.Array, k: int, stride: int, pad: int
) -> jax.Array:
    """(L, Cw) packed -> (K, L_out, Cw) tap views (line-buffer mirror)."""
    l = x_packed.shape[0]
    xp = jnp.pad(x_packed, ((pad, pad), (0, 0)))
    l_out = (l + 2 * pad - k) // stride + 1
    taps = [xp[t : t + (l_out - 1) * stride + 1 : stride] for t in range(k)]
    return jnp.stack(taps, axis=0)


# ---------------------------------------------------------------------------
# Dense layer entry points
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def twm_linear(
    x_bits: jax.Array,
    w_t: jax.Array,
    thr: jax.Array | None = None,
    flip: jax.Array | None = None,
    *,
    mode: str = "sa",
    interpret: bool | None = None,
) -> jax.Array:
    """Binary-activation ternary-weight dense layer via the popcount kernel.

    x_bits (M, K) {0,1}; w_t (K, N) {-1,0,1}.  Returns (M, N): uint32 bits in
    ``sa`` mode, int32 popcount diff in ``raw`` mode.
    """
    interpret = default_interpret() if interpret is None else interpret
    m, kdim = x_bits.shape
    n = w_t.shape[1]
    xq = pack_activations(x_bits)
    wp, wn = pack_weight_planes(w_t)

    bm = _pick_block(m, _mm.DEFAULT_BM)
    bn = _pick_block(n, _mm.DEFAULT_BN)
    xq = _pad_axis(xq, bm, 0)
    wp = _pad_axis(wp, bn, 1)
    wn = _pad_axis(wn, bn, 1)
    if mode == "sa":
        thr_p = _pad_axis(thr.astype(jnp.float32), bn, 0)
        flip_p = _pad_axis(flip.astype(jnp.int32), bn, 0)
        out = _mm.twm_matmul(
            xq, wp, wn, thr_p, flip_p, bm=bm, bn=bn, mode="sa", interpret=interpret
        )
    else:
        out = _mm.twm_matmul(xq, wp, wn, bm=bm, bn=bn, mode="raw", interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def twm_linear_mxu(
    x_bits: jax.Array,
    w_t: jax.Array,
    thr: jax.Array,
    flip: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """MXU int8 path with identical semantics (beyond-paper, compute-bound)."""
    interpret = default_interpret() if interpret is None else interpret
    m, kdim = x_bits.shape
    n = w_t.shape[1]
    bm = _pick_block(m, 256)
    bn = _pick_block(n, 256)
    x8 = _pad_axis(x_bits.astype(jnp.int8), bm, 0)
    w8 = _pad_axis(w_t.astype(jnp.int8), bn, 1)
    thr_p = _pad_axis(thr.astype(jnp.float32), bn, 0)
    flip_p = _pad_axis(flip.astype(jnp.int32), bn, 0)
    out = _mm.twm_matmul_mxu(x8, w8, thr_p, flip_p, bm=bm, bn=bn, interpret=interpret)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Conv layer entry point (PWB-fused)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("stride", "pad", "pool", "mode", "interpret")
)
def bnn_conv1d(
    x_bits: jax.Array,
    w_t: jax.Array,
    thr: jax.Array | None = None,
    flip: jax.Array | None = None,
    *,
    stride: int = 1,
    pad: int = 0,
    pool: int = 1,
    mode: str = "sa",
    interpret: bool | None = None,
) -> jax.Array:
    """Fused binary conv1d -> SA -> max-pool (the paper's conv+PWB pipeline).

    x_bits (L, Cin) {0,1}; w_t (K, Cin, Cout).  Output (L_out//pool, Cout)
    uint32 bits (or (L_out, Cout) int32 when mode='raw').
    """
    interpret = default_interpret() if interpret is None else interpret
    k, cin, cout = w_t.shape
    l = x_bits.shape[0]
    l_out = (l + 2 * pad - k) // stride + 1

    xq = pack_activations(x_bits)  # (L, Cw)
    xs = shifted_strided_views(xq, k, stride, pad)  # (K, L_out, Cw)
    wp, wn = pack_weight_planes(w_t)  # (K, Cw, Cout)

    bn = _pick_block(cout, _conv.DEFAULT_BN)
    # block length: multiple of pool, divides padded L_out
    bl = _pick_block(l_out, _conv.DEFAULT_BL, step=pool)
    xs = _pad_axis(xs, bl, 1)
    wp = _pad_axis(wp, bn, 2)
    wn = _pad_axis(wn, bn, 2)

    if mode == "sa":
        thr_p = _pad_axis(thr.astype(jnp.float32), bn, 0)
        flip_p = _pad_axis(flip.astype(jnp.int32), bn, 0)
        out = _conv.bnn_conv1d_packed(
            xs, wp, wn, thr_p, flip_p,
            pool=pool, bl=bl, bn=bn, mode="sa", interpret=interpret,
        )
        return out[: l_out // pool, :cout]
    out = _conv.bnn_conv1d_packed(
        xs, wp, wn, pool=1, bl=bl, bn=bn, mode="raw", interpret=interpret
    )
    return out[:l_out, :cout]


def bitserial_conv1d(
    x_u: jax.Array,
    w_t: jax.Array,
    bits: int,
    offset: int = 0,
    *,
    stride: int = 1,
    pad: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Multi-bit-input conv in ONE kernel launch (first-layer path).

    The ``<< b`` plane accumulation runs inside the kernel
    (``bnn_bitserial_step_packed``) instead of as ``bits`` separate
    dispatches with HBM-resident partials.  Spatial padding uses the
    offset code (see kernels/ref.py)."""
    return bitserial_conv1d_batched(
        x_u[None], w_t, bits=bits, offset=offset, stride=stride, pad=pad,
        interpret=interpret,
    )[0]


def _conv_rows(xq, k: int, stride: int, l_out: int):
    """(..., B, L, Cw) packed words -> (..., K, Cw, B * L_out) tap views
    with one lane per (stream, position) row (the batched kernels'
    layout)."""
    span = (l_out - 1) * stride + 1
    taps = jnp.stack([xq[..., t : t + span : stride, :] for t in range(k)],
                     axis=-4)  # (..., K, B, L_out, Cw)
    rows = taps.reshape(*taps.shape[:-3], -1, taps.shape[-1])
    return jnp.swapaxes(rows, -1, -2)


def _rows_blocks(b: int, l_out: int, cout: int, bb: int | None,
                 pooled: bool) -> tuple[int, int, int]:
    """(slot padding, row block, channel block) for the batched kernels.

    Pooled: one grid cell is one ``bb``-slot tenant block (single-tenant
    by placement).  Otherwise the row block is whole 128-lane tiles."""
    bn = _round_up(cout, 8) if cout <= 512 else 256
    if pooled:
        return _round_up(b, bb) - b, bb * l_out, bn
    br = min(_conv.DEFAULT_BR, _round_up(b * l_out, 128))
    return 0, br, bn


def _rows_out(out, b: int, l_out: int, cout: int):
    """(Cout_pad, R_pad) kernel output -> (B, L_out, Cout)."""
    return out[:cout, : b * l_out].T.reshape(b, l_out, cout)


def _rows_planes(w_t, bn: int):
    """Ternary ([M,] K, Cin, Cout) -> packed ([M,] K, Cout_pad, Cw)
    planes, output channels on sublanes."""
    wp, wn = pack_weight_planes(w_t)  # ([M,] K, Cw, Cout)
    return tuple(_pad_axis(jnp.swapaxes(x, -1, -2), bn, -2) for x in (wp, wn))


@functools.partial(
    jax.jit,
    static_argnames=("bits", "offset", "stride", "pad", "bb", "interpret"),
)
def bitserial_conv1d_batched(
    x_u: jax.Array,
    w_t: jax.Array,
    model_idx: jax.Array | None = None,
    *,
    bits: int,
    offset: int = 0,
    stride: int = 1,
    pad: int = 0,
    bb: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched multi-bit-input raw conv, all bit planes in one launch.

    x_u (B, L, Cin) integer codes in [0, 2^bits); w_t (K, Cin, Cout) — or
    a pooled (M, K, Cin, Cout) stack with ``model_idx`` ((B,) int32 tenant
    ids, constant per ``bb`` slot block).  Returns (B, L_out, Cout) int32
    raw popcount diff with the offset code already folded out
    (``acc - offset * sum(w)``, per tenant when pooled).  The per-plane
    views are packed host-side; the kernel loops planes x taps with the
    weight planes fetched into VMEM once (paper §II-F bit-serial
    scheduling).
    """
    interpret = default_interpret() if interpret is None else interpret
    pooled = model_idx is not None
    b, l, cin = x_u.shape
    k, cin2, cout = w_t.shape[-3:]
    assert cin == cin2, (cin, cin2)
    l_out = (l + 2 * pad - k) // stride + 1
    bb = _conv.DEFAULT_BB if bb is None else bb
    pad_b, br, bn = _rows_blocks(b, l_out, cout, bb, pooled)
    # spatial padding carries the offset code; padded slots are sliced off
    x_u = jnp.pad(x_u.astype(jnp.uint32), ((0, pad_b), (pad, pad), (0, 0)),
                  constant_values=offset)
    planes = jnp.stack(
        [(x_u >> bi) & 1 for bi in range(bits)], axis=0
    )  # (bits, B, L_pad, Cin)
    xs = _pad_axis(_conv_rows(pack_activations(planes), k, stride, l_out),
                   br, -1)
    wp, wn = _rows_planes(w_t, bn)
    mi = _block_model_idx(model_idx, b, bb, pad_b) if pooled else None
    out = _conv.bnn_bitserial_step_packed(
        xs, wp, wn, mi, br=br, bn=bn, interpret=interpret
    )
    acc = _rows_out(out, b, l_out, cout)
    if offset:
        if pooled:
            wsum = jnp.sum(w_t.astype(jnp.int32), axis=(1, 2))  # (M, Cout)
            acc = acc - offset * wsum[
                jnp.asarray(model_idx, jnp.int32)
            ][:, None, :]
        else:
            wsum = jnp.sum(w_t.astype(jnp.int32), axis=(0, 1))
            acc = acc - offset * wsum[None, None, :]
    return acc


# ---------------------------------------------------------------------------
# Batched multi-stream conv entry point (repro.stream scheduler)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("stride", "pad", "bb", "interpret"),
)
def bnn_conv1d_batched(
    x_bits: jax.Array,
    w_t: jax.Array,
    model_idx: jax.Array | None = None,
    *,
    stride: int = 1,
    pad: int = 0,
    bb: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched binary raw conv1d with weights shared across the batch axis.

    x_bits (B, L, Cin) {0,1}; w_t (K, Cin, Cout) broadcast over B.  Output
    (B, L_out, Cout) int32 raw popcount diff.  Every (stream, position)
    row maps onto the kernel grid: one weight fetch serves every stream,
    mirroring shared-weight CIM batching.  With ``model_idx`` ((B,) int32
    tenant ids, constant per ``bb`` slot block) ``w_t`` is a pooled
    (M, K, Cin, Cout) stack.
    """
    interpret = default_interpret() if interpret is None else interpret
    pooled = model_idx is not None
    b, l = x_bits.shape[:2]
    k, cin, cout = w_t.shape[-3:]
    l_out = (l + 2 * pad - k) // stride + 1
    bb = _conv.DEFAULT_BB if bb is None else bb
    pad_b, br, bn = _rows_blocks(b, l_out, cout, bb, pooled)

    xq = pack_activations(x_bits)  # (B, L, Cw)
    xq = jnp.pad(xq, ((0, pad_b), (pad, pad), (0, 0)))
    xs = _pad_axis(_conv_rows(xq, k, stride, l_out), br, -1)
    wp, wn = _rows_planes(w_t, bn)
    mi = _block_model_idx(model_idx, b, bb, pad_b) if pooled else None
    out = _conv.bnn_conv1d_step_packed(
        xs, wp, wn, mi, br=br, bn=bn, interpret=interpret,
    )
    return _rows_out(out, b, l_out, cout)


# ---------------------------------------------------------------------------
# Shard-safe batched entry points (mesh-wide slot pool)
# ---------------------------------------------------------------------------
#
# pallas_call is opaque to GSPMD: called on operands sharded over a mesh it
# would force an all-gather (or fail to partition).  The shard-safe entry
# points wrap the batched kernels in shard_map over the mesh's data axes,
# so each device runs the kernel on its *local* block of batch rows with
# the (replicated) weights — zero collectives, exactly the semantics of
# the slot pool where a stream's math never leaves its shard.


def _batch_spec(mesh):
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import dp_axes
    # a PartitionSpec entry takes a tuple of axis names directly
    return P(dp_axes(mesh)), P()


def _data_size(mesh) -> int:
    from repro.launch.mesh import dp_size
    return dp_size(mesh)


def _per_shard(fn, mesh, args, batched):
    """Run ``fn(*args)`` on each shard's local rows: ``batched[i]`` says
    whether ``args[i]`` is split over the data axes or replicated."""
    bspec, rep = _batch_spec(mesh)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(bspec if s else rep for s in batched),
        out_specs=bspec, check_vma=False,
    )(*args)


def bnn_conv1d_batched_sharded(
    x_bits: jax.Array,
    w_t: jax.Array,
    model_idx: jax.Array | None = None,
    *,
    mesh=None,
    stride: int = 1,
    pad: int = 0,
    bb: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``bnn_conv1d_batched`` with the batch axis sharded over ``mesh``.

    Each shard convolves its own rows; weights are replicated (pooled
    (M, ...) stacks replicate whole, like the single weight set).  With no
    mesh (or a 1-device mesh) this IS ``bnn_conv1d_batched`` — the
    single-device path stays byte-identical.
    """
    kw = dict(stride=stride, pad=pad, bb=bb, interpret=interpret)
    if mesh is None or _data_size(mesh) == 1:
        return bnn_conv1d_batched(x_bits, w_t, model_idx, **kw)
    if model_idx is not None:
        return _per_shard(
            lambda x, w, mi: bnn_conv1d_batched(x, w, mi, **kw), mesh,
            (x_bits, w_t, model_idx), (True, False, True))
    return _per_shard(lambda x, w: bnn_conv1d_batched(x, w, **kw), mesh,
                      (x_bits, w_t), (True, False))


def bitserial_conv1d_batched_sharded(
    x_u: jax.Array,
    w_t: jax.Array,
    model_idx: jax.Array | None = None,
    *,
    mesh=None,
    bits: int,
    offset: int = 0,
    stride: int = 1,
    pad: int = 0,
    bb: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``bitserial_conv1d_batched`` with the batch axis sharded over
    ``mesh`` (weights replicated, one launch per shard)."""
    kw = dict(bits=bits, offset=offset, stride=stride, pad=pad, bb=bb,
              interpret=interpret)
    if mesh is None or _data_size(mesh) == 1:
        return bitserial_conv1d_batched(x_u, w_t, model_idx, **kw)
    if model_idx is not None:
        return _per_shard(
            lambda x, w, mi: bitserial_conv1d_batched(x, w, mi, **kw), mesh,
            (x_u, w_t, model_idx), (True, False, True))
    return _per_shard(lambda x, w: bitserial_conv1d_batched(x, w, **kw),
                      mesh, (x_u, w_t), (True, False))


# ---------------------------------------------------------------------------
# Hop megakernel entry points (repro.stream fused hop)
# ---------------------------------------------------------------------------

def _mega_geoms(stages) -> tuple:
    """Kernel geometry for a plan's conv stages, checked against what the
    megakernel's layout takes: stride and multi-bit input in stage 0 only,
    and stage-0 offset codes inside int8."""
    geoms = tuple(_mega.stage_geom(st) for st in stages)
    for g in geoms[1:]:
        if g.stride != 1 or g.in_bits != 1:
            raise ValueError(
                "hop megakernel: only stage 0 may be strided or take "
                f"multi-bit input, got {g}")
    g0 = geoms[0]
    if g0.in_bits > 1:
        lo, hi = -g0.in_offset, (1 << g0.in_bits) - 1 - g0.in_offset
        if lo < -128 or hi > 127:
            raise ValueError(
                f"hop megakernel: stage-0 codes [{lo}, {hi}] exceed int8")
    return geoms


def _codes0(x, g):
    """Stage-0 input codes -> the values its GEMM multiplies: the code
    masked to ``in_bits`` (the bit planes telescope to it) minus the
    offset; binary input passes through."""
    x = jnp.asarray(x, jnp.int32)
    if g.in_bits > 1:
        x = jnp.bitwise_and(x, (1 << g.in_bits) - 1) - g.in_offset
    return x


def _phases(x, g, n: int):
    """(B, L, cin) stage-0 window -> (Q, B, stride * cin) stride phases
    for ``n`` outputs: row ``q`` holds samples ``q*s .. q*s + s - 1``, so
    tap group ``a`` of output ``j`` is row ``j + a`` (see
    ``_mega_weights``).  At least one row, so an empty flush still has a
    block."""
    a = -(-g.k // g.stride)
    q = max(n + a - 1, 1)
    need = q * g.stride
    x = jnp.pad(x, ((0, 0), (0, max(need - x.shape[1], 0)), (0, 0)))
    x = x[:, :need].reshape(x.shape[0], q, g.stride * g.cin)
    return jnp.swapaxes(x, 0, 1)


def _flush0(tail0, g):
    """Stage 0's flush window (its tail + the right pad, whose offset code
    is 0 after ``_codes0``) in stride phases."""
    win = jnp.concatenate(
        [_codes0(tail0, g),
         jnp.zeros((tail0.shape[0], g.pad, g.cin), jnp.int32)], axis=1)
    return _phases(win, g, g.flush_conv)


def _mega_weights(geoms, ws, thrs, flips, fc_ws, fc_thrs, fc_flips,
                  pooled: bool):
    """Weights in the kernel's dtypes: int8 tap groups (stage 0's ``k``
    taps regrouped into ``ceil(k / s)`` groups of ``s * cin`` rows, zero
    where a group runs past ``k``), (1, C) float32 thresholds and int32
    flips — each with a leading tenant axis when ``pooled``."""
    g0 = geoms[0]
    a0 = -(-g0.k // g0.stride)
    w0 = jnp.asarray(ws[0])
    lead = w0.shape[:-3]
    w0 = jnp.pad(w0, ((0, 0),) * len(lead)
                 + ((0, a0 * g0.stride - g0.k), (0, 0), (0, 0)))
    w0 = w0.reshape(*lead, a0, g0.stride * g0.cin, g0.cout)

    def _i8(w):
        return jnp.asarray(w).astype(jnp.int8)

    def _sa(x, dtype):
        x = jnp.asarray(x).astype(dtype)
        if pooled:  # (K, C) tenant stack -> (K, 1, C)
            return x.reshape(x.shape[0], 1, -1)
        return x.reshape(1, -1)

    return (
        tuple(_i8(w) for w in (w0, *ws[1:])),
        tuple(_sa(t, jnp.float32) for t in thrs),
        tuple(_sa(f, jnp.int32) for f in flips),
        tuple(_i8(w) for w in fc_ws),
        tuple(_sa(t, jnp.float32) for t in fc_thrs),
        tuple(_sa(f, jnp.int32) for f in fc_flips),
    )


def _mega_bb(b: int, bb: int | None, pooled: bool) -> int:
    """Slot block: the tenant block when pooled (blocks must stay
    single-tenant), else the default rounded to whole (8, 128) tiles."""
    if pooled:
        return min(bb, b)
    return min(_mega.DEFAULT_BB if bb is None else bb, _round_up(b, 8))


def _block_model_idx(model_idx, b, bb, pad_b):
    """(B,) per-slot tenant ids -> (B // bb, 1) per-block ids.

    Slot blocks are single-tenant by placement (the scheduler sorts slot
    blocks by tenant at pack time), so the block id is its first row's id;
    tail padding rows inherit the last real block's id harmlessly (their
    outputs are masked/sliced)."""
    mi = jnp.asarray(model_idx, jnp.int32)
    if pad_b:
        mi = jnp.pad(mi, ((0, pad_b),))
    return mi.reshape(-1, bb)[:, :1]


def _mega_state(tails, pendings, geoms, pad):
    """Stage > 0 tails and every pending carry, time-major (T, B, C) —
    zero-width state never enters the kernel."""
    t_in = tuple(jnp.swapaxes(pad(jnp.asarray(tails[i], jnp.int32)), 0, 1)
                 for i, g in enumerate(geoms) if i and g.tail)
    p_in = tuple(jnp.swapaxes(pad(jnp.asarray(p, jnp.int32)), 0, 1)
                 for p, g in zip(pendings, geoms) if g.phase)
    return t_in, p_in


def _slot_padder(pad_b: int):
    if not pad_b:
        return lambda x: x
    return lambda x: jnp.pad(x, ((0, pad_b),) + ((0, 0),) * (x.ndim - 1))


def hop_megakernel(
    audio: jax.Array,
    mask: jax.Array,
    tails: tuple[jax.Array, ...],
    pendings: tuple[jax.Array, ...],
    gap: jax.Array,
    ws: tuple[jax.Array, ...],
    thrs: tuple[jax.Array, ...],
    flips: tuple[jax.Array, ...],
    fc_ws: tuple[jax.Array, ...] = (),
    fc_thrs: tuple[jax.Array, ...] = (),
    fc_flips: tuple[jax.Array, ...] = (),
    model_idx: jax.Array | None = None,
    *,
    stages,
    emit: bool,
    fc_raw: tuple[bool, ...] = (),
    bb: int | None = None,
    interpret: bool | None = None,
):
    """One fused launch for a whole streaming hop (single device / shard).

    audio (B, hop, Cin0) codes; mask (B,) advance flags; tails/pendings
    one per conv stage (zero-width entries pass through untouched); gap
    (B, C) counts.  ``stages`` is the plan's ConvStage tuple.  With
    ``model_idx`` ((B,) int32 per-slot tenant ids, constant within each
    ``bb`` slot block) the weight operands are pooled (K, ...) stacks and
    the launch stays ONE dispatch regardless of K.  Returns
    ``(tails, pendings, gap)`` plus int32 logits when ``emit`` (the ghost
    flush + classifier ride in the SAME launch).  Bit-exact with the
    per-stage path — kernels/hop_megakernel.py is the contract.

    Layout work stays here, in XLA around the launch: stage 0's window
    (its tail + the hop) is built and cut into stride phases, its tail
    carry and mask merge happen here, and the other stages' state is
    transposed to the kernel's time-major layout and back.
    """
    interpret = default_interpret() if interpret is None else interpret
    pooled = model_idx is not None
    geoms = _mega_geoms(stages)
    g0 = geoms[0]
    b = gap.shape[0]
    bb = _mega_bb(b, bb, pooled)
    pad_b = _round_up(b, bb) - b
    pad = _slot_padder(pad_b)
    m = pad(jnp.asarray(mask, jnp.int32).reshape(b, 1))
    tail0 = pad(jnp.asarray(tails[0], jnp.int32))
    win0 = jnp.concatenate([tail0, pad(jnp.asarray(audio, jnp.int32))],
                           axis=1)
    tail0 = jnp.where(m[:, :, None] != 0,
                      win0[:, g0.n_conv * g0.stride :], tail0)
    t_in, p_in = _mega_state(tails, pendings, geoms, pad)
    out = _mega.hop_megakernel_packed(
        _phases(_codes0(win0, g0), g0, g0.n_conv), m, t_in, p_in,
        pad(jnp.asarray(gap, jnp.int32)),
        _flush0(tail0, g0) if emit else None,
        *_mega_weights(geoms, ws, thrs, flips, fc_ws, fc_thrs, fc_flips,
                       pooled),
        _block_model_idx(model_idx, b, bb, pad_b) if pooled else None,
        geoms=geoms, emit=emit, fc_raw=tuple(fc_raw), bb=bb,
        interpret=interpret,
    )
    tails_out = [tail0[:b]] + list(tails[1:])
    it = iter(out[0])
    for i, g in enumerate(geoms):
        if i and g.tail:
            tails_out[i] = jnp.swapaxes(next(it), 0, 1)[:b]
    pends_out = list(pendings)
    it = iter(out[1])
    for i, g in enumerate(geoms):
        if g.phase:
            pends_out[i] = jnp.swapaxes(next(it), 0, 1)[:b]
    res = (tuple(tails_out), tuple(pends_out), out[2][:b])
    return res + (out[3][:b],) if emit else res


def hop_megakernel_sharded(
    audio: jax.Array,
    mask: jax.Array,
    tails: tuple[jax.Array, ...],
    pendings: tuple[jax.Array, ...],
    gap: jax.Array,
    ws: tuple[jax.Array, ...],
    thrs: tuple[jax.Array, ...],
    flips: tuple[jax.Array, ...],
    fc_ws: tuple[jax.Array, ...] = (),
    fc_thrs: tuple[jax.Array, ...] = (),
    fc_flips: tuple[jax.Array, ...] = (),
    model_idx: jax.Array | None = None,
    *,
    mesh=None,
    stages,
    emit: bool,
    fc_raw: tuple[bool, ...] = (),
    bb: int | None = None,
    interpret: bool | None = None,
):
    """``hop_megakernel`` with per-slot state sharded over ``mesh``: each
    shard runs ONE fused launch on its local slot rows with replicated
    weights (the whole (K, ...) pool replicates exactly like the single
    weight set) — the per-hop dispatch count is 1 per shard, emit
    included, regardless of K."""
    kw = dict(stages=stages, emit=emit, fc_raw=fc_raw, bb=bb,
              interpret=interpret)
    if mesh is None or _data_size(mesh) == 1:
        return hop_megakernel(audio, mask, tails, pendings, gap, ws, thrs,
                              flips, fc_ws, fc_thrs, fc_flips, model_idx,
                              **kw)
    bspec, rep = _batch_spec(mesh)
    nt, npd, ns, nf = len(tails), len(pendings), len(ws), len(fc_ws)
    out_specs = ((bspec,) * nt, (bspec,) * npd, bspec)
    if emit:
        out_specs = out_specs + (bspec,)
    in_specs = (bspec, bspec, (bspec,) * nt, (bspec,) * npd, bspec,
                (rep,) * ns, (rep,) * ns, (rep,) * ns,
                (rep,) * nf, (rep,) * nf, (rep,) * nf)
    args = (audio, mask, tuple(tails), tuple(pendings), gap, tuple(ws),
            tuple(thrs), tuple(flips), tuple(fc_ws), tuple(fc_thrs),
            tuple(fc_flips))
    if model_idx is not None:
        in_specs, args = in_specs + (bspec,), args + (model_idx,)
    return jax.shard_map(
        lambda *a: hop_megakernel(*a, **kw), mesh=mesh, in_specs=in_specs,
        out_specs=out_specs, check_vma=False,
    )(*args)


def finalize_megakernel(
    tails: tuple[jax.Array, ...],
    pendings: tuple[jax.Array, ...],
    gap: jax.Array,
    ws: tuple[jax.Array, ...],
    thrs: tuple[jax.Array, ...],
    flips: tuple[jax.Array, ...],
    fc_ws: tuple[jax.Array, ...],
    fc_thrs: tuple[jax.Array, ...],
    fc_flips: tuple[jax.Array, ...],
    model_idx: jax.Array | None = None,
    *,
    stages,
    fc_raw: tuple[bool, ...],
    bb: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Standalone ghost-flush + classifier launch (hop-boundary peeks)."""
    interpret = default_interpret() if interpret is None else interpret
    pooled = model_idx is not None
    geoms = _mega_geoms(stages)
    b = gap.shape[0]
    bb = _mega_bb(b, bb, pooled)
    pad_b = _round_up(b, bb) - b
    pad = _slot_padder(pad_b)
    t_in, p_in = _mega_state(tails, pendings, geoms, pad)
    out = _mega.finalize_megakernel_packed(
        t_in, p_in, pad(jnp.asarray(gap, jnp.int32)),
        _flush0(pad(jnp.asarray(tails[0], jnp.int32)), geoms[0]),
        *_mega_weights(geoms, ws, thrs, flips, fc_ws, fc_thrs, fc_flips,
                       pooled),
        _block_model_idx(model_idx, b, bb, pad_b) if pooled else None,
        geoms=geoms, fc_raw=tuple(fc_raw), bb=bb, interpret=interpret,
    )
    return out[:b]


def finalize_megakernel_sharded(
    tails: tuple[jax.Array, ...],
    pendings: tuple[jax.Array, ...],
    gap: jax.Array,
    ws: tuple[jax.Array, ...],
    thrs: tuple[jax.Array, ...],
    flips: tuple[jax.Array, ...],
    fc_ws: tuple[jax.Array, ...],
    fc_thrs: tuple[jax.Array, ...],
    fc_flips: tuple[jax.Array, ...],
    model_idx: jax.Array | None = None,
    *,
    mesh=None,
    stages,
    fc_raw: tuple[bool, ...],
    bb: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``finalize_megakernel`` over a mesh-sharded slot pool."""
    kw = dict(stages=stages, fc_raw=fc_raw, bb=bb, interpret=interpret)
    if mesh is None or _data_size(mesh) == 1:
        return finalize_megakernel(tails, pendings, gap, ws, thrs, flips,
                                   fc_ws, fc_thrs, fc_flips, model_idx,
                                   **kw)
    bspec, rep = _batch_spec(mesh)
    nt, npd, ns, nf = len(tails), len(pendings), len(ws), len(fc_ws)
    in_specs = ((bspec,) * nt, (bspec,) * npd, bspec,
                (rep,) * ns, (rep,) * ns, (rep,) * ns,
                (rep,) * nf, (rep,) * nf, (rep,) * nf)
    args = (tuple(tails), tuple(pendings), gap, tuple(ws), tuple(thrs),
            tuple(flips), tuple(fc_ws), tuple(fc_thrs), tuple(fc_flips))
    if model_idx is not None:
        in_specs, args = in_specs + (bspec,), args + (model_idx,)
    return jax.shard_map(
        lambda *a: finalize_megakernel(*a, **kw), mesh=mesh,
        in_specs=in_specs, out_specs=bspec, check_vma=False,
    )(*args)


@jax.jit
def _gather_rows_keep(x: jax.Array, perm: jax.Array,
                      keep: jax.Array) -> jax.Array:
    out = jnp.take(x, perm, axis=0)
    k = keep.reshape(keep.shape + (1,) * (x.ndim - 1))
    return jnp.where(k, out, jnp.zeros_like(out))


def remap_slot_rows(
    x: jax.Array,
    perm: np.ndarray,
    keep: np.ndarray,
    *,
    mesh=None,
) -> jax.Array:
    """Permute the leading (slot) axis of one batched state array:
    ``out[i] = x[perm[i]]`` where ``keep[i]``, else a zero row.

    This is the device half of a cross-shard slot migration
    (``SlotPlacement.rebalance``): the per-slot ring state lives inside
    arrays the Pallas kernels consume, and ``pallas_call`` is opaque to
    GSPMD, so the row motion cannot ride inside a kernel — it runs as
    this standalone gather, where the partitioner is free to lower the
    cross-shard rows into collectives while vacated rows scrub to zero.
    With ``mesh`` the result is settled back onto the mesh's data-axis
    sharding so subsequent hops see the same layout as after a resize.
    """
    out = _gather_rows_keep(
        x, jnp.asarray(perm, jnp.int32), jnp.asarray(keep, bool)
    )
    if mesh is not None and _data_size(mesh) > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import dp_axes
        spec = P(dp_axes(mesh), *([None] * (out.ndim - 1)))
        out = jax.device_put(out, NamedSharding(mesh, spec))
    return out


def classifier_tail_sharded(
    gap: jax.Array,
    fc_ws: tuple[jax.Array, ...],
    fc_thrs: tuple[jax.Array, ...],
    fc_flips: tuple[jax.Array, ...],
    model_idx: jax.Array | None = None,
    *,
    mesh=None,
    out_raw: tuple[bool, ...],
    bb: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``classifier_tail`` over a mesh-sharded batch of GAP counts."""
    kw = dict(out_raw=out_raw, bb=bb, interpret=interpret)
    if mesh is None or _data_size(mesh) == 1:
        return classifier_tail(gap, fc_ws, fc_thrs, fc_flips, model_idx,
                               **kw)
    args = (gap, tuple(fc_ws), tuple(fc_thrs), tuple(fc_flips))
    if model_idx is not None:
        return _per_shard(
            lambda g, ws, ts, fs, mi: classifier_tail(g, ws, ts, fs, mi,
                                                      **kw),
            mesh, args + (model_idx,), (True, False, False, False, True))
    return _per_shard(
        lambda g, ws, ts, fs: classifier_tail(g, ws, ts, fs, **kw), mesh,
        args, (True, False, False, False))


# ---------------------------------------------------------------------------
# Fused classifier tail (repro.stream in-jit finalization)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("out_raw", "bb", "interpret"))
def classifier_tail(
    gap: jax.Array,
    fc_ws: tuple[jax.Array, ...],
    fc_thrs: tuple[jax.Array, ...],
    fc_flips: tuple[jax.Array, ...],
    model_idx: jax.Array | None = None,
    *,
    out_raw: tuple[bool, ...],
    bb: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """GAP counts -> raw logits: saturate at the 8-bit PWB ceiling, then the
    whole fc cascade fused in one kernel launch.

    gap (B, C) int32; fc_ws per-layer (Cin, Cout) ternary; fc_thrs/fc_flips
    per-layer (Cout,) SA params.  With ``model_idx`` ((B,) int32 tenant
    ids, constant per ``bb`` slot block) the fc params are pooled
    (M, ...) stacks.  Returns (B, n_classes) int32 raw logits — bit-exact
    with ``StreamState.logits`` (integer thresholds make the float32
    compare exact; the int8 GEMMs accumulate in int32).
    """
    interpret = default_interpret() if interpret is None else interpret
    pooled = model_idx is not None
    b = gap.shape[0]
    bb = _pick_block(b, _conv.DEFAULT_BB if bb is None else bb)
    gap_p = _pad_axis(gap.astype(jnp.int32), bb, 0)
    ws = tuple(w.astype(jnp.int8) for w in fc_ws)
    if pooled:
        thrs = tuple(
            t.astype(jnp.float32).reshape(t.shape[0], 1, -1)
            for t in fc_thrs
        )
        flips = tuple(
            f.astype(jnp.int32).reshape(f.shape[0], 1, -1) for f in fc_flips
        )
        mi = _block_model_idx(model_idx, b, bb, _round_up(b, bb) - b)
    else:
        thrs = tuple(t.astype(jnp.float32).reshape(1, -1) for t in fc_thrs)
        flips = tuple(f.astype(jnp.int32).reshape(1, -1) for f in fc_flips)
        mi = None
    out = _conv.classifier_tail_packed(
        gap_p, ws, thrs, flips, mi,
        out_raw=out_raw, bb=bb, interpret=interpret,
    )
    return out[:b]


# ---------------------------------------------------------------------------
# Dispatch heuristic: popcount (bandwidth) vs MXU (compute)
# ---------------------------------------------------------------------------

def pick_path(m: int, k: int, n: int) -> str:
    """Choose kernel path from arithmetic intensity on v5e constants.

    popcount path: bytes = m*k/8 + 2*k*n/8, "flops" = m*k*n VPU ops at
    ~4e12 ops/s effective; MXU path: bytes = m*k + k*n (int8),
    197e12/2 int8 macs/s.  Pick the lower predicted time.
    """
    t_pop = max((m * k / 8 + 2 * k * n / 8) / 819e9, (m * k * n) / 4e12)
    t_mxu = max((m * k + k * n) / 819e9, (m * k * n) / 98e12)
    return "popcount" if t_pop <= t_mxu else "mxu"


def _pick_block(dim: int, preferred: int, step: int = 1) -> int:
    """Largest block <= preferred that is a multiple of ``step`` and keeps
    padding overhead small; dim is padded up to a block multiple anyway."""
    b = min(preferred, max(step, _round_up(dim, step)))
    b = _round_up(b, step)
    return b


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
