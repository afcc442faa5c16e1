"""Pallas TPU kernel: binary 1-D convolution with fused SA + pooling (PWB).

Reproduces the PSCNN dataflow (paper §II-E, Fig. 4): the K-tap convolution is
computed as K *shifted* popcount GEMMs accumulated in VMEM — the digital twin
of "shift the IFM downward in the line buffer and activate wordline groups
alternately".  Because the accumulation covers the whole (Cin x K) receptive
field inside one grid cell, each cell emits *finished* activations in IFM
order, which is exactly what lets the paper bolt pooling onto the write-back
path (PWB, §II-H): here the max-pool (an OR on binary data) runs in-register
before the tile is written — the OFM tile that leaves the kernel is already
pooled, so the pooled layer costs zero extra HBM traffic.

Layouts (host side prepares these via ``ops.shifted_strided_views``):
  xs  : (K, L_out, Cw) uint32 — tap-shifted strided views, channel-packed
  wp  : (K, Cw, Cout) uint32  — positive plane per tap
  wn  : (K, Cw, Cout) uint32  — negative plane
  thr : (1, Cout) float32, flip : (1, Cout) int32

Grid: (L_out / bl, Cout / bn).  Output: (L_out / pool, Cout).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import dispatch

DEFAULT_BL = 512
DEFAULT_BN = 128


# ---------------------------------------------------------------------------
# Kernel-body helpers shared by every kernel module (values, not refs).
# Mosaic has no int32 x int32 matmul and no boolean truncation, so GEMMs run
# on int8 operands and the SA polarity flip is an integer xor.
# ---------------------------------------------------------------------------

def sa_bits(raw, thr, flip):
    """SA binarization -> int32 {0, 1}, executor-exact (integer thresholds
    keep the float32 compare knife-edge free); ``flip`` is int32 0/1."""
    ge = (raw.astype(jnp.float32) >= thr).astype(jnp.int32)
    return jnp.bitwise_xor(ge, flip)


def dot_i8(x, w):
    """(M, K) @ (K, N) on int8 operands, int32 accumulation.  Exact for
    operands inside int8: binary activations, ternary weights, offset
    codes of up to 8 bits."""
    return jax.lax.dot_general(
        x.astype(jnp.int8), w.astype(jnp.int8), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def dot_u8(h, w):
    """``dot_i8`` for activations in [0, 255] (saturated GAP counts): the
    low 7 bits and the top bit go through the MXU as two int8 GEMMs."""
    lo = dot_i8(jnp.bitwise_and(h, 127), w)
    hi = dot_i8(jnp.right_shift(h, 7), w)
    return lo + hi * 128


def classifier_val(gap, fc_params, out_raw: tuple[bool, ...]):
    """GAP counts (bb, C) -> raw logits through the fc cascade.
    ``fc_params`` is ``(w, [thr, flip])`` per layer, SA params only for
    the layers that binarize."""
    # 8-bit PWB counter ceiling (executor: gap counts saturate at 255)
    h = jnp.minimum(gap, 255)
    idx = 0
    for j, raw_out in enumerate(out_raw):
        w = fc_params[idx]
        idx += 1
        # the first fc reads 8-bit counts, the later ones binary SA bits
        raw = dot_u8(h, w) if j == 0 else dot_i8(h, w)
        if raw_out:
            h = raw
        else:
            h = sa_bits(raw, fc_params[idx], fc_params[idx + 1])
            idx += 2
    return h


def _conv_tile(xs, wp, wn, k: int, cw: int):
    """Accumulate K shifted popcount GEMM taps -> (bl, bn) int32."""
    bl = xs.shape[1]
    acc = jnp.zeros((bl, wp.shape[2]), jnp.int32)
    for tap in range(k):
        for c in range(cw):
            xa = xs[tap, :, c : c + 1]  # (bl, 1)
            p = jax.lax.population_count(
                jnp.bitwise_and(xa, wp[tap, c : c + 1, :]))
            n = jax.lax.population_count(
                jnp.bitwise_and(xa, wn[tap, c : c + 1, :]))
            acc = acc + p.astype(jnp.int32) - n.astype(jnp.int32)
    return acc


def _kernel(
    xs_ref, wp_ref, wn_ref, thr_ref, flip_ref, o_ref, *, k: int, cw: int, pool: int
):
    diff = _conv_tile(xs_ref[...], wp_ref[...], wn_ref[...], k, cw)
    y = sa_bits(diff, thr_ref[...], flip_ref[...]).astype(jnp.uint32)
    if pool > 1:
        bl, bn = y.shape
        # PWB: OR-reduce the window before write-back (binary max-pool).
        y = jnp.max(y.reshape(bl // pool, pool, bn), axis=1)
    o_ref[...] = y


def _kernel_raw(xs_ref, wp_ref, wn_ref, o_ref, *, k: int, cw: int):
    o_ref[...] = _conv_tile(xs_ref[...], wp_ref[...], wn_ref[...], k, cw)


@functools.partial(
    jax.jit, static_argnames=("pool", "bl", "bn", "mode", "interpret")
)
def bnn_conv1d_packed(
    xs: jax.Array,
    wp: jax.Array,
    wn: jax.Array,
    thr: jax.Array | None = None,
    flip: jax.Array | None = None,
    *,
    pool: int = 1,
    bl: int = DEFAULT_BL,
    bn: int = DEFAULT_BN,
    mode: str = "sa",
    interpret: bool,
) -> jax.Array:
    """Fused conv1d -> SA -> pool on pre-shifted packed views.

    L_out must divide into bl blocks and bl into pool windows (pad L_out with
    dead positions first; they pool to whatever the pad computes and are
    sliced off by the caller).
    """
    k, l_out, cw = xs.shape
    k2, cw2, n = wp.shape
    assert k == k2 and cw == cw2 and wn.shape == wp.shape
    bl = min(bl, l_out)
    bn = min(bn, n)
    assert l_out % bl == 0 and n % bn == 0, (l_out, bl, n, bn)
    assert bl % pool == 0, (bl, pool)
    grid = (l_out // bl, n // bn)

    xs_spec = pl.BlockSpec((k, bl, cw), lambda i, j: (0, i, 0))
    w_spec = pl.BlockSpec((k, cw, bn), lambda i, j: (0, 0, j))
    v_spec = pl.BlockSpec((1, bn), lambda i, j: (0, j))

    if mode == "sa":
        assert thr is not None and flip is not None
        o_spec = pl.BlockSpec((bl // pool, bn), lambda i, j: (i, j))
        return dispatch.pallas_call(
            functools.partial(_kernel, k=k, cw=cw, pool=pool),
            grid=grid,
            in_specs=[xs_spec, w_spec, w_spec, v_spec, v_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((l_out // pool, n), jnp.uint32),
            interpret=interpret,
        )(xs, wp, wn, thr.reshape(1, n), flip.astype(jnp.int32).reshape(1, n))
    elif mode == "raw":
        assert pool == 1, "raw mode has no SA output to pool"
        o_spec = pl.BlockSpec((bl, bn), lambda i, j: (i, j))
        return dispatch.pallas_call(
            functools.partial(_kernel_raw, k=k, cw=cw),
            grid=grid,
            in_specs=[xs_spec, w_spec, w_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((l_out, n), jnp.int32),
            interpret=interpret,
        )(xs, wp, wn)
    raise ValueError(f"mode {mode!r}")


# ---------------------------------------------------------------------------
# Batched multi-stream step (repro.stream): one CIM macro, many users.
#
# The streaming scheduler packs B concurrent audio streams onto a shared
# batch axis; the ternary weight planes are broadcast across it — exactly the
# "weights stay resident in the macro, activations stream past" economics of
# the silicon, so the batch dimension rides free through the Pallas grid
# (one extra grid axis, zero extra weight traffic).
#
# Layout: the (stream, position) rows ride the LANE axis and output
# channels the sublanes — xs (K, Cw, R) packed words, planes (K, Cout, Cw),
# output (Cout, R) — so a narrow packed-channel axis (Cw = 1 for the
# one-channel audio input) never pads a tile, and each word is a lane-dense
# row broadcast against a column of weight words.  ``ops`` builds the rows
# (R = B * L_out) and transposes the result back.
#
# Shard-safety contract: pallas_call is opaque to GSPMD, so these kernels
# must never see a mesh-sharded operand directly.  Under the mesh-wide slot
# pool each device invokes the kernel on its LOCAL block of batch rows via
# the shard_map entry points (ops.bnn_conv1d_batched_sharded /
# ops.classifier_tail_sharded); per-shard batches can be as small as one
# row, which the ops-layer entry points absorb by padding.
# ---------------------------------------------------------------------------

DEFAULT_BB = 8      # slot block of the classifier tail / tenant block
DEFAULT_BR = 512    # lane block of (stream, position) rows


def _popcount_taps(xs_ref, wp, wn, plane=()):
    """Sum K taps x Cw words of popcount diffs -> (bn, br) int32.

    xs_ref[(*plane, tap)] is (Cw, br) packed activation words (rows on
    lanes); wp/wn are (K, bn, Cw) weight planes (channels on sublanes).
    """
    k, cw = xs_ref.shape[-3], xs_ref.shape[-2]
    acc = None
    for tap in range(k):
        x = xs_ref[(*plane, tap)]
        for c in range(cw):
            xa = x[c : c + 1, :]  # (1, br)
            p = jax.lax.population_count(
                jnp.bitwise_and(xa, wp[tap][:, c : c + 1]))
            n = jax.lax.population_count(
                jnp.bitwise_and(xa, wn[tap][:, c : c + 1]))
            d = p.astype(jnp.int32) - n.astype(jnp.int32)
            acc = d if acc is None else acc + d
    return acc


def _planes(refs, pooled: bool):
    """(wp, wn) of this grid cell; pooled stacks gather the block's tenant
    row once per cell (slot blocks are single-tenant by placement)."""
    wp, wn = refs[1][...], refs[2][...]
    if pooled:
        midx = refs[3][0, 0]
        wp = jax.lax.dynamic_index_in_dim(wp, midx, 0, keepdims=False)
        wn = jax.lax.dynamic_index_in_dim(wn, midx, 0, keepdims=False)
    return wp, wn


def _batched_kernel_raw(*refs, pooled: bool = False):
    """refs = xs, wp, wn, [model (pooled),] out."""
    wp, wn = _planes(refs, pooled)
    refs[-1][...] = _popcount_taps(refs[0], wp, wn)


def _batched_kernel_bitserial(*refs, pooled: bool = False):
    """refs = xs (bits, K, Cw, br), wp, wn, [model (pooled),] out."""
    wp, wn = _planes(refs, pooled)
    acc = None
    for b in range(refs[0].shape[0]):
        d = _popcount_taps(refs[0], wp, wn, plane=(b,)) * (1 << b)
        acc = d if acc is None else acc + d
    refs[-1][...] = acc


def _rows_call(kernel, xs, wp, wn, model_idx, br: int, bn: int,
               interpret: bool):
    """Grid (R / br, Cout / bn) over a rows-on-lanes popcount kernel."""
    pooled = model_idx is not None
    r = xs.shape[-1]
    n = wp.shape[-2]
    assert r % br == 0 and n % bn == 0, (r, br, n, bn)
    lead = xs.ndim - 1
    xs_spec = pl.BlockSpec(
        xs.shape[:-1] + (br,), lambda i, j: (0,) * lead + (i,))
    wl = wp.ndim - 2
    w_spec = pl.BlockSpec(
        wp.shape[:-2] + (bn, wp.shape[-1]), lambda i, j: (0,) * wl + (j, 0))
    in_specs = [xs_spec, w_spec, w_spec]
    args = [xs, wp, wn]
    if pooled:
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j: (i, 0)))
        args.append(model_idx.astype(jnp.int32))
    return dispatch.pallas_call(
        functools.partial(kernel, pooled=pooled),
        grid=(r // br, n // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, br), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((n, r), jnp.int32),
        interpret=interpret,
    )(*args)


def bnn_conv1d_step_packed(
    xs: jax.Array,
    wp: jax.Array,
    wn: jax.Array,
    model_idx: jax.Array | None = None,
    *,
    br: int,
    bn: int,
    interpret: bool,
) -> jax.Array:
    """Batched raw conv step on pre-shifted packed rows.

    xs : (K, Cw, R) uint32 — tap-shifted packed words, one lane per
        (stream, position) row
    wp/wn : (K, Cout, Cw) uint32 — shared across the rows; with
        ``model_idx`` (``(R // br, 1)`` int32, one tenant per row block)
        a pooled (M, K, Cout, Cw) stack, gathered per grid cell
    Output: (Cout, R) int32 raw popcount diff.  Not jitted itself: each
    call from a traced wrapper is one launch the dispatch counter sees.
    """
    return _rows_call(_batched_kernel_raw, xs, wp, wn, model_idx, br, bn,
                      interpret)


# ---------------------------------------------------------------------------
# Fused classifier tail (repro.stream in-jit finalization).
#
# The GAP counters plus the fc cascade are the model's "answer now" path:
# saturate the 8-bit PWB counts, run every fc layer, emit raw logits.  On
# silicon this is one drain of the PWB counters through the macro; here it
# is one kernel so the streaming scheduler's per-hop finalization never
# leaves the device — each grid cell loads the (tiny) fc weight stack once
# and finishes ``bb`` streams end to end.
# ---------------------------------------------------------------------------


def _tail_kernel(*refs, out_raw: tuple[bool, ...], pooled: bool = False):
    """refs = [gap, [model (pooled),] (w, [thr, flip])* , out].  One cell:
    bb streams.  ``pooled``: fc params carry a leading tenant axis,
    gathered once per cell."""
    gap_ref, o_ref = refs[0], refs[-1]
    params = refs[1:-1]
    if pooled:
        midx = params[0][0, 0]
        params = [
            jax.lax.dynamic_index_in_dim(r[...], midx, 0, keepdims=False)
            for r in params[1:]
        ]
    else:
        params = [r[...] for r in params]
    o_ref[...] = classifier_val(gap_ref[...], params, out_raw)


@functools.partial(jax.jit, static_argnames=("out_raw", "bb", "interpret"))
def classifier_tail_packed(
    gap: jax.Array,
    fc_ws: tuple[jax.Array, ...],
    fc_thrs: tuple[jax.Array, ...],
    fc_flips: tuple[jax.Array, ...],
    model_idx: jax.Array | None = None,
    *,
    out_raw: tuple[bool, ...],
    bb: int = DEFAULT_BB,
    interpret: bool,
) -> jax.Array:
    """Saturate GAP counts and run the whole fc cascade in one kernel.

    gap : (B, C) int32 GAP counts (possibly already clamped; idempotent)
    fc_ws : per-fc (Cin, Cout) int8 ternary weights — or pooled
        (M, Cin, Cout) stacks when ``model_idx`` (``(B // bb, 1)`` int32,
        one tenant per slot block) is given
    fc_thrs/fc_flips : per-fc (1, Cout) (pooled: (M, 1, Cout)) float32 /
        int32 SA params (entries for ``out_raw`` layers present but unused)
    Output: (B, n_classes) int32 raw logits.
    """
    pooled = model_idx is not None
    b, c = gap.shape
    n_fc = len(fc_ws)
    assert n_fc and b % bb == 0, (b, bb, n_fc)
    assert fc_ws[0].shape[-2] == c

    grid = (b // bb,)
    in_specs = [pl.BlockSpec((bb, c), lambda s: (s, 0))]
    args = [gap]
    if pooled:
        in_specs.append(pl.BlockSpec((1, 1), lambda s: (s, 0)))
        args.append(model_idx.astype(jnp.int32))

    def _rep_spec(x):
        nd = x.ndim
        return pl.BlockSpec(x.shape, lambda s, _n=nd: (0,) * _n)

    for j, w in enumerate(fc_ws):
        in_specs.append(_rep_spec(w))
        args.append(w)
        if not out_raw[j]:
            in_specs.append(_rep_spec(fc_thrs[j]))
            in_specs.append(_rep_spec(fc_flips[j]))
            args.extend([fc_thrs[j], fc_flips[j]])
    n_out = fc_ws[-1].shape[-1]
    return dispatch.pallas_call(
        functools.partial(_tail_kernel, out_raw=out_raw, pooled=pooled),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, n_out), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_out), jnp.int32),
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# Bit-serial batched conv (multi-bit first layer) — ONE kernel launch.
#
# The first layer consumes 8-bit offset-binary audio.  The original path
# dispatched one raw-conv kernel per bit plane and accumulated the `<< b`
# partials in HBM between launches; here the plane loop moves INSIDE the
# kernel (paper §II-F: the macro serializes input bits over cycles, not
# over kernel launches), so the weights load into VMEM once and the
# accumulator never leaves the grid cell.  The offset fold (subtracting
# ``offset * sum(w)``) stays host-side in ops.bitserial_conv1d*, as before.
# ---------------------------------------------------------------------------


def bnn_bitserial_step_packed(
    xs: jax.Array,
    wp: jax.Array,
    wn: jax.Array,
    model_idx: jax.Array | None = None,
    *,
    br: int,
    bn: int,
    interpret: bool,
) -> jax.Array:
    """Batched bit-serial raw conv on pre-shifted per-plane packed rows.

    xs : (bits, K, Cw, R) uint32; wp/wn : (K, Cout, Cw) uint32 shared
    across rows AND planes (the whole point: one weight fetch for all
    ``bits`` passes) — or pooled (M, K, Cout, Cw) stacks when
    ``model_idx`` (``(R // br, 1)`` int32, one tenant per row block) is
    given.  Output: (Cout, R) int32 raw popcount diff already accumulated
    over planes (offset NOT yet folded).
    """
    return _rows_call(_batched_kernel_bitserial, xs, wp, wn, model_idx, br,
                      bn, interpret)
