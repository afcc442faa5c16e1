"""Pallas megakernel: one launch per streaming hop.

The per-stage streaming path (scheduler ``backend="pallas"``) issues one
``pallas_call`` per conv stage per hop — plus the whole cascade again for
the ghost flush on emit hops, plus the classifier tail — bouncing every
intermediate feature map through HBM between launches.  This module fuses
the entire hop into ONE kernel:

  * the bit-serial first layer: its ``2^b`` code planes telescope back to
    the offset code (``sum_b plane_b << b``), so one shared-tap GEMM on the
    code replaces ``in_bits`` passes;
  * SA binarization, max-pool with the steady pool phase, receptive-field
    tail carry and pending-frame carry for every stage;
  * GAP accumulation saturated at the 8-bit PWB ceiling;
  * the masked-slot merge (rows whose stream had no full hop keep their
    previous state bit-for-bit);
  * on ``emit`` hops, the ghost end-of-stream flush AND the fc classifier
    tail run in the same launch on the merged state, so an emit hop is
    still a single dispatch.

Intermediate feature maps never leave the kernel: only the hop's input and
the updated slot state (tails / pendings / GAP, plus logits on emit) touch
HBM — the paper's ping-pong feature SRAM (§II-C), with Mosaic placing the
stage-to-stage values in VMEM.

Layout (what Mosaic needs, prepared by ``ops.hop_megakernel``):

  * per-slot state is **time-major**: a stage's tail is ``(tail, B, cin)``
    and its pending frames ``(phase, B, cout)``, so one frame is a 2-D
    ``(bb, C)`` tile (slots on sublanes, channels on lanes) and every tap
    slice, pool window and tail carry is a list slice of whole tiles;
  * stage 0's window arrives as **stride phases** ``(Q, B, s * cin)``:
    row ``q`` holds samples ``q*s .. q*s + s - 1``, and the taps are
    regrouped into ``ceil(k / s)`` unit-stride groups, so the strided
    first layer is ``ceil(k / s)`` dense GEMMs.  Its codes arrive already
    offset (``x - in_offset``, masked to ``in_bits``); stage 0's own tail
    is carried by the wrapper, which builds the window;
  * every GEMM runs on int8 operands with int32 accumulation — exact, since
    ternary weights, binary activations and offset 8-bit codes all fit
    int8; the classifier's 0..255 GAP counts split into two int8 halves.

Grid: ``(B / bb,)`` over slot blocks — weights/thresholds are replicated
per grid cell (one weight fetch serves every stream, the shared-weight CIM
batching economics), per-slot state is block-mapped.

Shard-safety: ``pallas_call`` is GSPMD-opaque, so this kernel must never
see a mesh-sharded operand — the mesh-wide slot pool enters through the
shard_map wrappers ``ops.hop_megakernel_sharded`` /
``ops.finalize_megakernel_sharded``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import dispatch
from repro.kernels.bnn_conv1d import classifier_val, dot_i8, sa_bits

# slot-block size: big blocks amortize the weight fetch and keep the grid
# short (a whole 256-slot shard is one grid cell; at width 64 that cell
# fits Mosaic's default 16 MiB VMEM scope)
DEFAULT_BB = 256


@dataclasses.dataclass(frozen=True)
class StageGeom:
    """One conv stage's static geometry — the subset of the stream plan's
    ``ConvStage`` the kernel needs, duplicated here so the kernel layer
    never imports the stream runtime (hashable => usable as a jit static
    argument)."""

    k: int
    stride: int
    pad: int
    pool: int
    cin: int
    cout: int
    in_bits: int
    in_offset: int
    tail: int
    phase: int
    n_conv: int
    n_out: int
    flush_in: int
    flush_conv: int
    flush_out: int


def stage_geom(st) -> StageGeom:
    """Build a :class:`StageGeom` from anything with ConvStage's fields."""
    return StageGeom(
        k=st.k, stride=st.stride, pad=st.pad, pool=st.pool, cin=st.cin,
        cout=st.cout, in_bits=st.in_bits, in_offset=st.in_offset,
        tail=st.tail, phase=st.phase, n_conv=st.n_conv, n_out=st.n_out,
        flush_in=st.flush_in, flush_conv=st.flush_conv,
        flush_out=st.flush_out,
    )


# ---------------------------------------------------------------------------
# Kernel-body math (pure value helpers, shared by hop and finalize modes).
# A "frame list" is a python list of (bb, C) int32 tiles, one per position.
# ---------------------------------------------------------------------------

def _conv_frames(w, thr, flip, win, n: int, bb: int):
    """Conv + SA over a frame list: ``w`` holds the (A, C, Cout) tap
    groups, group ``a`` reads frames ``a .. a + n - 1``.  Returns ``n``
    binarized (bb, Cout) frames."""
    acc = None
    for a in range(w.shape[0]):
        lhs = win[a] if n == 1 else jnp.concatenate(win[a : a + n], axis=0)
        d = dot_i8(lhs, w[a])
        acc = d if acc is None else acc + d
    y = sa_bits(acc, thr, flip)
    return [y[j * bb : (j + 1) * bb] for j in range(n)]


def _pool_frames(frames, n_out: int, pool: int):
    """Max-pool a frame list (drop-remainder windows, ref_maxpool1d)."""
    return [
        functools.reduce(jnp.maximum, frames[j * pool : (j + 1) * pool])
        for j in range(n_out)
    ]


def _gap_add(gap, frames):
    if not frames:
        return gap
    return jnp.minimum(gap + functools.reduce(jnp.add, frames), 255)


def _steady_cascade(geoms, x0, tails, pends, ws, thrs, flips, bb):
    """The per-hop conv cascade on one slot block.  ``x0`` is stage 0's
    stride-phase window; returns the final stage's pooled frames plus the
    carried tails/pendings of stages 1.. (stage 0's tail is the
    wrapper's)."""
    new_tails, new_pends = [[]], []
    cur = None
    for i, g in enumerate(geoms):
        win = x0 if i == 0 else tails[i] + cur
        if i:
            new_tails.append(win[g.n_conv :])
        y = _conv_frames(ws[i], thrs[i], flips[i], win, g.n_conv, bb)
        frames = pends[i] + y
        if g.pool > 1:
            cur = _pool_frames(frames, g.n_out, g.pool)
            new_pends.append(frames[g.n_out * g.pool :])
        else:
            cur = y
            new_pends.append(pends[i])
    return cur, new_tails, new_pends


def _flush_cascade(geoms, f0, tails, pends, gap, ws, thrs, flips, bb):
    """Ghost end-of-stream flush from (merged) steady state -> saturated
    GAP counts, mirror of ``_BatchedModel._finalize``.  ``f0`` is stage
    0's flush window (its tail + right pad) in stride phases."""
    cur = []
    for i, g in enumerate(geoms):
        if i == 0:
            win = f0
        else:
            win = tails[i] + (cur if g.flush_in else [])
            if g.pad:
                win = win + [jnp.zeros((bb, g.cin), jnp.int32)] * g.pad
        y = (
            _conv_frames(ws[i], thrs[i], flips[i], win, g.flush_conv, bb)
            if g.flush_conv > 0 else []
        )
        cur = _pool_frames(pends[i] + y, g.flush_out, g.pool)
    return _gap_add(gap, cur)


# ---------------------------------------------------------------------------
# The kernel: one grid cell == one slot block through the whole hop
# ---------------------------------------------------------------------------

def _n_fc_params(fc_raw: tuple[bool, ...]) -> int:
    return sum(1 if r else 3 for r in fc_raw)


def _frames(ref):
    """Time-major (T, bb, C) block -> frame list."""
    return [ref[t] for t in range(ref.shape[0])]


def _megakernel(
    *refs, geoms: tuple[StageGeom, ...], emit: bool, finalize_only: bool,
    fc_raw: tuple[bool, ...], pooled: bool = False,
):
    """refs = [x0, mask,] tails(stage>0, tail>0)*, pends(phase>0)*, gap,
    [f0 (emit/finalize),] [model (pooled),] (w, thr, flip) per stage,
    fc params (emit/finalize) | outputs.  Outputs: tails*, pends*, gap
    [, logits] (finalize: logits only).

    ``pooled``: every weight/threshold operand carries a leading tenant
    axis ``(K, ...)`` and a per-block ``(1, 1)`` int32 model index follows
    ``f0`` — the block's weight planes are gathered out of the pool ONCE
    per grid cell (each slot block is single-tenant by placement), so the
    pool costs one dynamic row index, not K-way compute.
    """
    ns = len(geoms)
    n_tail = sum(1 for i, g in enumerate(geoms) if i and g.tail)
    n_pend = sum(1 for g in geoms if g.phase)
    with_fc = emit or finalize_only
    refs = list(refs)
    if not finalize_only:
        x0_ref, mask_ref = refs.pop(0), refs.pop(0)
    tail_refs = [refs.pop(0) for _ in range(n_tail)]
    pend_refs = [refs.pop(0) for _ in range(n_pend)]
    gap_ref = refs.pop(0)
    f0_ref = refs.pop(0) if with_fc else None
    model_ref = refs.pop(0) if pooled else None
    stage_refs = [refs.pop(0) for _ in range(3 * ns)]
    fc_refs = [refs.pop(0) for _ in range(_n_fc_params(fc_raw) if with_fc
                                          else 0)]
    out_refs = refs

    bb = gap_ref.shape[0]
    gap = gap_ref[...]
    tails, pends = [[]], []
    ti = pi = 0
    for i, g in enumerate(geoms):
        if i:
            tails.append(_frames(tail_refs[ti]) if g.tail else [])
            ti += bool(g.tail)
        pends.append(_frames(pend_refs[pi]) if g.phase else [])
        pi += bool(g.phase)
    ws = [stage_refs[3 * i][...] for i in range(ns)]
    thrs = [stage_refs[3 * i + 1][...] for i in range(ns)]
    flips = [stage_refs[3 * i + 2][...] for i in range(ns)]
    fc_params = [r[...] for r in fc_refs]
    if pooled:
        midx = model_ref[0, 0]

        def sel(x):
            return jax.lax.dynamic_index_in_dim(x, midx, 0, keepdims=False)

        ws = [sel(w) for w in ws]
        thrs = [sel(t) for t in thrs]
        flips = [sel(f) for f in flips]
        fc_params = [sel(p) for p in fc_params]

    if finalize_only:
        gap_f = _flush_cascade(geoms, _frames(f0_ref), tails, pends, gap,
                               ws, thrs, flips, bb)
        out_refs[0][...] = classifier_val(gap_f, fc_params, fc_raw)
        return

    cur, new_tails, new_pends = _steady_cascade(
        geoms, _frames(x0_ref), tails, pends, ws, thrs, flips, bb
    )
    gap2 = _gap_add(gap, cur)

    # masked-slot merge in-kernel: rows whose stream had no full hop keep
    # their previous state bit-for-bit; the flush below runs on the MERGED
    # state so every primed slot's logits stay valid (scheduler contract)
    m = mask_ref[...] != 0  # (bb, 1)

    def merge(new, old):
        return [jnp.where(m, a, b) for a, b in zip(new, old)]

    merged_tails = [merge(nt, t) for nt, t in zip(new_tails, tails)]
    merged_pends = [merge(np_, p) for np_, p in zip(new_pends, pends)]
    merged_gap = jnp.where(m, gap2, gap)

    oi = 0
    for i, (g, t) in enumerate(zip(geoms, merged_tails)):
        if i and g.tail:
            for j, v in enumerate(t):
                out_refs[oi][j] = v
            oi += 1
    for g, p in zip(geoms, merged_pends):
        if g.phase:
            for j, v in enumerate(p):
                out_refs[oi][j] = v
            oi += 1
    out_refs[oi][...] = merged_gap
    oi += 1
    if emit:
        gap_f = _flush_cascade(
            geoms, _frames(f0_ref), merged_tails, merged_pends, merged_gap,
            ws, thrs, flips, bb,
        )
        out_refs[oi][...] = classifier_val(gap_f, fc_params, fc_raw)


# ---------------------------------------------------------------------------
# Packed entry points (ops.py prepares the layout, pads and shard_maps)
# ---------------------------------------------------------------------------

def _state_spec(shape, bb):
    """Block over the slot axis: axis 1 of a time-major (T, B, C) state
    array, axis 0 of a (B, C) one."""
    if len(shape) == 3:
        return pl.BlockSpec((shape[0], bb, shape[2]), lambda s: (0, s, 0))
    return pl.BlockSpec((bb,) + tuple(shape[1:]),
                        lambda s, _n=len(shape): (s,) + (0,) * (_n - 1))


def _rep_spec(shape):
    return pl.BlockSpec(tuple(shape), lambda s, _n=len(shape): (0,) * _n)


def _params(specs, args, ws, thrs, flips, fc_ws, fc_thrs, fc_flips, fc_raw):
    for w, t, f in zip(ws, thrs, flips):
        for x in (w, t, f):
            specs.append(_rep_spec(x.shape))
            args.append(x)
    for j, raw_out in enumerate(fc_raw):
        group = (fc_ws[j],) if raw_out else (fc_ws[j], fc_thrs[j],
                                             fc_flips[j])
        for x in group:
            specs.append(_rep_spec(x.shape))
            args.append(x)


def _n_logits(fc_ws, fc_raw, geoms):
    # shape[-1] so a pooled (K, cin, cout) stack reads the same as (cin, cout)
    return fc_ws[-1].shape[-1] if fc_raw else geoms[-1].cout


def _call(kernel, grid, specs, args, out_shapes, bb, interpret):
    return dispatch.pallas_call(
        kernel,
        grid=grid,
        in_specs=specs,
        out_specs=[_state_spec(s.shape, bb) for s in out_shapes],
        out_shape=tuple(out_shapes),
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=("geoms", "emit", "fc_raw", "bb", "interpret"),
)
def hop_megakernel_packed(
    x0: jax.Array,
    mask: jax.Array,
    tails: tuple[jax.Array, ...],
    pendings: tuple[jax.Array, ...],
    gap: jax.Array,
    f0: jax.Array | None,
    ws: tuple[jax.Array, ...],
    thrs: tuple[jax.Array, ...],
    flips: tuple[jax.Array, ...],
    fc_ws: tuple[jax.Array, ...],
    fc_thrs: tuple[jax.Array, ...],
    fc_flips: tuple[jax.Array, ...],
    model_idx: jax.Array | None = None,
    *,
    geoms: tuple[StageGeom, ...],
    emit: bool,
    fc_raw: tuple[bool, ...],
    bb: int,
    interpret: bool,
):
    """One fused hop over a slot-block grid, in the kernel layout (module
    docstring): ``x0`` (Q, B, s*cin0) stage-0 stride phases, ``mask``
    (B, 1), ``tails`` one time-major entry per stage > 0 with ``tail > 0``,
    ``pendings`` one per stage with ``phase > 0``, ``gap`` (B, C), ``f0``
    stage 0's flush window (emit only), int8 tap groups ``ws``.  B must
    divide into ``bb`` blocks (the ops wrapper pads).  ``model_idx``
    (``(B // bb, 1)`` int32, one tenant per slot block) switches every
    weight operand to a pooled ``(K, ...)`` stack — same grid, same single
    launch.  Returns ``(tails, pendings, gap[, logits])``.
    """
    b = gap.shape[0]
    assert b % bb == 0, (b, bb)
    pooled = model_idx is not None
    specs: list = []
    args: list = []
    for x in (x0, mask, *tails, *pendings, gap) + ((f0,) if emit else ()):
        specs.append(_state_spec(x.shape, bb))
        args.append(x)
    if pooled:
        specs.append(pl.BlockSpec((1, 1), lambda s: (s, 0)))
        args.append(model_idx)
    _params(specs, args, ws, thrs, flips, fc_ws, fc_thrs, fc_flips,
            fc_raw if emit else ())
    out_shapes = [jax.ShapeDtypeStruct(x.shape, jnp.int32)
                  for x in (*tails, *pendings, gap)]
    if emit:
        out_shapes.append(jax.ShapeDtypeStruct(
            (b, _n_logits(fc_ws, fc_raw, geoms)), jnp.int32))
    out = _call(
        functools.partial(
            _megakernel, geoms=geoms, emit=emit, finalize_only=False,
            fc_raw=fc_raw if emit else (), pooled=pooled,
        ),
        (b // bb,), specs, args, out_shapes, bb, interpret,
    )
    nt, npend = len(tails), len(pendings)
    tails_out = out[:nt]
    pends_out = out[nt : nt + npend]
    gap_out = out[nt + npend]
    if emit:
        return tails_out, pends_out, gap_out, out[nt + npend + 1]
    return tails_out, pends_out, gap_out


@functools.partial(
    jax.jit, static_argnames=("geoms", "fc_raw", "bb", "interpret")
)
def finalize_megakernel_packed(
    tails: tuple[jax.Array, ...],
    pendings: tuple[jax.Array, ...],
    gap: jax.Array,
    f0: jax.Array,
    ws: tuple[jax.Array, ...],
    thrs: tuple[jax.Array, ...],
    flips: tuple[jax.Array, ...],
    fc_ws: tuple[jax.Array, ...],
    fc_thrs: tuple[jax.Array, ...],
    fc_flips: tuple[jax.Array, ...],
    model_idx: jax.Array | None = None,
    *,
    geoms: tuple[StageGeom, ...],
    fc_raw: tuple[bool, ...],
    bb: int,
    interpret: bool,
) -> jax.Array:
    """Ghost flush + classifier tail alone (hop-boundary peeks): one
    launch from resident state to logits, same layout as the hop."""
    b = gap.shape[0]
    assert b % bb == 0, (b, bb)
    pooled = model_idx is not None
    specs: list = []
    args: list = []
    for x in (*tails, *pendings, gap, f0):
        specs.append(_state_spec(x.shape, bb))
        args.append(x)
    if pooled:
        specs.append(pl.BlockSpec((1, 1), lambda s: (s, 0)))
        args.append(model_idx)
    _params(specs, args, ws, thrs, flips, fc_ws, fc_thrs, fc_flips, fc_raw)
    out_shape = jax.ShapeDtypeStruct(
        (b, _n_logits(fc_ws, fc_raw, geoms)), jnp.int32)
    return _call(
        functools.partial(
            _megakernel, geoms=geoms, emit=True, finalize_only=True,
            fc_raw=fc_raw, pooled=pooled,
        ),
        (b // bb,), specs, args, [out_shape], bb, interpret,
    )[0]
