"""Pallas TPU kernel: bit-packed ternary-weight-mapping popcount GEMM.

This is the TPU-native adaptation of the PSCNN macro read (paper §II-C/D):
one grid cell computes a (bm, bn) tile of *finished* activations — popcount
difference, SA threshold, binarize — with the full contraction (≤1024
wordlines = ≤32 packed uint32 words) resident in VMEM, so no partial sums
ever leave the core.  That is the software image of "a single large macro
needs no partial-sum ADCs / adder trees".

Layouts
-------
x_packed : (M, Kw)  uint32   — activations, 32 binary lanes per word
wp, wn   : (Kw, N)  uint32   — positive / negative TWM weight planes
thr      : (1, N)   float32  — folded BN threshold (SA offset)
flip     : (1, N)   int32    — 1 where BN gamma < 0 (compare inverted)

Two output modes:
  * ``raw``  -> int32 popcount difference (final layer / logits)
  * ``sa``   -> uint32 {0,1} binarized activations

VMEM per grid cell (defaults bm=256, bn=256, Kw<=32):
  x 256*32*4 = 32 KiB, planes 2*32*256*4 = 64 KiB, acc 256*256*4 = 256 KiB
  -> ~352 KiB, comfortably inside the ~16 MiB VMEM of a v5e core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import dispatch
from repro.kernels.bnn_conv1d import sa_bits

DEFAULT_BM = 256
DEFAULT_BN = 256


def _popdiff_tile(x, wp, wn, kw: int):
    """(bm, Kw) u32, (Kw, bn) u32 planes -> (bm, bn) int32 popcount diff.

    The k-loop is a static unroll over packed words: each step is a
    rank-1-broadcast AND + popcount on the VPU — the digital twin of one
    wordline-group activation.
    """
    acc = jnp.zeros((x.shape[0], wp.shape[1]), jnp.int32)
    for k in range(kw):
        xa = x[:, k][:, None]  # (bm, 1)
        p = jax.lax.population_count(jnp.bitwise_and(xa, wp[k][None, :]))
        n = jax.lax.population_count(jnp.bitwise_and(xa, wn[k][None, :]))
        acc = acc + p.astype(jnp.int32) - n.astype(jnp.int32)
    return acc


def _kernel_sa(x_ref, wp_ref, wn_ref, thr_ref, flip_ref, o_ref, *, kw: int):
    diff = _popdiff_tile(x_ref[...], wp_ref[...], wn_ref[...], kw)
    o_ref[...] = sa_bits(diff, thr_ref[...], flip_ref[...]).astype(jnp.uint32)


def _kernel_raw(x_ref, wp_ref, wn_ref, o_ref, *, kw: int):
    o_ref[...] = _popdiff_tile(x_ref[...], wp_ref[...], wn_ref[...], kw)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "mode", "interpret")
)
def twm_matmul(
    x_packed: jax.Array,
    wp: jax.Array,
    wn: jax.Array,
    thr: jax.Array | None = None,
    flip: jax.Array | None = None,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    mode: str = "sa",
    interpret: bool,
) -> jax.Array:
    """Packed popcount GEMM with optional fused SA epilogue.

    Shapes must be pre-padded: M % bm == 0, N % bn == 0 (pad with zero rows /
    dead columns — inactive wordlines / unused bitline pairs).
    """
    m, kw = x_packed.shape
    kw2, n = wp.shape
    assert kw == kw2 and wn.shape == wp.shape, (x_packed.shape, wp.shape, wn.shape)
    bm = min(bm, m)
    bn = min(bn, n)
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
    grid = (m // bm, n // bn)

    x_spec = pl.BlockSpec((bm, kw), lambda i, j: (i, 0))
    w_spec = pl.BlockSpec((kw, bn), lambda i, j: (0, j))
    v_spec = pl.BlockSpec((1, bn), lambda i, j: (0, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))

    if mode == "sa":
        assert thr is not None and flip is not None
        return dispatch.pallas_call(
            functools.partial(_kernel_sa, kw=kw),
            grid=grid,
            in_specs=[x_spec, w_spec, w_spec, v_spec, v_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.uint32),
            interpret=interpret,
        )(x_packed, wp, wn, thr.reshape(1, n), flip.astype(jnp.int32).reshape(1, n))
    elif mode == "raw":
        return dispatch.pallas_call(
            functools.partial(_kernel_raw, kw=kw),
            grid=grid,
            in_specs=[x_spec, w_spec, w_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
            interpret=interpret,
        )(x_packed, wp, wn)
    raise ValueError(f"mode {mode!r}")


# ---------------------------------------------------------------------------
# Beyond-paper MXU path: ternary weights as int8 on the systolic array.
# Wins when the shape is compute-bound (big M); the popcount path wins when
# memory-bound (weights 16x smaller).  See DESIGN.md §2.4.
# ---------------------------------------------------------------------------

def _kernel_mxu(x_ref, w_ref, thr_ref, flip_ref, o_ref):
    acc = jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    o_ref[...] = sa_bits(acc, thr_ref[...], flip_ref[...]).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def twm_matmul_mxu(
    x_i8: jax.Array,
    w_i8: jax.Array,
    thr: jax.Array,
    flip: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    interpret: bool,
) -> jax.Array:
    """int8 MXU GEMM (x in {0,1}, w in {-1,0,1}) with the same SA epilogue.

    K stays un-tiled (<=1024 fits VMEM at int8: 256 KiB per x tile).
    """
    m, k = x_i8.shape
    k2, n = w_i8.shape
    assert k == k2
    bm = min(bm, m)
    bn = min(bn, n)
    assert m % bm == 0 and n % bn == 0
    grid = (m // bm, n // bn)
    return dispatch.pallas_call(
        _kernel_mxu,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.uint32),
        interpret=interpret,
    )(x_i8, w_i8, thr.reshape(1, n), flip.astype(jnp.int32).reshape(1, n))
