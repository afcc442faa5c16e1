"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand_dense(m, k, n):
    x = RNG.integers(0, 2, (m, k)).astype(np.uint32)
    w = RNG.integers(-1, 2, (k, n)).astype(np.int32)
    thr = RNG.normal(0, 3, (n,)).astype(np.float32)
    flip = RNG.integers(0, 2, (n,)).astype(bool)
    return jnp.array(x), jnp.array(w), jnp.array(thr), jnp.array(flip)


@pytest.mark.parametrize(
    "m,k,n",
    [
        (1, 32, 16),     # minimal
        (7, 100, 12),    # unaligned everything
        (64, 1024, 128), # macro-shaped: full wordline contraction
        (33, 513, 65),   # prime-ish
    ],
)
def test_twm_matmul_raw_and_sa(m, k, n):
    x, w, thr, flip = _rand_dense(m, k, n)
    raw = ops.twm_linear(x, w, mode="raw")
    np.testing.assert_array_equal(np.asarray(raw),
                                  np.asarray(ref.ref_twm_matmul(x, w)))
    sa = ops.twm_linear(x, w, thr, flip, mode="sa")
    np.testing.assert_array_equal(
        np.asarray(sa), np.asarray(ref.ref_twm_matmul_sa(x, w, thr, flip))
    )


@pytest.mark.parametrize("m,k,n", [(5, 64, 20), (16, 256, 64)])
def test_twm_matmul_mxu_path(m, k, n):
    x, w, thr, flip = _rand_dense(m, k, n)
    got = ops.twm_linear_mxu(x, w, thr, flip)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.ref_twm_matmul_sa(x, w, thr, flip))
    )


@pytest.mark.parametrize(
    "l,cin,cout,k,stride,pad,pool",
    [
        (40, 8, 16, 3, 1, 1, 1),
        (100, 24, 40, 3, 1, 1, 2),
        (64, 16, 20, 5, 1, 2, 4),
        (128, 32, 48, 7, 2, 3, 1),
        (200, 64, 128, 3, 1, 1, 2),   # KWS-block-like
        (33, 8, 12, 2, 2, 0, 1),      # even kernel, no pad
    ],
)
def test_bnn_conv1d_sweep(l, cin, cout, k, stride, pad, pool):
    x = jnp.array(RNG.integers(0, 2, (l, cin)), jnp.uint32)
    w = jnp.array(RNG.integers(-1, 2, (k, cin, cout)), jnp.int32)
    thr = jnp.array(RNG.normal(0, 2, (cout,)), jnp.float32)
    flip = jnp.array(RNG.integers(0, 2, (cout,)), bool)
    got = ops.bnn_conv1d(x, w, thr, flip, stride=stride, pad=pad, pool=pool)
    want = ref.ref_bnn_conv1d_sa(x, w, thr, flip, stride=stride, pad=pad,
                                 pool=pool)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bnn_conv1d_raw():
    x = jnp.array(RNG.integers(0, 2, (50, 16)), jnp.uint32)
    w = jnp.array(RNG.integers(-1, 2, (3, 16, 24)), jnp.int32)
    got = ops.bnn_conv1d(x, w, stride=1, pad=1, mode="raw")
    want = ref.ref_bnn_conv1d(x, w, stride=1, pad=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bits,offset,stride", [(8, 128, 8), (4, 8, 2)])
def test_bitserial_conv(bits, offset, stride):
    x = jnp.array(RNG.integers(0, 2**bits, (160, 1)), jnp.uint32)
    w = jnp.array(RNG.integers(-1, 2, (19, 1, 16)), jnp.int32)
    got = ops.bitserial_conv1d(x, w, bits=bits, offset=offset, stride=stride,
                               pad=9)
    want = ref.ref_bitserial_conv1d(x, w, bits=bits, offset=offset,
                                    stride=stride, pad=9)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bitserial_matmul():
    x = jnp.array(RNG.integers(0, 256, (3, 96)), jnp.uint32)
    w = jnp.array(RNG.integers(-1, 2, (96, 12)), jnp.int32)
    want = ref.ref_bitserial_matmul(x, w, bits=8, offset=0)
    got = sum(
        (1 << b) * np.asarray(ops.twm_linear(((x >> b) & 1).astype(jnp.uint32),
                                             w, mode="raw"))
        for b in range(8)
    )
    np.testing.assert_array_equal(got, np.asarray(want))


def test_pick_path_heuristic():
    # tiny-batch (memory-bound) prefers popcount; big GEMM prefers MXU
    assert ops.pick_path(1, 1024, 512) == "popcount"
    assert ops.pick_path(65536, 1024, 4096) == "mxu"


def test_interpret_refused_on_tpu_backend(monkeypatch):
    """An interpreted kernel on a TPU would run slowly in place of the
    Mosaic kernel: the dispatch layer refuses it."""
    import jax

    from repro.kernels import dispatch

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.default_interpret() is False
    x, w, thr, flip = _rand_dense(8, 32, 16)
    with pytest.raises(ValueError, match="interpret"):
        dispatch.pallas_call(lambda x_ref, o_ref: None, interpret=True,
                             out_shape=jax.ShapeDtypeStruct((8,), jnp.int32))
    with pytest.raises(ValueError, match="interpret"):
        ops.twm_linear(x, w, thr, flip, interpret=True)
