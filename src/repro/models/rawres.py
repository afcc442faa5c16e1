"""M34-res: the 34-layer residual network over raw waveforms, binarized.

Dai, Dai, Qu, Li and Das, "Very Deep Convolutional Neural Networks for Raw
Waveforms", ICASSP 2017 (arXiv:1610.00087), Table 1, column M34-res: 8 kHz
audio, one [80/4, 48] conv and max-pool 4, then four stages of residual
blocks of two [3, w] convs -- (3, 4, 6, 3) blocks at widths (48, 96, 192,
384) -- with a max-pool 4 after each of the first three, global average
pooling and a softmax classifier.  33 convs; about 4.06 M weights with the
1x1 projection shortcuts at the three channel doublings and a 12-class
output.

Run in the paper's binary arithmetic (PSCNN), as the KWS network is:
ternary weights, binary activations, BN and ReLU folded into per-channel
integer SA thresholds, the first conv reading 8-bit offset-binary audio
bit-serially, GAP as 8-bit saturating counters and one FC emitting raw
logits.  A block joins its second conv's raw counts ``s`` with ``g`` times
the shortcut (``cnn_spec.ResBlock1DSpec``), ``g`` the per-channel integer
ratio of the two branches' folded BN scales.

``offline_logits`` is the plain whole-clip forward in integers; it imports
nothing of ``repro.stream`` and is what the streaming runtime is held to.
"""
from __future__ import annotations

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

from repro.core.cnn_spec import (
    CNN1DSpec,
    Conv1DSpec,
    FCSpec,
    GAPSpec,
    PoolSpec,
    ResBlock1DSpec,
)

IN_OFFSET = 128


def build_m34res_spec(
    in_len: int = 32000,
    n_classes: int = 12,
    widths: tuple[int, ...] = (48, 96, 192, 384),
    blocks: tuple[int, ...] = (3, 4, 6, 3),
    k0: int = 80,
    stride0: int = 4,
    pad0: int = 38,
    pool: int = 4,
) -> CNN1DSpec:
    """Table 1's M34-res (defaults), or any network of its shape: a first
    conv with its pool fused, ``blocks[i]`` residual blocks of two [3, w]
    convs at ``widths[i]`` with a standalone pool after every stage but
    the last,
    GAP and one raw-logit FC.  A block that changes the width projects
    its shortcut."""
    layers: list = [Conv1DSpec(1, widths[0], k=k0, stride=stride0, pad=pad0,
                               pool=pool, in_bits=8, in_offset=IN_OFFSET,
                               name="conv0")]
    c = widths[0]
    for si, (w, nb) in enumerate(zip(widths, blocks), start=1):
        for bi in range(1, nb + 1):
            layers.append(ResBlock1DSpec(c, w, proj=c != w,
                                         name=f"s{si}b{bi}"))
            c = w
        if si < len(widths):
            layers.append(PoolSpec(w, pool, name=f"pool{si}"))
    layers += [GAPSpec(c, name="gap"),
               FCSpec(c, n_classes, in_bits=8, out_raw=True, name="fc")]
    return CNN1DSpec(in_len=in_len, in_channels=1, in_bits=8,
                     layers=tuple(layers), name="m34res")


def build_m34res_small_spec() -> CNN1DSpec:
    """The same shape, small enough for CPU tests: two stages (identity
    blocks, one projection block) around one standalone pool."""
    return build_m34res_spec(in_len=1024, n_classes=12, widths=(6, 10),
                             blocks=(2, 2), k0=8, stride0=2, pad0=3,
                             pool=2)


def random_weights(spec: CNN1DSpec, seed: int, zero_share: float = 0.33,
                   gains: tuple[int, int] = (1, 4)):
    """Seeded ternary weights and integer SA thresholds for ``spec``, in
    the layout the compiler and the stream take: ``{layer: array}`` and
    ``{layer: (thr, flip)}``; a residual block's entries are
    ``{"w1", "w2"[, "wp"]}`` and ``{"sa1", "sa2", "gain"}``.  Gains are
    drawn from ``gains`` (inclusive).  Thresholds sit near the middle of
    each channel's pre-activation range, so channels fire at many
    rates."""
    rng = np.random.default_rng([seed, 0x34E5])

    def ternary(shape):
        u = rng.random(shape)
        return np.where(u < zero_share, 0,
                        np.where(u < (1 + zero_share) / 2, 1, -1)
                        ).astype(np.int8)

    def sa(cout, std):
        thr = np.round(rng.uniform(-0.5, 1.0, cout) * std)
        return thr.astype(np.float64), rng.random(cout) < 0.2

    weights, thresholds = {}, {}
    for li, L in enumerate(spec.layers):
        if isinstance(L, Conv1DSpec):
            weights[li] = ternary((L.k, L.cin, L.cout))
            rms = 40.0 if L.in_bits > 1 else 0.6
            thresholds[li] = sa(L.cout, np.sqrt(L.k * L.cin) * rms)
        elif isinstance(L, ResBlock1DSpec):
            w = {"w1": ternary((L.k, L.cin, L.cout)),
                 "w2": ternary((L.k, L.cout, L.cout))}
            if L.proj:
                w["wp"] = ternary((L.cin, L.cout))
            weights[li] = w
            thresholds[li] = {
                "sa1": sa(L.cout, np.sqrt(L.k * L.cin) * 0.6),
                "sa2": sa(L.cout, np.sqrt(L.k * L.cout) * 0.6),
                "gain": rng.integers(gains[0], gains[1] + 1, L.cout),
            }
        elif isinstance(L, FCSpec):
            weights[li] = ternary((L.cin, L.cout))
            thresholds[li] = (np.zeros(L.cout), np.zeros(L.cout, bool))
    return weights, thresholds


# -- the plain whole-clip forward ---------------------------------------------

def _conv(x: np.ndarray, w: np.ndarray, stride: int, pad: int) -> np.ndarray:
    """Raw counts of a conv over (n, cin) frames, ``pad`` zeros each side."""
    z = np.zeros((pad, x.shape[1]), np.int64)
    xp = np.concatenate([z, x, z])
    k = w.shape[0]
    if xp.shape[0] < k:
        return np.zeros((0, w.shape[2]), np.int64)
    win = sliding_window_view(xp, k, axis=0)[::stride]     # (n, cin, k)
    lhs = win.transpose(0, 2, 1).reshape(win.shape[0], -1)
    return lhs @ w.reshape(-1, w.shape[2]).astype(np.int64)


def _sa(z: np.ndarray, thr_flip) -> np.ndarray:
    thr, flip = thr_flip
    ge = z >= thr
    return np.where(flip, ~ge, ge).astype(np.int64)


def _maxpool(y: np.ndarray, p: int) -> np.ndarray:
    n = y.shape[0] // p
    return y[:n * p].reshape(n, p, y.shape[1]).max(axis=1)


def offline_logits(spec: CNN1DSpec, weights, thresholds,
                   codes: np.ndarray) -> np.ndarray:
    """The whole network over one clip of u8 codes, in integers: the
    first conv reads the codes less their offset (its pads read 0),
    pools drop a partial window, GAP counts saturate at 255."""
    x = np.asarray(codes, np.int64).reshape(-1, spec.in_channels)
    for li, L in enumerate(spec.layers):
        if isinstance(L, Conv1DSpec):
            xc = x - L.in_offset if L.in_bits > 1 else x
            z = _conv(xc, np.asarray(weights[li]), L.stride, L.pad)
            y = z if L.out_raw else _sa(z, thresholds[li])
            x = _maxpool(y, L.pool)
        elif isinstance(L, PoolSpec):
            x = _maxpool(x, L.pool)
        elif isinstance(L, ResBlock1DSpec):
            w, t = weights[li], thresholds[li]
            h = _sa(_conv(x, np.asarray(w["w1"]), 1, L.pad), t["sa1"])
            s = _conv(h, np.asarray(w["w2"]), 1, L.pad)
            r = x @ np.asarray(w["wp"], np.int64) if L.proj else x
            x = _sa(s + np.asarray(t["gain"], np.int64) * r, t["sa2"])
        elif isinstance(L, GAPSpec):
            x = np.minimum(x.sum(axis=0, keepdims=True), 255)
        elif isinstance(L, FCSpec):
            z = x @ np.asarray(weights[li], np.int64)
            x = z if L.out_raw else _sa(z, thresholds[li])
    return x[0]
