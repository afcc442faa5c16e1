"""Plain reference of the PSCNN keyword-spotting network, and its weights.

The network (arXiv:2205.01569, Fig. 7): 8-bit offset-binary audio, a
strided multi-bit conv, three binary conv blocks with OR (max) pooling,
a global average pool read as saturating 8-bit counters, and two FC
layers, the last one emitting raw popcount logits.  Every layer's output
is compared with an integer threshold (``raw >= thr``, inverted where
``flip``), as the sense amplifiers of the paper do.

``offline_logits`` is the whole network over one clip, written out
plainly.  ``Reference`` gives the same numbers for many prefixes of one
stream at once: it runs each layer once over the whole stream with only
the left pad, and for each prefix recomputes only the few positions
whose windows reach the right pad.  Tests hold the two equal.

This module imports nothing of the system under test; the weights are
made here from the seed, so both sides read the same benchmark-made
numbers.
"""
from __future__ import annotations

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

from work import conv_layers, fc_layers


# -- weights ----------------------------------------------------------------

def make_weights(cfg: dict, seed: int):
    """Ternary weights and integer SA thresholds of one model, from
    ``seed``, in the layout ``{layer index: array}`` /
    ``{layer index: (thresholds, flip)}``.

    Weights are -1/0/+1 with ``cfg["weights"]["zero_share"]`` zeros.
    Each channel's threshold sits ``z`` estimated standard deviations
    above the mean pre-activation, ``z`` drawn per channel from
    ``threshold_z``, and a ``flip_share`` of channels is inverted, so
    channels fire at rates from rare to frequent and the GAP counters
    saturate at different times."""
    wcfg = cfg["weights"]
    rng = np.random.default_rng([seed, 0x5C11])
    zero = wcfg["zero_share"]
    zlo, zhi = wcfg["threshold_z"]
    weights, thresholds = {}, {}
    for li, ly in enumerate(cfg["layers"]):
        if ly["kind"] == "conv":
            shape = (ly["k"], ly["cin"], ly["cout"])
            fan_in = ly["k"] * ly["cin"]
            rms_in = wcfg["input_rms"] if ly.get("in_bits", 1) > 1 \
                else wcfg["act_rms"]
        elif ly["kind"] == "fc":
            shape = (ly["cin"], ly["cout"])
            fan_in = ly["cin"]
            rms_in = wcfg["gap_rms"] if ly.get("in_bits", 1) > 1 \
                else wcfg["act_rms"]
        else:
            continue
        u = rng.random(shape)
        w = np.where(u < zero, 0, np.where(u < (1 + zero) / 2, 1, -1))
        weights[li] = w.astype(np.int8)
        cout = shape[-1]
        if ly.get("out_raw"):
            thresholds[li] = (np.zeros(cout), np.zeros(cout, bool))
            continue
        std = np.sqrt(fan_in * (1 - zero)) * rms_in
        z = rng.uniform(zlo, zhi, cout)
        thr = np.round(z * std).astype(np.float64)
        flip = rng.random(cout) < wcfg["flip_share"]
        thresholds[li] = (thr, flip)
    return weights, thresholds


# -- one layer --------------------------------------------------------------

def _centered(codes: np.ndarray, ly: dict) -> np.ndarray:
    """Input frames as signed integers; the pads then read 0."""
    x = np.asarray(codes, np.int64)
    return x - ly.get("in_offset", 0) if ly.get("in_bits", 1) > 1 else x


def _conv_sa(x: np.ndarray, w: np.ndarray, thr: np.ndarray,
             flip: np.ndarray, stride: int) -> np.ndarray:
    """SA outputs of a conv over already padded frames ``x`` (n, cin)."""
    k = w.shape[0]
    if x.shape[0] < k:
        return np.zeros((0, w.shape[2]), np.int8)
    win = sliding_window_view(x, k, axis=0)[::stride]      # (n, cin, k)
    lhs = win.transpose(0, 2, 1).reshape(win.shape[0], -1)
    rhs = w.reshape(-1, w.shape[2])
    # float32 is exact here: every product and partial sum is an
    # integer far below 2**24
    raw = lhs.astype(np.float32) @ rhs.astype(np.float32)
    ge = raw >= thr[None, :]
    return np.where(flip[None, :], ~ge, ge).astype(np.int8)


def _pool(y: np.ndarray, p: int) -> np.ndarray:
    n = y.shape[0] // p
    return y[:n * p].reshape(n, p, y.shape[1]).max(axis=1)


def _classifier(cfg, weights, thresholds, gap: np.ndarray) -> np.ndarray:
    sat = next(ly for ly in cfg["layers"]
               if ly["kind"] == "gap")["not_in_spec"]["saturate"]
    h = np.minimum(gap, sat).astype(np.int64)
    for li, ly in enumerate(cfg["layers"]):
        if ly["kind"] != "fc":
            continue
        raw = h @ weights[li].astype(np.int64)
        if ly.get("out_raw"):
            h = raw
        else:
            thr, flip = thresholds[li]
            ge = raw >= thr
            h = np.where(flip, ~ge, ge).astype(np.int64)
    return h


def _requantize(codes: np.ndarray, bits: int) -> np.ndarray:
    """Audio codes at ``bits`` of precision instead of eight (the
    control): keep the top bits, read back at the bucket's middle."""
    if bits >= 8:
        return codes
    step = 1 << (8 - bits)
    return (codes.astype(np.int64) // step) * step + step // 2


def offline_logits(cfg: dict, weights, thresholds, codes: np.ndarray,
                   input_bits: int = 8) -> np.ndarray:
    """The whole network over one clip of u8 codes."""
    x = _requantize(np.asarray(codes).reshape(-1, 1), input_bits)
    for li, ly in enumerate(cfg["layers"]):
        if ly["kind"] != "conv":
            continue
        xc = _centered(x, ly)
        z = np.zeros((ly["pad"], ly["cin"]), np.int64)
        thr, flip = thresholds[li]
        y = _conv_sa(np.concatenate([z, xc, z]), weights[li], thr, flip,
                     ly["stride"])
        x = _pool(y, ly["pool"])
    return _classifier(cfg, weights, thresholds, x.sum(axis=0))


# -- many prefixes of one stream ---------------------------------------------

class Reference:
    """Logits of the network over prefixes of one stream's audio."""

    def __init__(self, cfg: dict, weights, thresholds, codes: np.ndarray,
                 input_bits: int = 8) -> None:
        self.cfg, self.w, self.t = cfg, weights, thresholds
        self.layers = [(li, ly) for li, ly in enumerate(cfg["layers"])
                       if ly["kind"] == "conv"]
        x = _requantize(np.asarray(codes).reshape(-1, 1), input_bits)
        self.x0 = _centered(x, self.layers[0][1])
        # per layer: its input frames (left-pad causal) and its SA outputs
        self.inputs, self.sa = [], []
        cur = self.x0
        for li, ly in self.layers:
            z = np.zeros((ly["pad"], ly["cin"]), np.int64)
            thr, flip = thresholds[li]
            y = _conv_sa(np.concatenate([z, cur]), weights[li], thr, flip,
                         ly["stride"])
            self.inputs.append(cur)
            self.sa.append(y)
            cur = _pool(y, ly["pool"]).astype(np.int64)
        self.gap_cum = np.concatenate(
            [np.zeros((1, cur.shape[1]), np.int64), np.cumsum(cur, axis=0)])

    def logits(self, n_samples: int) -> np.ndarray:
        """The network's logits had the stream ended after
        ``n_samples`` samples (right pads applied there)."""
        c = n_samples            # leading input frames equal to the causal run
        edge = np.zeros((0, 1), np.int64)
        for i, (li, ly) in enumerate(self.layers):
            k, s, pad, p = ly["k"], ly["stride"], ly["pad"], ly["pool"]
            n = c + edge.shape[0]
            n_conv = (n + 2 * pad - k) // s + 1 if n + 2 * pad >= k else 0
            u = (pad + c - k) // s + 1 if pad + c >= k else 0
            u = min(u, n_conv)
            # positions u.. read the right pad or the edge: recompute them
            padded_start = u * s         # index into [pad zeros | x | pad]
            head = self.inputs[i][max(0, padded_start - pad):c]
            lead = np.zeros((max(0, pad - padded_start), ly["cin"]), np.int64)
            tail = np.zeros((pad, ly["cin"]), np.int64)
            local = np.concatenate([lead, head, edge, tail])
            thr, flip = self.t[li]
            y_edge = _conv_sa(local, self.w[li], thr, flip, s)[:n_conv - u]
            c_next = u // p
            n_pool = n_conv // p
            y = np.concatenate([self.sa[i][c_next * p:u], y_edge])
            edge = _pool(y[:(n_pool - c_next) * p], p).astype(np.int64)
            c = c_next
        gap = self.gap_cum[c] + edge.sum(axis=0)
        return _classifier(self.cfg, self.w, self.t, gap)
