"""Plain reference of M34-res, the binarized raw-waveform residual network,
and its weights.

The network (arXiv:1610.00087, Table 1, M34-res, in the PSCNN arithmetic
the configuration's ``assumed`` lists): 8-bit offset-binary audio, a
strided multi-bit conv with a fused OR (max) pool, residual blocks of two
binary convs, standalone OR pools between the stages, a global average
pool read as saturating 8-bit counters and one FC emitting raw popcount
logits.  A conv's output is compared with an integer threshold (``raw >=
thr``, inverted where ``flip``).  A block computes ``h = SA1(conv(x;
W1))``, ``s = conv(h; W2)`` in raw counts, and ``y = SA2(s + g * r)``
with ``r`` the block input (or its 1x1 projection ``x @ Wp``) and ``g``
a per-channel integer gain.

``offline_logits`` is the whole network over one clip, written out
plainly.  ``Reference`` gives the same numbers for many prefixes of one
stream: it runs each layer once over the whole stream with only the left
pads (in blocks of rows), and for each prefix recomputes only the few
positions whose windows reach a right pad.  Tests hold the two equal.

This module imports nothing of the system under test; the weights are
made here from the seed, so both sides read the same benchmark-made
numbers.
"""
from __future__ import annotations

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

from pscnn_kws import _classifier, _pool, _requantize

ROWS = 1 << 15   # conv positions per matmul of the whole-stream pass
STREAMED = ("conv", "pool", "res")


# -- weights ----------------------------------------------------------------

def make_weights(cfg: dict, seed: int):
    """Ternary weights, integer SA thresholds and integer shortcut gains
    of one model, from ``seed``: ``{layer: array}`` / ``{layer: (thr,
    flip)}``, and for a residual block ``{"w1", "w2"[, "wp"]}`` /
    ``{"sa1", "sa2", "gain"}``.

    Weights are -1/0/+1 with ``zero_share`` zeros.  Each SA threshold sits
    ``z`` estimated standard deviations from the mean of what it reads,
    ``z`` drawn per channel from ``threshold_z``, and a ``flip_share`` of
    channels is inverted.  A block's gain is ``u`` times the ratio of the
    estimated standard deviations of its two branches, ``u`` drawn per
    channel from ``gain_ratio``, rounded and at least 1: the shortcut
    moves the join by as much as the residual branch does."""
    wc = cfg["weights"]
    rng = np.random.default_rng([seed, 0x34E5])
    zero = wc["zero_share"]
    zlo, zhi = wc["threshold_z"]
    act = wc["act_rms"]

    def ternary(shape):
        u = rng.random(shape)
        return np.where(u < zero, 0, np.where(u < (1 + zero) / 2, 1, -1)
                        ).astype(np.int8)

    def sa(cout, std, mean=0.0):
        thr = np.round(mean + rng.uniform(zlo, zhi, cout) * std)
        return thr.astype(np.float64), rng.random(cout) < wc["flip_share"]

    def std(fan_in, rms):
        return np.sqrt(fan_in * (1 - zero)) * rms

    weights, thresholds = {}, {}
    for li, ly in enumerate(cfg["layers"]):
        kind = ly["kind"]
        if kind == "conv":
            weights[li] = ternary((ly["k"], ly["cin"], ly["cout"]))
            rms = wc["input_rms"] if ly.get("in_bits", 1) > 1 else act
            thresholds[li] = sa(ly["cout"], std(ly["k"] * ly["cin"], rms))
        elif kind == "res":
            k, ci, co = ly["k"], ly["cin"], ly["cout"]
            w = {"w1": ternary((k, ci, co)), "w2": ternary((k, co, co))}
            s1 = sa(co, std(k * ci, act))
            std_s = std(k * co, act)
            if ly.get("proj", False):
                w["wp"] = ternary((ci, co))
                std_r, mean_r = std(ci, act), 0.0
            else:
                std_r, mean_r = act, wc["act_mean"]
            g = np.maximum(1, np.round(
                rng.uniform(*wc["gain_ratio"], co) * std_s / std_r)
            ).astype(np.int64)
            std_z = np.sqrt(std_s ** 2 + (g * std_r) ** 2)
            weights[li] = w
            thresholds[li] = {"sa1": s1, "sa2": sa(co, std_z, g * mean_r),
                              "gain": g}
        elif kind == "fc":
            weights[li] = ternary((ly["cin"], ly["cout"]))
            if ly.get("out_raw"):
                thresholds[li] = (np.zeros(ly["cout"]),
                                  np.zeros(ly["cout"], bool))
            else:
                rms = wc["gap_rms"] if ly.get("in_bits", 1) > 1 else act
                thresholds[li] = sa(ly["cout"], std(ly["cin"], rms))
    return weights, thresholds


# -- layers -------------------------------------------------------------------

def _conv_raw(x: np.ndarray, w: np.ndarray, stride: int) -> np.ndarray:
    """Raw counts of a conv over already padded frames ``x`` (n, cin),
    ``ROWS`` positions a matmul."""
    k, cout = w.shape[0], w.shape[2]
    if x.shape[0] < k:
        return np.zeros((0, cout), np.int64)
    win = sliding_window_view(x, k, axis=0)[::stride]      # (n, cin, k)
    rhs = w.transpose(1, 0, 2).reshape(-1, cout).astype(np.float32)
    out = np.empty((win.shape[0], cout), np.int64)
    for a in range(0, win.shape[0], ROWS):
        lhs = win[a:a + ROWS].reshape(-1, rhs.shape[0]).astype(np.float32)
        # float32 is exact here: every product and partial sum is an
        # integer far below 2**24
        out[a:a + ROWS] = lhs @ rhs
    return out


def _sa(z: np.ndarray, thr_flip) -> np.ndarray:
    thr, flip = thr_flip
    ge = z >= thr
    return np.where(flip, ~ge, ge).astype(np.int8)


def _pad(x: np.ndarray, left: int, right: int) -> np.ndarray:
    z = np.zeros((1, x.shape[1]), x.dtype)
    return np.concatenate([np.repeat(z, left, 0), x, np.repeat(z, right, 0)])


def _join(s: np.ndarray, x: np.ndarray, w: dict, t: dict) -> np.ndarray:
    """``SA2(s + g * r)``, ``r`` the block inputs ``x`` or their 1x1
    projection."""
    r = x.astype(np.int64)
    if "wp" in w:
        r = r @ w["wp"].astype(np.int64)
    return _sa(s + t["gain"] * r, t["sa2"])


def _audio(cfg: dict, codes: np.ndarray, input_bits: int) -> np.ndarray:
    """The first conv's input: codes less their offset, so pads read 0."""
    first = cfg["layers"][0]
    x = _requantize(np.asarray(codes).reshape(-1, 1), input_bits)
    return np.asarray(x, np.int64) - first.get("in_offset", 0)


def offline_logits(cfg: dict, weights, thresholds, codes: np.ndarray,
                   input_bits: int = 8) -> np.ndarray:
    """The whole network over one clip of u8 codes."""
    x = _audio(cfg, codes, input_bits)
    for li, ly in enumerate(cfg["layers"]):
        kind = ly["kind"]
        if kind == "conv":
            y = _sa(_conv_raw(_pad(x, ly["pad"], ly["pad"]), weights[li],
                              ly["stride"]), thresholds[li])
            x = _pool(y, ly["pool"])
        elif kind == "pool":
            x = _pool(x, ly["pool"])
        elif kind == "res":
            w, t, p = weights[li], thresholds[li], ly["pad"]
            h = _sa(_conv_raw(_pad(x, p, p), w["w1"], 1), t["sa1"])
            s = _conv_raw(_pad(h, p, p), w["w2"], 1)
            x = _join(s, x, w, t)
    return _classifier(cfg, weights, thresholds,
                       x.astype(np.int64).sum(axis=0))


# -- many prefixes of one stream ---------------------------------------------

def _edge(causal_in, c: int, edge: np.ndarray, k: int, stride: int,
          pad: int, w: np.ndarray) -> tuple[int, np.ndarray]:
    """A conv over a prefix whose input is ``causal_in[:c]`` then
    ``edge``: the count ``u`` of leading positions equal to the
    left-pad-only run's, and the raw counts of positions ``u..`` (those
    whose windows reach the edge or the right pad)."""
    n = c + edge.shape[0]
    n_conv = (n + 2 * pad - k) // stride + 1 if n + 2 * pad >= k else 0
    u = (pad + c - k) // stride + 1 if pad + c >= k else 0
    u = min(u, n_conv)
    start = u * stride                # index into [pad zeros | x | pad]
    head = np.asarray(causal_in[max(0, start - pad):c], np.int64)
    local = _pad(np.concatenate([head, edge.astype(np.int64)]),
                 max(0, pad - start), pad)
    return u, _conv_raw(local, w, stride)[:n_conv - u]


class Reference:
    """Logits of the network over prefixes of one stream's audio."""

    def __init__(self, cfg: dict, weights, thresholds, codes: np.ndarray,
                 input_bits: int = 8) -> None:
        self.cfg, self.w, self.t = cfg, weights, thresholds
        self.layers = [(li, ly) for li, ly in enumerate(cfg["layers"])
                       if ly["kind"] in STREAMED]
        # per layer, the left-pad-only run of what a prefix's edge reads
        self.runs = []
        cur = _audio(cfg, codes, input_bits)
        for li, ly in self.layers:
            w, t = weights.get(li), thresholds.get(li)
            if ly["kind"] == "conv":
                y = _sa(_conv_raw(_pad(cur, ly["pad"], 0), w, ly["stride"]),
                        t)
                self.runs.append({"x": cur, "y": y})
                cur = _pool(y, ly["pool"])
            elif ly["kind"] == "pool":
                self.runs.append({"x": cur})
                cur = _pool(cur, ly["pool"])
            else:
                p = ly["pad"]
                h = _sa(_conv_raw(_pad(cur, p, 0), w["w1"], 1), t["sa1"])
                s = _conv_raw(_pad(h, p, 0), w["w2"], 1)
                self.runs.append({"x": cur, "h": h})
                cur = _join(s, cur[:s.shape[0]], w, t)
        self.gap_cum = np.concatenate(
            [np.zeros((1, cur.shape[1]), np.int64),
             np.cumsum(cur, axis=0, dtype=np.int64)])

    def logits(self, n_samples: int) -> np.ndarray:
        """The network's logits had the stream ended after
        ``n_samples`` samples (right pads applied there)."""
        c = n_samples        # leading frames equal to the left-pad-only run
        edge = np.zeros((0, 1), np.int64)
        for (li, ly), run in zip(self.layers, self.runs):
            w, t = self.w.get(li), self.t.get(li)
            if ly["kind"] == "conv":
                u, z = _edge(run["x"], c, edge, ly["k"], ly["stride"],
                             ly["pad"], w)
                p = ly["pool"]
                n_pool = (u + z.shape[0]) // p
                y = np.concatenate([run["y"][u // p * p:u], _sa(z, t)])
                edge = _pool(y[:(n_pool - u // p) * p], p)
                c = u // p
            elif ly["kind"] == "pool":
                p = ly["pool"]
                n_pool = (c + edge.shape[0]) // p
                x = np.concatenate([run["x"][c // p * p:c], edge])
                edge = _pool(x[:(n_pool - c // p) * p], p)
                c = c // p
            else:
                k, p = ly["k"], ly["pad"]
                u1, z1 = _edge(run["x"], c, edge, k, 1, p, w["w1"])
                u2, s = _edge(run["h"], u1, _sa(z1, t["sa1"]), k, 1, p,
                              w["w2"])
                x = np.concatenate([run["x"][u2:c], edge])
                edge = _join(s, x, w, t)
                c = u2
        gap = self.gap_cum[c] + edge.astype(np.int64).sum(axis=0)
        return _classifier(self.cfg, self.w, self.t, gap)
