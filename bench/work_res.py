"""Work of one emitted stream-hop for networks with residual blocks and
standalone pools, computed from the network's geometry.

The streaming schedule is that of ``bench/work.py`` with two more kinds of
layer.  A standalone pool (``"pool"``) keeps only the frames pending in
its window.  A residual block (``"res"``) streams as its two convs (the
second emitting raw counts) and a *join*, ``y = SA2(s + g * r)``, whose
skip ring holds the block inputs still waiting for the second conv to
reach their position (the two convs' lag).  Every hop that emits logits
also runs the end-of-stream ("ghost") flush, which in a deep network
re-computes the lag of every conv stacked above it.  The module derives
all of it from the configuration file alone, so the operation and byte
counts read the same whatever backend runs the hop.

Operations are two per multiply-accumulate: the convs' and the FC's, a
projection's ``cin`` per joined value, and one per joined value for
``s + g * r``.  Bytes are counted at the algorithm's own widths, as in
``bench/work.py``: 8-bit audio, first-conv tail and GAP counters, 1-bit
activations, tails, skip rings and pool windows, 16-bit raw logits, and
ternary weights (2 bits) read once per batched step.  On a network of
convs with fused pools only, the counts equal ``bench/work.py``'s.
"""
from __future__ import annotations

import dataclasses

from work import LOGIT_BITS, WEIGHT_BITS


@dataclasses.dataclass(frozen=True)
class Stage:
    """One streamed primitive's per-hop geometry in the steady state."""

    name: str
    kind: str        # "conv", "pool" or "join"
    k: int
    stride: int
    pad: int
    pool: int
    cin: int
    cout: int
    in_bits: int
    proj: bool       # join: the shortcut is a 1x1 projection
    src: int         # join: index of the block's first conv
    tail: int        # frames carried from hop to hop: a conv's input tail
    #                  (left pad incl.), a join's skip ring
    phase: int       # frames waiting for a full pool window
    n_conv: int      # per hop: conv positions, or frames joined / pooled in
    flush_conv: int  # what a ghost flush adds to n_conv


@dataclasses.dataclass(frozen=True)
class Geometry:
    hop_samples: int
    prime_samples: int
    stages: tuple[Stage, ...]
    fcs: tuple[tuple[int, int], ...]   # (cin, cout) per FC layer
    gap_channels: int
    gap_bits: int


def primitives(cfg: dict) -> list[dict]:
    """The configuration's streamed layers as primitives: a conv, a pool,
    and for a residual block its two convs and its join."""
    out: list[dict] = []
    for ly in cfg["layers"]:
        kind = ly["kind"]
        base = {"stride": 1, "pad": 0, "pool": 1, "k": 1, "in_bits": 1,
                "proj": False, "src": -1}
        if kind == "conv":
            out.append({**base, **{k: ly[k] for k in (
                "name", "k", "stride", "pad", "pool", "cin", "cout")},
                "kind": "conv", "in_bits": ly.get("in_bits", 1)})
        elif kind == "pool":
            out.append({**base, "name": ly["name"], "kind": "pool",
                        "pool": ly["pool"], "cin": ly["channels"],
                        "cout": ly["channels"]})
        elif kind == "res":
            conv = {**base, "kind": "conv", "k": ly["k"], "pad": ly["pad"]}
            src = len(out)
            out.append({**conv, "name": ly["name"] + ".conv1",
                        "cin": ly["cin"], "cout": ly["cout"]})
            out.append({**conv, "name": ly["name"] + ".conv2",
                        "cin": ly["cout"], "cout": ly["cout"]})
            out.append({**base, "name": ly["name"] + ".join",
                        "kind": "join", "cin": ly["cin"], "cout": ly["cout"],
                        "proj": ly.get("proj", False), "src": src})
    return out


def _counts(prims: list[dict], pushes: list[int]):
    """Frames fed and taken up per primitive after ``pushes`` input
    chunks: a conv's padded input and positions, a pool's frames in, a
    join's block inputs and joined frames.  The left pad arrives with a
    conv's first frame."""
    n = len(prims)
    fed, took, pooled = [0] * n, [0] * n, [0] * n
    for push in pushes:
        cur, ins = push, [0] * n
        for i, p in enumerate(prims):
            ins[i] = cur
            if p["kind"] == "join":
                fed[i] += ins[p["src"]]
                took[i] += cur
                continue
            if p["kind"] == "conv":
                if fed[i] == 0 and cur > 0:
                    fed[i] += p["pad"]
                fed[i] += cur
                took[i] = (fed[i] - p["k"]) // p["stride"] + 1 \
                    if fed[i] >= p["k"] else 0
            else:
                fed[i] += cur
                took[i] = fed[i]
            new_pool = took[i] // p["pool"] - pooled[i]
            pooled[i] += new_pool
            cur = new_pool
    return fed, took


def geometry(cfg: dict) -> Geometry:
    """The steady streaming schedule at ``cfg["hop_frames"]`` final frames
    per hop.  The prime is the shortest prefix, a multiple of the first
    stride, after which every conv has seen a whole receptive field."""
    prims = primitives(cfg)
    unit = 1
    for p in prims:
        unit *= p["stride"] * p["pool"]
    hop = cfg["hop_frames"] * unit
    s0 = prims[0]["stride"]
    prime = 0
    for cand in range(s0, 64 * unit + 1, s0):
        f, ok = cand, True
        for p in prims:
            if p["kind"] == "conv":
                if p["pad"] + f < p["k"]:
                    ok = False
                    break
                f = ((p["pad"] + f - p["k"]) // p["stride"] + 1) \
                    // p["pool"]
            elif p["kind"] == "pool":
                f //= p["pool"]
        if ok:
            prime = cand
            break
    if not prime:
        raise ValueError("no priming prefix")
    fed1, took1 = _counts(prims, [prime, hop])
    fed2, took2 = _counts(prims, [prime, hop, hop])
    stages: list[Stage] = []
    f_ins: list[int] = []
    f_in = 0
    for i, p in enumerate(prims):
        f_ins.append(f_in)
        if p["kind"] == "conv":
            tail = fed2[i] - took2[i] * p["stride"]
            steady = fed1[i] - took1[i] * p["stride"]
            phase = took2[i] % p["pool"]
            avail = tail + f_in + p["pad"]
            f_conv = (avail - p["k"]) // p["stride"] + 1 \
                if avail >= p["k"] else 0
            f_out = (phase + f_conv) // p["pool"]
        elif p["kind"] == "pool":
            tail = steady = 0
            phase = took2[i] % p["pool"]
            f_conv, f_out = f_in, (phase + f_in) // p["pool"]
        else:
            tail, steady = fed2[i] - took2[i], fed1[i] - took1[i]
            phase = 0
            if f_in != tail + f_ins[p["src"]]:
                raise ValueError(f"{p['name']}: the flush leaves the skip "
                                 "ring unjoined")
            f_conv = f_out = f_in
        if tail != steady:
            raise ValueError(f"{p['name']}: no steady state at this hop")
        stages.append(Stage(
            p["name"], p["kind"], p["k"], p["stride"], p["pad"], p["pool"],
            p["cin"], p["cout"], p["in_bits"], p["proj"], p["src"], tail,
            phase, took2[i] - took1[i], f_conv))
        f_in = f_out
    gap = next(ly for ly in cfg["layers"] if ly["kind"] == "gap")
    fcs = tuple((ly["cin"], ly["cout"]) for ly in cfg["layers"]
                if ly["kind"] == "fc")
    return Geometry(hop, prime, tuple(stages), fcs, gap["channels"],
                    gap["not_in_spec"]["bits"])


def _macs_each(s: Stage) -> int:
    """Multiply-accumulates of one position (a conv) or one joined frame
    (a join: the projection, then ``s + g * r``)."""
    if s.kind == "conv":
        return s.k * s.cin * s.cout
    if s.kind == "join":
        return (s.cin + 1) * s.cout if s.proj else s.cout
    return 0


def macs_per_hop(g: Geometry) -> dict[str, int]:
    """Multiply-accumulates of one emitted stream-hop, by part."""
    conv = sum(s.n_conv * _macs_each(s) for s in g.stages
               if s.kind == "conv")
    join = sum(s.n_conv * _macs_each(s) for s in g.stages
               if s.kind == "join")
    flush = sum(s.flush_conv * _macs_each(s) for s in g.stages)
    fc = sum(ci * co for ci, co in g.fcs)
    return {"conv": conv, "join": join, "flush": flush, "classifier": fc,
            "total": conv + join + flush + fc}


def ops_per_hop(g: Geometry) -> int:
    """Operations (two per MAC) of one emitted stream-hop."""
    return 2 * macs_per_hop(g)["total"]


def bytes_per_hop(g: Geometry) -> float:
    """Bytes one emitted stream-hop moves: its audio in, its slot state
    (tails, skip rings, pool windows, GAP counters) read and written,
    logits out."""
    bits = g.hop_samples * g.stages[0].in_bits
    for s in g.stages:
        bits += 2 * (s.tail * s.cin * s.in_bits + s.phase * s.cout)
    bits += 2 * g.gap_channels * g.gap_bits
    bits += g.fcs[-1][1] * LOGIT_BITS
    return bits / 8


def weight_bytes(g: Geometry) -> float:
    """Ternary weights of the whole network (convs, projections, FCs),
    read once per batched step."""
    n = sum(s.k * s.cin * s.cout for s in g.stages if s.kind == "conv")
    n += sum(s.cin * s.cout for s in g.stages if s.proj)
    n += sum(ci * co for ci, co in g.fcs)
    return n * WEIGHT_BITS / 8


def least_step_s(g: Geometry, stream_hops: int, peak: dict) -> tuple[float,
                                                                    str]:
    """The least time a chip could take for one batched step that emits
    ``stream_hops`` stream-hops, and which bound sets it."""
    t_ops = stream_hops * ops_per_hop(g) / peak["int8_ops_per_s"]
    t_mem = (weight_bytes(g) + stream_hops * bytes_per_hop(g)) \
        / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
