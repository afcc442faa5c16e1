"""Work of one emitted stream-hop, computed from the network's geometry.

The streaming schedule of a conv -> GAP -> FC network is fixed by its
layer sizes and the hop: how many conv positions each layer computes per
hop, how many frames each layer keeps as its tail, and how many positions
the end-of-stream ("ghost") flush adds when a hop also emits logits.  This
module derives that schedule from the configuration file alone, so the
operation and byte counts read the same whatever backend runs the hop.

Bytes are counted at the algorithm's own widths: 8-bit audio and GAP
counters, 1-bit activations and tails, 16-bit raw logits, and ternary
weights (2 bits) read once per batched step.

A configuration names the module that counts its work (``"work"``); this
one serves the conv -> GAP -> FC networks.  A network of another shape
brings its own module with the same five functions: ``geometry``,
``ops_per_hop``, ``bytes_per_hop``, ``weight_bytes`` and
``least_step_s``; the harness and the readers use no others.
"""
from __future__ import annotations

import dataclasses

LOGIT_BITS = 16
WEIGHT_BITS = 2


@dataclasses.dataclass(frozen=True)
class Stage:
    """One conv layer's per-hop geometry in the steady state."""

    name: str
    k: int
    stride: int
    pad: int
    pool: int
    cin: int
    cout: int
    in_bits: int
    tail: int        # input frames carried from hop to hop (left pad incl.)
    phase: int       # conv outputs waiting for a full pool window
    n_conv: int      # conv positions per hop
    flush_conv: int  # conv positions a ghost flush adds


@dataclasses.dataclass(frozen=True)
class Geometry:
    hop_samples: int
    prime_samples: int
    stages: tuple[Stage, ...]
    fcs: tuple[tuple[int, int], ...]   # (cin, cout) per FC layer
    gap_channels: int
    gap_bits: int


def conv_layers(cfg: dict) -> list[dict]:
    return [ly for ly in cfg["layers"] if ly["kind"] == "conv"]


def fc_layers(cfg: dict) -> list[dict]:
    return [ly for ly in cfg["layers"] if ly["kind"] == "fc"]


def _counts(convs: list[dict], pushes: list[int]):
    """Frames fed / conv positions emitted per layer after ``pushes``
    input chunks; the left pad arrives with a layer's first frame."""
    fed = [0] * len(convs)
    emitted = [0] * len(convs)
    pooled = [0] * len(convs)
    for push in pushes:
        cur = push
        for i, ly in enumerate(convs):
            if fed[i] == 0 and cur > 0:
                fed[i] += ly["pad"]
            fed[i] += cur
            total = (fed[i] - ly["k"]) // ly["stride"] + 1 \
                if fed[i] >= ly["k"] else 0
            emitted[i] = total
            new_pool = total // ly["pool"] - pooled[i]
            pooled[i] += new_pool
            cur = new_pool
    return fed, emitted


def geometry(cfg: dict) -> Geometry:
    """The steady streaming schedule at ``cfg["hop_frames"]`` final frames
    per hop.  The prime is the shortest prefix, a multiple of the first
    stride, after which every layer has seen a whole receptive field."""
    convs = conv_layers(cfg)
    unit = 1
    for ly in convs:
        unit *= ly["stride"] * ly["pool"]
    hop = cfg["hop_frames"] * unit
    s0 = convs[0]["stride"]
    prime = 0
    for p in range(s0, 64 * unit + 1, s0):
        f, ok = p, True
        for ly in convs:
            if ly["pad"] + f < ly["k"]:
                ok = False
                break
            f = ((ly["pad"] + f - ly["k"]) // ly["stride"] + 1) // ly["pool"]
        if ok:
            prime = p
            break
    if not prime:
        raise ValueError("no priming prefix")
    fed1, em1 = _counts(convs, [prime, hop])
    fed2, em2 = _counts(convs, [prime, hop, hop])
    stages = []
    f_in = 0
    for i, ly in enumerate(convs):
        tail = fed2[i] - em2[i] * ly["stride"]
        phase = em2[i] % ly["pool"]
        if tail != fed1[i] - em1[i] * ly["stride"]:
            raise ValueError(f"{ly['name']}: no steady tail at this hop")
        avail = tail + f_in + ly["pad"]
        f_conv = (avail - ly["k"]) // ly["stride"] + 1 \
            if avail >= ly["k"] else 0
        f_in = (phase + f_conv) // ly["pool"]
        stages.append(Stage(
            ly["name"], ly["k"], ly["stride"], ly["pad"], ly["pool"],
            ly["cin"], ly["cout"], ly.get("in_bits", 1), tail, phase,
            em2[i] - em1[i], f_conv))
    gap = next(ly for ly in cfg["layers"] if ly["kind"] == "gap")
    fcs = tuple((ly["cin"], ly["cout"]) for ly in fc_layers(cfg))
    return Geometry(hop, prime, tuple(stages), fcs, gap["channels"],
                    gap["not_in_spec"]["bits"])


def macs_per_hop(g: Geometry) -> dict[str, int]:
    """Multiply-accumulates of one emitted stream-hop, by part."""
    conv = sum(s.n_conv * s.k * s.cin * s.cout for s in g.stages)
    flush = sum(s.flush_conv * s.k * s.cin * s.cout for s in g.stages)
    fc = sum(ci * co for ci, co in g.fcs)
    return {"conv": conv, "flush": flush, "classifier": fc,
            "total": conv + flush + fc}


def ops_per_hop(g: Geometry) -> int:
    """Operations (two per MAC) of one emitted stream-hop."""
    return 2 * macs_per_hop(g)["total"]


def bytes_per_hop(g: Geometry) -> float:
    """Bytes one emitted stream-hop moves: its audio in, its slot state
    (tails, pool phases, GAP counters) read and written, logits out."""
    bits = g.hop_samples * g.stages[0].in_bits
    for s in g.stages:
        bits += 2 * (s.tail * s.cin * s.in_bits + s.phase * s.cout)
    bits += 2 * g.gap_channels * g.gap_bits
    bits += g.fcs[-1][1] * LOGIT_BITS
    return bits / 8


def weight_bytes(g: Geometry) -> float:
    """Ternary weights of the whole network, read once per batched step."""
    n = sum(s.k * s.cin * s.cout for s in g.stages)
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
