"""Ragged chunks of audio at real time, or a closed loop of them.

Keys of the mix besides the harness's (``bench/generator.py``):

* ``phase_ms`` (open loop): the range of each stream's start offset.

Open loop: each stream sends whole chunks, their lengths drawn from
``chunk_ms``, each due when its last sample would have been spoken, from
its start phase to the run's end.  Closed loop: each stream has a table
of chunk lengths that the harness cycles through.
"""
from __future__ import annotations

import numpy as np

from generator import Schedule, chunk_table, quantiles


def build(mix: dict, seed: int, run_s: float, sample_rate: int,
          prefill: int, bank_len: int) -> Schedule:
    rng = np.random.default_rng([seed, 0x7AFF1C])
    sr = sample_rate
    lo, hi = (int(ms * sr // 1000) for ms in mix["chunk_ms"])
    n = int(mix["streams"])
    offset = rng.integers(0, bank_len, n)
    pre = np.full(n, prefill, np.int64)
    if mix["loop"] == "closed":
        return Schedule(offset, pre, *(np.zeros(0, np.int64),) * 3,
                        np.zeros(0), chunk_table(rng, n, 64, lo, hi))
    p_lo, p_hi = mix.get("phase_ms", [0, 0])
    t_start = (p_lo + (p_hi - p_lo) * quantiles(rng, n)) / 1000
    # every stream draws m chunks, then keeps those due inside the run
    span = run_s - t_start
    m = int(np.ceil(max(span.max(), 0) * sr / ((lo + hi) / 2) * 1.25)) + 8
    lens = chunk_table(rng, n, m, lo, hi)
    ends = prefill + np.cumsum(lens, axis=1)
    due = t_start[:, None] + (ends - prefill) / sr
    if (due[:, -1] <= run_s).any():
        raise ValueError("chunk table too short for the run")
    keep = due <= run_s
    sid = np.broadcast_to(np.arange(n)[:, None], lens.shape)[keep]
    c_end = ends[keep]
    c_start = c_end - lens[keep]
    c_due = due[keep]
    order = np.argsort(c_due, kind="stable")
    return Schedule(offset, pre, sid[order], c_start[order], c_end[order],
                    c_due[order], np.zeros((n, 0), np.int64))
