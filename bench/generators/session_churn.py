"""Sessions that come and go: open-loop arrivals of ragged real-time audio.

Keys of the mix besides the harness's (``bench/generator.py``):

* ``streams``: the mean number of sessions open at once.
* ``session_s``: ``{"median": m, "sigma": s}``, a session's length in
  seconds, lognormal.
* ``tenant_zipf_s``: sessions pick a tenant model by Zipf's law with this
  exponent over the configuration's tenants.
* ``phase_ms``: the range of the start phase of sessions already open.

The run starts in the steady state: ``streams`` sessions are open at 0,
each with what is left of its session (the residual length of a session
met at a random time: a length-biased draw, cut at a uniform point).
New sessions then arrive as a Poisson process at the rate that keeps the
mean at ``streams``.  Every session sends whole chunks, their lengths
drawn from ``chunk_ms``, each due when its last sample would have been
spoken; it closes when its last chunk is due.  A session that arrives
sends its first chunk from sample 0; one open at the start has its
prefill pushed in set-up, as ``ragged_chunks`` does.

Sizes come from evenly spaced quantiles in another order for every seed
(``generator.quantiles``): arrival gaps, lengths and tenants form the
same set for every seed, so seeds differ in order, not in work.
"""
from __future__ import annotations

import statistics

import numpy as np

from generator import Schedule, quantiles

_NORMAL = np.vectorize(statistics.NormalDist().inv_cdf)


def lognormal(rng, n: int, median: float, sigma: float,
              biased: bool = False) -> np.ndarray:
    """``n`` lognormal lengths; ``biased``: drawn in proportion to length
    (the lengths of the sessions open at a random instant)."""
    mu = np.log(median) + (sigma * sigma if biased else 0.0)
    return np.exp(mu + sigma * _NORMAL(quantiles(rng, n)))


def zipf_tenants(rng, n: int, tenants: int, s: float) -> np.ndarray:
    """``n`` tenant indices, tenant ``i`` with weight ``(i + 1) ** -s``."""
    w = np.arange(1, tenants + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(w) / w.sum()
    return np.minimum(np.searchsorted(cdf, quantiles(rng, n), side="right"),
                      tenants - 1)


def build(mix: dict, seed: int, run_s: float, sample_rate: int,
          prefill: int, bank_len: int, tenants: int = 1) -> Schedule:
    rng = np.random.default_rng([seed, 0x5E551])
    sr = sample_rate
    lo, hi = (int(ms * sr // 1000) for ms in mix["chunk_ms"])
    conc = float(mix["streams"])
    med, sigma = mix["session_s"]["median"], mix["session_s"]["sigma"]
    rate = conc / (med * np.exp(sigma * sigma / 2))   # Little's law
    # sessions open at the start, with what is left of each
    n0 = int(round(conc))
    left = lognormal(rng, n0, med, sigma, biased=True) * quantiles(rng, n0)
    p_lo, p_hi = mix.get("phase_ms", [0, 0])
    phase = (p_lo + (p_hi - p_lo) * quantiles(rng, n0)) / 1000
    # arrivals: exponential gaps, as a fixed set in another order
    gaps = -np.log1p(-quantiles(rng, int(np.ceil(rate * run_s)) + 1)) / rate
    arrive = np.cumsum(gaps)
    arrive = arrive[arrive < run_s]
    n1 = arrive.size
    life = lognormal(rng, n1, med, sigma)
    n = n0 + n1
    join = np.concatenate([np.zeros(n0), arrive])
    start = np.concatenate([phase, arrive])        # first chunk's clock
    end = np.concatenate([phase + left, arrive + life])
    pre = np.concatenate([np.full(n0, prefill, np.int64),
                          np.zeros(n1, np.int64)])
    tenant = zipf_tenants(rng, n, tenants, float(mix["tenant_zipf_s"]))
    offset = rng.integers(0, bank_len, n)
    # chunk lengths: one pool of quantiles, cut into each session's share
    span = np.minimum(end, run_s) - start
    need = (np.ceil(np.maximum(span, 0) * sr / ((lo + hi) / 2) * 1.25)
            + 8).astype(np.int64)
    pool = (lo + np.floor(quantiles(rng, int(need.sum())) * (hi - lo + 1))
            ).astype(np.int64)
    cuts = np.concatenate([[0], np.cumsum(need)])
    close = np.full(n, np.inf)
    sid, c_start, c_end, c_due = [], [], [], []
    for s in range(n):
        lens = pool[cuts[s]:cuts[s + 1]]
        ends = pre[s] + np.cumsum(lens)
        due = start[s] + (ends - pre[s]) / sr
        # the session's last chunk is the first that reaches its end
        last = int(np.searchsorted(due, end[s], side="left"))
        if last >= lens.size:
            if due[-1] <= run_s:
                raise ValueError("chunk table too short for the run")
        elif due[last] <= run_s:
            close[s] = due[last]
        keep = min(last + 1, int(np.searchsorted(due, run_s, side="right")))
        sid.append(np.full(keep, s, np.int64))
        c_end.append(ends[:keep])
        c_start.append(ends[:keep] - lens[:keep])
        c_due.append(due[:keep])
    c_due = np.concatenate(c_due)
    order = np.argsort(c_due, kind="stable")
    return Schedule(offset, pre, np.concatenate(sid)[order],
                    np.concatenate(c_start)[order],
                    np.concatenate(c_end)[order], c_due[order],
                    np.zeros((n, 0), np.int64), join=join, close=close,
                    tenant=tenant)
