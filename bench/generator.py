"""The traffic generators' one interface.

A mix (``bench/traffic/<name>.json``) is parameters only.  Its key
``generator`` names the module that reads it,
``bench/generators/<generator>.py``, whose
``build(mix, seed, run_s, sample_rate, prefill, bank_len)`` returns a
:class:`Schedule`; for a configuration of several tenant models it is
also given ``tenants=<count>``.  A new shape of traffic is a new module there; a new
mix of an existing shape is a new data file.  The harness itself reads
these keys of every mix:

* ``loop``: ``"open"`` (chunks arrive on a clock, whatever the system
  does) or ``"closed"`` (every stream is topped up after each step so it
  is ready at the next one).
* ``streams``: streams (open loop: concurrent sessions; closed loop:
  pinned slots).  Unless the schedule says when each joins and closes,
  all are joined and primed in set-up, each open for the whole run.
* ``slots`` (optional): the pinned slot pool, where sessions come and go
  (default: ``streams``).
* ``chunk_ms``: the range of chunk lengths.
* ``lead_in_s``: seconds of traffic before the measured window opens.
* ``check``: how many streams, and hops per stream, the correctness
  check samples.

Audio is never in the schedule: stream ``s`` reads the bank from
``offset[s]`` on, wrapping at its end, and a chunk is a sample range.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib

import numpy as np

GENERATORS = pathlib.Path(__file__).resolve().parent / "generators"


@dataclasses.dataclass
class Schedule:
    """Everything the run offers, in seconds from the schedule's origin.

    Streams are numbered 0..n-1; that number is also the stream id the
    harness gives the system.  Open loop: chunk ``j`` of the flat arrays
    carries samples ``[c_start[j], c_end[j])`` of stream ``c_sid[j]``
    and is due at ``c_due[j]``; flat arrays are sorted by due time.

    Sessions that come and go: stream ``s`` joins at ``join[s]`` (at or
    before 0: in set-up, with its prefill) and closes at ``close[s]``
    (``inf``: it stays open), and is bound to tenant model ``tenant[s]``.
    Left None, every stream is open for the whole run on the first model.
    """

    offset: np.ndarray       # (n,) bank offset of each stream's sample 0
    prefill: np.ndarray      # (n,) samples pushed in set-up
    c_sid: np.ndarray
    c_start: np.ndarray
    c_end: np.ndarray
    c_due: np.ndarray
    lengths: np.ndarray      # (n, m) closed loop: chunk lengths, cycled
    join: np.ndarray | None = None     # (n,) seconds
    close: np.ndarray | None = None    # (n,) seconds, inf: never
    tenant: np.ndarray | None = None   # (n,) tenant model index

    @property
    def n_streams(self) -> int:
        return self.offset.size


def quantiles(rng, n: int) -> np.ndarray:
    """n evenly spaced probabilities in (0, 1), shuffled: a random size
    drawn through them gives every seed the same amount of work in
    another order."""
    return rng.permutation((np.arange(n) + 0.5) / n)


def chunk_table(rng, n: int, m: int, lo: int, hi: int) -> np.ndarray:
    """(n, m) chunk lengths in samples, uniform over [lo, hi]."""
    q = quantiles(rng, n * m).reshape(n, m)
    return (lo + np.floor(q * (hi - lo + 1))).astype(np.int64)


def build(mix: dict, seed: int, run_s: float, sample_rate: int,
          prefill: int, bank_len: int, **extra) -> Schedule:
    """The schedule of ``mix`` for a run of ``run_s`` seconds, from the
    generator the mix names.  Every stream open at the start is given
    ``prefill`` samples in set-up."""
    path = GENERATORS / f"{mix['generator']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic generator {mix['generator']!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_generator_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build(mix, seed, run_s, sample_rate, prefill, bank_len,
                     **extra)
