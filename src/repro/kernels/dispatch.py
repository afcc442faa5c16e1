"""Trace-time device-dispatch accounting for the Pallas kernels.

Every kernel module routes its ``pl.pallas_call`` through :func:`pallas_call`
below, which bumps a module-global counter *at trace time*.  Because jit
executes the wrapper's python exactly once per trace — and a traced program
executes every ``pallas_call`` it captured once per run — the number of
bumps observed while tracing a function IS its per-execution dispatch
count.  That gives the observability plane an exact ``device_dispatches``
figure without any runtime hook into XLA:

* ``_BatchedModel.dispatches_per_hop`` computes the count statically from
  the plan + backend; ``tests/test_megakernel.py`` asserts it equals the
  traced count from this counter, so the static figure reported per hop in
  ``StreamMetrics`` / trace spans / BENCH_stream.json can never drift from
  the kernels actually launched.

The counter is deliberately dumb (no thread-locals): tests that read it
trace under :func:`counting` which snapshots around a single trace.
"""
from __future__ import annotations

import contextlib

import jax
from jax.experimental import pallas as pl

_dispatches = 0


def bump(n: int = 1) -> None:
    global _dispatches
    _dispatches += n


def count() -> int:
    """Total pallas_call sites traced since import (monotone)."""
    return _dispatches


def pallas_call(*args, **kwargs):
    """Drop-in ``pl.pallas_call`` that records the launch at trace time.

    Refuses the Pallas interpreter on a TPU backend: an interpreted kernel
    there would run, slowly and without a word, in place of the Mosaic
    kernel the caller meant to measure."""
    if kwargs.get("interpret") and jax.default_backend() == "tpu":
        raise ValueError(
            "pallas_call asked to interpret on a TPU backend; pass "
            "interpret=False (or None, resolved by ops.default_interpret)")
    bump()
    return pl.pallas_call(*args, **kwargs)


@contextlib.contextmanager
def counting():
    """Yield a zero-arg callable returning the dispatches traced since
    entry — wrap exactly one ``jax.eval_shape``/first-call trace with it to
    read a function's per-execution dispatch count.  Call
    ``jax.clear_caches()`` first when the function may already be traced:
    a jit cache hit skips the wrapper's python and records nothing."""
    start = count()
    yield lambda: count() - start
