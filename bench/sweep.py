#!/usr/bin/env python3
"""Find a cell's knee: run its traffic at several stream counts.

    python bench/sweep.py --workload kws_rt --seed 11 --seconds 6 \\
        --streams 2048 2560 3072

Each point is one run of the cell with ``streams`` replaced (and the
pinned pool's ``slots``, with ``--slots``), in one process.  A point sustains its load when the buffered audio at the
window's close is no larger than at its open (``backlog hops`` on the
``window:`` line).  The cell's mix then takes 4/5 of the highest
sustained count; the sweep itself is recorded in ``PERF.md``.
"""
from __future__ import annotations

import argparse
import copy
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--streams", type=int, nargs="+", required=True)
    ap.add_argument("--slots", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import harness

    base = harness.cell(harness.load_benchmark(), args.workload)
    devices = harness.open_chips(int(base["workload"]["chips"]), "sweep")
    for n in args.streams:
        c = copy.deepcopy(base)
        c["mix"]["streams"] = n
        if args.slots is not None:
            c["mix"]["slots"] = args.slots
        print(f"sweep point streams={n}", flush=True)
        try:
            line = harness.execute(c, args.seed, args.seconds, False,
                                   devices, time.perf_counter())
        except MemoryError as e:   # an inbox or the pool overflowed
            line = f"overloaded: {e}"
        print(f"sweep result streams={n} {line}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
