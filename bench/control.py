#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/control.py --workload kws_rt --seconds 5 --seeds 1 2 3

For each seed, one short window of the cell at its own load, in one
process; then each compared number twice: for the program's answers
(the lower reading; every sound run must read 0) and for the control,
the reference at 4-bit audio put in the program's place (the upper
reading; it has to read above the limit).  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import harness

    c = harness.cell(harness.load_benchmark(), args.workload)
    harness.open_chips(int(c["workload"]["chips"]), "control")
    for seed in args.seeds:
        run = harness.Run(c, seed, args.seconds, False)
        t0 = time.perf_counter()
        run.setup()
        run.window()
        run.close_sampled()
        run.stop()
        prog = harness.check(run)
        ctrl = harness.check(run, control=True)
        fmt = lambda d: " ".join(  # noqa: E731
            f"{k}={v['value']}/{v['of']}" for k, v in d.items())
        print(f"control {args.workload} seed={seed} "
              f"run_s={time.perf_counter() - t0:.1f} failed={run.failed()} "
              f"program: {fmt(prog)} | control: {fmt(ctrl)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
