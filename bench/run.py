#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload kws_rt --seed 7 --seconds 20 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; see ``bench/harness.py``.  ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read from a
profiled slice of the window and from the program's own spans.  The last
line of standard output is one JSON object; the checks that decide
``correct`` close standard error.  Without a TPU, or with fewer chips than
the cell asks for, the run prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not here",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import harness

    c = harness.cell(harness.load_benchmark(), args.workload)
    devices = harness.open_chips(int(c["workload"]["chips"]), "bench")
    line = harness.execute(c, args.seed, args.seconds, bool(args.trace),
                           devices, T_START)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
