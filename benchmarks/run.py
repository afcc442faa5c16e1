"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call/value,derived`` CSV rows (repo convention).

  PYTHONPATH=src python -m benchmarks.run            # all tables
  PYTHONPATH=src python -m benchmarks.run table1 pwb # subset
"""
from __future__ import annotations

import sys
import traceback

SUITES = {
    "table1": "benchmarks.table1",          # Table I perf summary
    "pwb": "benchmarks.pwb_latency",        # §II-H fused pooling -35.9%
    "twm": "benchmarks.twm_vs_bwm",         # Fig. 3 sensing margin
    "pingpong": "benchmarks.pingpong_bench",  # Fig. 5 flexible SRAM
    "wstream": "benchmarks.weight_stream",  # §II-G weight replacement
    "kws": "benchmarks.kws_accuracy",       # §III-A network simulation
    "kernel": "benchmarks.kernel_bench",    # beyond-paper kernel duel
    "roofline": "benchmarks.roofline_table",  # dry-run aggregation
    "stream": "benchmarks.stream_bench",    # multi-stream always-on runtime
}


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    which = sys.argv[1:] or list(SUITES)
    print("name,us_per_call,derived")
    failed = []
    for key in which:
        mod_name = SUITES[key]
        try:
            mod = __import__(mod_name, fromlist=["run"])
            for line in mod.run():
                print(line, flush=True)
        except Exception as e:  # noqa: BLE001
            failed.append(key)
            print(f"{key}.ERROR,{type(e).__name__},{e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmark suites failed: {failed}")


if __name__ == "__main__":
    main()
