"""Shared helpers of the benchmark's CPU tests: a tiny cell of the real
harness (width 8, a few streams, a window of a fraction of a second)."""
from __future__ import annotations

import copy
import os
import pathlib
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
for p in (str(BENCH / "configs"), str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


def small_config(cfg: dict, w: int = 8) -> dict:
    """``cfg`` with every channel count scaled from 64 down to ``w``."""
    cfg = copy.deepcopy(cfg)
    ch = {"l0": (1, w), "b1": (w, 2 * w), "b2": (2 * w, 4 * w),
          "b3": (4 * w, int(5.5 * w))}
    for ly in cfg["layers"]:
        if ly["kind"] == "conv":
            ly["cin"], ly["cout"] = ch[ly["name"]]
        elif ly["kind"] == "gap":
            ly["channels"] = int(5.5 * w)
        elif ly["name"] == "fc1":
            ly["cin"], ly["cout"] = int(5.5 * w), 8 * w
        else:
            ly["cin"] = 8 * w
    cfg["program"]["args"]["width"] = w
    return cfg


def tiny_cell(name: str, streams: int = 8) -> dict:
    c = harness.cell(harness.load_benchmark(), name)
    c["config"] = small_config(c["config"])
    c["mix"]["streams"] = streams
    c["mix"]["lead_in_s"] = 0.2
    return c


def execute(c: dict, seed: int = 2**31 + 99, seconds: float = 0.6) -> dict:
    import json

    import jax

    line = harness.execute(c, seed, seconds, False, jax.devices(),
                           time.perf_counter())
    return json.loads(line)
