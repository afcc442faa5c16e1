"""Discovery by name, the contract of BENCHMARK.json, the result line,
and the refusals of the command."""
import os
import re
import shutil
import subprocess
import sys

import numpy as np

from bench_testkit import BENCH, ROOT, execute, tiny_cell

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_resolves_to_its_files():
    b = harness.load_benchmark()
    configs = {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert w["config"] in configs
        c = harness.cell(b, w["name"])
        assert c["config"]["name"] == w["config"]
        assert harness.reference(c["config"]).Reference
        assert c["mix"]["loop"] in ("open", "closed")
        names = {m["name"] for m in c["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert c["per_layer"]
        for m in c["per_layer"]:
            assert callable(harness.layer_reader(m["name"]).read)
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")


def test_contract_shapes():
    b = harness.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench"]
    assert 1 <= b["run_seconds"] <= 51
    metrics = b["end_to_end"] + b["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_result_line_keys():
    c = tiny_cell("kws_rt")
    out = execute(c)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in c["end_to_end"]}
    assert {"hop_latency_p50_ms", "setup_s"} <= set(out["metrics"])
    for v in out["metrics"].values():
        assert v["value"] > 0 and v["unit"]
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(v["value"] == 0 and v["limit"] == 0 and v["of"] > 0
               for v in out["checks"].values())


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kws_rt", "--seed",
         "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_refuses_a_cpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "{" not in p.stdout


def test_command_refuses_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench")
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_weights_are_the_configurations_and_inputs_the_seeds():
    """Two seeds run the same model on other audio and traffic, so a
    seed seen first compiles nothing that another seed compiled."""
    runs = []
    for seed in (3, 2**31 + 11):
        run = harness.Run(tiny_cell("kws_rt"), seed, 0.2, False)
        run.setup()
        runs.append(run)
    (wa, ta), (wb, tb) = (r.models[0] for r in runs)
    assert wa.keys() == wb.keys()
    assert all(np.array_equal(wa[k], wb[k]) for k in wa)
    assert all(np.array_equal(ta[k][0], tb[k][0]) for k in ta)
    assert not np.array_equal(runs[0].feed.bank2, runs[1].feed.bank2)
    assert not np.array_equal(runs[0].sch.c_end, runs[1].sch.c_end)


def test_open_loop_survives_a_stall_longer_than_an_inbox():
    """A host that stands still for 5 s, longer than the program's default
    inbox holds (65,536 samples, 4.1 s), delays hops and overflows
    nothing: every hop still comes out, and right."""
    t = [0.0]
    stalled = [False]

    def clock():
        t[0] += 0.002
        if run.opened and not stalled[0]:
            stalled[0] = True
            t[0] += 5.0
        return t[0]

    def sleep(s):
        t[0] += s

    run = harness.Run(tiny_cell("kws_rt"), 2**31 + 5, 6.0, False,
                      clock=clock, sleep=sleep)
    run.setup()
    run.window()
    assert run.deepest > 1 << 16
    assert run.latencies().max() > 5.0
    assert run.failed() == 0
    run.close_sampled()
    assert all(v["value"] == 0 for v in harness.check(run).values())
