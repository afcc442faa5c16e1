"""``chip_smoke.py``'s stream phase on the CPU, at the width-16 smoke spec
with interpreted kernels: every hop backend and the async plane stay
bit-exact with ``jnp`` and with the offline executor on ragged chunked
traffic.  The script's own ``main`` refuses a CPU."""
import importlib.util
import pathlib
import sys

import numpy as np
import pytest

from repro.models import kws

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


def test_stream_phase_backends_agree(chip_smoke):
    lines = []
    runs = chip_smoke.stream_phase(kws.build_kws_smoke_spec(), n_streams=8,
                                   seed=3, interpret=True,
                                   report=lines.append)
    assert [r.label for r in runs] == ["jnp", "pallas", "megakernel",
                                       "jnp async"]
    assert [r.launches_per_hop for r in runs] == [0, 9, 1, 0]
    assert all(r.new_jit_entries == 0 for r in runs)  # warm-up covered it
    assert all(sum(map(len, r.hops.values())) > 8 for r in runs)
    assert len(lines) == 5 and "bit-exact" in lines[-1]


def test_check_same_catches_a_flipped_logit(chip_smoke):
    spec = kws.build_kws_smoke_spec()
    weights, thresholds = chip_smoke.make_model(spec, 0)
    _, chunks = chip_smoke.make_traffic(2, spec.in_len, 0)
    run = chip_smoke.stream_run(spec, weights, thresholds, chunks,
                                backend="jnp", interpret=True)
    bad = chip_smoke.Run(**{**run.__dict__, "label": "bad"})
    bad.closes = dict(run.closes)
    sid = next(iter(bad.closes))
    bad.closes[sid] = run.closes[sid] + 1
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_same(run, bad)


def test_traffic_is_ragged_and_whole(chip_smoke):
    clips, chunks = chip_smoke.make_traffic(4, 16000, 7)
    for clip, cs in zip(clips, chunks):
        np.testing.assert_array_equal(np.concatenate(cs), clip)
        assert all(160 <= len(c) <= 1600 for c in cs[:-1])


def test_main_refuses_a_cpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """``$JAX_COMPILATION_CACHE_DIR`` places the cache when set; otherwise
    it is the fixed, gitignored ``<repo>/.jax_cache``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    else:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        got = compile_cache.use_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert ".jax_cache/" in (ROOT / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
