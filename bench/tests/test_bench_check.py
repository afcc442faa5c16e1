"""The comparison that decides ``correct``: the reference against plain
and independent witnesses, the control, and a run with the timed path
broken underneath for each fault the cells can have."""
import numpy as np
import pytest

from bench_testkit import execute, small_config, tiny_cell

import audio
import harness
import work
import pscnn_kws as ref


@pytest.fixture(scope="module")
def tiny():
    c = tiny_cell("kws_rt")
    cfg = c["config"]
    w, t = ref.make_weights(cfg, 2**33 + 5)
    return cfg, w, t, audio.bank(4, 3)


def test_prefix_logits_equal_the_whole_network(tiny):
    cfg, w, t, bank = tiny
    r = ref.Reference(cfg, w, t, bank[:9000])
    rng = np.random.default_rng(0)
    for n in [112, 113, 239, 240, 9000, *rng.integers(112, 9000, 12)]:
        assert np.array_equal(r.logits(int(n)),
                              ref.offline_logits(cfg, w, t, bank[:n]))


def test_reference_matches_the_offline_executor(tiny):
    """A second witness: the repository's offline CIM executor."""
    from repro.core import compiler, executor
    from repro.models import kws

    cfg, w, t, bank = tiny
    clip = bank[:4000]
    spec = kws.build_kws_spec(in_len=clip.size,
                              width=cfg["program"]["args"]["width"])
    prog = compiler.compile_model(spec, w, t, rotate_hints=(),
                                  rowsplit_hints={})
    want = executor.Executor(prog).run(clip[:, None]).output.ravel()
    assert np.array_equal(ref.offline_logits(cfg, w, t, clip), want)


def test_control_reads_above_the_limits(tiny):
    cfg, w, t, bank = tiny
    g = work.geometry(cfg)
    hi = ref.Reference(cfg, w, t, bank[:20000])
    lo = ref.Reference(cfg, w, t, bank[:20000], input_bits=4)
    n = [g.prime_samples + i * g.hop_samples for i in range(1, 150, 3)]
    bad = sum(not np.array_equal(hi.logits(x), lo.logits(x)) for x in n)
    assert bad >= 5, bad


@pytest.mark.parametrize("name", ["kws_rt", "kws_backlog"])
def test_sound_tiny_runs_are_correct_and_the_control_is_not(name):
    c = tiny_cell(name, streams=12)
    run = harness.Run(c, 2**31 + 3, 0.5, False)
    run.setup()
    run.window()
    run.close_sampled()
    prog = harness.check(run)
    ctrl = harness.check(run, control=True)
    assert all(v["value"] == 0 for v in prog.values()), prog
    assert prog["hop_logit_mismatch"]["of"] >= 12
    assert ctrl["hop_logit_mismatch"]["value"] > 0, ctrl


def _broken_step(mode):
    from repro.stream.scheduler import _BatchedModel

    orig = _BatchedModel._step

    def step(self, audio, mask, tails, pendings, gap, model_idx=None, *,
             emit):
        if mode == "half":
            half = (np.arange(mask.shape[0]) % 2 == 0)
            mask = mask & half
        out = orig(self, audio, mask, tails, pendings, gap, model_idx,
                   emit=emit)
        if mode == "unchanged":
            out = (tuple(tails), tuple(pendings), gap, *out[3:])
        return out

    return step


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.stream.scheduler import StreamScheduler, _BatchedModel

    if fault == "altered":
        orig = StreamScheduler.step_batch

        def step_batch(self):
            hb = orig(self)
            if hb is not None and hb.logits is not None:
                hb.logits[::3, 0] += 1
            return hb

        monkeypatch.setattr(StreamScheduler, "step_batch", step_batch)
    else:
        monkeypatch.setattr(_BatchedModel, "_step", _broken_step(fault))
    out = execute(tiny_cell("kws_rt"), seconds=0.4)
    assert out["correct"] is False
    assert out["checks"]["hop_logit_mismatch"]["value"] > 0
