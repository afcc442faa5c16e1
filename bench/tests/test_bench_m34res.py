"""The M34-res configuration: the harness builds its network from the
file, the plain reference holds to the whole-clip forward, the work count
holds to a hand count and to ``bench/work.py``, the control reads
mismatches, and a run whose hop step drops the shortcut or skips a
standalone pool reads ``correct`` false."""
import copy
import json

import numpy as np
import pytest

from bench_testkit import BENCH, execute

import audio
import harness
import m34res as ref
import peaks
import work
import work_res

SMALL_ARGS = {"in_len": 1024, "n_classes": 12, "widths": [6, 10],
              "blocks": [2, 2], "k0": 8, "stride0": 2, "pad0": 3, "pool": 2}


def _cfg(name="m34res_kws_8k"):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def small_config() -> dict:
    """The configuration cut to ``rawres.build_m34res_small_spec``'s
    network: widths 6 and 10, two blocks a stage, pools of 2."""
    cfg = copy.deepcopy(_cfg())
    res = lambda n, ci, co: {  # noqa: E731
        "kind": "res", "name": n, "cin": ci, "cout": co, "k": 3, "pad": 1,
        **({"proj": True} if ci != co else {})}
    cfg["layers"] = [
        {"kind": "conv", "name": "conv0", "cin": 1, "cout": 6, "k": 8,
         "stride": 2, "pad": 3, "pool": 2, "in_bits": 8, "in_offset": 128},
        res("s1b1", 6, 6), res("s1b2", 6, 6),
        {"kind": "pool", "name": "pool1", "channels": 6, "pool": 2},
        res("s2b1", 6, 10), res("s2b2", 10, 10),
        {"kind": "gap", "name": "gap", "channels": 10,
         "not_in_spec": {"bits": 8, "saturate": 255}},
        {"kind": "fc", "name": "fc", "cin": 10, "cout": 12, "in_bits": 8,
         "out_raw": True},
    ]
    cfg["program"]["args"] = dict(SMALL_ARGS)
    return cfg


def tiny_cell(streams: int = 8) -> dict:
    c = harness.cell(harness.load_benchmark(), "m34res_backlog")
    c["config"] = small_config()
    c["mix"].update(streams=streams, lead_in_s=0.2)
    return c


@pytest.fixture(scope="module")
def full():
    cfg = _cfg()
    w, t = ref.make_weights(cfg, cfg["weights"]["seed"])
    return cfg, w, t, audio.bank(6, 3)


def test_program_spec_takes_the_file_layer_for_layer():
    cfg = _cfg()
    spec = harness.program_spec(cfg)
    kinds = [harness.layer_kind(ly) for ly in spec.layers]
    assert kinds.count("res") == 16 and kinds.count("pool") == 3
    assert spec.total_weights == 4_058_880
    g = work_res.geometry(cfg)
    from repro.stream import plan_stream

    plan = plan_stream(spec, hop_frames=cfg["hop_frames"])
    assert (g.hop_samples, g.prime_samples) == (plan.hop_samples,
                                                plan.prime_samples)
    assert (g.hop_samples, cfg["sample_rate"]) == (1024, 8000)
    bad = copy.deepcopy(cfg)
    bad["layers"][5].pop("proj")          # s2b1 projects its shortcut
    with pytest.raises(ValueError, match="s2b1"):
        harness.program_spec(bad)


def test_small_config_is_the_small_preset():
    from repro.models import rawres

    spec = harness.program_spec(small_config())
    assert spec.layers == rawres.build_m34res_small_spec().layers


def test_prefix_logits_equal_the_whole_network():
    cfg = small_config()
    w, t = ref.make_weights(cfg, 2**33 + 7)
    clip = audio.bank(3, 1)[:3000]
    r = ref.Reference(cfg, w, t, clip)
    rng = np.random.default_rng(1)
    for n in [60, 61, 68, 75, 76, 2999, 3000, *rng.integers(60, 3000, 30)]:
        assert np.array_equal(r.logits(int(n)),
                              ref.offline_logits(cfg, w, t, clip[:n])), n


def test_reference_matches_the_program_forward_at_full_size(full):
    """A second witness: the repository's plain whole-clip forward
    (``rawres.offline_logits``), and the prefix reference at full size."""
    from repro.models import rawres

    cfg, w, t, bank = full
    spec = harness.program_spec(cfg)
    clip = bank[:24000]
    want = ref.offline_logits(cfg, w, t, clip)
    assert np.array_equal(rawres.offline_logits(spec, w, t, clip), want)
    g = work_res.geometry(cfg)
    r = ref.Reference(cfg, w, t, clip)
    for n in (g.prime_samples + g.hop_samples, 17_777, 24_000):
        assert np.array_equal(r.logits(n),
                              ref.offline_logits(cfg, w, t, clip[:n]))


def test_the_shortcut_decides_a_share_of_block_outputs(full):
    cfg, w, t, bank = full
    x = ref._audio(cfg, bank[:20000], 8)
    shares = []
    for li, ly in enumerate(cfg["layers"]):
        if ly["kind"] == "conv":
            x = ref._pool(ref._sa(ref._conv_raw(
                ref._pad(x, ly["pad"], ly["pad"]), w[li], ly["stride"]),
                t[li]), ly["pool"])
        elif ly["kind"] == "pool":
            x = ref._pool(x, ly["pool"])
        elif ly["kind"] == "res":
            p = ly["pad"]
            h = ref._sa(ref._conv_raw(ref._pad(x, p, p), w[li]["w1"], 1),
                        t[li]["sa1"])
            s = ref._conv_raw(ref._pad(h, p, p), w[li]["w2"], 1)
            y = ref._join(s, x, w[li], t[li])
            no_g = dict(t[li], gain=0 * t[li]["gain"])
            shares.append((y != ref._join(s, x, w[li], no_g)).mean())
            x = y
    assert len(shares) == 16 and min(shares) > 0.03, shares


def test_control_reads_above_the_limits(full):
    cfg, w, t, bank = full
    g = work_res.geometry(cfg)
    hi = ref.Reference(cfg, w, t, bank[:40000])
    lo = ref.Reference(cfg, w, t, bank[:40000], input_bits=4)
    n = [g.prime_samples + i * g.hop_samples for i in range(1, 28, 3)]
    bad = sum(not np.array_equal(hi.logits(x), lo.logits(x)) for x in n)
    assert bad >= 5, bad


def test_work_count_by_hand_at_the_small_preset():
    g = work_res.geometry(small_config())
    assert (g.hop_samples, g.prime_samples) == (8, 60)
    c0 = 8 * 1 * 6                       # one conv0 position's MACs
    c66, c610, c1010 = 3 * 6 * 6, 3 * 6 * 10, 3 * 10 * 10
    # per hop: conv0 4 positions; stage 1 two frames, stage 2 one frame
    conv = 4 * c0 + 2 * (2 * 2 * c66) + (c610 + c1010) + 2 * c1010
    join = 2 * (2 * 6) + 1 * (6 + 1) * 10 + 1 * 10
    # the ghost flush: each k=3 conv adds the lag of the convs above it
    flush = (2 * c0 + (2 + 3) * c66 + 3 * 6 + (4 + 5) * c66 + 5 * 6
             + 3 * c610 + 4 * c1010 + 4 * 7 * 10 + (5 + 6) * c1010 + 6 * 10)
    fc = 10 * 12
    assert work_res.macs_per_hop(g) == {
        "conv": conv, "join": join, "flush": flush, "classifier": fc,
        "total": conv + join + flush + fc}
    assert work_res.ops_per_hop(g) == 2 * (conv + join + flush + fc)
    # audio 8 x 8 bits; conv0's 7-frame 8-bit tail; 2-frame tails of
    # every block conv and skip ring; GAP; 16-bit logits
    state = 7 * 8 + 2 * (2 * 6 + 2 * 6 + 2 * 6) \
        + (2 * 6 + 2 * 10 + 2 * 6) + (2 * 10 + 2 * 10 + 2 * 10)
    assert work_res.bytes_per_hop(g) == (8 * 8 + 2 * state + 2 * 10 * 8
                                         + 12 * 16) / 8
    weights = 8 * 6 + 2 * (2 * c66) + (c610 + c1010 + 6 * 10) \
        + 2 * c1010 + 10 * 12
    assert work_res.weight_bytes(g) == weights * 2 / 8


def test_work_count_equals_work_py_on_a_conv_network():
    cfg = _cfg("pscnn_kws_w64")
    a, b = work.geometry(cfg), work_res.geometry(cfg)
    assert (a.hop_samples, a.prime_samples) == (b.hop_samples,
                                                b.prime_samples)
    assert work.ops_per_hop(a) == work_res.ops_per_hop(b) == 8_147_328
    assert work.bytes_per_hop(a) == work_res.bytes_per_hop(b)
    assert work.weight_bytes(a) == work_res.weight_bytes(b)
    p = peaks.peak("TPU v5 lite")
    assert work.least_step_s(a, 4096, p) == work_res.least_step_s(b, 4096, p)


def test_m34res_work_per_stream_hop():
    g = work_res.geometry(_cfg())
    m = work_res.macs_per_hop(g)
    assert m["conv"] + m["join"] == 14_475_264 + 242_304
    assert m["flush"] > 2 * m["conv"]    # the deep lag dominates a hop
    assert work_res.weight_bytes(g) == 4_058_880 * 2 / 8


def test_a_sound_tiny_run_is_correct():
    c = tiny_cell(streams=12)
    run = harness.Run(c, 2**31 + 13, 0.5, False)
    run.setup()
    assert run.sched.plan.hop_samples == run.g.hop_samples
    run.window()
    run.close_sampled()
    prog = harness.check(run)
    assert all(v["value"] == 0 for v in prog.values()), prog
    assert prog["hop_logit_mismatch"]["of"] >= 12
    assert run.failed() == 0


@pytest.mark.parametrize("fault", ["no_shortcut", "no_pool"])
def test_a_step_without_the_shortcut_or_the_pool_is_not_correct(
        fault, monkeypatch):
    import jax.numpy as jnp

    from repro.stream.scheduler import _BatchedModel

    if fault == "no_shortcut":          # g = 0: the join reads s alone
        def join(self, i, ring, x, s, model_idx=None):
            xs = ring if x is None else jnp.concatenate([ring, x], axis=1)
            return self._sa(i, s, model_idx), xs[:, s.shape[1]:]

        monkeypatch.setattr(_BatchedModel, "_join", join)
    else:           # the pool takes each window's first frame, no OR
        def pool(self, i, pending, y, n_out):
            p = self.plan.stages[i].pool
            frames = jnp.concatenate([pending, y], axis=1)
            return frames[:, :n_out * p:p], frames[:, n_out * p:]

        monkeypatch.setattr(_BatchedModel, "_pool", pool)
    out = execute(tiny_cell(), seconds=0.4)
    assert out["correct"] is False
    assert out["checks"]["hop_logit_mismatch"]["value"] > 0
