"""The configuration names its network, its work count and its tenants;
the session-churn traffic; a tenant pool with sessions that come and go,
checked per tenant."""
import copy
import json
import textwrap

import numpy as np
import pytest

from bench_testkit import BENCH, small_config, tiny_cell

import generator
import harness
import pscnn_kws as ref

SR, PRIME, BANK = 16000, 112 + 128, 1 << 20


def _w64():
    return json.loads((BENCH / "configs" / "pscnn_kws_w64.json").read_text())


# -- a network the harness has never seen ------------------------------------

BUILDER = '''
import dataclasses

from repro.core.cnn_spec import CNN1DSpec, Conv1DSpec, FCSpec, GAPSpec


@dataclasses.dataclass(frozen=True)
class Shortcut1DSpec:
    """A layer kind the harness knows nothing of."""
    channels: int
    source: str
    name: str = "shortcut"


def two_block(width, n_classes, shortcut=False):
    layers = [Conv1DSpec(1, width, k=9, stride=4, pad=4, in_bits=8,
                         in_offset=128, name="c0"),
              Conv1DSpec(width, 2 * width, k=3, pad=1, pool=2, name="c1")]
    if shortcut:
        layers.append(Shortcut1DSpec(2 * width, source="c0"))
    layers += [GAPSpec(2 * width), FCSpec(2 * width, n_classes,
                                          out_raw=True, name="fc")]
    return CNN1DSpec(in_len=800, in_channels=1, in_bits=8,
                     layers=tuple(layers))
'''

WORK = '''
from work import bytes_per_hop, geometry, least_step_s, weight_bytes

OPS_PER_HOP = 7


def ops_per_hop(g):
    return OPS_PER_HOP
'''


def _two_block_config(tmp_path, monkeypatch, shortcut=False) -> dict:
    (tmp_path / "two_block_net.py").write_text(textwrap.dedent(BUILDER))
    (tmp_path / "two_block_work.py").write_text(textwrap.dedent(WORK))
    monkeypatch.syspath_prepend(str(tmp_path))
    layers = [
        {"kind": "conv", "name": "c0", "cin": 1, "cout": 8, "k": 9,
         "stride": 4, "pad": 4, "pool": 1, "in_bits": 8, "in_offset": 128},
        {"kind": "conv", "name": "c1", "cin": 8, "cout": 16, "k": 3,
         "stride": 1, "pad": 1, "pool": 2},
        {"kind": "gap", "name": "gap", "channels": 16,
         "not_in_spec": {"bits": 8, "saturate": 255}},
        {"kind": "fc", "name": "fc", "cin": 16, "cout": 10, "out_raw": True},
    ]
    if shortcut:
        layers.insert(2, {"kind": "shortcut", "name": "s", "channels": 16,
                          "source": "c0"})
    cfg = copy.deepcopy(_w64())
    cfg.update(name="two_block", layers=layers,
               work=str(tmp_path / "two_block_work.py"),
               program={"builder": "two_block_net:two_block",
                        "args": {"width": 8, "n_classes": 10,
                                 "shortcut": shortcut}})
    return cfg


def test_a_new_network_goes_through_to_set_up(tmp_path, monkeypatch):
    """A configuration of another network, with its own builder and work
    count, runs through cell, program_spec and set-up with no file of
    the benchmark changed."""
    cfg = _two_block_config(tmp_path, monkeypatch)
    c = harness.cell(harness.load_benchmark(), "kws_rt")
    c["config"] = cfg
    c["mix"].update(streams=4, lead_in_s=0.1)
    assert [ly.cout for ly in harness.program_spec(cfg).layers[:2]] == [8, 16]
    run = harness.Run(c, 2**31 + 41, 0.2, False)
    assert run.work.ops_per_hop(run.g) == 7
    run.setup()
    assert run.sched.plan.hop_samples == run.g.hop_samples
    run.window()
    run.close_sampled()
    assert all(v["value"] == 0 for v in harness.check(run).values())


def test_a_layer_kind_the_harness_does_not_know(tmp_path, monkeypatch):
    cfg = _two_block_config(tmp_path, monkeypatch, shortcut=True)
    spec = harness.program_spec(cfg)
    assert [harness.layer_kind(ly) for ly in spec.layers] == [
        "conv", "conv", "shortcut", "gap", "fc"]
    cfg["layers"][2]["source"] = "c1"
    with pytest.raises(ValueError, match="source"):
        harness.program_spec(cfg)


def _drop(key):
    return lambda ly: ly.pop(key)


@pytest.mark.parametrize("layer,change", [
    (0, {"k": 17}),
    (1, {"pool": 1}),
    (3, {"cout": 353}),
    (0, _drop("in_offset")),       # a field left at a default it is not
    (6, _drop("out_raw")),
    (4, {"bits": 8}),              # a key that is no field of the layer
    (5, {"kind": "conv"}),
])
def test_a_file_that_differs_in_one_field_is_refused(layer, change):
    cfg = _w64()
    assert harness.program_spec(cfg).layers[0].k == 19
    if callable(change):
        change(cfg["layers"][layer])
    else:
        cfg["layers"][layer].update(change)
    with pytest.raises(ValueError, match=cfg["layers"][layer]["name"]):
        harness.program_spec(cfg)


def test_a_file_with_a_layer_more_is_refused():
    cfg = _w64()
    cfg["layers"].append(dict(cfg["layers"][-1], name="fc3"))
    with pytest.raises(ValueError, match="layers"):
        harness.program_spec(cfg)


def test_w64_work_through_its_named_module():
    cfg = _w64()
    assert cfg["work"] == "bench/work.py"
    work = harness.work_count(cfg)
    g = work.geometry(cfg)
    assert work.macs_per_hop(g) == {
        "conv": 2_804_736, "flush": 1_082_560, "classifier": 186_368,
        "total": 4_073_664}
    assert work.ops_per_hop(g) == 8_147_328
    assert work.bytes_per_hop(g) == 1_266
    assert work.weight_bytes(g) == 161_584


def test_readers_take_the_work_count_from_the_context():
    """The roofline and step_mfu readers count with the configuration's
    work module as the context hands it over, not with bench/work.py."""
    import types

    calls = []

    def least_step_s(g, hops, peak):
        calls.append((g, hops))
        return 1e-3 * hops / 1024, "compute"

    stub = types.SimpleNamespace(ops_per_hop=lambda g: 1_000,
                                 least_step_s=least_step_s)
    geometry = object()
    ctx = harness.Context(
        window_s=1.0, step_hops=np.array([4096, 2048]), spans=[],
        lag_s=np.zeros(0), geometry=geometry, work=stub,
        peak={"int8_ops_per_s": 1e9, "hbm_bytes_per_s": 819e9}, chips=4,
        trace={"busy_s": 0.01, "window_s": 2.0})
    mfu = harness.layer_reader("step_mfu").read(ctx)
    assert mfu == pytest.approx(100 * 1_000 * 6144 / (2.0 * 4 * 1e9))
    roof = harness.layer_reader("hop_step_roofline").read(ctx)
    assert roof == pytest.approx(100 * (1e-3 + 0.5e-3) / 0.01)
    assert calls == [(geometry, 1024), (geometry, 512)]


def test_one_group_is_a_plain_draw_of_the_count():
    """Where every stream is open from set-up on one model, the sample is
    the count drawn from all streams with the seed's generator, as the
    cells of one model always drew it."""
    seed = 2**31 + 57
    run = harness.Run(tiny_cell("kws_rt", streams=20), seed, 0.2, False)
    run.setup()
    k = run.mix["check"]["streams"]
    want = np.zeros(20, bool)
    want[np.random.default_rng([seed, 0xC4EC]).choice(20, min(k, 20),
                                                      replace=False)] = True
    assert np.array_equal(run.rec.sampled, want)


# -- the churn generator ------------------------------------------------------

# a mix of the session_churn generator; its numbers are the test's own
CHURN = {
    "generator": "session_churn", "loop": "open", "streams": 26,
    "slots": 128, "session_s": {"median": 5.0, "sigma": 1.2},
    "tenant_zipf_s": 1.1, "chunk_ms": [10, 100], "phase_ms": [0, 100],
    "lead_in_s": 1.0, "check": {"streams": 16, "hops_per_stream": 40},
}


def _churn(**kw):
    return copy.deepcopy(CHURN) | kw


def _schedule(seed, run_s=30.0, **kw):
    return generator.build(_churn(**kw), seed, run_s, SR, PRIME, BANK,
                           tenants=8)


def test_churn_same_seed_same_schedule():
    big = 2**31 + 2**20 + 9
    a, b, c = (_schedule(s, 6.0, streams=64) for s in (big, big, big + 1))
    for f in ("offset", "prefill", "c_sid", "c_start", "c_end", "c_due",
              "join", "close", "tenant"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert not np.array_equal(a.c_due, c.c_due)


def test_churn_lives_and_tenants_follow_their_parameters():
    s = _schedule(7, 600.0, streams=100)
    arrived = s.join > 0
    done = arrived & np.isfinite(s.close)
    # Poisson arrivals at the rate that keeps 100 open: 100 / E[life]
    rate = 100 / (5.0 * np.exp(1.2 ** 2 / 2))
    assert arrived.sum() == pytest.approx(rate * 600, rel=0.01)
    gaps = np.diff(s.join[arrived])
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)
    # lives: lognormal, median 5 s, sigma 1.2 (sessions that end well
    # inside the run; a life is its last chunk's due, within 0.1 s)
    early = done & (s.join < 300)
    life = np.log(s.close[early] - s.join[early])
    assert np.median(life) == pytest.approx(np.log(5.0), abs=0.06)
    q = np.percentile(life, [16, 84])
    assert (q[1] - q[0]) / 2 == pytest.approx(1.2, rel=0.06)
    # Zipf s = 1.1 over the 8 tenants
    w = np.arange(1, 9) ** -1.1
    share = np.bincount(s.tenant, minlength=8) / s.tenant.size
    assert np.abs(share - w / w.sum()).max() < 0.005
    # steady concurrency from the start
    for t in (0.5, 100.0, 400.0):
        open_ = (s.join <= t) & ((s.close > t) | ~np.isfinite(s.close))
        assert open_.sum() == pytest.approx(100, rel=0.25)


def test_churn_sessions_send_real_time_audio():
    s = _schedule(11, 20.0, streams=40)
    lens = s.c_end - s.c_start
    assert lens.min() >= 160 and lens.max() <= 1600
    assert np.all(np.diff(s.c_due) >= 0)
    for sid in range(s.n_streams):
        m = s.c_sid == sid
        if not m.any():
            continue
        start, end, due = s.c_start[m], s.c_end[m], s.c_due[m]
        assert start[0] == s.prefill[sid]
        assert np.array_equal(start[1:], end[:-1])
        assert due[0] >= s.join[sid]
        # each chunk is due when its last sample has been spoken
        t0 = due[0] - (end[0] - s.prefill[sid]) / SR
        assert np.allclose(due, t0 + (end - s.prefill[sid]) / SR)
        if np.isfinite(s.close[sid]):
            assert s.close[sid] == due[-1]
    assert s.prefill[s.join <= 0].min() == PRIME
    assert not s.prefill[s.join > 0].any()


# -- a tenant pool with sessions that come and go ----------------------------

def _mt_cell():
    c = tiny_cell("kws_rt", streams=10)
    mt8 = json.loads((BENCH / "configs" / "pscnn_kws_w64_mt8.json"
                      ).read_text())
    c["config"] = small_config(mt8, 16)
    c["config"]["program"]["args"]["in_len"] = 800
    c["config"]["tenants"]["count"] = 2
    c["mix"] = _churn(streams=10, lead_in_s=0.2, slots=32,
                      session_s={"median": 0.3, "sigma": 1.2})
    return c


def _mt_run(monkeypatch, seconds=0.8):
    monkeypatch.setattr(harness, "WARM_JOINS", 4)
    run = harness.Run(_mt_cell(), 2**31 + 23, seconds, False)
    run.setup()
    run.window()
    run.close_sampled()
    return run


def test_a_tenant_churn_window_is_correct(monkeypatch):
    run = _mt_run(monkeypatch)
    assert run.in_window(run.join) > 0 and run.in_window(run.close) > 0
    assert run.failed() == 0
    sampled = np.flatnonzero(run.rec.sampled)
    assert set(run.tenant[sampled].tolist()) == {0, 1}
    churned = run.rec.closed[sampled] & (run.join[sampled] > 0)
    assert churned.any()
    checks = harness.check(run)
    assert all(v["value"] == 0 for v in checks.values()), checks
    assert checks["close_logit_mismatch"]["of"] == sampled.size
    ctrl = harness.check(run, control=True)
    assert ctrl["hop_logit_mismatch"]["value"] > 0


def test_a_tenant_given_another_tenants_weights_is_not_correct(monkeypatch):
    from repro.stream import StreamScheduler

    orig = StreamScheduler.register_model
    other = ref.make_weights(_mt_cell()["config"], 5)

    def register_model(self, model_id, weights, thresholds):
        if model_id == "tenant1":
            weights, thresholds = other
        return orig(self, model_id, weights, thresholds)

    monkeypatch.setattr(StreamScheduler, "register_model", register_model)
    checks = harness.check(_mt_run(monkeypatch))
    assert checks["hop_logit_mismatch"]["value"] > 0


def test_a_churn_loop_stalled_at_the_close_is_late_not_wrong(monkeypatch):
    """A host that stands still for 2 s over the window's close: the loop
    then takes what fell due meanwhile in time order, so the pool never
    holds more sessions than the schedule has open (32 slots here), every
    sampled session is offered its audio, late, and the run stays
    correct."""
    monkeypatch.setattr(harness, "WARM_JOINS", 4)
    t = [0.0]
    stalled = [False]

    def clock():
        t[0] += 0.002
        if run.opened and not stalled[0] and t[0] > run.t_hi - 1.5:
            stalled[0] = True
            t[0] += 2.0
        return t[0]

    def sleep(s):
        t[0] += s

    run = harness.Run(_mt_cell(), 2**31 + 29, 2.0, False, clock=clock,
                      sleep=sleep)
    run.setup()
    # the stall spans joins and closes, and more joins than free slots
    over = (run.join > run.t_lo + 0.5) & (run.join <= run.t_lo + 2.0)
    assert over.sum() > 32 - 10
    run.window()
    assert stalled[0] and run.failed() == 0
    # every session due to join or close before the close did so
    live = set(run.sched.active)
    assert all(s in live or run.rec.closed[s]
               for s in np.flatnonzero(run.join <= run.t_hi).tolist())
    assert run.rec.closed[run.close <= run.t_hi].all()
    assert run.rec.pushed[run.rec.sampled].min() > 0
    run.close_sampled()
    checks = harness.check(run)
    assert all(v["value"] == 0 for v in checks.values()), checks
