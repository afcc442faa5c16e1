"""The traffic generators and the mixes they read."""
import json

import numpy as np
import pytest

from bench_testkit import BENCH

import generator

SR, PRIME, BANK = 16000, 112 + 128, 1 << 20


def _mix(name, **kw):
    m = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    m.update(kw)
    return m


def _arrays(s):
    return [s.offset, s.prefill, s.c_sid, s.c_start, s.c_end, s.c_due,
            s.lengths]


@pytest.mark.parametrize("name", ["kws_rt", "kws_backlog"])
def test_same_seed_same_schedule(name):
    mix = _mix(name, streams=64)
    big = 2**31 + 2**20 + 7
    a = generator.build(mix, big, 3.0, SR, PRIME, BANK)
    b = generator.build(mix, big, 3.0, SR, PRIME, BANK)
    c = generator.build(mix, big + 1, 3.0, SR, PRIME, BANK)
    assert all(np.array_equal(x, y) for x, y in zip(_arrays(a), _arrays(b)))
    assert not all(np.array_equal(x, y)
                   for x, y in zip(_arrays(a), _arrays(c)))


def test_open_loop_chunks_real_time():
    mix = _mix("kws_rt", streams=50)
    run_s = 4.0
    s = generator.build(mix, 5, run_s, SR, PRIME, BANK)
    lens = s.c_end - s.c_start
    assert lens.min() >= 160 and lens.max() <= 1600      # 10-100 ms
    assert np.all(np.diff(s.c_due) >= 0)
    # per stream, chunks are contiguous from the prime on, and the audio
    # due by any time is real time, give or take one chunk and the phase
    for sid in range(s.n_streams):
        m = s.c_sid == sid
        assert s.c_start[m][0] == PRIME
        assert np.array_equal(s.c_start[m][1:], s.c_end[m][:-1])
        due_by_end = s.c_end[m][-1] - PRIME
        assert run_s * SR - 1600 - 0.1 * SR <= due_by_end <= run_s * SR
    # the whole offer is real time for every stream
    assert (s.c_end - s.c_start).sum() == pytest.approx(
        50 * (run_s - 0.05) * SR, rel=0.03)


def test_every_seed_offers_the_same_audio():
    """Sizes are drawn as quantiles in another order per seed, so seeds
    differ in order, not in the amount of work."""
    mix = _mix("kws_rt", streams=200)
    offered = [(s.c_end - s.c_start).sum() for s in (
        generator.build(mix, seed, 6.0, SR, PRIME, BANK)
        for seed in (1, 2**31 + 5, 2**33 + 9))]
    assert max(offered) / min(offered) - 1 < 0.002


def test_closed_loop_lengths():
    s = generator.build(_mix("kws_backlog", streams=32), 3, 2.0, SR, PRIME,
                        BANK)
    assert s.lengths.shape[0] == 32
    assert s.lengths.min() >= 160 and s.lengths.max() <= 1600
    assert np.all(s.prefill == PRIME)


def test_a_mix_names_its_generator(tmp_path, monkeypatch):
    """A new shape of traffic is a new module, found by the name the mix
    gives it; a name with no module is an error."""
    (tmp_path / "one_chunk.py").write_text(
        "import numpy as np\n"
        "from generator import Schedule\n"
        "def build(mix, seed, run_s, sr, prefill, bank_len):\n"
        "    n = mix['streams']\n"
        "    z = np.zeros(1, np.int64)\n"
        "    return Schedule(np.arange(n), np.full(n, prefill), z, z + prefill,\n"
        "                    z + prefill + 160, np.zeros(1), np.zeros((n, 0)))\n")
    monkeypatch.setattr(generator, "GENERATORS", tmp_path)
    s = generator.build(_mix("kws_rt", generator="one_chunk", streams=3), 1,
                        1.0, SR, PRIME, BANK)
    assert s.n_streams == 3 and s.c_end.tolist() == [PRIME + 160]
    with pytest.raises(FileNotFoundError):
        generator.build(_mix("kws_rt", generator="nowhere"), 1, 1.0, SR,
                        PRIME, BANK)
