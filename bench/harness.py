"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the network as it is run, naming its
  plain reference ``bench/configs/<reference>.py``, the program's builder
  of the network (``program``), the module that counts its work
  (``work``) and, where several models share the pool, its tenants;
* ``bench/traffic/<traffic>.json``: the mix, naming the generator
  ``bench/generators/<generator>.py`` that reads it (``generator.py``);
* ``bench/layer_metrics/<metric>.py``: a ``read(ctx)`` that returns the
  metric, or None where the run gives it nothing to read.  A metric named
  ``<base>.<split>`` falls back to ``<base>.py`` when it has no file of
  its own.

The system under test is ``repro.stream.StreamScheduler`` with its
default backend: the window drives ``push_audio_batch`` and
``step_batch``, and ``add_stream`` and ``close_stream`` where sessions
come and go.  A cell on several chips spreads the slot pool over
``make_stream_mesh(chips)``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(BENCH / "configs")]

import audio  # noqa: E402
import generator  # noqa: E402

BANK_SECONDS = 64
TRACE_S = 2.0          # length of a traced run's window, all profiled
SLACK_S = 2.0          # schedule beyond the window's planned close
WARM_JOINS = 16        # most joins primed together, where sessions join


# -- discovery ---------------------------------------------------------------

def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    # a dataclass looks its module up by name while it is defined
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(bench: dict, name: str) -> dict:
    """A workload with its configuration, mix and metrics resolved."""
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = json.loads((BENCH / "configs" / f"{wl['config']}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{wl['traffic']}.json"
                      ).read_text())

    def mine(m):
        return name in m["workloads"] if "workloads" in m else True

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return {"workload": wl, "config": cfg, "mix": mix, "end_to_end": e2e,
            "per_layer": layer}


def layer_reader(name: str):
    """The reader module of per-layer metric ``name``."""
    d = BENCH / "layer_metrics"
    for stem in (name, name.split(".")[0]):
        path = d / f"{stem}.py"
        if path.exists():
            return load_module(path)
    raise FileNotFoundError(f"no reader for per-layer metric {name!r}")


def reference(cfg: dict):
    return load_module(BENCH / "configs" / f"{cfg['reference']}.py")


def work_count(cfg: dict):
    """The module that counts the network's work (``cfg["work"]``, a path
    from the checkout's root): ``geometry``, ``ops_per_hop``,
    ``bytes_per_hop``, ``weight_bytes`` and ``least_step_s``."""
    return load_module(ROOT / cfg["work"])


def open_chips(chips: int, who: str) -> list:
    """The TPU devices of this machine, with the compile cache on; a
    message and ``SystemExit(2)`` where there is no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"{who}: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"{who}: needs {chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        raise SystemExit(2)
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    # cache every program, however quick to compile, so that a second run
    # of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"jax {jax.__version__} platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind} device_count="
          f"{len(devices)} compile_cache={cache}", flush=True)
    return devices


# -- the system under test ---------------------------------------------------

# keys of a configuration's layer that are not the program's to state
UNCOMPARED = ("kind", "name", "not_in_spec")


def layer_kind(layer) -> str:
    """A program layer's kind: its ``kind`` attribute where it has one,
    else its class name less ``Spec`` and a trailing ``<n>D``, in lower
    case (``Conv1DSpec``: ``conv``, ``GAPSpec``: ``gap``)."""
    kind = getattr(layer, "kind", None)
    if kind is not None:
        return kind
    name = type(layer).__name__.removesuffix("Spec")
    return re.sub(r"\d+D$", "", name).lower()


def program_spec(cfg: dict):
    """The program's network for ``cfg``, from the builder the file names
    (``program``: ``{"builder": "<module>:<function>", "args": {...}}``);
    refuses a file that does not describe it layer for layer.  Each key of
    a file's layer, but for ``UNCOMPARED``, equals the program layer's
    field of that name, and a field the file leaves out holds its
    default."""
    mod, _, fn = cfg["program"]["builder"].partition(":")
    spec = getattr(importlib.import_module(mod), fn)(**cfg["program"]["args"])
    if len(spec.layers) != len(cfg["layers"]):
        raise ValueError(f"configuration has {len(cfg['layers'])} layers, "
                         f"the program's network {len(spec.layers)}")
    for ly, ps in zip(cfg["layers"], spec.layers):
        fields = {f.name: f.default for f in dataclasses.fields(ps)}
        bad = [] if layer_kind(ps) == ly["kind"] else ["kind"]
        bad += [k for k, v in ly.items() if k not in UNCOMPARED
                and (k not in fields or getattr(ps, k) != v)]
        bad += [k for k, d in fields.items() if k not in UNCOMPARED
                and k not in ly and getattr(ps, k) != d]
        if bad:
            raise ValueError(f"configuration layer {ly['name']} differs "
                             f"from the program's {ps} in {bad}")
    return spec


def model_ids(cfg: dict) -> list[str]:
    """The id the scheduler knows each tenant model by; the first is the
    scheduler's construction model."""
    from repro.stream import DEFAULT_MODEL

    n = cfg.get("tenants", {}).get("count", 1)
    return [DEFAULT_MODEL] + [f"tenant{i}" for i in range(1, n)]


def build_system(cfg: dict, slots: int, models: list, obs,
                 inbox: int | None = None, chips: int = 1):
    """The scheduler with its slot pool pinned at ``slots``, each inbox
    ``inbox`` samples deep (None: the program's default), every tenant
    model registered, and the pool spread over ``chips`` chips."""
    from repro.stream import StreamScheduler

    kw = {}
    if "tenants" in cfg:
        kw = {"max_models": cfg["tenants"]["count"],
              "tenant_block": cfg["tenants"]["tenant_block"]}
    if chips > 1:
        from repro.launch.mesh import make_stream_mesh

        kw["mesh"] = make_stream_mesh(chips)
    sched = StreamScheduler(program_spec(cfg), *models[0], capacity=slots,
                            initial_capacity=slots, min_capacity=slots,
                            hop_frames=cfg["hop_frames"], obs=obs,
                            inbox_samples=inbox, **kw)
    if "tenants" in cfg:
        for mid, (w, t) in zip(model_ids(cfg), models):
            sched.register_model(mid, w, t)
    return sched


# -- recording ---------------------------------------------------------------

class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while on."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compiles = 0
        self.cache_loads = 0
        self.on = False
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _ev(self, event, **kw) -> None:
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1


@dataclasses.dataclass
class Record:
    """What the window saw, in seconds from the schedule's origin."""

    n: int
    sampled: np.ndarray                       # (n,) bool
    hops_done: np.ndarray = None
    pushed: np.ndarray = None
    step_t: list = dataclasses.field(default_factory=list)
    step_sids: list = dataclasses.field(default_factory=list)
    step_idx: list = dataclasses.field(default_factory=list)
    rows: dict = dataclasses.field(default_factory=dict)   # sid -> {idx: logits}
    closes: dict = dataclasses.field(default_factory=dict)  # sid -> logits
    lag: list = dataclasses.field(default_factory=list)
    recording: bool = False
    closed: np.ndarray = None                # (n,) bool: its session closed

    def __post_init__(self) -> None:
        self.hops_done = np.zeros(self.n, np.int64)
        self.pushed = np.zeros(self.n, np.int64)
        self.closed = np.zeros(self.n, bool)

    def step(self, hb, t: float) -> None:
        sids = hb.sids
        idx = self.hops_done[sids] + 1
        self.hops_done[sids] = idx
        if self.recording:
            self.step_t.append(t)
            self.step_sids.append(sids)
            self.step_idx.append(idx)
        m = self.sampled[sids]
        if m.any():
            for s, i, lg in zip(sids[m].tolist(), idx[m].tolist(),
                                hb.logits[m]):
                self.rows.setdefault(s, {})[i] = lg.copy()


def _null(*a, **k):
    return contextlib.nullcontext()


class Feed:
    """Cuts stream audio out of the bank; ``bank2`` repeats the bank's
    head so that any chunk up to ``pad`` samples is one slice."""

    def __init__(self, bank: np.ndarray, offset: np.ndarray, pad: int):
        self.n = bank.size
        self.bank2 = np.concatenate([bank, bank[:pad]])
        self.offset = offset

    def chunk(self, sid: int, a: int, b: int) -> np.ndarray:
        s = (self.offset[sid] + a) % self.n
        return self.bank2[s:s + (b - a)]

    def stream(self, sid: int, n: int) -> np.ndarray:
        return np.take(self.bank2[:self.n],
                       np.arange(self.offset[sid], self.offset[sid] + n),
                       mode="wrap")


# -- the loops -----------------------------------------------------------------

class Run:
    """One cell's system, schedule and record, from set-up to the check."""

    def __init__(self, c: dict, seed: int, seconds: float, trace: bool,
                 clock=time.perf_counter, sleep=time.sleep) -> None:
        self.c, self.seed, self.seconds, self.trace = c, seed, seconds, trace
        self.cfg, self.mix = c["config"], c["mix"]
        self.clock, self.sleep = clock, sleep
        self.work = work_count(self.cfg)
        self.g = self.work.geometry(self.cfg)
        self.chips = int(c["workload"]["chips"])
        self.ann = _null
        self.opened = False
        self.deepest = 0           # most samples one inbox held in the window
        self.longest_turn = 0.0    # longest turn of the loop in the window

    # set-up ------------------------------------------------------------------

    def setup(self) -> None:
        from repro.obs import Observability

        cfg, mix = self.cfg, self.mix
        clock, t0 = self.clock, self.clock()
        self.setup_split = split = {}

        def lap(name):
            nonlocal t0
            split[name] = round(clock() - t0, 3)
            t0 = clock()

        self.counter = CompileCounter()
        self.counter.on = True
        # the weights are the configuration's own, whatever the seed: a
        # checkpoint belongs to the configuration, and the hop step is
        # compiled for its weights, so new weights would compile anew.
        # Tenant i's come from the configuration's seed + i.
        seed0 = cfg["weights"]["seed"]
        self.models = [reference(cfg).make_weights(cfg, seed0 + i)
                       for i in range(cfg.get("tenants", {}).get("count", 1))]
        self.ids = model_ids(cfg)
        lead = float(mix["lead_in_s"])
        # the window closes `seconds` after it opens; the schedule runs
        # on past that, so a late opening never starves it
        self.t_lo, self.t_hi = lead, lead + self.seconds
        bank = audio.bank(self.seed, BANK_SECONDS)
        # set-up gives every stream its prime and one hop, and steps
        # once: the first hop on freshly primed state then
        # compiles whatever it needs before the window
        g = self.g
        extra = {"tenants": len(self.models)} if "tenants" in cfg else {}
        self.sch = sch = generator.build(
            mix, self.seed, self.t_hi + SLACK_S, cfg["sample_rate"],
            g.prime_samples + g.hop_samples, bank.size, **extra)
        n = sch.n_streams
        self.join = np.zeros(n) if sch.join is None else sch.join
        self.close = np.full(n, np.inf) if sch.close is None else sch.close
        self.tenant = np.zeros(n, np.int64) if sch.tenant is None \
            else sch.tenant
        hi = int(mix["chunk_ms"][1] * cfg["sample_rate"] // 1000)
        self.feed = Feed(bank, sch.offset,
                         max(hi, g.prime_samples + g.hop_samples))
        self.ptr = np.zeros(n, np.int64)  # closed loop
        lap("inputs")
        self.obs = Observability.create(trace_capacity=1 << 20,
                                        mirror_events=False)
        # open loop: each inbox holds all the audio the run offers its
        # stream, so a host that stands still for seconds delays hops and
        # never overflows an inbox (the program's default holds 4 s)
        inbox = None if mix["loop"] == "closed" else int(
            self.sch.c_end.max(initial=0))
        self.sched = build_system(cfg, int(mix.get("slots", n)), self.models,
                                  self.obs, inbox, self.chips)
        lap("build")
        # the pool is pinned: the run never leaves its one capacity
        self.caps = [self.sched.capacity]
        self.sched.warm(self.sched.capacity)
        every = np.flatnonzero(self.join <= 0)
        batch = every.size
        if (self.join > 0).any():
            # each new size of a batch of joins primed together compiles
            # the program's scatters: set-up warms sizes up to WARM_JOINS
            # and primes its own sessions in batches no larger, whatever
            # the seed's tenants
            batch = WARM_JOINS
            self._warm_joins(batch)
        lap("warm")
        for s in every.tolist():
            self._join(s)
        lap("join")
        rng = np.random.default_rng([self.seed, 0xC4EC])
        self.rec = Record(n, self._sample(rng))
        for part in np.array_split(every, -(-every.size // batch)):
            self._push(part, np.zeros(part.size, np.int64),
                       sch.prefill[part])
            hb = self.sched.step_batch()    # primes them and runs one hop
            if hb is not None:
                self.rec.step(hb, 0.0)
        lap("prime_and_first_hop")
        self.counter.on = False
        # set-up's objects never die: keep the collector from walking them
        # in the window (a full collection of them stalls for ~0.1 s)
        gc.collect()
        gc.freeze()
        split["compiles"] = self.counter.compiles
        split["cache_loads"] = self.counter.cache_loads

    def _model(self, tenant: int) -> str | None:
        """The model id a stream of ``tenant`` binds to (None: the one
        model of a scheduler without tenants)."""
        return self.ids[tenant] if "tenants" in self.cfg else None

    def _join(self, sid: int) -> None:
        self.sched.add_stream(sid=sid, model=self._model(self.tenant[sid]))

    def _warm_joins(self, most: int) -> None:
        """Join, prime and close 1 to ``most`` streams at once, so that
        every batch of joins the window can prime has its programs
        compiled in set-up.  Their stream ids lie past the schedule's."""
        sch, g = self.sch, self.g
        sid = sch.n_streams
        audio = self.feed.chunk(0, 0, g.prime_samples + g.hop_samples)
        for k in range(1, most + 1):
            sids = list(range(sid, sid + k))
            sid += k
            for s in sids:
                self.sched.add_stream(sid=s, model=self._model(0))
            self.sched.push_audio_batch(sids, [audio] * k)
            self.sched.step_batch()
            for s in sids:
                self.sched.close_stream(s)

    def _sample(self, rng) -> np.ndarray:
        """Streams whose hops the check compares, drawn from the seed.
        Streams fall in groups by tenant and by whether they join and
        close inside the window; the draw's count goes round the groups
        (each tenant's churning sessions, then its others), one stream a
        group a pass, and each group's streams are drawn from the seed.
        With one group this is a plain draw of the count."""
        n = self.sch.n_streams
        m = np.zeros(n, bool)
        window = min(self.seconds, TRACE_S) if self.trace else self.seconds
        churn = (self.join >= self.t_lo) & (self.close <= self.t_lo + window)
        # a stream is eligible where it is open from set-up, or where its
        # first chunk is due a second (a quarter of a shorter window) or
        # more before the window closes
        first = np.full(n, np.inf)
        np.minimum.at(first, self.sch.c_sid, self.sch.c_due)
        live = (self.join <= 0) | (
            first < self.t_lo + window - min(1.0, window / 4))
        groups = {}
        for s in np.flatnonzero(live).tolist():
            key = (int(self.tenant[s]), not churn[s])
            groups.setdefault(key, []).append(s)
        order = sorted(groups)
        quota = dict.fromkeys(order, 0)
        left = min(self.mix["check"]["streams"], int(live.sum()))
        while left:
            for g in order:
                if left and quota[g] < len(groups[g]):
                    quota[g] += 1
                    left -= 1
        for g in order:
            members = np.asarray(groups[g])
            pick = rng.choice(members.size, quota[g], replace=False)
            m[members[pick]] = True
        return m

    def _push(self, sids: np.ndarray, a: np.ndarray, b: np.ndarray) -> None:
        chunk = self.feed.chunk
        self.sched.push_audio_batch(
            sids.tolist(), [chunk(s, x, y) for s, x, y in
                            zip(sids.tolist(), a.tolist(), b.tolist())])
        np.add.at(self.rec.pushed, sids, b - a)

    # the window ---------------------------------------------------------------

    def window(self) -> None:
        """Drive the traffic until the window closes, then drain: no more
        audio, and every hop already buffered still comes out.  A traced
        run starts the profiler before the traffic's clock starts (starting
        it stalls the host for seconds) and its window is the profiled
        slice, ``TRACE_S`` long."""
        self.counter.compiles = self.counter.cache_loads = 0
        tick = self._closed_tick if self.mix["loop"] == "closed" \
            else self._open_tick()
        if self.trace:
            self._start_profiler()
        clock = self.clock
        self.origin = origin = clock()
        prev = None
        while True:
            now = clock() - origin
            if not self.opened and now >= self.t_lo:
                self._mark(now)
            if self.opened:
                if prev is not None:
                    self.longest_turn = max(self.longest_turn, now - prev)
                prev = now
            if now >= self.t_hi:
                break
            tick(now)
        if self.mix["loop"] == "open":
            self._settle(self.t_hi)
        self.t_close = clock() - origin
        self.counter.on = False
        if self.trace:
            self._stop_profiler()
        self.spans = self.obs.trace.spans()
        self.backlog_end = self._backlog()
        self.rec.recording = False
        while (hb := self.sched.step_batch()) is not None:
            self.rec.step(hb, clock() - origin)

    def _mark(self, t: float) -> None:
        """Open the measured window at ``t``."""
        self.opened = True
        self.rec.recording = True
        self.t_open = t
        self.t_hi = t + (min(self.seconds, TRACE_S) if self.trace
                         else self.seconds)
        self.t_open_abs = self.clock()
        self.backlog_open = self._backlog()
        self.obs.trace.reset()
        self.counter.on = True
        if self.trace:
            # a TraceAnnotation's span starts when it is made
            self._slice = self._jax_profiler.TraceAnnotation("bench.window")
            self._slice.__enter__()
            self.ann = self._jax_profiler.TraceAnnotation

    def _start_profiler(self) -> None:
        import jax

        self._jax_profiler = jax.profiler
        self._tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._tdir, profiler_options=opts)

    def _stop_profiler(self) -> None:
        self._slice.__exit__(None, None, None)
        self.ann = _null
        self._jax_profiler.stop_trace()

    def _open_tick(self):
        """One turn of the open loop: join, push and close what is due,
        step once, and sleep to the next due event when no stream is
        ready."""
        sch, rec = self.sch, self.rec
        due = sch.c_due
        pos = [0]
        joins = np.flatnonzero(self.join > 0)
        joins = joins[np.argsort(self.join[joins], kind="stable")]
        j_due = self.join[joins]
        closes = np.flatnonzero(np.isfinite(self.close))
        closes = closes[np.argsort(self.close[closes], kind="stable")]
        x_due = self.close[closes]
        jpos, xpos = [0], [0]

        def join_due(h: float) -> None:
            a = jpos[0]
            b = int(np.searchsorted(j_due, h, side="right"))
            for s in joins[a:b].tolist():
                self._join(s)
            jpos[0] = b

        def push_due(h: float, now: float) -> None:
            i = pos[0]
            j = int(np.searchsorted(due, h, side="right"))
            if j == i:
                return
            with self.ann("bench.push"):
                self._push(sch.c_sid[i:j], sch.c_start[i:j],
                           sch.c_end[i:j])
                if self.opened:
                    rec.lag.append(now - due[i:j])
                    sids = sch.c_sid[i:j]
                    self.deepest = max(self.deepest, int(
                        (rec.pushed[sids] - self.g.prime_samples
                         - rec.hops_done[sids] * self.g.hop_samples).max()))
            pos[0] = j

        def close_due(h: float) -> None:
            a = xpos[0]
            b = int(np.searchsorted(x_due, h, side="right"))
            for s in closes[a:b].tolist():
                res = self.sched.close_stream(s)
                rec.closed[s] = True
                if rec.sampled[s]:
                    rec.closes[s] = (res.logits, int(rec.pushed[s]))
            xpos[0] = b

        def catch_up(h: float, now: float) -> None:
            """Join, push and close what is due by ``h``, in time order,
            with ``now`` the time of the push: a turn that ran late takes
            the closes due in the order of their times, each after the
            joins and audio due before it, so the pool never holds more
            sessions than the schedule has open."""
            while True:
                upto = h
                if xpos[0] < x_due.size and x_due[xpos[0]] < h:
                    upto = x_due[xpos[0]]
                if joins.size:
                    with self.ann("bench.join"):
                        join_due(upto)
                push_due(upto, now)
                if closes.size:
                    with self.ann("bench.close"):
                        close_due(upto)
                if upto == h:
                    return
                now = self.clock() - self.origin

        def settle(h: float) -> None:
            """Where sessions come and go: the sessions due to join or
            close before the window closed, and their audio due, are
            offered however late the loop ran."""
            if joins.size or closes.size:
                catch_up(h, self.clock() - self.origin)

        self._settle = settle

        def tick(now: float) -> None:
            catch_up(now, now)
            with self.ann("bench.step_batch"):
                hb = self.sched.step_batch()
            t = self.clock() - self.origin
            if hb is None:
                i = pos[0]
                nxt = min(due[i] if i < due.size else self.t_hi,
                          j_due[jpos[0]] if jpos[0] < j_due.size
                          else self.t_hi,
                          x_due[xpos[0]] if xpos[0] < x_due.size
                          else self.t_hi)
                wait = min(nxt, self.t_hi) - t
                if wait > 0:
                    self.sleep(wait)
                return
            with self.ann("bench.account"):
                rec.step(hb, t)

        return tick

    def _closed_tick(self, now: float) -> None:
        """One turn of the closed loop: top up every stream holding less
        than a hop with its next chunk, then step once."""
        sch, rec, g = self.sch, self.rec, self.g
        with self.ann("bench.push"):
            buffered = rec.pushed - g.prime_samples \
                - rec.hops_done * g.hop_samples
            need = np.flatnonzero(buffered < g.hop_samples)
            if need.size:
                lens = sch.lengths[need, self.ptr[need] % sch.lengths.shape[1]]
                self.ptr[need] += 1
                a = rec.pushed[need]
                self._push(need, a, a + lens)
        with self.ann("bench.step_batch"):
            hb = self.sched.step_batch()
        if hb is not None:
            with self.ann("bench.account"):
                rec.step(hb, self.clock() - self.origin)

    def _backlog(self) -> int:
        """Whole hops of audio buffered and not yet consumed."""
        live = (self.rec.pushed >= self.g.prime_samples) & ~self.rec.closed
        left = (self.rec.pushed - self.g.prime_samples
                - self.rec.hops_done * self.g.hop_samples)
        return int((left[live] // self.g.hop_samples).sum())

    # after the window -----------------------------------------------------------

    def latencies(self) -> np.ndarray:
        """Per stream-hop latency in the window, seconds: from the due
        time of the chunk that carried the hop's last sample to the
        return of the ``step_batch`` that emitted it."""
        sch, g = self.sch, self.g
        if not self.rec.step_t:
            return np.zeros(0)
        counts = [s.size for s in self.rec.step_sids]
        done = np.repeat(np.asarray(self.rec.step_t), counts)
        sids = np.concatenate(self.rec.step_sids)
        idx = np.concatenate(self.rec.step_idx)
        last = g.prime_samples + idx * g.hop_samples - 1
        key = sch.c_sid.astype(np.int64) << 32
        order = np.lexsort((sch.c_end, sch.c_sid))
        keys = key[order] + sch.c_end[order]
        pos = np.searchsorted(keys, (sids.astype(np.int64) << 32) + last,
                              side="right")
        due = sch.c_due[order][pos]
        return done - due

    def in_window(self, times: np.ndarray) -> int:
        """How many of the schedule's ``times`` fell inside the window."""
        return int(((times > self.t_open) & (times <= self.t_close)).sum())

    def memory_peak(self, devices) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices]
        return int(max(peaks))

    def failed(self) -> int:
        """Hops missing, or too many, after the post-window drain, over
        every stream: each open stream has all its whole hops out, and a
        closed one no more than it was sent (its close scored the rest)."""
        want = np.maximum(0, self.rec.pushed - self.g.prime_samples
                          ) // self.g.hop_samples
        gap = want - self.rec.hops_done
        return int(np.where(self.rec.closed, np.maximum(-gap, 0),
                            np.abs(gap)).sum())

    def close_sampled(self) -> None:
        """Close the sampled streams still open, keeping their flushed
        logits."""
        for s in np.flatnonzero(self.rec.sampled).tolist():
            if s not in self.rec.closes:
                res = self.sched.close_stream(s)
                self.rec.closes[s] = (res.logits, int(self.rec.pushed[s]))

    def stop(self) -> None:
        del self.sched


def check(run: Run, control: bool = False) -> dict:
    """With ``control`` the answers compared are not the program's but
    the reference's own at 4-bit audio (the precision below the
    configuration's 8), on the same prefixes: a check that cannot tell
    those apart cannot tell a wrong program either."""
    cfg, g, rec = run.cfg, run.g, run.rec
    ref = reference(cfg)
    chk = run.mix["check"]
    rng = np.random.default_rng([run.seed, 0xC4EC, 1])
    n_rows = hop_bad = close_bad = count_bad = 0
    for s in np.flatnonzero(rec.sampled).tolist():
        rows = rec.rows.get(s, {})
        closed = s in rec.closes
        n_pushed = rec.closes[s][1] if closed else int(rec.pushed[s])
        if not closed:
            want_hops = max(0, (n_pushed - g.prime_samples) // g.hop_samples)
            if len(rows) != want_hops or (rows and max(rows) != want_hops):
                count_bad += 1
        elif rows and (max(rows) != len(rows) or g.prime_samples
                       + max(rows) * g.hop_samples > n_pushed):
            count_bad += 1
        codes = run.feed.stream(s, n_pushed)
        w, t = run.models[run.tenant[s]]
        r = ref.Reference(cfg, w, t, codes)
        if control:
            low = ref.Reference(cfg, w, t, codes, input_bits=4)
            rows = {i: low.logits(g.prime_samples + i * g.hop_samples)
                    for i in rows}
        keys = sorted(rows)
        if len(keys) > chk["hops_per_stream"]:
            mid = rng.choice(keys[1:-1], chk["hops_per_stream"] - 2,
                             replace=False)
            keys = [keys[0], *sorted(mid.tolist()), keys[-1]]
        for i in keys:
            n_rows += 1
            want = r.logits(g.prime_samples + i * g.hop_samples)
            hop_bad += not np.array_equal(rows[i], want)
        if closed:
            got = low.logits(n_pushed) if control else rec.closes[s][0]
            close_bad += not np.array_equal(got, r.logits(n_pushed))
    n_closes = len(rec.closes)
    return {
        "hop_logit_mismatch": {"value": hop_bad, "limit": 0,
                               "of": n_rows},
        "close_logit_mismatch": {"value": close_bad, "limit": 0,
                                 "of": n_closes},
        "hop_count_mismatch": {"value": count_bad, "limit": 0,
                               "of": int(rec.sampled.sum())},
    }


# -- metrics -------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    window_s: float
    step_hops: np.ndarray            # stream-hops of each step in the window
    spans: list                      # the program's spans in the window
    lag_s: np.ndarray                # generator lag of each chunk pushed
    geometry: object
    work: object                     # the configuration's work count
    peak: dict | None
    chips: int
    trace: dict | None               # reduced profile of the window


def end_to_end(run: Run, names: list[str], setup_s: float) -> dict:
    lat = run.latencies() if any(
        n.startswith("hop_latency_p") for n in names) else None
    window_s = run.t_close - run.t_open
    hops = int(sum(s.size for s in run.rec.step_sids))
    out = {}
    for name in names:
        if name == "setup_s":
            out[name] = {"value": setup_s, "unit": "s"}
        elif name == "stream_hops_per_s":
            out[name] = {"value": hops / window_s, "unit": "hops/s"}
        elif name.startswith("hop_latency_p"):
            q = int(name[len("hop_latency_p"):].split("_")[0])
            out[name] = {"value": 1e3 * float(np.percentile(lat, q)),
                         "unit": "ms"}
        else:
            raise KeyError(f"no end-to-end metric {name!r}")
    return out


def per_layer(run: Run, metrics: list[dict], peak: dict | None,
              chips: int, trace: dict | None) -> dict:
    step_hops = np.array([s.size for s in run.rec.step_sids], np.int64)
    ctx = Context(
        window_s=run.t_close - run.t_open, step_hops=step_hops,
        spans=run.spans,
        lag_s=np.concatenate(run.rec.lag) if run.rec.lag else np.zeros(0),
        geometry=run.g, work=run.work, peak=peak, chips=chips, trace=trace)
    out = {}
    for m in metrics:
        v = layer_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def reduce_trace(run: Run) -> dict:
    import xplane

    try:
        path, = pathlib.Path(run._tdir).rglob("*.xplane.pb")
        return xplane.reduce(str(path))
    finally:
        shutil.rmtree(run._tdir, ignore_errors=True)


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: dict, breakdown: dict | None) -> str:
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def say(line: str) -> None:
    print(line, flush=True)


def execute(c: dict, seed: int, seconds: float, trace: bool, devices,
            t_start: float) -> str:
    """Set up, measure, check; returns the result line.  ``t_start`` is
    the process's start on the same clock as ``time.perf_counter``."""
    chips = int(c["workload"]["chips"])
    devices = list(devices)[:chips]
    kind = devices[0].device_kind
    platform = devices[0].platform
    peak = None
    if platform == "tpu":
        import peaks

        peak = peaks.peak(kind)
    run = Run(c, seed, seconds, trace)
    run.t_setup = time.perf_counter()
    run.setup()
    say(f"system: backend={run.sched.backend} launches_per_hop="
            f"{run.sched._model.dispatches_per_hop(True)} "
            f"device_kind={kind} chips={chips} capacities={run.caps} "
            f"streams={run.sch.n_streams} checked streams="
            f"{int(run.rec.sampled.sum())}")
    say(f"setup split (s): {run.setup_split}, process start to set-up "
            f"{run.t_setup - t_start:.3f}")
    run.window()
    # set-up ends where the window opens: the lead-in of traffic counts
    setup_s = run.t_open_abs - t_start
    window_compiles = sum(1 for s in run.spans if s["name"] == "compile")
    say(f"window: {run.t_close - run.t_open:.3f} s, steps="
            f"{len(run.rec.step_t)}, stream_hops="
            f"{sum(s.size for s in run.rec.step_sids)}, scheduler compile "
            f"events={window_compiles}, xla compiles={run.counter.compiles},"
            f" cache loads={run.counter.cache_loads}, backlog hops at open="
            f"{run.backlog_open} at close={run.backlog_end}, deepest inbox="
            f"{run.deepest} samples, longest turn={run.longest_turn:.3f} s, "
            f"joins={run.in_window(run.join)} closes="
            f"{run.in_window(run.close)}")
    mem = run.memory_peak(devices)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": mem}
    breakdown = red = None
    if trace:
        red = reduce_trace(run)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        metrics = per_layer(run, c["per_layer"], peak, chips, red)
    else:
        metrics = end_to_end(run, [m["name"] for m in c["end_to_end"]],
                             setup_s)
    attempted = int(sum(s.size for s in run.rec.step_sids)) \
        + run.backlog_end
    failed = run.failed()
    run.close_sampled()
    run.stop()
    t0 = time.perf_counter()
    checks = check(run)
    say(f"check: {time.perf_counter() - t0:.2f} s")
    correct = all(v["value"] <= v["limit"] for v in checks.values()) and \
        window_compiles == 0
    for name, v in checks.items():
        print(f"check {name} {v['value']} limit {v['limit']} "
              f"(of {v['of']})", file=sys.stderr, flush=True)
    return result_line(correct, attempted, failed, metrics, device, checks,
                       breakdown)
