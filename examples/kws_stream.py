"""Always-on streaming KWS: many live audio streams, one shared model.

1. build + briefly QAT-train the reduced binary KWS CNN,
2. export ternary weights + SA thresholds (same artifacts the compiler eats),
3. open a StreamScheduler and let several synthetic "microphones" push
   audio in ragged real-world-sized chunks through the vectorized ingest
   plane (push_audio_batch: one quantize + one scatter into the shared
   RingArena; the elastic slot pool grows from its minimum as they join),
4. watch per-hop finalized logits — computed on-device by the in-jit
   finalization tail — feed the hysteresis detector and emit keyword
   events per stream,
5. close each stream and verify the flushed logits are bit-exact with the
   offline executor on the same audio.

Run:  PYTHONPATH=src python examples/kws_stream.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compiler
from repro.core.executor import Executor
from repro.data import gscd
from repro.models import kws
from repro.stream import DetectorConfig, StreamScheduler
from repro.train import optimizer as opt_lib

STEPS, BATCH, IN_LEN, WIDTH = 80, 24, 2000, 16
N_STREAMS = 4


def main() -> None:
    spec = kws.build_kws_spec(in_len=IN_LEN, width=WIDTH)
    params = kws.init_kws_params(jax.random.PRNGKey(0), spec)
    ocfg = opt_lib.OptConfig(lr=2e-3)
    state = opt_lib.init_opt_state(ocfg, params)

    @jax.jit
    def step(state, params, x, y):
        loss, grads = jax.value_and_grad(kws.kws_loss)(params, x, y, spec)
        state, _ = opt_lib.update(ocfg, state, grads)
        return state, opt_lib.cast_params_like(state["master"], params), loss

    print("training briefly on the synthetic corpus...")
    for i in range(STEPS):
        xb, yb = gscd.batch(seed=0, step=i, batch_size=BATCH, n=IN_LEN)
        state, params, loss = step(state, params, jnp.array(xb), jnp.array(yb))
    print(f"  final loss {float(loss):.3f}")

    weights, thresholds = kws.export_kws(params, spec)
    sched = StreamScheduler(
        spec, weights, thresholds, capacity=N_STREAMS, hop_frames=2,
        detector_cfg=DetectorConfig(smooth_frames=2, on_threshold=0.5),
    )
    plan = sched.plan
    print(f"\nstream plan: hop={plan.hop_samples} samples "
          f"({plan.frames_per_hop} frames), prime={plan.prime_samples}, "
          f"tails={[st.tail for st in plan.convs]}")

    # each stream speaks one keyword; chunks arrive ragged like RTP packets
    rng = np.random.default_rng(3)
    classes = rng.integers(0, 10, N_STREAMS)
    clips = [gscd.sample(rng, int(c), n=IN_LEN) for c in classes]
    sids = [sched.add_stream() for _ in range(N_STREAMS)]
    pos = [0] * N_STREAMS
    while any(p < IN_LEN for p in pos):
        feed_sids, feed_chunks = [], []
        for j, sid in enumerate(sids):
            n = int(rng.integers(80, 400))
            if pos[j] < IN_LEN:
                feed_sids.append(sid)
                feed_chunks.append(clips[j][pos[j] : pos[j] + n])
                pos[j] += n
        # one vectorized quantize+scatter lands every microphone's chunk
        sched.push_audio_batch(feed_sids, feed_chunks)
        for sid, frame, logits, det in sched.step():
            if det is not None:
                print(f"  [stream {sid}] DETECT class {det.cls} "
                      f"@frame {det.frame} score {det.score:.2f}")
    sched.run_until_starved()

    print("\nclosing streams (flush) and checking offline bit-exactness:")
    prog = compiler.compile_model(spec, weights, thresholds)
    ex = Executor(prog)
    for j, sid in enumerate(sids):
        res = sched.close_stream(sid)
        off = ex.run(clips[j][:, None]).output.ravel()
        ok = np.array_equal(res.logits, off)
        pred = int(np.argmax(res.logits))
        print(f"  stream {sid}: true={classes[j]} pred={pred} "
              f"frames={res.frames} events={len(res.events)} "
              f"offline-match={'OK' if ok else 'MISMATCH'}")
        assert ok, "streaming/offline divergence"

    m = sched.metrics.summary()
    ps = sched.metrics.phase_summary()
    e = sched.metrics.energy_summary()
    print(f"\nmetrics: {m['frames_total']:.0f} frames, "
          f"{m['frames_per_sec']:.0f} frames/s, "
          f"step p50 {m['step_ms_p50']:.1f} ms (hop -> on-device logits; "
          f"host pack {m['host_pack_ms_p50']:.2f} ms, wait on the device "
          f"{ps['fence']['ms_p50']:.2f} ms, result copy "
          f"{ps['fetch']['ms_p50']:.2f} ms of it), "
          f"silicon-equivalent {e['tops_per_w_equiv']:.0f} TOPS/W")
    print(f"elastic pool: {m['resizes']:.0f} resizes, "
          f"final capacity {sched.capacity} of max {sched.max_capacity}")


if __name__ == "__main__":
    main()
