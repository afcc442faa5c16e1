"""Ahead-of-time compiles for a TPU v5e at the paper's width.

The interpreter that runs every other kernel test checks neither tiling nor
VMEM nor what Mosaic lowers, so these cases compile the streaming hop's
device programs for a described ``v5e:2x2`` topology: the paper's network
(width 64) at 256 slots, with ``interpret=False``.  Nothing runs; a case
passes when the chip's compiler accepts the program.  This file is the
place to try a kernel change before spending chip time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.models import kws
from repro.stream import StreamScheduler

SLOTS = 256
HOP_FRAMES = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back here; keep the cache off around them
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model():
    spec = kws.build_kws_spec()
    params = kws.init_kws_params(jax.random.PRNGKey(0), spec)
    return (spec,) + kws.export_kws(params, spec)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(plan, b, sharding):
    """Shapes of one hop's arguments: audio, mask, tails, pendings, gap."""
    return (
        _sds((b, plan.hop_samples), jnp.int32, sharding),
        _sds((b,), bool, sharding),
        tuple(_sds((b, st.tail, st.cin), jnp.int32, sharding)
              for st in plan.convs),
        tuple(_sds((b, st.phase, st.cout), jnp.int32, sharding)
              for st in plan.convs),
        _sds((b, plan.gap_channels), jnp.int32, sharding),
    )


def _model(model, backend):
    spec, weights, thresholds = model
    s = StreamScheduler(spec, weights, thresholds, capacity=SLOTS,
                        hop_frames=HOP_FRAMES, backend=backend,
                        interpret=False)
    return s.plan, s._model


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("emit", [False, True])
def test_jnp_hop_step(model, one_chip, emit):
    plan, m = _model(model, "jnp")
    compiled = m.step.lower(*_state(plan, SLOTS, one_chip),
                            emit=emit).compile()
    assert _kernels(compiled) == 0


def test_pallas_hop_step(model, one_chip):
    """The per-stage popcount kernels: bit-serial layer 0, the binary
    convs, the ghost flush's convs and the classifier tail."""
    plan, m = _model(model, "pallas")
    compiled = m.step.lower(*_state(plan, SLOTS, one_chip),
                            emit=True).compile()
    assert _kernels(compiled) == m.dispatches_per_hop(True)


def test_classifier_tail(model, one_chip):
    plan, m = _model(model, "pallas")
    fc = lambda xs: tuple(_sds(x.shape, x.dtype, one_chip)  # noqa: E731
                          for x in xs)
    compiled = jax.jit(
        lambda g, w, t, f: ops.classifier_tail(
            g, w, t, f, out_raw=m._fc_raw, interpret=False)
    ).lower(_sds((SLOTS, plan.gap_channels), jnp.int32, one_chip),
            fc(m._fc_w), fc(m._fc_thr), fc(m._fc_flip)).compile()
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("emit", [False, True])
def test_megakernel_hop(model, one_chip, emit):
    plan, m = _model(model, "megakernel")
    compiled = m.step.lower(*_state(plan, SLOTS, one_chip),
                            emit=emit).compile()
    assert _kernels(compiled) == 1


def test_megakernel_finalize(model, one_chip):
    plan, m = _model(model, "megakernel")
    compiled = m.finalize.lower(*_state(plan, SLOTS, one_chip)[2:]).compile()
    assert _kernels(compiled) == 1


def test_megakernel_hop_on_four_chip_mesh(model, topo):
    """One fused launch per shard through ``jax.shard_map``: 256 slots on
    each of four chips, weights replicated, no collective."""
    plan, m = _model(model, "megakernel")
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    weights = tuple(
        tuple(_sds(x.shape, x.dtype, rep) for x in group)
        for group in (m._w, m._thr, m._flip, m._fc_w, m._fc_thr, m._fc_flip)
    )

    def hop(audio, mask, tails, pendings, gap, *w):
        audio = audio.reshape(audio.shape[0], plan.hop_samples, 1)
        return ops.hop_megakernel_sharded(
            audio, mask.astype(jnp.int32), tails, pendings, gap, *w,
            mesh=mesh, stages=plan.convs, emit=True, fc_raw=m._fc_raw,
            interpret=False)

    compiled = jax.jit(hop).lower(*_state(plan, 4 * SLOTS, rows),
                                  *weights).compile()
    text = compiled.as_text()
    assert _kernels(compiled) == 1
    assert "all-gather" not in text and "all-reduce" not in text
