"""Sharding rules + roofline HLO parsing (no multi-device requirement:
divisibility logic is pure; the parser works on HLO text)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import arch_names, get_arch
from repro.launch import roofline as rl
from repro.models import api
from repro.sharding import specs as sh


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


class FakeMeshMP:
    axis_names = ("pod", "data", "model")
    shape = {"pod": 2, "data": 16, "model": 16}


def _leaf(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_param_rules_megatron_pairing():
    m = FakeMesh()
    assert sh.param_pspec("blocks/0/attn/wq", _leaf((28, 1024, 2048)), m) == \
        P(None, None, "model")
    assert sh.param_pspec("blocks/0/attn/wo", _leaf((28, 2048, 1024)), m) == \
        P(None, "model", None)
    # vocab-parallel embed; 19MB/shard is under the 32MB FSDP threshold
    assert sh.param_pspec("embed", _leaf((151936, 1024)), m) == \
        P("model", None)
    # a 4x bigger embed crosses the threshold and gains FSDP on d_model
    assert sh.param_pspec("embed", _leaf((151936, 4096)), m) == \
        P("model", "data")
    # shared experts are plain MLPs
    assert sh.param_pspec("blocks/0/moe/shared/wi_up",
                          _leaf((27, 2048, 2816)), m) == \
        P(None, None, "model")
    # norms replicate (P(None) == fully replicated 1-D)
    assert sh.param_pspec("blocks/0/norm1", _leaf((1024,)), m) == P(None)


def test_param_rules_moe_expert_parallel():
    m = FakeMesh()
    # fine-grained bank (deepseek-moe: 69MB/shard after TP) stays unsharded
    # over E — grouped local-capacity dispatch, zero token movement; FSDP
    # adds 'data' storage sharding on the biggest free dim (>32MB/shard)
    spec = sh.param_pspec("blocks/0/moe/wi_gate",
                          _leaf((27, 64, 2048, 1408)), m)
    assert spec == P(None, None, "data", "model")
    spec = sh.param_pspec("blocks/0/moe/wo", _leaf((27, 64, 1408, 2048)), m)
    assert spec == P(None, None, "model", "data")
    # a bank too big to keep resident (>4GB/shard after TP) goes
    # expert-parallel over data
    spec = sh.param_pspec("blocks/0/moe/wi_gate",
                          _leaf((36, 64, 8192, 24576)), m)
    assert spec == P(None, "data", None, "model")
    spec = sh.param_pspec("blocks/0/moe/wo", _leaf((36, 64, 24576, 8192)), m)
    assert spec == P(None, "data", "model", None)


def test_fsdp_added_for_large_params():
    m = FakeMesh()
    # deepseek-33b mlp wi: (62, 7168, 19200) bf16: per model-shard 148MB
    spec = sh.param_pspec("blocks/0/mlp/wi_up", _leaf((62, 7168, 19200)), m)
    assert spec == P(None, "data", "model")
    # small layer stays TP-only
    spec = sh.param_pspec("blocks/0/mlp/wi_up", _leaf((2, 64, 128)), m)
    assert spec == P(None, None, "model")


def test_degradation_on_indivisible():
    m = FakeMesh()
    rep = sh.ShardingReport()
    spec = sh.param_pspec("blocks/0/attn/wq", _leaf((2, 30, 30)), m,
                          report=rep)
    assert spec == P(None, None, None)
    assert rep.degraded


@pytest.mark.parametrize("name", arch_names())
def test_no_degradations_for_full_archs(name):
    """Every parameter of every assigned arch shards cleanly on 16x16."""
    cfg = get_arch(name)
    params = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    rep = sh.ShardingReport()
    m = FakeMesh()
    from repro.utils.tree import tree_map_with_path_names
    tree_map_with_path_names(
        lambda n, l: sh.param_pspec(n, l, m, cfg, rep), params
    )
    assert rep.degraded == [], (name, rep.degraded[:5])


def test_batch_specs():
    m = FakeMesh()
    assert sh.batch_pspec("b", _leaf((256, 4096), jnp.int32), m) == \
        P("data", None)
    assert sh.batch_pspec("b", _leaf((1, 1), jnp.int32), m) == P()
    assert sh.batch_pspec("b", _leaf((16, 16, 4096), jnp.int32), m,
                          micro=True) == P(None, "data", None)
    mp = FakeMeshMP()
    assert sh.batch_pspec("b", _leaf((256, 4096), jnp.int32), mp) == \
        P(("pod", "data"), None)


def test_decode_state_specs():
    m = FakeMesh()
    # KV cache (reps, B, S, Hk_eff, Dh): with kv replication Hk_eff=16
    # shards over model (zero-comm attention)
    assert sh.decode_state_pspec("layers/0/0",
                                 _leaf((28, 128, 32768, 16, 128)), m) == \
        P(None, "data", None, "model", None)
    # unreplicated kv=8: falls to sequence sharding
    assert sh.decode_state_pspec("layers/0/0",
                                 _leaf((28, 128, 32768, 8, 128)), m) == \
        P(None, "data", "model", None, None)
    # long-context B=1: sequence sharding
    assert sh.decode_state_pspec("layers/0/0",
                                 _leaf((9, 1, 524288, 8, 128)), m) == \
        P(None, None, "data", None, None)
    # recurrent state B=1: feature sharding over model
    assert sh.decode_state_pspec("layers/0/1", _leaf((9, 1, 16384, 16)), m) \
        == P(None, None, "data", None)


# ---------------------------------------------------------------------------
# roofline HLO parsing
# ---------------------------------------------------------------------------

HLO = """
HloModule test
ENTRY main {
  %p0 = bf16[16,512]{1,0} parameter(0)
  %ag = bf16[16,8192]{1,0} all-gather(%p0), dimensions={1}
  %ar = f32[1024]{0} all-reduce(%conv), to_apply=%add
  %rs = f32[64]{0} reduce-scatter(%ar), dimensions={0}
  %cp = bf16[16,512]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  %a2a = bf16[16,512]{1,0} all-to-all(%p0), dimensions={0}
}
"""


def test_collective_parser():
    colls = rl.parse_collectives(HLO)
    kinds = sorted(c.kind for c in colls)
    assert kinds == ["all-gather", "all-reduce", "all-to-all",
                     "collective-permute", "reduce-scatter"]
    ag = next(c for c in colls if c.kind == "all-gather")
    assert ag.operand_bytes == 16 * 512 * 2
    assert ag.result_bytes == 16 * 8192 * 2
    assert ag.moved_bytes == ag.result_bytes - ag.operand_bytes
    ar = next(c for c in colls if c.kind == "all-reduce")
    assert ar.moved_bytes == 2 * ar.operand_bytes


def test_roofline_terms():
    r = rl.Roofline(flops=197e12, hbm_bytes=819e9, coll_bytes=0,
                    collectives={})
    assert abs(r.compute_s - 1.0) < 1e-9
    assert abs(r.memory_s - 1.0) < 1e-9
    assert r.dominant in ("compute", "memory")
    r2 = rl.Roofline(flops=1, hbm_bytes=1, coll_bytes=50e9, collectives={})
    assert r2.dominant == "collective"


def test_model_flops():
    assert rl.model_flops_train(1e9, 1000) == 6e12
    assert rl.model_flops_infer(1e9, 1) == 2e9


def test_active_param_count_moe():
    cfg = get_arch("deepseek-moe-16b")
    total = api.param_count(cfg)
    active = api.active_param_count(cfg)
    assert active < total
    # 27 MoE layers x 58 inactive experts x 3*2048*1408
    assert total - active == 27 * 58 * 3 * 2048 * 1408


# ---------------------------------------------------------------------------
# launch/report.py rendering
# ---------------------------------------------------------------------------

def _ok_cell(uf):
    c = {
        "arch": "a", "shape": "s", "mesh": "16x16", "status": "ok",
        "roofline": {"compute_s": 0.5, "memory_s": 0.2, "collective_s": 0.1,
                     "dominant": "compute"},
        "mem": {"peak_gb": 1.0},
    }
    if uf is not None:
        c["useful_flops_frac"] = uf
    return c


def test_report_zero_useful_flops_renders_as_value():
    """useful_flops_frac == 0.0 is a measurement, not a missing field: it
    must render as 0.00, while an absent field renders as em-dash."""
    from repro.launch import report

    line_zero = report.roofline_lines([_ok_cell(0.0)])[2]
    assert "| 0.00 |" in line_zero and "| — |" not in line_zero
    line_missing = report.roofline_lines([_ok_cell(None)])[2]
    assert "| — |" in line_missing
    line_half = report.roofline_lines([_ok_cell(0.5)])[2]
    assert "| 0.50 |" in line_half


def test_report_stream_table_renders_sweep_and_sharded():
    from repro.launch import report

    bench = {
        "sweep": {"8": {"hop_ms_p50": 1.5, "hop_ms_p99": 3.8,
                        "host_pack_ms_p50": 0.2,
                        "fence_ms_p50": 1.3,
                        "stream_hops_per_sec": 4000.0,
                        "uj_per_inference": 0.0005}},
        "sharded": {
            "total_streams": 1024,
            "configs": {
                "1": {"hop_ms_p50": 180.0, "host_pack_ms_p50": 4.0,
                      "fence_ms_p50": 176.0,
                      "stream_hops_per_sec": 5000.0,
                      "uj_per_inference": 0.0005},
                "8": {"hop_ms_p50": 150.0, "host_pack_ms_p50": 4.0,
                      "fence_ms_p50": 146.0,
                      "stream_hops_per_sec": 6000.0,
                      "uj_per_inference": 0.0005},
            },
            "multi_vs_single": 1.2,
        },
        "host_pack": {"streams": 1024.0, "host_pack_ms_before": 20.0,
                      "host_pack_ms_after": 2.0, "reduction": 10.0},
    }
    lines = report.stream_lines(bench)
    text = "\n".join(lines)
    assert ("| steady | 8 | 1 | 1.500 | 3.800 | 0.200 | 1.300 "
            "| 4000 | 0.0005 |") in text
    assert ("| mesh-sharded | 1024 | 8 | 150.000 | — | 4.000 | 146.000 "
            "| 6000 | 0.0005 |") in text
    assert "1.20x aggregate stream-hops/s" in text
    assert "10.0x" in text  # host-pack before/after footer
    # rows missing the newer fields (older artifacts) degrade to em-dash;
    # a measured 0.0 in any column must still render as a number, and a
    # NaN (empty latency window) must render as em-dash, never 0.0
    legacy = report.stream_lines(
        {"sweep": {"8": {"hop_ms_p50": 1.5, "hop_ms_p99": float("nan"),
                         "host_pack_ms_p50": 0.0}}}
    )
    assert ("| steady | 8 | 1 | 1.500 | — | 0.000 | — | — | — |"
            in "\n".join(legacy))
