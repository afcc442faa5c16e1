"""Elastic checkpoint restore: save sharded on mesh A, restore onto mesh B.

Runs in a subprocess so it can set XLA_FLAGS for 4 host devices without
polluting the main test process (which must keep seeing 1 device).
"""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    kw = {"axis_types": (AxisType.Auto,) * 2}
    from repro.train import checkpoint as ckpt

    mesh_a = jax.make_mesh((4, 1), ("data", "model"), **kw)
    mesh_b = jax.make_mesh((2, 2), ("data", "model"), **kw)

    tree = {
        "w": jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh_a, P("data", None))),
        "m": jax.device_put(jnp.ones((4, 8), jnp.bfloat16),
                            NamedSharding(mesh_a, P(None, None))),
    }
    d = tempfile.mkdtemp()
    ckpt.save(d, 3, tree)

    shardings_b = {
        "w": NamedSharding(mesh_b, P("data", "model")),
        "m": NamedSharding(mesh_b, P(None, "model")),
    }
    step, restored, _ = ckpt.restore(d, tree, shardings=shardings_b)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(64).reshape(8, 8))
    assert restored["w"].sharding.mesh.shape["model"] == 2
    assert restored["w"].sharding.is_equivalent_to(shardings_b["w"], 2)
    print("ELASTIC_OK")
""")


def test_elastic_reshard_across_meshes():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]
