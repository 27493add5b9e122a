"""shard_map hierarchical aggregation on a real multi-device (host) mesh.
Runs in a subprocess so the 8-device XLA flag never leaks into the other
tests (dryrun.py owns the 512-device flag)."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import AxisType
    from repro.fl.collectives import (flat_allreduce, global_sync,
                                      hierarchical_allreduce,
                                      stack_for_clusters)
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)

    x = jnp.arange(8.0)
    xs = jax.device_put(x, NamedSharding(mesh, P(("data",))))
    # local-only reduce: mean over data axis
    local = hierarchical_allreduce(xs, mesh, do_global=False)
    # full hierarchical reduce
    both = hierarchical_allreduce(xs, mesh, do_global=True)
    flat = flat_allreduce(jax.device_put(x, NamedSharding(mesh,
                                         P(("pod", "data")))), mesh)
    # x has 8 elements over data(2): shards [0..3],[4..7]; psum over data
    # sums shard-wise -> mean of the two shards
    expect_local = (x[:4] + x[4:]) / 2
    np.testing.assert_allclose(np.asarray(local), np.asarray(expect_local))
    # global: dim 0 co-sharded over (data, pod) -> mean of the 4 blocks
    expect_both = x.reshape(4, 2)
    np.testing.assert_allclose(np.asarray(both),
                               np.asarray(expect_both).mean(axis=0))
    # flat over pod+data: 4 shards of 2
    xf = x.reshape(4, 2)
    np.testing.assert_allclose(np.asarray(flat), xf.mean(axis=0))

    # cluster-replica global_sync on a pod-sharded leading dim
    params = {"w": jnp.ones((4, 4))}
    stacked = stack_for_clusters(params, 2)
    stacked = jax.tree.map(lambda t: t + jnp.arange(2.0)[:, None, None],
                           stacked)
    sh = NamedSharding(mesh, P("pod"))
    stacked = jax.tree.map(lambda t: jax.device_put(t, sh), stacked)
    synced = jax.jit(global_sync)(stacked)
    np.testing.assert_allclose(np.asarray(synced["w"][0]),
                               np.asarray(synced["w"][1]))
    np.testing.assert_allclose(np.asarray(synced["w"][0]),
                               np.ones((4, 4)) + 0.5)
    # the pod-axis collective actually appears in the lowered program
    txt = jax.jit(global_sync).lower(stacked).compile().as_text()
    assert ("all-reduce" in txt) or ("all-gather" in txt), "no collective!"
    print("MULTIDEVICE_OK")
""")


def test_hierarchical_allreduce_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MULTIDEVICE_OK" in out.stdout


SCRIPT_SM = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import AxisType
    from repro.fl.collectives import (global_sync_shardmap,
                                      make_hfl_local_step_shardmap)
    from repro.fl.compression import (EFState,
                                      compressed_global_sync_shardmap,
                                      init_ef_state)
    mesh = jax.make_mesh((2, 2, 2), ("cluster", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    sh = NamedSharding(mesh, P("cluster"))
    rng = np.random.default_rng(0)

    # shard_map local step: per-cluster SGD on different data
    def base(p, o, b):
        loss, g = jax.value_and_grad(
            lambda w: jnp.mean((b["x"] @ w - b["y"]) ** 2))(p["w"])
        return {"w": p["w"] - 0.1 * g}, o, loss

    stepped = make_hfl_local_step_shardmap(base, mesh)
    p = {"w": jax.device_put(jnp.ones((2, 4)), sh)}
    o = jax.device_put(jnp.zeros((2,)), sh)
    b = {"x": jax.device_put(jnp.asarray(rng.normal(size=(2, 8, 4)),
                                         jnp.float32), sh),
         "y": jax.device_put(jnp.asarray(rng.normal(size=(2, 8)),
                                         jnp.float32), sh)}
    p2, _, losses = jax.jit(stepped)(p, o, b)
    assert losses.shape == (2,)
    # clusters trained on different data -> diverged replicas
    assert not np.allclose(np.asarray(p2["w"][0]), np.asarray(p2["w"][1]))
    # no cross-cluster collective in the local step
    txt = jax.jit(stepped).lower(p, o, b).compile().as_text()
    from repro.launch.roofline import collective_stats
    st = collective_stats(txt, pod_size=4)   # 4 devices per cluster here
    assert st.cross_pod_bytes == 0, st.bytes_by_kind

    # global sync equalizes
    p3 = jax.jit(lambda q: global_sync_shardmap(q, mesh))(p2)
    np.testing.assert_allclose(np.asarray(p3["w"][0]),
                               np.asarray(p3["w"][1]), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(p3["w"][0]),
        np.asarray(p2["w"]).mean(axis=0), rtol=1e-5)

    # int8-on-the-wire sync: anchor = params at last sync (pre-divergence)
    ef = init_ef_state(p)
    p4, ef2 = jax.jit(lambda q, e: compressed_global_sync_shardmap(
        q, e, mesh))(p2, ef)
    np.testing.assert_allclose(np.asarray(p4["w"][0]),
                               np.asarray(p4["w"][1]), rtol=1e-6)
    err = np.abs(np.asarray(p4["w"][0]) - np.asarray(p2["w"]).mean(0))
    assert err.max() < 0.01

    # fully-manual variant (local shards on the wire) agrees too
    from repro.fl.compression import compressed_global_sync_manual
    specs = [P("cluster", "data")]
    p5, _ = jax.jit(lambda q, e: compressed_global_sync_manual(
        q, e, mesh, specs))(jax.device_put(
            p2, NamedSharding(mesh, P("cluster", "data"))),
        init_ef_state(p))
    np.testing.assert_allclose(np.asarray(p5["w"][0]),
                               np.asarray(p5["w"][1]), rtol=1e-6)
    err5 = np.abs(np.asarray(p5["w"][0]) - np.asarray(p2["w"]).mean(0))
    assert err5.max() < 0.02
    print("SHARDMAP_HFL_OK")
""")


def test_hfl_shardmap_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT_SM], env=env,
                         capture_output=True, text=True, timeout=400)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDMAP_HFL_OK" in out.stdout
