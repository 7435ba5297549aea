"""Digraph collectives: schedules (unit) + shard_map execution on 8 host
devices (subprocess — device count must be set before jax init)."""
import os
import subprocess
import sys
import textwrap


from repro.collectives.schedules import (doubling_schedule, gs_flood_schedule,
                                         ring_schedule)


def test_ring_schedule_shape():
    s = ring_schedule(8)
    assert len(s) == 7 and all(len(step) == 8 for step in s)


def test_doubling_schedule():
    s = doubling_schedule(8)
    assert len(s) == 3  # log2(8)


def test_gs_flood_schedule_covers_all():
    offsets, steps = gs_flood_schedule(16, 3)
    assert len(offsets) == 3
    # flood completes within diameter steps
    known = {0}
    for _ in range(steps):
        known |= {(d + o) % 16 for d in known for o in offsets}
    assert known == set(range(16))


_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    import sys
    sys.path.insert(0, "src")
    from repro.kernels.compat import shard_map
    from repro.collectives.ops import (ring_allgather, doubling_allgather,
                                       gs_flood_allgather, ring_allreduce,
                                       graph_allreduce)
    mesh = jax.make_mesh((8,), ("x",))
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)

    def run(fn, extra=()):
        # jitted: jax.make_mesh gives explicit axes, under which an eager
        # shard_map cannot index by axis_index
        return jax.jit(shard_map(lambda a: fn(a[0], "x", *extra), mesh=mesh,
                                 in_specs=P("x"), out_specs=P("x")))(x)

    for name, fn, extra in [("ring", ring_allgather, ()),
                            ("doubling", doubling_allgather, ()),
                            ("gs_flood", gs_flood_allgather, (3,))]:
        g = np.asarray(run(fn, extra)).reshape(8, 8, 4)
        for dev in range(8):
            np.testing.assert_allclose(g[dev], np.asarray(x))
    expect = np.asarray(x).sum(axis=0)
    r = np.asarray(run(ring_allreduce)).reshape(8, 4)
    for dev in range(8):
        np.testing.assert_allclose(r[dev], expect, rtol=1e-6)
    for strat in ["binomial", "gs_flood", "psum"]:
        r = np.asarray(run(graph_allreduce, extra=(strat,))).reshape(8, 4)
        for dev in range(8):
            np.testing.assert_allclose(r[dev], expect, rtol=1e-6)
    print("COLLECTIVES_OK")
""")


def test_collectives_on_eight_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "COLLECTIVES_OK" in res.stdout, res.stderr[-3000:]
