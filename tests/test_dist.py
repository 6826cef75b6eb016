"""Distribution tests in a subprocess with 8 virtual devices.

The main pytest process must keep the default single CPU device (jax locks
the device count at first init), so every sharded scenario runs in a child
interpreter with XLA_FLAGS set before importing jax.
"""
import pytest

from _child import run_child


def test_sharded_train_step_runs():
    out = run_child("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import get_smoke
        from repro.dist import sharding as S
        from repro.models import model as M, params as PRM
        from repro.train import train_step as TS
        from repro.data.tokens import make_data_iter

        cfg = get_smoke("granite-8b")
        from repro.launch import mesh as LM
        mesh = LM.make_mesh((2, 4), ("data", "model"))
        with S.use_mesh(mesh):
            state = TS.init_state(cfg, jax.random.PRNGKey(0))
            shard = PRM.param_specs(M.param_table(cfg), mesh)
            state = TS.TrainState(
                jax.device_put(state.params, shard),
                state.opt._replace(mu=jax.device_put(state.opt.mu, shard),
                                   nu=jax.device_put(state.opt.nu, shard)),
                None)
            step = jax.jit(TS.make_train_step(cfg, microbatches=2))
            it = make_data_iter(cfg, batch=4, seq=32)
            state, m = step(state, it(0))
            state, m = step(state, it(1))
            print("LOSS", float(m["loss"]))
    """)
    assert "LOSS" in out


def test_logical_sharding_divisibility_fallback():
    out = run_child("""
        import jax
        from repro.dist import sharding as S
        from repro.launch import mesh as LM
        mesh = LM.make_mesh((2, 4), ("data", "model"))
        with S.use_mesh(mesh):
            # 20 heads on a 4-way model axis -> shards (20 % 4 == 0)
            s1 = S.spec_for((8, 20, 64), ("batch", "model", None))
            # 25 heads -> falls back to replication
            s2 = S.spec_for((8, 25, 64), ("batch", "model", None))
            print("S1", s1)
            print("S2", s2)
    """)
    assert "S1 PartitionSpec('data', 'model', None)" in out
    assert "S2 PartitionSpec('data', None, None)" in out


def test_compressed_pod_allreduce_matches_mean():
    out = run_child("""
        import functools
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.dist.collectives import compressed_pod_allreduce

        from repro.launch import mesh as LM
        mesh = LM.make_mesh((2, 2, 2), ("pod", "data", "model"))
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 512)) * 0.01

        def podwise(xs):
            return compressed_pod_allreduce(xs[0][None] * 0 + xs, "pod")

        f = jax.shard_map(lambda a: compressed_pod_allreduce(a, "pod"),
                          mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                          axis_names=frozenset({"pod"}))
        y = f(x)
        want = jnp.broadcast_to(jnp.mean(x, 0, keepdims=True), x.shape)
        err = float(jnp.max(jnp.abs(y - want)))
        rel = err / float(jnp.max(jnp.abs(want)))
        print("REL", rel)
        assert rel < 0.05, rel
    """)
    assert "REL" in out


def test_elastic_remesh():
    out = run_child("""
        import jax, jax.numpy as jnp
        from repro.dist import sharding as S, fault as F
        from repro.launch import mesh as LM
        mesh = LM.make_mesh((4, 2), ("data", "model"))
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        axes = {"w": ("fsdp", "model")}
        with S.use_mesh(mesh):
            placed = F.remesh_state(tree, axes, mesh)
        small = F.shrink_mesh(mesh, "data", 2)
        with S.use_mesh(small):
            replaced = F.remesh_state(placed, axes, small)
        assert replaced["w"].sharding.mesh.shape["data"] == 2
        import numpy as np
        np.testing.assert_array_equal(np.asarray(replaced["w"]),
                                      np.asarray(tree["w"]))
        print("REMESH OK")
    """)
    assert "REMESH OK" in out


def test_podsync_mode_compiles_and_runs():
    out = run_child("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import get_smoke
        from repro.dist import sharding as S
        from repro.models import model as M, params as PRM
        from repro.train import train_step as TS
        from repro.train.grad_compress import CompressConfig
        from repro.data.tokens import make_data_iter

        cfg = get_smoke("granite-3-2b")
        from repro.launch import mesh as LM
        mesh = LM.make_mesh((2, 2, 2), ("pod", "data", "model"))
        with S.use_mesh(mesh):
            state = TS.stack_for_podsync(
                TS.init_state(cfg, jax.random.PRNGKey(0), compress=True), 2)
            step = jax.jit(TS.make_train_step(
                cfg, microbatches=1, mode="podsync", mesh=mesh,
                compress=CompressConfig(enabled=True, gate_ratio=0.0)))
            it = make_data_iter(cfg, batch=4, seq=32)
            state, m = step(state, it(0))
            print("PODSYNC LOSS", float(m["loss"]))
    """)
    assert "PODSYNC LOSS" in out
