"""Fault tolerance: dist.fault primitives, the fault-injection harness,
admission control, and multi-process chaos scenarios.

The chaos tests run real ``jax.distributed`` cohorts via
``_child.run_procs`` and break them deliberately -- SIGKILL mid-launch
via ``repro.dist.faultinject`` (armed through ``REPRO_FAULT_INJECT`` in
``proc_env``), wedged peers, leader death under an external
coordinator, double faults -- and assert the service's contract: every
outstanding future completes bit-equal to the single-device engine (or
fails with a typed ``FabricError``), recovery is bounded by
``launch_timeout_s``, survivors shut down cleanly with exit code 0.
Chaos children end with ``os._exit(0)`` on purpose: a cohort with a
killed peer must skip the atexit ``jax.distributed.shutdown`` barrier,
which would otherwise QFATAL against the dead process.
"""
import textwrap
import threading
import time

import numpy as np
import pytest

from _child import run_child, run_procs

from repro.dist import fault as F
from repro.dist import faultinject as FI
from repro.launch import mesh as LM


# ---------------------------------------------------------------------------
# FabricError / fault-injection harness (pure in-process units)
# ---------------------------------------------------------------------------

def test_fabric_error_carries_typed_fields():
    e = F.FabricError("boom", kind="follower_lost", lost=(2, 1),
                      retriable=True)
    assert e.kind == "follower_lost" and e.lost == (2, 1) and e.retriable
    assert "lost processes=[2, 1]" in str(e) and "retriable" in str(e)
    e2 = F.FabricError("gone", kind="leader_lost")
    assert not e2.retriable and e2.lost == ()
    assert "restart" in str(e2)
    assert isinstance(e2, RuntimeError)


def test_faultinject_parse():
    spec = FI.parse("a:kill:1,b:hang:2,c:slow:3:0.25")
    assert spec["a"] == [("kill", 1, 1.0)]
    assert spec["b"] == [("hang", 2, 3600.0)]     # hang defaults to 1 h
    assert spec["c"] == [("slow", 3, 0.25)]
    assert FI.parse("a:kill:1,a:exit:4")["a"] == \
        [("kill", 1, 1.0), ("exit", 4, 1.0)]
    assert FI.parse("") == {}
    for bad in ("a:frob:1", "a:kill", "a:kill:0", "a:kill:x", "a:slow:1:zz"):
        with pytest.raises(ValueError):
            FI.parse(bad)


def test_faultinject_fire_counts_and_disarm():
    FI.configure("s:slow:2:0.2")
    try:
        t0 = time.perf_counter()
        FI.fire("s")                       # nth=1: counted, no fault
        assert time.perf_counter() - t0 < 0.1
        t0 = time.perf_counter()
        FI.fire("s")                       # nth=2: sleeps 0.2 s
        assert time.perf_counter() - t0 >= 0.2
        FI.fire("other")                   # unarmed site: not even counted
        assert FI.counts() == {"s": 2}
    finally:
        FI.configure(None)
    FI.fire("s")                           # disarmed: no-op, no counting
    assert FI.counts() == {}


# ---------------------------------------------------------------------------
# Re-meshing primitives
# ---------------------------------------------------------------------------

def test_shrink_mesh_rejects_bad_axis_and_size():
    mesh = LM.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="not 'model'"):
        F.shrink_mesh(mesh, "model", 1)
    for bad in (0, 2):
        with pytest.raises(ValueError, match="outside"):
            F.shrink_mesh(mesh, "data", bad)
    assert tuple(F.shrink_mesh(mesh, "data", 1).axis_names) == ("data",)


def test_surviving_submesh_rejects_nd_and_empty():
    with pytest.raises(ValueError, match="1-D"):
        F.surviving_submesh(LM.make_mesh((1, 1), ("a", "b")), [0])
    mesh = LM.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="no devices left"):
        F.surviving_submesh(mesh, [99])
    sub = F.surviving_submesh(mesh, [0])
    assert [d.id for d in sub.devices.flat] == \
        [d.id for d in mesh.devices.flat]


def test_remesh_state_preserves_values_across_shrink():
    """Shrinking a mesh axis and re-placing a sharded state tree keeps
    every leaf bit-identical (4 virtual devices, single process)."""
    run_child("""
        import numpy as np, jax
        from repro.dist import fault as F
        from repro.dist import sharding as S
        from repro.launch import mesh as LM

        m4 = LM.make_mesh((4,), ("data",))
        m22 = LM.make_mesh((2, 2), ("data", "model"))
        tree = {"w": np.arange(32, dtype=np.float32).reshape(8, 4),
                "b": np.arange(4, dtype=np.float32)}
        axes = {"w": ("batch", None), "b": (None,)}
        with S.use_mesh(m4, {"batch": "data"}):
            sharded = F.remesh_state(tree, axes, m4)
        assert len(sharded["w"].sharding.device_set) == 4
        small = F.shrink_mesh(m4, "data", 2)
        with S.use_mesh(small, {"batch": "data"}):
            moved = F.remesh_state(sharded, axes, small)
        assert len(moved["w"].sharding.device_set) == 2
        for k in tree:
            assert np.array_equal(np.asarray(moved[k]), tree[k]), k
        s2 = F.shrink_mesh(m22, "model", 1)
        assert s2.devices.shape == (2, 1)
        assert [d.id for d in s2.devices.flat] == \
            [d.id for d in m22.devices[:, :1].flat]
        print("OK", flush=True)
    """, devices=4)


def test_surviving_submesh_keeps_process_blocks_contiguous():
    """On a 2-process mesh the survivor submesh of each side is that
    side's contiguous device block, in original order."""
    outs = run_procs("""
        import os, sys
        import numpy as np, jax
        from repro.dist import fault as F
        from repro.launch import mesh as M

        mesh = M.make_sweep_mesh()
        for alive in ([0], [1], [0, 1]):
            sub = F.surviving_submesh(mesh, alive)
            want = [d.id for d in mesh.devices.flat
                    if d.process_index in set(alive)]
            assert [d.id for d in sub.devices.flat] == want, (alive, want)
            assert tuple(sub.axis_names) == tuple(mesh.axis_names)
        print("SUBMESH OK", flush=True)
        sys.stdout.flush(); os._exit(0)
    """, num_procs=2, devices=2, timeout=240,
        # process 0 finishing first must not close the coordination
        # service under process 1
        external_coordinator=True)
    for out in outs:
        assert "SUBMESH OK" in out


# ---------------------------------------------------------------------------
# Admission control + fabric-error scoping (single-process service)
# ---------------------------------------------------------------------------

def _tiny_stack(k, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((k, 16, 16)).astype(np.float32)


def test_retry_after_backpressure():
    from repro.core import predictors as PRED
    from repro.serve.sweep_service import (RetryAfter, ServiceConfig,
                                           SweepService)
    eps = np.asarray([1e-2, 1e-1], np.float32)
    # long max_wait_ms parks the first request in the queue so the
    # second submission sees a full queue deterministically
    svc = SweepService(ServiceConfig(max_wait_ms=10_000.0,
                                     max_batch_slices=64, max_queue_rows=4))
    try:
        stack = _tiny_stack(3)
        fut = svc.submit_featurize(stack, eps)
        with pytest.raises(RetryAfter) as exc:
            svc.submit_featurize(_tiny_stack(2, seed=1), eps)
        assert exc.value.pending_rows == 3
        assert exc.value.retry_after_s >= 10.0   # >= the queue drain bound
        assert "retry after" in str(exc.value)
        assert svc.stats()["rejected"] == 1
    finally:
        svc.close()                              # drains the parked request
    got = fut.result(60)
    assert np.array_equal(
        got, np.asarray(PRED.features_sweep(stack, eps, sharded=False)))

    # a single over-wide request into an EMPTY queue is never rejected:
    # it must remain servable (it flushes alone)
    svc = SweepService(ServiceConfig(max_wait_ms=1.0, max_queue_rows=2))
    try:
        wide = svc.submit_featurize(_tiny_stack(5), eps).result(60)
        assert wide.shape == (5, 2, 2)
        assert svc.stats()["rejected"] == 0
    finally:
        svc.close()


def test_fabric_error_fails_everything_and_close_is_idempotent():
    """A non-retriable FabricError from the launch path is fabric-scoped:
    the in-flight batch AND queued requests all fail with it, later
    submits are refused, serve() raises it, close() stays idempotent."""
    from repro.serve.sweep_service import ServiceConfig, SweepService
    eps = np.asarray([1e-2], np.float32)
    svc = SweepService(ServiceConfig(max_wait_ms=1.0))
    release = threading.Event()

    def poisoned(*a, **kw):
        release.wait(30)
        raise F.FabricError("injected fabric fault", kind="failed")

    svc._collective_sweep = poisoned
    f1 = svc.submit_featurize(_tiny_stack(2), eps)
    time.sleep(0.2)                    # worker is now blocked in poisoned()
    f2 = svc.submit_featurize(_tiny_stack(1), eps)
    release.set()
    for fut in (f1, f2):
        with pytest.raises(F.FabricError, match="injected fabric fault"):
            fut.result(30)
    with pytest.raises(F.FabricError):
        svc.serve()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit_featurize(_tiny_stack(1), eps)
    assert svc._fabric_error is not None
    svc.close()
    svc.close()                        # idempotent after a fabric failure


# ---------------------------------------------------------------------------
# Chaos scenarios (multi-process cohorts + fault injection)
# ---------------------------------------------------------------------------

_CHAOS_PRELUDE = """
    import dataclasses, os, sys, time
    import numpy as np
    from repro.serve.sweep_service import ServiceConfig, SweepService
    from repro.core import predictors as PRED

    mesh = _M.make_sweep_mesh()
    # launch_timeout_s must cover a FIRST launch's executable compile
    # under full-cohort CPU contention (tens of seconds on a loaded CI
    # box) -- a too-small deadline spuriously evicts healthy followers
    scfg = ServiceConfig(launch_timeout_s=%s, heartbeat_s=0.25,
                         max_wait_ms=20.0)
    svc = SweepService(scfg, mesh=mesh)
    rng = np.random.default_rng(0)
    stack = rng.standard_normal((4, 32, 32)).astype(np.float32)
    eps = np.asarray([1e-3, 1e-2, 1e-1], np.float32)

    def ref(x):
        return np.asarray(PRED.features_sweep(x, eps, sharded=False))
"""


def _chaos_body(tail: str, launch_timeout_s: float = 45) -> str:
    # dedent each fragment here: the prelude and the tails carry
    # different source indentation, and run_procs dedents only once
    return (textwrap.dedent(_CHAOS_PRELUDE % launch_timeout_s)
            + textwrap.dedent(tail))


def test_follower_loss_recovery_3proc():
    """The headline scenario: a 3-process fabric loses one follower
    mid-launch (SIGKILL inside its collective join).  The leader detects
    it within the launch deadline, shrinks to the 2 survivors, relaunches
    on the KV transport, and every outstanding future -- including the
    in-flight one -- completes bit-equal to the single-device engine.
    The surviving follower re-joins and later shuts down cleanly."""
    outs = run_procs(_chaos_body("""
        if PID == 0:
            r1 = svc.submit_featurize(stack, eps).result(60)
            assert np.array_equal(r1, ref(stack))
            t0 = time.monotonic()
            futs = [svc.submit_featurize(stack[i*2:(i+1)*2] + i, eps)
                    for i in range(2)]
            rs = [f.result(180) for f in futs]
            dt = time.monotonic() - t0
            for i, r in enumerate(rs):
                assert np.array_equal(r, ref(stack[i*2:(i+1)*2] + i)), i
            st = svc.stats()
            assert st["epoch"] == 1 and st["transport"] == "kv", st
            assert st["recoveries"] == 1 and st["procs"] == [0, 1], st
            # detection + recovery + relaunch stays well under the old
            # behaviour of waiting out the 560 s child-reap timeout
            assert dt < 3 * scfg.launch_timeout_s, dt
            print("RECOVERED BITEXACT", flush=True)
            svc.close()
            print("CLOSED", flush=True)
        else:
            try:
                svc.serve()
                print("SERVED-CLEAN", flush=True)
            except Exception as e:
                print("SERVED-ERR", type(e).__name__,
                      getattr(e, "kind", None), flush=True)
            svc.close()
        sys.stdout.flush(); os._exit(0)
    """), num_procs=3, devices=2, timeout=300,
        proc_env={2: {"REPRO_FAULT_INJECT": "follower_launch:kill:2"}},
        expect_fail={2})
    assert "RECOVERED BITEXACT" in outs[0] and "CLOSED" in outs[0]
    assert "SERVED-CLEAN" in outs[1]


def test_follower_loss_during_warmup_degrades_to_local():
    """A follower dying during the leader's warmup launch recovers the
    same way as during serving: with no other survivors the fabric
    degrades to the single-process path and requests still complete."""
    outs = run_procs(_chaos_body("""
        if PID == 0:
            svc.warmup([(32, 32)], grid_sizes=(3,), row_buckets=(4,))
            r = svc.submit_featurize(stack, eps).result(60)
            assert np.array_equal(r, ref(stack))
            st = svc.stats()
            assert st["recoveries"] == 1 and st["procs"] == [0], st
            print("WARMUP RECOVERED", flush=True)
            svc.close()
        else:
            try:
                svc.serve()
            except Exception as e:
                print("SERVED-ERR", type(e).__name__,
                      getattr(e, "kind", None), flush=True)
            svc.close()
        sys.stdout.flush(); os._exit(0)
    """), num_procs=2, devices=2, timeout=300,
        proc_env={1: {"REPRO_FAULT_INJECT": "follower_launch:kill:1"}},
        expect_fail={1})
    assert "WARMUP RECOVERED" in outs[0]


def test_double_fault_shrinks_twice_then_serves_local():
    """Two faults in one request: follower 2 dies on the gloo launch,
    then follower 1 dies on the post-recovery KV launch.  The leader
    sheds both across two epochs and still completes the future
    bit-equal, alone."""
    outs = run_procs(_chaos_body("""
        if PID == 0:
            r1 = svc.submit_featurize(stack, eps).result(60)
            assert np.array_equal(r1, ref(stack))
            r2 = svc.submit_featurize(stack + 1, eps).result(180)
            assert np.array_equal(r2, ref(stack + 1))
            st = svc.stats()
            assert st["recoveries"] == 2 and st["procs"] == [0], st
            assert st["epoch"] >= 2 and st["transport"] == "kv", st
            print("DOUBLE-FAULT SURVIVED", flush=True)
            svc.close()
        else:
            try:
                svc.serve()
            except Exception as e:
                print("SERVED-ERR", type(e).__name__,
                      getattr(e, "kind", None), flush=True)
            svc.close()
        sys.stdout.flush(); os._exit(0)
    """), num_procs=3, devices=2, timeout=300,
        proc_env={2: {"REPRO_FAULT_INJECT": "follower_launch:kill:2"},
                  1: {"REPRO_FAULT_INJECT": "kv_launch:kill:1"}},
        expect_fail={1, 2})
    assert "DOUBLE-FAULT SURVIVED" in outs[0]


def test_leader_death_raises_typed_error_on_followers():
    """With the coordination service in its own process (so the KV store
    survives), a leader SIGKILL mid-launch releases the follower from
    serve() with FabricError(kind='leader_lost') promptly -- it must not
    hang in the collective forever -- and the follower exits 0."""
    outs = run_procs(_chaos_body("""
        if PID == 0:
            r1 = svc.submit_featurize(stack, eps).result(60)
            assert np.array_equal(r1, ref(stack))
            svc.submit_featurize(stack, eps).result(60)   # killed here
            print("UNEXPECTED SURVIVAL", flush=True)
        else:
            t0 = time.monotonic()
            try:
                svc.serve()
                print("SERVED-CLEAN (unexpected)", flush=True)
            except Exception as e:
                # prompt: launch1 (compile-bound) + a few heartbeat
                # windows, never a wedged-forever collective
                dt = time.monotonic() - t0
                assert dt < 150, dt
                print("SERVED-ERR", type(e).__name__,
                      getattr(e, "kind", None), flush=True)
            svc.close()
        sys.stdout.flush(); os._exit(0)
    """), num_procs=2, devices=2, timeout=300,
        proc_env={0: {"REPRO_FAULT_INJECT": "leader_launch:kill:2"}},
        expect_fail={0}, external_coordinator=True)
    assert "SERVED-ERR FabricError leader_lost" in outs[1]
    assert "UNEXPECTED SURVIVAL" not in outs[0]


def test_hung_follower_evicted_within_deadline():
    """A wedged-but-alive follower (hangs inside the join, heartbeat
    thread still running) cannot be told apart from inside the
    collective: the leader's launch deadline expires, it evicts the
    follower and completes leader-local; the follower's bounded join
    notices the new epoch, learns it was evicted, and serve() raises
    FabricError(kind='evicted').  Both exit 0."""
    outs = run_procs(_chaos_body("""
        if PID == 0:
            r1 = svc.submit_featurize(stack, eps).result(120)
            assert np.array_equal(r1, ref(stack))       # warm launch
            # executables are compiled now: a short deadline cleanly
            # bounds the wedged launch without risking compile evictions
            svc.scfg = dataclasses.replace(svc.scfg, launch_timeout_s=8.0)
            t0 = time.monotonic()
            r2 = svc.submit_featurize(stack + 1, eps).result(120)
            dt = time.monotonic() - t0
            assert np.array_equal(r2, ref(stack + 1))
            st = svc.stats()
            assert st["recoveries"] == 1 and st["procs"] == [0], st
            assert dt < 45, dt
            print("HUNG FOLLOWER EVICTED", flush=True)
            svc.close()
        else:
            try:
                svc.serve()
                print("SERVED-CLEAN (unexpected)", flush=True)
            except Exception as e:
                print("SERVED-ERR", type(e).__name__,
                      getattr(e, "kind", None), flush=True)
            svc.close()
        sys.stdout.flush(); os._exit(0)
    """), num_procs=2, devices=2, timeout=300,
        proc_env={1: {"REPRO_FAULT_INJECT": "follower_launch:hang:2:3600"}})
    assert "HUNG FOLLOWER EVICTED" in outs[0]
    assert "SERVED-ERR FabricError evicted" in outs[1]
