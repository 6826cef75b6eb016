"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
initialization.  Single pod: (data=16, model=16) = 256 chips (TPU v5e pod
slice); multi-pod: (pod=2, data=16, model=16) = 512 chips.
"""
from __future__ import annotations

import time

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """The one mesh constructor of this repo.

    Every axis is ``Auto``: the sweep, train and serve paths leave
    sharding propagation to XLA and slice ragged results freely, which
    ``jax.make_mesh``'s default ``Explicit`` axes reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def _probe_coordinator_port(address: str, attempts: int = 10,
                            wait_s: float = 0.3) -> None:
    """Pre-flight the coordinator bind: the embedded coordination
    service CHECK-aborts the whole process (uncatchable) when its port
    is taken, so probe with a plain socket first and retry a bounded
    number of times (a just-released port clears TIME_WAIT quickly).
    Raises a *catchable* RuntimeError when the port stays busy, which
    harnesses translate into a relaunch on a fresh port."""
    import socket
    host, _, port = address.rpartition(":")
    last = None
    for _ in range(max(1, attempts)):
        try:
            with socket.socket() as s:
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind((host or "127.0.0.1", int(port)))
            return
        except OSError as e:
            last = e
            time.sleep(wait_s)
    raise RuntimeError(
        f"coordinator port {address} is already in use "
        f"(after {attempts} probes): {last}")


def dist_init(coordinator_address: str | None = None, *,
              num_processes: int | None = None,
              process_id: int | None = None,
              cpu_collectives: str = "gloo",
              external_coordinator: bool = False,
              init_timeout_s: float = 60.0) -> tuple[int, int]:
    """Join the multi-process sweep fabric: ``jax.distributed`` init.

    Call ONCE per process, before any other jax use, on every process
    that will participate in a process-spanning sweep mesh.  Arguments
    left ``None`` fall back to jax's environment autodetection
    (``JAX_COORDINATOR_ADDRESS`` etc., or the cluster plugin on managed
    fleets).  On the CPU backend the collective implementation defaults
    to gloo, which is what the two-process test harness and the
    ``bench_multihost`` gate run on; combine with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (exported
    before jax is imported) for N virtual devices per process.

    Process 0 normally EMBEDS the coordination service; if that process
    dies, every other process's coordination client hard-aborts, so a
    follower can never outlive its leader.  For leader-death tolerance
    pass ``external_coordinator=True`` on every process (including
    process 0) and host the service elsewhere with
    :func:`serve_coordinator` -- the processes then build plain
    coordination clients against it, and losing any *worker* process
    (leader included) leaves the others functional.

    Returns ``(process_index, process_count)``.
    """
    jax.config.update("jax_cpu_collectives_implementation", cpu_collectives)
    if external_coordinator:
        if (coordinator_address is None or num_processes is None or
                process_id is None):
            raise ValueError(
                "external_coordinator=True needs explicit "
                "coordinator_address, num_processes and process_id")
        from jax._src import distributed as _dist
        from jax._src.lib import _jax
        client = _jax.get_distributed_runtime_client(
            coordinator_address, process_id,
            init_timeout=max(1, int(init_timeout_s)), use_compression=True)
        client.connect()
        gs = _dist.global_state
        gs.client = client
        gs.process_id = process_id
        gs.num_processes = num_processes
        gs.coordinator_address = coordinator_address
        return jax.process_index(), jax.process_count()
    if coordinator_address is not None and process_id == 0:
        # only the coordinator-hosting process races for the bind
        _probe_coordinator_port(coordinator_address)
    kw = {}
    if coordinator_address is not None:
        kw["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kw["num_processes"] = num_processes
    if process_id is not None:
        kw["process_id"] = process_id
    jax.distributed.initialize(**kw)
    return jax.process_index(), jax.process_count()


def serve_coordinator(address: str, num_processes: int,
                      block: bool = True):
    """Host a standalone ``jax.distributed`` coordination service.

    Run this in its OWN process (it should never be a fabric worker:
    the point is that worker deaths -- the leader's included -- leave
    the coordination service up for the survivors' KV store, barriers
    and fault detection).  Workers join with
    ``dist_init(address, ..., external_coordinator=True)``.

    ``block=True`` serves until the process is killed; ``block=False``
    returns the service handle (caller keeps it alive).
    """
    from jax._src.lib import _jax
    _probe_coordinator_port(address)
    host, _, port = address.rpartition(":")
    service = _jax.get_distributed_runtime_service(
        f"[::]:{port}", int(num_processes))
    if not block:
        return service
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    return service


def make_sweep_mesh(num_devices: int | None = None):
    """1-D ("data",) mesh for distributed featurization sweeps.

    The sweep engine shards its slice axis over "data" (logical axis
    "slices"; see ``repro.dist.sweep``), so a flat all-device data mesh
    serves one sweep from every device/host.  On a CPU dev box export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax is
    imported to get N virtual devices.

    Process-aware: under an initialized ``jax.distributed`` runtime (see
    :func:`dist_init`) ``jax.devices()`` is the GLOBAL device list
    (``jax.process_count() x local_device_count``), so the default mesh
    spans every process and each process later feeds its own block of
    the slice axis (``repro.dist.sweep`` handles the per-process
    ingestion and gather).  Asking for more devices than the runtime has
    -- in particular asking for a process-spanning mesh when
    ``jax.distributed`` was never initialized -- raises immediately with
    a clear error instead of hanging in a half-joined collective.
    """
    devs = jax.devices()
    n = num_devices if num_devices is not None else len(devs)
    if n < 1:
        raise ValueError(f"make_sweep_mesh needs >= 1 device, got {n}")
    if n > len(devs):
        local = jax.local_device_count()
        hint = ""
        if jax.process_count() == 1 and n > local:
            hint = (" -- a mesh spanning more than this process's "
                    f"{local} local device(s) needs the multi-process "
                    "fabric: call repro.launch.mesh.dist_init(...) on "
                    "every participating process before building the mesh")
        raise ValueError(
            f"make_sweep_mesh({n}) exceeds the {len(devs)} visible "
            f"device(s) of this runtime{hint}")
    return make_mesh((n,), ("data",), devices=devs[:n])


# TPU v5e hardware model used by the roofline analysis (per chip).
# Compute/bandwidth peaks live in the backend-keyed kernels.tune table;
# interconnect and HBM capacity are mesh-level concerns kept here.
from repro.kernels.tune import BACKEND_HW as _BHW  # noqa: E402

HW = {
    "peak_flops_bf16": _BHW["tpu-v5e"]["peak_flops"],   # FLOP/s
    "hbm_bw": _BHW["tpu-v5e"]["mem_bw"],                # bytes/s
    "ici_bw": 50e9,                # bytes/s per link
    "hbm_bytes": 16e9,             # capacity
}
