"""Sweep-service launcher: synthetic multi-client UC1/UC2/featurize load.

Drives ``repro.serve.sweep_service.SweepService`` with concurrent client
threads issuing a mixed request stream over a small set of hot fields --
the production traffic shape the coalescing layers target -- and prints
throughput, latency quantiles, and cache/launch statistics.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    python -m repro.launch.sweep_serve --clients 8 --requests 64 --mesh auto

Multi-process fabric (one command per process; process 0 is the leader
that trains the models and runs the clients, the rest serve as
followers)::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    python -m repro.launch.sweep_serve --mesh auto \\
        --coordinator 127.0.0.1:7654 --num-processes 2 --process-id 0 &
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    python -m repro.launch.sweep_serve --mesh auto \\
        --coordinator 127.0.0.1:7654 --num-processes 2 --process-id 1

For leader-death tolerance, host the coordination service in its own
process (``--coordinator-only``) and join every worker with
``--external-coordinator``::

    python -m repro.launch.sweep_serve --coordinator 127.0.0.1:7654 \\
        --num-processes 2 --coordinator-only &
    ... --coordinator 127.0.0.1:7654 --num-processes 2 --process-id 0 \\
        --external-coordinator ...

``--launch-timeout-s`` bounds each collective launch on the leader
(size it to cover a first launch's executable compile);
``--queue-rows`` enables bounded-queue admission control (overload
rejects with ``RetryAfter`` instead of queueing without limit);
``--chaos SPEC`` arms ``repro.dist.faultinject`` on this process for
recovery drills (e.g. ``--chaos follower_launch:kill:2``).
"""
from __future__ import annotations

import argparse
import threading
import time

import numpy as np


def _exit_barrier():
    """Align process teardown on the multi-process fabric: a final
    collective barrier guarantees every in-flight gloo op has completed
    on all processes before any of them starts closing transports
    (otherwise a fast-exiting peer can reset connections under a slower
    one and abort it at interpreter shutdown)."""
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("sweep_serve_exit")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=64,
                    help="total requests across all clients")
    ap.add_argument("--fields", default="miranda-vx,scale-u")
    ap.add_argument("--hot-slices", type=int, default=4,
                    help="distinct slices per field the clients hammer")
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--compressor", default="zfp")
    ap.add_argument("--train-slices", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-wait-ms", type=float, default=3.0)
    ap.add_argument("--min-wait-ms", type=float, default=0.0,
                    help="adaptive micro-batch window floor under "
                         "sustained load")
    ap.add_argument("--max-live-batches", type=int, default=2,
                    help="launched-but-not-post-processed batches in "
                         "flight (admission control)")
    ap.add_argument("--no-adaptive-window", action="store_true",
                    help="pin the micro-batch window at --max-wait-ms "
                         "instead of adapting it to load")
    ap.add_argument("--cache-bytes", type=int, default=4 << 20)
    ap.add_argument("--mesh", default=None,
                    help="'auto' = 1-D all-device sweep mesh")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 -> join the "
                         "jax.distributed multi-process fabric")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--external-coordinator", action="store_true",
                    help="the coordination service runs out-of-process "
                         "(see --coordinator-only); workers survive "
                         "leader death")
    ap.add_argument("--coordinator-only", action="store_true",
                    help="host the standalone coordination service at "
                         "--coordinator and exit on SIGINT (not a "
                         "fabric worker)")
    ap.add_argument("--launch-timeout-s", type=float, default=60.0,
                    help="leader's bound per collective launch; must "
                         "cover a first launch's executable compile")
    ap.add_argument("--heartbeat-s", type=float, default=0.5,
                    help="fabric liveness publish interval")
    ap.add_argument("--queue-rows", type=int, default=0,
                    help="bounded-queue admission control: reject with "
                         "RetryAfter beyond this many queued rows "
                         "(0 = unbounded)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="arm repro.dist.faultinject on this process, "
                         "e.g. follower_launch:kill:2")
    from repro.launch import xla_flags as XF
    ap.add_argument("--xla-preset", default=None,
                    choices=sorted(XF.PRESETS),
                    help="apply a curated per-backend XLA_FLAGS preset "
                         "(launch.xla_flags) before jax initializes; "
                         "user-exported XLA_FLAGS still win on conflicts")
    args = ap.parse_args()

    if args.xla_preset:
        # must precede the mesh import below -- that is the first jax
        # import of this process, where XLA_FLAGS is read
        XF.apply_preset(args.xla_preset)

    from repro.launch import mesh as M
    if args.coordinator_only:
        if args.coordinator is None or args.num_processes is None:
            raise SystemExit("--coordinator-only needs --coordinator "
                             "and --num-processes")
        print(f"# serving coordination service at {args.coordinator} "
              f"for {args.num_processes} processes ...", flush=True)
        M.serve_coordinator(args.coordinator, args.num_processes)
        return
    if args.chaos is not None:
        from repro.dist import faultinject
        faultinject.configure(args.chaos)
        print(f"# chaos armed: {args.chaos}")
    if args.coordinator is not None:
        # must run before any other jax use (device counts lock at init)
        pid, nproc = M.dist_init(
            args.coordinator, num_processes=args.num_processes,
            process_id=args.process_id,
            external_coordinator=args.external_coordinator)
        print(f"# joined fabric: process {pid}/{nproc}")

    import jax
    import jax.numpy as jnp
    from repro import compressors as C
    from repro.core import pipeline as PL, usecases as UC
    from repro.launch import compile_cache
    compile_cache.enable()
    from repro.data import scientific
    from repro.serve.sweep_service import ServiceConfig, SweepService

    mesh = None
    if args.mesh == "auto" and len(jax.devices()) > 1:
        mesh = M.make_sweep_mesh()
    elif args.mesh and args.mesh != "auto":
        shape = tuple(int(x) for x in args.mesh.split("x"))
        mesh = M.make_mesh(shape, ("data",) if len(shape) == 1
                           else ("data", "model"))

    if args.coordinator is not None:
        from repro.dist import sweep as DS
        if not DS.mesh_spans_processes(mesh):
            # fail loudly on every process: a non-spanning mesh would
            # leave followers serving a service that never stops and the
            # leader blocked in the exit barrier
            raise SystemExit(
                "--coordinator needs a process-spanning mesh: pass "
                "--mesh auto (or a shape covering every process's "
                "devices)")

    scfg = ServiceConfig(max_batch_slices=args.max_batch,
                         max_wait_ms=args.max_wait_ms,
                         min_wait_ms=args.min_wait_ms,
                         adapt_window=not args.no_adaptive_window,
                         max_live_batches=args.max_live_batches,
                         cache_bytes=args.cache_bytes,
                         launch_timeout_s=args.launch_timeout_s,
                         heartbeat_s=args.heartbeat_s,
                         max_queue_rows=args.queue_rows)

    if args.coordinator is not None and jax.process_index() != 0:
        # follower: contribute this process's devices until the leader
        # closes the fabric -- no local clients, no model training
        svc = SweepService(scfg, mesh=mesh)
        print(f"# follower {jax.process_index()} serving ...", flush=True)
        try:
            svc.serve()
        except Exception as e:
            # typed fabric fault (leader death, eviction): the fabric is
            # gone, so no exit barrier -- report and leave
            print(f"# follower {jax.process_index()} fabric error: {e}")
            svc.close()
            return
        print(f"# follower {jax.process_index()} done "
              f"({svc.launches} collective launches joined)")
        if svc.stats()["transport"] == "gloo":
            # post-recovery fabrics exchange over KV (gloo is poisoned
            # after a faulted collective), so only an unfaulted run may
            # align teardown with a gloo barrier
            _exit_barrier()
        return

    # construct the service BEFORE model training: the leader's heartbeat
    # starts at construction, so followers joining the fabric can already
    # distinguish "leader busy training" from "leader dead"
    svc = SweepService(scfg, mesh=mesh)

    fields = args.fields.split(",")
    print(f"# training {args.compressor} grid models on {fields} ...")
    hot, grid_models, uc2_models = {}, {}, {}
    for f in fields:
        slices = scientific.field_slices(
            f, count=args.train_slices + args.hot_slices, n=args.n)
        rng = float(jnp.max(slices) - jnp.min(slices))
        ebs = [r * rng for r in (1e-5, 1e-4, 1e-3, 1e-2)]
        train = slices[:args.train_slices]
        grid_models[f] = UC.EbGridModel.train(train, args.compressor, ebs)
        eps = ebs[2]
        models = {}
        for name in (args.compressor, "bitgrooming"):
            comp = C.get(name)
            crs = jnp.asarray([comp.cr(s, eps) for s in train])
            models[name] = PL.CRPredictor.train(train, crs, eps)
        uc2_models[f] = (models, eps)
        hot[f] = slices[args.train_slices:]

    lat, lock = [], threading.Lock()

    def client(svc, cid: int, count: int):
        rnd = np.random.default_rng(cid)
        for i in range(count):
            f = fields[int(rnd.integers(len(fields)))]
            x = hot[f][int(rnd.integers(args.hot_slices))]
            t0 = time.perf_counter()
            if rnd.random() < 0.5:
                svc.find_eb(grid_models[f], x,
                            target_cr=float(rnd.uniform(3.0, 12.0)))
            else:
                models, eps = uc2_models[f]
                svc.best_compressor(models, x, eps)
            with lock:
                lat.append(time.perf_counter() - t0)

    per_client = max(1, args.requests // args.clients)
    with svc:
        svc.warmup([(args.n, args.n)], grid_sizes=(1, 4),
                   row_buckets=(1, args.clients))
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(svc, c, per_client))
                   for c in range(args.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stats = svc.stats()

    done = len(lat)
    lat_ms = np.sort(np.asarray(lat)) * 1e3
    print(f"served {done} requests from {args.clients} clients in "
          f"{wall:.2f}s -> {done / wall:.1f} req/s")
    print(f"latency p50={np.percentile(lat_ms, 50):.1f}ms "
          f"p95={np.percentile(lat_ms, 95):.1f}ms "
          f"max={lat_ms[-1]:.1f}ms")
    cache = stats["cache"]
    total_probes = cache["hits"] + cache["misses"]
    print(f"launches={stats['launches']} rows={stats['rows_launched']} "
          f"pad_rows={stats['pad_rows']} batches={stats['batches']} "
          f"executables={stats['executables']} "
          f"window_ms={stats['window_ms']:.3f} "
          f"(shrinks={stats['window_shrinks']})")
    for name, m in sorted(stats["methods"].items()):
        print(f"method {name}: {m['completed']} done ({m['failed']} "
              f"failed), {m['rows']} rows, p50={m['p50_ms']:.1f}ms "
              f"p95={m['p95_ms']:.1f}ms")
    print(f"cache: hit_rate={cache['hits'] / max(total_probes, 1):.2%} "
          f"({cache['hits']}/{total_probes}), entries={cache['entries']}, "
          f"bytes={cache['bytes']}", flush=True)
    if stats["recoveries"]:
        print(f"recoveries={stats['recoveries']} epoch={stats['epoch']} "
              f"transport={stats['transport']} procs={stats['procs']} "
              f"rejected={stats['rejected']}")
    if args.coordinator is not None and stats["transport"] == "gloo":
        _exit_barrier()    # see the follower-side note on faulted fabrics


if __name__ == "__main__":
    main()
