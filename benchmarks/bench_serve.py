"""Sweep-service benchmark: coalesced vs serial dispatch + the bit-equal
and cross-request-cache gates (ISSUE 3 acceptance).

Scenarios (run in a child interpreter with 8 virtual CPU devices, since
the device count is locked at jax init):

* ``mixed``     -- a hot-field session: ROUNDS x 8 concurrent mixed
                   UC1/UC2 requests over 2 hot slices (1 target-CR search
                   + 3 best-compressor rankings per slice per round, UC1
                   targets varying by round).  Serial baseline = today's
                   per-request dispatch (``find_error_bound_for_cr`` /
                   ``best_compressor`` called one at a time, each
                   featurizing on its own, every round).  The service
                   coalesces round 1 into ONE launch of 2 deduplicated
                   rows and serves later rounds from the cross-request
                   cache with zero launches.  GATED: >= 3x session
                   throughput, bit-equal results.  The cold first round
                   alone (pure coalescing+dedup, no cache) is reported as
                   ``cold_speedup`` -- on a 2-core CI host its compute
                   parallelism is limited, the cache is what pays here.
* ``fanin``     -- 8 concurrent featurize requests of 2 slices each under
                   the mesh.  Serial = one auto-sharded launch per
                   request (each padded 2 -> 8 rows, the waste named in
                   the ROADMAP follow-on); coalesced = ONE packed 16-row
                   ``gather=False`` launch.  GATED: >= 1.5x, bit-equal.
* ``cache``     -- resubmitting a UC1 on a hot slice after the mixed run:
                   GATED: zero additional sweep launches.
* ``load_sweep``-- open-loop paced arrivals at 1x/3x/10x the mixed run's
                   measured request rate, mixing UC1/UC2/kv_gate over hot
                   fields against a warm cache.  Per-method p50/p95 (from
                   ``stats()["methods"]``) land in the JSON per rate.
                   GATED: worst per-method p95 at 10x stays bounded (the
                   adaptive micro-batch window must shrink under load
                   instead of letting queueing delay compound).

Writes machine-readable ``results/BENCH_serve.json`` (throughput, p50/p95
latency, cache hit rate, per-method load-sweep tails) so the perf
trajectory is tracked across PRs.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

FIELD = "miranda-vx"
N = 160                  # slice side
GRID_RELS = (1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 1e-2)
TRAIN = 10               # training slices for the grid / UC2 models
REPS = 3                 # timed repetitions (median)
ROUNDS = 4               # hot-field session rounds of 8 requests
DEVICES = 8

MIXED_GATE = 3.0
FANIN_GATE = 2.0
LOAD_REQS = 40           # paced requests per load-sweep rate
LOAD_MULTS = (1, 3, 10)
# p95 bound at 10x: generous absolute ceiling OR a multiple of the idle
# p50 -- CI hosts are 2-core, the gate is about tails not compounding
LOAD_P95_ABS_MS = 1500.0
LOAD_P95_REL = 20.0


def _percentiles(lat_s):
    ms = np.sort(np.asarray(lat_s)) * 1e3
    return float(np.percentile(ms, 50)), float(np.percentile(ms, 95))


def _child(out_path: str) -> None:
    import jax
    import jax.numpy as jnp
    from repro import compressors as C
    from repro.core import pipeline as PL, predictors as P, usecases as UC
    from repro.data import scientific
    from repro.dist import sharding as S
    from repro.launch import mesh as M
    from repro.serve.sweep_service import ServiceConfig, SweepService

    assert len(jax.devices()) == DEVICES, jax.devices()
    mesh = M.make_sweep_mesh()

    slices = scientific.field_slices(FIELD, count=TRAIN + 18, n=N)
    rng = float(jnp.max(slices) - jnp.min(slices))
    ebs = [r * rng for r in GRID_RELS]
    train = slices[:TRAIN]
    gm = UC.EbGridModel.train(train, "zfp", ebs)
    eps = ebs[3]
    uc2 = {}
    for name in ("zfp", "bitgrooming"):
        comp = C.get(name)
        crs = jnp.asarray([comp.cr(s, eps) for s in train])
        uc2[name] = PL.CRPredictor.train(train, crs, eps)

    hot = [slices[TRAIN], slices[TRAIN + 1]]
    round_targets = [(5.0, 8.0), (4.0, 9.5), (6.5, 7.0), (5.5, 8.5)][:ROUNDS]

    # ---- mixed UC1/UC2 hot-field session: ROUNDS x 8 requests ---------
    def serial_round(targets):
        out = []
        for x, t in zip(hot, targets):
            out.append(("uc1", UC.find_error_bound_for_cr(gm, x, t)))
            for _ in range(3):
                out.append(("uc2", UC.best_compressor(uc2, x, eps)))
        return out

    serial_ref = []

    def serial_session():
        serial_ref[:] = [serial_round(t) for t in round_targets]

    from benchmarks import common as BC
    serial_s = BC.time_fn(serial_session, warmup=1, iters=REPS)

    def coalesced_round(svc, targets, lat):
        results = [None] * 8

        def one(i, kind, fn):
            t0 = time.perf_counter()
            results[i] = (kind, fn())
            lat.append(time.perf_counter() - t0)

        threads, i = [], 0
        for x, t in zip(hot, targets):
            threads.append(threading.Thread(
                target=one, args=(i, "uc1",
                                  lambda x=x, t=t: svc.find_eb(gm, x, t))))
            i += 1
            for _ in range(3):
                threads.append(threading.Thread(
                    target=one, args=(i, "uc2",
                                      lambda x=x: svc.best_compressor(
                                          uc2, x, eps))))
                i += 1
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return results

    scfg = ServiceConfig(max_batch_slices=8, max_wait_ms=5.0)
    # warm the coalesced executables once (persistent across services)
    with SweepService(scfg, mesh=mesh) as svc:
        svc.warmup([(N, N)], grid_sizes=(len(ebs),), row_buckets=(2,))
        coalesced_round(svc, round_targets[0], [])
    coal_times, cold_times, lat = [], [], []
    results = cache_stats = cache_extra_launches = None
    for rep in range(REPS):
        with SweepService(scfg, mesh=mesh) as svc:   # cold cache each rep
            lat = []
            t0 = time.perf_counter()
            results = []
            for r, targets in enumerate(round_targets):
                results.append(coalesced_round(svc, targets, lat))
                if r == 0:
                    cold_times.append(time.perf_counter() - t0)
            coal_times.append(time.perf_counter() - t0)
            if rep == REPS - 1:
                # cache gate: one more UC1 on a hot slice -> zero launches
                before = svc.launches
                again = svc.find_eb(gm, hot[0], round_targets[0][0])
                cache_extra_launches = svc.launches - before
                assert again == results[0][0][1]
                cache_stats = svc.stats()["cache"]
                launches_session = svc.launches
    coal_s = float(np.median(coal_times))
    cold_s = float(np.median(cold_times))

    mixed_equal = all(
        (rk == sk) and (rv == sv)
        for rnd, srnd in zip(results, serial_ref)
        for (rk, rv), (sk, sv) in zip(rnd, srnd))
    p50, p95 = _percentiles(lat)
    n_req = 8 * ROUNDS

    # ---- featurize fan-in: 8 x (k=2) requests under the mesh ----------
    stacks = [slices[TRAIN + 2 + 2 * i: TRAIN + 4 + 2 * i] for i in range(8)]
    epss = np.asarray(ebs, np.float32)

    def serial_fanin():
        # today's behavior: one auto-sharded launch per request, each
        # padded from 2 rows to the 8-device extent
        with S.use_mesh(mesh):
            return [np.asarray(P.features_sweep(st, epss)) for st in stacks]

    fan_serial_ref = serial_fanin()                  # warm
    fan_serial_s = BC.time_fn(serial_fanin, warmup=0, iters=1)

    fan_scfg = ServiceConfig(max_batch_slices=16, max_wait_ms=5.0)
    with SweepService(fan_scfg, mesh=mesh) as svc:   # warm executables
        svc.warmup([(N, N)], grid_sizes=(len(ebs),), row_buckets=(16,))

    def coalesced_fanin(svc):
        futs = [svc.submit_featurize(st, epss) for st in stacks]
        return [f.result(timeout=300) for f in futs]

    fan_walls, fan_res, fan_stats = [], None, None
    for _ in range(REPS):
        with SweepService(fan_scfg, mesh=mesh) as svc:  # cold cache
            t0 = time.perf_counter()
            fan_res = coalesced_fanin(svc)
            fan_walls.append(time.perf_counter() - t0)
            fan_stats = svc.stats()
    fan_coal_s = float(np.median(fan_walls))
    fan_equal = all(np.array_equal(a, b)
                    for a, b in zip(fan_res, fan_serial_ref))

    # ---- load sweep: open-loop paced arrivals at 1x/3x/10x ------------
    base_rps = n_req / coal_s
    rnd = np.random.default_rng(0)
    kv_leaves = [np.asarray(rnd.standard_normal((4, 4, 32, 32)), np.float32)
                 for _ in range(4)]
    load = {}
    for mult in LOAD_MULTS:
        rate = base_rps * mult
        with SweepService(scfg, mesh=mesh) as svc:
            svc.warmup([(N, N)], grid_sizes=(len(ebs),), row_buckets=(2,))
            svc.kv_gate(kv_leaves[:1])               # compile the gate jit
            coalesced_round(svc, round_targets[0], [])  # warm feature cache
            futs = []
            t0 = time.perf_counter()
            for i in range(LOAD_REQS):
                target = t0 + i / rate
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
                x, j = hot[i % 2], i % 5
                if j < 2:
                    futs.append(svc.submit_find_eb(
                        gm, x, round_targets[i % ROUNDS][i % 2]))
                elif j < 4:
                    futs.append(svc.submit_best_compressor(uc2, x, eps))
                else:
                    futs.append(svc.submit_kv_gate(
                        [kv_leaves[i % len(kv_leaves)]]))
            for fut in futs:
                fut.result(timeout=300)
            wall = time.perf_counter() - t0
            st = svc.stats()
        load[f"{mult}x"] = {
            "offered_rps": rate,
            "achieved_rps": LOAD_REQS / wall,
            "window_ms": st["window_ms"],
            "window_shrinks": st["window_shrinks"],
            "launches": st["launches"],
            "methods": {name: {k: m[k] for k in
                               ("completed", "rows", "p50_ms", "p95_ms")}
                        for name, m in st["methods"].items()},
        }
    p50_1x = max(m["p50_ms"] for m in load["1x"]["methods"].values())
    p95_10x = max(m["p95_ms"]
                  for m in load[f"{LOAD_MULTS[-1]}x"]["methods"].values())
    load_sweep = {
        "base_rps": base_rps,
        "requests_per_rate": LOAD_REQS,
        "rates": load,
        "p50_1x_ms": p50_1x,
        "p95_10x_ms": p95_10x,
        "p95_10x_limit_ms": max(LOAD_P95_ABS_MS, LOAD_P95_REL * p50_1x),
    }

    with open(out_path, "w") as f:
        json.dump({
            "load_sweep": load_sweep,
            "mixed": {
                "requests": n_req,
                "rounds": ROUNDS,
                "serial_s": serial_s,
                "coalesced_s": coal_s,
                "speedup": serial_s / coal_s,
                "cold_round_s": cold_s,
                "cold_speedup": (serial_s / ROUNDS) / cold_s,
                "throughput_rps": n_req / coal_s,
                "serial_throughput_rps": n_req / serial_s,
                "p50_ms": p50, "p95_ms": p95,
                "launches": launches_session,
                "bitequal": bool(mixed_equal),
                "cache": cache_stats,
                "cache_hit_rate": cache_stats["hits"] / max(
                    cache_stats["hits"] + cache_stats["misses"], 1),
            },
            "fanin": {
                "requests": 8,
                "serial_s": fan_serial_s,
                "coalesced_s": fan_coal_s,
                "speedup": fan_serial_s / fan_coal_s,
                "throughput_rps": 8 / fan_coal_s,
                "bitequal": bool(fan_equal),
                "launches": fan_stats["launches"],
                "rows_launched": fan_stats["rows_launched"],
            },
            "cache_second_uc1_extra_launches": cache_extra_launches,
        }, f, indent=1)


def main() -> dict:
    from benchmarks import common

    if "--child" in sys.argv:
        _child(sys.argv[sys.argv.index("--child") + 1])
        return {}

    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "serve.json")
        common.run_child_module("benchmarks.bench_serve", ["--child", out],
                                DEVICES)
        with open(out) as f:
            res = json.load(f)

    mixed, fanin = res["mixed"], res["fanin"]
    nr = mixed["requests"]
    common.emit("serve_mixed_serial", mixed["serial_s"] * 1e6 / nr,
                f"{nr} reqs in {mixed['serial_s'] * 1e3:.1f}ms")
    common.emit("serve_mixed_coalesced", mixed["coalesced_s"] * 1e6 / nr,
                f"speedup={mixed['speedup']:.2f}x "
                f"(cold {mixed['cold_speedup']:.2f}x) "
                f"p50={mixed['p50_ms']:.1f}ms p95={mixed['p95_ms']:.1f}ms "
                f"hit_rate={mixed['cache_hit_rate']:.2f} "
                f"bitequal={mixed['bitequal']}")
    common.emit("serve_fanin_serial", fanin["serial_s"] * 1e6 / 8,
                f"8 reqs in {fanin['serial_s'] * 1e3:.1f}ms")
    common.emit("serve_fanin_coalesced", fanin["coalesced_s"] * 1e6 / 8,
                f"speedup={fanin['speedup']:.2f}x launches="
                f"{fanin['launches']} bitequal={fanin['bitequal']}")
    ls = res["load_sweep"]
    common.emit("serve_load_p95_10x", ls["p95_10x_ms"] * 1e3,
                f"p95@10x={ls['p95_10x_ms']:.1f}ms "
                f"(limit {ls['p95_10x_limit_ms']:.0f}ms, "
                f"p50@1x={ls['p50_1x_ms']:.1f}ms, "
                f"window@10x={ls['rates']['10x']['window_ms']:.3f}ms)")
    common.save_json("BENCH_serve", res)

    assert mixed["bitequal"], "coalesced mixed results != serial dispatch"
    assert fanin["bitequal"], "coalesced featurize results != serial"
    assert res["cache_second_uc1_extra_launches"] == 0, \
        f"second UC1 on a hot field launched sweeps: {res}"
    assert mixed["speedup"] >= MIXED_GATE, \
        f"coalesced mixed speedup {mixed['speedup']:.2f}x < {MIXED_GATE}x"
    assert fanin["speedup"] >= FANIN_GATE, \
        f"coalesced fan-in speedup {fanin['speedup']:.2f}x < {FANIN_GATE}x"
    assert ls["p95_10x_ms"] <= ls["p95_10x_limit_ms"], \
        (f"load sweep: p95 at 10x = {ls['p95_10x_ms']:.1f}ms exceeds "
         f"{ls['p95_10x_limit_ms']:.0f}ms -- adaptive window failed to "
         f"keep the tail bounded")
    print(f"# mixed {mixed['speedup']:.2f}x (gate {MIXED_GATE}x), "
          f"fanin {fanin['speedup']:.2f}x (gate {FANIN_GATE}x), "
          f"cache hit rate {mixed['cache_hit_rate']:.2%}, "
          f"load p95@10x {ls['p95_10x_ms']:.1f}ms "
          f"(limit {ls['p95_10x_limit_ms']:.0f}ms) -- OK")
    return res


if __name__ == "__main__":
    main()
