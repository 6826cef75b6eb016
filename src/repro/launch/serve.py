"""Serving launcher: batched generate with optional KV compression.

    python -m repro.launch.serve --arch granite-3-2b --smoke \\
        --batch 4 --prompt-len 32 --steps 16 --kv-compress

With ``--kv-gate-service`` the engine's KV-cache gate CRs are served by
the shared :class:`repro.serve.sweep_service.SweepService` through its
registered ``kv_gate`` method instead of the engine's private jit --
concurrent engines coalesce their gate scoring into batched launches and
repeated KV blocks ride the cross-request cache.
"""
import argparse
import time

from repro.launch import xla_flags as XF


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--kv-compress", action="store_true")
    ap.add_argument("--kv-gate-service", action="store_true",
                    help="serve KV-gate CR predictions through the "
                         "shared sweep service (kv_gate method) instead "
                         "of the engine's private jit")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--xla-preset", default=None,
                    choices=sorted(XF.PRESETS),
                    help="apply a curated per-backend XLA_FLAGS preset "
                         "(launch.xla_flags) before jax initializes; "
                         "user-exported XLA_FLAGS still win on conflicts")
    args = ap.parse_args()

    if args.xla_preset:
        XF.apply_preset(args.xla_preset)

    # deferred so --xla-preset lands before the first jax import reads
    # XLA_FLAGS
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_arch, get_smoke
    from repro.dist import sharding as S
    from repro.launch import mesh as M
    from repro.serve.engine import Engine, ServeConfig
    from repro.train import train_step as TS

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    params = TS.init_state(cfg, jax.random.PRNGKey(0)).params
    scfg = ServeConfig(max_len=args.max_len, kv_compress=args.kv_compress)

    svc = None
    if args.kv_gate_service:
        # construct OUTSIDE the (data, model) serving mesh context: the
        # gate's int8-CR launcher is a plain vmapped jit, and the service
        # must not capture the token engine's mesh for its own launches
        from repro.serve.sweep_service import ServiceConfig, SweepService
        svc = SweepService(ServiceConfig(max_wait_ms=1.0), mesh=None)

    def run():
        eng = Engine(cfg, params, scfg, sweep_service=svc)
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len),
            0, cfg.vocab_size, dtype=jnp.int32)}
        t0 = time.time()
        out = eng.generate(batch, steps=args.steps)
        dt = time.time() - t0
        toks = args.batch * args.steps
        print(f"{cfg.name}: generated {out.shape} in {dt:.1f}s "
              f"({toks / dt:.1f} tok/s)")
        if args.kv_compress:
            print(f"KV gate: {eng.kv_saved_bytes:,}/{eng.kv_total_bytes:,} "
                  f"bytes saved")

    try:
        if args.mesh:
            shape = tuple(int(x) for x in args.mesh.split("x"))
            axes = ("data", "model") if len(shape) == 2 else ("pod", "data", "model")
            with S.use_mesh(M.make_mesh(shape, axes)):
                run()
        else:
            run()
    finally:
        if svc is not None:
            gate = svc.stats()["methods"].get("kv_gate")
            if gate is not None:
                print(f"kv_gate service: {gate['completed']} requests, "
                      f"{gate['rows']} leaves, p50={gate['p50_ms']:.1f}ms "
                      f"p95={gate['p95_ms']:.1f}ms")
            svc.close()


if __name__ == "__main__":
    main()
