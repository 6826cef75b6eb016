"""Training launcher: --arch <id> on the production mesh (or CPU smoke).

    python -m repro.launch.train --arch granite-8b --smoke --steps 20
    python -m repro.launch.train --arch granite-8b --mesh 16x16 \\
        --batch 256 --seq 4096 --microbatches 8 --compress

On real hardware the mesh axes map onto the pod slice; on this container
use --smoke (reduced config, single device) or the dry-run entry point.
"""
import argparse

import jax

from repro.configs.base import get_arch, get_smoke
from repro.ckpt.checkpoint import LossyPolicy
from repro.data.tokens import make_data_iter
from repro.dist import sharding as S
from repro.launch import mesh as M
from repro.train import loop as LOOP
from repro.train import optimizer as OPT
from repro.train import train_step as TS
from repro.train.grad_compress import CompressConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default=None, help="e.g. 16x16 (data x model)")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--lossy-ckpt", action="store_true")
    args = ap.parse_args()

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    compress = CompressConfig(enabled=True) if args.compress else None

    def run():
        state = TS.init_state(cfg, jax.random.PRNGKey(0),
                              compress=compress is not None)
        step = jax.jit(TS.make_train_step(
            cfg, OPT.AdamWConfig(lr=args.lr), microbatches=args.microbatches,
            compress=compress))
        data = make_data_iter(cfg, args.batch, args.seq)
        lc = LOOP.LoopConfig(
            total_steps=args.steps, ckpt_every=max(args.steps // 4, 1),
            ckpt_dir=args.ckpt_dir,
            lossy=LossyPolicy(enabled=args.lossy_ckpt))
        state, res = LOOP.run(cfg, state, step, data, lc)
        ks = sorted(res.losses)
        print(f"{cfg.name}: steps {ks[0]}..{ks[-1]} "
              f"loss {res.losses[ks[0]]:.3f} -> {res.losses[ks[-1]]:.3f}")

    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model") if len(shape) == 2 else ("pod", "data", "model")
        mesh = M.make_mesh(shape, axes)
        with S.use_mesh(mesh):
            run()
    else:
        run()


if __name__ == "__main__":
    main()
