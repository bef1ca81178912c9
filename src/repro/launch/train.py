"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch vusa_edge --steps 100 \
        [--smoke] [--batch 8] [--seq 128] [--ckpt DIR] [--data N --model M]

On a real fleet this binary runs once per host (jax.distributed initializes
from the cluster env); here it sizes the mesh to the local devices.
"""

import argparse

from ..configs import get_config, get_smoke_config
from ..train import TrainConfig, Trainer, TrainHParams
from .mesh import make_local_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--data", type=int, default=1, help="data-parallel mesh axis")
    ap.add_argument("--model", type=int, default=1, help="model-parallel mesh axis")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress", action="store_true")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh(args.data, args.model)
    tc = TrainConfig(
        steps=args.steps,
        global_batch=args.batch,
        seq_len=args.seq,
        ckpt_dir=args.ckpt,
        token_range=256,
        hp=TrainHParams(
            lr=args.lr,
            warmup=max(args.steps // 10, 1),
            total_steps=args.steps,
            microbatches=args.microbatches,
            grad_compress=args.grad_compress,
        ),
    )
    out = Trainer(cfg, tc, mesh=mesh).train()
    print(f"final loss {out['final_loss']:.4f}  sparsity {out['sparsity']:.2%}")


if __name__ == "__main__":
    main()
