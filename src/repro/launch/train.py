"""Training launcher.

CPU-scale (default): reduced config, workers as an array axis —
    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --algo moniqua --workers 8 --bits 8 --steps 50

One worker per device present (e.g. a four-chip host; --workers is the
device count) —
    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
        --mesh workers --full-size --seq 1024 --batch 16

Production mesh (requires a real fleet or forced host devices) —
    PYTHONPATH=src python -m repro.launch.train --arch internlm2-20b \
        --mesh production --shape train_4k --full-size
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--algo", default="moniqua",
                    help="allreduce|dpsgd|naive|moniqua|choco|deepsqueeze|"
                         "dcd|ecd|d2|moniqua_d2")
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--theta", type=float, default=2.0)
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--mesh", choices=["cpu", "workers", "production"],
                    default="cpu")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--shape", default=None,
                    help="assigned input shape name (production mesh)")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full published config (default: reduced)")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.configs.base import InputShape, get_input_shape
    from repro.launch import compile_cache
    from repro.models.model_factory import build_model
    from repro.models.sharding import ShardingRules
    from repro.train.trainer import Trainer, TrainerConfig

    compile_cache.enable()

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    model = build_model(cfg)

    mesh = rules = None
    if args.mesh == "workers":
        from repro.launch.mesh import make_worker_mesh
        mesh = make_worker_mesh()
        rules = ShardingRules("decentralized")
        args.workers = mesh.devices.size
        shape = InputShape("cli", args.seq, args.batch, "train")
    elif args.mesh == "production":
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        rules = ShardingRules(cfg.dist_mode, multi_pod=args.multi_pod)
        shape = get_input_shape(args.shape or "train_4k")
    else:
        shape = InputShape("cli", args.seq, args.batch, "train")

    tc = TrainerConfig(algo=args.algo, topology=args.topology,
                       n_workers=args.workers, bits=args.bits,
                       theta=args.theta, gamma=args.gamma, lr=args.lr,
                       steps=args.steps, log_every=args.log_every,
                       seed=args.seed, checkpoint_path=args.checkpoint,
                       checkpoint_every=0 if not args.checkpoint else 50)
    trainer = Trainer(model, shape, tc, mesh=mesh, rules=rules)

    def log(k, m):
        print(f"step {k:5d}  loss {m['loss']:.4f}  alpha {m['alpha']:.4g}  "
              f"theta {m['theta']:.3g}  g_inf {m['g_inf']:.3g}")

    out = trainer.run(callback=log)
    print(f"bytes/step/worker = {out['bytes_per_step']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
