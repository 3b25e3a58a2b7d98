"""Training launcher.

CPU-scale entry point exercising the full production path (config ->
mesh -> sharded train step -> checkpointed loop).  On a real TPU pod
the same driver runs with ``--mesh pod|multipod`` after
``jax.distributed.initialize()``; on CPU it defaults to a 1x1 mesh.

  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
      --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import SyntheticLMData
from repro.launch.compile_cache import use_compile_cache
from repro.dist.sharding import ShardingPolicy
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models.transformer import TransformerLM
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=("none", "full", "dots"))
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=("auto", "pod", "multipod"),
                    default="auto")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.mesh == "auto":
        n = len(jax.devices())
        mesh = make_mesh((1, n), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))
    policy = ShardingPolicy.for_mesh(mesh)

    model = TransformerLM(cfg, remat=args.remat)
    data = SyntheticLMData(cfg.vocab_size, args.batch, args.seq,
                           seed=args.seed)
    trainer = Trainer(
        model, AdamWConfig(lr=args.lr, total_steps=max(args.steps, 10)),
        mesh, policy, data, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, microbatch=args.microbatch,
        seed=args.seed)
    trainer.install_preemption_handler()
    report = trainer.run(args.steps)
    print(f"arch={cfg.name} steps={report.steps_run} "
          f"resumed_from={report.resumed_from} "
          f"loss[0]={report.losses[0]:.4f} loss[-1]={report.losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
