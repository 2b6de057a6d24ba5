"""Production training launcher.

On a real TPU cluster this is the per-host entry point (``jax.distributed``
initializes from the TPU environment; the mesh spans all chips).  On CPU it
runs the same code path over however many devices exist — used by the
multi-device integration tests via the host-platform flag.

Usage:
  python -m repro.launch.train --arch granite-3-2b --steps 100 \
      [--mesh 16x16] [--smoke] [--sparse-ffn]
"""
import argparse
import dataclasses
import logging
import os

import jax
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 16x16 (data x model); default: single device")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--sparse-ffn", action="store_true")
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO)
    # multi-host: initialize the distributed runtime when launched by a
    # cluster scheduler (JAX_COORDINATOR_ADDRESS set per host)
    if os.environ.get("JAX_COORDINATOR_ADDRESS"):
        jax.distributed.initialize()

    from repro.configs import get_config, get_smoke
    from repro.configs.base import SparsityConfig
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.sharding import Partitioner
    from repro.train import TrainConfig, Trainer
    from repro.train.optimizer import OptimizerConfig

    use_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.sparse_ffn:
        cfg = dataclasses.replace(cfg, sparsity=SparsityConfig(
            enabled=True, density=0.25, group_size=128, impl="ref"))

    mesh = part = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model")[: len(shape)] if len(shape) == 2 \
            else ("pod", "data", "model")
        mesh = make_mesh(shape, axes)
        part = Partitioner(mesh, "train")
        cfg = dataclasses.replace(
            cfg, act_shard=True,
            mesh_batch_axes=("pod", "data") if len(shape) == 3 else ("data",))

    seq = args.seq or (32 if args.smoke else 4096)
    batch = args.batch or (8 if args.smoke else 256)
    tc = TrainConfig(steps=args.steps, microbatches=args.micro,
                     ckpt_dir=args.ckpt_dir,
                     opt=OptimizerConfig(name=args.optimizer,
                                         warmup_steps=max(args.steps // 20, 5),
                                         decay_steps=args.steps))
    trainer = Trainer(cfg, tc, mesh=mesh, partitioner=part)
    state = trainer.init_state(seq_len=seq, global_batch=batch)
    if mesh is not None:
        with mesh:
            state, step = trainer.run(state)
    else:
        state, step = trainer.run(state)
    print(f"done: {step} steps, final loss "
          f"{trainer.history[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
