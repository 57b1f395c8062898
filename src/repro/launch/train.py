"""End-to-end training driver.

Runs any assigned architecture (reduced or full geometry) with either engine on
the available devices, with checkpoint/restart fault tolerance.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 50 --engine mapreduce --reduce-mode hierarchical
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_arch, reduced as make_reduced
from ..data.pipeline import Prefetcher, token_batches
from ..models.params import specs_tree
from ..models.registry import build_model, init_params
from ..models.steps import make_train_step
from ..optim import OptConfig, init_opt_state, opt_state_defs
from ..runtime import LoopConfig, TrainLoop
from .mesh import make_host_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0, help="override n_layers")
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--engine", default="pjit", choices=["pjit", "mapreduce"])
    ap.add_argument("--reduce-mode", default="allreduce")
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--data-parallel", type=int, default=0,
                    help="#devices for the data axis (default: all)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--opt", default="adamw")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    over = {}
    if args.layers:
        over["n_layers"] = args.layers
    if args.d_model:
        over["d_model"] = args.d_model
    over["remat"] = "none"
    cfg = dataclasses.replace(cfg, **over)

    ndev = len(jax.devices())
    dp = args.data_parallel or ndev
    need_mesh = ndev > 1 or args.engine == "mapreduce"
    mesh = make_host_mesh(data=dp, model=ndev // dp) if need_mesh else None

    opt_cfg = OptConfig(name=args.opt, lr=args.lr, schedule="linear_warmup_cosine",
                        warmup=max(1, args.steps // 10), total_steps=args.steps)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    opt_state = init_opt_state(params, opt_cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, engine={args.engine}, "
          f"devices={ndev}, batch={args.global_batch}x{args.seq_len}")

    step_fn = make_train_step(cfg, mesh, opt_cfg, engine=args.engine,
                              reduce_mode=args.reduce_mode, n_micro=args.n_micro)
    jitted = jax.jit(step_fn)

    def loop_step(state, batch):
        params, opt_state = state
        b = {"tokens": jnp.asarray(batch["tokens"])}
        params, opt_state, metrics = jitted(params, opt_state, b)
        return (params, opt_state), metrics

    data = token_batches(cfg.vocab, args.global_batch, args.seq_len,
                         seed=args.seed)
    loop = TrainLoop(loop_step, (params, opt_state), data,
                     LoopConfig(ckpt_dir=args.ckpt_dir,
                                ckpt_every=args.ckpt_every, log_every=5))
    out = loop.run(args.steps)
    print(f"[train] done: final loss {out['final_loss']:.4f} "
          f"after {out['steps']} steps")
    return out


if __name__ == "__main__":
    from .compile_cache import use_compile_cache
    use_compile_cache()
    main()
