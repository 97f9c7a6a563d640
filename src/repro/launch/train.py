"""Training launcher: HAD distillation (or CE pretrain) on the host mesh.

Runs REAL training on the devices present (CPU container: 1 device; on a
TPU slice the same code path shards over the full mesh via the production
sharding rules). The dry-run (dryrun.py) is the no-hardware counterpart
for the 16x16 / 2x16x16 production meshes.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --steps 100 --batch 4 --seq 64 --ckpt-dir /tmp/ck
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --reduced \
      --mode pretrain --steps 50
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.core.distill import DistillConfig, tiny_schedule
from repro.data import lm_stream, shard_batches
from repro.distributed import sharding as SH
from repro.distributed.compression import CompressionConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.optim import adam
from repro.train import (LoopConfig, StepConfig, build_distill_step,
                         build_pretrain_step, init_distill_state,
                         init_pretrain_state, run)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale reduced config")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "distill", "pretrain"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--steps-per-stage", type=int, default=25)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compression", default="none",
                    choices=["none", "onebit", "int8"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    mode = args.mode
    if mode == "auto":
        mode = ("distill" if cfg.had.enabled and cfg.has_attention
                else "pretrain")
    print(f"arch={cfg.name} mode={mode} params~{M.param_count(cfg):,}")

    opt_cfg = adam.AdamWConfig()
    step_cfg = StepConfig(
        grad_accum=args.grad_accum,
        compression=CompressionConfig(method=args.compression))
    key = jax.random.PRNGKey(args.seed)
    if mode == "distill":
        dcfg = DistillConfig(schedule=tiny_schedule(args.steps_per_stage))
        state = init_distill_state(key, cfg, opt_cfg, step_cfg)
        step_fn = jax.jit(build_distill_step(cfg, dcfg, opt_cfg, step_cfg))
        max_steps = min(args.steps, dcfg.total_steps)
    else:
        state = init_pretrain_state(key, cfg, opt_cfg, step_cfg)
        step_fn = jax.jit(build_pretrain_step(cfg, opt_cfg, lambda s: 3e-4,
                                              step_cfg))
        max_steps = args.steps

    data = shard_batches(
        lm_stream(vocab=cfg.vocab_size, batch=args.batch, seq=args.seq,
                  seed=args.seed))
    res = run(step_fn, state, data,
              LoopConfig(max_steps=max_steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir, log_every=10,
                         log_path=args.log))
    last = res.metrics_history[-1] if res.metrics_history else {}
    print(f"done: step={max_steps} metrics={ {k: round(v, 4) for k, v in last.items()} } "
          f"stragglers={res.straggler_events} resumed_from={res.resumed_from}")


if __name__ == "__main__":
    main()
