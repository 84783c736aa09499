"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Runs the real fault-tolerant loop on the host devices (tests/examples).
Reduced configs (--reduced) make every arch runnable on CPU.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs.base import get_config
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_train_step
from repro.models.api import build_model
from repro.optim import adamw as OPT
from repro.train import checkpoint as CKPT
from repro.train.loop import TrainLoopConfig, run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", default="synthetic")
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, max_positions=max(4096, args.seq))
    mesh = make_host_mesh(model=args.model_parallel)

    opt_cfg = OPT.AdamWConfig(lr_peak=args.lr, warmup_steps=10,
                              total_steps=args.steps)
    step = build_train_step(model, mesh, opt_cfg, zero1=args.zero1)

    params, _ = model.init(jax.random.PRNGKey(args.seed))
    opt_state = OPT.init_state(params)
    # place on mesh
    pshard, oshard = step.in_shardings
    params = jax.device_put(params, pshard)
    opt_state = jax.device_put(opt_state, oshard)

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch, seed=args.seed,
                          source=args.data, path=args.data_path)
    loop_cfg = TrainLoopConfig(total_steps=args.steps,
                               ckpt_every=args.ckpt_every,
                               ckpt_dir=args.ckpt_dir)
    out = run(step, params, opt_state, data_cfg, loop_cfg,
              shardings={"params": pshard, "opt": oshard})
    print(f"final loss: {out['losses'][-1]:.4f} "
          f"(first: {out['losses'][0]:.4f}, "
          f"stragglers: {out['stragglers']})")
    return out


if __name__ == "__main__":
    main()
