"""End-to-end training driver: ``python -m repro.launch.train --arch
minicpm-2b --reduced --steps 200`` trains a (reduced or full) architecture
on synthetic LM data with gradient accumulation, WSD schedule,
checkpointing and (on a real multi-chip platform) the production
sharding. On this CPU container it is exercised by examples/quickstart.py
at ~100M scale."""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import save
from repro.configs import ARCH_NAMES, get_config
from repro.data import SyntheticLM
from repro.models import init_params, param_count
from repro.sharding.hooks import activation_rules
from repro.sharding.rules import make_rules
from repro.util.compile_cache import enable_compile_cache
from repro.train import (TrainConfig, adamw_init, make_jit_train_step,
                         wsd_schedule)


def build_argparser():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="minicpm-2b", choices=ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--distributed", action="store_true",
                    help="use the production mesh + sharding rules")
    return ap


def main(argv=None):
    args = build_argparser().parse_args(argv)
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, dtype=args.dtype)

    sched = wsd_schedule(peak_lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                         stable_steps=int(args.steps * 0.7),
                         decay_steps=max(int(args.steps * 0.25), 1))
    tc = TrainConfig(accum_steps=args.accum_steps, schedule=sched)

    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    print(f"arch={cfg.name} params={param_count(params):,} "
          f"accum={args.accum_steps}")

    ctx = None
    if args.distributed:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh()
        rules = make_rules(mesh)
        ctx = activation_rules(rules.activation_table(), mesh)
        ctx.__enter__()
    # params/opt-state are donated (in-place update; the training loop
    # below re-binds both from the outputs every step)
    step = make_jit_train_step(cfg, tc)

    data = SyntheticLM(cfg, args.batch, args.seq)
    t0 = time.time()
    for i, batch in zip(range(args.steps), data):
        params, opt, m = step(params, opt, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            loss = float(m["loss"])
            print(f"step {i:5d} loss {loss:.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} "
                  f"({(time.time() - t0) / (i + 1):.3f}s/step)", flush=True)
    if ctx is not None:
        ctx.__exit__(None, None, None)
    if args.checkpoint:
        save(args.checkpoint, params=params, opt_state=opt, step=args.steps)
        print(f"saved {args.checkpoint}")
    return float(m["loss"])


if __name__ == "__main__":
    main()
