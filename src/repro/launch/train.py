"""Training driver: ``python -m repro.launch.train --arch qwen2-0.5b ...``

Wires config -> model -> synthetic data (prefetched) -> jitted train step ->
async checkpointing + supervisor.  ``--smoke`` uses the reduced config so the
loop runs on CPU; the full configs target the production mesh.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import ARCH_IDS, get_config, get_smoke_config
from ..models import Model
from ..training import (AdamWConfig, CheckpointManager, Prefetcher,
                        SyntheticDataset, adamw_init, make_train_step)
from ..training.train_step import settings_for
from ..distributed.fault_tolerance import TrainSupervisor
from .compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    settings = settings_for(args.arch)
    if args.batch % settings.accum_steps != 0:
        import dataclasses
        import math
        settings = dataclasses.replace(
            settings, accum_steps=math.gcd(args.batch,
                                           settings.accum_steps))
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=20,
                          decay_steps=max(args.steps, 100),
                          state_dtype=settings.opt_state_dtype)
    step_fn = jax.jit(make_train_step(model, opt_cfg, settings),
                      donate_argnums=(0, 1))

    data = Prefetcher(SyntheticDataset(cfg, args.batch, args.seq), depth=2)
    mgr = CheckpointManager(args.ckpt_dir)
    sup = TrainSupervisor(mgr, save_every=args.save_every)

    def init_state():
        params = model.init(jax.random.PRNGKey(0))
        return {"params": params, "opt": adamw_init(params, opt_cfg)}

    abstract = jax.eval_shape(init_state)
    start_step, state = sup.startup(init_state, abstract)
    print(f"arch={cfg.name} params={model.count_params() / 1e6:.1f}M "
          f"start_step={start_step}", flush=True)

    params, opt = state["params"], state["opt"]
    tokens_per_step = args.batch * args.seq
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = next(data)
        params, opt, metrics = step_fn(params, opt, batch)
        if (step + 1) % args.log_every == 0:
            dt = time.time() - t0
            tput = tokens_per_step * args.log_every / dt
            print(f"step {step + 1:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics.get('grad_norm', 0)):.3f} "
                  f"tok/s={tput:,.0f}", flush=True)
            t0 = time.time()
        sup.maybe_save(step + 1, {"params": params, "opt": opt})
    sup.finalize(args.steps, {"params": params, "opt": opt})
    data.close()
    mgr.close()
    print("done", flush=True)


if __name__ == "__main__":
    main()
