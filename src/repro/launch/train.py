"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b --smoke \
        --steps 200 --batch 8 --seq 128

Builds the mesh (or single-device), shards params/optimizer via the
logical-axis rules, seals ONE train-step executable ahead of time (the
Nimble discipline: the loop only submits), and streams the synthetic data
pipeline through it with periodic checkpointing.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.data import Prefetcher, SyntheticLM, data_config_for
from repro.checkpoint import save_checkpoint
from repro.distributed.sharding import tree_shardings, use_sharding_ctx
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import init_model
from repro.models.transformer import abstract_model
from repro.optim import adamw_init, cosine_schedule
from repro.training.train_lib import make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt", default="", help="checkpoint dir (optional)")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--dtype", default="")
    args = ap.parse_args()
    use_compile_cache()

    cfg = C.get(args.arch, smoke=args.smoke)
    if args.dtype:
        cfg = dataclasses.replace(cfg, dtype=args.dtype)

    mesh = make_host_mesh(model_axis=args.model_axis) if len(jax.devices()) > 1 else None

    params, axes = init_model(jax.random.key(0), cfg)
    opt_state = adamw_init(params)
    lr = lambda step: cosine_schedule(
        step, peak_lr=args.lr, warmup_steps=args.warmup, total_steps=args.steps
    )
    step_fn = make_train_step(cfg, lr=lr)

    dcfg = data_config_for(cfg, batch_size=args.batch, seq_len=args.seq)
    data = Prefetcher(SyntheticLM(dcfg))

    # --- AoT scheduling: seal the step (lower+compile once) ----------------
    in_shardings = None
    if mesh is not None:
        p_sds, p_axes = abstract_model(cfg)
        p_shard = tree_shardings(p_sds, p_axes, mesh)
        params = jax.device_put(params, p_shard)
        opt_state = jax.device_put(opt_state, adamw_init_shardings(p_shard))

    t0 = time.perf_counter()
    example = next(data)
    with use_sharding_ctx(mesh):
        sealed = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
            params, opt_state, example
        ).compile()
    print(f"sealed train step in {time.perf_counter() - t0:.1f}s "
          f"({cfg.name}: {cfg.param_count/1e6:.1f}M params)")

    losses = []
    t_start = time.perf_counter()
    for step in range(args.steps):
        batch = example if step == 0 else next(data)
        params, opt_state, metrics = sealed(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.perf_counter() - t_start
            tok_s = (step + 1) * args.batch * args.seq / dt
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"ce {float(metrics['ce']):.4f} gnorm {float(metrics['grad_norm']):.3f} "
                  f"tok/s {tok_s:,.0f}")
        if args.ckpt and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt, {"params": params}, step=step + 1)
    data.close()

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"loss: first10={first:.4f} last10={last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    if args.ckpt:
        save_checkpoint(args.ckpt, {"params": params}, step=args.steps)
        print(f"checkpoint -> {args.ckpt}")


def adamw_init_shardings(p_shard):
    from repro.optim.adamw import AdamWState
    from jax.sharding import NamedSharding, PartitionSpec as P

    anyshard = jax.tree_util.tree_leaves(p_shard)[0]
    return AdamWState(
        step=NamedSharding(anyshard.mesh, P()),
        mu=p_shard,
        nu=p_shard,
    )


if __name__ == "__main__":
    main()
