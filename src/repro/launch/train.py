"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \
        --steps 200 --reduced --batch 8 --seq 64

Wires together: config registry -> model init (sharded) -> deterministic
data pipeline -> train_step (pjit) -> checkpoint manager (+restart) ->
heartbeat/straggler policies. On this CPU container use --reduced; on real
hardware the full config + production mesh apply unchanged.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.runtime.fault_tolerance import HeartbeatMonitor, StragglerPolicy
from repro.sharding.rules import make_plan
from repro.configs.base import ShapeSpec
from repro.train.optimizer import OptimizerConfig
from repro.train.steps import StepConfig, init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeSpec("cli", args.seq, args.batch, "train")
    mesh = make_production_mesh() if args.production_mesh \
        else make_host_mesh()
    plan = make_plan(mesh, cfg, shape)
    # minicpm trains with the WSD schedule (its paper's contribution)
    schedule = "wsd" if args.arch.startswith("minicpm") else "cosine"
    opt_cfg = OptimizerConfig(lr=args.lr, warmup_steps=max(args.steps // 20,
                                                           5),
                              total_steps=args.steps, schedule=schedule)
    step_cfg = StepConfig(microbatches=args.microbatches, remat=True,
                          compute_dtype=jnp.float32 if args.reduced
                          else jnp.bfloat16)

    data = SyntheticLMData(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch,
        frontend_seq=cfg.frontend_seq if cfg.modality != "text" else 0,
        d_model=cfg.d_model))

    with mesh:
        state = init_train_state(jax.random.PRNGKey(0), cfg, step_cfg)
        state_sh = plan.params_shardings(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         state))
        state = jax.tree.map(
            lambda x, sh: jax.device_put(x, sh), state, state_sh)
        step = jax.jit(make_train_step(cfg, opt_cfg, step_cfg,
                                       plan.shard_fn()),
                       donate_argnums=(0,))

        start_step = 0
        ckpt = None
        if args.ckpt_dir:
            ckpt = CheckpointManager(args.ckpt_dir, every=args.ckpt_every)
            state, start_step, _ = ckpt.restore_or_init(state, state_sh)
            if start_step:
                print(f"[restore] resumed from step {start_step}")

        hb = HeartbeatMonitor(n_hosts=1)
        straggler = StragglerPolicy()
        bspec = NamedSharding(mesh, plan.batch_spec())
        losses = []
        for s in range(start_step, args.steps):
            t0 = time.monotonic()
            host_batch = data.batch(s)
            batch = {k: jax.device_put(jnp.asarray(v), bspec if
                                       np.asarray(v).ndim >= 2 else None)
                     for k, v in host_batch.items()}
            state, metrics = step(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.monotonic() - t0
            hb.beat(0)
            straggler.record(0, dt)
            if s % args.log_every == 0 or s == args.steps - 1:
                print(f"step {s:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f}ms", flush=True)
            if ckpt:
                ckpt.maybe_save(s, state, {"loss": loss})
        print(f"[done] first-10 mean loss {np.mean(losses[:10]):.4f} -> "
              f"last-10 mean loss {np.mean(losses[-10:]):.4f}")
        return losses


if __name__ == "__main__":
    main()
