"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --mode pipeline --steps 200 --seq-len 128 --batch 8 --d-model 256

Runs on whatever devices exist (CPU smoke: pass --debug-mesh to force a 2x2
fake-device mesh via XLA_FLAGS before starting python, or use --mesh 1,1).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpoint import save
from repro.configs.base import get_config
from repro.data.pipeline import batches_for
from repro.dist import api as A
from repro.launch.cache import enable_compile_cache
from repro.optim.adamw import adamw_init


def make_mesh(spec: str):
    """Mesh from "data,model" or "pod,data,model".  Axes are Auto: the
    runners place their layouts through shardings, not sharding-in-types."""
    dims = [int(x) for x in spec.split(",")]
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return jax.make_mesh(tuple(dims), names,
                         axis_types=(AxisType.Auto,) * len(dims))


def shardings(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", default="fsdp",
                    choices=["fsdp", "semantic", "pipeline"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="1,1")
    ap.add_argument("--schedule", default="gspmd",
                    choices=["gspmd", "gpipe", "1f1b"],
                    help="pipeline mode: gspmd (compiler-placed stage scan) "
                         "or the explicit shard_map+ppermute stage graph")
    ap.add_argument("--n-microbatches", type=int, default=0,
                    help="pipeline microbatch count (0: mesh 'model' size)")
    ap.add_argument("--memory-budget", type=int, default=0,
                    help="gpipe: cap on saved in-flight microbatches "
                         "(0: unbounded)")
    ap.add_argument("--expert-parallel", action="store_true",
                    help="MoE: shard experts over 'model' (with an explicit "
                         "--schedule the all-to-all path runs end-to-end)")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant of the arch")
    ap.add_argument("--d-model", type=int, default=0,
                    help="override d_model (with --reduced)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        if args.d_model:
            cfg = cfg.replace(d_model=args.d_model)
    enable_compile_cache()
    return train(cfg, make_mesh(args.mesh), mode=args.mode, steps=args.steps,
                 seq_len=args.seq_len, batch=args.batch, lr=args.lr,
                 schedule=args.schedule,
                 n_microbatches=args.n_microbatches,
                 memory_budget=args.memory_budget,
                 expert_parallel=args.expert_parallel, ckpt=args.ckpt,
                 log_every=args.log_every)


def train(cfg, mesh, *, mode: str, steps: int, seq_len: int, batch: int,
          lr: float, schedule: str = "gspmd", n_microbatches: int = 0,
          memory_budget: int = 0, expert_parallel: bool = False,
          ckpt: str = "", log_every: int = 10):
    """Train ``cfg`` (in float32) on ``mesh`` for ``steps`` steps of the
    seeded synthetic stream; returns the per-step losses.  Parameters and
    data depend only on the seed, so two runs that differ only in ``mode``
    or ``schedule`` see the same step-0 parameters and batch."""
    cfg = cfg.replace(dtype="float32")
    runner = A.build_runner(
        cfg, mode, mesh,
        n_microbatches=n_microbatches or None,
        schedule=schedule if mode == "pipeline" else "gspmd",
        memory_budget=memory_budget or None,
        expert_parallel=expert_parallel)
    rcfg = runner.cfg
    if mode == "pipeline":
        print("schedule:", runner.schedule_stats(batch, seq_len), flush=True)
    key = jax.random.PRNGKey(0)
    p_specs = runner.param_specs(jax.eval_shape(runner.init, key))
    p_shard = shardings(mesh, p_specs)
    o_shard = shardings(mesh, A.make_opt_specs(p_specs))
    # initialize straight into the shardings: an unsharded init holds the
    # whole model and optimizer state on the first device
    params = jax.jit(runner.init, out_shardings=p_shard)(key)
    opt = jax.jit(adamw_init, out_shardings=o_shard)(params)

    step_fn = A.make_train_step(runner, lr=lr, remat=True)
    jstep = jax.jit(step_fn, in_shardings=(p_shard, o_shard, None),
                    out_shardings=(p_shard, o_shard, None), donate_argnums=(0, 1))

    data = batches_for(rcfg, seq_len=seq_len, global_batch=batch)
    losses = []
    t0 = time.time()
    for step in range(steps):
        b = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt, loss = jstep(params, opt, b)
        losses.append(float(loss))
        if step % log_every == 0 or step == steps - 1:
            dt = time.time() - t0
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"({dt / (step + 1):.2f}s/step)", flush=True)
    if ckpt:
        save(f"{ckpt}/step_{steps}.npz", params, step=steps)
        print(f"checkpoint -> {ckpt}/step_{steps}.npz")
    print(f"first-10 mean {np.mean(losses[:10]):.4f} -> "
          f"last-10 mean {np.mean(losses[-10:]):.4f}")
    return losses


if __name__ == "__main__":
    main()
