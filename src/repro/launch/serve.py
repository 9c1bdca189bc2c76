"""Serving launcher: the unified placement engine over a chosen architecture
and mesh (MAB policy + JaxBackend with EDF continuous batching).

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --batches 8                              # reduced widths, float32
    PYTHONPATH=src python -m repro.launch.serve --no-reduced \
        --dtype bfloat16 --cache-len 1024        # published widths

For pod-scale layout experiments use launch/dryrun.py (AOT, no allocation);
this driver executes real steps on the available devices.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.configs.base import get_config
from repro.engine import JaxBackend, MABPolicy, PlacementEngine, Request
from repro.launch.cache import enable_compile_cache
from repro.launch.train import make_mesh


def build_engine(cfg, mesh, *, cache_len: int, max_batch: int,
                 bandit: str = "ucb", **backend_kw) -> PlacementEngine:
    """The served stack: MAB split policy over a ``JaxBackend`` holding the
    default split arms; ``backend_kw`` passes through to ``JaxBackend``."""
    return PlacementEngine(
        MABPolicy(bandit=bandit, ema_init_values=None, n_ctx=8),
        JaxBackend(cfg, mesh, cache_len=cache_len, max_batch=max_batch,
                   **backend_kw))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--mesh", default="1,1")
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="smoke-scale widths (--no-reduced: published)")
    ap.add_argument("--dtype", choices=["float32", "bfloat16"], default=None,
                    help="parameter/cache dtype (default: the config's)")
    ap.add_argument("--bandit", default="ucb")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.dtype:
        cfg = cfg.replace(dtype=args.dtype)
    enable_compile_cache()
    eng = build_engine(cfg, make_mesh(args.mesh), cache_len=args.cache_len,
                       max_batch=args.max_batch, bandit=args.bandit)
    rng = np.random.default_rng(0)
    rid = 0
    for b in range(args.batches):
        reqs = []
        for _ in range(args.batch_size):
            tight = rng.random() < 0.5
            reqs.append(Request(
                rid=rid, app_id=int(rng.integers(3)),
                tokens=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                sla_s=float(0.05 if tight else 5.0), max_new=4))
            rid += 1
        eng.submit(reqs)
        eng.drain()
    print(json.dumps(eng.summary(), indent=2))


if __name__ == "__main__":
    main()
