"""Tiny stand-ins of the cells for CPU tests: the same files and code
paths, with every width cut so a CPU can serve it in seconds."""
from __future__ import annotations

import copy

from cells import load_cell

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "head_dim": 16,
              "d_ff": 128, "vocab_size": 256, "dtype": "float32"}
TINY_SERVING = {"lanes_per_arm": 4, "cache_len": 128}
TINY_TRAFFIC = {
    "chat-shared": {"system_len": 32, "prompt_max": 96, "total_max": 128,
                    "user_len": {"median": 12, "sigma": 0.4, "min": 8,
                                 "max": 24},
                    "output_len": {"median": 6, "sigma": 0.5, "min": 3,
                                   "max": 16},
                    "think_s": {"min": 0.2, "mean_extra": 0.5},
                    "rate_rps": 6.0, "horizon_s": 20, "drain_s": 20,
                    "preroll_s": 1.0,
                    "sample_per_arm": 6, "trace_s": 0.5},
    "code-batch": {"clients": {"layer": 3, "semantic": 3},
                   "requests_per_client": 200, "total_max": 128,
                   "prompt_len": {"median": 30, "sigma": 0.3, "min": 16,
                                  "max": 64},
                   "output_len": {"median": 8, "sigma": 0.4, "min": 4,
                                  "max": 16},
                   "drain_s": 20, "sample_per_arm": 6, "trace_s": 0.5,
                   "preroll_s": 1.0},
}


def tiny_cell(name: str, **kw):
    cell = load_cell(name, **kw)
    cfg = copy.deepcopy(cell.config)
    cfg.update(TINY_MODEL)
    cfg["n_kv_heads"] = min(cfg["n_kv_heads"], 4) if cfg["n_kv_heads"] > 4 \
        else 2
    cfg["serving"].update(TINY_SERVING)
    cell.config = cfg
    traffic = copy.deepcopy(cell.traffic)
    traffic.update(TINY_TRAFFIC[cell.traffic_name])
    cell.traffic = traffic
    return cell
