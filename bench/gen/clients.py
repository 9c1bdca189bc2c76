"""Closed-loop clients, each pinned to one split arm.

Every client sends its next request as soon as its previous one completes.
Prompts are unshared (random tokens), so the prefix cache finds nothing.
Each client's sequence of request sizes comes from ``shape_seed`` alone, so
every run seed offers the same work; the run seed draws every token.
"""
from __future__ import annotations

import numpy as np

from gen.common import ARMS, Item, Schedule, lognormal_ints, seed_rng


def make(p: dict, seed: int, vocab: int) -> Schedule:
    shape_rng = np.random.default_rng(p["shape_seed"])
    tok = seed_rng(seed, 2)
    per = p["requests_per_client"]
    clients = {}
    cid = 0
    rid = 0
    for arm_name in sorted(p["clients"]):
        n_cli = p["clients"][arm_name]
        n = n_cli * per
        plens = lognormal_ints(shape_rng, p["prompt_len"], n)
        outs = lognormal_ints(shape_rng, p["output_len"], n)
        outs = np.minimum(outs, p["total_max"] - plens)
        for c in range(n_cli):
            reqs = []
            for k in range(c * per, (c + 1) * per):
                reqs.append(Item(
                    rid=rid, client=cid,
                    tokens=tok.integers(0, vocab, int(plens[k])).astype(
                        np.int32),
                    max_new=int(outs[k]), arm=ARMS[arm_name],
                    sla_s=float(p["sla_s"])))
                rid += 1
            clients[cid] = reqs
            cid += 1
    return Schedule(loop="closed", clients=clients)
