"""Open-loop multi-turn chat sessions over shared system prompts.

Requests arrive as a Poisson process at ``rate_rps``.  The arrivals are
chained into sessions: a session's first turn takes the earliest free
arrival, and each later turn the earliest free arrival at least a think
time after the turn before it.  Each session opens with one of
``system_prompts`` system prompts of ``system_len`` tokens (Zipf
popularity) and sends 1-4 turns; turn j's prompt is turn j-1's prompt plus
a new user message, so a later turn finds its earlier prompt in the prefix
cache, and the earlier answer is not replayed, so the traffic does not
depend on outputs.  A session ends early once its prompt would pass
``prompt_max``.  Every session belongs to one SLA class, and the class pins
the split arm.

The schedule (arrival times, sizes, classes, which system prompt each
session opens with) comes from ``shape_seed`` alone, so every run seed
offers the same work; the run seed draws every token.
"""
from __future__ import annotations

import bisect

import numpy as np

from gen.common import ARMS, Item, Schedule, lognormal_ints, seed_rng


def _session(p: dict, rng: np.random.Generator, zipf, cls_names, shares):
    n_turns = int(rng.integers(p["turns"][0], p["turns"][1] + 1))
    users = lognormal_ints(rng, p["user_len"], n_turns)
    outs = lognormal_ints(rng, p["output_len"], n_turns)
    thinks = p["think_s"]["min"] + rng.exponential(
        p["think_s"]["mean_extra"], n_turns)
    system = int(rng.choice(p["system_prompts"], p=zipf))
    cls = cls_names[int(rng.choice(len(cls_names), p=shares))]
    turns, plen = [], p["system_len"]
    for j in range(n_turns):
        plen += int(users[j])
        if plen > p["prompt_max"]:
            break
        turns.append((int(users[j]), int(min(outs[j], p["total_max"] - plen)),
                      float(thinks[j])))
    return system, cls, turns


def make(p: dict, seed: int, vocab: int) -> Schedule:
    rng = np.random.default_rng(p["shape_seed"])
    n = int(np.ceil(p["rate_rps"] * p["horizon_s"] * 1.3)) + 16
    times = np.cumsum(rng.exponential(1.0 / p["rate_rps"], n))
    times = times[times <= p["horizon_s"]]
    free = list(range(len(times)))        # arrival indices not yet taken
    cls_names = sorted(p["classes"])
    shares = np.array([p["classes"][c]["share"] for c in cls_names], float)
    zipf = 1.0 / np.arange(1, p["system_prompts"] + 1) ** p["system_zipf_a"]
    zipf /= zipf.sum()
    plan = []       # (arrival index, system, class, user_len, max_new, turn)
    sid = 0
    while free:
        system, cls, turns = _session(p, rng, zipf, cls_names, shares)
        at = free.pop(0)
        for j, (user, max_new, think) in enumerate(turns):
            if j:
                k = bisect.bisect_left(free, int(np.searchsorted(
                    times, times[at] + think)))
                if k == len(free):
                    break
                at = free.pop(k)
            plan.append((at, sid, system, cls, user, max_new, j))
        sid += 1

    tok = seed_rng(seed, 1)
    systems = [tok.integers(0, vocab, p["system_len"]).astype(np.int32)
               for _ in range(p["system_prompts"])]
    prompts = {}
    items = []
    for at, sid, system, cls, user, max_new, j in sorted(
            plan, key=lambda x: (x[1], x[6])):
        prev = prompts.get(sid, systems[system])
        prompts[sid] = np.concatenate(
            [prev, tok.integers(0, vocab, user).astype(np.int32)])
        c = p["classes"][cls]
        items.append(Item(rid=0, tokens=prompts[sid], max_new=max_new,
                          arm=ARMS[c["arm"]], sla_s=float(c["sla_s"]),
                          due_s=float(times[at])))
    items.sort(key=lambda it: it.due_s)
    for i, it in enumerate(items):
        it.rid = i
    return Schedule(loop="open", items=items)
