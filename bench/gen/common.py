"""What every traffic generator returns: a schedule of request items.

A generator module ``bench/gen/<kind>.py`` defines
``make(params, seed, vocab) -> Schedule``.  ``params`` is the traffic file's
JSON; ``seed`` is the run's ``--seed``.  The sizes and arrival times come
from the file's ``shape_seed``, so every run seed serves the same work; the
run seed picks the order and the token contents.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

ARMS = {"layer": 0, "semantic": 1}


@dataclass
class Item:
    """One request as the harness will submit it."""
    rid: int
    tokens: np.ndarray        # prompt, int32
    max_new: int
    arm: int                  # 0 = LAYER, 1 = SEMANTIC
    sla_s: float
    due_s: Optional[float] = None   # open loop: offset from the window start
    client: int = -1                # closed loop: the client that sends it


@dataclass
class Schedule:
    loop: str                               # "open" | "closed"
    items: List[Item] = field(default_factory=list)       # open, by due_s
    clients: Dict[int, List[Item]] = field(default_factory=dict)  # closed

    def all_items(self) -> List[Item]:
        if self.loop == "open":
            return list(self.items)
        return [it for c in sorted(self.clients) for it in self.clients[c]]


def lognormal_ints(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` integers from a lognormal with the spec's median and sigma,
    clipped to [min, max]."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def seed_rng(seed: int, salt: int) -> np.random.Generator:
    """Generator for the run seed (any non-negative integer) and a salt."""
    return np.random.default_rng([int(seed) & (2**64 - 1), salt])
