"""End-to-end metrics from the harness's own samples, and the per-token
reconstruction that the trace readers integrate over a traced window.

Times are on the backend clock.  A request is *due* when the open loop's
schedule (or a closed-loop client) sends it; every latency counts from
then, so a stalled server also delays the requests queued behind it.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def pct(values, q: float) -> Optional[float]:
    """The q-th percentile, by linear interpolation between order
    statistics (numpy's default)."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def delta(rec, key: str) -> float:
    return float(rec.edge1.get(key, 0) - rec.edge0.get(key, 0))


def window_requests(rec) -> List[dict]:
    return [r for r in rec.requests.values() if rec.in_window(r)]


def served(r: dict) -> bool:
    return "finish" in r and not r["failed"] and r.get("first") is not None


def ttft_s(rec, r: dict) -> float:
    """Due -> first token; a request that failed, was shed, or had no first
    token by the drain limit counts at that limit."""
    if served(r):
        return r["first"] - r["due"]
    return rec.drain_end - r["due"]


def end_to_end(rec) -> Dict[str, float]:
    """Every end-to-end metric the harness can compute from one window;
    the cell reports those BENCHMARK.json lists for it."""
    win = window_requests(rec)
    seconds = rec.t1 - rec.t0
    out = {}
    if win:
        out["ttft_p90_ms"] = 1e3 * pct([ttft_s(rec, r) for r in win], 90)
        met = [served(r) and r["finish"] - r["due"] <= r["sla"]
               for r in win]
        out["sla_met_share"] = float(np.mean(met))
    # over the requests due in the window, like TTFT: which requests finish
    # just inside the window's end turns on timing noise, and with a dozen
    # requests that alone moved the 90th percentile by 80%
    tpots = [(r["finish"] - r["first"]) / (len(r["out"]) - 1)
             for r in win if served(r) and len(r["out"]) >= 2]
    if tpots:
        out["tpot_p90_ms"] = 1e3 * pct(tpots, 90)
    firsts = sum(1 for r in rec.requests.values()
                 if served(r) and rec.t0 <= r["first"] < rec.t1)
    out["output_tokens_per_s"] = (delta(rec, "decoded_tokens") + firsts) \
        / seconds
    return out


# ------------------------------------------------------------ token events
def prefill_chunks(rec, r: dict, chunk: int):
    """(time, start, n_tok) of the request's prefill chunks: its uncached
    prompt tail (cached count from the scheduler's ``seat`` event) cut in
    ``chunk``-token pieces, spread evenly between seating and its first
    token."""
    if not served(r):
        return []
    plen = len(r["prompt"])
    t_seat, cached = rec.seats.get(r["rid"], (r["join"], 0))
    cached = min(int(cached), plen)
    starts = list(range(cached, plen, chunk))
    m = len(starts)
    span = max(r["first"] - t_seat, 0.0)
    return [(t_seat + span * (j + 1) / m, s, min(chunk, plen - s))
            for j, s in enumerate(starts)]


def decode_tokens(r: dict):
    """(time, context) of each decoded token after the first: token j
    attends over prompt + j positions, spread evenly between the first
    token and the finish."""
    if not served(r):
        return []
    n = len(r["out"])
    plen = len(r["prompt"])
    if n < 2:
        return []
    span = r["finish"] - r["first"]
    return [(r["first"] + span * j / (n - 1), plen + j) for j in range(1, n)]
