#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip.

    python3 bench/sweep.py --workload stablelm-1.6b.chat-shared \
        --rates 0.25,1,1.5,2,3 --seconds 40 --sla-x 4 --out FILE

One process: one engine (weights from ``--seed``), warmed up, then a window
per rate (the traffic file's mix at that rate, another seed each), each
drained before the next.  The first rate is the low load: each class's SLA
is set to ``--sla-x`` times that class's median response time there, and
every later rate runs and is judged with those SLAs; ``--sla-x 0`` keeps
the traffic file's SLAs for every rate.  The schedule reaches past the
window and its drain, however long the window.  For each rate it prints the
share of the window's requests that met their SLA, the backlog (requests
due but not finished) at each quarter of the window, and the tails.  The knee is the highest rate whose backlog does not grow and whose
SLA share is at least 0.9.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))


def backlog(rec, t: float) -> int:
    return sum(1 for r in rec.requests.values()
               if r["due"] <= t and r.get("finish", np.inf) > t)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--sla-x", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import drive
    import measure
    import run
    from cells import load_cell
    from gen.common import ARMS
    cell = load_cell(args.workload)
    run.check_device(cell.chips)
    run.setup_jax()
    counter = drive.CompileCounter()
    import jax
    jax.monitoring.register_event_duration_secs_listener(counter)
    eng = drive.build(cell.config, args.seed)
    drive.warm_up(eng, cell.config)
    gen = cell.generator()
    traffic = copy.deepcopy(cell.traffic)
    traffic["horizon_s"] = max(traffic["horizon_s"],
                               traffic.get("preroll_s", 0.0) + args.seconds
                               + traffic["drain_s"])
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    rates = [float(r) for r in args.rates.split(",")]
    for i, rate in enumerate(rates):
        traffic["rate_rps"] = rate
        sched = gen.make(traffic, args.seed + 1 + i,
                         cell.config["vocab_size"])
        d = drive.Driver(eng, sched, seconds=args.seconds,
                         drain_s=traffic["drain_s"],
                         preroll_s=traffic.get("preroll_s", 0.0))
        rec = d.run(counter)
        win = measure.window_requests(rec)
        resp = {}
        # at the low load every served request counts, pre-roll included
        pool = list(rec.requests.values()) if i == 0 else win
        for name, c in traffic["classes"].items():
            arm = ARMS[c["arm"]]
            rs = [r["finish"] - r["due"] for r in pool
                  if measure.served(r) and r["arm"] == arm]
            resp[name] = float(np.median(rs)) if rs else None
        if i == 0 and args.sla_x > 0:
            for name, c in traffic["classes"].items():
                if resp[name]:
                    c["sla_s"] = round(args.sla_x * resp[name], 3)
            # judge the low-load window by the SLAs it just set
            by_arm = {ARMS[c["arm"]]: c["sla_s"]
                      for c in traffic["classes"].values()}
            for r in win:
                r["sla"] = by_arm[r["arm"]]
        e2e = measure.end_to_end(rec)
        line = {"rate_rps": rate, "due": len(win),
                "served": sum(measure.served(r) for r in win),
                "sla_s": {k: c["sla_s"] for k, c in
                          traffic["classes"].items()},
                "median_response_s": resp,
                "backlog_quarters": [backlog(rec, rec.t0 + args.seconds
                                             * q / 4) for q in (1, 2, 3)]
                + [backlog(rec, rec.t1)],
                "drain_s": rec.drain_end - rec.t1,
                "compiles_in_window": rec.compiles_in_window,
                **{k: e2e.get(k) for k in ("ttft_p90_ms", "tpot_p90_ms",
                                           "sla_met_share",
                                           "output_tokens_per_s")},
                "at": time.strftime("%H:%M:%S")}
        with out.open("a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
