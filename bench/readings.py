#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/readings.py --workload stablelm-1.6b.chat-shared \
        --seeds 101-112 --control 101-103 --seconds 10 --out FILE

For each seed, in one process: a run of the cell as ``run.py`` makes it
(set-up, a short window at the cell's own load, the drain) and the served
tokens' widest gaps per arm against the plain reference; on the
``--control`` seeds also the gaps of the float8 reference's first choices at
the same positions (the control, which the limits must fail).  Both go
through ``correct.checks`` and ``correct.is_correct`` with the cell's
committed limits, as a benchmark run's own tokens do.  One JSON line per
seed goes to ``--out`` and to stdout.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))


def _seeds(spec: str):
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import run
    import correct
    from cells import load_cell
    cell = load_cell(args.workload)
    run.check_device(cell.chips)
    run.setup_jax()
    limits = correct.load_limits(cell)
    control = set(_seeds(args.control))
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for seed in _seeds(args.seeds):
        t = time.perf_counter()
        rec, setup_s, peak = run.serve_window(cell, seed, args.seconds,
                                              t_start=t)
        sample = correct.draw_sample(rec, cell.traffic["sample_per_arm"],
                                     seed)
        line = {"seed": seed, "setup_s": setup_s, "peak": peak}
        sides = ("program", "control") if seed in control else ("program",)
        for side in sides:
            gaps = correct.served_gaps(cell, seed, sample,
                                       control=side == "control")
            chk = correct.checks(rec, sample, gaps, limits)
            line[side] = gaps
            line[f"{side}_checks"] = chk
            line[f"{side}_correct"] = correct.is_correct(chk, sample)
        line["seconds"] = time.perf_counter() - t
        with out.open("a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
