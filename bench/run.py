#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload stablelm-1.6b.chat-shared \
        --seed 1234 --seconds 51 --trace 0

Set-up (counted in ``setup_s``): check for the chips the cell asks for,
build the served stack with weights drawn from the seed, run every program
shape the cell's traffic reaches, generate the traffic from the seed, and
offer the traffic's pre-roll.  Then the window: ``--seconds`` of traffic
offered to ``PlacementEngine``, timed from the client's side, and a drain
that lets the window's requests finish.  Then, with the served model freed, the plain reference checks a sample of
the served tokens (``correct``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
with the JAX profiler (the traffic file's ``trace_s`` in the window's middle,
or, where that is as long as the window, the window and its drain) and
reports the cell's per-layer metrics, the device's busy time and a
breakdown.  The last line
of stdout is the JSON result; the numbers compared for ``correct`` are the
last lines of stderr and the result's last key.  Without a TPU, or with
fewer chips than the cell asks for, the run exits 2 before timing anything.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))

from cells import Cell, load_cell  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_device(chips: int):
    """The devices, or exit 2: the benchmark runs only on TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: no TPU (JAX's first device is {devs[0].platform!r})")
        sys.exit(2)
    if len(devs) < chips:
        log(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
        sys.exit(2)
    return devs


def setup_jax() -> str:
    import jax
    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def serve_window(cell: Cell, seed: int, seconds: float, *, trace_dir=None,
                 t_start: float = T_START):
    """Build, warm up and drive the cell; returns (record, set-up seconds,
    peak device bytes, tracer seats)."""
    import jax
    import drive
    from repro.obs import Tracer, set_annotations, set_tracer

    counter = drive.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    eng = drive.build(cell.config, seed)
    n_warm = drive.warm_up(eng, cell.config)
    sched = cell.generator().make(cell.traffic, seed,
                                  cell.config["vocab_size"])
    trace = None
    if trace_dir is not None:
        # a trace shorter than the window sits in its middle; one as long
        # as the window covers it and the drain after it
        tr = cell.traffic
        start = max(0.0, (seconds - tr["trace_s"]) / 2)
        trace = {"dir": trace_dir, "start": start,
                 "length": tr["trace_s"] if tr["trace_s"] < seconds
                 else None}
        set_annotations(True)
        be = eng.backend
        tracer_t0 = be.now
        set_tracer(Tracer(clock=lambda: be.now))
    log(f"bench: built and warmed up in {time.perf_counter() - t_start:.3f}s "
        f"({n_warm} warm-up requests, {counter.n} compile events, "
        f"{counter.seconds:.3f}s compiling)")
    driver = drive.Driver(eng, sched, seconds=seconds,
                          drain_s=cell.traffic["drain_s"],
                          preroll_s=cell.traffic.get("preroll_s", 0.0),
                          trace=trace)
    rec = driver.run(counter)
    # set-up ends when the window opens: the pre-roll of traffic that brings
    # the queues and the prefix cache to their steady state is part of it
    setup_s = rec.opened_perf - t_start
    log(f"bench: set-up {setup_s:.3f}s")
    if trace_dir is not None:
        from repro.obs import NULL_TRACER, get_tracer
        # the scheduler's seat events: when each request was seated and how
        # many of its prompt tokens the prefix cache already held
        for _, _, _, ts, _, a in get_tracer().events("seat"):
            rec.seats[a["req"]] = (tracer_t0 + ts / 1e6, a["cached"])
        set_tracer(NULL_TRACER)
        set_annotations(False)
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"bench: window {rec.t1 - rec.t0:.3f}s, "
        f"{sum(rec.in_window(r) for r in rec.requests.values())} requests "
        f"due in it, drain ended {rec.drain_end - rec.t1:.3f}s after it; "
        f"compile events in the window: {rec.compiles_in_window}; "
        f"generator late by up to {rec.late_s_max:.3f}s")
    del driver, eng, sched
    gc.collect()
    return rec, setup_s, peak


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the raw profiler trace to this directory")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    devs = check_device(cell.chips)
    import flops
    peak_table = flops.peaks(devs[0].device_kind)
    cache = setup_jax()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"bench: {cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={device} compile cache {cache}")

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        rec, setup_s, peak = serve_window(cell, args.seed, args.seconds,
                                          trace_dir=tmp)
        device["memory_peak_bytes"] = peak
        import measure
        win = measure.window_requests(rec)
        metrics = {}
        if args.trace:
            import readers
            import trace_reduce
            path = trace_reduce.find_xplane(tmp)
            if args.keep_trace:
                keep = Path(args.keep_trace)
                keep.mkdir(parents=True, exist_ok=True)
                shutil.copy(path, keep / Path(path).name)
                (keep / "describe.txt").write_text(
                    trace_reduce.describe(path))
            reduced = trace_reduce.reduce(path, rec.trace_t1 - rec.trace_t0)
            ctx = readers.Context(cell=cell, rec=rec, trace=reduced,
                                  peak=peak_table)
            for m in cell.per_layer:
                v = cell.reader(m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        else:
            e2e = dict(measure.end_to_end(rec), setup_s=setup_s)
            metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in cell.end_to_end if m["name"] in e2e}
        for name, m in metrics.items():
            log(f"bench: {name} = {m['value']} {m['unit']}")

        import correct
        t_ref = time.perf_counter()
        sample = correct.draw_sample(rec, cell.traffic["sample_per_arm"],
                                     args.seed)
        gaps = correct.served_gaps(cell, args.seed, sample)
        chk = correct.checks(rec, sample, gaps, correct.load_limits(cell))
        ok = correct.is_correct(chk, sample)
        log(f"bench: reference check {time.perf_counter() - t_ref:.3f}s "
            f"over {sum(g['tokens'] for g in gaps.values())} served tokens; "
            + "; ".join(f"{a}: agree {g['agree']:.4f}"
                        for a, g in gaps.items()))
        out = {"correct": ok, "attempted": len(win),
               "failed": sum(1 for r in win if not measure.served(r)),
               "metrics": metrics, "device": device}
        if args.trace:
            out["breakdown"] = reduced["breakdown"]
        out["checks"] = chk
        for name, c in chk.items():
            log(f"check {name}: {c['value']} (limit {c['limit']})")
        print(json.dumps(out), flush=True)
        return 0
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
