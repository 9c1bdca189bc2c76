"""Build the served stack for a cell, warm it up, and drive its window.

The stack is the one ``repro.launch.serve.build_engine`` builds:
``PlacementEngine`` -> ``JaxBackend`` (colocated, paged, both split arms
resident) -> ``PagedArmScheduler`` -> ``paged_model`` -> the Pallas paged
kernels.  Requests carry their split decision, so the engine's policy never
chooses an arm: parent and change do the same work.

All times are on the backend's clock (``engine.backend.now``), the clock
the program stamps its outcomes with.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

ARCH_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab_size", "norm_type", "norm_eps", "mlp_type",
             "rope_theta", "dtype")


def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file, every size as
    the file states it."""
    from repro.configs.base import get_config
    return get_config(cfg["arch"]).replace(**{k: cfg[k] for k in ARCH_KEYS})


def build(cfg: dict, seed: int):
    """The served engine, its weights drawn by the program from ``seed``."""
    from repro.launch.serve import build_engine
    from repro.launch.train import make_mesh
    sv = cfg["serving"]
    eng = build_engine(arch_config(cfg), make_mesh("1,1"),
                       cache_len=sv["cache_len"],
                       max_batch=sv["lanes_per_arm"], seed=seed)
    branches = eng.backend.runners[1].cfg.n_branches
    if branches != sv["semantic_branches"]:
        raise ValueError(f"the SEMANTIC arm has {branches} branches, the "
                         f"configuration states {sv['semantic_branches']}")
    return eng


def _pow2_upto(n: int) -> List[int]:
    out, p = [], 1
    while p <= n:
        out.append(p)
        p *= 2
    return out


def warm_up(eng, cfg: dict) -> int:
    """Run every program shape this cell's traffic can reach through the
    engine's own submit/step: each prefill wave width x chunk length, each
    decode lane width x scan length, and each copy-on-write batch size, on
    both arms.  Returns the number of warm-up requests."""
    from repro.engine import Request
    be = eng.backend
    vocab = cfg["vocab_size"]
    lanes = cfg["serving"]["lanes_per_arm"]
    rng = np.random.default_rng(7)
    scans = _pow2_upto(be.scan_tokens)
    n = 0

    def serve(reqs):
        nonlocal n
        for r in reqs:
            r.rid = -1 - n
            n += 1
        eng.submit(reqs)
        eng.drain()

    def req(arm, tokens, max_new):
        return Request(rid=0, app_id=0, tokens=np.asarray(tokens, np.int32),
                       sla_s=1e4, max_new=max_new, decision=arm)

    for arm in sorted(be.runners):
        i = 0
        for w in _pow2_upto(lanes):
            for c in _pow2_upto(be.prefill_chunk):
                k = scans[i % len(scans)]
                i += 1
                serve([req(arm, rng.integers(0, vocab, c), k + 1)
                       for _ in range(w)])
        # copy-on-write: a prompt whose first divergent block partly
        # matches a cached one; unshared traffic reaches it too, when a
        # prompt's first tokens happen to equal a cached block's
        if be.prefix_sharing:
            bs = be.block_size
            base = rng.integers(0, vocab, 2 * bs + bs // 2)
            serve([req(arm, base, 2)])
            for w in _pow2_upto(lanes):
                serve([req(arm, np.concatenate(
                    [base[:bs + bs // 2], rng.integers(0, vocab, bs)]), 2)
                    for _ in range(w)])
    return n


class CompileCounter:
    """Counts JAX trace and backend-compile events (persistent-cache hits
    still trace) and their seconds."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event in self.EVENTS:
            self.n += 1
            self.seconds += duration


@dataclass
class Record:
    """What one window produced, on the backend clock."""
    t0: float = 0.0
    t1: float = 0.0
    opened_perf: float = 0.0     # time.perf_counter() when the window opened
    drain_end: float = 0.0
    edge0: dict = field(default_factory=dict)    # engine.summary() at t0
    edge1: dict = field(default_factory=dict)    # ... at t1
    requests: Dict[int, dict] = field(default_factory=dict)
    compiles_in_window: int = 0
    late_s_max: float = 0.0      # how late the generator submitted
    trace_t0: Optional[float] = None
    trace_t1: Optional[float] = None
    trace_edge0: dict = field(default_factory=dict)
    trace_edge1: dict = field(default_factory=dict)
    seats: Dict[int, tuple] = field(default_factory=dict)  # rid -> (t, cached)
    prefill_chunk: int = 0       # the scheduler's chunk length

    def in_window(self, r: dict) -> bool:
        return self.t0 <= r["due"] < self.t1


def _note(rec: Record, o) -> None:
    r = rec.requests.get(o.request.rid)
    if r is None:
        return
    req = o.request
    enq = o.finish_s - o.latency_s
    r["failed"] = bool(o.failed or o.shed)
    r["finish"] = o.finish_s
    if not r["failed"]:
        r["join"] = enq + o.queue_wait_s
        r["first"] = enq + req.ttft_s if req.ttft_s > 0 else None
        r["out"] = np.asarray(req.output, np.int32)


class Driver:
    """Drives one cell's window: open loop (arrivals on a schedule) or
    closed loop (each client sends its next request on completion)."""

    def __init__(self, eng, schedule, *, seconds: float, drain_s: float,
                 preroll_s: float = 0.0, trace: Optional[dict] = None):
        self.eng = eng
        self.be = eng.backend
        self.sch = schedule
        self.seconds = seconds
        self.drain_s = drain_s
        self.preroll_s = preroll_s    # traffic before the window opens
        # {"dir", "start", "length"} or None; a length of None traces to
        # the drain's end, so that stopping the profiler, which stalls the
        # host for seconds, delays no request the run measures
        self.trace = trace
        self.rec = Record(prefill_chunk=self.be.prefill_chunk)

    # -- tracing hooks -----------------------------------------------------
    def _annot(self, name: str):
        if self.trace is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _maybe_trace(self, now: float) -> None:
        if self.trace is None:
            return
        import jax
        rec = self.rec
        if rec.trace_t0 is None and now >= rec.t0 + self.trace["start"]:
            rec.trace_edge0 = self.eng.summary()
            # host annotations and device activity; no Python call tracer
            # (its per-call events would slow the host it measures)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace["dir"],
                                     profiler_options=opts)
            rec.trace_t0 = self.be.now
        elif (rec.trace_t0 is not None and rec.trace_t1 is None
              and self.trace["length"] is not None
              and now >= rec.trace_t0 + self.trace["length"]):
            rec.trace_t1 = self.be.now
            jax.profiler.stop_trace()
            rec.trace_edge1 = self.eng.summary()

    def _finish_trace(self) -> None:
        if self.rec.trace_t0 is not None and self.rec.trace_t1 is None:
            import jax
            self.rec.trace_t1 = self.be.now
            jax.profiler.stop_trace()
            self.rec.trace_edge1 = self.eng.summary()

    # -- bookkeeping --------------------------------------------------------
    def _submit(self, items, due: List[float]) -> None:
        from repro.engine import Request
        reqs = []
        for it, d in zip(items, due):
            reqs.append(Request(rid=it.rid, app_id=0, tokens=it.tokens,
                                sla_s=it.sla_s, max_new=it.max_new,
                                arrival_s=d, decision=it.arm))
            self.rec.requests[it.rid] = {
                "rid": it.rid, "arm": it.arm, "due": d, "sla": it.sla_s,
                "prompt": it.tokens, "max_new": it.max_new,
                "client": it.client}
        with self._annot("bench.submit"):
            self.eng.submit(reqs)
        late = self.be.now - min(due)
        self.rec.late_s_max = max(self.rec.late_s_max, late)

    def _step(self):
        with self._annot("bench.step"):
            outs = self.eng.step()
        for o in outs:
            _note(self.rec, o)
        return outs

    def _open_window(self, counter: CompileCounter) -> None:
        rec = self.rec
        rec.t0 = self.be.now
        rec.opened_perf = time.perf_counter()
        rec.edge0 = self.eng.summary()
        self._c0 = counter.n

    def _close_window(self, counter: CompileCounter) -> None:
        rec = self.rec
        rec.t1 = self.be.now
        rec.edge1 = self.eng.summary()
        rec.compiles_in_window = counter.n - self._c0

    def _window_done(self) -> bool:
        return all("finish" in r for r in self.rec.requests.values()
                   if self.rec.in_window(r))

    # -- loops --------------------------------------------------------------
    def run(self, counter: CompileCounter) -> Record:
        if self.sch.loop == "open":
            self._run_open(counter)
        else:
            self._run_closed(counter)
        self.rec.drain_end = self.be.now
        self._finish_trace()
        return self.rec

    def _run_open(self, counter) -> None:
        items = self.sch.items
        i = 0
        origin = self.be.now          # the schedule's time zero
        opened = closed = False
        while True:
            now = self.be.now
            if not opened and now >= origin + self.preroll_s:
                self._open_window(counter)
                opened = True
            if opened and not closed and now >= self.rec.t0 + self.seconds:
                self._close_window(counter)
                closed = True
            if opened:
                self._maybe_trace(now)
            if closed and (self._window_done()
                           or now >= self.rec.t1 + self.drain_s):
                return
            due = []
            while i < len(items) and origin + items[i].due_s <= now:
                due.append(items[i])
                i += 1
            if due:
                self._submit(due, [origin + it.due_s for it in due])
            if self.be.pending():
                self._step()
            else:
                nxt = origin + items[i].due_s if i < len(items) \
                    else now + 0.01
                with self._annot("bench.wait"):
                    time.sleep(min(max(nxt - self.be.now, 0.0), 0.005))

    def _run_closed(self, counter) -> None:
        queues = {c: list(reqs) for c, reqs in self.sch.clients.items()}
        origin = self.be.now
        first = [q.pop(0) for q in queues.values() if q]
        self._submit(first, [origin] * len(first))
        opened = closed = False
        while True:
            now = self.be.now
            if not opened and now >= origin + self.preroll_s:
                self._open_window(counter)
                opened = True
            if opened and not closed and now >= self.rec.t0 + self.seconds:
                self._close_window(counter)
                closed = True
            if opened:
                self._maybe_trace(now)
            if closed and (self._window_done()
                           or now >= self.rec.t1 + self.drain_s):
                return
            if not self.be.pending():
                if not opened:      # every client has sent its last request
                    self._open_window(counter)
                if not closed:
                    self._close_window(counter)
                return
            outs = self._step()
            if closed:
                continue
            nxt = []
            for o in outs:
                c = self.rec.requests[o.request.rid]["client"]
                if queues.get(c):
                    nxt.append(queues[c].pop(0))
            if nxt:
                self._submit(nxt, [self.be.now] * len(nxt))
