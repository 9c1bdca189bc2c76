"""The served program's spans as a profiler trace holds them: every host
phase of a step lies under a program span and nests as the step runs it,
the benchmark's trace reduction reads a trace with the spans as it reads one
without, the Pallas kernels keep their names on the device, and the prefill
counters cover the same lanes from each seating."""
import gzip
import heapq
import json
import re
import shutil
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import trace_reduce as T  # noqa: E402

SPAN_PREFIX = "repro."
LABEL = re.compile(r"^(decode|prefill|cow|ship):")
HOST_PHASES = {"prefill_prep", "prefill_readback", "decode_prep",
               "decode_readback", "retire"}


def _harness_would_read(name: str) -> bool:
    """The names ``trace_reduce.load_events`` keeps from the host plane."""
    return bool(name.startswith("bench.") or T.DISPATCH_MARK.match(name)
                or T.COMPILE_MARK.search(name))


def _inside(outer, inner) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


# --------------------------------------------------------- recorded trace
@pytest.fixture(scope="module")
def spans_trace(tmp_path_factory):
    """A chip trace of code-batch with the program's spans mirrored, and
    the host plane's harness and program events as (start, end, name,
    stats)."""
    path = tmp_path_factory.mktemp("spans") / "t.xplane.pb"
    with gzip.open(BENCH / "testdata" / "code-batch.spans.xplane.pb.gz",
                   "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    expected = json.loads(
        (BENCH / "testdata" / "code-batch.spans.expected.json").read_text())
    host = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != T.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(("bench.", SPAN_PREFIX)) or \
                        LABEL.match(name):
                    s = int(ev.start_ns)
                    host.append((s, s + int(ev.duration_ns), name,
                                 {k: v for k, v in ev.stats}))
    return str(path), expected, host


def _named(host, *names):
    return [h for h in host if h[2] in names]


def test_recorded_trace_holds_every_host_phase(spans_trace):
    path, exp, host = spans_trace
    names = {h[2] for h in host}
    assert {SPAN_PREFIX + n for n in HOST_PHASES | {"engine_step", "step",
                                                    "join_wave"}} <= names
    benches = _named(host, "bench.step")
    steps = _named(host, "repro.engine_step")
    assert steps and all(any(_inside(b, s) for b in benches) for s in steps)
    # the dispatch labels the reduction sees are the program's labels
    labels = [h[2] for h in sorted(host) if T.DISPATCH_MARK.match(h[2])]
    assert labels == exp["dispatches"]


def test_recorded_phase_spans_wrap_no_dispatch(spans_trace):
    """The host-phase spans nest inside a step and hold no dispatch label;
    the prefill readback lies inside its ``prefill:`` label, as the
    dispatch labels cover what they covered before the phases existed."""
    _, _, host = spans_trace
    labels = [h for h in host if LABEL.match(h[2])]
    steps = _named(host, "repro.engine_step")
    for h in _named(host, *(SPAN_PREFIX + n for n in HOST_PHASES)):
        assert any(_inside(s, h) for s in steps), h[2]
        if h[2] == "repro.prefill_readback":
            assert any(_inside(lb, h) for lb in labels
                       if lb[2].startswith("prefill:"))
        else:
            assert not any(lb[0] < h[1] and h[0] < lb[1] for lb in labels), \
                h[2]


def test_recorded_labels_carry_scalar_stats(spans_trace):
    """A dispatch label's stats are its span's scalar attributes; the
    request ids stay in the Tracer."""
    _, _, host = spans_trace
    for s, e, name, stats in host:
        if name.startswith("prefill:"):
            w, c = map(int, name.split(":")[1].split("x"))
            assert stats["chunk"] == c and 0 < stats["wave"] <= w
        elif name.startswith("decode:"):
            w, k = map(int, name.split(":")[1].split("x"))
            assert stats["scan"] == k and 0 < stats["lanes"] <= w
        else:
            continue
        assert "rids" not in stats


def test_recorded_trace_names_the_pallas_kernels(spans_trace):
    """``name=`` on the paged ``pallas_call``s names their ops on the
    device, and the reduction still finds each kernel by its program."""
    path, exp, _ = spans_trace
    ops = set()
    for plane in ProfileData.from_file(path).planes:
        if T.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == T.OPS_LINE:
                    ops |= {T.op_name(ev.name) for ev in line.events}
    for kernel in ("paged_decode_attention", "paged_prefill_attention"):
        assert any(re.match(rf"^{kernel}\.\d+\[tpu_custom_call\]$", op)
                   for op in ops), kernel
    r = T.reduce(path, exp["window_s"])
    assert r["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    assert r["kernels"].keys() == exp["kernels"].keys()
    for k, v in exp["kernels"].items():
        assert r["kernels"][k] == pytest.approx(v, rel=1e-9)
    for k, v in exp["gaps_by_cause"].items():
        assert r["gaps_by_cause"][k] == pytest.approx(v, rel=1e-9)


class _Hidden:
    """``ProfileData`` with the program's ``repro.*`` events taken out of
    the host plane, as a program that mirrors no span of its own writes."""

    def __init__(self, obj, hide=False):
        self._obj, self._hide = obj, hide

    @classmethod
    def from_file(cls, path):
        return cls(ProfileData.from_file(path))

    @property
    def name(self):
        return self._obj.name

    @property
    def planes(self):
        return [_Hidden(p, p.name == T.HOST_PLANE) for p in self._obj.planes]

    @property
    def lines(self):
        return [_Hidden(ln, self._hide) for ln in self._obj.lines]

    @property
    def events(self):
        return [e for e in self._obj.events
                if not (self._hide and e.name.startswith(SPAN_PREFIX))]


def test_reduce_is_unmoved_by_program_spans(spans_trace, monkeypatch):
    """Every key of ``trace_reduce.reduce`` reads the same with and without
    the program's ``repro.*`` events in the trace."""
    import jax.profiler
    path, exp, _ = spans_trace
    with_spans = json.dumps(T.reduce(path, exp["window_s"]), sort_keys=True)
    monkeypatch.setattr(jax.profiler, "ProfileData", _Hidden)
    hidden = [ev.name for p in _Hidden.from_file(path).planes
              for ln in p.lines for ev in ln.events]
    assert hidden and not any(n.startswith(SPAN_PREFIX) for n in hidden)
    assert json.dumps(T.reduce(path, exp["window_s"]),
                      sort_keys=True) == with_spans


# ------------------------------------------------------ the served program
@pytest.fixture(scope="module")
def tiny_run():
    """A tiny served run on the CPU with a Tracer installed."""
    import cells
    import drive
    from repro.engine import Request
    from repro.obs import Tracer, set_tracer

    tiny = cells.load_module(BENCH / "tests" / "tiny.py", "bench_tiny")
    cell = tiny.tiny_cell("stablelm-1.6b.chat-shared")
    eng = drive.build(cell.config, seed=3)
    rng = np.random.default_rng(0)
    tr = Tracer()
    old = set_tracer(tr)
    try:
        eng.submit([Request(rid=i, app_id=0, sla_s=1e4, max_new=5,
                            decision=i % 2,
                            tokens=rng.integers(0, 256, 40 + 7 * i)
                            .astype(np.int32)) for i in range(6)])
        eng.drain()
    finally:
        set_tracer(old)
    return eng, tr


def test_program_names_stay_out_of_the_harness_filter(tiny_run):
    """A mirrored span, ``repro.<name>``, is never an event the harness
    reads; only the dispatch labels are."""
    _, tr = tiny_run
    served = {e[1] for e in tr.events() if e[0] == "X"}
    assert served >= HOST_PHASES | {"engine_step"}
    for name in served | {"admit", "decide", "ship_wave", "transfer"}:
        assert not _harness_would_read(SPAN_PREFIX + name), name
    for label in ["decode:8x8", "prefill:4x32", "cow:2"]:
        assert _harness_would_read(label)


def test_every_dispatching_step_is_covered_by_host_phase_spans(tiny_run):
    eng, tr = tiny_run
    assert eng.summary()["completed"] == 6
    spans = [e for e in tr.events() if e[0] == "X"]
    steps = [e for e in spans if e[1] == "engine_step"]
    assert steps and {e[1] for e in spans} >= HOST_PHASES | {"engine_step"}

    def inside(step, name):
        t0, t1 = step[3], step[3] + step[4]
        return [e for e in spans
                if e[1] == name and t0 <= e[3] and e[3] + e[4] <= t1]
    dispatching = 0
    for st in steps:
        if inside(st, "prefill_chunk"):
            assert inside(st, "prefill_prep") and \
                inside(st, "prefill_readback")
        if inside(st, "decode_scan"):
            assert inside(st, "decode_prep") and \
                inside(st, "decode_readback") and inside(st, "retire")
        dispatching += bool(inside(st, "prefill_chunk")
                            or inside(st, "decode_scan"))
    assert dispatching > 0
    # the dispatch spans carry their lanes' request ids (Tracer only)
    for name in ("prefill_chunk", "decode_scan"):
        rids = [r for e in tr.events(name) for r in e[5]["rids"]]
        assert set(rids) <= set(range(6)) and rids


def test_prefill_counters_cover_the_same_lanes(tiny_run):
    eng, _ = tiny_run
    s = eng.summary()
    assert s["prefill_busy_lane_s"] > 0
    assert s["prefill_wait_lane_s"] >= s["prefill_busy_lane_s"]


def test_prefill_counters_sum_across_arms(tiny_run):
    eng, _ = tiny_run
    arms = [s.stats() for s in eng.backend._all_scheds()]
    assert len(arms) == 2
    s = eng.summary()
    for key in ("prefill_wait_lane_s", "prefill_busy_lane_s"):
        assert all(a[key] > 0 for a in arms), key
        assert s[key] == pytest.approx(sum(a[key] for a in arms)), key


class _Clock:
    """A host clock that moves one second at each read, and further when
    the test says the host was busy elsewhere."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t - 1.0


def test_prefill_counters_count_from_each_seating(tiny_cfg, tiny_mesh):
    """A lane's wait runs from its seating to its first token and its busy
    time sums its chunk waves; a lane spilled and resumed counts again
    from its re-seat, not from its first seating."""
    from repro.decode.scheduler import PagedArmScheduler
    from repro.engine import Request
    from repro.models.model import build_model

    model = build_model(tiny_cfg)
    params = model.init(jax.random.PRNGKey(1))
    prompt = np.random.default_rng(4).integers(
        0, tiny_cfg.vocab_size, 8).astype(np.int32)
    clock = _Clock()
    sched = PagedArmScheduler(model, params, n_lanes=1, cache_len=32,
                              block_size=4, scan_tokens=4, prefill_chunk=4,
                              clock=clock)
    q = [(9.0, 0, 0.0, Request(rid=0, app_id=0, tokens=prompt, sla_s=9.0,
                               max_new=6, arrival_s=0.0))]
    heapq.heapify(q)
    sched.try_join(q, 0.0)            # seated at 0
    sched.prefill_step(0.0)           # wave [1, 2]
    clock.t += 10.0                   # the host works elsewhere
    sched.prefill_step(0.0)           # wave [13, 14], first token at 14
    assert sched.prefill_wait_lane_s == 14.0
    assert sched.prefill_busy_lane_s == 2.0

    assert sched.spill_all(0.0) == 1
    clock.t += 100.0                  # queued for resume
    seat = clock.t
    chunks = sched.prefill_chunks
    sched.try_join([], 0.0)           # re-seated at ``seat``
    while sched.prefill_left[0] > 0 or sched.prefill_chunks == chunks:
        sched.prefill_step(0.0)
    waves = sched.prefill_chunks - chunks
    assert clock.t == seat + 1 + 2 * waves
    assert sched.prefill_wait_lane_s == 14.0 + 2 * waves
    assert sched.prefill_busy_lane_s == 2.0 + waves
