"""The ``repro.obs`` tracing + metrics subsystem.

Covers the Tracer in isolation (span nesting, track -> pid/tid mapping,
Chrome trace-event schema round-trip), the disabled-path overhead guard
(the NullTracer singletons must not allocate per call), the log-bucket
Histogram (bounded-relative-error percentiles, exact merge — hypothesis
property), the kind-declared MetricRegistry, EngineStats percentile
fields, and the end-to-end lifecycle traces both backends emit: the
SimBackend's per-tick phases and the disaggregated JaxBackend fleet's
admit -> prefill -> ship -> decode -> retire ordering on distinct
prefill/decode tracks.
"""
import json
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.obs import (COUNTER, GAUGE, NULL_SPAN, NULL_TRACER, Histogram,
                       MetricRegistry, Tracer, get_tracer, merge_stat_dicts,
                       set_tracer, trace_to)


# ---------------------------------------------------------------- the tracer
def test_span_nesting_and_track_inheritance():
    """Instants and child spans emitted inside an open span inherit its
    track; sibling spans nest LIFO and each records its own duration."""
    clk = iter(range(100))
    tr = Tracer(clock=lambda: next(clk))
    with tr.span("outer", track=("armX", "prefill"), wave=2) as sp:
        tr.instant("seat", req=7)
        with tr.span("inner"):
            pass
        sp.set(admitted=2)
    tr.instant("observe")               # stack empty -> engine track
    (seat,) = tr.events("seat")
    assert seat[2] == ("armX", "prefill")          # inherited
    (inner,) = tr.events("inner")
    assert inner[2] == ("armX", "prefill")
    (outer,) = tr.events("outer")
    assert outer[0] == "X" and outer[4] > inner[4]  # outer strictly longer
    assert outer[5] == {"wave": 2, "admitted": 2}
    (obs,) = tr.events("observe")
    assert obs[2] == ("engine", "lifecycle")


def test_chrome_trace_schema_roundtrip(tmp_path):
    """Exported JSON is valid trace-event format: every event carries
    ph/ts/pid/tid, X events a dur, instants a scope, and each distinct
    (process, thread) label pair gets exactly one M-metadata naming."""
    clk = iter(np.arange(0.0, 10.0, 0.5))
    tr = Tracer(clock=lambda: next(clk))
    with tr.span("prefill_chunk", track=("arm0", "prefill"), chunk=8):
        tr.instant("first_token", req=1)
    with tr.span("decode_scan", track=("arm0", "decode"), lanes=np.int64(4)):
        pass
    path = tmp_path / "trace.json"
    tr.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert evs and set(e["ph"] for e in evs) == {"M", "X", "i"}
    for e in evs:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] != "M":
            assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] > 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    procs = [e for e in evs if e["ph"] == "M"
             and e["name"] == "process_name"]
    threads = [e for e in evs if e["ph"] == "M"
               and e["name"] == "thread_name"]
    assert {p["args"]["name"] for p in procs} == {"arm0"}
    assert {t["args"]["name"] for t in threads} == {"prefill", "decode"}
    # numpy attrs became plain JSON numbers
    (scan,) = [e for e in evs if e["name"] == "decode_scan"]
    assert scan["args"]["lanes"] == 4
    # the instant landed on its enclosing span's (pid, tid)
    (chunk,) = [e for e in evs if e["name"] == "prefill_chunk"]
    (ft,) = [e for e in evs if e["name"] == "first_token"]
    assert (ft["pid"], ft["tid"]) == (chunk["pid"], chunk["tid"])


def test_trace_to_installs_exports_and_restores(tmp_path):
    path = tmp_path / "t.json"
    assert get_tracer() is NULL_TRACER
    with trace_to(str(path)) as tr:
        assert get_tracer() is tr
        with tr.span("work"):
            pass
    assert get_tracer() is NULL_TRACER
    assert any(e["name"] == "work"
               for e in json.loads(path.read_text())["traceEvents"])


def test_streaming_tracer_writes_incrementally(tmp_path):
    """stream_path mode: events land in the file as they are recorded (flat
    memory — the in-process buffer stays empty), the finalized document is
    byte-for-byte valid trace-event JSON, and close() is idempotent."""
    path = tmp_path / "stream.json"
    clk = iter(np.arange(0.0, 10.0, 0.5))
    tr = Tracer(clock=lambda: next(clk), stream_path=str(path))
    with tr.span("prefill_chunk", track=("arm0", "prefill"), chunk=8):
        tr.instant("first_token", req=1)
    # events went to disk, not the buffer; n_events still counts them
    assert tr.events() == []
    assert tr.n_events == 2
    # mid-stream the file already holds the recorded events (valid after
    # appending the closing bracket — the incremental-write contract)
    doc = json.loads(path.read_text() + "]}")
    assert {e["ph"] for e in doc["traceEvents"]} >= {"i", "X", "M"}
    assert tr.export_chrome_trace("ignored") == str(path)
    assert tr.close() == str(path)            # idempotent
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} == {"M", "X", "i"}
    (chunk,) = [e for e in evs if e["name"] == "prefill_chunk"]
    (ft,) = [e for e in evs if e["name"] == "first_token"]
    assert (ft["pid"], ft["tid"]) == (chunk["pid"], chunk["tid"])


def test_trace_to_streaming(tmp_path):
    path = tmp_path / "t.json"
    with trace_to(str(path), stream=True) as tr:
        with tr.span("work"):
            pass
        assert tr.stream_path == str(path)
        assert tr.events() == []
    doc = json.loads(path.read_text())
    assert any(e["name"] == "work" for e in doc["traceEvents"])
    assert doc["displayTimeUnit"] == "ms"


def test_null_tracer_has_no_per_call_allocations():
    """The disabled hot path: span()/instant() return shared singletons
    and allocate nothing, so per-dispatch instrumentation is free when
    tracing is off."""
    tr = NULL_TRACER
    assert tr.span("a") is tr.span("b") is NULL_SPAN
    assert tr.instant("x", req=1) is None
    with tr.span("a", anything=1) as sp:
        assert sp.set(more=2) is NULL_SPAN
    # allocation guard: 10k traced-region entries on the disabled path must
    # not grow the heap (kwargs dicts are transient; no event tuples ever
    # materialize).  A generous slack of 50 blocks absorbs interpreter
    # noise while catching any O(n) leak.
    for _ in range(100):                      # warm caches outside the count
        with tr.span("warm", k=1):
            tr.instant("w")
    base = sys.getallocatedblocks()
    for i in range(10_000):
        with tr.span("hot", step=i):
            tr.instant("tick", req=i)
    assert sys.getallocatedblocks() - base < 50
    with pytest.raises(RuntimeError, match="disabled"):
        tr.export_chrome_trace("/tmp/never.json")


def _profile(tmp_path, body):
    """Run ``body`` under a CPU profiler capture; the host plane's events
    as {name: stats}."""
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    out[ev.name] = {k: v for k, v in ev.stats}
    return out


def test_spans_mirror_into_the_profiler(tmp_path):
    """With annotations on, a Tracer span also lands on the profiler's host
    plane: under its label (or ``repro.<name>``), its scalar attributes and
    those set mid-span as stats, its lists in the Tracer only."""
    from repro.obs import set_annotations
    tr = Tracer()

    def body():
        with tr.span("decode_scan", label="decode:4x8", lanes=3, scan=8,
                     rids=[5, 6]) as sp:
            with tr.span("decode_readback", frac=0.5, arm="layer"):
                pass
            sp.set(retired=1)

    set_annotations(True)
    try:
        events = _profile(tmp_path, body)
    finally:
        set_annotations(False)
    assert events["decode:4x8"] == {"lanes": 3, "scan": 8, "retired": 1}
    assert events["repro.decode_readback"] == {"frac": 0.5, "arm": "layer"}
    assert "decode_scan" not in events and "repro.decode_scan" not in events
    (scan,) = tr.events("decode_scan")
    assert scan[5] == {"lanes": 3, "scan": 8, "rids": [5, 6], "retired": 1}
    assert tr.events("decode_readback")


def test_null_tracer_mirrors_only_with_annotations_on(tmp_path):
    """The NullTracer keeps the dispatch labels of a profiled run with no
    Tracer installed, and stays the shared no-op with annotations off."""
    from repro.obs import set_annotations
    tr = NULL_TRACER

    def body():
        with tr.span("cow_copy", label="cow:4", pairs=3):
            pass
        with tr.span("join_wave", free=2) as sp:
            sp.set(admitted=1)

    set_annotations(True)
    try:
        assert tr.span("x") is not NULL_SPAN
        events = _profile(tmp_path, body)
    finally:
        set_annotations(False)
    assert events["cow:4"] == {"pairs": 3}
    assert events["repro.join_wave"] == {"free": 2, "admitted": 1}
    assert tr.span("x", label="cow:4") is NULL_SPAN


# ------------------------------------------------------------- the histogram
def test_histogram_percentile_bounded_relative_error():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-2.0, sigma=2.0, size=5000)
    h = Histogram()
    for v in vals:
        h.observe(v)
    tol = np.sqrt(h.growth)
    for q in (1, 25, 50, 75, 95, 99):
        exact = float(np.percentile(vals, q, method="inverted_cdf"))
        approx = h.percentile(q)
        assert exact / tol <= approx <= exact * tol
    assert h.n == len(vals)
    assert h.mean == pytest.approx(float(np.mean(vals)))
    # summary carries the flat fields; empty histograms stay silent
    s = h.summary("lat")
    assert set(s) == {"lat_p50", "lat_p95", "lat_p99", "lat_mean",
                      "lat_count"}
    assert Histogram().summary("lat") == {}


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000), na=st.integers(0, 200),
       nb=st.integers(0, 200))
def test_histogram_merge_is_exact(seed, na, nb):
    """merge(A, B) is indistinguishable from observing A + B directly —
    distributed collection (per-arm, per-worker) loses nothing."""
    rng = np.random.default_rng(seed)
    a = rng.lognormal(sigma=3.0, size=na)
    b = rng.lognormal(sigma=3.0, size=nb)
    ha, hb, hab = Histogram(), Histogram(), Histogram()
    for v in a:
        ha.observe(v)
        hab.observe(v)
    for v in b:
        hb.observe(v)
        hab.observe(v)
    ha.merge(hb)
    assert ha.counts == hab.counts and ha.n == hab.n
    assert ha.vmin == hab.vmin and ha.vmax == hab.vmax
    for q in (50, 95, 99):
        assert ha.percentile(q) == hab.percentile(q)


def test_histogram_layout_mismatch_and_nan():
    h = Histogram()
    h.observe(float("nan"))
    assert h.n == 0
    with pytest.raises(ValueError, match="layouts differ"):
        h.merge(Histogram(growth=2.0))


# -------------------------------------------------------------- the registry
def test_registry_kinds_aggregate_correctly():
    """Counters sum across sources, gauges take the max, ratios recompute
    from the MERGED counters (token-weighted, not a mean of ratios)."""
    kinds = {"hit_rate": ("ratio", "hits", "queries"), "pool_bytes": GAUGE}
    srcs = [
        {"hits": 90, "queries": 100, "hit_rate": 0.9, "pool_bytes": 64},
        {"hits": 0, "queries": 900, "hit_rate": 0.0, "pool_bytes": 128},
    ]
    m = merge_stat_dicts(srcs, kinds)
    assert m["hits"] == 90 and m["queries"] == 1000
    assert m["hit_rate"] == 0.09          # NOT (0.9 + 0.0) / 2
    assert m["pool_bytes"] == 128         # max, never 192
    # zero denominator reads 0.0, not a crash
    assert merge_stat_dicts([{"hit_rate": 0.0}], kinds)["hit_rate"] == 0.0


def test_registry_redeclaration_raises_and_histograms_expand():
    reg = MetricRegistry()
    reg.counter("x", 1)
    with pytest.raises(ValueError, match="redeclared"):
        reg.gauge("x", 2.0)
    reg.observe("lat", 0.5)
    reg.observe("lat", 1.5)
    out = reg.as_dict()
    assert out["lat_count"] == 2 and "lat_p99" in out and "x" in out
    assert COUNTER in reg.kinds().values()


# -------------------------------------------------- EngineStats percentiles
def test_engine_stats_percentiles():
    from repro.engine.types import EngineStats, Outcome, Request
    st_ = EngineStats()
    for i in range(50):
        req = Request(rid=i, app_id=0, sla_s=10.0, max_new=5,
                      ttft_s=0.1 * (i + 1))
        req.output = np.zeros(5, np.int32)
        st_.record(Outcome(request=req, decision=0,
                           latency_s=0.1 * (i + 1) + 0.4, queue_wait_s=0.01,
                           accuracy=0.9, finish_s=1.0))
    s = st_.summary()
    for prefix in ("response", "queue_wait", "ttft", "tpot"):
        assert s[f"{prefix}_p50"] <= s[f"{prefix}_p95"] <= s[f"{prefix}_p99"]
    # tpot = (latency - ttft) / (n_out - 1) = 0.4 / 4 for every request
    assert s["tpot_p99"] == pytest.approx(0.1, rel=0.07)


# ------------------------------------------------------ end-to-end lifecycle
def test_sim_backend_emits_tick_phases():
    from repro.engine import PlacementEngine, Request
    from repro.engine.sim_backend import SimBackend

    class Pol:
        def decide(self, r):
            return 0

        def place(self, frag, hosts):
            return 0

        def observe(self, o):
            pass

    tr = Tracer()
    old = set_tracer(tr)
    try:
        eng = PlacementEngine(Pol(), SimBackend(n_hosts=4))
        eng.submit([Request(rid=i, app_id=0, sla_s=30.0) for i in range(3)])
        eng.drain(max_steps=500)
    finally:
        set_tracer(old)
    names = {e[1] for e in tr.events()}
    assert {"admit", "decide", "place", "place_frags", "sim_tick",
            "retire", "observe"} <= names
    assert all(e[2] == ("sim", "testbed") for e in tr.events("sim_tick"))


@pytest.mark.slow
def test_disagg_fleet_trace_lifecycle(tiny_cfg, tiny_mesh, tmp_path):
    """The acceptance trace: a disagg run emits every lifecycle phase, in
    order per request (admit <= seat <= first prefill chunk <= ship <=
    admit_shipped <= decode scan <= retire), with the prefill / ship /
    decode work on distinct threads of the arm's process row."""
    from repro.engine import (LAYER, FixedPolicy, PlacementEngine, Request)
    from repro.engine.jax_backend import JaxBackend

    rng = np.random.default_rng(0)
    path = tmp_path / "disagg.json"
    with trace_to(str(path)) as tr:
        backend = JaxBackend(tiny_cfg, tiny_mesh, cache_len=16, max_batch=4,
                             block_size=4, scan_tokens=4, arms=(LAYER,),
                             fleet="disagg")
        eng = PlacementEngine(FixedPolicy(LAYER, placement=None), backend)
        eng.submit([Request(rid=i, app_id=0,
                            tokens=rng.integers(1, 100, 6).astype(np.int32),
                            sla_s=60.0, max_new=6) for i in range(5)])
        eng.drain()
    assert eng.summary()["completed"] == 5

    # per-request phase ordering over the in-process event stream.  X-event
    # timestamps are span STARTS; instants are points.  For each request:
    # its admit instant precedes its seat, the first prefill chunk AFTER the
    # seat ends before its ship instant, ship precedes admit_shipped, some
    # decode scan runs between seating and retirement, retire last.
    def at(name, rid):
        ts = [e[3] for e in tr.events(name) if e[5].get("req") == rid]
        assert ts, f"no {name!r} event for request {rid}"
        return min(ts)

    spans = {n: tr.events(n)
             for n in ("prefill_chunk", "decode_scan", "ship_wave")}
    for rid in range(5):
        admit, seat, ship = at("admit", rid), at("seat", rid), at("ship", rid)
        admitted, retire = at("admit_shipped", rid), at("retire", rid)
        assert admit <= seat <= ship <= admitted <= retire
        # a prefill chunk covering (seat, ship) and a decode scan covering
        # (admitted, retire) both exist
        assert any(seat <= e[3] and e[3] + e[4] <= ship + 1e-3
                   for e in spans["prefill_chunk"])
        assert any(admitted - 1e-3 <= e[3] <= retire
                   for e in spans["decode_scan"])
    assert spans["ship_wave"], "no ship_wave span recorded"

    # exported track layout: one process row for the arm, prefill and
    # decode on different threads, ship on its own thread
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    by_name = {}
    for e in evs:
        if e["ph"] in ("X", "i"):
            by_name.setdefault(e["name"], e)
    pf, dc = by_name["prefill_chunk"], by_name["decode_scan"]
    sh = by_name["ship_wave"]
    assert pf["pid"] == dc["pid"] == sh["pid"]      # same arm process
    assert len({pf["tid"], dc["tid"], sh["tid"]}) == 3
    threads = {e["args"]["name"] for e in evs
               if e["ph"] == "M" and e["name"] == "thread_name"
               and e["pid"] == pf["pid"]}
    assert any(t.startswith("prefill@") for t in threads)
    assert any(t.startswith("decode@") for t in threads)
