"""The end-to-end arithmetic on a synthetic run record: percentiles from
due times, failures and unfinished requests counted at the drain limit,
rates over the window's edges."""
import numpy as np
import pytest

import measure
from drive import Record


def _req(rid, due, first=None, finish=None, n_out=5, sla=10.0,
         failed=False):
    r = {"rid": rid, "arm": 0, "due": due, "sla": sla,
         "prompt": np.zeros(40, np.int32), "max_new": n_out, "client": -1}
    if finish is not None:
        r.update(finish=finish, failed=failed, join=due + 0.1, first=first,
                 out=np.zeros(n_out, np.int32))
    return r


def _record():
    rec = Record(t0=100.0, t1=110.0, drain_end=130.0, prefill_chunk=32)
    rec.edge0 = {"decoded_tokens": 1000, "lane_steps": 2000,
                 "prefix_hit_tokens": 10, "prefix_query_tokens": 100}
    rec.edge1 = {"decoded_tokens": 1400, "lane_steps": 2500,
                 "prefix_hit_tokens": 60, "prefix_query_tokens": 300}
    reqs = [
        _req(0, 99.0, first=100.5, finish=101.0),          # before window
        _req(1, 100.0, first=101.0, finish=102.0),          # ttft 1.0
        _req(2, 101.0, first=103.0, finish=105.0),          # ttft 2.0
        _req(3, 102.0, first=102.5, finish=111.0, sla=5.0),  # misses sla
        _req(4, 103.0, finish=104.0, failed=True),           # failed
        _req(5, 109.0),                                      # never done
        _req(6, 110.0, first=110.5, finish=111.0),           # after window
    ]
    rec.requests = {r["rid"]: r for r in reqs}
    return rec


def test_window_membership():
    rec = _record()
    assert [r["rid"] for r in measure.window_requests(rec)] == [1, 2, 3, 4, 5]


def test_ttft_counts_failures_at_the_drain_limit():
    rec = _record()
    vals = sorted(measure.ttft_s(rec, r) for r in measure.window_requests(rec))
    assert vals == pytest.approx([0.5, 1.0, 2.0, 21.0, 27.0])
    e2e = measure.end_to_end(rec)
    assert e2e["ttft_p90_ms"] == pytest.approx(
        1e3 * np.percentile([0.5, 1.0, 2.0, 21.0, 27.0], 90))


def test_sla_share_and_tpot():
    rec = _record()
    e2e = measure.end_to_end(rec)
    # served within sla: 1 and 2; 3 missed, 4 failed, 5 never finished
    assert e2e["sla_met_share"] == pytest.approx(2 / 5)
    # due inside [t0, t1) and served with >= 2 tokens: 1, 2, 3
    tpots = [(102.0 - 101.0) / 4, (105.0 - 103.0) / 4, (111.0 - 102.5) / 4]
    assert e2e["tpot_p90_ms"] == pytest.approx(
        1e3 * np.percentile(tpots, 90))


def test_tokens_per_s_from_counters_at_the_edges():
    rec = _record()
    e2e = measure.end_to_end(rec)
    firsts = 4       # first tokens at 100.5, 101.0, 103.0, 102.5
    assert e2e["output_tokens_per_s"] == pytest.approx((400 + firsts) / 10)


def test_token_reconstruction():
    rec = _record()
    r = rec.requests[2]
    toks = measure.decode_tokens(r)
    assert [c for _, c in toks] == [41, 42, 43, 44]
    assert toks[-1][0] == pytest.approx(105.0)
    rec.seats[2] = (101.5, 16)
    chunks = measure.prefill_chunks(rec, r, 16)
    assert [(s, n) for _, s, n in chunks] == [(16, 16), (32, 8)]
    assert chunks[-1][0] == pytest.approx(103.0)


def test_readers_on_a_reduced_trace():
    """Every per-layer reader of both cells on a synthetic record and a
    reduced trace: numbers where there is something to read, None without
    a trace, and no share of a roofline or a peak above 100%."""
    import flops
    import readers
    from cells import load_cell
    rec = _record()
    rec.trace_t0, rec.trace_t1 = 100.0, 106.0
    rec.trace_edge0 = {"prefix_query_tokens": 100, "prefix_hit_tokens": 10}
    rec.trace_edge1 = {"prefix_query_tokens": 300, "prefix_hit_tokens": 60}
    trace = {"busy_s": 5.0, "window_s": 6.0, "idle_share": 1 / 6,
             "programs": {"jit_decode": 4.0, "jit_chunk": 1.0},
             "kernels": {"jit_decode": 1.5, "jit_chunk": 0.4},
             "dispatches": ["decode:2x8", "prefill:2x32", "decode:1x4"]}
    for name in ("stablelm-1.6b.chat-shared", "starcoder2-15b.code-batch"):
        cell = load_cell(name)
        peak = flops.peaks("TPU v5 lite")
        ctx = readers.Context(cell=cell, rec=rec, trace=trace, peak=peak)
        bare = readers.Context(cell=cell, rec=rec, trace=None, peak=peak)
        for m in cell.per_layer:
            read = cell.reader(m["name"]).read
            v = read(ctx)
            assert v is not None and v >= 0, m["name"]
            if m["unit"] == "%":
                assert v <= 100, (m["name"], v)
            if m["source"] == "device_trace":
                assert read(bare) is None, m["name"]
    assert readers.decode_step_ms(ctx) == pytest.approx(1e3 * 4.0 / 12)
