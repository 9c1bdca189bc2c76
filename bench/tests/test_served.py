"""A whole run at tiny sizes on the CPU, past the harness's look for a
chip: the served path against the plain reference (float32 here, so the
served tokens are the reference's best), the float8 control failing the
comparison, and a run whose decode alters a token failing ``correct``."""
import numpy as np
import pytest

import correct
import measure
import run
from tiny import tiny_cell

CELLS = ["stablelm-1.6b.chat-shared", "starcoder2-15b.code-batch"]
SEED = 2**31 + 77
# float32 served and float32 reference differ by summation order only
TINY_LIMITS = {"gap_layer": 1e-3, "gap_semantic": 1e-3}


def _serve(name, seconds=3.0):
    cell = tiny_cell(name)
    rec, setup_s, _ = run.serve_window(cell, SEED, seconds)
    sample = correct.draw_sample(rec, cell.traffic["sample_per_arm"], SEED)
    return cell, rec, sample


@pytest.fixture(scope="module", params=CELLS)
def served(request):
    return _serve(request.param)


def test_window_served_requests(served):
    cell, rec, sample = served
    win = measure.window_requests(rec)
    assert win and all(measure.served(r) for r in win)
    assert rec.compiles_in_window == 0
    e2e = measure.end_to_end(rec)
    assert e2e["tpot_p90_ms"] > 0 and e2e["output_tokens_per_s"] > 0
    assert all(sample.values())


def test_served_tokens_match_reference(served):
    cell, rec, sample = served
    gaps = correct.served_gaps(cell, SEED, sample)
    chk = correct.checks(rec, sample, gaps, TINY_LIMITS)
    assert correct.is_correct(chk, sample), chk
    for g in gaps.values():
        assert g["agree"] == 1.0 and g["tokens"] > 0


def test_float8_control_fails(served):
    cell, rec, sample = served
    gaps = correct.served_gaps(cell, SEED, sample, control=True)
    chk = correct.checks(rec, sample, gaps, TINY_LIMITS)
    assert not correct.is_correct(chk, sample), chk


def test_altered_token_fails(monkeypatch):
    """Decode serves a token other than the one it computed."""
    import jax.numpy as jnp
    import repro.decode.scheduler as S
    make = S.make_decode_fn

    def broken(model, **kw):
        fn = make(model, **kw)

        def decode(*args):
            pool, tok, lengths, remaining, toks = fn(*args)
            toks = toks.at[:, -1].set((toks[:, -1] + 1) % 256)
            return pool, tok, lengths, remaining, toks
        return decode

    monkeypatch.setattr(S, "make_decode_fn", broken)
    cell, rec, sample = _serve(CELLS[0], seconds=2.0)
    gaps = correct.served_gaps(cell, SEED, sample)
    chk = correct.checks(rec, sample, gaps, TINY_LIMITS)
    assert not correct.is_correct(chk, sample), chk


def test_trace_stops_after_the_drain(monkeypatch, tmp_path):
    """Stopping the profiler stalls the host.  A trace as long as the window
    runs through the drain, so the stall comes after every measured request
    has finished: the window keeps its length and no queue wait holds it."""
    import time

    import jax
    stop = jax.profiler.stop_trace
    stall_s = 2.0

    def stalling_stop():
        stop()
        time.sleep(stall_s)

    monkeypatch.setattr(jax.profiler, "stop_trace", stalling_stop)
    cell = tiny_cell(CELLS[0])
    seconds = 3.0
    cell.traffic["trace_s"] = seconds
    rec, _, _ = run.serve_window(cell, SEED, seconds,
                                 trace_dir=str(tmp_path))
    win = measure.window_requests(rec)
    assert win and all(measure.served(r) for r in win)
    assert rec.t1 - rec.t0 < seconds + stall_s / 2
    assert rec.t0 <= rec.trace_t0 < rec.t1 <= rec.drain_end <= rec.trace_t1
    assert all(r["finish"] <= rec.drain_end for r in win)
