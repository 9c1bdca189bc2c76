"""The trace reduction against a trace recorded on the chip (a TPU v5 lite
serving the chat cell): busy time is the union of program executions, op
self times add up inside their programs, kernels are found by program, and
the numbers match those written down when the trace was recorded."""
import gzip
import json
import shutil

import pytest

import trace_reduce as T
from cells import BENCH

DATA = BENCH / "testdata"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    gz = DATA / "chat-shared.xplane.pb.gz"
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(gz, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    expected = json.loads((DATA / "chat-shared.expected.json").read_text())
    return str(path), expected


def test_matches_recorded_numbers(trace):
    path, exp = trace
    r = T.reduce(path, exp["window_s"])
    assert r["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    for k, v in exp["programs"].items():
        assert r["programs"][k] == pytest.approx(v, rel=1e-9)
    for k, v in exp["kernels"].items():
        assert r["kernels"][k] == pytest.approx(v, rel=1e-9)
    assert sorted(r["dispatches"]) == sorted(exp["dispatches"])


def test_invariants(trace):
    path, exp = trace
    r = T.reduce(path, exp["window_s"])
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] <= sum(r["programs"].values()) + 1e-9
    # op self times never exceed the time their programs ran
    for prog, secs in r["programs"].items():
        own = sum(v for k, v in r["ops"].items() if k.startswith(prog + "/"))
        assert own <= secs * (1 + 1e-6)
        assert r["kernels"].get(prog, 0.0) <= secs
    # every idle stretch within the host annotations' span is attributed
    ev = T.load_events(path)
    mods = sorted(ev["modules"]["/device:TPU:0"])
    merged = T._union([(s, e) for s, e, _ in mods])
    lo = min([s for s, _, _ in ev["host"]] + [merged[0][0]])
    hi = max([e for _, e, _ in ev["host"]] + [merged[-1][1]])
    busy = sum(e - s for s, e in merged)
    assert sum(r["gaps_by_cause"].values()) == pytest.approx(
        (hi - lo - busy) / 1e9)
    assert len(r["breakdown"]["device_ops"]) <= 10


def test_union_and_self_time():
    assert T._union([(0, 5), (3, 8), (10, 12), (11, 11)]) == [(0, 8),
                                                             (10, 12)]
    st = T._self_times([(0, 10, "while"), (1, 4, "a"), (5, 9, "b"),
                        (20, 25, "c")])
    assert [(n, s) for _, _, n, s in st] == [("while", 3), ("a", 3),
                                             ("b", 4), ("c", 5)]
