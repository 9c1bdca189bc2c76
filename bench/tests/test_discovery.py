"""A new traffic mix, cell and per-layer metric are new files and entries
only: the loader finds and runs them by name, no existing file edited."""
import json
import shutil

import numpy as np

from cells import BENCH, ROOT, load_cell

NEW_GEN = '''
import numpy as np
from gen.common import Item, Schedule


def make(p, seed, vocab):
    rng = np.random.default_rng(seed)
    items = [Item(rid=i, tokens=rng.integers(0, vocab, p["len"]).astype(
        np.int32), max_new=4, arm=0, sla_s=1.0, due_s=float(i))
        for i in range(p["n"])]
    return Schedule(loop="open", items=items)
'''

NEW_METRIC = '''
def read(ctx):
    return float(len(ctx.rec.requests))
'''


def test_new_files_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "testdata"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (bench / "traffic" / "burst-test.json").write_text(json.dumps(
        {"kind": "burst_test", "loop": "open", "len": 7, "n": 3,
         "sample_per_arm": 1, "drain_s": 1}))
    (bench / "gen" / "burst_test.py").write_text(NEW_GEN)
    (bench / "metrics" / "requests_seen.py").write_text(NEW_METRIC)
    spec["workloads"].append({"name": "stablelm-1.6b.burst-test",
                              "config": "stablelm-1.6b",
                              "traffic": "burst-test", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "requests_seen", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine", "moves": "setup_s",
                              "workloads": ["stablelm-1.6b.burst-test"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = load_cell("stablelm-1.6b.burst-test", root=root, bench_dir=bench)
    sched = cell.generator().make(cell.traffic, 9, 100)
    assert [len(it.tokens) for it in sched.items] == [7, 7, 7]
    assert [m["name"] for m in cell.per_layer] == ["requests_seen"]

    class Ctx:
        class rec:
            requests = {1: {}, 2: {}}
    assert cell.reader("requests_seen").read(Ctx) == 2.0
    for p, data in before.items():
        assert p.read_bytes() == data
