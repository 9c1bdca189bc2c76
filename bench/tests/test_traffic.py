"""Seeded traffic: one seed gives one schedule, another seed reorders the
same set of sizes and arrival times."""
import numpy as np
import pytest

from cells import load_cell

CELLS = ["stablelm-1.6b.chat-shared", "starcoder2-15b.code-batch"]


def _make(name, seed):
    cell = load_cell(name)
    return cell.generator().make(cell.traffic, seed,
                                 cell.config["vocab_size"])


def _shape(s):
    items = s.all_items()
    return sorted((len(it.tokens), it.max_new, it.arm, it.sla_s)
                  for it in items)


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_schedule(name):
    a, b = _make(name, 2**31 + 5), _make(name, 2**31 + 5)
    ia, ib = a.all_items(), b.all_items()
    assert len(ia) == len(ib)
    for x, y in zip(ia, ib):
        assert np.array_equal(x.tokens, y.tokens)
        assert (x.max_new, x.arm, x.sla_s, x.due_s, x.client) == \
            (y.max_new, y.arm, y.sla_s, y.due_s, y.client)


@pytest.mark.parametrize("name", CELLS)
def test_other_seed_same_work(name):
    """Another seed draws other tokens for the same set of sizes (and, in
    the open loop, the same arrival times)."""
    a, b = _make(name, 1), _make(name, 2)
    ia, ib = a.all_items(), b.all_items()
    assert any(not np.array_equal(x.tokens, y.tokens)
               for x, y in zip(ia, ib))
    assert _shape(a) == _shape(b)
    if a.loop == "open":
        assert [it.due_s for it in ia] == [it.due_s for it in ib]


def test_open_loop_arrivals_are_poisson_at_the_rate():
    cell = load_cell(CELLS[0])
    p = dict(cell.traffic, horizon_s=4000)
    s = cell.generator().make(p, 1, 1000)
    gaps = np.diff([0.0] + [it.due_s for it in s.items])
    assert np.mean(gaps) == pytest.approx(1 / p["rate_rps"], rel=0.08)
    assert np.std(gaps) == pytest.approx(1 / p["rate_rps"], rel=0.12)
    # later turns extend earlier prompts
    head = p["system_len"]
    by_prefix = sum(any(np.array_equal(o.tokens, it.tokens[:len(o.tokens)])
                        for o in s.items[:i] if len(o.tokens) > head)
                    for i, it in enumerate(s.items[:300]))
    assert by_prefix > 20


def test_chat_lengths_and_sharing():
    cell = load_cell(CELLS[0])
    p = cell.traffic
    s = _make(CELLS[0], 3)
    heads = {it.tokens[:p["system_len"]].tobytes() for it in s.items}
    assert len(heads) <= p["system_prompts"]
    for it in s.items:
        assert p["system_len"] + p["user_len"]["min"] <= len(it.tokens) \
            <= p["prompt_max"]
        assert len(it.tokens) + it.max_new <= p["total_max"]
        assert it.arm == (1 if it.sla_s == p["classes"]["tight"]["sla_s"]
                          else 0)
    dues = [it.due_s for it in s.items]
    assert dues == sorted(dues)


def test_code_batch_clients_pinned():
    cell = load_cell(CELLS[1])
    s = _make(CELLS[1], 4)
    assert len(s.clients) == sum(cell.traffic["clients"].values())
    for reqs in s.clients.values():
        assert len({it.arm for it in reqs}) == 1
        for it in reqs:
            assert len(it.tokens) + it.max_new <= cell.traffic["total_max"]
