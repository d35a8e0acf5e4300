"""Every seed gets the same work; the seed draws the token ids."""
from benchlib import traffic
import tiny

BIG = 2**31 + 2**30 + 12345


def test_same_seed_same_inputs():
    a = traffic.make(tiny.OPEN, BIG, 512, 2.0)
    b = traffic.make(tiny.OPEN, BIG, 512, 2.0)
    assert [(i.prompt.tolist(), i.max_new, i.due) for i in a] == \
        [(i.prompt.tolist(), i.max_new, i.due) for i in b]


def test_seeds_share_sizes_and_arrivals():
    a = traffic.make(tiny.OPEN, 3, 512, 2.0)
    b = traffic.make(tiny.OPEN, BIG, 512, 2.0)
    assert len(a) == len(b) and len(a) % tiny.OPEN["block"] == 0
    assert [(len(i.prompt), i.max_new, i.due) for i in a] == \
        [(len(i.prompt), i.max_new, i.due) for i in b]
    assert [i.prompt.tolist() for i in a] != [i.prompt.tolist() for i in b]


def test_closed_batch_sizes_within_limits():
    items = traffic.make(tiny.CLOSED, BIG, 512, 2.0)
    assert len(items) == tiny.CLOSED["requests"]
    p, o = tiny.CLOSED["prompt"], tiny.CLOSED["output"]
    assert all(p["min"] <= len(i.prompt) <= p["max"] for i in items)
    assert all(o["min"] <= i.max_new <= o["max"] for i in items)
    assert all(i.due is None for i in items)
    assert all(0 <= t < 512 for i in items for t in i.prompt)

