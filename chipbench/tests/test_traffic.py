import json
import os

import pytest

from chipbench import traffic as tg

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
BIG_SEED = 2 ** 31 + 12345


def load(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def _files(*kinds):
    """The traffic files of the given kinds, by what `traffic/` holds: a
    mix a later PR adds is a case of every test over its kind."""
    names = sorted(n[:-len(".json")] for n in os.listdir(TRAFFIC)
                   if n.endswith(".json"))
    return [n for n in names if load(n)["kind"] in kinds]


@pytest.mark.parametrize("name", _files("backlog"))
def test_same_seed_same_list_and_another_seed_differs(name):
    t = load(name)
    a = tg.make_requests(t, BIG_SEED, 50257)
    b = tg.make_requests(t, BIG_SEED, 50257)
    c = tg.make_requests(t, BIG_SEED + 1, 50257)
    assert len(a) == t["requests"]
    assert [(r.prompt_len, r.max_new) for r in a] == \
        [(r.prompt_len, r.max_new) for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert [(r.prompt_len, r.max_new) for r in a] != \
        [(r.prompt_len, r.max_new) for r in c]
    assert not (a[0].prompt[:8] == c[0].prompt[:8]).all() \
        or a[0].prompt_len != c[0].prompt_len


@pytest.mark.parametrize("name", _files("backlog", "open_loop"))
def test_every_block_holds_one_draw_per_stratum(name):
    t = load(name)
    k = t["strata"]
    lens = tg.request_lengths(t, 7, 10 * k)
    assert tg.request_lengths(t, 7, 10 * k) == lens
    for key, col in (("prompt_len", 0), ("output_len", 1)):
        edges = [tg.quantile(t[key], j / k) for j in range(k + 1)]
        for b in range(10):
            block = sorted(x[col] for x in lens[b * k:(b + 1) * k])
            for j, v in enumerate(block):
                # rounding may move a draw half a token past its edge
                assert edges[j] - 0.5 <= v <= edges[j + 1] + 0.5, (b, j, v)


def test_every_seed_offers_the_same_sizes_block_by_block():
    t = load("chat-backlog")
    k = t["strata"]
    a = tg.request_lengths(t, 1, 8 * k)
    b = tg.request_lengths(t, BIG_SEED, 8 * k)
    assert a != b
    for blk in range(8):
        sl = slice(blk * k, (blk + 1) * k)
        for col in (0, 1):
            assert sorted(x[col] for x in a[sl]) == sorted(x[col] for x in b[sl])


@pytest.mark.parametrize("name", _files("backlog", "open_loop"))
def test_lengths_respect_the_files_limits(name):
    t = load(name)
    lo_p, hi_p = t["prompt_len"]["knots"][0][1], t["prompt_len"]["knots"][-1][1]
    lo_o, hi_o = t["output_len"]["knots"][0][1], t["output_len"]["knots"][-1][1]
    for p, o in tg.request_lengths(t, 3, 640):
        assert lo_p <= p <= hi_p and lo_o <= o <= hi_o
        assert p + o <= t["max_total"]


def test_chat_median_prompt_is_near_128():
    t = load("chat-backlog")
    p = sorted(x[0] for x in tg.request_lengths(t, 0, 640))
    assert 110 <= p[len(p) // 2] <= 150


def test_order_from_the_layout_seed_replays_one_trace():
    t = load("chat-steady")
    assert t["order"] == "layout"
    a = tg.make_requests(t, 1, 50257, horizon_s=120.0)
    b = tg.make_requests(t, BIG_SEED, 50257, horizon_s=120.0)
    assert [(r.prompt_len, r.max_new, r.due_s) for r in a] == \
        [(r.prompt_len, r.max_new, r.due_s) for r in b]
    assert not all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    with pytest.raises(ValueError):
        tg.order_seed(dict(t, order="clock"), 1)


def test_group_is_required_and_must_divide_the_block():
    t = load("chat-backlog")
    with pytest.raises(KeyError):
        tg.request_lengths({k: v for k, v in t.items() if k != "group"}, 1, 16)
    with pytest.raises(ValueError):
        tg.block_order(1, "prompt", 0, 16, 5)
    assert sorted(tg.block_order(1, "prompt", 0, 16, 16)) == list(range(16))
    for g in (1, 2, 4, 8):
        order = tg.block_order(1, "prompt", 3, 16, g)
        assert sorted(order) == list(range(16))
        for r in range(16 // g):  # every run holds one stratum per part
            assert sorted(j // (16 // g) for j in order[r * g:(r + 1) * g]) \
                == list(range(g))


def test_open_loop_same_gaps_for_every_seed_in_another_order():
    t = dict(load("chat-steady"), order="seed")
    a = tg.arrival_times(t, 1, 400.0)
    b = tg.arrival_times(t, BIG_SEED, 400.0)
    assert a == tg.arrival_times(t, 1, 400.0)
    assert a != b
    k = t["strata"]
    n = min(len(a), len(b)) // k * k

    def gaps(x):
        return [v - u for u, v in zip([0.0] + x, x)]

    ga, gb = gaps(a)[:n], gaps(b)[:n]
    for blk in range(n // k):
        sa = sorted(ga[blk * k:(blk + 1) * k])
        sb = sorted(gb[blk * k:(blk + 1) * k])
        assert sa == pytest.approx(sb, abs=1e-9)
    rate = t["arrivals"]["rate_hz"]
    assert len(a) == pytest.approx(rate * 400.0, rel=0.25)


def test_open_loop_requests_carry_due_times_inside_the_horizon():
    t = load("chat-steady")
    reqs = tg.make_requests(t, 5, 50257, horizon_s=60.0)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0.0 < due[0] and due[-1] < 60.0
    assert all(len(r.prompt) == r.prompt_len for r in reqs)


def test_batches_from_the_seed():
    t = load("batch-forward")
    a = tg.make_batches(t, BIG_SEED, 50257)
    b = tg.make_batches(t, BIG_SEED, 50257)
    assert len(a) == t["distinct_batches"]
    assert a[0].shape == (t["batch"], t["seq"]) and a[0].dtype.name == "int32"
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == a[1]).all()
    assert 1 <= a[0].min() and a[0].max() < 50257


@pytest.mark.parametrize("name", _files("backlog"))
def test_a_backlog_cannot_run_dry(name):
    """A traced run submits until the capture is on disk and a faster
    program completes more: the list has to outlast both (the chat cell
    sends ~350-570 a run, PERF.md section 4)."""
    t = load(name)
    assert t["requests"] >= 4000, name
    assert t["requests_why"]


@pytest.mark.parametrize("name", _files("backlog"))
def test_a_longer_backlog_keeps_its_first_requests(name):
    """Sizes come from `layout_seed` block by block and ids from (seed,
    index): raising `requests` appends, it does not change what a run
    that ends earlier was sent."""
    t = load(name)
    short = tg.make_requests(dict(t, requests=640), BIG_SEED, 50257)
    full = tg.make_requests(t, BIG_SEED, 50257)
    assert len(full) == t["requests"] > len(short)
    for a, b in zip(short, full):
        assert (a.index, a.prompt_len, a.max_new) == \
            (b.index, b.prompt_len, b.max_new)
        assert (a.prompt == b.prompt).all()
