"""The generator gives every seed the same work in another order."""

import json
from collections import Counter

import pytest

from bench import traffic as T
from bench.harness import BENCH, load_benchmark

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
OPEN = [m for m in MIXES if T.load_mix(m)["arrivals"]["loop"] == "open"]


@pytest.mark.parametrize("mix", MIXES)
def test_same_multiset_other_order(mix):
    m = T.load_mix(mix)
    a = T.plan(m, seed=1, seconds=30, vocab=1000, n_servers=2)
    b = T.plan(m, seed=2**31 + 77, seconds=30, vocab=1000, n_servers=2)
    assert Counter(len(r.prompt) for r in a) == \
        Counter(len(r.prompt) for r in b)
    assert Counter(r.max_new for r in a) == Counter(r.max_new for r in b)
    assert Counter(r.server for r in a) == Counter(r.server for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("mix", OPEN)
def test_open_loop_fills_the_window_exactly(mix):
    m = T.load_mix(mix)
    for seed in (3, 4):
        r = T.plan(m, seed=seed, seconds=30, vocab=1000, n_servers=2)
        assert len(r) == round(m["arrivals"]["rate_per_s"] * 30)
        due = [x.due for x in r]
        assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30


@pytest.mark.parametrize("base", [2, 3, 5, 7])
def test_stratified_order_spreads_every_block(base):
    import numpy as np

    n = base ** 3
    for seed in (11, 2**31 + 5):
        order = T.stratified_order(n, base, np.random.default_rng(seed))
        assert sorted(order) == list(range(n))
        # each aligned run of ``base`` positions takes one rank from each
        # of ``base`` strata, and each run of ``base**2`` one from each of
        # ``base**2``
        for size in (base, base * base):
            for b in range(0, n, size):
                strata = sorted(order[b:b + size] // (n // size))
                assert strata == list(range(size))


@pytest.mark.parametrize("mix", OPEN)
def test_open_loop_work_near_the_close_hardly_moves_with_the_seed(mix):
    """The requests due in the window's last fifth, where the close cuts
    them short, ask for about the same work on every seed (a plain
    shuffle of the same multisets lets their count range over 26-57 and
    their positions over a factor of 2.3 on 40 seeds)."""
    m = T.load_mix(mix)
    works, counts = [], []
    for seed in range(2**31, 2**31 + 20):
        late = [r for r in T.plan(m, seed=seed, seconds=51, vocab=1000,
                                  n_servers=2) if r.due >= 51 * 0.8]
        counts.append(len(late))
        works.append(sum(len(r.prompt) + r.max_new for r in late))
    assert max(counts) - min(counts) <= 8
    assert max(works) / min(works) < 1.3


def test_every_request_fits_its_cache():
    bench = load_benchmark()
    for w in bench["workloads"]:
        conf = json.loads((BENCH.parent / next(
            c["file"] for c in bench["configs"]
            if c["name"] == w["config"])).read_text())
        m = T.load_mix(w["traffic"])
        assert m["prompt_len"]["max"] + m["output_len"]["max"] \
            <= conf["max_len"] - 1


def test_closed_loop_blocks_hold_the_same_work():
    m = {"arrivals": {"loop": "closed", "clients": 4, "pool": 10},
         "prompt_len": {"dist": "lognormal", "median": 64, "sigma": 0.8,
                        "min": 16, "max": 512},
         "output_len": {"dist": "fixed", "value": 8, "min": 8, "max": 8}}
    for seed in (5, 2**31 + 9):
        r = T.plan(m, seed=seed, seconds=30, vocab=1000, n_servers=1)
        assert len(r) == 8
        assert sorted(len(x.prompt) for x in r[:4]) == \
            sorted(len(x.prompt) for x in r[4:])


def test_lengths_follow_the_distribution():
    spec = {"dist": "lognormal", "median": 64, "sigma": 0.8, "min": 16,
            "max": 512}
    xs = sorted(T.length_quantiles(spec, 1001))
    assert xs[500] == 64 and xs[0] >= 16 and xs[-1] <= 512
