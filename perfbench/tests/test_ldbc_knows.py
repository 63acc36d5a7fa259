"""The LDBC SNB knows stand-in (``generators/ldbc_knows.py``): exact sizes,
a plain edge list, one graph per seed, the three correlation passes in
their shares, and a landmark set clear of degree ties at the
configuration's own size and seed."""
import json
from pathlib import Path

import numpy as np
import pytest

import graphgen
import plugins

ROOT = Path(__file__).resolve().parents[2]
CONF = json.loads((ROOT / "perfbench/configs/ldbc-snb-sf10-r20.json").read_text())
KNOWS = CONF["graph"]["knows"]
gen = plugins.load("generators", "ldbc_knows")


def small(seed, n=2000, m=30000):
    return gen.ldbc_knows(n, m, KNOWS, seed)


def test_the_same_seed_gives_the_same_edges():
    assert np.array_equal(small(3), small(3))
    assert not np.array_equal(small(3), small(4))


@pytest.mark.parametrize("n,m", [(2000, 30000), (500, 1500)])
def test_exact_sizes_and_a_plain_edge_list(n, m):
    e = small(7, n, m)
    assert e.shape == (m, 2) and e.dtype == np.int64
    assert e.min() >= 0 and e.max() < n
    assert np.all(e[:, 0] < e[:, 1])                        # no self loop
    assert np.unique(e[:, 0] * n + e[:, 1]).size == m       # no duplicate


def test_the_three_passes_make_their_shares_of_the_link_ends():
    """Each pass deals ``share`` of every target degree (times the
    overshoot), so its links are that share of all links within 1%."""
    rng = np.random.default_rng(5)
    n, m = 20000, 600000
    target = gen.target_degrees(n, m, KNOWS, rng)
    assert target.mean() == pytest.approx(2 * m / n)
    links = [gen.pass_edges(target, s, KNOWS["window"], rng).shape[0]
             for s in KNOWS["shares"]]
    for got, share in zip(links, KNOWS["shares"]):
        assert got / sum(links) == pytest.approx(share, abs=0.01)
        assert 2 * got / (share * target.sum()) == pytest.approx(1, abs=0.01)


def test_a_pass_links_persons_near_in_its_order():
    """Two persons of a pass link with a chance that falls off with their
    distance in the pass's order: most links of a pass of a thousand
    persons span a few windows, not the whole order."""
    rng = np.random.default_rng(2)
    target = np.full(1000, 40.0)
    state = rng.bit_generator.state
    order = rng.permutation(1000)
    rank = np.empty(1000, np.int64)
    rank[order] = np.arange(1000)
    rng.bit_generator.state = state                  # the pass draws the same order
    links = gen.pass_edges(target, 0.45, KNOWS["window"], rng)
    gap = np.abs(rank[links[:, 0]] - rank[links[:, 1]])
    assert np.median(gap) < 2 * KNOWS["window"]
    assert np.mean(gap < 6 * KNOWS["window"]) > 0.9


def test_the_configuration_has_exact_sizes_and_a_clear_landmark_set():
    g = CONF["graph"]
    n, m = g["n_vertices"], g["n_edges"]
    assert (n, m) == (65645, 1938516)
    e = graphgen.generate(g, g["structure_seed"])
    assert e.shape == (m, 2)
    deg = np.bincount(e.reshape(-1), minlength=n)
    assert deg.mean() == pytest.approx(59.06, abs=0.01)
    assert graphgen.clear_top(e, n, CONF["index"]["n_landmarks"])
