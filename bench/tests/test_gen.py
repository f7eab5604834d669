"""The traffic generators are functions of the seed: the same seed gives
the same arrays, another seed gives others."""
import json

import numpy as np
import pytest

import gen
from run import BENCH

MCF = json.loads((BENCH / "traffic" / "table2_mcf.json").read_text())
BACKLOG = json.loads((BENCH / "traffic" / "serve_backlog.json").read_text())


def _same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("make", [
    lambda s: (gen.table2_rates(MCF, 512, s),),
    lambda s: gen.table2_trace(MCF, 4096, 320, s),
    lambda s: (gen.first_touch_order(320, 512, s),),
    lambda s: gen.serve_requests(BACKLOG, 64, s, 1000),
    lambda s: (np.asarray(gen.prompt_tokens(s, 50, 1000)),),
])
def test_same_seed_same_arrays(make):
    assert _same(make(7), make(7))
    assert not _same(make(7), make(8))


def test_renamed_pages_are_the_same_work():
    rates = gen.table2_rates(MCF, 512, 1)
    order = gen.first_touch_order(320, 512, 2)
    pages = gen.table2_trace(MCF, 4096, 320, 3)[0]
    r7, o7, p7 = gen.rename_pages(7, rates, order, pages)
    assert _same((r7, o7, p7), gen.rename_pages(7, rates, order, pages))
    assert not _same((r7, o7, p7), gen.rename_pages(8, rates, order, pages))
    # one bijection: the same access counts and the same rates per access
    assert np.array_equal(np.sort(np.bincount(p7, minlength=512)),
                          np.sort(np.bincount(pages, minlength=512)))
    assert np.array_equal(r7[p7], rates[pages])
    assert np.array_equal(r7[o7], rates[order])


def test_sub_seeds_take_large_seeds():
    a = gen.sub_seeds(3 * 2**31 + 5, 3)
    assert a == gen.sub_seeds(3 * 2**31 + 5, 3)
    assert all(0 <= s < 2**31 for s in a) and len(set(a)) == 3


def test_lengths_respect_clips():
    plen, olen, _ = gen.serve_requests(BACKLOG, 2000, 1, 1000)
    assert plen.min() >= 64 and plen.max() <= 2048
    assert olen.min() >= 16 and olen.max() <= 1024
    assert 900 < np.median(plen) < 1100


@pytest.mark.parametrize("a", [0.8, 1.1])
def test_zipf_ranks_follow_the_truncated_law(a):
    n, size = 50, 200_000
    ranks = gen.zipf_ranks(np.random.default_rng(3), a, n, size)
    assert ranks.min() >= 1 and ranks.max() <= n
    p = np.arange(1, n + 1, dtype=float) ** -a
    p /= p.sum()
    seen = np.bincount(ranks, minlength=n + 1)[1:] / size
    assert np.abs(seen - p).max() < 0.005
