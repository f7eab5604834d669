"""Traffic generators: every input of a run is a function of ``--seed`` and
the parameters in one ``bench/traffic/<mix>.json`` file.

The Table 2 trace model (``table2_rates``, ``table2_trace``) and the
first-touch order are copies of ``simx/trace.py``'s ``make_rates_table`` /
``make_trace`` and ``simx/engine.first_touch_populate``, kept here so that
the yardstick does not move when the program does (``table2_trace``
draws its Zipf law exactly, see its docstring). The serve generators draw
request lengths; prompt token ids are drawn per request.
"""
from __future__ import annotations

import numpy as np


def sub_seeds(seed: int, n: int) -> list:
    """``n`` independent 31-bit seeds derived from any whole ``seed``
    (a seed may exceed 32 bits; JAX keys want 31)."""
    state = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(s) & 0x7FFFFFFF for s in state]


# ---------------------------------------------------------------------------
# replay: the paper's Table 2 workload model
# ---------------------------------------------------------------------------

def table2_rates(mix: dict, n_pages: int, seed: int, blocks: int = 4
                 ) -> np.ndarray:
    """Per-page per-block rate codes (0 zero / 1 4-bit / 2 8-bit / 3 raw)."""
    rng = np.random.default_rng(seed)
    zero_page = rng.random(n_pages) < mix["zero_frac"]
    p_raw = max(0.0, 1.0 - mix["mix4"] - mix["mix8"])
    rates = rng.choice([1, 2, 3], size=(n_pages, blocks),
                       p=[mix["mix4"], mix["mix8"], p_raw])
    rates[zero_page] = 0
    zb = rng.random((n_pages, blocks)) < 0.08     # zero blocks in normal pages
    rates[zb & ~zero_page[:, None]] = 0
    return rates.astype(np.int32)


def zipf_ranks(rng: np.random.Generator, a: float, n: int, size: int
               ) -> np.ndarray:
    """``size`` ranks in 1..n of a Zipf law truncated at ``n``: P(k) is
    proportional to k^-a, drawn exactly by inverse CDF (any a > 0)."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -float(a))
    cdf /= cdf[-1]
    return np.searchsorted(cdf, rng.random(size), side="right") + 1


def table2_trace(mix: dict, n_accesses: int, n_pages: int, seed: int):
    """(ospn int32, is_write bool, block int32): a Zipf head over a random
    page ranking plus a wrapping streaming scan, shuffled together.

    The Zipf law is truncated at the footprint and drawn exactly with the
    mix's exponent. (``simx/trace.py`` clamps the exponent to at least
    1.01 for numpy's sampler and maps draws beyond the footprint to rank 1
    one at a time: over mcf's 20,480 pages that put 39% of the accesses on
    one page and took minutes at 2^20 accesses.)"""
    rng = np.random.default_rng(seed + 1)
    n_stream = int(n_accesses * mix["stream_frac"])
    n_zipf = n_accesses - n_stream
    ranks = zipf_ranks(rng, mix["zipf_a"], n_pages, n_zipf)
    perm = rng.permutation(n_pages)
    zipf_pages = perm[(ranks - 1).astype(np.int64)]
    start = rng.integers(0, n_pages)
    stream_pages = perm[(start + np.arange(n_stream)) % n_pages]
    pages = np.concatenate([zipf_pages, stream_pages])
    pages = pages[rng.permutation(n_accesses)]
    is_write = rng.random(n_accesses) < mix["write_frac"]
    block = rng.integers(0, 4, size=n_accesses)
    return (pages.astype(np.int32), is_write.astype(bool),
            block.astype(np.int32))


def first_touch_order(n_used: int, n_pages: int, seed: int) -> np.ndarray:
    """One write per tracked page slot, cycling over the ``n_used`` pages
    the workload touches in a seeded order."""
    order = np.random.default_rng(seed).permutation(n_used).astype(np.int32)
    return order[np.arange(n_pages) % n_used]


def rename_pages(seed: int, rates: np.ndarray, order: np.ndarray,
                 pages: np.ndarray):
    """The same work under another naming of the pages: a seeded
    permutation ``pi`` of the page space; page ``pi[q]`` takes page ``q``'s
    rate codes, and the first-touch order and the trace name ``pi[q]``
    wherever they named ``q``. Returns (rates, order, pages)."""
    pi = np.random.default_rng(seed).permutation(rates.shape[0])
    renamed = np.empty_like(rates)
    renamed[pi] = rates
    return (renamed, pi[order].astype(np.int32),
            pi[pages].astype(np.int32))


def footprint_pages(mix: dict, n_pchunks: int, n_pages: int) -> int:
    return min(max(int(n_pchunks * mix["footprint"]), 32), n_pages)


# ---------------------------------------------------------------------------
# serve: request lengths and prompts
# ---------------------------------------------------------------------------

def lognormal_lengths(rng: np.random.Generator, n: int, spec: dict
                      ) -> np.ndarray:
    """Lengths with median ``spec['median']`` and log-sd ``spec['sigma']``,
    clipped to [lo, hi]."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.round(x), spec["lo"], spec["hi"]).astype(np.int64)


def serve_requests(mix: dict, n: int, seed: int, vocab: int):
    """``n`` requests as (prompt_len, max_new_tokens, prompt_seed) arrays.
    Prompt ids are drawn on demand by ``prompt_tokens``."""
    rng = np.random.default_rng(seed)
    plen = lognormal_lengths(rng, n, mix["prompt"])
    olen = lognormal_lengths(rng, n, mix["output"])
    pseed = rng.integers(0, 2**31 - 1, size=n)
    return plen, olen, pseed


def prompt_tokens(pseed: int, length: int, vocab: int) -> list:
    rng = np.random.default_rng(int(pseed))
    return rng.integers(1, vocab, size=int(length)).tolist()
