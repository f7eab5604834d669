"""Functional free-chunk lists (§4.1.1).

The paper tracks free C-chunks and P-chunks with linked lists plus a head
register each. A functional array-stack is the JAX-native equivalent: ``items``
holds free chunk indices, ``top`` is the head register. Pop returns the head;
push writes back. All ops are O(1) and jit-safe; popping an empty list returns
sentinel -1 (callers must check, mirroring the hardware's watermark logic that
prevents true exhaustion).

Compaction (§4.7) splits the compressed region into sub-regions so chunk
pointers share MSBs. We model S sub-regions as S independent stacks laid out in
one array; the allocator round-robins pages across sub-regions ("all C-chunks
allocated to a single OSPA page must belong to the same sub-region").
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class FreeList(NamedTuple):
    items: jnp.ndarray      # int32[capacity]
    top: jnp.ndarray        # int32[] — number of free items (head register)

    @property
    def capacity(self) -> int:
        return self.items.shape[0]


def make_freelist(n: int, reverse: bool = False) -> FreeList:
    idx = jnp.arange(n, dtype=jnp.int32)
    if reverse:
        idx = idx[::-1]
    return FreeList(items=idx, top=jnp.asarray(n, jnp.int32))


def free_count(fl: FreeList) -> jnp.ndarray:
    return fl.top


def pop(fl: FreeList) -> Tuple[FreeList, jnp.ndarray]:
    """Pop one index; returns -1 if empty."""
    has = fl.top > 0
    idx = jnp.where(has, fl.items[jnp.maximum(fl.top - 1, 0)], -1)
    new_top = jnp.where(has, fl.top - 1, fl.top)
    return FreeList(fl.items, new_top), idx.astype(jnp.int32)


def push(fl: FreeList, idx: jnp.ndarray) -> FreeList:
    """Push one index; idx < 0 is a no-op (makes masked pushes trivial)."""
    return push_n(fl, jnp.asarray(idx)[None])


def pop_n(fl: FreeList, k: int, valid_n: jnp.ndarray) -> Tuple[FreeList, jnp.ndarray]:
    """Pop up to ``k`` (static) indices, of which only the first ``valid_n``
    (dynamic) are actually consumed. Returns int32[k] with -1 padding.
    Pops write nothing: the items are read, the head register moves."""
    i = jnp.arange(k, dtype=jnp.int32)
    take = (i < valid_n) & (i < fl.top)
    vals = fl.items[jnp.clip(fl.top - 1 - i, 0, fl.capacity - 1)]
    out = jnp.where(take, vals, -1).astype(jnp.int32)
    return FreeList(fl.items, fl.top - jnp.sum(take).astype(jnp.int32)), out


def pack(idxs: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The non-negative entries of ``idxs`` moved to the front, in order,
    and their count: the items a ``push_n`` of ``idxs`` pushes."""
    do = idxs >= 0
    rank = jnp.where(do, jnp.cumsum(do) - 1, idxs.shape[0])
    packed = jnp.full(idxs.shape, -1, jnp.int32).at[rank].set(
        idxs.astype(jnp.int32), mode="drop")
    return packed, jnp.sum(do).astype(jnp.int32)


def write_run(items: jnp.ndarray, at, vals: jnp.ndarray, n) -> jnp.ndarray:
    """``items`` with ``vals[:n]`` written at ``at, at + 1, ...``: one
    predicated window write of ``len(vals)`` slots. Exact while the run
    fits below the capacity, which holds for a push of free chunks (every
    chunk is free at most once, so a list never holds more than its
    capacity)."""
    k = vals.shape[0]
    cap = items.shape[0]
    start = jnp.clip(at, 0, cap - k)
    old = jax.lax.dynamic_slice(items, (start,), (k,))
    off = start + jnp.arange(k, dtype=jnp.int32) - at
    mine = (off >= 0) & (off < n)
    new = jnp.where(mine, vals[jnp.clip(off, 0, k - 1)], old)
    return jax.lax.dynamic_update_slice(items, new, (start,))


def push_n(fl: FreeList, idxs: jnp.ndarray) -> FreeList:
    """Push all non-negative entries of ``idxs`` (static length)."""
    vals, n = pack(idxs)
    return FreeList(write_run(fl.items, fl.top, vals, n), fl.top + n)
