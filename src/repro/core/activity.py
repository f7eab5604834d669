"""Page-activity region + second-chance (clock) demotion engine (§4.4).

The activity region holds one 4B entry per P-chunk: ``allocated | referenced |
OSPN``; 16 entries per 64B fetch. The demotion cursor (clock hand) scans
fetch-group by fetch-group:

  * referenced=1 allocated entries get their bit reset (second chance);
  * the first allocated, unreferenced entry whose page is NOT resident in the
    metadata cache (probe, lazy-update safety) is the victim;
  * if a fetched group contains allocated entries but no candidate, one of the
    non-cache-resident allocated entries is chosen at random (bounded worst-case
    bandwidth — paper reports 0.6% of selections);
  * a group with no eligible entry at all advances the hand (rare: promoted
    region is near-full whenever demotion runs).

Each scanned group costs one 64B read + one 64B write (bit resets), which is
exactly the paper's "control traffic" — counters are returned to the caller.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import mcache as mc
from repro.core.metadata import (act_allocated, act_ospn, act_referenced,
                                 act_set_referenced)

GROUP = 16  # activity entries per 64B fetch


class Victim(NamedTuple):
    hand: jnp.ndarray
    victim_pidx: jnp.ndarray     # P-chunk index, -1 if none found
    victim_ospn: jnp.ndarray     # -1 if none
    used_random: jnp.ndarray     # bool
    groups_scanned: jnp.ndarray  # int32 — traffic: 1 rd + 1 wr of 64B each


class ScanResult(NamedTuple):
    activity: jnp.ndarray
    hand: jnp.ndarray
    victim_pidx: jnp.ndarray
    victim_ospn: jnp.ndarray
    used_random: jnp.ndarray
    groups_scanned: jnp.ndarray


def find_victim(activity: jnp.ndarray, hand: jnp.ndarray, cache: mc.MCache,
                rng: jnp.ndarray, max_groups: int = 8,
                force: jnp.ndarray | bool = False) -> Victim:
    """The clock scan without its writes: ``activity`` is only read, and
    the second-chance clears it makes are ``clear_scanned``'s. The groups a
    scan visits are consecutive from the hand's, so a group is visited a
    second time only after every group was, and then reads as cleared.

    ``force`` widens the random fallback to cache-resident pages — the
    emergency path when the promoted region is exhausted and every resident
    page probes hot (cannot occur at the paper's region ratios, but a correct
    device must not deadlock)."""
    force = jnp.asarray(force)
    n = activity.shape[0]
    n_groups = n // GROUP

    def probe_many(ospns: jnp.ndarray) -> jnp.ndarray:
        return jax.vmap(lambda o: mc.probe(cache, o))(ospns)

    def cond(carry):
        (_, found, _, _, _, groups, _) = carry
        return (~found) & (groups < max_groups)

    def body(carry):
        hand, found, victim, _, used_rnd, groups, rng = carry
        g = (hand // GROUP) % n_groups
        start = g * GROUP
        entries = jax.lax.dynamic_slice(activity, (start,), (GROUP,))
        alloc = act_allocated(entries) == 1
        revisit = groups >= n_groups
        entries = jnp.where(revisit & alloc, act_set_referenced(entries, 0),
                            entries)
        ref = act_referenced(entries) == 1
        ospns = act_ospn(entries).astype(jnp.int32)
        probed = probe_many(ospns)
        eligible = alloc & (~ref) & (~probed)
        any_eligible = jnp.any(eligible)
        first = jnp.argmax(eligible)
        # random fallback among allocated, non-resident entries
        rnd_pool = alloc & ((~probed) | force)
        any_rnd = jnp.any(rnd_pool)
        rng, sub = jax.random.split(rng)
        weights = rnd_pool.astype(jnp.float32)
        rnd_pick = jax.random.categorical(sub, jnp.log(weights + 1e-9))
        pick = jnp.where(any_eligible, first, rnd_pick)
        got = any_eligible | any_rnd
        used_rnd_now = (~any_eligible) & any_rnd
        victim_new = jnp.where(got, start + pick, -1)
        ospn = jnp.where(got, ospns[pick], -1)
        return (hand + GROUP, got, victim_new.astype(jnp.int32), ospn,
                used_rnd_now, groups + 1, rng)

    init = (hand, jnp.asarray(False), jnp.asarray(-1, jnp.int32),
            jnp.asarray(-1, jnp.int32), jnp.asarray(False),
            jnp.asarray(0, jnp.int32), rng)
    hand, _, victim, ospn, used_rnd, groups, _ = \
        jax.lax.while_loop(cond, body, init)
    return Victim(hand, victim, ospn, used_rnd, groups)


def clear_scanned(activity: jnp.ndarray, hand: jnp.ndarray, groups,
                  max_groups: int = 8) -> jnp.ndarray:
    """Second chance: clear the referenced bits of the allocated entries in
    the ``groups`` fetch groups a scan from ``hand`` visited. Two predicated
    window writes (the run from the hand's group, and the part that wrapped
    to group 0), so the region updates in place."""
    n_groups = activity.shape[0] // GROUP
    width = min(max_groups, n_groups) * GROUP
    g0 = (hand // GROUP) % n_groups

    def clear_window(act, start):
        e = jax.lax.dynamic_slice(act, (start,), (width,))
        g = (start + jnp.arange(width, dtype=jnp.int32)) // GROUP
        scanned = ((g - g0) % n_groups) < groups
        new = jnp.where(scanned & (act_allocated(e) == 1),
                        act_set_referenced(e, 0), e)
        return jax.lax.dynamic_update_slice(act, new, (start,))

    activity = clear_window(activity, jnp.clip(g0 * GROUP, 0,
                                               n_groups * GROUP - width))
    return clear_window(activity, jnp.asarray(0, jnp.int32))


def clock_scan(activity: jnp.ndarray, hand: jnp.ndarray, cache: mc.MCache,
               rng: jnp.ndarray, max_groups: int = 8,
               force: jnp.ndarray | bool = False) -> ScanResult:
    """One demotion-cursor scan with its clears applied (``find_victim``
    then ``clear_scanned``)."""
    v = find_victim(activity, hand, cache, rng, max_groups, force)
    return ScanResult(clear_scanned(activity, hand, v.groups_scanned,
                                    max_groups), *v)


def put_word(activity: jnp.ndarray, pidx, word, ok=True) -> jnp.ndarray:
    """Entry ``pidx`` set to ``word`` where ``ok``: one predicated element
    write, so the region updates in place."""
    safe = jnp.clip(pidx, 0, activity.shape[0] - 1)
    old = jax.lax.dynamic_slice(activity, (safe,), (1,))
    new = jnp.where(ok, jnp.asarray(word, jnp.uint32)[None], old)
    return jax.lax.dynamic_update_slice(activity, new, (safe,))


def lazy_touch(activity: jnp.ndarray, pidx: jnp.ndarray) -> jnp.ndarray:
    """Set the referenced bit (the §4.4 lazy update, performed on metadata-cache
    eviction rather than on every access). pidx < 0 is a no-op."""
    e = activity[jnp.clip(pidx, 0, activity.shape[0] - 1)]
    return put_word(activity, pidx, act_set_referenced(e, 1), pidx >= 0)
