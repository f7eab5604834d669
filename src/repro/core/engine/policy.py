"""Policy layer (DESIGN.md §5): per-scheme residency + accounting decisions.

A ``Policy`` owns everything that differs *between the compared designs*
(paper §5/§6) while ``engine.ops`` owns the shared mechanisms:

  * **promotion trigger** — which block states promote on access;
  * **victim selection**  — how a demotion victim is chosen (the pool's clock
    engine; the serving engine reuses the same shape at lane granularity via
    ``SecondChanceLanes``);
  * **residency/traffic accounting** — hooks called at the *site* where a
    scheme's extra traffic physically occurs (LRU-list node updates, dual
    metadata-table probes, zsmalloc fragmentation bookkeeping, migration
    granularity multipliers). This replaces the old ``simx.engine._finalize``
    post-hoc counter arithmetic: traffic is counted where it happens.

Policies are frozen dataclasses so they hash and can be closed over by
``jax.jit`` as static arguments; hooks are pure jit-traceable functions of the
counters array.

Schemes (paper §5/§6):
  ibex        full IBEX (shadow + co-location + compaction, clock demotion);
              the Fig. 13 ablation ladder (ibex_base/_s/_sc/_scm) is the same
              policy with mechanism toggles flipped
  tmcc        4KB blocks, variable-size chunks (zsmalloc bookkeeping +
              fragmentation reclaim traffic), list-based recency, no shadow
  dylect      tmcc + dual metadata tables (2nd probe per mcache miss)
  mxt         4KB promotion cache with on-chip tags (no activity traffic)
              but page-granular promotion, no zero elision
  dmc         32KB migration granularity (promotion/demotion traffic x8)
  compresso   line-level: no promotion machinery at all, low ratio
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import activity as act
from repro.core.engine.state import (C_ACT_WR, C_DEMO_WR, C_META_RD,
                                     C_META_WR, bump)


@dataclass(frozen=True)
class Policy:
    """Base policy: pure IBEX behavior. Subclasses override hooks to charge
    their design's extra traffic in place."""
    name: str = "ibex"
    # mechanism toggles the policy requires of its PoolConfig (ablation S/C/M)
    coloc: bool = True
    shadow: bool = True
    compact: bool = True
    zero_elision: bool = True
    # device-model knob: 4KB-block schemes pay 4x compression-engine latency
    block4k_engine: bool = False
    # line-level schemes bypass the pool entirely (no promotion machinery)
    line_level: bool = False

    # -- accounting hooks (pure: counters -> counters) ----------------------

    def on_host_access(self, counters: jnp.ndarray, is_write, n=1
                       ) -> jnp.ndarray:
        """Per host access, at access time (e.g. recency-list maintenance)."""
        return counters

    def on_mcache_miss(self, counters: jnp.ndarray, n=1) -> jnp.ndarray:
        """Extra traffic per metadata-cache miss (e.g. a second table probe);
        ``n`` misses at once from the batched front-end. The base metadata
        read itself is mechanism traffic (ops.mcache_step)."""
        return counters

    def on_compress_store(self, counters: jnp.ndarray) -> jnp.ndarray:
        """Per compressed-page store (dirty demotion or recompression)."""
        return counters

    def on_demotion(self, counters: jnp.ndarray, clean) -> jnp.ndarray:
        """Per demotion, after the mechanism's own traffic is charged."""
        return counters

    def charge_activity(self, counters: jnp.ndarray, idx: int, n=1
                        ) -> jnp.ndarray:
        """Activity-region traffic (clock scans, lazy reference updates).
        Schemes with on-chip recency state suppress this."""
        return bump(counters, idx, n)

    def charge_migration(self, counters: jnp.ndarray, idx: int, n=1
                         ) -> jnp.ndarray:
        """Promotion/demotion data movement (promo_rd/wr, demo_rd/wr).
        Coarser migration granularity multiplies it."""
        return bump(counters, idx, n)

    # -- residency decisions ------------------------------------------------

    def select_victim(self, activity: jnp.ndarray, hand: jnp.ndarray, cache,
                      rng: jnp.ndarray, force=False) -> act.Victim:
        """Victim selection: the §4.4 second-chance clock over the activity
        region, which it only reads; the engine applies the scan's clears
        (``activity.clear_scanned``). (The serving engine applies the same
        policy shape at lane granularity — see ``SecondChanceLanes``.)"""
        return act.find_victim(activity, hand, cache, rng, force=force)


@dataclass(frozen=True)
class IbexPolicy(Policy):
    """Full IBEX. Ablation rungs are mechanism toggles on the same policy."""


@dataclass(frozen=True)
class TmccPolicy(Policy):
    """TMCC: 4KB blocks, zsmalloc-style variable chunks, LRU-list recency.

    Extra traffic charged where it occurs:
      * one recency-list node update per host access (list-based LRU);
      * two bookkeeping writes per compressed-page store (zspage alloc maps);
      * one reclaim access per demotion (fragmentation compaction).
    """
    name: str = "tmcc"
    coloc: bool = False
    shadow: bool = False
    block4k_engine: bool = True

    def on_host_access(self, counters, is_write, n=1):
        return bump(counters, C_ACT_WR, n)

    def on_compress_store(self, counters):
        return bump(counters, C_META_WR, 2)

    def on_demotion(self, counters, clean):
        return bump(counters, C_DEMO_WR, 1)


@dataclass(frozen=True)
class DylectPolicy(TmccPolicy):
    """DyLeCT: TMCC plus dual metadata tables — every metadata-cache miss
    probes both tables (one extra metadata read at the miss site)."""
    name: str = "dylect"

    def on_mcache_miss(self, counters, n=1):
        return bump(counters, C_META_RD, n)


@dataclass(frozen=True)
class MxtPolicy(Policy):
    """MXT-style 4KB promotion cache with on-chip tags: recency state never
    touches device memory, so activity traffic is suppressed at the charge
    site; page-granular promotion, no zero elision."""
    name: str = "mxt"
    coloc: bool = False
    zero_elision: bool = False
    block4k_engine: bool = True

    def charge_activity(self, counters, idx, n=1):
        return counters


@dataclass(frozen=True)
class DmcPolicy(Policy):
    """DMC: 32KB migration granularity — every promotion/demotion moves 8x
    the data, charged at the movement site."""
    name: str = "dmc"
    coloc: bool = False
    shadow: bool = False
    block4k_engine: bool = True
    migrate_mult: int = 8

    def charge_migration(self, counters, idx, n=1):
        return bump(counters, idx, jnp.asarray(n) * self.migrate_mult)


@dataclass(frozen=True)
class CompressoPolicy(Policy):
    """Compresso: line-level compression, no promotion machinery. The simx
    engine routes this through its dedicated line-level model."""
    name: str = "compresso"
    line_level: bool = True


DEFAULT_POLICY = IbexPolicy()

POLICIES: Dict[str, Policy] = {
    "ibex": IbexPolicy(),
    "ibex_base": dataclasses.replace(IbexPolicy(), name="ibex_base",
                                     coloc=False, shadow=False, compact=False,
                                     block4k_engine=True),
    "ibex_s": dataclasses.replace(IbexPolicy(), name="ibex_s", coloc=False,
                                  shadow=True, compact=False,
                                  block4k_engine=True),
    "ibex_sc": dataclasses.replace(IbexPolicy(), name="ibex_sc", coloc=True,
                                   shadow=True, compact=False),
    "ibex_scm": dataclasses.replace(IbexPolicy(), name="ibex_scm", coloc=True,
                                    shadow=True, compact=True),
    "tmcc": TmccPolicy(),
    "dylect": DylectPolicy(),
    "mxt": MxtPolicy(),
    "dmc": DmcPolicy(),
    "compresso": CompressoPolicy(),
}


class SecondChanceLanes:
    """The §4.4 second-chance victim-selection policy at *lane* (request)
    granularity, used by the serving engine: reference bit = "generated a
    token since last sweep". Mirrors ``Policy.select_victim`` over lane
    state instead of the activity region, including the bounded sweep +
    round-robin fallback (the paper's random fallback).

    ``select_mask`` is the vectorized form: one pass of array ops over all
    lanes (the serving engine keeps lane bookkeeping as arrays, so the sweep
    must not loop lane-by-lane). ``select`` keeps the callback form for
    callers holding per-lane Python state."""

    def __init__(self, n_lanes: int):
        self.n = n_lanes
        self.hand = 0

    def select_mask(self, occupied, referenced, groups=None, group_load=None):
        """One-pass sweep. occupied/referenced: bool[n] arrays. Returns
        (victim lane or None, new referenced bits). Semantics match the
        serial clock: ref bits of occupied lanes between the hand and the
        victim are cleared (their second chance); if every occupied lane is
        referenced, all are cleared and the first occupied lane after the
        hand is taken (round-robin fallback).

        ``groups``/``group_load`` (fabric-aware serving): lanes carry an
        expander id and every expander a current parked-payload load; among
        the sweep's candidates the victim is the first lane belonging to
        the least-loaded candidate expander, so preemptions park evenly
        across expanders instead of piling onto whichever expander the hand
        happens to point at. With ``groups=None`` behavior is unchanged."""
        occ = np.asarray(occupied, bool)
        ref = np.array(referenced, bool, copy=True)
        order = (self.hand + np.arange(self.n)) % self.n
        cand = occ[order] & ~ref[order]
        if cand.any():
            if groups is None:
                k = int(np.argmax(cand))
            else:
                pos = np.nonzero(cand)[0]
                loads = np.asarray(group_load)[
                    np.asarray(groups)[order[pos]]]
                k = int(pos[int(np.argmin(loads))])   # first-min: earliest
            swept = order[:k]
            ref[swept[occ[swept]]] = False
        elif occ.any():
            k = int(np.argmax(occ[order]))
            ref[occ] = False          # full revolution: everyone spent theirs
        else:
            return None, ref
        victim = int(order[k])
        self.hand = (victim + 1) % self.n
        return victim, ref

    def select(self, occupied: Callable[[int], bool],
               referenced: Callable[[int], bool],
               clear: Callable[[int], None]) -> Optional[int]:
        occ = np.array([bool(occupied(i)) for i in range(self.n)])
        ref = np.array([occ[i] and bool(referenced(i)) for i in range(self.n)])
        victim, new_ref = self.select_mask(occ, ref)
        for i in np.nonzero(ref & ~new_ref)[0]:
            clear(int(i))
        return victim
