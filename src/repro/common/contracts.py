"""Machine-readable host-sync contracts (DESIGN.md §15).

Every speedup layer in this repro rests on a host-sync discipline — ONE
device→host sync per decode step (serve), per replayed segment and per
migration epoch (fabric). Until now those contracts lived in docstrings
and were enforced only at runtime by harnesses a regressing PR may not run.
``@sync_contract`` turns them into annotations that are checked twice:

  * **statically** — ``repro.analysis`` rule R5 counts the device→host
    fetch sites (``jax.device_get``, ``.item()``, ``block_until_ready``,
    ``self._fetch``, device-sourced ``np.asarray``) lexically present in
    the annotated function and fails the lint when the count exceeds the
    declared budget, or when a fetch site sits inside a host loop (one
    sync per *iteration* is how the one-sync contract quietly becomes
    O(n));
  * **at runtime** — ``verify_sync_counters`` cross-checks the measured
    sync counters (``step_syncs == steps``, ``segment_syncs ==
    segments``, ...) against the declared budget, so the tests assert
    the *declared* contract rather than a magic constant of their own.

The decorator is intentionally a no-op at call time (it only attaches a
``SyncContract`` record): the annotated functions are the hottest host
loops in the repo and must not pay a wrapper frame per step.

This module must stay importable without jax — the static analyzer and
CI lint step run it on machines with no accelerator stack.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

_ATTR = "__sync_contract__"

# The event kinds the repo's runtime counters are keyed on. Free-form
# strings are allowed (the analyzer only needs identity), but sticking to
# these keeps the runtime cross-checks uniform. "boundary" is the sharded
# fabric driver's fused per-segment-boundary fetch, "drain" its one
# deferred migration-off fetch per replay() call, "call" a per-invocation
# metric fetch (Fabric.delivered_time).
KNOWN_EVENTS = ("step", "segment", "epoch", "admission", "boundary",
                "drain", "call")


@dataclass(frozen=True)
class SyncContract:
    """Declared host-sync budget: at most ``fetches`` device→host fetch
    sites per ``syncs_per`` event."""
    syncs_per: str
    fetches: int = 1

    def expected_syncs(self, n_events: int) -> int:
        return n_events * self.fetches


def sync_contract(syncs_per: str, fetches: int = 1) -> Callable:
    """Annotate a function with its host-sync contract.

    ``syncs_per`` names the event the contract is counted against
    ("step", "segment", "epoch"); ``fetches`` is the maximum number of
    device→host fetch sites the body may contain per event. Returns the
    function UNCHANGED (no wrapper) with a ``SyncContract`` attached —
    the static analyzer reads the decorator from source, runtime
    cross-checks read the attribute.
    """
    if not isinstance(fetches, int) or fetches < 0:
        raise ValueError(f"fetches must be a non-negative int, got {fetches!r}")

    def attach(fn):
        setattr(fn, _ATTR, SyncContract(syncs_per=syncs_per, fetches=fetches))
        return fn

    return attach


def get_sync_contract(fn) -> Optional[SyncContract]:
    """The contract attached to ``fn`` (bound methods resolve through to
    the underlying function), or None when undeclared."""
    return getattr(fn, _ATTR, None)


def verify_sync_counters(fn, n_events: int, n_syncs: int,
                         what: str = "") -> SyncContract:
    """Runtime half of the contract: assert the measured sync count
    matches the budget ``fn`` declared. Raises AssertionError when ``fn``
    declares no contract (the cross-check exists precisely so the
    annotation cannot be silently deleted) or when the counters disagree.
    Returns the contract so callers can report it."""
    c = get_sync_contract(fn)
    name = getattr(fn, "__qualname__", repr(fn))
    assert c is not None, f"{name} declares no @sync_contract ({what})"
    expected = c.expected_syncs(n_events)
    assert n_syncs == expected, (
        f"{name}: measured {n_syncs} syncs over {n_events} {c.syncs_per}s, "
        f"contract declares {c.fetches} per {c.syncs_per} "
        f"(expected {expected}) {what}")
    return c
