"""Device op self time of the serve decode-step program by the program's
scopes, for the per-layer metrics of one kernel region of the step.

``hlo_scopes.scope_seconds`` joins op names over every scoped program that
ran in the window; a serve window runs the decode step beside prefill
programs of other shapes, whose instruction names collide with the step's.
Here only the ops launched under the decode phase (``serve.decode``, the
forms' label of ``Engine.step``) are joined, and only with the scopes of
the decode-step program (``jit_serve_decode_step``).

XLA's TPU rewrite of ``jax.lax.ragged_dot`` into ``ragged-dot-*`` custom
calls drops their scope (their ``op_name`` is their own name), so those
instructions, the grouped products of the expert layer, are charged to
``moe.experts`` by their name."""
from __future__ import annotations

from collections import defaultdict
from typing import Dict

import hlo_scopes

PHASE = "serve.decode"
MODULE = "jit_serve_decode_step"
GROUPED = ("ragged-dot", "moe.experts")   # name prefix, its scope

_last: list = [None, None]


def decode_scope_seconds(red) -> Dict[str, float]:
    """Self seconds by scope (and ``unscoped``) of the decode step's ops in
    the traced window; empty where the program declares no scopes."""
    if _last[0] is red:
        return _last[1]
    try:
        from repro.obs.spans import SERVE_SCOPES as scopes
    except ImportError:
        scopes = ()
    mods = [m for m in red.module_s if m.startswith(MODULE)]
    names = hlo_scopes.live_scopes(mods, scopes) if scopes and mods else {}
    if GROUPED[1] in scopes:
        for name in names:
            if name.startswith(GROUPED[0]):
                names[name] = GROUPED[1]
    out: Dict[str, float] = defaultdict(float)
    for key, t in red.op_s.items():
        phase, name = key.split(":", 1)
        name = name.lstrip("%")
        if phase == PHASE and name in names:
            out[names[name]] += t
    _last[:] = [red, dict(out)]
    return _last[1]
