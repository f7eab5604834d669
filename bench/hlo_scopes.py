"""The program's device scopes, read from the optimized HLO of the programs
this process has compiled, for the per-layer metrics that charge device op
time to a scope.

A scope is a ``jax.named_scope`` that the program declares in
``repro.obs.spans.SCOPES``; it shows as one component of an instruction's
``op_name`` metadata. An instruction takes the innermost declared scope in
its own ``op_name``, else the scope of the instruction that calls its
computation (a ``while`` body or condition, a ``conditional`` branch): the
profiler's "XLA Ops" line nests a loop's body inside its ``while`` op, so a
copy XLA put into a loop belongs to that loop's phase. What no scope covers
is ``unscoped``.

The trace reduction keys each op's self time by its instruction name
(``Reduced.op_s``, ``<phase>:<name>``); ``scope_seconds`` joins those keys
with the instruction names of the live executables whose module ran in the
window. A program that declares no scopes gives an empty result, and so do
programs whose instruction names the trace does not show.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

UNSCOPED = "unscoped"
AMBIGUOUS = "ambiguous"   # one name, two scopes, in two programs that ran

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:body|condition|true_computation|false_computation"
                     r"|to_apply|calls)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def declared_scopes() -> Tuple[str, ...]:
    """The scope names the program declares, or () without such a table."""
    try:
        from repro.obs import spans
    except ImportError:
        return ()
    return tuple(getattr(spans, "SCOPES", ()))


def instruction_scopes(hlo_text: str, scopes: Iterable[str]
                       ) -> Dict[str, Optional[str]]:
    """Instruction name -> its scope (None: no declared scope) for one HLO
    module's text, with the inheritance the module docstring describes."""
    scopes = frozenset(scopes)
    comps: Dict[str, List[Tuple[str, Optional[str], List[str]]]] = {}
    entry = None
    cur: Optional[str] = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and cur is not None:
            own = None
            op = _OP_NAME.search(line)
            if op:
                for part in reversed(op.group(1).split("/")):
                    if part in scopes:
                        own = part
                        break
            called = _CALLED.findall(line)
            for b in _BRANCHES.findall(line):
                called += [c.strip().lstrip("%") for c in b.split(",")]
            comps[cur].append((m.group(1), own, called))
            continue
        h = _HEADER.match(line)
        if h and "=" not in line.split("{", 1)[0].split("(", 1)[0]:
            cur = h.group(1)
            comps[cur] = []
            if line.startswith("ENTRY"):
                entry = cur
    out: Dict[str, Optional[str]] = {}
    if entry is None:
        return out
    seen = set()
    stack: List[Tuple[str, Optional[str]]] = [(entry, None)]
    while stack:
        comp, inherited = stack.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, own, called in comps[comp]:
            sc = own or inherited
            out[name] = sc
            stack += [(c, sc) for c in called]
    return out


def live_scopes(module_names: Iterable[str], scopes: Iterable[str]
                ) -> Dict[str, str]:
    """Instruction name -> scope over the live executables whose HLO module
    is named in ``module_names`` and carries a declared scope; a name two of
    them give different scopes is ``ambiguous``."""
    import jax
    wanted = set(module_names)
    scopes = tuple(scopes)
    merged: Dict[str, str] = {}
    for exe in jax.devices()[0].client.live_executables():
        for mod in exe.hlo_modules():
            if mod.name not in wanted:
                continue
            own = instruction_scopes(mod.to_string(), scopes)
            if not any(own.values()):
                continue
            for name, sc in own.items():
                sc = sc or UNSCOPED
                if merged.setdefault(name, sc) != sc:
                    merged[name] = AMBIGUOUS
    return merged


_last: list = [None, None]     # (Reduced, its result): four readers ask


def scope_seconds(red) -> Dict[str, float]:
    """Device op self seconds of the traced window by scope (and
    ``unscoped``), over the ops of the modules that declare some scope;
    empty where the program declares none or none of its ops carry one.
    An op of another module that shares an instruction name with a scoped
    one is counted with it: ``Reduced.op_s`` does not key ops by module."""
    if _last[0] is red:
        return _last[1]
    scopes = declared_scopes()
    names = live_scopes(red.module_s, scopes) if scopes else {}
    out: Dict[str, float] = defaultdict(float)
    for key, t in red.op_s.items():
        name = key.split(":", 1)[1].lstrip("%")
        if name in names:
            out[names[name]] += t
    _last[:] = [red, dict(out)]
    return _last[1]
