"""R5 — declared host-sync contracts (``@sync_contract``).

The static half of ``repro.common.contracts`` (the runtime half is
``verify_sync_counters`` in tests/test_obs.py and test_spans.py). For
every function annotated ``@sync_contract(syncs_per=..., fetches=N)``:

  * count the lexical device→host *fetch sites* in the body —
    ``jax.device_get``, ``.item()``, ``.block_until_ready()``,
    ``self._fetch(...)``, and ``np.asarray``/``np.array`` whose argument
    is device-sourced (host-side numpy on an already-fetched value is
    free and exempt);
  * a fetch site inside a host ``for``/``while`` loop is a finding
    regardless of count — one sync per *iteration* is how "one sync per
    step" quietly becomes O(n);
  * more than ``N`` loop-free sites is a finding per excess site.

Suppressed sites (``# lint: host-ok(reason)``) do not count against the
budget — that is the designed escape hatch for intentional host work.

Additionally, REQUIRED_CONTRACTS pins the repo's three load-bearing
contracts to their functions: deleting the ``@sync_contract`` annotation
from any of them is itself a finding, so the contract cannot be
silently removed to appease the fetch count.
"""
import ast
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis import core

RULE = "R5"
TITLE = "host-sync contract (@sync_contract) violation"

# path suffix -> {function qualname: required syncs_per}
REQUIRED_CONTRACTS: Dict[str, Dict[str, str]] = {
    "serve/engine.py": {"Engine.step": "step"},
    "fabric/replay.py": {"Fabric._fetch_view": "segment",
                         "Fabric._commit_epoch": "epoch",
                         "Fabric._commit_boundary": "boundary",
                         "Fabric._drain_deferred": "drain",
                         "Fabric.delivered_time": "call"},
}

_DEVICE_GET = {"jax.device_get", "device_get"}
_NP_FETCH = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
# attribute names that denote device-resident state in this repo
_DEVICE_ATTRS = {"pools", "counters", "state", "cache", "cfree", "gfree",
                 "pfree", "meta", "activity", "hand", "times", "stats"}


def contract_of(node) -> Optional[Tuple[str, int, ast.AST]]:
    """(syncs_per, fetches, decorator node) parsed from the source
    decorator, or None."""
    for dec in node.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        d = core.dotted(dec.func) or ""
        if d.split(".")[-1] != "sync_contract":
            continue
        per, fetches = None, 1
        if dec.args:
            v = core._literal(dec.args[0])
            per = v if isinstance(v, str) else None
        if len(dec.args) > 1:
            v = core._literal(dec.args[1])
            fetches = v if isinstance(v, int) else 1
        for kw in dec.keywords:
            v = core._literal(kw.value)
            if kw.arg == "syncs_per" and isinstance(v, str):
                per = v
            elif kw.arg == "fetches" and isinstance(v, int):
                fetches = v
        return per or "?", fetches, dec
    return None


def _name_flow(fn) -> Tuple[Set[str], Set[str]]:
    """(host_names, device_names): a bounded fixpoint over the simple
    assignments in ``fn``. Names bound from a fetch call (device_get /
    self._fetch) or from another host name are HOST; names bound from
    jnp/jax producers or device-attr chains are DEVICE. Host wins ties
    (the ``x = jax.device_get(x)`` rebinding pattern)."""
    host: Set[str] = set()
    device: Set[str] = set()
    assigns: List[Tuple[List[str], ast.AST]] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            names: List[str] = []
            for tgt in node.targets:
                names.extend(_flat_names(tgt))
            if names:
                assigns.append((names, node.value))
    for _ in range(5):
        changed = False
        for names, value in assigns:
            kind = _value_kind(value, host)
            pool = host if kind == "host" else (
                device if kind == "device" else None)
            if pool is not None and not set(names) <= pool:
                pool.update(names)
                changed = True
        if not changed:
            break
    return host, device - host


def _flat_names(tgt) -> List[str]:
    if isinstance(tgt, ast.Name):
        return [tgt.id]
    if isinstance(tgt, (ast.Tuple, ast.List)):
        out: List[str] = []
        for el in tgt.elts:
            out.extend(_flat_names(el))
        return out
    if isinstance(tgt, ast.Starred):
        return _flat_names(tgt.value)
    return []


def _value_kind(value, host: Set[str]) -> Optional[str]:
    if isinstance(value, ast.Call):
        d = core.dotted(value.func) or ""
        if d in _DEVICE_GET or _is_self_fetch(value):
            return "host"
        root = d.split(".")[0]
        if root in {"jnp", "jax", "lax"}:
            return "device"
        if root in core.NUMPY_ROOTS:
            return "host"
    if isinstance(value, ast.Name) and value.id in host:
        return "host"
    if _device_chain(value):
        return "device"
    if isinstance(value, (ast.Tuple, ast.List)) and value.elts and \
            all(isinstance(e, (ast.Attribute, ast.Name)) for e in value.elts):
        if any(_device_chain(e) for e in value.elts):
            return "device"
    return None


def _device_chain(node) -> bool:
    """Attribute/subscript chain touching a device-state attribute, e.g.
    ``self.pools.cfree.top``."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        if isinstance(node, ast.Attribute) and node.attr in _DEVICE_ATTRS:
            return True
        node = node.value
    return False


def _is_self_fetch(call: ast.Call) -> bool:
    return isinstance(call.func, ast.Attribute) and \
        call.func.attr in {"_fetch", "fetch"}


class _Site:
    def __init__(self, node: ast.AST, in_loop: bool, desc: str):
        self.node, self.in_loop, self.desc = node, in_loop, desc


def _fetch_sites(fn, host: Set[str], device: Set[str]) -> List[_Site]:
    sites: List[_Site] = []

    def walk(node, loop_depth):
        for child in ast.iter_child_nodes(node):
            depth = loop_depth + (1 if isinstance(
                child, (ast.For, ast.While)) else 0)
            if isinstance(child, ast.Call):
                desc = _fetch_desc(child, host, device)
                if desc:
                    sites.append(_Site(child, depth > 0, desc))
            walk(child, depth)

    walk(fn, 0)
    return sites


def _fetch_desc(call: ast.Call, host: Set[str],
                device: Set[str]) -> Optional[str]:
    d = core.dotted(call.func) or ""
    if d in _DEVICE_GET:
        return "jax.device_get"
    if _is_self_fetch(call):
        return f"self.{call.func.attr}"
    if isinstance(call.func, ast.Attribute) and \
            call.func.attr in {"item", "block_until_ready"}:
        v = call.func.value
        if _device_chain(v) or (isinstance(v, ast.Name)
                                and v.id in device) or \
                not (isinstance(v, ast.Name) and v.id in host):
            return f".{call.func.attr}()"
        return None
    if d in _NP_FETCH and call.args:
        a = call.args[0]
        if _device_chain(a):
            return f"{d} on device state"
        root = a
        while isinstance(root, (ast.Attribute, ast.Subscript)):
            root = root.value
        if isinstance(root, ast.Name) and root.id in device:
            return f"{d} on device value `{root.id}`"
    return None


def check(module: core.ModuleInfo) -> List[core.Finding]:
    out: List[core.Finding] = []

    for suffix, reqs in REQUIRED_CONTRACTS.items():
        if not module.relpath.endswith(suffix):
            continue
        for qn, per in reqs.items():
            node = module.get_function(qn)
            missing = node is None or contract_of(node) is None
            if missing:
                out.append(module.finding(
                    RULE, node if node is not None else module.tree,
                    f"required @sync_contract(syncs_per=\"{per}\") is "
                    f"missing on `{qn}` — the {per}-sync contract must stay "
                    f"machine-readable (see common/contracts.py)"))

    for node, qn in module.functions:
        parsed = contract_of(node)
        if parsed is None:
            continue
        per, fetches, _dec = parsed
        host, device = _name_flow(node)
        sites = _fetch_sites(node, host, device)
        budget_sites = []
        for s in sites:
            if module.suppression_at(s.node) is not None:
                # still reported (as suppressed) so the count is visible
                out.append(module.finding(
                    RULE, s.node,
                    f"{s.desc} in `{qn}` excluded from the "
                    f"{fetches}/{per} budget"))
                continue
            if s.in_loop:
                out.append(module.finding(
                    RULE, s.node,
                    f"{s.desc} inside a host loop in `{qn}` — syncs once "
                    f"per iteration, violating the declared one-fetch-per-"
                    f"{per} contract"))
            else:
                budget_sites.append(s)
        for s in budget_sites[fetches:]:
            out.append(module.finding(
                RULE, s.node,
                f"{s.desc} exceeds the declared budget of {fetches} "
                f"fetch site(s) per {per} in `{qn}` "
                f"({len(budget_sites)} found) — fuse fetches into one "
                f"device_get or raise the contract deliberately"))
    return out
