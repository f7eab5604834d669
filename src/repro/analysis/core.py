"""Core AST machinery for the jit-hygiene static analyzer (DESIGN.md §15).

Pure stdlib (``ast`` + ``re``) — this package must run in CI and
pre-commit contexts with no jax installed, so nothing here imports the
runtime stack. Three layers:

  * **ModuleInfo** — one parsed source file: parent links, function
    qualnames, lexical scope tables for resolving a ``Name`` to the local
    function it references, ``# lint: host-ok(reason)`` suppressions, and
    the resolved jit regions.
  * **Region resolution** — a *jit region* is code that executes under
    tracing, where a hidden host sync is a per-access CXL round trip
    rather than a one-time cost. Regions are found syntactically:
    functions decorated ``@jax.jit`` / ``@functools.partial(jax.jit,
    ...)``; functions wrapped at a call site ``jax.jit(f)`` /
    ``jax.jit(functools.partial(f, kw=...))`` (partial-bound kwargs are
    closure constants → static); bodies passed to ``lax.scan`` /
    ``cond`` / ``while_loop`` / ``fori_loop`` / ``switch`` / ``vmap``;
    and Pallas kernels (first argument of ``pl.pallas_call``).
  * **Taint walk** — a lightweight traced-value dataflow over one
    region: non-static parameters seed the taint set; assignments
    propagate it; ``.shape``/``.dtype``-style metadata access drops it
    (static at trace time); structural tests (``x is None``,
    ``"key" in pytree``, ``isinstance``/``len``) are exempt.  The walk
    emits *events* (host cast, ``.item()``, numpy call on a traced
    value, ``print``, Python branch on a traced value) that rule R1
    turns into findings.  Local calls resolve one module deep
    (call-site argument taint maps onto callee parameters), so helpers
    like ``batch._window_step`` — jitted only through their callers —
    are still covered.

The walk is deliberately conservative in BOTH directions: unknown names
(imports, closures from non-region scopes) are untainted — a false
positive costs developer trust, a false negative is caught by the
runtime sync counters the tests already assert — and every finding is
suppressible inline with ``# lint: host-ok(reason)``.
"""
from __future__ import annotations

import ast
import dataclasses
import hashlib
import re
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

SUPPRESS_RE = re.compile(r"#\s*lint:\s*host-ok\(([^)#]*)\)")

JIT_NAMES = {"jax.jit", "jit", "jax.pjit", "pjit"}
PARTIAL_NAMES = {"functools.partial", "partial"}
PALLAS_NAMES = {"pl.pallas_call", "pallas_call", "pallas.pallas_call"}
TRACER_WRAPPERS = {"jax.vmap", "vmap", "jax.pmap", "pmap", "shard_map",
                   "jax.checkpoint", "jax.remat", "checkpoint", "remat",
                   "jax.grad", "grad", "jax.value_and_grad",
                   "value_and_grad"}
LAX_COMBINATORS = {"scan", "cond", "while_loop", "fori_loop", "switch",
                   "map", "associative_scan"}

# attribute access that yields trace-time-static metadata, not a traced value
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize", "nbytes",
                "aval", "weak_type"}

HOST_CASTS = {"int", "float", "bool", "complex"}
NUMPY_ROOTS = {"np", "numpy", "onp"}
# roots whose calls produce traced values inside a region
TRACED_ROOTS = {"jnp", "jax", "lax", "pl", "pltpu"}
DEVICE_GET_NAMES = {"jax.device_get", "device_get"}

_TAINT_DEPTH = 3    # local-call propagation depth (module-local only)


# ---------------------------------------------------------------------------
# Findings.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Finding:
    rule: str
    path: str          # posix-style path relative to the scan root's parent
    line: int
    col: int
    func: str          # enclosing function qualname, or "<module>"
    message: str
    suppressed: bool = False
    suppress_reason: str = ""

    def fingerprint(self) -> str:
        """Line-number-free identity used by the baseline ratchet: findings
        survive unrelated edits above them, and a *new* instance of an
        already-baselined (rule, func, message) in the same file still
        counts as new (baselines are multisets)."""
        key = f"{self.rule}|{self.path}|{self.func}|{self.message}"
        return hashlib.sha1(key.encode()).hexdigest()[:16]

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["fingerprint"] = self.fingerprint()
        return d

    def render(self) -> str:
        sup = f"  [host-ok: {self.suppress_reason}]" if self.suppressed else ""
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.func}] {self.message}{sup}")


# ---------------------------------------------------------------------------
# Small AST helpers.
# ---------------------------------------------------------------------------

def dotted(node: Optional[ast.AST]) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _literal(node: Optional[ast.AST]):
    if node is None:
        return None
    try:
        return ast.literal_eval(node)
    except (ValueError, TypeError, SyntaxError, MemoryError):
        return None


def is_lax_combinator(name: Optional[str]) -> bool:
    if not name:
        return False
    return any(name in (f"jax.lax.{c}", f"lax.{c}") for c in LAX_COMBINATORS)


FuncNode = (ast.FunctionDef, ast.AsyncFunctionDef)
FuncLike = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def param_names(node) -> List[str]:
    """Positional-capable parameter names in order (posonly + args)."""
    a = node.args
    return [p.arg for p in a.posonlyargs + a.args]


def all_param_names(node) -> List[str]:
    a = node.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


# ---------------------------------------------------------------------------
# Jit-decorator / call-site parsing.
# ---------------------------------------------------------------------------

def _parse_jit_kwargs(call: ast.Call) -> dict:
    meta = {"static_argnums": None, "static_argnames": None, "node": call}
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            v = _literal(kw.value)
            if isinstance(v, int):
                v = (v,)
            if isinstance(v, (tuple, list)):
                meta["static_argnums"] = tuple(x for x in v
                                               if isinstance(x, int))
        elif kw.arg == "static_argnames":
            v = _literal(kw.value)
            if isinstance(v, str):
                v = (v,)
            if isinstance(v, (tuple, list)):
                meta["static_argnames"] = tuple(x for x in v
                                                if isinstance(x, str))
    return meta


def jit_decorator_info(dec: ast.AST) -> Optional[dict]:
    """``@jax.jit`` / ``@jax.jit(...)`` / ``@functools.partial(jax.jit,
    static_arg...=...)`` → jit metadata, else None."""
    if dotted(dec) in JIT_NAMES:
        return {"static_argnums": None, "static_argnames": None, "node": dec}
    if isinstance(dec, ast.Call):
        fd = dotted(dec.func)
        if fd in JIT_NAMES:
            return _parse_jit_kwargs(dec)
        if fd in PARTIAL_NAMES and dec.args and \
                dotted(dec.args[0]) in JIT_NAMES:
            return _parse_jit_kwargs(dec)
    return None


def unwrap_partial(node: ast.AST) -> Tuple[Optional[ast.AST], Tuple[str, ...]]:
    """``functools.partial(f, kw=...)`` → (f, bound kwarg names); anything
    else passes through with no bound names. Partial-bound kwargs become
    closure constants of the traced callable → static."""
    if isinstance(node, ast.Call) and dotted(node.func) in PARTIAL_NAMES \
            and node.args:
        return node.args[0], tuple(kw.arg for kw in node.keywords
                                   if kw.arg is not None)
    return node, ()


def static_names_for(node, meta: dict,
                     extra: Sequence[str] = ()) -> frozenset:
    """Resolve static_argnums/static_argnames metadata against a concrete
    signature into a set of static parameter names."""
    names = set(extra)
    names.update(meta.get("static_argnames") or ())
    pos = param_names(node)
    for i in meta.get("static_argnums") or ():
        if 0 <= i < len(pos):
            names.add(pos[i])
    return frozenset(names)


# ---------------------------------------------------------------------------
# Regions.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Region:
    node: ast.AST                  # FunctionDef / AsyncFunctionDef / Lambda
    kind: str                      # "jit" (real jit boundary) | "traced"
    static_names: frozenset
    qualname: str
    reason: str                    # how it was discovered (for messages)
    jit_meta: Optional[dict] = None


class ModuleInfo:
    """One parsed source file plus everything the rules need from it."""

    def __init__(self, path, src: Optional[str] = None,
                 relpath: Optional[str] = None):
        self.path = Path(path)
        self.src = self.path.read_text() if src is None else src
        self.relpath = (relpath if relpath is not None
                        else self.path.name).replace("\\", "/")
        self.lines = self.src.splitlines()
        self.suppressions: Dict[int, str] = {}
        for i, line in enumerate(self.lines, 1):
            m = SUPPRESS_RE.search(line)
            if m:
                self.suppressions[i] = m.group(1).strip()
        self.tree = ast.parse(self.src, filename=str(self.path))
        self._index()
        self.regions = self._discover_regions()

    # -- indexing -----------------------------------------------------------

    def _index(self) -> None:
        self.parent: Dict[int, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parent[id(child)] = node
        self.qualnames: Dict[int, str] = {}
        self.functions: List[Tuple[ast.AST, str]] = []
        # scope tables: enclosing function of each function, and the
        # functions defined directly within each scope (None = module)
        self._scope_of: Dict[int, Optional[ast.AST]] = {}
        self._local_defs: Dict[Optional[int], Dict[str, ast.AST]] = {None: {}}

        def visit(node, scope, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, FuncNode):
                    qn = prefix + child.name
                    self.qualnames[id(child)] = qn
                    self.functions.append((child, qn))
                    self._scope_of[id(child)] = scope
                    key = id(scope) if scope is not None else None
                    self._local_defs.setdefault(key, {})[child.name] = child
                    self._local_defs.setdefault(id(child), {})
                    visit(child, child, qn + ".")
                elif isinstance(child, ast.Lambda):
                    qn = f"{prefix}<lambda:{child.lineno}>"
                    self.qualnames[id(child)] = qn
                    self._scope_of[id(child)] = scope
                    visit(child, scope, prefix)
                elif isinstance(child, ast.ClassDef):
                    visit(child, scope, prefix + child.name + ".")
                else:
                    visit(child, scope, prefix)

        visit(self.tree, None, "")

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        cur = self.parent.get(id(node))
        while cur is not None and not isinstance(cur, FuncLike):
            cur = self.parent.get(id(cur))
        return cur

    def func_qualname(self, node: ast.AST) -> str:
        fn = node if isinstance(node, FuncLike) else \
            self.enclosing_function(node)
        if fn is None:
            return "<module>"
        return self.qualnames.get(id(fn), "<?>")

    def get_function(self, qualname: str) -> Optional[ast.AST]:
        for node, qn in self.functions:
            if qn == qualname:
                return node
        return None

    def resolve_function(self, name: str,
                         at: ast.AST) -> Optional[ast.AST]:
        """Resolve a bare Name reference to a function defined in an
        enclosing lexical scope of this module (nearest scope wins)."""
        scope = self.enclosing_function(at)
        while True:
            key = id(scope) if scope is not None else None
            table = self._local_defs.get(key, {})
            if name in table:
                return table[name]
            if scope is None:
                return None
            scope = self._scope_of.get(id(scope))

    # -- suppression / finding construction ---------------------------------

    def suppression_at(self, node: ast.AST) -> Optional[str]:
        """Inline suppression covering ``node``: same line, the closing
        line of a multi-line construct, or a comment-only line directly
        above."""
        for ln in {getattr(node, "lineno", 0),
                   getattr(node, "end_lineno", 0) or 0,
                   max(getattr(node, "lineno", 1) - 1, 1)}:
            if ln in self.suppressions:
                if ln == getattr(node, "lineno", 0) - 1:
                    text = self.lines[ln - 1].strip()
                    if not text.startswith("#"):
                        continue          # code line above: not ours
                return self.suppressions[ln]
        return None

    def finding(self, rule: str, node: ast.AST, message: str) -> Finding:
        reason = self.suppression_at(node)
        return Finding(
            rule=rule, path=self.relpath,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            func=self.func_qualname(node), message=message,
            suppressed=reason is not None,
            suppress_reason=reason or "")

    # -- region discovery ---------------------------------------------------

    def _discover_regions(self) -> List[Region]:
        regions: Dict[int, Region] = {}

        def add(node, kind, static=frozenset(), reason="", jit_meta=None):
            if node is None or not isinstance(node, FuncLike):
                return
            cur = regions.get(id(node))
            if cur is None:
                regions[id(node)] = Region(
                    node=node, kind=kind, static_names=frozenset(static),
                    qualname=self.qualnames.get(id(node), "<?>"),
                    reason=reason, jit_meta=jit_meta)
            else:   # merge: a real jit boundary outranks a traced body
                cur.static_names = cur.static_names | frozenset(static)
                if kind == "jit" and cur.kind != "jit":
                    cur.kind, cur.reason, cur.jit_meta = kind, reason, jit_meta

        def resolve_callable(arg, at):
            """A function-valued argument: Lambda inline, or a Name
            resolved against local scopes. Returns (node, partial-bound
            static names)."""
            target, bound = unwrap_partial(arg)
            if isinstance(target, ast.Lambda):
                return target, bound
            if isinstance(target, ast.Name):
                return self.resolve_function(target.id, at), bound
            return None, ()

        for node, _qn in self.functions:
            for dec in node.decorator_list:
                meta = jit_decorator_info(dec)
                if meta is not None:
                    add(node, "jit", static_names_for(node, meta),
                        reason="jit decorator", jit_meta=meta)

        for call in ast.walk(self.tree):
            if not isinstance(call, ast.Call):
                continue
            d = dotted(call.func)
            if d in JIT_NAMES and call.args:
                fn, bound = resolve_callable(call.args[0], call)
                if fn is not None and isinstance(fn, FuncNode):
                    meta = _parse_jit_kwargs(call)
                    add(fn, "jit", static_names_for(fn, meta, extra=bound),
                        reason="jit call site", jit_meta=meta)
                elif isinstance(fn, ast.Lambda):
                    add(fn, "jit", frozenset(bound), reason="jit call site")
            elif d in PALLAS_NAMES and call.args:
                fn, bound = resolve_callable(call.args[0], call)
                add(fn, "traced", frozenset(bound), reason="pallas kernel")
            elif d in TRACER_WRAPPERS or is_lax_combinator(d):
                cands: List[ast.AST] = []
                for arg in call.args:
                    cands.extend(arg.elts if isinstance(
                        arg, (ast.List, ast.Tuple)) else [arg])
                for arg in cands:
                    fn, bound = resolve_callable(arg, call)
                    if fn is not None:
                        add(fn, "traced", frozenset(bound),
                            reason=d or "combinator body")

        # a root lexically nested inside another root is analyzed as part
        # of its ancestor's walk — keep only the outermost
        out = []
        for r in regions.values():
            anc = self.enclosing_function(r.node)
            nested = False
            while anc is not None:
                if id(anc) in regions:
                    nested = True
                    break
                anc = self.enclosing_function(anc)
            if not nested:
                out.append(r)
        out.sort(key=lambda r: r.node.lineno)
        return out


# ---------------------------------------------------------------------------
# Traced-value taint walk.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TaintEvent:
    node: ast.AST
    category: str       # cast | item | numpy | print | branch | host_fetch
    message: str


def _is_structural_test(node: ast.AST) -> bool:
    """Tests that are resolved at TRACE time even on traced operands:
    identity against None, constant-key pytree membership, isinstance /
    hasattr / len (static structure and shape), and boolean combinations
    thereof."""
    if isinstance(node, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return True
        if all(isinstance(op, (ast.In, ast.NotIn)) for op in node.ops) \
                and isinstance(node.left, ast.Constant):
            return True
        return False
    if isinstance(node, ast.Call):
        return dotted(node.func) in {"isinstance", "hasattr", "callable",
                                     "len", "getattr"}
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
        return _is_structural_test(node.operand)
    if isinstance(node, ast.BoolOp):
        return all(_is_structural_test(v) for v in node.values)
    return False


class _TaintWalk:
    """One region's traced-value walk (see module docstring)."""

    def __init__(self, module: ModuleInfo, region: Region):
        self.module = module
        self.region = region
        self.events: List[TaintEvent] = []
        self._callstack: List[int] = []
        self._memo: set = set()

    def run(self) -> List[TaintEvent]:
        node = self.region.node
        env = {}
        for p in all_param_names(node):
            env[p] = p not in self.region.static_names
        self._walk_func(node, env, depth=0)
        seen, out = set(), []
        for ev in self.events:
            key = (getattr(ev.node, "lineno", 0),
                   getattr(ev.node, "col_offset", 0), ev.category)
            if key not in seen:
                seen.add(key)
                out.append(ev)
        return out

    def _emit(self, node, category, message):
        self.events.append(TaintEvent(node, category, message))

    # -- function / statement walking ---------------------------------------

    def _walk_func(self, node, env, depth):
        if isinstance(node, ast.Lambda):
            self._expr(node.body, env, depth)
        else:
            self._block(node.body, env, depth)

    def _block(self, stmts, env, depth):
        for st in stmts:
            self._stmt(st, env, depth)

    def _bind(self, target, taint, env):
        if isinstance(target, ast.Name):
            env[target.id] = taint
        elif isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                self._bind(el, taint, env)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, taint, env)
        # attribute / subscript stores don't introduce local names

    def _stmt(self, st, env, depth):
        if isinstance(st, ast.Assign):
            t = self._expr(st.value, env, depth)
            for tgt in st.targets:
                self._bind(tgt, t, env)
        elif isinstance(st, ast.AnnAssign):
            if st.value is not None:
                self._bind(st.target, self._expr(st.value, env, depth), env)
        elif isinstance(st, ast.AugAssign):
            t = self._expr(st.value, env, depth)
            if isinstance(st.target, ast.Name):
                env[st.target.id] = env.get(st.target.id, False) or t
        elif isinstance(st, (ast.If, ast.While)):
            structural = _is_structural_test(st.test)
            t = self._expr(st.test, env, depth)
            if t and not structural:
                word = "if" if isinstance(st, ast.If) else "while"
                self._emit(st, "branch",
                           f"Python `{word}` on a traced value inside a jit "
                           f"region — forces a host sync per trace (use "
                           f"lax.cond/jnp.where or mark the operand static)")
            self._block(st.body, env, depth)
            self._block(st.orelse, env, depth)
        elif isinstance(st, ast.For):
            self._bind(st.target, self._expr(st.iter, env, depth), env)
            self._block(st.body, env, depth)
            self._block(st.orelse, env, depth)
        elif isinstance(st, FuncNode):
            # a def inside a jit region is itself traced when called —
            # walk it with every parameter tainted over the closure env
            env2 = dict(env)
            for p in all_param_names(st):
                env2[p] = True
            self._walk_func(st, env2, depth)
        elif isinstance(st, (ast.Return, ast.Expr)):
            if st.value is not None:
                self._expr(st.value, env, depth)
        elif isinstance(st, ast.With):
            for item in st.items:
                self._expr(item.context_expr, env, depth)
            self._block(st.body, env, depth)
        elif isinstance(st, ast.Try):
            self._block(st.body, env, depth)
            for h in st.handlers:
                self._block(h.body, env, depth)
            self._block(st.orelse, env, depth)
            self._block(st.finalbody, env, depth)
        else:
            for child in ast.iter_child_nodes(st):
                if isinstance(child, ast.expr):
                    self._expr(child, env, depth)
                elif isinstance(child, ast.stmt):
                    self._stmt(child, env, depth)

    # -- expression taint ---------------------------------------------------

    def _expr(self, node, env, depth) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return env.get(node.id, False)
        if isinstance(node, ast.Constant):
            return False
        if isinstance(node, ast.Attribute):
            base = self._expr(node.value, env, depth)
            return False if node.attr in STATIC_ATTRS else base
        if isinstance(node, ast.Subscript):
            tv = self._expr(node.value, env, depth)
            ts = self._expr(node.slice, env, depth)
            return tv or ts
        if isinstance(node, ast.Call):
            return self._call(node, env, depth)
        if isinstance(node, ast.Lambda):
            env2 = dict(env)
            for p in all_param_names(node):
                env2[p] = True
            self._expr(node.body, env2, depth)
            return False
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any([self._expr(e, env, depth) for e in node.elts])
        if isinstance(node, ast.Dict):
            parts = [self._expr(k, env, depth) for k in node.keys
                     if k is not None]
            parts += [self._expr(v, env, depth) for v in node.values]
            return any(parts)
        if isinstance(node, ast.IfExp):
            parts = [self._expr(node.test, env, depth),
                     self._expr(node.body, env, depth),
                     self._expr(node.orelse, env, depth)]
            return parts[1] or parts[2]
        # generic: BoolOp / BinOp / UnaryOp / Compare / comprehensions /
        # JoinedStr / Starred ... — any tainted child taints the result
        return any([self._expr(c, env, depth)
                    for c in ast.iter_child_nodes(node)
                    if isinstance(c, ast.expr)])

    def _call(self, node: ast.Call, env, depth) -> bool:
        d = dotted(node.func) or ""
        root = d.split(".")[0] if d else ""
        arg_taints = [self._expr(a, env, depth) for a in node.args]
        arg_taints += [self._expr(kw.value, env, depth)
                       for kw in node.keywords]
        any_tainted = any(arg_taints)
        recv_taint = False
        if isinstance(node.func, ast.Attribute):
            recv_taint = self._expr(node.func.value, env, depth)

        if isinstance(node.func, ast.Name) and node.func.id in HOST_CASTS \
                and any_tainted:
            self._emit(node, "cast",
                       f"`{node.func.id}()` on a traced value inside a jit "
                       f"region — a hidden device→host sync per call")
            return False
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item" \
                and recv_taint:
            self._emit(node, "item",
                       "`.item()` on a traced value inside a jit region — "
                       "a hidden device→host sync per call")
            return False
        if d in DEVICE_GET_NAMES or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready" and recv_taint):
            self._emit(node, "host_fetch",
                       f"`{d or 'block_until_ready'}` inside a jit region — "
                       f"device→host fetch in traced code")
            return False
        if root in NUMPY_ROOTS and any_tainted:
            self._emit(node, "numpy",
                       f"`{d}()` on a traced value inside a jit region — "
                       f"numpy concretizes the tracer (host sync per call); "
                       f"use the jnp equivalent")
            return False
        if isinstance(node.func, ast.Name) and node.func.id == "print":
            self._emit(node, "print",
                       "`print` inside a jit region — runs at trace time "
                       "only (or syncs under concretization); use "
                       "jax.debug.print")
            return False
        if root in TRACED_ROOTS:
            return True

        # one-module-deep local call propagation
        if isinstance(node.func, ast.Name) and depth < _TAINT_DEPTH:
            fn = self.module.resolve_function(node.func.id, node)
            if fn is not None and id(fn) not in self._callstack:
                env2 = {}
                pos = param_names(fn)
                has_star = any(isinstance(a, ast.Starred) for a in node.args)
                for p in all_param_names(fn):
                    env2[p] = has_star
                for i, a in enumerate(node.args):
                    if i < len(pos) and not has_star:
                        env2[pos[i]] = arg_taints[i]
                for kw, t in zip(node.keywords,
                                 arg_taints[len(node.args):]):
                    if kw.arg is not None:
                        env2[kw.arg] = t
                key = (id(fn), tuple(sorted(env2.items())))
                if key not in self._memo:
                    self._memo.add(key)
                    self._callstack.append(id(fn))
                    try:
                        self._walk_func(fn, env2, depth + 1)
                    finally:
                        self._callstack.pop()
                return any_tainted
        return any_tainted or recv_taint


def taint_events(module: ModuleInfo, region: Region) -> List[TaintEvent]:
    """The region's host-sync-relevant events (rule R1's input)."""
    return _TaintWalk(module, region).run()


def iter_calls(tree: ast.AST) -> Iterator[ast.Call]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            yield node
