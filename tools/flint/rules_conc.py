"""Concurrency rules: guarded fields, lock order, check-then-act, shm.

The serving/tenancy plane is multi-threaded (MiniCluster scheduler +
lookup clients + replica workers) and multi-process (frontends over the
shm hot-cache arena); its bug history is lost-update counters,
check-then-act races and lock-order hazards found by eye. These rules
make that class of bug a CI failure, same shape as the tracing rules:
pure AST over the package source, never importing it.

- **LCK01 guarded-field discipline** — per class, each ``self._x``
  field's guard lock is INFERRED from where the writes happen: if a
  strict majority of the non-``__init__`` write sites hold one
  ``with self._lock``, that lock is the field's guard (same spirit as
  TRC01's taint rooting — the code's own dominant discipline is the
  spec). Any read or mutation outside the guard is then a violation.
  A module-scope variant covers module-global state under a module
  lock. Private helpers whose every in-class call site holds a lock
  analyze as if holding it (one-level call-site inheritance), so
  ``_absorb``-style extracted bodies don't false-positive.
- **LCK02 lock-order consistency** — a static lock-acquisition graph:
  nodes are ``Class.attr`` / ``module.name`` lock identities, edges
  from lexically nested ``with`` blocks plus calls made while holding
  a lock (callee acquisitions resolved through
  :mod:`tools.flint.callgraph`, transitively). A cycle is a potential
  deadlock, reported with a witness site per leg.
- **LCK03 check-then-act across a release boundary** — within one
  function, guarded state read under one acquisition of a lock and
  written under a SEPARATE acquisition of the same lock: whatever the
  first block learned is stale by the second. Calls into same-scope
  helpers that take the lock count as acquisitions (a scoped override
  that reads the old value under one acquisition and restores it under
  another is exactly this shape).
- **SHM01 attached-handle write discipline** — scopes that attach to
  the shm hot-cache arena (``hc_attach``) are read-side by contract;
  calling any symbol in the ``HOTCACHE_WRITER_SYMBOLS`` registry
  (``flink_tpu/native/__init__.py``, a literal tuple like
  ``NATIVE_SYMBOL_PREFIXES``) from such a scope is a violation.

Known limits (documented in NOTES_r24.md): guards are per-class
(inherited fields don't unify), ``with`` on a local alias of a lock is
invisible, LCK02's non-``self`` lock expressions resolve by attribute
name within the defining module only, and closures fold into their
enclosing function's lock context.
"""

from __future__ import annotations

import ast
from collections import Counter
from typing import Dict, FrozenSet, Iterator, List, Optional, Set, Tuple

from tools.flint.callgraph import PackageIndex, _module_name
from tools.flint.core import Checker, Project, SourceFile, Violation, register

PACKAGE = "flink_tpu"

#: constructors that make an attribute/global a lock identity
_LOCK_CTORS = ("Lock", "RLock", "Condition", "named_lock")

#: method names that mutate their receiver in place — a call through a
#: guarded field is a WRITE to it (thread-safe queue.put/get stay out)
_MUTATORS = frozenset({
    "append", "appendleft", "extend", "add", "update", "pop", "popleft",
    "popitem", "clear", "remove", "discard", "insert", "setdefault",
    "sort", "reverse",
})

#: attribute calls too generic for the duck-typed call-graph fallback:
#: resolving `.get`/`.put`/`.items` to every same-named method in the
#: package would weld builtin-container use into a spurious lock web
_GENERIC_METHODS = frozenset({
    "get", "put", "pop", "add", "append", "appendleft", "extend",
    "update", "clear", "remove", "discard", "insert", "items", "keys",
    "values", "setdefault", "popleft", "popitem", "start", "join",
    "run", "stop", "close", "wait", "notify", "notify_all", "acquire",
    "release", "locked", "send", "recv", "read", "write", "flush",
    "submit", "result", "set", "is_set", "empty", "full", "qsize",
    "copy", "sort", "index", "count", "encode", "decode", "split",
    "strip", "format", "match", "search", "group", "open", "load",
    "dump", "loads", "dumps", "exists", "mkdir", "unlink", "replace",
})

_NATIVE_INIT = "flink_tpu/native/__init__.py"


# ----------------------------------------------------------------- helpers

def _ctor_name(value: ast.AST) -> Optional[str]:
    if not isinstance(value, ast.Call):
        return None
    f = value.func
    name = f.id if isinstance(f, ast.Name) else (
        f.attr if isinstance(f, ast.Attribute) else None)
    return name if name in _LOCK_CTORS else None


def _self_attr(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


def _local_names(func: ast.AST) -> Tuple[Set[str], Set[str]]:
    """(local names, global-declared names) of a function, params
    included; nested defs fold in (conservative — a name local to a
    closure shadows the global for the whole extent)."""
    locals_: Set[str] = set()
    globals_: Set[str] = set()
    for n in ast.walk(func):
        if isinstance(n, ast.Global):
            globals_.update(n.names)
        elif isinstance(n, ast.Name) and \
                isinstance(n.ctx, (ast.Store, ast.Del)):
            locals_.add(n.id)
        elif isinstance(n, ast.arg):
            locals_.add(n.arg)
    return locals_ - globals_, globals_


def _literal_str_tuple(sf: SourceFile, name: str):
    """((values, lineno)) of a module-level literal string tuple, or
    (None, None) when absent/non-literal."""
    if sf.tree is None:
        return None, None
    for node in sf.tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    if not isinstance(node.value, ast.Tuple):
                        return None, node.lineno
                    vals = []
                    for e in node.value.elts:
                        if isinstance(e, ast.Constant) and \
                                isinstance(e.value, str):
                            vals.append(e.value)
                        else:
                            return None, node.lineno
                    return tuple(vals), node.lineno
    return None, None


# ------------------------------------------------------------------ models

class _ClassModel:
    __slots__ = ("sf", "module", "node", "name", "methods", "lock_attrs",
                 "scans", "inherited", "guards")

    def __init__(self, sf: SourceFile, module: str, node: ast.ClassDef):
        self.sf = sf
        self.module = module
        self.node = node
        self.name = node.name
        self.methods: Dict[str, ast.AST] = {}
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.methods[item.name] = item
        #: attr -> canonical attr (Condition(self.lock) aliases to lock)
        self.lock_attrs: Dict[str, str] = {}
        aliases: List[Tuple[str, str]] = []
        for n in ast.walk(node):
            if isinstance(n, ast.Assign):
                ctor = _ctor_name(n.value)
                if ctor is None:
                    continue
                for t in n.targets:
                    a = _self_attr(t)
                    if a is None:
                        continue
                    self.lock_attrs[a] = a
                    if ctor == "Condition" and n.value.args:
                        under = _self_attr(n.value.args[0])
                        if under is not None:
                            aliases.append((a, under))
        for cond_attr, under in aliases:
            if under in self.lock_attrs:
                self.lock_attrs[cond_attr] = under


class _ModuleModel:
    __slots__ = ("sf", "module", "classes", "functions", "lock_globals",
                 "globals", "scans", "inherited", "guards")

    def __init__(self, sf: SourceFile):
        self.sf = sf
        self.module = _module_name(sf.path)
        self.classes: List[_ClassModel] = []
        self.functions: Dict[str, ast.AST] = {}
        self.lock_globals: Set[str] = set()
        self.globals: Set[str] = set()
        for node in sf.tree.body:
            if isinstance(node, ast.ClassDef):
                self.classes.append(_ClassModel(sf, self.module, node))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = node
            elif isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                is_lock = _ctor_name(getattr(node, "value", None)) \
                    is not None
                for t in targets:
                    if isinstance(t, ast.Name):
                        (self.lock_globals if is_lock
                         else self.globals).add(t.id)


# ---------------------------------------------------------------- scanning

# a lock token: ("self", canonical_attr) | ("g", global_name)
#             | ("other", attr)   # some other object's lock attribute
_Token = Tuple[str, str]


class _Access:
    __slots__ = ("scope", "name", "kind", "node", "held", "regions")

    def __init__(self, scope: str, name: str, kind: str, node: ast.AST,
                 held: FrozenSet[_Token], regions: FrozenSet[int]):
        self.scope = scope        # "field" | "global"
        self.name = name
        self.kind = kind          # "read" | "write" | "aug"
        self.node = node
        self.held = held
        self.regions = regions


class _WithRegion:
    __slots__ = ("rid", "node", "tokens", "parent_held")

    def __init__(self, rid: int, node: ast.AST,
                 tokens: FrozenSet[_Token], parent_held: FrozenSet[_Token]):
        self.rid = rid
        self.node = node
        self.tokens = tokens
        self.parent_held = parent_held


class _FuncScan:
    __slots__ = ("accesses", "withs", "self_calls", "local_calls", "calls")

    def __init__(self):
        self.accesses: List[_Access] = []
        self.withs: List[_WithRegion] = []
        #: (method name, call node, held, regions)
        self.self_calls: List[Tuple[str, ast.Call, FrozenSet[_Token],
                                    FrozenSet[int]]] = []
        #: (module function name, call node, held, regions)
        self.local_calls: List[Tuple[str, ast.Call, FrozenSet[_Token],
                                     FrozenSet[int]]] = []
        #: every call with held context (LCK02 resolves these)
        self.calls: List[Tuple[ast.Call, FrozenSet[_Token]]] = []


class _Scanner:
    """One function's lexical scan: accesses with held-lock context,
    ``with``-lock regions, and call sites."""

    def __init__(self, cls: Optional[_ClassModel], mod: _ModuleModel):
        self.cls = cls
        self.mod = mod
        self.out = _FuncScan()
        self._rid = 0
        self.locals: Set[str] = set()
        self.func_globals: Set[str] = set()

    def scan(self, func: ast.AST) -> _FuncScan:
        self.locals, self.func_globals = _local_names(func)
        empty: FrozenSet = frozenset()
        for stmt in func.body:
            self._visit(stmt, empty, empty)
        return self.out

    # -- lock-expression recognition

    def _lock_token(self, expr: ast.AST) -> Optional[_Token]:
        a = _self_attr(expr)
        if a is not None:
            if self.cls is not None and a in self.cls.lock_attrs:
                return ("self", self.cls.lock_attrs[a])
            return None
        if isinstance(expr, ast.Name):
            if expr.id in self.mod.lock_globals and \
                    expr.id not in self.locals:
                return ("g", expr.id)
            return None
        if isinstance(expr, ast.Attribute):
            # another object's lock (co._lock): identity by attr name,
            # resolved to candidate classes by LCK02 only
            return ("other", expr.attr)
        return None

    # -- recording

    def _record(self, scope: str, name: str, kind: str, node: ast.AST,
                held: FrozenSet, regions: FrozenSet) -> None:
        if scope == "field" and self.cls is not None:
            if name in self.cls.lock_attrs or name in self.cls.methods:
                return
        self.out.accesses.append(
            _Access(scope, name, kind, node, held, regions))

    def _field_root(self, expr: ast.AST):
        """(scope, name, slice exprs) when the attribute/subscript
        chain roots at ``self.<name>`` or a module global."""
        slices: List[ast.AST] = []
        cur = expr
        while isinstance(cur, (ast.Attribute, ast.Subscript)):
            if isinstance(cur, ast.Subscript):
                slices.append(cur.slice)
                cur = cur.value
            else:
                if isinstance(cur.value, ast.Name) and \
                        cur.value.id == "self":
                    if self.cls is None:
                        return None
                    return ("field", cur.attr, slices)
                cur = cur.value
        if isinstance(cur, ast.Name) and self.cls is None and \
                cur.id in self.mod.globals and cur.id not in self.locals:
            return ("global", cur.id, slices)
        return None

    # -- traversal

    def _target(self, t: ast.AST, held: FrozenSet, regions: FrozenSet,
                aug: bool = False) -> None:
        if isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                self._target(e, held, regions, aug)
            return
        if isinstance(t, ast.Starred):
            self._target(t.value, held, regions, aug)
            return
        root = self._field_root(t)
        if root is not None:
            scope, name, slices = root
            self._record(scope, name, "aug" if aug else "write",
                         t, held, regions)
            for s in slices:
                self._visit(s, held, regions)
            return
        if isinstance(t, ast.Name):
            if self.cls is None and t.id in self.func_globals and \
                    t.id in self.mod.globals:
                self._record("global", t.id, "write", t, held, regions)
            return
        self._visit(t, held, regions)

    def _visit(self, node: ast.AST, held: FrozenSet,
               regions: FrozenSet) -> None:
        if isinstance(node, (ast.With, ast.AsyncWith)):
            tokens: List[_Token] = []
            for item in node.items:
                t = self._lock_token(item.context_expr)
                if t is not None and t not in tokens:
                    tokens.append(t)
                self._visit(item.context_expr, held, regions)
            if tokens:
                rid = self._rid
                self._rid += 1
                self.out.withs.append(_WithRegion(
                    rid, node, frozenset(tokens), held))
                held = held | frozenset(tokens)
                regions = regions | {rid}
            for stmt in node.body:
                self._visit(stmt, held, regions)
            return
        if isinstance(node, ast.Assign):
            for t in node.targets:
                self._target(t, held, regions)
            self._visit(node.value, held, regions)
            return
        if isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self._target(node.target, held, regions)
                self._visit(node.value, held, regions)
            return
        if isinstance(node, ast.AugAssign):
            self._target(node.target, held, regions, aug=True)
            self._visit(node.value, held, regions)
            return
        if isinstance(node, ast.Delete):
            for t in node.targets:
                self._target(t, held, regions)
            return
        if isinstance(node, ast.Call):
            self.out.calls.append((node, held))
            f = node.func
            handled = False
            if isinstance(f, ast.Attribute):
                sa = _self_attr(f)
                if sa is not None:
                    if self.cls is not None and sa in self.cls.methods:
                        self.out.self_calls.append(
                            (sa, node, held, regions))
                        handled = True
                    elif self.cls is not None and \
                            sa in self.cls.lock_attrs:
                        handled = True   # self._lock.acquire() et al.
                else:
                    root = self._field_root(f.value)
                    if root is not None:
                        scope, name, slices = root
                        kind = "write" if f.attr in _MUTATORS else "read"
                        self._record(scope, name, kind, f.value,
                                     held, regions)
                        for s in slices:
                            self._visit(s, held, regions)
                        handled = True
            elif isinstance(f, ast.Name):
                if f.id not in self.locals and \
                        f.id in self.mod.functions:
                    self.out.local_calls.append(
                        (f.id, node, held, regions))
                    handled = True
            if not handled:
                self._visit(f, held, regions)
            for a in node.args:
                self._visit(a, held, regions)
            for kw in node.keywords:
                self._visit(kw.value, held, regions)
            return
        if isinstance(node, ast.Attribute):
            sa = _self_attr(node)
            if sa is not None:
                if self.cls is not None:
                    self._record("field", sa, "read", node, held, regions)
                return
            self._visit(node.value, held, regions)
            return
        if isinstance(node, ast.Name):
            if self.cls is None and isinstance(node.ctx, ast.Load) and \
                    node.id in self.mod.globals and \
                    node.id not in self.locals:
                self._record("global", node.id, "read", node,
                             held, regions)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # closures fold into the enclosing extent (callgraph idiom)
            for stmt in node.body:
                self._visit(stmt, held, regions)
            return
        if isinstance(node, ast.Lambda):
            self._visit(node.body, held, regions)
            return
        for child in ast.iter_child_nodes(node):
            self._visit(child, held, regions)


# ---------------------------------------------------------------- analysis

def _inherited_held(scans: Dict[str, _FuncScan],
                    private_ok) -> Dict[str, FrozenSet[_Token]]:
    """Per function, the lock set every in-scope call site provably
    holds (intersection) — a private helper called only under the lock
    analyzes as holding it. Fixed point over in-scope call edges."""
    callsites: Dict[str, List[Tuple[str, FrozenSet[_Token]]]] = {}
    for caller, scan in scans.items():
        for name, _node, held, _r in scan.self_calls + scan.local_calls:
            if name in scans:
                callsites.setdefault(name, []).append((caller, held))
    inherited = {m: frozenset() for m in scans}
    for _ in range(5):
        changed = False
        for m in scans:
            if not private_ok(m):
                continue
            sites = callsites.get(m)
            if not sites:
                continue
            eff: Optional[FrozenSet[_Token]] = None
            for caller, held in sites:
                s = held | inherited.get(caller, frozenset())
                eff = s if eff is None else (eff & s)
            eff = eff or frozenset()
            if eff != inherited[m]:
                inherited[m] = eff
                changed = True
        if not changed:
            break
    return inherited


def _infer_guards(scans: Dict[str, _FuncScan],
                  inherited: Dict[str, FrozenSet[_Token]],
                  scope: str, token_kind: str,
                  skip_funcs: FrozenSet[str] = frozenset(),
                  ) -> Dict[str, Tuple[_Token, int, int]]:
    """name -> (guard token, guarded write count, total write count)
    by strict majority over non-exempt write sites."""
    writes: Dict[str, List[FrozenSet[_Token]]] = {}
    for m, scan in scans.items():
        if m in skip_funcs:
            continue
        inh = inherited.get(m, frozenset())
        for a in scan.accesses:
            if a.scope != scope or a.kind == "read":
                continue
            writes.setdefault(a.name, []).append(a.held | inh)
    guards: Dict[str, Tuple[_Token, int, int]] = {}
    for name, helds in writes.items():
        total = len(helds)
        counts = Counter(t for h in helds for t in h
                         if t[0] == token_kind)
        if not counts:
            continue
        top = counts.most_common(2)
        token, c = top[0]
        if len(top) > 1 and top[1][1] == c:
            continue   # two locks tie: ambiguous, no inference
        if c * 2 > total:
            guards[name] = (token, c, total)
    return guards


def _is_private_method(name: str) -> bool:
    return name.startswith("_") and not name.startswith("__")


class _Analysis:
    """Shared model of the package: per class and per module, the
    scans, the call-site-inherited holds and the inferred guards.
    Built once per project (LCK01/LCK02/LCK03 all read it)."""

    def __init__(self, project: Project):
        self.modules: List[_ModuleModel] = []
        for sf in project.package_files(PACKAGE):
            if sf.tree is None:
                continue
            mm = _ModuleModel(sf)
            self.modules.append(mm)
            for cm in mm.classes:
                cm.scans = {
                    name: _Scanner(cm, mm).scan(fn)
                    for name, fn in cm.methods.items()}
                cm.inherited = _inherited_held(cm.scans,
                                               _is_private_method)
                cm.guards = _infer_guards(
                    cm.scans, cm.inherited, "field", "self",
                    skip_funcs=frozenset({"__init__"}))
            mm.scans = {
                name: _Scanner(None, mm).scan(fn)
                for name, fn in mm.functions.items()}
            mm.inherited = _inherited_held(mm.scans, _is_private_method)
            mm.guards = _infer_guards(mm.scans, mm.inherited,
                                      "global", "g")


def _analysis(project: Project) -> _Analysis:
    a = getattr(project, "_conc_analysis", None)
    if a is None:
        a = _Analysis(project)
        project._conc_analysis = a
    return a


def _token_str(token: _Token) -> str:
    return f"self.{token[1]}" if token[0] == "self" else token[1]


# ------------------------------------------------------------------- LCK01

@register
class GuardedFieldDiscipline(Checker):
    rule = "LCK01"
    title = ("guarded-field discipline: a field whose writes hold one "
             "lock by strict majority must never be touched without it")

    def check(self, project: Project) -> Iterator[Violation]:
        ana = _analysis(project)
        for mm in ana.modules:
            for cm in mm.classes:
                yield from self._scope(
                    mm.sf, cm.scans, cm.inherited, cm.guards,
                    "field", exempt=frozenset({"__init__"}),
                    owner=cm.name)
            yield from self._scope(
                mm.sf, mm.scans, mm.inherited, mm.guards,
                "global", exempt=frozenset(), owner=mm.module)

    def _scope(self, sf, scans, inherited, guards, scope, exempt,
               owner) -> Iterator[Violation]:
        if not guards:
            return
        for m, scan in scans.items():
            if m in exempt:
                continue
            inh = inherited.get(m, frozenset())
            for a in scan.accesses:
                if a.scope != scope:
                    continue
                g = guards.get(a.name)
                if g is None:
                    continue
                token, c, total = g
                if token in (a.held | inh):
                    continue
                what = "self." + a.name if scope == "field" \
                    else "global " + a.name
                verb = {"read": "read", "write": "written",
                        "aug": "mutated in place"}[a.kind]
                tail = (" — a lost-update race" if a.kind == "aug"
                        else "")
                yield Violation(
                    rule=self.rule, path=sf.path,
                    line=getattr(a.node, "lineno", 1),
                    col=getattr(a.node, "col_offset", 0),
                    message=(
                        f"'{what}' is guarded by "
                        f"'{_token_str(token)}' ({c} of {total} write "
                        f"sites hold it) but is {verb} here in "
                        f"{owner}.{m} without the lock{tail}"))


# ------------------------------------------------------------------- LCK02

def _resolve_guarded(idx: PackageIndex, fi, call: ast.Call):
    """resolve_call with the duck-typed fallback reined in: generic
    container/threading method names never fan out package-wide."""
    f = call.func
    if isinstance(f, ast.Attribute):
        base = f.value
        is_self = isinstance(base, ast.Name) and base.id == "self"
        if not is_self and f.attr in _GENERIC_METHODS:
            return []
        if is_self and f.attr in _GENERIC_METHODS:
            # keep real self-dispatch, drop the duck-typed fallback
            hits = idx._family_methods(fi.cls, f.attr)
            return hits
    return idx.resolve_call(fi, call)


@register
class LockOrderConsistency(Checker):
    rule = "LCK02"
    title = ("lock-order consistency: the static acquisition graph "
             "(nested with blocks + calls under a held lock) must be "
             "acyclic")

    def check(self, project: Project) -> Iterator[Violation]:
        ana = _analysis(project)
        idx = PackageIndex(project.package_files(PACKAGE))

        # scan lookup by callgraph qualname
        by_qual: Dict[str, Tuple[_ModuleModel, Optional[_ClassModel],
                                 str, _FuncScan]] = {}
        for mm in ana.modules:
            for cm in mm.classes:
                for name, scan in cm.scans.items():
                    by_qual[f"{mm.module}:{cm.name}.{name}"] = \
                        (mm, cm, name, scan)
            for name, scan in mm.scans.items():
                by_qual[f"{mm.module}:{name}"] = (mm, None, name, scan)

        def nodes_of(token: _Token, mm: _ModuleModel,
                     cm: Optional[_ClassModel]) -> List[str]:
            if token[0] == "self" and cm is not None:
                return [f"{cm.name}.{token[1]}"]
            if token[0] == "g":
                return [f"{mm.module}.{token[1]}"]
            if token[0] == "other":
                # attr-name resolution within the defining module, the
                # caller's own class excluded (same-class instance
                # pairs are the runtime sentinel's job)
                return [f"{c.name}.{c.lock_attrs[token[1]]}"
                        for c in mm.classes
                        if c is not cm and token[1] in c.lock_attrs]
            return []

        # pass 1: direct acquisitions + lexical nesting edges
        edges: Dict[Tuple[str, str], Tuple[str, int, str]] = {}

        def add_edge(a: str, b: str, path: str, line: int,
                     text: str) -> None:
            if a != b and (a, b) not in edges:
                edges[(a, b)] = (path, line, text)

        direct: Dict[str, Set[str]] = {}
        for qual, (mm, cm, name, scan) in by_qual.items():
            inh = (cm.inherited if cm is not None
                   else mm.inherited).get(name, frozenset())
            acq: Set[str] = set()
            inh_nodes = [n for t in inh for n in nodes_of(t, mm, cm)]
            for w in scan.withs:
                toks = list(w.tokens)
                held_nodes = list(inh_nodes)
                for t in w.parent_held:
                    held_nodes.extend(nodes_of(t, mm, cm))
                for i, t in enumerate(toks):
                    t_nodes = nodes_of(t, mm, cm)
                    acq.update(t_nodes)
                    for tn in t_nodes:
                        for hn in held_nodes:
                            add_edge(hn, tn, mm.sf.path, w.node.lineno,
                                     f"'{tn}' acquired while holding "
                                     f"'{hn}'")
                        # multi-item with: earlier items lock first
                        for prev in toks[:i]:
                            for pn in nodes_of(prev, mm, cm):
                                add_edge(pn, tn, mm.sf.path,
                                         w.node.lineno,
                                         f"'{tn}' acquired after "
                                         f"'{pn}' in one with")
            direct[qual] = acq

        # pass 2: transitive acquisitions through the call graph
        acq_all = {q: set(s) for q, s in direct.items()}
        changed = True
        while changed:
            changed = False
            for qual, fi in idx.functions.items():
                rec = by_qual.get(qual)
                if rec is None:
                    continue
                cur = acq_all.setdefault(qual, set())
                for call, _held in rec[3].calls:
                    for callee in _resolve_guarded(idx, fi, call):
                        s = acq_all.get(callee.qualname)
                        if s and not s <= cur:
                            cur |= s
                            changed = True

        # pass 3: call-under-lock edges
        for qual, fi in idx.functions.items():
            rec = by_qual.get(qual)
            if rec is None:
                continue
            mm, cm, name, scan = rec
            inh = (cm.inherited if cm is not None
                   else mm.inherited).get(name, frozenset())
            for call, held in scan.calls:
                hs = held | inh
                if not hs:
                    continue
                held_nodes = [n for t in hs for n in nodes_of(t, mm, cm)]
                if not held_nodes:
                    continue
                for callee in _resolve_guarded(idx, fi, call):
                    for tn in acq_all.get(callee.qualname, ()):
                        for hn in held_nodes:
                            add_edge(hn, tn, mm.sf.path, call.lineno,
                                     f"call to {callee.qualname} "
                                     f"(acquires '{tn}') while "
                                     f"holding '{hn}'")

        # cycles: any edge whose reverse direction is reachable
        adj: Dict[str, Set[str]] = {}
        for a, b in edges:
            adj.setdefault(a, set()).add(b)
        reported: Set[FrozenSet[str]] = set()
        for (a, b) in sorted(edges):
            back = self._path(adj, b, a)
            if back is None:
                continue
            cyc = frozenset([a] + back)
            if cyc in reported:
                continue
            reported.add(cyc)
            legs = [(a, b)] + list(zip(back, back[1:]))
            parts = []
            for x, y in legs:
                path, line, text = edges[(x, y)]
                parts.append(f"{text} [{path}:{line}]")
            path0, line0, _ = edges[(a, b)]
            ring = " -> ".join([a, b] + back[1:])
            yield Violation(
                rule=self.rule, path=path0, line=line0, col=0,
                message=(f"potential deadlock: lock-order cycle "
                         f"{ring}; " + "; ".join(parts)))

    @staticmethod
    def _path(adj: Dict[str, Set[str]], a: str,
              b: str) -> Optional[List[str]]:
        seen = {a}
        frontier: List[List[str]] = [[a]]
        while frontier:
            p = frontier.pop()
            if p[-1] == b:
                return p
            for nxt in sorted(adj.get(p[-1], ())):
                if nxt not in seen:
                    seen.add(nxt)
                    frontier.append(p + [nxt])
        return None


# ------------------------------------------------------------------- LCK03

class _Region:
    __slots__ = ("line", "reads", "writes", "desc")

    def __init__(self, line: int, reads: Set[str], writes: Set[str],
                 desc: str):
        self.line = line
        self.reads = reads
        self.writes = writes
        self.desc = desc


@register
class CheckThenActAcrossRelease(Checker):
    rule = "LCK03"
    title = ("check-then-act: guarded state read under one lock "
             "acquisition and acted on under a separate one — the "
             "check is stale across the release")

    def check(self, project: Project) -> Iterator[Violation]:
        ana = _analysis(project)
        for mm in ana.modules:
            for cm in mm.classes:
                yield from self._scope(mm.sf, cm.scans, cm.inherited,
                                       cm.guards, cm.name)
            yield from self._scope(mm.sf, mm.scans, mm.inherited,
                                   mm.guards, mm.module)

    def _scope(self, sf, scans, inherited, guards,
               owner) -> Iterator[Violation]:
        if not guards:
            return
        #: lock token -> fields it guards
        by_lock: Dict[_Token, Set[str]] = {}
        for name, (token, _c, _t) in guards.items():
            by_lock.setdefault(token, set()).add(name)

        #: per function: fields read/written while lexically holding L
        def under(scan: _FuncScan, token: _Token, fields: Set[str],
                  kind_read: bool) -> Set[str]:
            out = set()
            for a in scan.accesses:
                if a.name in fields and token in a.held and \
                        (a.kind == "read") == kind_read:
                    out.add(a.name)
            return out

        for fname, scan in scans.items():
            if fname == "__init__":
                continue
            inh = inherited.get(fname, frozenset())
            for token, fields in by_lock.items():
                if token in inh:
                    continue   # whole function runs under the lock
                regions: List[_Region] = []
                # real with-regions (outermost for this lock only)
                for w in scan.withs:
                    if token not in w.tokens or token in w.parent_held:
                        continue
                    reads, writes = set(), set()
                    for a in scan.accesses:
                        if a.name not in fields or \
                                w.rid not in a.regions:
                            continue
                        (reads if a.kind == "read" else writes).add(
                            a.name)
                    regions.append(_Region(
                        w.node.lineno, reads, writes,
                        f"the with block at line {w.node.lineno}"))
                # virtual regions: same-scope calls that take the lock
                for name, node, held, _r in (scan.self_calls +
                                             scan.local_calls):
                    if token in (held | inh):
                        continue
                    callee = scans.get(name)
                    if callee is None:
                        continue
                    reads = under(callee, token, fields, True)
                    writes = under(callee, token, fields, False)
                    if reads or writes:
                        regions.append(_Region(
                            node.lineno, reads, writes,
                            f"the call to {name}() at line "
                            f"{node.lineno}"))
                regions.sort(key=lambda r: r.line)
                seen: Set[Tuple[str, int]] = set()
                for i, r1 in enumerate(regions):
                    for r2 in regions[i + 1:]:
                        if r2.line == r1.line:
                            continue
                        # a second region that RE-READS the field under
                        # its own hold before writing has re-validated
                        # the check (compare-and-restore, drain loops):
                        # not check-then-act
                        for f in sorted((r1.reads & r2.writes)
                                        - r2.reads):
                            key = (f, r2.line)
                            if key in seen:
                                continue
                            seen.add(key)
                            yield Violation(
                                rule=self.rule, path=sf.path,
                                line=r2.line, col=0,
                                message=(
                                    f"check-then-act across a release "
                                    f"boundary in {owner}.{fname}: "
                                    f"'{f}' (guarded by "
                                    f"'{_token_str(token)}') is read "
                                    f"by {r1.desc} but acted on by "
                                    f"{r2.desc} under a separate "
                                    f"acquisition — the lock was "
                                    f"released in between"))


# ------------------------------------------------------------------- SHM01

@register
class AttachedHandleWriteDiscipline(Checker):
    rule = "SHM01"
    title = ("shm write discipline: hotcache writer symbols must never "
             "be called from an hc_attach-rooted (frontend) scope")

    def check(self, project: Project) -> Iterator[Violation]:
        sf = project.get(_NATIVE_INIT)
        if sf is None or sf.tree is None:
            return
        writers, line = _literal_str_tuple(sf, "HOTCACHE_WRITER_SYMBOLS")
        if writers is None:
            yield Violation(
                rule=self.rule, path=_NATIVE_INIT, line=line or 1,
                col=0,
                message=("HOTCACHE_WRITER_SYMBOLS literal string tuple "
                         "is missing from flink_tpu/native/__init__.py "
                         "— SHM01 derives the attach-side deny list "
                         "from it"))
            return
        prefixes, _ = _literal_str_tuple(sf, "NATIVE_SYMBOL_PREFIXES")
        if prefixes:
            for w in writers:
                if not any(w.startswith(p) for p in prefixes):
                    yield Violation(
                        rule=self.rule, path=_NATIVE_INIT, line=line,
                        col=0,
                        message=(f"writer symbol '{w}' matches no "
                                 f"NATIVE_SYMBOL_PREFIXES prefix — "
                                 f"the registry is drifting"))
        writer_set = set(writers)

        def called_symbol(call: ast.Call) -> Optional[str]:
            f = call.func
            if isinstance(f, ast.Attribute):
                return f.attr
            if isinstance(f, ast.Name):
                return f.id
            return None

        for sf2 in project.package_files(PACKAGE):
            if sf2.tree is None:
                continue
            scopes: List[Tuple[str, ast.AST]] = []
            for node in sf2.tree.body:
                if isinstance(node, ast.ClassDef):
                    scopes.append((node.name, node))
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    scopes.append((node.name, node))
            for scope_name, scope_node in scopes:
                calls = [n for n in ast.walk(scope_node)
                         if isinstance(n, ast.Call)]
                attach = [c for c in calls
                          if called_symbol(c) == "hc_attach"]
                if not attach:
                    continue
                for c in calls:
                    s = called_symbol(c)
                    if s in writer_set:
                        yield Violation(
                            rule=self.rule, path=sf2.path,
                            line=c.lineno, col=c.col_offset,
                            message=(
                                f"writer symbol '{s}' called in "
                                f"'{scope_name}', an attach-side scope "
                                f"(hc_attach at line "
                                f"{attach[0].lineno}) — attached shm "
                                f"handles are read-only; writes belong "
                                f"to the owner-side NativeHotRowCache"))
