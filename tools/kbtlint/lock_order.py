"""Pass 1: lock-order analysis (the PR 7 deadlock class, mechanical).

Builds the project lock-acquisition graph from ``with <lock>:`` /
``<lock>.acquire()`` sites and reports:

- **order cycles** — lock A held while acquiring B somewhere, and B
  held while acquiring A somewhere else (directly or through any
  resolved call chain);
- **leaf-lock violations** — acquiring ANY lock while holding a lock
  declared leaf (attr name in ``LEAF_LOCK_ATTRS``). The cache fence
  lock is leaf by design: the watchdog fences precisely when a wedged
  cycle may be deadlocked HOLDING ``cache.mutex``, so the fencing path
  joining any lock queue re-creates the PR 7 deadlock;
- **blocking work under cache.mutex** — device dispatch (calls
  resolving into the solver device modules), ``fetch``/sync calls, or
  blocking joins/waits while a lock whose attribute name is ``mutex``
  is held. One slow call under the cache mutex stalls every watch
  event, snapshot, and bind in the process;
- **self-deadlock** — re-acquiring a held non-reentrant ``Lock``.

Lock identity: ``module::Class.attr`` for ``self.X = threading.*()``
definitions, ``module::attr`` for module-level locks. Acquisition
sites resolve by (module, class, attr), then by project-unique attr
name; unresolvable sites are ignored (this is a lint — it
under-approximates rather than guessing). ``threading.Condition(X)``
aliases to X's lock; lockdebug's ``wrap_lock("name", threading.X())``
wrappers are transparent to discovery.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

from .callgraph import CallGraph, CallSite, get_callgraph
from .core import (
    Finding,
    FuncDef,
    Project,
    attr_chain,
    call_name,
    iter_functions,
    register_pass,
)

PASS_ID = "lock-order"

# Lock attributes that must be LEAVES: nothing may be acquired while
# one is held. _fence_lock is the PR 7 contract (see module docstring).
LEAF_LOCK_ATTRS = frozenset({"_fence_lock"})

# Calls that block (or dispatch to the device and then block) — never
# allowed while a `mutex` lock is held.
BLOCKING_CALL_NAMES = frozenset({
    "block_until_ready", "device_get", "device_put", "fetch", "result",
    "sleep", "wait", "wait_for_side_effects", "wait_for_bookkeeping",
    "bind_volumes", "wait_pod_volumes_bound", "call_with_deadline",
})

# Modules whose in-project callees count as device dispatch.
DEVICE_MODULE_SUFFIXES = (
    "solver/kernels.py", "solver/spmd.py", "solver/sharding.py",
    "solver/device_cache.py",
)

_LOCK_CTORS = {"Lock": "lock", "RLock": "rlock"}


@dataclass(frozen=True)
class LockDef:
    lock_id: str  # module::Class.attr | module::attr
    rel: str
    cls: Optional[str]
    attr: str
    kind: str  # lock | rlock | condition
    line: int


def _ctor_kind(expr: ast.AST) -> Optional[str]:
    """'lock'/'rlock' when ``expr`` contains a threading.Lock/RLock
    construction anywhere — including through the lockdebug
    ``wrap_lock(name)`` wrapper, whose default factory is a plain
    Lock (no visible threading ctor)."""
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name in _LOCK_CTORS:
                return _LOCK_CTORS[name]
            if name == "wrap_lock" and len(node.args) < 2 and not any(
                kw.arg == "lock" for kw in node.keywords
            ):
                return "lock"
    return None


def _condition_base(expr: ast.AST) -> Optional[ast.Call]:
    for node in ast.walk(expr):
        if isinstance(node, ast.Call) and call_name(node) == "Condition":
            return node
    return None


class LockIndex:
    def __init__(self, project: Project):
        self.defs: List[LockDef] = []
        self.by_exact: Dict[Tuple[str, Optional[str], str], LockDef] = {}
        self.by_attr: Dict[str, List[LockDef]] = {}
        # (rel, cls, attr) of a Condition -> the (rel, cls, attr) of
        # its base lock (resolved after discovery).
        self._cond_bases: Dict[
            Tuple[str, Optional[str], str], Tuple[str, Optional[str], str]
        ] = {}
        for pf in project.files:
            self._discover(pf)

    def _discover(self, pf) -> None:
        def scan(nodes, cls: Optional[str]):
            for node in nodes:
                if isinstance(node, ast.ClassDef):
                    scan(node.body, node.name)
                elif isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    scan(node.body, cls)
                elif isinstance(node, (ast.If, ast.Try, ast.With)):
                    for child in ast.iter_child_nodes(node):
                        if isinstance(child, ast.stmt):
                            scan([child], cls)
                elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                    self._maybe_add(pf.rel, cls, node.targets[0],
                                    node.value, node.lineno)

        scan(pf.tree.body, None)
        # Class bodies nest methods; a `self.X = Lock()` in __init__
        # defines a lock for the ENCLOSING class, which scan() tracked
        # via the cls parameter.

    def _maybe_add(self, rel, cls, target, value, lineno) -> None:
        chain = attr_chain(target)
        if chain is None:
            return
        if len(chain) == 2 and chain[0] == "self":
            attr = chain[1]
        elif len(chain) == 1 and cls is None:
            attr = chain[0]
        else:
            return
        kind = _ctor_kind(value)
        cond = _condition_base(value)
        if cond is not None:
            # Condition(base): alias to the base lock when one is
            # named; a bare Condition() owns a private RLock.
            if cond.args:
                base = attr_chain(cond.args[0])
                if base is not None:
                    if base[0] == "self" and len(base) == 2:
                        self._cond_bases[(rel, cls, attr)] = (
                            rel, cls, base[1]
                        )
                        return
                    if len(base) == 1:
                        self._cond_bases[(rel, cls, attr)] = (
                            rel, None, base[0]
                        )
                        return
            kind = "condition"
        if kind is None:
            return
        lock_id = (
            f"{rel}::{cls}.{attr}" if cls else f"{rel}::{attr}"
        )
        d = LockDef(lock_id=lock_id, rel=rel, cls=cls, attr=attr,
                    kind=kind, line=lineno)
        self.defs.append(d)
        self.by_exact[(rel, cls, attr)] = d
        self.by_attr.setdefault(attr, []).append(d)

    def resolve(self, rel: str, cls: Optional[str],
                expr: ast.AST) -> Optional[LockDef]:
        chain = attr_chain(expr)
        if chain is None:
            return None
        if chain[0] in ("self", "cls") and len(chain) == 2:
            attr = chain[1]
            key = (rel, cls, attr)
            key = self._cond_bases.get(key, key)
            exact = self.by_exact.get(key)
            if exact is not None:
                return exact
        elif len(chain) == 1:
            attr = chain[0]
            key = self._cond_bases.get((rel, None, attr), (rel, None, attr))
            exact = self.by_exact.get(key)
            if exact is not None:
                return exact
        else:
            attr = chain[-1]
        cands = self.by_attr.get(attr, [])
        if len(cands) == 1:
            return cands[0]
        return None


@dataclass
class Edge:
    held: LockDef
    acquired: LockDef
    rel: str
    line: int
    via: str  # "" for a direct nested acquisition, else the callee


def _analyze_function(
    fd: FuncDef, locks: LockIndex
) -> Tuple[Set[str], List[Tuple[LockDef, ast.AST, Tuple[LockDef, ...]]],
           List[Tuple[CallSite, Tuple[LockDef, ...]]]]:
    """Walk one function tracking the held-lock stack.

    Returns (direct_acquire_ids, acquisitions, calls_under_locks) where
    each acquisition/call carries the held stack at its site. Nested
    defs are walked inline (a closure defined under a lock is assumed
    callable under it — conservative; allowlist the exceptions)."""
    direct: Set[str] = set()
    acquisitions: List[Tuple[LockDef, ast.AST, Tuple[LockDef, ...]]] = []
    calls: List[Tuple[CallSite, Tuple[LockDef, ...]]] = []

    def walk_expr(expr: ast.AST, held: Tuple[LockDef, ...]) -> None:
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name is None:
                continue
            if name == "acquire":
                target = (
                    node.func.value
                    if isinstance(node.func, ast.Attribute) else None
                )
                lock = (
                    locks.resolve(fd.rel, fd.cls, target)
                    if target is not None else None
                )
                if lock is not None:
                    direct.add(lock.lock_id)
                    acquisitions.append((lock, node, held))
                    continue
            fn = node.func
            recv_self = bare = False
            if isinstance(fn, ast.Name):
                bare = True
            elif isinstance(fn, ast.Attribute):
                recv = fn.value
                recv_self = isinstance(recv, ast.Name) and recv.id in (
                    "self", "cls"
                )
            calls.append(
                (CallSite(name=name, recv_self=recv_self, bare=bare,
                          node=node), held)
            )

    def walk_stmts(stmts, held: Tuple[LockDef, ...]) -> None:
        for stmt in stmts:
            if isinstance(stmt, ast.With):
                inner = held
                for item in stmt.items:
                    walk_expr(item.context_expr, inner)
                    lock = locks.resolve(fd.rel, fd.cls, item.context_expr)
                    if lock is not None:
                        direct.add(lock.lock_id)
                        acquisitions.append((lock, stmt, inner))
                        inner = inner + (lock,)
                walk_stmts(stmt.body, inner)
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                walk_stmts(stmt.body, held)
            elif isinstance(stmt, ast.ClassDef):
                walk_stmts(stmt.body, held)
            elif isinstance(
                stmt, (ast.If, ast.While, ast.For, ast.AsyncFor)
            ):
                for expr in ast.iter_child_nodes(stmt):
                    if not isinstance(expr, ast.stmt):
                        walk_expr(expr, held)
                walk_stmts(getattr(stmt, "body", []), held)
                walk_stmts(getattr(stmt, "orelse", []), held)
            elif isinstance(stmt, ast.Try):
                walk_stmts(stmt.body, held)
                for handler in stmt.handlers:
                    walk_stmts(handler.body, held)
                walk_stmts(stmt.orelse, held)
                walk_stmts(stmt.finalbody, held)
            else:
                walk_expr(stmt, held)

    walk_stmts(fd.node.body, ())
    return direct, acquisitions, calls


def _is_blocking_join(site: CallSite) -> bool:
    """``X.join()`` / ``X.join(timeout)`` is a thread join;
    ``", ".join(parts)`` is string formatting. Disambiguate by arity
    and argument shape."""
    if site.name != "join":
        return False
    args = site.node.args
    if len(args) == 0:
        return True
    if len(args) == 1 and isinstance(args[0], (ast.Constant, ast.Name)):
        if isinstance(args[0], ast.Constant):
            return isinstance(args[0].value, (int, float))
    return bool(site.node.keywords)


@register_pass(PASS_ID)
def run(project: Project) -> List[Finding]:
    locks = LockIndex(project)
    graph = get_callgraph(project)
    findings: List[Finding] = []

    per_func: Dict[str, Tuple] = {}
    direct_acquires: Dict[str, Set[str]] = {}
    for pf in project.files:
        for fd in iter_functions(pf):
            analyzed = _analyze_function(fd, locks)
            per_func[fd.key] = (fd, analyzed)
            direct_acquires[fd.key] = analyzed[0]

    may_acquire = graph.transitive_marks(direct_acquires)
    by_id = {d.lock_id: d for d in locks.defs}

    edges: Dict[Tuple[str, str], Edge] = {}

    def add_edge(held: LockDef, acquired: LockDef, rel: str, line: int,
                 via: str) -> None:
        key = (held.lock_id, acquired.lock_id)
        if key not in edges:
            edges[key] = Edge(held=held, acquired=acquired, rel=rel,
                              line=line, via=via)

    for key, (fd, (direct, acquisitions, calls)) in per_func.items():
        entry = graph.entries.get(fd.key)
        for lock, node, held in acquisitions:
            for h in held:
                if h.lock_id == lock.lock_id:
                    if lock.kind == "lock":
                        findings.append(Finding(
                            PASS_ID, fd.rel, node.lineno,
                            f"self-deadlock: non-reentrant lock "
                            f"{lock.lock_id} re-acquired while already "
                            f"held in {fd.qualname}",
                        ))
                    continue
                add_edge(h, lock, fd.rel, node.lineno, via="")
        for site, held in calls:
            if not held or entry is None:
                continue
            callees = graph.resolve(entry, site)
            acquired_ids: Set[str] = set()
            for callee in callees:
                acquired_ids |= may_acquire.get(callee.fd.key, set())
            for lock_id in acquired_ids:
                lock = by_id[lock_id]
                for h in held:
                    if h.lock_id == lock_id:
                        continue  # reentrant/self handled at def site
                    add_edge(h, lock, fd.rel, site.node.lineno,
                             via=site.name)

    # Leaf-lock rule: nothing may be acquired while a leaf is held.
    for (held_id, acq_id), edge in sorted(edges.items()):
        if edge.held.attr in LEAF_LOCK_ATTRS:
            via = f" via {edge.via}()" if edge.via else ""
            findings.append(Finding(
                PASS_ID, edge.rel, edge.line,
                f"leaf-lock violation: {acq_id} acquired{via} while "
                f"holding leaf lock {held_id} (the fence path must "
                f"never join a lock queue — PR 7 deadlock class)",
            ))

    # Order cycles: SCCs of size >1 in the edge graph.
    findings.extend(_cycle_findings(edges))

    # Blocking/device work under a `mutex` lock.
    findings.extend(
        _mutex_blocking_findings(per_func, graph, may_acquire)
    )

    findings.sort(key=lambda f: (f.file, f.line, f.message))
    return findings


def _cycle_findings(edges: Dict[Tuple[str, str], Edge]) -> List[Finding]:
    adj: Dict[str, Set[str]] = {}
    for held_id, acq_id in edges:
        adj.setdefault(held_id, set()).add(acq_id)
        adj.setdefault(acq_id, set())

    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on_stack: Set[str] = set()
    stack: List[str] = []
    sccs: List[List[str]] = []
    counter = [0]

    def strongconnect(v: str) -> None:
        # Iterative Tarjan (the lock graph is tiny, but recursion
        # limits are not a failure mode a linter should have).
        work = [(v, iter(sorted(adj[v])))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on_stack.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(sorted(adj[w]))))
                    advanced = True
                    break
                if w in on_stack:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    scc.append(w)
                    if w == node:
                        break
                sccs.append(scc)

    for v in sorted(adj):
        if v not in index:
            strongconnect(v)

    findings: List[Finding] = []
    for scc in sccs:
        if len(scc) < 2:
            continue
        members = sorted(scc)
        cycle_name = " <-> ".join(members)
        for (held_id, acq_id), edge in sorted(edges.items()):
            if held_id in scc and acq_id in scc:
                via = f" via {edge.via}()" if edge.via else ""
                findings.append(Finding(
                    PASS_ID, edge.rel, edge.line,
                    f"lock-order cycle: {held_id} held while acquiring "
                    f"{acq_id}{via}; cycle: {cycle_name}",
                ))
    return findings


def _mutex_blocking_findings(per_func, graph: CallGraph,
                             may_acquire) -> List[Finding]:
    findings: List[Finding] = []
    for key, (fd, (direct, acquisitions, calls)) in per_func.items():
        entry = graph.entries.get(fd.key)
        for site, held in calls:
            if not any(h.attr == "mutex" for h in held):
                continue
            if site.name in BLOCKING_CALL_NAMES:
                findings.append(Finding(
                    PASS_ID, fd.rel, site.node.lineno,
                    f"blocking call {site.name}() while holding "
                    f"cache.mutex in {fd.qualname} (device sync / wait "
                    f"under the cache mutex stalls every watch event "
                    f"and bind in the process)",
                ))
                continue
            if _is_blocking_join(site):
                findings.append(Finding(
                    PASS_ID, fd.rel, site.node.lineno,
                    f"thread join() while holding cache.mutex in "
                    f"{fd.qualname}",
                ))
                continue
            if entry is None:
                continue
            for callee in graph.resolve(entry, site):
                if callee.fd.rel.replace("\\", "/").endswith(
                    DEVICE_MODULE_SUFFIXES
                ):
                    findings.append(Finding(
                        PASS_ID, fd.rel, site.node.lineno,
                        f"device dispatch {site.name}() "
                        f"({callee.fd.key}) while holding cache.mutex "
                        f"in {fd.qualname}",
                    ))
                    break
    return findings
