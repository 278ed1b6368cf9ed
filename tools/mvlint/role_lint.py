"""thread-role lint (pass 9): every thread declares a role; no
DISPATCH/LIVENESS thread can *reach* a blocking primitive.

The dispatch-thread-starvation class bit PRs 6, 9 and 12; the lexical
send-discipline pass (6) bans the one call shape that caused them,
but a blocking call two frames deep sails through lexical matching.
This pass is the interprocedural version, built on
:mod:`tools.mvlint.callgraph`:

* **Spawn discipline** — raw ``threading.Thread(...)`` inside
  ``multiverso_tpu`` is banned (``runtime/thread_roles.py`` itself
  and tests are exempt); threads start through
  ``thread_roles.spawn(ROLE, target=...)``.
* **Role resolution** — the role argument must be a literal role
  constant, or ``self.ROLE``: then the *binding* decides, and the
  spawn expands over the enclosing class plus every package subclass
  with a resolvable literal ``ROLE`` attribute (``Actor.start``
  spawns ``Communicator._main`` as DISPATCH but ``Worker._main`` as
  ACTOR from the same line).
* **Registry cross-check, BOTH directions** — the spawn-derived
  (entry -> role) table must equal the literal ``THREAD_ROLES`` in
  ``runtime/thread_roles.py``, and that registry must equal the
  ``docs/THREADS.md`` inventory table (the WIRE_FORMAT.md registry
  precedent: code, registry and doc can never drift apart silently).
* **Blocking reachability** — from every DISPATCH/LIVENESS/EVENTLOOP
  entry the transitive call closure must not reach a blocking
  primitive: blocking ``net.send``, socket ``recv``/``recv_into``/
  ``accept``/``connect``/``create_connection``, frame reads
  (``_read_exact``/``_recv_into_exact``), or ``join``/``wait``/
  ``wait_for``/queue-``get`` without a timeout. ``net.recv`` (the
  communicator's inbox drain) and ``mailbox.pop`` are the *idle
  states* of those loops, not blocking bugs, and are excluded —
  as is ``selector.select(timeout)``, the event loop's one sanctioned
  park (its entry frame, which the watchdog reads as idle). Handler
  calls the graph cannot resolve statically (the loop's generic
  ``job()`` closures) are the runtime watchdog's territory
  (``-debug_locks`` + ``-role_block_budget_ms`` backstops dynamically
  whatever this walk cannot see). Findings are deduplicated per call
  site and report the full call path — one pragma at the site covers
  every root that reaches it.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .callgraph import CallGraph, FuncInfo
from .framework import LintPass, ModuleInfo, Violation
from .lock_lint import _has_timeout

ROLE_NAMES = ("DISPATCH", "ACTOR", "LIVENESS", "WRITER", "BACKGROUND",
              "EVENTLOOP")
CRITICAL_ROLES = ("DISPATCH", "LIVENESS", "EVENTLOOP")
NET_NAMES = {"net", "_net"}

PKG_PREFIX = "multiverso_tpu/"
ROLES_REL = "multiverso_tpu/runtime/thread_roles.py"
DOC_REL = "docs/THREADS.md"

#: docs/THREADS.md inventory rows: | `entry` | ROLE | budget |
DOC_ROW_RE = re.compile(r"^\|\s*`([^`]+)`\s*\|\s*([A-Z]+)\s*\|")


def _strip_pkg(rel: str) -> str:
    return rel[len(PKG_PREFIX):] if rel.startswith(PKG_PREFIX) else rel


def _chain_tail(node: ast.AST) -> Optional[str]:
    while isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _func_name(call: ast.Call) -> Optional[str]:
    fn = call.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def classify_blocking(call: ast.Call) -> Optional[str]:
    """A short description when ``call`` is a blocking primitive,
    else None. Mirrors the lock-discipline classification plus the
    transport shapes the send-discipline pass bans."""
    fn = call.func
    if isinstance(fn, ast.Name):
        if fn.id in ("_read_exact", "_recv_into_exact"):
            return f"{fn.id}() frame read"
        if fn.id == "create_connection":
            return "create_connection()"
        return None
    if not isinstance(fn, ast.Attribute):
        return None
    method = fn.attr
    tail = _chain_tail(fn.value)
    if method == "send" and tail in NET_NAMES:
        return "blocking net.send()"
    if method in ("recv", "recv_into") and tail not in NET_NAMES:
        # net.recv is the communicator's inbox drain (its idle
        # state); any other receive is a socket-level block.
        return f"socket .{method}()"
    if method == "accept":
        return ".accept()"
    if method in ("connect", "create_connection"):
        return f".{method}()"
    if method in ("join", "wait", "wait_for") \
            and not _has_timeout(call, method):
        return f".{method}() without timeout"
    if method == "get" and not call.args \
            and not _has_timeout(call, method):
        # Zero-positional-arg .get() is the queue/future shape;
        # dict.get(key[, default]) always passes the key positionally
        # and never blocks. A class-name receiver (FlagRegister.get())
        # is a classmethod accessor, never a queue pop.
        recv = fn.value
        if isinstance(recv, ast.Name) and recv.id[:1].isupper():
            return None
        return ".get() without timeout"
    return None


def load_thread_roles(root: Path) -> Tuple[Dict[str, str], int]:
    """The literal THREAD_ROLES registry (parsed, never imported)."""
    path = root / ROLES_REL
    try:
        tree = ast.parse(path.read_text(encoding="utf-8"))
    except (OSError, SyntaxError):
        return {}, 1
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "THREAD_ROLES"
                for t in node.targets) \
                and isinstance(node.value, ast.Dict):
            table: Dict[str, str] = {}
            for k, v in zip(node.value.keys, node.value.values):
                if isinstance(k, ast.Constant) and \
                        isinstance(k.value, str) and \
                        isinstance(v, ast.Name):
                    table[k.value] = v.id
            return table, node.lineno
    return {}, 1


def load_doc_roles(root: Path) -> Dict[str, Tuple[str, int]]:
    """docs/THREADS.md inventory: entry -> (role, line)."""
    path = root / DOC_REL
    out: Dict[str, Tuple[str, int]] = {}
    try:
        lines = path.read_text(encoding="utf-8").splitlines()
    except OSError:
        return out
    for i, line in enumerate(lines, 1):
        m = DOC_ROW_RE.match(line.strip())
        if m and m.group(2) in ROLE_NAMES:
            out[m.group(1)] = (m.group(2), i)
    return out


class _Spawn:
    """One resolved spawn site -> (entry key, role) bindings."""

    __slots__ = ("node", "rel", "entries", "problems")

    def __init__(self, node: ast.Call, rel: str):
        self.node = node
        self.rel = rel
        #: entry key (package-relative) -> role
        self.entries: Dict[str, str] = {}
        #: (line, col, message) for unresolvable role/target
        self.problems: List[Tuple[int, int, str]] = []


class ThreadRoleLint(LintPass):
    name = "thread-role"

    def __init__(self, root: Path, graph: CallGraph):
        self.root = root
        self.graph = graph
        self.registry, self.registry_line = load_thread_roles(root)
        self.doc_roles = load_doc_roles(root)
        self.doc_exists = (root / DOC_REL).is_file()
        # Package-wide discovery once: spawn table + reachability
        # findings grouped by the module each site lives in, so the
        # site's own pragmas can suppress (the framework only applies
        # a module's pragmas to findings in that module).
        self._by_module: Dict[str, List[Violation]] = {}
        self._package_entries: Dict[str, Tuple[str, str, int]] = {}
        self._discover_package()
        self._funcs_by_rel: Dict[str, List[FuncInfo]] = {}

    # -- package discovery -------------------------------------------
    def _discover_package(self) -> None:
        spawns: List[_Spawn] = []
        for rel, tree in sorted(self.graph.module_trees.items()):
            if not rel.startswith(PKG_PREFIX):
                continue
            spawns.extend(self._scan_module(self.graph, rel, tree))
        for spawn in spawns:
            for line, col, msg in spawn.problems:
                self._add(Violation(spawn.rel, line, col, self.name,
                                    msg))
            for entry, role in spawn.entries.items():
                known = self._package_entries.get(entry)
                if known and known[0] != role:
                    self._add(Violation(
                        spawn.rel, spawn.node.lineno,
                        spawn.node.col_offset, self.name,
                        f"thread entry {entry!r} spawned as {role} "
                        f"here but as {known[0]} at {known[1]}:"
                        f"{known[2]} — one entry point, one role"))
                    continue
                self._package_entries[entry] = (role, spawn.rel,
                                                spawn.node.lineno)
                declared = self.registry.get(entry)
                if declared is None:
                    self._add(Violation(
                        spawn.rel, spawn.node.lineno,
                        spawn.node.col_offset, self.name,
                        f"thread entry {entry!r} (role {role}) is "
                        f"not declared in THREAD_ROLES "
                        f"(runtime/thread_roles.py) — the registry "
                        f"is the canonical inventory"))
                elif declared != role:
                    self._add(Violation(
                        spawn.rel, spawn.node.lineno,
                        spawn.node.col_offset, self.name,
                        f"thread entry {entry!r} spawns with role "
                        f"{role} but THREAD_ROLES declares "
                        f"{declared}"))
        self._reach_check(self.graph, spawns, add=self._add)

    def _add(self, v: Violation) -> None:
        self._by_module.setdefault(v.path, []).append(v)

    # -- per-module scan ---------------------------------------------
    def _scan_module(self, graph: CallGraph, rel: str,
                     tree: ast.AST) -> List[_Spawn]:
        """Spawn sites (and raw-Thread violations) in one module."""
        spawns: List[_Spawn] = []
        exempt_raw = rel.endswith("runtime/thread_roles.py")
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _func_name(node)
            has_target = any(kw.arg == "target"
                             for kw in node.keywords)
            if name == "Thread" and not exempt_raw:
                self._add(Violation(
                    rel, node.lineno, node.col_offset, self.name,
                    "raw threading.Thread() in the package — spawn "
                    "through thread_roles.spawn(ROLE, target=...) so "
                    "the thread carries a declared role (watchdog + "
                    "reachability gate, docs/THREADS.md)"))
                continue
            if name != "spawn" or not has_target:
                continue
            spawns.append(self._resolve_spawn(graph, rel, node))
        return spawns

    def _resolve_spawn(self, graph: CallGraph, rel: str,
                       node: ast.Call) -> _Spawn:
        spawn = _Spawn(node, rel)
        fn = self._enclosing(graph, rel, node)
        target = next(kw.value for kw in node.keywords
                      if kw.arg == "target")
        role_expr = node.args[0] if node.args else next(
            (kw.value for kw in node.keywords if kw.arg == "role"),
            None)
        if role_expr is None:
            spawn.problems.append((node.lineno, node.col_offset,
                                   "spawn(...) without a role"))
            return spawn
        # self.ROLE: the binding decides — expand over subclasses.
        if isinstance(role_expr, ast.Attribute) and \
                isinstance(role_expr.value, ast.Name) and \
                role_expr.value.id == "self":
            if fn is None or fn.cls is None or not isinstance(
                    target, ast.Attribute):
                spawn.problems.append((
                    node.lineno, node.col_offset,
                    "self-attribute role outside a method with a "
                    "self.<method> target cannot be resolved"))
                return spawn
            method = target.attr
            for info in graph.subclasses(fn.cls):
                role = graph.class_attr(info.name, role_expr.attr,
                                        info.rel)
                entry_fn = graph.lookup_method(info.name, method,
                                               info.rel)
                if role not in ROLE_NAMES or entry_fn is None:
                    spawn.problems.append((
                        node.lineno, node.col_offset,
                        f"subclass {info.name} ({info.rel}) has no "
                        f"literal {role_expr.attr} role or no "
                        f"{method}() — every binding of this spawn "
                        f"needs one"))
                    continue
                key = f"{_strip_pkg(info.rel)}::{info.name}.{method}"
                spawn.entries[key] = role
            return spawn
        role = self._literal_role(role_expr)
        if role is None:
            spawn.problems.append((
                node.lineno, node.col_offset,
                f"spawn role {ast.dump(role_expr)[:60]!r} is not a "
                f"literal role constant from runtime/thread_roles.py"))
            return spawn
        key = self._entry_key(graph, rel, fn, target)
        if key is None:
            spawn.problems.append((
                node.lineno, node.col_offset,
                "spawn target does not resolve to a known function "
                "(name a def/method, or functools.partial of one)"))
            return spawn
        spawn.entries[key] = role
        return spawn

    @staticmethod
    def _literal_role(expr: ast.AST) -> Optional[str]:
        if isinstance(expr, ast.Name) and expr.id in ROLE_NAMES:
            return expr.id
        if isinstance(expr, ast.Attribute) and expr.attr in ROLE_NAMES:
            return expr.attr
        if isinstance(expr, ast.Constant) and expr.value in ROLE_NAMES:
            return expr.value
        return None

    def _entry_key(self, graph: CallGraph, rel: str,
                   fn: Optional[FuncInfo],
                   target: ast.AST) -> Optional[str]:
        if fn is not None:
            resolved = graph.resolve_callable(target, fn, None)
            if resolved:
                entry, _ = resolved[0]
                return f"{_strip_pkg(entry.rel)}::{entry.qual}"
        if isinstance(target, ast.Attribute):
            # Unresolvable receiver (stdlib callables like
            # httpd.serve_forever): key by attribute name.
            return f"{_strip_pkg(rel)}::{target.attr}"
        return None

    def _enclosing(self, graph: CallGraph, rel: str,
                   node: ast.AST) -> Optional[FuncInfo]:
        best: Optional[FuncInfo] = None
        for fn in graph.functions.values():
            if fn.rel != rel:
                continue
            lo = fn.node.lineno
            hi = getattr(fn.node, "end_lineno", lo) or lo
            if lo <= node.lineno <= hi:
                if best is None or fn.node.lineno > best.node.lineno:
                    best = fn
        return best

    # -- reachability -------------------------------------------------
    def _reach_check(self, graph: CallGraph, spawns: List[_Spawn],
                     add) -> None:
        #: (path, line, col) -> [desc, roots, shortest chain]
        sites: Dict[Tuple[str, int, int], List] = {}
        for spawn in spawns:
            for entry, role in spawn.entries.items():
                if role not in CRITICAL_ROLES:
                    continue
                fn, binding = self._entry_func(graph, entry)
                if fn is None:
                    continue
                for where, call, path in graph.reachable_calls(
                        fn, binding,
                        prune=lambda f, c: classify_blocking(c)
                        is not None):
                    desc = classify_blocking(call)
                    if desc is None:
                        continue
                    site = (where.rel, call.lineno, call.col_offset)
                    chain = tuple(path) + (f"{where.rel}::"
                                           f"{where.qual}",)
                    root = f"{role} {entry}"
                    if site not in sites:
                        sites[site] = [desc, {root}, chain, entry]
                    else:
                        sites[site][1].add(root)
                        if len(chain) < len(sites[site][2]):
                            sites[site][2] = chain
        for (path, line, col), (desc, roots, chain, entry) \
                in sorted(sites.items()):
            rendered = " -> ".join(
                f"{Path(k.split('::')[0]).name}:{k.split('::')[1]}"
                for k in chain)
            add(Violation(
                path, line, col, self.name,
                f"{desc} reachable from latency-critical thread(s) "
                f"[{', '.join(sorted(roots))}] via {rendered} — "
                f"DISPATCH/LIVENESS/EVENTLOOP threads must never "
                f"block (docs/THREADS.md); route through send_async "
                f"or an event-loop timer/queue"))

    def _entry_func(self, graph: CallGraph,
                    entry: str) -> Tuple[Optional[FuncInfo],
                                         Optional[str]]:
        rel, qual = entry.split("::", 1)
        for prefix in (PKG_PREFIX, ""):
            fn = graph.functions.get(f"{prefix}{rel}::{qual}")
            if fn is not None:
                return fn, fn.cls
        # Virtual binding: Worker._main lives on Actor — resolve the
        # method through the MRO, carry the subclass as binding.
        if "." in qual:
            cls, method = qual.rsplit(".", 1)
            fn = graph.lookup_method(cls, method)
            if fn is not None:
                return fn, cls
        return None, None

    # -- framework hook ----------------------------------------------
    def check(self, module: ModuleInfo) -> Iterator[Violation]:
        rel = module.rel
        if rel.startswith("tests/"):
            return
        if rel.startswith(PKG_PREFIX):
            yield from self._by_module.get(rel, [])
            if rel == ROLES_REL:
                yield from self._registry_direction()
                yield from self._doc_direction()
            return
        # Outside the package (fixtures): overlay and self-check.
        overlay = self.graph.with_module(rel, module.tree)
        local: List[Violation] = []
        spawns = self._scan_local(overlay, rel, module.tree,
                                  local.append)
        self._reach_check(overlay, spawns, local.append)
        yield from local

    def _scan_local(self, graph: CallGraph, rel: str, tree: ast.AST,
                    add) -> List[_Spawn]:
        saved = self._add
        try:
            self._add = add  # type: ignore[assignment]
            spawns = self._scan_module(graph, rel, tree)
            for spawn in spawns:
                for line, col, msg in spawn.problems:
                    add(Violation(rel, line, col, self.name, msg))
                for entry, role in spawn.entries.items():
                    if role not in ROLE_NAMES:
                        add(Violation(rel, spawn.node.lineno,
                                      spawn.node.col_offset,
                                      self.name,
                                      f"unknown role {role!r}"))
        finally:
            self._add = saved  # type: ignore[assignment]
        return spawns

    def _registry_direction(self) -> Iterator[Violation]:
        for entry, role in sorted(self.registry.items()):
            if role not in ROLE_NAMES:
                yield Violation(
                    ROLES_REL, self.registry_line, 0, self.name,
                    f"THREAD_ROLES[{entry!r}] declares unknown role "
                    f"{role!r}")
            if entry not in self._package_entries:
                yield Violation(
                    ROLES_REL, self.registry_line, 0, self.name,
                    f"THREAD_ROLES entry {entry!r} matches no spawn "
                    f"site in the package — stale registry rows are "
                    f"drift (remove it or fix the spawn)")

    def _doc_direction(self) -> Iterator[Violation]:
        if not self.doc_exists:
            yield Violation(
                DOC_REL, 1, 0, self.name,
                "docs/THREADS.md is missing — the thread-role "
                "inventory table must document every THREAD_ROLES "
                "entry")
            return
        for entry, role in sorted(self.registry.items()):
            doc = self.doc_roles.get(entry)
            if doc is None:
                yield Violation(
                    DOC_REL, 1, 0, self.name,
                    f"THREAD_ROLES entry {entry!r} ({role}) has no "
                    f"row in the docs/THREADS.md inventory table")
            elif doc[0] != role:
                yield Violation(
                    DOC_REL, doc[1], 0, self.name,
                    f"docs/THREADS.md lists {entry!r} as {doc[0]} "
                    f"but THREAD_ROLES declares {role}")
        for entry, (role, line) in sorted(self.doc_roles.items()):
            if entry not in self.registry:
                yield Violation(
                    DOC_REL, line, 0, self.name,
                    f"docs/THREADS.md row {entry!r} ({role}) matches "
                    f"no THREAD_ROLES entry — remove the stale row "
                    f"or register the thread")

    def tree_report(self) -> List[str]:
        n_crit = sum(1 for r, _, _ in self._package_entries.values()
                     if r in CRITICAL_ROLES)
        return [f"thread-role: {len(self._package_entries)} entries "
                f"({n_crit} latency-critical) proved against "
                f"{len(self.registry)} registry rows"]
