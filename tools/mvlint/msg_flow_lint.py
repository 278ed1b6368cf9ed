"""msg-flow lint (pass 11, interprocedural): the message protocol
graph — construction sites, handler dispatch, reply pairing — checked
against the flow table in ``docs/WIRE_FORMAT.md``, both directions.

The two recurring hand-debugged failure classes in an actor system are
"nobody answers this request" (a waiter blocks forever) and "the reply
arrives but the waiter is never counted down" (PR-6/9/12 starvation
was the transport-level cousin; the repair/rejoin paths keep flirting
with the protocol-level one). Both are *extractable* facts: the PR-16
call graph resolves handler bodies, and ``register_handler`` /
intercept-by-name sites enumerate exactly who answers what. The pass:

* **Registry hygiene** — no duplicate ``MsgType`` ints (``IntEnum``
  silently aliases duplicates — the second name becomes a ghost), and
  no dead types (an enum member mentioned nowhere in the package
  outside ``core/message.py`` is abandoned protocol surface).
* **Flow table, BOTH directions** — ``docs/WIRE_FORMAT.md`` gains a
  message-flow table classifying every type ``request`` / ``reply`` /
  ``fire-and-forget`` with its paired reply and its handlers; every
  enum member needs a row and every row an enum member (the wire-slot
  registry precedent). The ``handled by`` column must equal the
  *computed* handler set: ``register_handler`` sites (actor classes,
  resolved through the MRO so ``SyncServer`` rows read ``server``) and
  intercept-by-name sites (``Communicator._local_forward``,
  ``ShmNet.recv``). ``zoo`` marks the mailbox-pop types
  (``Control_Reply_Barrier`` / ``Control_Reply_Register``) that have
  no in-actor handler by design.
* **Exactly-one-handler discipline** — a type registered twice in one
  actor class is a silent overwrite (the dispatch dict keeps the
  last); a ``request``-kind type with no handler anywhere strands its
  requester's waiter.
* **Reply paths reach the waiter** — every worker-band reply handler
  (``-32 < type < 0``) must *reach* (call-graph closure) a
  ``Waiter.notify``/``release`` AND a ``take_error`` inspection: the
  error path (``mark_error``) must count the same waiter down the
  success path does, or a failed request hangs instead of raising.
* **Requests get answered** — every ``request``-kind type needs at
  least one handler whose closure constructs the paired reply
  (``create_reply_message()`` or a literal ``Message(msg_type=...)``
  of the paired type); fire-and-forget types are exempt *because the
  table says so* — the declaration is the reviewed artifact.

Fixture files (outside the package) are checked per-class with a graph
overlay, like pass 9: duplicate registrations, waiter-less reply
handlers and reply-less request handlers are flagged locally.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .callgraph import CallGraph, FuncInfo
from .framework import LintPass, ModuleInfo, Violation

PKG_PREFIX = "multiverso_tpu/"
MSG_REL = "multiverso_tpu/core/message.py"
DOC_REL = "docs/WIRE_FORMAT.md"

KINDS = ("request", "reply", "fire-and-forget")

#: Message-flow rows: | `Type` | kind | `Reply` or — | handlers |.
#: The kind keyword in column 2 keeps these from ever cross-matching
#: the registry table (int column 2) or the slot table (int column 1).
FLOW_ROW_RE = re.compile(
    r"^\|\s*`([A-Za-z_][A-Za-z0-9_]*)`\s*"
    r"\|\s*(request|reply|fire-and-forget)\s*"
    r"\|\s*(?:`([A-Za-z_][A-Za-z0-9_]*)`|—|-)\s*"
    r"\|\s*([a-z, \-]*?)\s*\|")

#: Handler names the table may use: actor classes resolve to the four
#: roles; module-level intercepts resolve to their module stem; `zoo`
#: marks the mailbox-pop reply types with no in-actor handler.
HANDLER_NAMES = frozenset(
    {"worker", "server", "controller", "communicator", "shm", "zoo"})

#: Worker-band replies (-32 < t < 0) complete a blocked Waiter; their
#: handlers owe the notify/take_error discipline checked below.
WORKER_BAND = (-32, 0)


def load_msg_type_lines(path: Path) -> Dict[str, Tuple[int, int]]:
    """``MsgType`` members parsed (never imported): name ->
    (value, line). Negative values arrive as ``UnaryOp(USub)``."""
    out: Dict[str, Tuple[int, int]] = {}
    try:
        tree = ast.parse(path.read_text(encoding="utf-8"))
    except (OSError, SyntaxError):
        return out
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ClassDef) and node.name == "MsgType"):
            continue
        for stmt in node.body:
            if not (isinstance(stmt, ast.Assign)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)):
                continue
            value = stmt.value
            sign = 1
            if isinstance(value, ast.UnaryOp) and \
                    isinstance(value.op, ast.USub):
                sign, value = -1, value.operand
            if isinstance(value, ast.Constant) and \
                    isinstance(value.value, int):
                out[stmt.targets[0].id] = (sign * value.value,
                                           stmt.lineno)
    return out


def load_flow_table(path: Path) -> Dict[str, Tuple[str, Optional[str],
                                                   Tuple[str, ...], int]]:
    """docs/WIRE_FORMAT.md flow rows: name ->
    (kind, paired reply or None, handler names, line)."""
    out: Dict[str, Tuple[str, Optional[str], Tuple[str, ...], int]] = {}
    try:
        lines = path.read_text(encoding="utf-8").splitlines()
    except OSError:
        return out
    for i, line in enumerate(lines, 1):
        m = FLOW_ROW_RE.match(line.strip())
        if m is None:
            continue
        handlers = tuple(sorted(h.strip() for h in m.group(4).split(",")
                                if h.strip()))
        out[m.group(1)] = (m.group(2), m.group(3), handlers, i)
    return out


def _msgtype_attr(node: ast.AST) -> Optional[str]:
    """``MsgType.X`` -> ``"X"``."""
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and \
            node.value.id == "MsgType":
        return node.attr
    return None


def _compared_types(node: ast.Compare) -> List[str]:
    """Every MsgType name a comparison tests against (handles the
    ``== int(MsgType.X)`` and ``in (int(MsgType.X), ...)`` shapes)."""
    names: List[str] = []
    for comp in node.comparators:
        for sub in ast.walk(comp):
            name = _msgtype_attr(sub)
            if name is not None:
                names.append(name)
    return names


class _Handler:
    """One resolved dispatch site for a message type."""

    __slots__ = ("actor", "cls", "fn", "rel", "line", "kind")

    def __init__(self, actor: str, cls: Optional[str],
                 fn: Optional[FuncInfo], rel: str, line: int,
                 kind: str):
        self.actor = actor      # short handler name for the doc column
        self.cls = cls          # registering/intercepting class
        self.fn = fn            # handler body (None if unresolved)
        self.rel = rel
        self.line = line
        self.kind = kind        # "register" | "intercept"


class MsgFlowLint(LintPass):
    name = "msg-flow"

    def __init__(self, root: Path, graph: CallGraph):
        self.root = root
        self.graph = graph
        self.types = load_msg_type_lines(root / MSG_REL)
        self.flow = load_flow_table(root / DOC_REL)
        self.doc_exists = (root / DOC_REL).is_file()
        self._by_module: Dict[str, List[Violation]] = {}
        #: type name -> handler sites (package-wide)
        self._handlers: Dict[str, List[_Handler]] = {}
        #: type name -> every package mention outside message.py
        self._mentions: Dict[str, List[Tuple[str, int]]] = {}
        self._discover_package()

    # -- package discovery -------------------------------------------
    def _add(self, v: Violation) -> None:
        self._by_module.setdefault(v.path, []).append(v)

    def _discover_package(self) -> None:
        for rel, tree in sorted(self.graph.module_trees.items()):
            if not rel.startswith(PKG_PREFIX):
                continue
            self._scan_module(self.graph, rel, tree,
                              self._handlers, self._mentions,
                              self._add)
        self._check_handler_sets(self.graph, self._handlers, self._add,
                                 package=True)

    def _scan_module(self, graph: CallGraph, rel: str, tree: ast.AST,
                     handlers: Dict[str, List[_Handler]],
                     mentions: Dict[str, List[Tuple[str, int]]],
                     add) -> None:
        register_args: Set[int] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "register_handler" and node.args:
                self._record_register(graph, rel, node, handlers, add)
                register_args.add(id(node.args[0]))
            elif isinstance(node, ast.Compare):
                self._record_intercepts(graph, rel, node, handlers)
        if rel == MSG_REL:
            return  # the enum itself is not a use
        for node in ast.walk(tree):
            name = _msgtype_attr(node)
            if name is not None and id(node) not in register_args:
                mentions.setdefault(name, []).append((rel, node.lineno))

    def _record_register(self, graph: CallGraph, rel: str,
                         node: ast.Call,
                         handlers: Dict[str, List[_Handler]],
                         add) -> None:
        type_name = _msgtype_attr(node.args[0])
        if type_name is None:
            return  # dynamic registration: out of scope
        if type_name not in self.types:
            add(Violation(
                rel, node.lineno, node.col_offset, self.name,
                f"register_handler for unknown message type "
                f"MsgType.{type_name} — not a member of the "
                f"core/message.py registry"))
            return
        fn = self._enclosing(graph, rel, node)
        cls = fn.cls if fn is not None else None
        handler_fn: Optional[FuncInfo] = None
        if len(node.args) > 1 and cls is not None:
            target = node.args[1]
            if isinstance(target, ast.Attribute) and \
                    isinstance(target.value, ast.Name) and \
                    target.value.id == "self":
                handler_fn = graph.lookup_method(cls, target.attr, rel)
        actor = self._actor_name(graph, cls, rel) if cls else \
            Path(rel).stem
        site = _Handler(actor, cls, handler_fn, rel, node.lineno,
                        "register")
        prior = [h for h in handlers.get(type_name, ())
                 if h.kind == "register" and h.cls == cls]
        if prior:
            add(Violation(
                rel, node.lineno, node.col_offset, self.name,
                f"duplicate register_handler for MsgType.{type_name} "
                f"in class {cls} (first at {prior[0].rel}:"
                f"{prior[0].line}) — the dispatch table keeps only "
                f"the last registration; the first handler silently "
                f"never runs"))
        handlers.setdefault(type_name, []).append(site)

    def _record_intercepts(self, graph: CallGraph, rel: str,
                           node: ast.Compare,
                           handlers: Dict[str, List[_Handler]]) -> None:
        """Intercept-by-name dispatch: type comparisons inside the
        sanctioned routing interceptors (``_local_forward``; the shm
        transport's below-the-router announce consumption)."""
        fn = self._enclosing(graph, rel, node)
        if fn is None:
            return
        if fn.name != "_local_forward" and \
                not rel.endswith("runtime/shm.py"):
            return
        for type_name in _compared_types(node):
            if type_name not in self.types:
                continue
            actor = Path(rel).stem
            sites = handlers.setdefault(type_name, [])
            if any(h.kind == "intercept" and h.rel == rel and
                   h.fn is fn for h in sites):
                continue  # one interceptor, many comparisons: one site
            sites.append(_Handler(actor, fn.cls, fn, rel, node.lineno,
                                  "intercept"))

    def _actor_name(self, graph: CallGraph, cls: str, rel: str) -> str:
        """Doc-column name for a registering class: the topmost
        concrete actor below ``Actor`` in the MRO (``SyncServer`` ->
        ``server``), else the class name itself."""
        mro = graph.mro(cls, rel)
        for info in mro:
            if "Actor" in info.bases:
                return info.name.lower()
        return cls.lower()

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

    # -- reachability helpers ----------------------------------------
    def _reaches(self, graph: CallGraph, fn: FuncInfo,
                 binding: Optional[str], pred) -> bool:
        for _where, call, _path in graph.reachable_calls(fn, binding):
            if pred(call):
                return True
        return False

    @staticmethod
    def _is_notify(call: ast.Call) -> bool:
        return isinstance(call.func, ast.Attribute) and \
            call.func.attr in ("notify", "release")

    @staticmethod
    def _is_take_error(call: ast.Call) -> bool:
        name = call.func.attr if isinstance(call.func, ast.Attribute) \
            else (call.func.id if isinstance(call.func, ast.Name)
                  else None)
        return name == "take_error"

    @staticmethod
    def _builds_reply(call: ast.Call, paired: Optional[str]) -> bool:
        name = call.func.attr if isinstance(call.func, ast.Attribute) \
            else (call.func.id if isinstance(call.func, ast.Name)
                  else None)
        if name == "create_reply_message":
            return True
        if name == "Message" and paired is not None:
            for kw in call.keywords:
                if kw.arg == "msg_type" and \
                        _msgtype_attr(kw.value) == paired:
                    return True
        return False

    def _class_lexical(self, graph: CallGraph, cls: str, rel: str,
                       pred) -> bool:
        """Fallback when the closure walk cannot resolve a path: does
        ANY method of the class (MRO-wide) contain a matching call?"""
        for info in graph.mro(cls, rel):
            for fn in info.methods.values():
                for node in ast.walk(fn.node):
                    if isinstance(node, ast.Call) and pred(node):
                        return True
        return False

    def _handler_reaches(self, graph: CallGraph, site: _Handler,
                         pred) -> bool:
        if site.fn is not None and site.cls is not None:
            bindings = [site.cls] + [
                info.name for info in graph.subclasses(site.cls)
                if info.name != site.cls]
            for binding in bindings:
                if self._reaches(graph, site.fn, binding, pred):
                    return True
        if site.cls is not None:
            return self._class_lexical(graph, site.cls, site.rel, pred)
        return False

    # -- the behavioral checks ---------------------------------------
    def _check_handler_sets(self, graph: CallGraph,
                            handlers: Dict[str, List[_Handler]],
                            add, package: bool) -> None:
        """Waiter discipline + request-reply reachability. In package
        mode a request is satisfied when ANY of its handlers replies;
        fixture mode checks each class on its own."""
        for type_name, sites in sorted(handlers.items()):
            value = self.types.get(type_name, (None, 1))[0]
            if value is None:
                continue
            kind, paired = (self.flow.get(type_name) or
                            (None, None, (), 1))[:2]
            if WORKER_BAND[0] < value < WORKER_BAND[1]:
                for site in sites:
                    if site.kind != "register":
                        continue
                    where = site.fn if site.fn is not None else None
                    line = where.node.lineno if where else site.line
                    rel = where.rel if where else site.rel
                    if not self._handler_reaches(graph, site,
                                                 self._is_notify):
                        add(Violation(
                            rel, line, 0, self.name,
                            f"worker-band reply handler for "
                            f"MsgType.{type_name} in {site.cls} never "
                            f"reaches Waiter.notify/release — the "
                            f"requester's waiter blocks forever"))
                    if not self._handler_reaches(graph, site,
                                                 self._is_take_error):
                        add(Violation(
                            rel, line, 0, self.name,
                            f"reply handler for MsgType.{type_name} "
                            f"in {site.cls} never inspects "
                            f"take_error() — a mark_error reply must "
                            f"count the same waiter down the success "
                            f"path does, not vanish"))
            if kind == "request":
                answering = [
                    s for s in sites
                    if self._handler_reaches(
                        graph, s,
                        lambda c: self._builds_reply(c, paired))]
                if sites and not answering:
                    first = sites[0]
                    line = first.fn.node.lineno if first.fn is not None \
                        else first.line
                    rel = first.fn.rel if first.fn is not None \
                        else first.rel
                    add(Violation(
                        rel, line, 0, self.name,
                        f"request type MsgType.{type_name} has "
                        f"{len(sites)} handler(s) but none reaches "
                        f"create_reply_message() or a "
                        f"Message(msg_type=MsgType.{paired}) "
                        f"construction — nobody answers; declare it "
                        f"fire-and-forget in docs/WIRE_FORMAT.md or "
                        f"wire the reply"))

    # -- registry/doc directions (emitted scanning message.py) -------
    def _registry_checks(self) -> Iterator[Violation]:
        by_value: Dict[int, str] = {}
        for name, (value, line) in sorted(self.types.items(),
                                          key=lambda kv: kv[1][1]):
            if value in by_value:
                yield Violation(
                    MSG_REL, line, 0, self.name,
                    f"duplicate message-type int {value}: "
                    f"MsgType.{name} aliases MsgType.{by_value[value]} "
                    f"(IntEnum folds duplicate values into silent "
                    f"aliases — dispatch and band routing cannot tell "
                    f"them apart)")
            else:
                by_value[value] = name
        for name, (value, line) in sorted(self.types.items()):
            if name not in self._mentions and \
                    name not in self._handlers:
                yield Violation(
                    MSG_REL, line, 0, self.name,
                    f"dead message type MsgType.{name} ({value}): "
                    f"constructed and handled nowhere in the package "
                    f"— wire it up or delete it")
            kind = (self.flow.get(name) or (None,))[0]
            if kind == "request" and not self._handlers.get(name):
                yield Violation(
                    MSG_REL, line, 0, self.name,
                    f"request type MsgType.{name} ({value}) has no "
                    f"handler: no register_handler site and no "
                    f"intercept — its requester's waiter can never "
                    f"complete")

    def _doc_checks(self) -> Iterator[Violation]:
        if not self.doc_exists or not self.flow:
            yield Violation(
                DOC_REL, 1, 0, self.name,
                "docs/WIRE_FORMAT.md has no message-flow table "
                "(| `Type` | kind | `Reply` | handlers |) — every "
                "message type must be classified "
                "request/reply/fire-and-forget")
            return
        for name, (value, _line) in sorted(self.types.items()):
            if name not in self.flow:
                yield Violation(
                    DOC_REL, 1, 0, self.name,
                    f"MsgType.{name} ({value}) has no row in the "
                    f"docs/WIRE_FORMAT.md message-flow table — "
                    f"classify it request/reply/fire-and-forget")
        for name, (kind, paired, doc_handlers, line) in \
                sorted(self.flow.items()):
            if name not in self.types:
                yield Violation(
                    DOC_REL, line, 0, self.name,
                    f"message-flow row {name!r} matches no MsgType "
                    f"member — remove the stale row or register the "
                    f"type")
                continue
            bad = [h for h in doc_handlers if h not in HANDLER_NAMES]
            if bad:
                yield Violation(
                    DOC_REL, line, 0, self.name,
                    f"message-flow row {name!r} names unknown "
                    f"handler(s) {', '.join(bad)} — valid: "
                    f"{', '.join(sorted(HANDLER_NAMES))}")
            if kind == "request":
                if paired is None:
                    yield Violation(
                        DOC_REL, line, 0, self.name,
                        f"request row {name!r} names no paired reply "
                        f"— a request either has a reply type or is "
                        f"fire-and-forget")
                elif paired not in self.types:
                    yield Violation(
                        DOC_REL, line, 0, self.name,
                        f"request row {name!r} pairs with {paired!r} "
                        f"which is not a MsgType member")
                elif (self.flow.get(paired) or (None,))[0] != "reply":
                    yield Violation(
                        DOC_REL, line, 0, self.name,
                        f"request row {name!r} pairs with {paired!r} "
                        f"whose kind is not 'reply'")
            elif paired is not None:
                yield Violation(
                    DOC_REL, line, 0, self.name,
                    f"{kind} row {name!r} must not name a paired "
                    f"reply (column 3 is for request rows)")
            computed = sorted({h.actor for h in
                               self._handlers.get(name, ())})
            declared = sorted(doc_handlers)
            if "zoo" in declared:
                if declared != ["zoo"] or computed:
                    yield Violation(
                        DOC_REL, line, 0, self.name,
                        f"row {name!r}: 'zoo' marks a mailbox-pop "
                        f"type with NO in-actor handler, but the "
                        f"package computes handlers "
                        f"[{', '.join(computed) or 'none'}]")
            elif computed != declared:
                yield Violation(
                    DOC_REL, line, 0, self.name,
                    f"row {name!r} declares handlers "
                    f"[{', '.join(declared) or 'none'}] but the "
                    f"package computes [{', '.join(computed) or 'none'}] "
                    f"(register_handler + intercept sites) — the "
                    f"table and the code must agree both directions")

    # -- framework hook ----------------------------------------------
    def check(self, module: ModuleInfo) -> Iterator[Violation]:
        rel = module.rel
        if rel.startswith("tests/"):
            return
        if rel.startswith(PKG_PREFIX):
            yield from self._by_module.get(rel, [])
            if rel == MSG_REL:
                yield from self._registry_checks()
                yield from self._doc_checks()
            return
        # Fixture mode: overlay the module, check its classes locally.
        overlay = self.graph.with_module(rel, module.tree)
        local: List[Violation] = []
        handlers: Dict[str, List[_Handler]] = {}
        mentions: Dict[str, List[Tuple[str, int]]] = {}
        self._scan_module(overlay, rel, module.tree, handlers,
                          mentions, local.append)
        self._check_handler_sets(overlay, handlers, local.append,
                                 package=False)
        yield from local

    def tree_report(self) -> List[str]:
        n_handlers = sum(len(v) for v in self._handlers.values())
        return [f"msg-flow: {len(self.types)} message types, "
                f"{n_handlers} handler sites, "
                f"{len(self.flow)} flow rows proved both directions"]
