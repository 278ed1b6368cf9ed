"""copy-lint: the zero-copy wire path stays zero-copy.

PR 15 removed the per-payload copies from the transport hot path — the
send side serializes scatter-gather views drained by vectored
``sendmsg`` writes, the receive side cuts read-only Blob views out of
pooled frame buffers (docs/MEMORY.md). The three patterns that
reintroduce a payload copy are banned ON THE WIRE-PATH MODULES:

* ``x.tobytes()`` — materializes a private bytes copy of an array;
* ``bytes(x)`` (with arguments) — copies any buffer into a bytes
  object (``bytes()`` no-arg and ``bytes(n)`` allocation are copies of
  nothing, but the lint cannot tell an int from a buffer statically,
  so both forms are flagged and sanctioned sites carry the pragma);
* ``b"...".join(...)`` — the flat-frame join.

Sanctioned sites (``tcp._serialize``, the flat serializer kept as the
golden reference tests/test_zero_copy.py byte-compares the framer
against; the codec's flat-frame compat wrapper) carry
``# mvlint: ignore[copy-lint]`` pragmas — counted, visible exceptions.
Everything outside the wire-path module list is out of scope: tables,
models and snapshots copy for their own good reasons.

The wire-path module list below is cross-checked against the module
table in ``docs/MEMORY.md`` in BOTH directions (| `path` | wire-path |
rows), so the doc cannot drift from what the lint enforces — the same
contract as the metric-name and wire-slot doc checks.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Iterator

from .framework import LintPass, ModuleInfo, Violation

#: THE wire-path module set — every module a payload byte crosses
#: between a table op and the socket. Kept in lockstep with the table
#: in docs/MEMORY.md (both-direction cross-check below).
WIRE_PATH_MODULES = (
    "multiverso_tpu/core/blob.py",
    "multiverso_tpu/core/message.py",
    "multiverso_tpu/runtime/tcp.py",
    "multiverso_tpu/runtime/shm.py",
    "multiverso_tpu/runtime/communicator.py",
    "multiverso_tpu/runtime/allreduce_engine.py",
    "multiverso_tpu/util/wire_codec.py",
    "multiverso_tpu/util/buffer_pool.py",
)

#: The seeded-violation fixture self-checks this pass (tests/test_mvlint).
FIXTURE = "tools/mvlint/fixtures/bad_copies.py"

#: A doc-table row is `path` followed by the literal kind "wire-path" —
#: the marker that distinguishes the module table from the doc's other
#: backticked tables (size classes, copy counts).
DOC_ROW_RE = re.compile(r"^\|\s*`([A-Za-z0-9_/\.]+)`\s*\|\s*wire-path\b")


def parse_doc_modules(doc_path: Path) -> dict:
    """``| `path` | wire-path |`` rows from docs/MEMORY.md (path ->
    first line seen)."""
    rows: dict = {}
    if not doc_path.exists():
        return rows
    for lineno, line in enumerate(
            doc_path.read_text(encoding="utf-8").splitlines(), 1):
        m = DOC_ROW_RE.match(line.strip())
        if m:
            rows.setdefault(m.group(1), lineno)
    return rows


class CopyLint(LintPass):
    name = "copy-lint"

    def __init__(self, doc_path: Path,
                 doc_rel: str = "docs/MEMORY.md"):
        self.doc_path = doc_path
        self.doc_rel = doc_rel
        self._doc_checked = False

    def check(self, module: ModuleInfo) -> Iterator[Violation]:
        if not self._doc_checked:
            self._doc_checked = True
            yield from self._check_doc()
        rel = module.rel
        if rel not in WIRE_PATH_MODULES and rel != FIXTURE:
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "tobytes":
                yield self._violation(
                    module, node,
                    ".tobytes() copies the whole payload on the "
                    "zero-copy wire path — serialize views "
                    "(Blob.wire_views / serialize_views) instead")
            elif isinstance(fn, ast.Name) and fn.id == "bytes" \
                    and (node.args or node.keywords):
                yield self._violation(
                    module, node,
                    "bytes(...) copies its buffer on the zero-copy "
                    "wire path — read through memoryview/numpy views "
                    "(Message.text_payload for text payloads) instead")
            elif isinstance(fn, ast.Attribute) and fn.attr == "join" \
                    and isinstance(fn.value, ast.Constant) \
                    and isinstance(fn.value.value, bytes):
                yield self._violation(
                    module, node,
                    "bytes-join builds a flat frame copy on the "
                    "zero-copy wire path — emit a view list for the "
                    "vectored sendmsg write instead")

    def _violation(self, module: ModuleInfo, node: ast.AST,
                   message: str) -> Violation:
        return Violation(
            module.rel, node.lineno, node.col_offset, self.name,
            message + " (sanctioned sites: # mvlint: "
                      "ignore[copy-lint]; docs/MEMORY.md)")

    def _check_doc(self) -> Iterator[Violation]:
        if not self.doc_path.exists():
            yield Violation(
                self.doc_rel, 1, 0, self.name,
                "memory doc missing: the wire-path module list must be "
                "documented (| `path` | wire-path | table)")
            return
        doc = parse_doc_modules(self.doc_path)
        for path in WIRE_PATH_MODULES:
            if path not in doc:
                yield Violation(
                    self.doc_rel, 1, 0, self.name,
                    f"wire-path module {path} missing from the doc's "
                    f"module table (| `{path}` | wire-path | row)")
        for path, lineno in sorted(doc.items()):
            if path not in WIRE_PATH_MODULES:
                yield Violation(
                    self.doc_rel, lineno, 0, self.name,
                    f"doc lists {path} as a wire-path module but "
                    f"tools/mvlint/copy_lint.py WIRE_PATH_MODULES "
                    f"does not — stale doc entry")
