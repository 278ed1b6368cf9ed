"""README.md and docs/*.md cite only what this repository has.

A document that quotes a file which was deleted, or a flag which was
removed, describes a system nobody can run. For every backticked token
of a document:

* one that starts with a top-level directory of this repo (such as
  ``multiverso_tpu/``) must name something that exists
  (``file.py:12``, ``file.py::test`` and ``file.py --flag`` name the
  file; a glob must match), and a bare ``name.py``, ``name.sh`` or
  ``name.md`` must be a file at the root or the name of a module
  somewhere in the tree (documents say ``tcp.py`` for
  ``multiverso_tpu/runtime/tcp.py``);
* ``-name`` or ``-name=value`` with a lower-case ``name`` that holds an
  underscore must be a flag of ``CANONICAL_FLAGS``.

Paths of the upstream project (``src/...``, ``include/...``) start with
none of this repo's prefixes and are skipped.
"""

import functools
import glob
import os
import re

import pytest

from multiverso_tpu.util.configure import CANONICAL_FLAGS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md"] + sorted(
    os.path.join("docs", name) for name in os.listdir(
        os.path.join(REPO, "docs")) if name.endswith(".md"))

#: Top-level directories whose paths a document may cite.
_DIRS = ("multiverso_tpu/", "tests/", "tools/", "benchmark/", "docs/",
         "native/", "binding/", "deploy/")
_BARE_FILE = re.compile(r"^[A-Za-z_][\w.-]*\.(py|sh|md)$")
_FLAG = re.compile(r"^-([a-z][a-z0-9]*(?:_[a-z0-9]+)+)(?:=.*)?$")
_TICKED = re.compile(r"`([^`\n]+)`")


def _cited_path(token: str):
    """The repo path a backticked token cites, or None."""
    head = token.split()[0] if token.split() else ""
    head = head.split("::")[0]
    head = re.sub(r"(:\d+(-\d+)?(,\d+(-\d+)?)*)+$", "", head)
    head = head.rstrip(".,;:")
    if head.startswith(_DIRS) or _BARE_FILE.match(head):
        return head
    return None


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set()
    for _, dirs, files in os.walk(REPO):
        # dot-directories hold caches and unpacked copies of other commits
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "__pycache__")]
        names.update(files)
    return frozenset(names)


def _exists(path: str) -> bool:
    full = os.path.join(REPO, path)
    if "/" not in path:
        return path in _basenames()
    if any(ch in path for ch in "*?["):
        return bool(glob.glob(full))
    if any(ch in path for ch in "<>{}"):  # a pattern, not a path
        return True
    return os.path.exists(full)


@pytest.mark.parametrize("doc", DOCS)
def test_doc_cites_only_what_exists(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    missing, unknown = [], []
    for token in _TICKED.findall(text):
        path = _cited_path(token)
        if path is not None and not _exists(path):
            missing.append(path)
        flag = _FLAG.match(token.strip())
        if flag and flag.group(1) not in CANONICAL_FLAGS:
            unknown.append(token)
    assert not missing, f"{doc} cites files that do not exist: " \
        f"{sorted(set(missing))}"
    assert not unknown, f"{doc} cites flags that do not exist: " \
        f"{sorted(set(unknown))}"
