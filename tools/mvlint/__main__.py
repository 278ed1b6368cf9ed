"""CLI: ``python -m tools.mvlint [--baseline] [paths...]``.

Default paths: ``multiverso_tpu tests`` relative to the repo
root. Exit status: 0 when no (non-pragma'd) violation was found, 1
otherwise. ``--baseline`` prints the per-pass violation + suppression
counts and always exits 0 — the drift-at-a-glance mode future PRs diff
against.
"""

from __future__ import annotations

import argparse
import sys

from . import DEFAULT_PATHS, REPO_ROOT, run


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.mvlint",
        description="project-invariant static analysis "
                    "(see docs/STATIC_ANALYSIS.md)")
    parser.add_argument("paths", nargs="*", default=list(DEFAULT_PATHS),
                        help="files/directories to scan "
                             f"(default: {' '.join(DEFAULT_PATHS)})")
    parser.add_argument("--baseline", action="store_true",
                        help="print per-pass counts, always exit 0")
    parser.add_argument("--report-unused-pragmas", action="store_true",
                        help="warn about '# mvlint: ignore[...]' "
                             "pragmas that suppressed zero findings "
                             "(stale suppressions are drift); "
                             "informational, never changes the exit "
                             "status")
    args = parser.parse_args(argv)

    try:
        result = run(args.paths or DEFAULT_PATHS, REPO_ROOT)
    except FileNotFoundError as exc:
        print(str(exc), file=sys.stderr)
        return 2
    if not result.files_scanned and not result.violations:
        # Zero files parsed and nothing to report: a vacuous pass must
        # not look like a clean one (e.g. a directory of no .py files).
        print("mvlint: no files scanned — bad path set?",
              file=sys.stderr)
        return 2

    for violation in result.violations:
        print(violation.render())
    for line in result.info:
        print(f"note: {line}")
    if args.report_unused_pragmas:
        for rel, line, name in result.unused_pragmas:
            print(f"warning: {rel}:{line}: unused pragma "
                  f"[{name}] — suppresses no finding")
        print(f"mvlint: {len(result.unused_pragmas)} unused "
              f"pragma(s)")
    print(f"mvlint: scanned {result.files_scanned} files")
    for name in sorted(set(result.per_pass) | set(result.per_pass_suppressed)):
        count = result.per_pass.get(name, 0)
        sup = result.per_pass_suppressed.get(name, 0)
        print(f"  {name:18s} {count:3d} violations"
              f"  ({sup} pragma-suppressed)")
    if args.baseline:
        return 0
    if result.failed:
        print(f"mvlint: FAILED with {len(result.violations)} "
              f"violation(s)", file=sys.stderr)
        return 1
    print("mvlint: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
