"""mvlint: project-invariant static analysis for the actor/PS runtime.

Twelve passes over ``multiverso_tpu/`` and ``tests/``
(see each module's docstring for the precise rules):

* ``flag-lint`` — every flag access names a canonical registered flag
  with the canonical default (``util/configure.py CANONICAL_FLAGS``).
* ``wire-slot`` — reserved header slots 5-8 are accessed by registered
  name only (``core/message.py WIRE_SLOTS``), and the registry matches
  the slot table in ``docs/WIRE_FORMAT.md``.
* ``device-dispatch`` — multi-zoo-reachable eager dispatch sits inside
  a ``device_lock.guard()``-class context (the PR-1/PR-4 XLA wedge).
* ``lock-discipline`` — registered locks are ``with``-scoped and never
  lexically wrap a blocking call.
* ``metric-name`` — every ``monitor``/``samples``/``count`` literal
  names a canonical metric (``util/dashboard.py METRIC_NAMES``,
  cross-checked against the table in ``docs/OBSERVABILITY.md``).
* ``send-discipline`` — blocking ``net.send`` stays inside the
  transport layer; liveness/control frames ride ``send_async`` (the
  PR-6/PR-9 dispatch-thread-starvation class, now machine-checked).
* ``tunable-lint`` — every ``TUNABLE_FLAGS`` entry names a canonical
  flag and has a ``register_tunable_hook`` call site; every autotune
  policy's metric input names a canonical metric
  (``util/configure.py`` / ``runtime/autotune.py``; docs/AUTOTUNE.md).
* ``copy-lint`` — ``.tobytes()`` / ``bytes(...)`` / ``b"".join`` are
  banned on the zero-copy wire-path modules outside pragma-sanctioned
  sites, and the module list is cross-checked against the table in
  ``docs/MEMORY.md`` in both directions.
* ``thread-role`` — every thread spawns through
  ``thread_roles.spawn(ROLE, ...)``; the spawn-derived inventory
  matches ``THREAD_ROLES`` and ``docs/THREADS.md`` both directions;
  and no DISPATCH/LIVENESS entry can *reach* a blocking primitive
  through the interprocedural call graph (``callgraph.py`` — the
  proof-strength successor to the lexical send-discipline ban).
* ``guarded-by`` — ``# guarded_by: <lock>`` annotated fields are only
  touched under their witness-registered lock, lexically or via the
  caller-holds analysis (Clang ``-Wthread-safety`` adapted to
  ``lock_witness``).
* ``msg-flow`` — the message-protocol graph (``register_handler``
  dispatch, intercept-by-name, reply pairing) checked against the
  flow table in ``docs/WIRE_FORMAT.md`` both directions: every
  request type has a handler that answers, every worker-band reply
  handler reaches its ``Waiter`` notify AND inspects ``take_error``,
  no duplicate type ints, no dead types.
* ``wake-protocol`` — the gated wake-latch idiom (self-pipe /
  condition wake with a boolean gate) must re-arm the latch before
  the state checks and the park, in the lexical order the event loop
  uses post-PR-19 (the lost-wakeup ordering is rejected).

Run locally: ``python -m tools.mvlint multiverso_tpu tests``
(``--baseline`` prints per-pass counts without failing;
``--report-unused-pragmas`` lists suppressions that matched nothing).
The runtime complement — the ``-debug_locks`` lock-order witness and
the thread-role blocking watchdog — lives in
``multiverso_tpu/util/lock_witness.py`` and
``multiverso_tpu/runtime/thread_roles.py``. Docs:
``docs/STATIC_ANALYSIS.md``, ``docs/THREADS.md``.
"""

from __future__ import annotations

from pathlib import Path
from typing import List, Sequence

from .callgraph import CallGraph
from .copy_lint import CopyLint
from .device_dispatch_lint import DeviceDispatchLint
from .flag_lint import FlagLint, load_canonical_flags
from .framework import LintPass, RunResult, Violation, run_passes
from .guard_lint import GuardedByLint
from .lock_lint import LockDisciplineLint
from .metric_lint import MetricNameLint, load_metric_names
from .msg_flow_lint import MsgFlowLint
from .role_lint import ThreadRoleLint
from .send_lint import SendDisciplineLint
from .tunable_lint import (TunableLint, load_autotune_policies,
                           load_tunable_flags, scan_hook_sites)
from .wake_lint import WakeProtocolLint
from .wire_slot_lint import (WireSlotLint, load_msg_types,
                             load_wire_slots)

#: Repo root = two levels above this package (tools/mvlint/__init__.py).
REPO_ROOT = Path(__file__).resolve().parent.parent.parent

DEFAULT_PATHS = ("multiverso_tpu", "tests")


def build_passes(root: Path = REPO_ROOT) -> List[LintPass]:
    canonical = load_canonical_flags(
        root / "multiverso_tpu" / "util" / "configure.py")
    slots = load_wire_slots(
        root / "multiverso_tpu" / "core" / "message.py")
    msg_types = load_msg_types(
        root / "multiverso_tpu" / "core" / "message.py")
    metrics = load_metric_names(
        root / "multiverso_tpu" / "util" / "dashboard.py")
    tunables = load_tunable_flags(
        root / "multiverso_tpu" / "util" / "configure.py")
    policies = load_autotune_policies(
        root / "multiverso_tpu" / "runtime" / "autotune.py")
    hook_sites = scan_hook_sites(root / "multiverso_tpu")
    graph = CallGraph.build(root / "multiverso_tpu", root)
    return [
        FlagLint(canonical),
        WireSlotLint(slots, root / "docs" / "WIRE_FORMAT.md",
                     msg_types=msg_types),
        DeviceDispatchLint(),
        LockDisciplineLint(),
        MetricNameLint(metrics, root / "docs" / "OBSERVABILITY.md"),
        SendDisciplineLint(),
        TunableLint(tunables, canonical, metrics, policies,
                    hook_sites),
        CopyLint(root / "docs" / "MEMORY.md"),
        ThreadRoleLint(root, graph),
        GuardedByLint(graph),
        MsgFlowLint(root, graph),
        WakeProtocolLint(),
    ]


def run(paths: Sequence[str] = DEFAULT_PATHS,
        root: Path = REPO_ROOT) -> RunResult:
    return run_passes(build_passes(root), paths, root)
