#!/usr/bin/env python3
"""Set-up seen from inside, for one cell of the benchmark:

    python3 tools/setup_report.py --workload <cell> [--seed N] [--json FILE]

Does what ``benchmark/run.py`` does up to the opening of the measured
window (the attach, ``compile_cache.enable()``, the driver's ``build()``
and ``warm()``) and then prints what the process spent making programs,
from the program's own monitors (``util/dashboard.py``: the listeners to
``jax.monitoring`` that ``enable()`` registers):

(i) the phases, on this file's clock as ``run.py`` has them on its own;

(ii) ``PROGRAM_TRACE``, ``PROGRAM_LOWER``, ``PROGRAM_CACHE_READ`` and
    ``PROGRAM_COMPILE`` (count, seconds), which are exclusive, and what is
    left of ``build_s + warm_s`` under none of them. Programs are made on
    more than one thread (the server actor's builds the update programs
    while the trainer's builds the layers'), so the four are
    thread-seconds that can overlap on the clock: what is left is a floor,
    and negative in a process whose threads compile at once. Beside them the harness's own
    count (``benchmark/lib/builds.py``), which has to agree, and how many
    stage events JAX emitted (what the listeners are called for);

(iii) ``TABLE_INIT``, ``TRAINER_BUILD`` and ``DICT_ALIAS_BUILD``, printed
    as ENCLOSING: each holds the program builds that ran inside it, and
    nothing is subtracted;

(iv) ``dashboard.program_builds()``, largest total first, and BY NAME
    every program that XLA compiled: from a warm compile cache there
    should be none.

It imports the benchmark's library, as ``tools/trace_spans.py`` does, and
has no reader of its own. ``--rehearse`` runs the cell's tiny twin on any
backend (the CPU tests').
"""

import time

_ENTERED = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

STAGES = ("PROGRAM_TRACE", "PROGRAM_LOWER", "PROGRAM_CACHE_READ",
          "PROGRAM_COMPILE")
ENCLOSING = ("TABLE_INIT", "TRAINER_BUILD", "DICT_ALIAS_BUILD")
FIELDS = (("traces", "trace_ms"), ("lowerings", "lower_ms"),
          ("cache_reads", "cache_read_ms"), ("compiles", "compile_ms"))


def set_up(workload: str, seed: int, rehearse: bool) -> dict:
    """Run the cell's set-up and return what was counted."""
    from benchmark import run as bench_run
    faulthandler.dump_traceback_later(bench_run.DEADLINE_S, exit=True)
    t_process = _ENTERED - bench_run.process_age_at_entry()
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = bench_run.by_name(bench["workloads"], workload, "workload")
    entry = bench_run.by_name(bench["configs"], cell["config"],
                              "configuration")
    config = bench_run.with_rehearsal(
        bench_run.load_json(ROOT, entry["file"]), rehearse)
    traffic = bench_run.with_rehearsal(bench_run.load_json(
        bench_run.HERE, "traffic", f"{cell['traffic']}.json"), rehearse)

    import jax
    from benchmark.lib.builds import ProgramBuilds
    builds = ProgramBuilds()
    mark = builds.mark()
    heard = [0]     # stage events, nested ones too: what the listeners pay

    def stage_began(event, value, **kw):
        heard[0] += 1

    jax.monitoring.register_scalar_listener(stage_began)
    t_jax = time.monotonic()
    devices = jax.devices()
    t_attached = time.monotonic()
    if devices[0].platform != "tpu" and not rehearse:
        raise SystemExit(f"the backend is {devices[0].platform!r}, not "
                         "'tpu' (--rehearse runs the tiny twin anywhere)")

    from multiverso_tpu.util import compile_cache, dashboard
    from benchmark.lib import harness
    cache_dir = compile_cache.enable()
    driver = bench_run.load_module("drivers", config["driver"]).Driver(
        harness.Context(config, traffic, seed, builds, bench_run.DEADLINE_S))
    t_imported = time.monotonic()
    driver.build()
    t_built = time.monotonic()
    driver.warm()
    t_warm = time.monotonic()

    monitors = dashboard.metrics_snapshot(max_samples=0)["monitors"]
    found = {
        "workload": workload, "seed": seed, "compile_cache": cache_dir,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "phases": {"setup.import_s": (t_jax - t_process)
                   + (t_imported - t_attached),
                   "setup.attach_s": t_attached - t_jax,
                   "setup.build_s": t_built - t_imported,
                   "setup.warm_s": t_warm - t_built},
        "monitors": {name: monitors[name] for name in STAGES + ENCLOSING
                     if name in monitors},
        "harness": builds.since(mark), "stage_events": heard[0],
        # a tree from before PR 68 has the phases and the harness's count
        "programs": getattr(dashboard, "program_builds", dict)()}
    driver.close()
    faulthandler.cancel_dump_traceback_later()
    return found


def _seconds(found: dict, name: str) -> float:
    return found["monitors"].get(name, {}).get("elapsed_ms", 0.0) / 1e3


def unnamed_s(found: dict) -> float:
    """What is left of ``build_s + warm_s`` under none of the four."""
    phases = found["phases"]
    return phases["setup.build_s"] + phases["setup.warm_s"] \
        - sum(_seconds(found, name) for name in STAGES)


def _total_s(row: dict) -> float:
    return sum(row[ms] for _, ms in FIELDS) / 1e3


def compiled(found: dict) -> list:
    """``[(program, compiles, seconds)]`` of the programs XLA compiled."""
    return sorted(((key, row["compiles"], row["compile_ms"] / 1e3)
                   for key, row in found["programs"].items()
                   if row["compiles"]), key=lambda x: -x[2])


def render(found: dict, top: int = 25) -> str:
    phases = found["phases"]
    whole = phases["setup.build_s"] + phases["setup.warm_s"]
    lines = [f"{found['workload']} seed {found['seed']} on "
             f"{found['device']['count']} x {found['device']['kind']}, "
             f"compile cache {found['compile_cache']}", "",
             "| phase | s |", "| --- | --- |"]
    lines += [f"| `{name}` | {s:.2f} |" for name, s in phases.items()]
    lines += ["", "| monitor | entries | s | % of build_s + warm_s |",
              "| --- | --- | --- | --- |"]
    for name in STAGES:
        if name in found["monitors"]:
            lines.append(f"| `{name}` | {found['monitors'][name]['count']} "
                         f"| {_seconds(found, name):.2f} | "
                         f"{100 * _seconds(found, name) / whole:.1f} |")
    left = unnamed_s(found)
    lines.append(f"| under none of the four (a floor: threads overlap) | "
                 f"| {left:.2f} | {100 * left / whole:.1f} |")
    for name in ENCLOSING:
        if name in found["monitors"]:
            lines.append(f"| `{name}` (ENCLOSING the builds inside it) | "
                         f"{found['monitors'][name]['count']} | "
                         f"{_seconds(found, name):.2f} | "
                         f"{100 * _seconds(found, name) / whole:.1f} |")
    harness = found["harness"]
    agree = all(
        found["monitors"].get(name, {}).get("count") == harness[key]
        for name, key in (("PROGRAM_COMPILE", "programs_compiled"),
                          ("PROGRAM_CACHE_READ", "from_persistent_cache")))
    lines += ["", f"the harness's listener: {harness['programs_built']} "
              f"programs, {harness['from_persistent_cache']} from the "
              f"persistent cache, {harness['programs_compiled']} compiled: "
              + ("the monitors agree" if agree else "THE MONITORS DISAGREE"
                 if "PROGRAM_COMPILE" in found["monitors"]
                 else "this tree does not listen (before PR 68)"),
              f"stage events heard (every nested trace is one; "
              f"`tools/span_cost.py` has the microseconds an event): "
              f"{found['stage_events']}",
              "", f"{len(found['programs'])} programs by name, the "
              f"{min(top, len(found['programs']))} largest:", "",
              "| program | total s | " + " | ".join(
                  f"{counted} | s" for counted, _ in FIELDS) + " |",
              "| --- | --- | " + " | ".join("--- | ---" for _ in FIELDS)
              + " |"]
    rows = sorted(found["programs"].items(),
                  key=lambda item: -_total_s(item[1]))
    for key, row in rows[:top]:
        lines.append(f"| `{key}` | {_total_s(row):.3f} | "
                     + " | ".join(f"{row[counted]} | {row[ms] / 1e3:.3f}"
                                  for counted, ms in FIELDS) + " |")
    made = compiled(found)
    lines += ["", f"programs XLA compiled: {len(made)}"
              + (" (a warm cache should read none)" if made else "")]
    lines += [f"  {key}: {n} x, {s:.2f} s" for key, n, s in made]
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--top", type=int, default=25,
                        help="rows of the by-program table to print")
    parser.add_argument("--json", default="",
                        help="file to write everything counted to")
    parser.add_argument("--rehearse", action="store_true",
                        help="the cell's tiny sizes, on any backend")
    args = parser.parse_args(argv)
    found = set_up(args.workload, args.seed, args.rehearse)
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(found, f)
    print(render(found, args.top), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 - as benchmark/run.py: out, not hung
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    sys.exit(code)
