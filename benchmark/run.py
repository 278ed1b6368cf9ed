#!/usr/bin/env python3
"""The benchmark's one command (see benchmark/README.md):

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, and everything that belongs to it by
name in files of their own: benchmark/configs/<config>.json,
benchmark/traffic/<traffic>.json, benchmark/drivers/<driver>.py (named
by the configuration) and benchmark/metrics/<metric>.py. This file
holds no table of cells, configurations or metrics.
"""

import time

_ENTERED = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 3.0   # the traced window that follows the measured one
DEADLINE_S = 1150     # a hang dumps every stack and exits non-zero


def process_age_at_entry() -> float:
    """Seconds from the start of this process (the kernel's record) to
    this file's first statement: interpreter start. 0.0 where /proc does
    not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK")) \
            - (time.monotonic() - _ENTERED)
        return age if 0.0 <= age < 30.0 else 0.0
    except (OSError, ValueError, IndexError, AttributeError):
        return 0.0


def log(message: str) -> None:
    print(f"[bench] {message}", flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def by_name(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"BENCHMARK.json names no {what} {name!r}")


def with_rehearsal(params: dict, rehearse: bool) -> dict:
    """A file's ``rehearsal`` group holds the tiny sizes the CPU tests
    run at; it is read only with --rehearse."""
    params = dict(params)
    tiny = params.pop("rehearsal", {})
    if rehearse:
        params.update(tiny)
    return params


def main(argv=None) -> int:
    t_process = _ENTERED - process_age_at_entry()
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes, any backend, no device metric "
                             "written: for the CPU tests")
    parser.add_argument("--keep-trace", default="",
                        help="directory to copy the .xplane.pb into")
    parser.add_argument("--dump-samples", default="",
                        help="file to write the window's caller-side "
                             "samples to, in order, as JSON")
    args = parser.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    from benchmark.lib import allocator
    malloc = allocator.hold_default()   # before anything large is freed

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = by_name(bench["workloads"], args.workload, "workload")
    entry = by_name(bench["configs"], cell["config"], "configuration")
    config = with_rehearsal(load_json(ROOT, entry["file"]), args.rehearse)
    traffic = with_rehearsal(
        load_json(HERE, "traffic", f"{cell['traffic']}.json"), args.rehearse)

    import jax
    from benchmark.lib.builds import ProgramBuilds
    builds = ProgramBuilds()
    setup_mark = builds.mark()
    t_jax = time.monotonic()
    devices = jax.devices()     # the attach: nothing of the program yet
    t_attached = time.monotonic()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"workload={cell['name']} seed={args.seed} platform="
        f"{device['platform']} device_kind={device['kind']} "
        f"devices={device['count']} allocator={malloc}")
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.rehearse:
        print(f"[bench] FAIL: the backend is {device['platform']!r}, not "
              "'tpu'; nothing was run", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"[bench] FAIL: the cell needs {cell['chips']} chips and JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    if len(devices) > cell["chips"]:
        print(f"[bench] warning: the cell is cut for {cell['chips']} chips "
              f"and the tables will spread over {len(devices)}",
              file=sys.stderr)

    from multiverso_tpu.util import compile_cache
    from benchmark.lib import harness, stats, xplane
    cache_dir = compile_cache.enable()
    driver_module = load_module("drivers", config["driver"])
    ctx = harness.Context(config, traffic, args.seed, builds, DEADLINE_S)
    driver = driver_module.Driver(ctx)
    t_imported = time.monotonic()

    driver.build()
    t_built = time.monotonic()
    driver.warm()
    # Every window starts from the same collector state. A process this
    # young has most of its long-lived objects still pending, so a full
    # collection (80-170 ms here) falls into some windows and not others;
    # a long-running trainer is past that. Collections in the window are
    # on its log line.
    gc.collect()
    setup_builds = builds.since(setup_mark)
    window = driver.measure(args.seconds)
    phases = {
        "setup.import_s": (t_jax - t_process) + (t_imported - t_attached),
        "setup.attach_s": t_attached - t_jax,
        "setup.build_s": t_built - t_imported,
        "setup.warm_s": window.t_start - t_built}
    log("setup " + json.dumps({
        **phases, "process_start_to_window_s": sum(phases.values()),
        "setup.programs_compiled": setup_builds["programs_compiled"],
        "programs_built": setup_builds["programs_built"],
        "compile_cache": cache_dir}))
    log("window " + json.dumps({
        "seconds": window.seconds, "rounds": window.rounds,
        "work": window.work, "attempted": window.attempted,
        "failed": window.failed, "programs_built": window.builds,
        "gc_pauses": window.gc_pauses,
        "samples": {k: [len(v), stats.median(v)]
                    for k, v in window.samples.items()}}))

    if args.dump_samples:
        with open(args.dump_samples, "w") as f:
            json.dump(window.samples, f)

    traced, reduced = None, None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # The Python tracer records every call of every thread and slows
        # the host it shares with the loop, so the traced window would read
        # the profiler's idle time as the program's. The host tracer stays
        # as it is: the ``mv:`` and ``bench:`` spans are its events.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        try:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            ctx.annotate(True)
            try:
                traced = driver.measure(TRACE_SECONDS)
            finally:
                ctx.annotate(False)
                jax.profiler.stop_trace()
            t_stopped = time.monotonic()
            path = xplane.find_xplane(trace_dir)
            file_bytes = os.path.getsize(path)
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(path, args.keep_trace)
            reduced = xplane.reduce(xplane.load(path))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        log("traced " + json.dumps({
            "seconds": traced.seconds, "rounds": traced.rounds,
            "device_planes": reduced and reduced["device_count"],
            "file_bytes": file_bytes,
            "reduction_s": time.monotonic() - t_stopped}))

    # the peak after the window, before the checks allocate their own
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices), default=0)
    problems = driver.check()
    driver.close()
    for problem in problems:
        log(f"incorrect: {problem}")

    device["memory_peak_bytes"] = peak
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    peaks = load_json(HERE, "peaks.json").get(device["kind"])
    if on_chip and peaks is None:
        print(f"[bench] FAIL: no peaks recorded for {device['kind']!r} in "
              "benchmark/peaks.json", file=sys.stderr)
        return 1
    obs = harness.Observations(
        phases=phases, setup_builds=setup_builds, window=window,
        traced=traced, trace=reduced, shapes=ctx.shapes, peaks=peaks,
        device=device, config=config, traffic=traffic)
    metrics = {}
    for metric in bench["per_layer" if args.trace else "end_to_end"]:
        if cell["name"] not in metric.get("workloads", [cell["name"]]):
            continue
        if not on_chip and metric["source"] != "program_counter":
            continue    # no time, rate or share is written from a CPU run
        value = load_module("metrics", metric["name"]).read(obs)
        if value is not None:
            metrics[metric["name"]] = {"value": value,
                                       "unit": metric["unit"]}
    result = {"correct": not problems,
              "attempted": window.attempted, "failed": window.failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = xplane.breakdown(reduced)
    # each number the check compared, beside its limit: last in the line,
    # and the last lines on standard error
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, (value, limit) in driver.compared.items()}
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps(result), flush=True)
    for name, (value, limit) in driver.compared.items():
        print(f"[bench] compared {name} {value!r} limit {limit!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    try:
        code = main()
    except BaseException:  # noqa: BLE001 - not survived: reported, then out
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        # Actor threads may still be parked on the device; leave without
        # waiting for them, so that a failure never turns into a hang.
        os._exit(1)
    sys.exit(code)
