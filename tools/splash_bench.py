#!/usr/bin/env python3
"""The library's splash attention kernels alone, at lists of tile sizes, on
the chip this runs on (it refuses the CPU, but for ``--describe`` and
``--rehearse``):

    chiprun -- python3 tools/splash_bench.py [--kinds a,b] [--seed n]
        [--candidates "q,kv,c/q,kv,c/q,kv;..."] [--out chiprun_out/f.json]

A KIND is what ``model._splash`` is called with in a cell (``KINDS``: the
positions, the key-value heads of a sequence and the query heads of each, q
. k's and v's lanes, the mask); a CANDIDATE is the eight sizes of
``model.Blocks`` (forward ``block_q, block_kv, block_kv_compute`` / dkv the
same three / dq ``block_q, block_kv``). For each candidate of each kind,
from seeded inputs and warm: the forward program (the kernel that keeps no
residuals, what a layer's forward program runs) and the pulled program
(forward with residuals, dq, dkv: what a layer's backward program runs),
each kernel's milliseconds APART, the median over a profiler trace of
``REPEATS`` calls of each in turn (the operations named ``splash_mqa_fwd..`` / ``_dq..`` /
``_dkv..`` inside each program's runs on the device), and beside them the
host's clock around each program (``*_host_ms``: a call costs ~0.6 ms of its
own). The three kernels' sizes are independent fields, so one candidate
tries one setting of each and the best of each column is the best triple.
A kernel the compiler refuses (fast memory) goes back to 512 and says why.
The chosen rule's own sizes (``model.attention_blocks``) and 512 everywhere
run in every kind. One JSON line a kind; ``--out`` keeps them all.

``--describe`` only compiles each candidate for a described v5e, here on
the CPU, and says which fit (nothing runs: no time comes from it).
``--rehearse`` runs the tool's own path at 512 positions with the kernels
interpreted and writes no time.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from multiverso_tpu.models.lm import model as lm  # noqa: E402

REPEATS = 5
# a kernel (as its operations are named) and its fields of ``model.Blocks``
FIELDS = {"fwd": slice(0, 3), "dkv": slice(3, 6), "dq": slice(6, 8)}

# what ``_splash`` is called with, a sequence of one layer (the cells that
# call so): positions, key-value heads, query heads each, lanes, mask
KINDS = {
    # laguna33b.ps-8k's full layers (st21b.ps-8k: 4 x 7, solar250b.ps-8k)
    "causal-8k-128": dict(t=8192, groups=8, per_group=6, qk=128, v=128,
                          mask=0),
    "causal-8k-128x7": dict(t=8192, groups=4, per_group=7, qk=128, v=128,
                            mask=0),
    # laguna33b.ps-8k's window layers
    "window512-8k-128": dict(t=8192, groups=8, per_group=8, qk=128, v=128,
                             mask=512),
    # st21b.ps-8k's window layers
    "window4096-8k-128": dict(t=8192, groups=4, per_group=7, qk=128, v=128,
                              mask=4096),
    # glm30b.ps-8k: every head a group
    "causal-8k-256": dict(t=8192, groups=20, per_group=1, qk=256, v=256,
                          mask=0),
    # kimi48b.ps-8k's latent layer; xing29b.ps-4k's
    "causal-8k-192": dict(t=8192, groups=32, per_group=1, qk=192, v=128,
                          mask=0),
    "causal-4k-192": dict(t=4096, groups=4, per_group=1, qk=192, v=128,
                          mask=0),
    # lfm8b.ps-8k's two attention layers: heads of 64 lanes, half a tile
    "causal-8k-64": dict(t=8192, groups=8, per_group=4, qk=64, v=64,
                         mask=0),
    # sdar30b.ps-bd4k: 2 x 4096 positions, blocks of 4
    "blockdiff-8k-128": dict(t=8192, groups=4, per_group=8, qk=128, v=128,
                             mask=("blockdiff", 4096, 4)),
}

# (block_q, block_kv, block_kv_compute) of the forward and of the dkv
# kernel, (block_q, block_kv) of the dq kernel: a mask that reaches far
WIDE = [(512, 512, 512), (1024, 512, 512), (1024, 1024, 512),
        (1024, 1024, 1024), (2048, 512, 512), (2048, 1024, 512),
        (2048, 2048, 512), (512, 1024, 512), (512, 2048, 512),
        (1024, 2048, 512), (1024, 2048, 1024), (256, 512, 512),
        (256, 1024, 512), (512, 1024, 1024), (512, 512, 256),
        (1024, 1024, 256), (2048, 2048, 1024), (512, 4096, 512),
        (4096, 512, 512), (256, 2048, 512)]
WIDE_DQ = [(512, 512), (1024, 512), (1024, 1024), (512, 1024), (2048, 512),
           (2048, 1024), (2048, 2048), (512, 2048), (1024, 2048),
           (256, 512), (256, 1024), (256, 2048), (4096, 512), (512, 4096),
           (128, 1024), (128, 2048), (4096, 1024), (1024, 4096),
           (256, 256), (512, 256)]
# and one that reaches a block or less
NEAR = [(512, 512, 512), (256, 256, 256), (128, 128, 128), (256, 128, 128),
        (128, 256, 256), (512, 256, 256), (512, 128, 128), (256, 512, 512),
        (1024, 256, 256), (1024, 128, 128), (1024, 512, 512),
        (256, 512, 256), (512, 512, 256), (512, 512, 128), (2048, 256, 256),
        (2048, 128, 128), (2048, 512, 512), (128, 512, 512),
        (1024, 512, 256), (1024, 1024, 512)]
NEAR_DQ = [(512, 512), (256, 256), (128, 128), (256, 128), (128, 256),
           (512, 256), (512, 128), (256, 512), (1024, 256), (1024, 128),
           (1024, 512), (2048, 256), (2048, 128), (2048, 512), (128, 512),
           (4096, 128), (4096, 256), (1024, 1024), (4096, 512), (128, 1024)]


def mask_of(spec):
    return lm.Mask.blockdiff(*spec[1:]) if isinstance(spec, tuple) \
        else lm.Mask.of(spec)


def default_candidates(kind):
    """512 everywhere, the rule's own sizes for the kind, then a column of
    each kernel's settings side by side."""
    mask = mask_of(kind["mask"])
    near = mask.kind == "blockdiff" or (
        mask.kind == "window" and mask.window <= 1024)
    cols = (NEAR, NEAR, NEAR_DQ) if near else (WIDE, WIDE, WIDE_DQ)
    if mask.kind == "blockdiff":    # its clean half is causal: both lists
        cols = (NEAR + WIDE[1:], NEAR + WIDE[1:], NEAR_DQ + WIDE_DQ[1:])
    out = [lm.attention_blocks(mask, kind["t"], kind["qk"], kind["v"],
                               kind["per_group"])]
    out += [lm.Blocks(*f, *kv, *dq) for f, kv, dq in zip(*cols)]
    return list(dict.fromkeys(out))


def parse_candidates(text):
    """``"q,kv,c/q,kv,c/q,kv;..."`` -> [Blocks]."""
    out = []
    for one in text.split(";"):
        parts = [tuple(int(n) for n in part.split(","))
                 for part in one.strip().split("/")]
        assert [len(p) for p in parts] == [3, 3, 2], one
        out.append(lm.Blocks(*parts[0], *parts[1], *parts[2]))
    return out


def usable(blocks, t):
    """Whether the library takes ``blocks`` at ``t`` positions."""
    b = blocks
    return (all(t % n == 0 and n % 128 == 0 for n in b)
            and b.block_kv % b.block_kv_compute == 0
            and b.block_kv_dkv % b.block_kv_dkv_compute == 0)


def programs(kind, blocks, index, interpret=False):
    """``(forward, pulled)`` jitted, named by the candidate's index so a
    trace's programs are told apart."""
    kernel = lm._splash_at(kind["t"], kind["per_group"],
                           mask_of(kind["mask"]), blocks, interpret)

    def forward(q, k, v):
        return jax.vmap(kernel)(q, k, v)

    def pulled(q, k, v, do):
        return jax.vjp(forward, q, k, v)[1](do)

    forward.__name__, pulled.__name__ = f"fwd{index}x", f"pull{index}x"
    return jax.jit(forward), jax.jit(pulled)


def shapes(kind):
    g, p, t = kind["groups"], kind["per_group"], kind["t"]
    return ((g, p, t, kind["qk"]), (g, t, kind["qk"]), (g, t, kind["v"]),
            (g, p, t, kind["v"]))


def draw(kind, seed):
    """q (scaled), k, v and a cotangent of the output, bfloat16, on the
    device."""
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 4)
    scales = (kind["qk"] ** -0.5, 1.0, 1.0, 1.0)
    return tuple((jax.random.normal(key, shape) * s).astype(jnp.bfloat16)
                 for key, shape, s in zip(keys, shapes(kind), scales))


def settled(blocks, build):
    """``(line, build(blocks))`` with every kernel the compiler refuses
    put back to 512 and named under ``refused`` (its sizes, the reason), so
    that one kernel's refusal does not take the other two's settings with
    it; ``(line, None)`` where the refusal names no kernel."""
    line = {"blocks": list(blocks), "refused": {}}
    start = time.perf_counter()
    built = None
    for _ in range(len(FIELDS) + 1):
        try:
            built = build(blocks)
            break
        except Exception as e:  # noqa: BLE001  the compiler's refusal
            said = str(e)
            why = next((text.strip()[:200] for text in said.splitlines()
                        if "vmem" in text.lower() or "exceed" in text.lower()),
                       said.strip().splitlines()[-1][:200])
            kernel = next((k for k in FIELDS if f"splash_mqa_{k}" in said
                           and k not in line["refused"]), None)
            if kernel is None:
                line["why"] = why
                break
            line["refused"][kernel] = [list(blocks)[FIELDS[kernel]], why]
            sizes = list(blocks)
            sizes[FIELDS[kernel]] = [lm.PLAIN_BLOCK] * len(
                sizes[FIELDS[kernel]])
            blocks = lm.Blocks(*sizes)
    line["ran"] = list(blocks)
    line["build_s"] = time.perf_counter() - start
    return line, built


def describe(kind, candidates):
    """Which candidates the TPU's compiler takes at the kind's real shapes,
    for a described v5e (nothing runs)."""
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)
            for s in shapes(kind)]
    lines = []
    for index, blocks in enumerate(candidates):
        def build(blocks, index=index):
            forward, pulled = programs(kind, blocks, index)
            forward.lower(*args[:3]).compile()
            return pulled.lower(*args).compile()

        line, compiled = settled(blocks, build)
        if compiled is not None:
            line["temp_mb"] = \
                compiled.memory_analysis().temp_size_in_bytes / 1e6
        lines.append(line)
    return lines


def _host_ms(fn, *args):
    times = []
    for _ in range(REPEATS):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - start))
    return float(np.median(times))


def kernels_ms(trace_dir):
    """``{program stem: {kernel: median ms of its operations}}`` from a
    trace: each splash kernel's operations inside each program's runs on
    the device (the median over the runs: the profiler drops an event now
    and then, which a mean over the runs would read as a faster kernel);
    what else ran there, summed over a run, under ``other``."""
    from benchmark.lib import xplane, xspace
    trace = xspace.load(xplane.find_xplane(trace_dir))
    found = {}
    for device in trace["devices"].values():
        runs = sorted((a, b, xplane.stem(name))
                      for name, a, b in device["modules"])
        for name, a, b, path in device["ops"]:
            inside = [(lo, stem) for lo, hi, stem in runs
                      if lo <= a and b <= hi]
            if not inside:
                continue
            run, stem = inside[0]
            which = next((k for k in FIELDS
                          if f"splash_mqa_{k}" in name + " " + path), "other")
            of = found.setdefault(stem, {}).setdefault(which, {})
            of[run] = of.get(run, 0.0) + (b - a) / 1e6
    return {stem: {which: float(np.median(list(by_run.values())))
                   for which, by_run in kernels.items()}
            for stem, kernels in found.items()}


def measure(kind, candidates, seed, interpret=False):
    """A line a candidate: its kernels' milliseconds, or why it did not
    build."""
    args = draw(kind, seed)
    lines, built = [], []
    for index, blocks in enumerate(candidates):
        def build(blocks, index=index):
            forward, pulled = programs(kind, blocks, index, interpret)
            jax.block_until_ready(forward(*args[:3]))
            jax.block_until_ready(pulled(*args))
            return forward, pulled

        line, programs_ = settled(blocks, build)
        if programs_:
            built.append((index, line, *programs_))
        lines.append(line)
    if interpret:       # a rehearsal: the path, no time
        return lines
    for _, line, forward, pulled in built:
        line["forward_host_ms"] = _host_ms(forward, *args[:3])
        line["pulled_host_ms"] = _host_ms(pulled, *args)
    trace_dir = tempfile.mkdtemp(prefix="splash_bench_")
    try:
        jax.profiler.start_trace(trace_dir)
        for _ in range(REPEATS):    # in turn: a drift falls on every one
            for _, _, forward, pulled in built:
                jax.block_until_ready(forward(*args[:3]))
                jax.block_until_ready(pulled(*args))
        jax.profiler.stop_trace()
        read = kernels_ms(trace_dir)
    except Exception as e:  # noqa: BLE001  the host's clock stays
        print(f"splash_bench: no trace read: {e!r}", file=sys.stderr)
        read = {}
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    for index, line, _, _ in built:
        f, p = (read.get(f"jit_{n}{index}x", {}) for n in ("fwd", "pull"))
        if "fwd" in f:
            line["forward_ms"] = f["fwd"]
        for k in FIELDS:
            if k in p:
                line[f"pulled_{k}_ms"] = p[k]
        if all(k in p for k in FIELDS):
            line["pulled_ms"] = sum(p[k] for k in FIELDS)
        if "other" in p:    # the row sums of o . do, the copies
            line["pulled_other_ms"] = p["other"]
    return lines


def best(lines):
    """Each kernel's fastest setting among the lines, beside 512's."""
    out = {}
    for key, fields in [("forward_ms", FIELDS["fwd"])] + [
            (f"pulled_{k}_ms", fields) for k, fields in FIELDS.items()]:
        timed = [(line[key], line["ran"][fields]) for line in lines
                 if key in line]
        if timed:
            plain = [ms for ms, b in timed if set(b) == {lm.PLAIN_BLOCK}]
            ms, blocks = min(timed)
            out[key] = {"best": blocks, "ms": ms,
                        "plain_ms": plain[0] if plain else None}
    return out


def main(argv) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--kinds", default=",".join(KINDS))
    parser.add_argument("--candidates", default="")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default="")
    parser.add_argument("--describe", action="store_true")
    parser.add_argument("--rehearse", action="store_true")
    opts = parser.parse_args(argv)
    on_chip = not (opts.describe or opts.rehearse)
    if on_chip and jax.default_backend() != "tpu":
        print("splash_bench: needs a TPU (or --describe, --rehearse)",
              file=sys.stderr)
        return 2
    device = jax.devices()[0]
    results = []
    for name in opts.kinds.split(","):
        kind = dict(KINDS[name])
        if opts.rehearse:   # the path alone: short, two groups
            kind.update(t=512, groups=2, mask=(
                ("blockdiff", 256, 4) if isinstance(kind["mask"], tuple)
                else min(kind["mask"], 256)))
        wanted = parse_candidates(opts.candidates) if opts.candidates \
            else default_candidates(kind)
        if opts.rehearse:
            wanted = [lm._blocks_within(b, kind["t"]) for b in wanted[:3]]
        plain = lm._blocks_within(lm.PLAIN_BLOCKS, kind["t"])
        candidates = list(dict.fromkeys(
            [plain] + [b for b in wanted if usable(b, kind["t"])]))
        result = {"kind": name, **kind,
                  "device": {"platform": device.platform,
                             "kind": device.device_kind},
                  "rule": list(lm.attention_blocks(
                      mask_of(kind["mask"]), kind["t"], kind["qk"],
                      kind["v"], kind["per_group"]))}
        if opts.describe:
            result["described"] = describe(kind, candidates)
        else:
            result["candidates"] = measure(kind, candidates, opts.seed,
                                           interpret=opts.rehearse)
            if on_chip:
                result["best"] = best(result["candidates"])
        results.append(result)
        print(json.dumps(result), flush=True)
    if opts.out:
        os.makedirs(os.path.dirname(opts.out) or ".", exist_ok=True)
        with open(opts.out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
