"""The routed experts' two buffers where the short one is HALF the ``T * k``
rows: a chip that holds a quarter of its experts (``st21b.ps-8k``, which had
ONE buffer of them all until PR 59). The same ``model.routed_experts`` and
the same criterion as tests/test_lm_experts_short.py, whose helpers these
are: to the bit against one buffer of every row where a sequence's
assignments on held experts fit the short one, the full buffer through
XLA's grouped product where they do not. Then a routing that overflows
against the benchmark's float32 reference within the cell's own limits,
and every cell's capacity from its configuration's file."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lm_step as ref
from multiverso_tpu.models.lm import model as lm
from tests import test_lm_experts_short as short
from tests.test_lm_experts_short import form, kernel    # noqa: F401

T, K, EXPERTS, FIRST, EVERY = short.T, short.K, short.EXPERTS, short.FIRST, \
    short.EVERY
HELD = EXPERTS // 4     # the even share 512, the short buffer 1024 of 2048
CFG = dataclasses.replace(short.CFG, experts_held=(FIRST, HELD))
CAP = lm.experts_capacity(CFG, T)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the capacity -------------------------------------------------------------

def test_a_quarter_of_the_experts_held_is_half_the_rows():
    assert (CAP, EVERY) == (1024, 2048)


@pytest.mark.parametrize("config, traffic, positions, every, want", [
    # the seven cells' configurations: a sequence's positions through the
    # layers, its assignments, the short buffer's rows (st21b.ps-8k: all
    # 49,152 until PR 59)
    ("smallthinker-21ba3b-l4", "lm-ps-step-8k", 8192, 49152, 24576),
    ("sdar-30b-a3b-l6", "lm-ps-blockdiff-4k", 8192, 65536, 16384),
    ("xing4-29b-a4b-l5", "lm-ps-step-4k", 4096, 16384, 4096),
    ("laguna-xs2-33b-a3b-l5", "lm-ps-step-8k", 8192, 65536, 16384),
    ("keye-vl2-30b-a3b-lm", "lm-ps-step-16k", 16384, 131072, 32768),
    ("kimi-linear-48b-a3b-l5", "lm-ps-step-8k", 8192, 65536, 4096),
    ("glm47-flash-30b-a3b-l5", "lm-ps-step-8k", 8192, 32768, 8192)])
def test_a_cell_s_short_buffer(config, traffic, positions, every, want):
    def read(*parts):
        with open(os.path.join(ROOT, "benchmark", *parts)) as f:
            return json.load(f)

    cfg = lm.LMConfig.from_dict(read("configs", f"{config}.json"))
    t = read("traffic", f"{traffic}.json")["seq_len"]
    t *= 1 + (cfg.objective == "block_diffusion")
    assert (t, t * cfg.top_k) == (positions, every)
    assert lm.experts_capacity(cfg, t) == want
    # whole tiles, and short of every row: each cell has the two buffers
    assert want % lm.GROUPED_TILE_ROWS == 0 and want <= every // 2


# -- routings with a given number of assignments on held experts ------------

def _routing(n_live, one_expert=False):
    return short._routing(n_live, one_expert, held=HELD)


def _routing_of(*counts):
    """ids [T, K] that give held expert ``e`` the first ``counts[e]``
    tokens: its rows lie at ``sum(counts[:e])`` on in the sorted order."""
    away = [e for e in range(EXPERTS) if not FIRST <= e < FIRST + HELD]
    ids = np.empty((T, K), np.int32)
    for i in range(T):
        held = [FIRST + e for e, n in enumerate(counts) if i < n]
        ids[i] = held + [away[(i + j) % len(away)]
                         for j in range(K - len(held))]
    return jnp.asarray(ids)


#: every assignment on a held expert fits the short buffer
FIT = {
    "none": lambda: _routing(0), "one": lambda: _routing(1),
    "cap-1": lambda: _routing(CAP - 1), "cap": lambda: _routing(CAP),
    "one-expert": lambda: _routing(T, one_expert=True),
    "zipf": lambda: short._zipf_routing(3)}
#: they do not: the buffer of every row. ``straddle``: expert 2's rows
#: are 900 .. 1199 of the sorted order, across the short buffer's end
OVERFLOW = {
    "cap+1": lambda: _routing(CAP + 1),
    "1.5-caps": lambda: _routing(CAP * 3 // 2),
    "straddle": lambda: _routing_of(400, 500, 300, 0, 200, 0, 0, 0),
    "every": lambda: _routing(EVERY)}
ROUTINGS = {**FIT, **OVERFLOW}

_operands = functools.partial(short._operands, cfg=CFG)
_chosen = functools.partial(short._chosen, cfg=CFG)
_in = functools.partial(short._full, cfg=CFG)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_chosen_buffer_equals_the_one_st21b_had_to_the_bit(routing, dtype,
                                                               form):
    ids = ROUTINGS[routing]()
    mats, *rest = _operands(dtype)
    got, want = _chosen(mats, ids, *rest), _in(mats, ids, *rest)
    if routing in FIT:
        short._assert_equal(got, want, short.FORMS[form])
    elif form == "xla":     # the fallback IS the full buffer in this form
        short._assert_equal(got, want)
    else:                   # XLA's product against the kernel's
        short._assert_equal(got, want, short.FALLBACK_ROOM, everywhere=True)
    live = int(jnp.sum(lm.held_groups(CFG, ids)[1]))
    assert (live <= CAP) == (routing in FIT)
    if live:    # the case is not vacuous: something came back
        assert float(jnp.abs(want["out"]).max()) > 0
        assert float(jnp.abs(want["w_down"]).max()) > 0


@pytest.mark.parametrize("routing", ["cap-1", "cap", "zipf"])
def test_the_half_buffer_alone_equals_the_full_one(routing, kernel):  # noqa: F811
    """``_experts_in`` at the capacity, not chosen by a ``cond``: a
    fallback that always engaged could not pass for it."""
    ids = FIT[routing]()
    mats, *rest = _operands("bfloat16", seed=1)
    short._assert_equal(_in(mats, ids, *rest, n=CAP), _in(mats, ids, *rest))


# -- a routing that overflows, against the benchmark's reference --------------

def _relative(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _reference(mats, ids, h, weights, dy):
    """benchmark/reference/lm_step.py ``experts`` on the same numbers:
    float32 ``jax.numpy``, every held expert over every token weighted by
    its ``w_e`` or by 0, no sorting and no buffer."""
    c = {"held": HELD, "hidden": CFG.hidden, "expert_width": CFG.expert_width}
    held = (ids[:, :, None] == FIRST + jnp.arange(HELD)).astype(jnp.float32)

    def fn(mats, h, weights):
        return ref.experts(c, h, jnp.einsum("tk,tke->te", weights, held),
                           mats["w_gate"], mats["w_up"], mats["w_down"])

    with ref.PRECISION:
        out, pull = jax.vjp(
            fn, {n: m.astype(jnp.float32) for n, m in mats.items()},
            h.astype(jnp.float32), weights)
        d_mats, dh, dw = pull(dy)
    return {"out": out, "dh": dh, "dweights": dw, **d_mats}


@pytest.mark.parametrize("routing", ["cap", *OVERFLOW])
def test_either_buffer_is_the_reference_s_within_the_cell_s_limits(routing,
                                                                   form):
    """Forward, ``dh``, ``dw`` and the three matrices' gradients, each
    against its own norm under st21b.ps-8k's limits (the gate's matrix
    under ``gradient.gate``: a gate value within rounding of zero flips
    relu's derivative)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21ba3b-l4.json")) as f:
        limits = json.load(f)["limits"]
    ids = ROUTINGS[routing]()
    mats, h, weights, _, dy = _operands("bfloat16", seed=5)
    got = _chosen(mats, ids, h, weights, None, dy)
    want = _reference(mats, ids, h, weights, dy)
    assert sorted(got) == sorted(want)
    for name in want:
        kind = "gradient.gate" if name == "w_gate" else "gradient.table"
        assert _relative(got[name], want[name]) <= limits[kind], name
