"""The routed experts' two buffers (``model.routed_experts``): a short one
of ``experts_capacity`` rows where a sequence's assignments on held experts
fit it, the full one of ``T * k`` rows where they do not, chosen on the
device. For the short buffer the criterion is equality to the bit, not a
tolerance: the result and every gradient against the full buffer's, at the
boundary routings, through each caller. The fallback is the full buffer
through XLA's grouped product, whatever form the short one takes: the same
sums, rounded in another order where the short one takes the kernel. Then
PR 42's suspected fault built on purpose, and the backward program's
arrays by shape."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.lm import PSLMTrainer, model as lm
from multiverso_tpu.models.lm import streams
from multiverso_tpu.util import dashboard
from tests import test_lm_mixed, test_lm_mla, test_lm_model

T, K, EXPERTS, FIRST, HELD = 512, 4, 32, 4, 4
EVERY = T * K       # 2048 assignments; the even share 256, the buffer 512
WIDTH = 128         # hidden and an expert's: the kernel's narrowest tile


def _config(config, **widths):
    return dataclasses.replace(
        lm.LMConfig.from_dict(dict(config, hidden_size=WIDTH, **widths)),
        n_experts=EXPERTS, top_k=K, experts_held=(FIRST, HELD))


CFG = _config(test_lm_model.CONFIG, moe_ffn_hidden_size=WIDTH)
CAP = lm.experts_capacity(CFG, T)


@pytest.fixture
def kernel(monkeypatch):
    """The grouped products as a TPU runs them: megablox's kernel wherever
    ``_use_gmm`` would pick it there, interpreted here (the fallback still
    asks for XLA's form by name). Its tiles are ``GROUPED_TILE_ROWS`` rows
    whatever the buffer's length, so a group's rows are summed in the same
    order in both buffers and the matrices' gradients are equal to the
    bit. XLA's ragged contraction on the CPU blocks a group's rows by the
    buffer's length: there they agree to the last place of float32 and
    no further (``FORMS``)."""
    monkeypatch.setattr(lm, "_use_gmm", lambda rows, k, n: True)
    for name in ("grouped_product", "grouped_outer"):
        monkeypatch.setattr(lm, name, functools.partial(
            getattr(lm, name), interpret=True))


#: the matrices' gradients' room, against their largest element: none in
#: the chip's form; float32's last place in XLA's on the CPU (read 2.9e-8
#: to 2.0e-7), where everything else is still equal to the bit
FORMS = {"kernel": 0.0, "xla": 1e-6}


#: the routings that take the fallback, and the room of a product summed
#: in another order under bfloat16 rows (against the largest element)
FALLBACK, FALLBACK_ROOM = {"cap+1", "every"}, 2.0 ** -6


@pytest.fixture(params=list(FORMS))
def form(request):
    if request.param == "kernel":
        request.getfixturevalue("kernel")
    return request.param


def test_the_capacity_is_twice_the_even_share_in_whole_tiles():
    assert (CAP, EVERY) == (512, 2048)
    # the four cells' sizes (ISSUE 49): positions, k, held of outputs;
    # st21b.ps-8k holds a quarter: twice that is half (all of them until
    # PR 59: tests/test_lm_experts_half.py has the two buffers there)
    for t, k, held, outputs, want in (
            (8192, 8, 16, 128, 16384), (8192, 8, 32, 256, 16384),
            (8192, 6, 16, 64, 24576), (4096, 8, 32, 256, 8192)):
        cfg = dataclasses.replace(CFG, n_experts=outputs, top_k=k,
                                  experts_held=(0, held))
        assert lm.experts_capacity(cfg, t) == want
    # a rehearsal's sequence, and a chip that holds a quarter, half, all
    assert lm.experts_capacity(CFG, 32) == 32 * K
    for held, want in ((EXPERTS // 4, EVERY // 2), (EXPERTS // 2, EVERY),
                       (EXPERTS, EVERY)):
        more = dataclasses.replace(CFG, experts_held=(0, held))
        assert lm.experts_capacity(more, T) == want
    less = dataclasses.replace(CFG, experts_held=(0, EXPERTS // 4 - 1))
    assert lm.experts_capacity(less, 4 * T) == 7 * 512     # of 8,192


# -- routings with a given number of assignments on held experts ------------

def _routing(n_live, one_expert=False, held=HELD):
    """ids [T, K] with ``n_live`` assignments on the ``held`` experts, from
    the first token on (or one a token, all on ONE held expert); every
    other assignment on experts this chip does not hold."""
    ids = np.empty((T, K), np.int32)
    away = [e for e in range(EXPERTS) if not FIRST <= e < FIRST + held]
    left = n_live
    for i in range(T):
        here = min(1 if one_expert else K, left)
        left -= here
        on = [FIRST + 1] if one_expert else \
            [FIRST + (i + j) % held for j in range(here)]
        ids[i] = on[:here] + [away[(i + j) % len(away)]
                              for j in range(K - here)]
    assert left == 0
    return jnp.asarray(ids)


def _zipf_routing(seed):
    """Each token's K distinct experts drawn with Zipf weights over the
    router's outputs, the held ones neither first nor last."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, EXPERTS + 1)
    return jnp.asarray(np.stack([
        rng.choice(EXPERTS, K, replace=False, p=p / p.sum())
        for _ in range(T)]).astype(np.int32))


ROUTINGS = {
    "none": lambda: _routing(0), "one": lambda: _routing(1),
    "cap-1": lambda: _routing(CAP - 1), "cap": lambda: _routing(CAP),
    "cap+1": lambda: _routing(CAP + 1), "every": lambda: _routing(EVERY),
    "one-expert": lambda: _routing(T, one_expert=True),
    "zipf": lambda: _zipf_routing(3)}


def _operands(dtype, seed=0, cfg=CFG):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(      # noqa: E731
        rng.normal(0, 0.3, shape), jnp.float32)
    h, w, held = cfg.hidden, cfg.expert_width, cfg.experts_held[1]
    mats = {"w_gate": draw(held * h, w).astype(lm.BF16),
            "w_up": draw(held * h, w).astype(lm.BF16),
            "w_down": draw(held * w, h).astype(lm.BF16)}
    weights = jax.nn.softmax(draw(T, K), axis=-1)
    # float32: the stream, normed inside; bfloat16: the normed input
    norm = 1 + draw(h) if dtype == "float32" else None
    return mats, draw(T, h).astype(dtype), weights, norm, draw(T, h)


def _pulled(fn, mats, h, weights, norm, dy):
    """``fn(sinks, h, weights, norm)`` and its pull of ``dy``, as arrays by
    name."""
    sinks = {n: jnp.zeros(m.shape, jnp.float32) for n, m in mats.items()}
    out, pull = jax.vjp(fn, sinks, h, weights, norm)
    d_sinks, dh, dw, d_norm = pull(dy)
    got = {"out": out, "dh": dh, "dweights": dw, **d_sinks}
    if norm is not None:
        got["dnorm"] = d_norm
    return got


def _chosen(mats, ids, *rest, cfg=CFG):
    return jax.jit(lambda: _pulled(
        lambda s, h, w, norm: lm.routed_experts(cfg, mats, s, h, ids, w,
                                                norm)[0], mats, *rest))()


def _full(mats, ids, *rest, cfg=CFG, n=EVERY):
    """Today's lines in ONE buffer of ``n`` rows (all ``T * K``, or fewer
    that hold every held assignment), differentiated as they were:
    ``jax.vjp`` straight through them."""
    order, sizes = lm.held_groups(cfg, ids)
    back = jnp.argsort(order).astype(jnp.int32)
    return jax.jit(lambda: _pulled(
        lambda s, h, w, norm: lm._experts_in(cfg, n, mats, s, h, w, norm,
                                             order, back, sizes),
        mats, *rest))()


def _equal(a, b):
    """To the bit (bfloat16 read as float32)."""
    return np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def _close(a, b, room):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return np.abs(a - b).max() <= room * max(np.abs(b).max(), 1e-30)


def _assert_equal(got, want, room=0.0, everywhere=False):
    """To the bit, but for the matrices' gradients (or ``everywhere``)
    within ``room`` of their largest element where there is room."""
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        if room and (everywhere or name in lm.DENSE):
            assert _close(got[name], want[name], room), name
        else:
            assert _equal(got[name], want[name]), name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_chosen_buffer_equals_the_full_one_to_the_bit(routing, dtype,
                                                          form):
    ids = ROUTINGS[routing]()
    mats, *rest = _operands(dtype)
    got, want = _chosen(mats, ids, *rest), _full(mats, ids, *rest)
    if routing not in FALLBACK:
        _assert_equal(got, want, FORMS[form])
    elif form == "xla":     # the fallback IS the full buffer in this form
        _assert_equal(got, want)
    else:                   # XLA's product against the kernel's
        _assert_equal(got, want, FALLBACK_ROOM, everywhere=True)
    live = int(jnp.sum(lm.held_groups(CFG, ids)[1]))
    if live:    # the case is not vacuous: something came back
        assert float(jnp.abs(want["out"]).max()) > 0
        assert float(jnp.abs(want["w_down"]).max()) > 0


@pytest.mark.parametrize("routing", ["cap-1", "cap", "zipf"])
def test_the_short_buffer_alone_equals_the_full_one(routing, kernel):
    """``_experts_in`` at the capacity, not chosen by a ``cond``: the
    short path itself, so that a fallback that always engaged could not
    pass for it."""
    ids = ROUTINGS[routing]()
    mats, *rest = _operands("bfloat16", seed=1)
    _assert_equal(_full(mats, ids, *rest, n=CAP), _full(mats, ids, *rest))


# -- PR 42's suspected fault, built on purpose -------------------------------

@pytest.mark.parametrize("n_live, caught", [(CAP, True), (CAP - 1, False)])
def test_an_unclamped_sum_back_is_caught_at_the_boundary(monkeypatch, kernel,
                                                         n_live, caught):
    """A sum back that reads ``rows[back]`` from the short buffer as it is:
    the gather clamps an index past the end to the LAST row, which is zero
    unless the live rows reach the buffer's end. So the fault shows for
    some routings alone (a full short buffer), as PR 42's did for one seed
    of many; the boundary case above is the one that decides."""
    ids = _routing(n_live)
    mats, *rest = _operands("bfloat16")
    want = _full(mats, ids, *rest)
    monkeypatch.setattr(lm, "_rows_at", lambda rows, back: rows[back])
    got = _chosen(mats, ids, *rest)
    differs = [n for n in want if not _equal(got[n], want[n])]
    assert bool(differs) == caught, differs
    if caught:      # the forward sum and what flows back into the tokens
        assert {"out", "dh"} <= set(differs)


# -- through each caller -----------------------------------------------------

def _route_to_held(monkeypatch):
    """Every token's K experts the held ones (the weights stay the
    router's own, so its gradient flows): the sequence takes the full
    buffer."""
    route = lm.route

    def to_held(cfg, router, x, bias=None):
        held = FIRST + (jnp.arange(K) + jnp.arange(x.shape[0])[:, None]) % HELD
        return held.astype(jnp.int32), route(cfg, router, x, bias)[1]

    monkeypatch.setattr(lm, "route", to_held)


def _one_buffer(monkeypatch):
    """Every capacity the whole ``T * k``: today's one path."""
    monkeypatch.setattr(lm, "EXPERTS_SHORT_SHARES", 10 ** 6)


def _layer(cfg, index, seed):
    rng = np.random.default_rng(seed)
    return {n: jnp.asarray(rng.normal(0, 0.2, s), jnp.float32)
            for n, s in cfg.layer_shapes(index).items()}


@pytest.fixture(params=["short", "full"])
def fits(request, monkeypatch):
    """The caller's sequence takes the short buffer by its own routing, or
    the full one (every token routed to the held experts): what its count
    of held assignments then has to satisfy."""
    if request.param == "full":
        _route_to_held(monkeypatch)
        return lambda n: n > CAP
    return lambda n: 0 < n <= CAP


def _twice(monkeypatch, run, fits):
    """``run()`` with the two buffers and again with one: the same to the
    bit where the sequence fits the short buffer, and within the
    fallback's room (XLA's product against the kernel's) where it does
    not. ``run`` returns ``(held assignments, everything compared)``."""
    live, got = run()
    assert fits(int(live)), int(live)
    short = fits(1)
    _one_buffer(monkeypatch)
    leaves, tree = jax.tree_util.tree_flatten(got)
    again, same = jax.tree_util.tree_flatten(run()[1])
    assert tree == same
    for a, b in zip(leaves, again):
        assert _equal(a, b) if short or a.dtype == jnp.int32 \
            else _close(a, b, FALLBACK_ROOM)


MIXED = _config(test_lm_mixed.CONFIG, moe_intermediate_size=WIDTH)
MLA = _config(test_lm_mla.CONFIG, moe_intermediate_size=WIDTH,
              num_nextn_predict_layers=0)


def test_sparse_vjp_through_either_buffer(monkeypatch, kernel, fits):
    p = _layer(MIXED, 1, 11)
    mats = {n: p[n].astype(lm.BF16) for n in MIXED.matrices(1)}
    small = {n: p[n] for n in p if n not in mats}
    rng = np.random.default_rng(12)
    u, dv = (jnp.asarray(rng.normal(size=(T, MIXED.hidden)), jnp.float32)
             for _ in range(2))

    def run():
        @jax.jit
        def both():
            sinks = lm._zeros_like_f32(mats)
            v, (ids, sizes, load), pull = lm.sparse_vjp(MIXED, mats, sinks,
                                                        small, u)
            return jnp.sum(sizes), (v, ids, load, pull(dv))
        return both()

    _twice(monkeypatch, run, fits)


def test_experts_block_with_its_norm_through_either_buffer(monkeypatch, kernel,
                                                           fits):
    """The first two families' experts as ``layer_forward`` and
    ``layer_grads`` call them: ``experts_block`` norms the stream itself
    and adds the residual, the routing is ``_route_layer``'s. (Not the
    whole layer's programs: around the experts XLA's CPU compiler fuses a
    sum into its neighbours differently beside a ``cond`` than without
    one, and ``dx`` moves in float32's last place; no operation of the
    experts does.)"""
    p = _layer(CFG, 0, 13)
    mats = {n: p[n].astype(lm.BF16) for n in lm.DENSE}
    rng = np.random.default_rng(14)
    a, dy = (jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
             for _ in range(2))

    def run():
        @jax.jit
        def both():
            ids, weights = lm._route_layer(CFG, p["router"], p["norm_ffn"], a)
            sizes = lm.experts_block(CFG, mats, lm._zeros_like_f32(mats),
                                     p["norm_ffn"], a, ids, weights)[1]
            y, pull = jax.vjp(
                lambda s, norm, a, w: lm.experts_block(CFG, mats, s, norm, a,
                                                       ids, w)[0],
                lm._zeros_like_f32(mats), p["norm_ffn"], a, weights)
            return jnp.sum(sizes), (y, ids, pull(dy))
        return both()

    _twice(monkeypatch, run, fits)


def test_the_streams_layer_through_either_buffer(monkeypatch, kernel, fits):
    p = _layer(MLA, 1, 15)
    mats = {n: p[n].astype(lm.BF16) for n in MLA.matrices(1)}
    small = {n: p[n] for n in p if n not in mats}
    rng = np.random.default_rng(16)
    x, dy = (jnp.asarray(rng.normal(size=(MLA.hc_mult * MLA.hidden, T)),
                         jnp.float32) for _ in range(2))

    def run():
        @jax.jit
        def both():
            y, (ids, sizes, load), pull = streams.layer_vjp(MLA, 1, mats,
                                                            small, x)
            return jnp.sum(sizes), (y, ids, load, pull(dy))
        return both()

    _twice(monkeypatch, run, fits)


# -- the counters: the device's choice, made again by the trainer -------------

@pytest.mark.parametrize("held, sparse, short, full", [
    # held assignments by layer and sequence; which layers are sparse
    ([[0, 1], [CAP - 1, CAP]], [1, 1], 4, 0),
    ([[CAP + 1, EVERY], [CAP, 7]], [1, 1], 2, 2),
    ([[0, 0], [CAP + 1, 3], [9, 9]], [0, 1, 1], 3, 1),    # a dense layer's
    ([[EVERY, EVERY]], [1], 0, 2)])
def test_the_trainer_counts_which_buffer_each_sequence_took(held, sparse,
                                                            short, full):
    """``PSLMTrainer._count_stats`` on made-up device counts: one count a
    sparse layer a sequence, SHORT where the held assignments fit the
    capacity, a dense layer's two zeros counted nowhere."""
    trainer = PSLMTrainer.__new__(PSLMTrainer)
    trainer.cfg, trainer._sparse, trainer._experts_cap = CFG, sparse, CAP
    trainer._attn_pass, trainer._heads = [], (1, 1)
    trainer._attn_blocks = []
    stats = [np.stack([np.asarray(row), np.zeros(len(row), int)], axis=1)
             for row in held]

    def counted():
        monitors = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        return [monitors.get(n, {"count": 0})["count"]
                for n in ("LM_EXPERTS_SHORT", "LM_EXPERTS_FULL")]

    before = counted()
    trainer._count_stats((stats, 5, 7))
    assert [a - b for a, b in zip(counted(), before)] == [short, full]


# -- the backward program's arrays, by shape ---------------------------------

def _braced(text, at):
    """``text`` from the first ``{`` at or after ``at`` to its match."""
    start = text.index("{", at)
    depth = 0
    for i in range(start, len(text)):
        depth += {"{": 1, "}": -1}.get(text[i], 0)
        if depth == 0:
            return text[start:i + 1]
    raise AssertionError("unbalanced")


def _float_arrays(jaxpr, rows):
    """Shapes of the floating-point arrays of ``rows`` rows and more than
    one column anywhere in ``jaxpr``, its sub-programs included."""
    found = set()
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = v.aval
            if getattr(aval, "ndim", 0) == 2 and aval.shape[0] == rows \
                    and aval.shape[1] > 1 \
                    and jnp.issubdtype(aval.dtype, jnp.floating):
                found.add((aval.shape, str(aval.dtype)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= _float_arrays(sub, rows)
    return found


def _conds(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _conds(sub)


def test_the_short_path_s_backward_program_holds_no_full_float32_buffer():
    """A residual kept from one branch for the other, zero-filled at full
    size, would lie OUTSIDE the ``case``: lowered, the backward program's
    own body holds no floating-point array of ``T * k`` rows outside its
    two ``case`` operations (the forward rule's, whose result is dead in a
    layer's backward program, and the pull's). And the pull's short branch
    holds no float32 array of ``T * k`` rows at all: its two sum-backs
    gather bfloat16 rows and widen them as [T, k, hidden]."""
    ids = ROUTINGS["zipf"]()
    mats, h, weights, _, dy = _operands("bfloat16")

    def backward(mats, h, weights, dy, ids):
        return _pulled(lambda s, h, w, norm: lm.routed_experts(
            CFG, mats, s, h, ids, w, norm)[0], mats, h, weights, None, dy)

    text = jax.jit(backward).lower(mats, h, weights, dy, ids).as_text()
    body = text[text.index("func.func public @main"):]
    body = body[:body.index("func.func private")]    # what it calls: below
    cases = [m.start() for m in re.finditer(r"stablehlo\.case", body)]
    assert len(cases) == 2
    wide = rf"tensor<{EVERY}x{WIDTH}x(f32|bf16)>"
    assert re.search(wide, body)            # inside the branches: the full one
    for at in reversed(cases):              # cut each case's regions out
        end = at
        while True:                         # region after region
            region = _braced(body, end)
            end = body.index(region, end) + len(region)
            if not re.match(r"\s*,\s*\{", body[end:]):
                break
        body = body[:at] + body[end:]
    assert not re.search(wide, body), re.findall(wide, body)

    conds = list(_conds(jax.make_jaxpr(backward)(mats, h, weights, dy,
                                                 ids).jaxpr))
    assert len(conds) == 2
    for cond in conds:
        full, short = (_float_arrays(b.jaxpr, EVERY)
                       for b in cond.params["branches"])    # false first
        assert ((EVERY, WIDTH), "float32") in full      # today's lines
        assert all(dtype == "bfloat16" for _, dtype in short), short
        assert _float_arrays(cond.params["branches"][1].jaxpr, CAP)
    pull = conds[1].params["branches"][1].jaxpr
    assert _float_arrays(pull, EVERY) == {((EVERY, WIDTH), "bfloat16")}
