"""``PSLMTrainer`` through the actors on the CPU at small widths: three
steps against three steps of the reference's loop (benchmark/reference/
lm_step.py), every table and both of its moments; what a step counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark.reference import lm_step as ref
from multiverso_tpu.models.lm import LMConfig, PSLMTrainer, zipf_tokens
from multiverso_tpu.util import dashboard
from tests.test_lm_model import CONFIG

T, B, STEPS = 32, 2, 3
LR, B1, B2, EPS = 3e-4, 0.9, 0.95, 1e-8


def _state(trainer, table):
    """The server's side of a table: (weights, m, v, t) as numpy."""
    server = table.zoo.server_tables[table.table_id]
    m, v, t = server._engine.state
    n = server.my_rows if hasattr(server, "my_rows") else server.size
    cut = (lambda a: np.asarray(a)[:n, :table.num_col]) \
        if hasattr(table, "num_col") else (lambda a: np.asarray(a)[:n])
    return np.asarray(table.get_device()), cut(m), cut(v), int(t)


def _as_reference(tables):
    """The tables' values in the reference's tree."""
    layers = {}
    for name, value in tables.items():
        if name.startswith("layer"):
            layer, part = name.split(".")
            layers.setdefault(int(layer[5:]), {})[part] = value
    return {"embedding": tables["embedding"], "head": tables["head"],
            "final_norm": tables["final_norm"],
            "layers": [layers[i] for i in sorted(layers)]}


def _flat(tree):
    out = {"embedding": tree["embedding"], "head": tree["head"],
           "final_norm": tree["final_norm"]}
    for i, layer in enumerate(tree["layers"]):
        out.update({f"layer{i}.{n}": v for n, v in layer.items()})
    return out


@pytest.fixture(scope="module")
def run():
    """Three steps through the tables, and the reference's three beside
    them from the same start, given each step's chosen experts."""
    from multiverso_tpu.util import configure
    mv.init(["-updater_type=adam"])
    try:
        cfg = LMConfig.from_dict(CONFIG)
        trainer = PSLMTrainer(cfg, T, B, seed=3, lr=LR, beta1=B1, beta2=B2,
                              eps=EPS)
        tables = trainer.tables()
        start = {n: jnp.asarray(_state(trainer, t)[0])
                 for n, t in tables.items()}
        before = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        chosen, losses = [], []

        forward = dict(trainer._forward)
        for kind, program in forward.items():
            def spy(*args, _program=program):
                out = _program(*args)
                chosen[-1].append(out[3])
                return out
            trainer._forward[kind] = spy
        key = jax.random.PRNGKey(5)
        batches = [zipf_tokens(jax.random.fold_in(key, i), (B, T + 1),
                               cfg.vocab) for i in range(STEPS)]
        for tokens in batches:
            chosen.append([])
            losses.append(float(trainer.step(tokens)))
        trainer.sync()
        trainer.flush_stats()
        after = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        got = {n: _state(trainer, t) for n, t in tables.items()}

        c = ref.sizes(CONFIG)
        params = _as_reference(start)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        m, v, want_losses = zeros, zeros, []
        with ref.PRECISION:
            for t, tokens in enumerate(batches, start=1):
                loss, g = jax.value_and_grad(
                    lambda p: ref.step_loss(c, p, tokens, chosen[t - 1]))(
                        params)
                want_losses.append(float(loss))
                ids = tokens[:, :-1]
                new = jax.tree_util.tree_map(
                    lambda w, m_, v_, g_: ref.adam(w, m_, v_, t, g_, LR, B1,
                                                   B2, EPS), params, m, v, g)
                pick = lambda i: jax.tree_util.tree_map(     # noqa: E731
                    lambda x: x[i], new,
                    is_leaf=lambda x: isinstance(x, tuple))
                named = jnp.zeros(cfg.vocab, bool).at[ids.reshape(-1)].set(
                    True)[:, None]
                lazy = [jnp.where(named, pick(i)["embedding"], old["embedding"])
                        for i, old in enumerate((params, m, v))]
                params, m, v = pick(0), pick(1), pick(2)
                params["embedding"], m["embedding"], v["embedding"] = lazy
        want = {n: (np.asarray(_flat(params)[n]), np.asarray(_flat(m)[n]),
                    np.asarray(_flat(v)[n])) for n in tables}
        yield {"cfg": cfg, "losses": losses, "want_losses": want_losses,
               "got": got, "want": want, "start": start,
               "counters": (before, after)}
    finally:
        mv.shutdown()
        configure.reset_flags()


def test_forty_three_tables(run):
    assert len(run["got"]) == 43
    assert run["cfg"].parameters() == sum(
        w.size for w, *_ in run["got"].values())


def test_losses_follow_the_reference(run):
    for got, want in zip(run["losses"], run["want_losses"]):
        assert abs(got - want) < 2e-3 * want


@pytest.mark.parametrize("name", ["embedding", "head", "final_norm"] + [
    f"layer{i}.{n}" for i in range(4)
    for n in LMConfig.from_dict(CONFIG).layer_shapes()])
def test_table_and_moments_after_three_steps(run, name):
    """Adam divides the gradient by its own size, so a table's change
    after three steps is compared by its direction: bfloat16 products
    against float32 ones make the gradients differ by a few percent."""
    w, m, v, t = run["got"][name]
    want_w, want_m, want_v = run["want"][name]
    assert t == STEPS
    start = np.asarray(run["start"][name])
    norm = np.linalg.norm
    assert norm(m - want_m) < 6e-2 * norm(want_m), name
    assert norm(v - want_v) < 0.15 * norm(want_v), name
    assert norm((w - start) - (want_w - start)) \
        < 0.25 * norm(want_w - start), name
    assert norm(want_w - start) > 0


def test_rows_not_named_keep_weights_and_moments(run):
    w, m, v, _ = run["got"]["embedding"]
    quiet = ~(np.abs(m).sum(1) > 0)
    assert quiet.any() and not quiet.all()
    assert np.array_equal(w[quiet], np.asarray(run["start"]["embedding"])[quiet])
    assert not v[quiet].any()


def test_what_a_step_counts(run):
    before, after = run["counters"]

    def delta(name):
        return after[name]["count"] - before.get(name, {"count": 0})["count"]

    cfg = run["cfg"]
    assert delta("LM_STEP") == STEPS
    assert delta("LM_TOKENS") == STEPS * B * T
    whole = 4 * (cfg.parameters() - cfg.vocab * cfg.hidden)
    assert delta("LM_GET_BYTES") == delta("LM_ADD_BYTES") == STEPS * whole
    held = delta("LM_HELD_ASSIGNMENTS")
    assert 0 < held <= STEPS * cfg.n_layers * B * T * cfg.top_k
    assert held / (cfg.experts_held[1] * STEPS * cfg.n_layers * B) \
        <= delta("LM_EXPERT_MAX_TOKENS") / (STEPS * cfg.n_layers * B) <= T
    assert delta("LM_EXPERTS_SHORT") == STEPS * cfg.n_layers * B
    assert "LM_EXPERTS_FULL" not in after
    assert 0 < delta("LM_EMBED_ROWS") <= STEPS * min(B * T, cfg.vocab)
    # 43 whole or row Gets and 43 Adds a step, and the closing row Get
    assert delta("WORKER_PROCESS_GET") == STEPS * 43 + 1
    assert delta("WORKER_PROCESS_ADD") == STEPS * 43
