"""Driver for a language model trained through the parameter server
(multiverso_tpu/models/lm ``PSLMTrainer``), set up in the order
models/lm/main.py run() sets it up: mv.init under ``-updater_type=adam``,
the trainer (which creates its 43 tables), then ``step`` on a fresh batch
of tokens each time.

A round is one step: the embedding rows' Get by device keys, 42
whole-table device Gets, the layer programs, 42 whole-table device Adds
and the embedding's device-key Add, closed loop, one step in flight. The
batches (``batches`` of them, Zipf over the vocabulary slice, the next
token the target) are drawn on the device from ``--seed`` before the
window. The measured window closes at the first step boundary after
``seconds`` by a Get of one row of the last table written
(``trainer.sync``); ``work["words"]`` is tokens trained.

``check`` runs one more step at the cell's sizes through the trainer's
own programs and holds it to benchmark/reference/lm_step.py on the same
device; see ``check``.
"""

import functools
import math
import time

import numpy as np

from benchmark.reference import lm_step as ref

# What ``check`` compares, in the order of the configuration's ``limits``
# (the numbers and their readings are the configuration's and PERF.md's,
# section 4). Every gradient is held to the reference's TABLE BY TABLE,
# relative L2 error against the table's own norm, the worst table of each
# kind against the kind's limit:
# - ``loss``: the step's loss against the reference's, relative;
# - ``gradient.table``: every table but those of the next kind: embedding,
#   head, the four attention projections, routers, up and down projections,
#   the attention's and the final norm;
# - ``gradient.gate``: the experts' gate matrices and the norm that feeds
#   them, where a gate value within rounding of zero flips relu's
#   derivative for its element;
# - ``adam.moments`` / ``adam.update``: every table's two moments and its
#   change after the Add against the reference's Adam applied to the
#   trainer's OWN gradient, relative L2, the worst table: float32 rounding
#   alone, so a moment kept in bfloat16 (2**-9) fails whatever the model
#   computed.
# A table's own norm is a yardstick only while the residual stream differs
# from token to token: the configuration draws the embedding at the size of
# a normed activation and warms the learning rate up for that reason
# (``assumed``), and ``routing.held_share`` / ``routing.max_over_mean`` by
# layer, reported beside the limits, say whether it held.
GATE = ("w_gate", "norm_ffn")


def _kind(name: str) -> str:
    return "gradient.gate" if name.rsplit(".", 1)[-1] in GATE \
        else "gradient.table"


class Driver:
    def __init__(self, ctx):
        # a checkout without the trainer fails here, before any actor
        # thread exists: at once and cleanly
        from multiverso_tpu.models.lm import LMConfig, PSLMTrainer  # noqa: F401
        self.ctx = ctx
        self.config, self.traffic = ctx.config, ctx.traffic
        self.T = int(self.traffic["seq_len"])
        self.B = int(self.traffic["sequences_per_step"])
        self.losses = []        # device scalars, one a step
        self.compared = {}      # what check() compared: [value, limit]
        self._next = 0

    # -- set-up ---------------------------------------------------------
    def build(self):
        import jax
        import multiverso_tpu as mv
        from multiverso_tpu.models.lm import (LMConfig, PSLMTrainer,
                                              zipf_tokens)
        assert self.traffic["trainer"] == "ps"
        seed = self.ctx.seed % (2 ** 31 - 1)
        mv.init(["-updater_type=adam",
                 f"-rpc_timeout_s={self.ctx.deadline_s}"])
        self.cfg = LMConfig.from_dict(self.config)
        opt = self.config["optimizer"]
        self.trainer = PSLMTrainer(
            self.cfg, self.T, self.B, seed=seed % (2 ** 24), lr=opt["lr"],
            beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["eps"],
            init_std=self.config["init_std"],
            embedding_std=self.config["embedding_init_std"],
            warmup_steps=opt["warmup_steps"])
        n = int(self.traffic["batches"])
        exponent = self.traffic["token_distribution"]["exponent"]
        pool = jax.jit(lambda key: zipf_tokens(
            key, (n + 1, self.B, self.T + 1), self.cfg.vocab, exponent))(
                jax.random.PRNGKey(seed))
        self.batches = [pool[i] for i in range(n)]
        self.check_batch = pool[n]
        jax.block_until_ready(self.batches)
        c = self.cfg
        self.ctx.shapes.update(
            family="lm", sequences=self.B, seq_len=self.T, hidden=c.hidden,
            heads=c.n_heads, kv_heads=c.n_kv_heads, head_dim=c.head_dim,
            router_outputs=c.n_experts, held=c.experts_held[1],
            expert_width=c.expert_width, vocab=c.vocab, layers=c.n_layers,
            window=c.window, window_layout=list(c.window_layout),
            parameters=c.parameters())

    def _step(self):
        tokens = self.batches[self._next % len(self.batches)]
        self._next += 1
        self.losses.append(self.trainer.step(tokens))

    def warm(self):
        for _ in range(int(self.traffic["warm_steps"])):
            self._step()
        self.trainer.sync()
        self.trainer.flush_stats()

    # -- the window -------------------------------------------------------
    def measure(self, seconds: float):
        window = self.ctx.open_window()
        deadline = window.t_start + seconds
        first = len(self.losses)
        while True:
            with self.ctx.span("step"):
                self._step()
            window.rounds += 1
            if time.monotonic() >= deadline:
                break
        self.trainer.sync()
        self.trainer.flush_stats()
        self.ctx.close_window(window)
        window.attempted = window.rounds
        window.work["words"] = float(window.rounds * self.B * self.T)
        window.failed = sum(not math.isfinite(float(x))
                            for x in self.losses[first:])
        return window

    # -- after the window ---------------------------------------------------
    def check(self) -> list:
        """One more step, on a batch of its own drawn from the seed,
        through the trainer's own programs, against the reference on the
        same device: (1) the loss; (2) every table's gradient, relative
        L2 error; (3) every table and both of its moments after the Add
        against the reference's Adam applied to the TRAINER's gradient.

        The reference takes each token's set of experts from the program
        (the forward programs return them): the program's residuals
        differ from the reference's at bfloat16 rounding from layer 1
        on, and a near-tie would flip an expert and with it a whole
        token's gradient. ``routing.differs`` is the share of tokens
        whose set is not the reference's own choice; it is reported, not
        limited. The reference goes a sequence and a layer at a time and
        keeps its gradients on the host, so that it fits beside the
        tables; then the trainer's step runs with every table's Add
        wrapped from outside (``_Check.watch``), which compares each
        gradient as it leaves and the table's and the rule's state on
        both sides of the Add."""
        wrong = []
        if not all(math.isfinite(float(x)) for x in self.losses):
            wrong.append("non-finite step loss")
        self.compared["non_finite_losses"] = [len(wrong), 0]
        wrong += _Check(self).run()
        return wrong

    def close(self):
        import multiverso_tpu as mv
        self.trainer.close()
        del self.trainer
        mv.shutdown()


def _relative(a, b):
    import jax.numpy as jnp
    return jnp.linalg.norm((a - b).ravel()) / jnp.maximum(
        jnp.linalg.norm(b.ravel()), 1e-30)


@functools.lru_cache(maxsize=None)
def _adam_errors(by_rows: bool):
    """``(w0, m0, v0, g, w1, m1, v1, t, hyp, ids) -> (m's, v's, the
    change's relative error)`` against the reference's Adam, one compiled
    program a table shape. The moments come as stored (padded); ``hyp`` is
    (lr, beta1, beta2, eps)."""
    import jax

    def errors(w0, m0, v0, g, w1, m1, v1, t, hyp, ids):
        m0, v0, m1, v1 = (a[tuple(slice(0, n) for n in w0.shape)]
                          for a in (m0, v0, m1, v1))
        with ref.PRECISION:
            if by_rows:
                w, m, v = ref.adam_rows(w0, m0, v0, t, ids, g, *hyp)
            else:
                w, m, v = ref.adam(w0, m0, v0, t, g.reshape(w0.shape), *hyp)
        return (_relative(m1, m), _relative(v1, v),
                _relative(w1 - w0, w - w0))

    return jax.jit(errors)


class _Check:
    def __init__(self, driver):
        self.d = driver
        self.trainer, self.cfg = driver.trainer, driver.cfg
        self.c = ref.sizes(driver.config)
        self.worst = {}     # limit's name -> (value, table)
        self.by_table = {}  # table -> its gradient's error over its own norm
        self.rms = {}       # table -> its reference gradient's size an element
        self.grads = {}     # table -> the reference's gradient, on the host

    def note(self, name, value, table):
        value = float(value)
        if not value <= self.worst.get(name, (-1.0, ""))[0]:
            self.worst[name] = (value, table)

    # -- the program's forward pass, for each token's experts ---------------
    def chosen(self, tokens):
        t = self.trainer
        ids, _, _ = t._split(tokens)
        x = t.embedding.get_rows_device(ids)
        chosen = []
        for i, kind in enumerate(zip(self.cfg.rope_layout,
                                     self.cfg.window_layout)):
            mats, small = t._pull_layer(i)
            x, _, _, layer_ids = t._forward[kind](mats, small, x)
            chosen.append(layer_ids)
        return chosen

    def loads(self, chosen):
        """By layer: the share of the step's assignments that fell on held
        experts (a quarter when even) and the fullest held expert's over
        the mean held expert's."""
        first, count = self.cfg.experts_held
        out = []
        for ids in chosen:
            counts = np.bincount(np.asarray(ids).ravel(),
                                 minlength=self.cfg.n_experts)
            held = counts[first:first + count]
            out.append((held.sum() / counts.sum(),
                        held.max() / max(held.mean(), 1e-30)))
        return out

    # -- the reference, a sequence and a layer at a time --------------------
    def reference(self, tokens, chosen):
        import jax
        import jax.numpy as jnp
        c, t = self.c, self.trainer
        ids, targets = tokens[:, :-1], tokens[:, 1:]
        total = targets.size
        kinds = ref.kinds(c)

        def pull(i):
            return {n: table.get_device().reshape(self.cfg.layer_shapes()[n])
                    for n, table in t.layers[i].items()}

        @functools.lru_cache(maxsize=None)
        def forward_of(rope, window):       # one program a kind of layer
            return jax.jit(lambda p, x, s: ref.layer(c, rope, window, p, x, s))

        @functools.lru_cache(maxsize=None)
        def backward_of(rope, window):
            return jax.jit(lambda p, x, s, dy: jax.vjp(
                lambda p, x: ref.layer(c, rope, window, p, x, s), p, x)[1](dy))

        own = jax.jit(lambda r, x: ref.routing(c, r, x)[0])
        embedding = t.embedding.get_device()
        inputs, x, differs = [], embedding[ids], []
        del embedding
        for i, kind in enumerate(kinds):
            p, forward = pull(i), forward_of(*kind)
            inputs.append(x)
            differs.append(sum(
                float(jnp.mean(jnp.any(
                    jnp.sort(own(p["router"], x[b]), -1)
                    != jnp.sort(chosen[i][b], -1), axis=-1)))
                for b in range(x.shape[0])) / x.shape[0])
            x = jnp.stack([forward(p, x[b], chosen[i][b])
                           for b in range(x.shape[0])])
        head, norm = t.head.get_device(), t.final_norm.get_device()
        block = min(self.cfg.loss_block, self.d.T)
        loss_block = jax.jit(jax.value_and_grad(
            lambda x, h, n, y: ref.head_loss(c, h, n, x, y, total),
            (0, 1, 2)))
        loss, d_head, d_norm, dx = 0.0, 0.0, 0.0, []
        flat, flat_y = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
        for at in range(0, total, block):
            part, (dxb, dh, dn) = loss_block(
                flat[at:at + block], head, norm, flat_y[at:at + block])
            loss, d_head, d_norm = loss + part, d_head + dh, d_norm + dn
            dx.append(dxb)
        self.grads["head"] = np.asarray(d_head)
        self.grads["final_norm"] = np.asarray(d_norm)
        del head, d_head, flat, x
        dx = jnp.concatenate(dx).reshape(inputs[0].shape)
        for i in reversed(range(len(kinds))):
            p, backward = pull(i), backward_of(*kinds[i])
            total_p, parts = None, []
            for b in range(dx.shape[0]):
                dp, dxb = backward(p, inputs[i][b], chosen[i][b], dx[b])
                total_p = dp if total_p is None else jax.tree_util.tree_map(
                    jnp.add, total_p, dp)
                parts.append(dxb)
            dx = jnp.stack(parts)
            for n, g in total_p.items():
                self.grads[f"layer{i}.{n}"] = np.asarray(g).reshape(
                    t.layers[i][n].get_device().shape)
            del p, total_p
        self.grads["embedding"] = np.asarray(dx)    # a row a position
        self.rms = {n: float(np.sqrt(np.mean(np.square(g, dtype=np.float64))))
                    for n, g in self.grads.items()}
        return float(loss), differs

    # -- the trainer's step, each Add looked at from both sides ------------
    def watch(self):
        """Wrap every table's Add (the embedding's by rows, the others'
        whole) so that ``on_add`` sees it; returns what undoes it."""
        wrapped = []
        for name, table in self.trainer.tables().items():
            rows = table is self.trainer.embedding
            method = "add_rows_async" if rows else "add_async"
            send = getattr(table, method)

            def add(*args, _name=name, _table=table, _send=send, _rows=rows):
                ids, grad, option = args if _rows else (None,) + args
                return self.on_add(_name, _table, grad, ids, option,
                                   lambda: _send(*args))

            setattr(table, method, add)
            wrapped.append((table, method))
        return lambda: [delattr(t, m) for t, m in wrapped]

    def on_add(self, name, table, grad, ids, opt, send):
        import jax.numpy as jnp
        hyp = (opt.learning_rate, opt.momentum, opt.rho, opt.lambda_)
        want = jnp.asarray(self.grads.pop(name))
        got = grad.reshape(want.shape)
        error, norm = float(jnp.sum((got - want) ** 2)), float(
            jnp.sum(want ** 2))
        self.by_table[name] = (error / max(norm, 1e-60)) ** 0.5
        del want
        server = table.zoo.server_tables[table.table_id]
        w0 = table.get_device()
        m0, v0, t0 = (jnp.copy(s) for s in server._engine.state)
        msg_id = send()
        table.wait(msg_id)
        w1 = table.get_device()
        m1, v1, _ = server._engine.state

        em, ev, ew = _adam_errors(ids is not None)(
            w0, m0, v0, grad, w1, m1, v1, jnp.int32(int(t0) + 1),
            jnp.asarray(hyp, jnp.float32), ids)
        self.note("adam.moments", max(float(em), float(ev)), name)
        self.note("adam.update", ew, name)
        return msg_id

    def run(self) -> list:
        tokens = self.d.check_batch
        chosen = self.chosen(tokens)
        loads = self.loads(chosen)
        want_loss, differs = self.reference(tokens, chosen)
        del chosen
        undo = self.watch()
        try:
            loss = float(self.trainer.step(tokens))
            self.trainer.sync()
        finally:
            undo()
        self.worst["loss"] = (abs(loss - want_loss) / abs(want_loss), "")
        for name, own in self.by_table.items():
            self.note(_kind(name), own, name)
        wrong = []
        if self.grads:
            wrong.append(f"no Add for {sorted(self.grads)}")
        limits = {k: v for k, v in self.d.config["limits"].items()
                  if k != "what"}
        for name, limit in limits.items():
            value, table = self.worst.get(name, (float("nan"), ""))
            self.d.compared[name] = [value, limit]
            if not value <= limit:
                wrong.append(f"{name} {value:.3e} > {limit:g} ({table}; "
                             f"loss {loss:.6f} against {want_loss:.6f})")
        for i, share in enumerate(differs):
            self.d.compared[f"routing.differs.layer{i}"] = [share, 1.0]
        for i, (share, fullest) in enumerate(loads):    # reported alone
            self.d.compared[f"routing.held_share.layer{i}"] = [share, 1.0]
            self.d.compared[f"routing.max_over_mean.layer{i}"] = [
                fullest, float(self.cfg.experts_held[1])]
        print(f"[bench] check: loss {loss:.6f} reference {want_loss:.6f}; "
              f"worst by limit {self.worst}; share of tokens whose experts "
              f"differ from the reference's own choice, by layer: "
              f"{[round(s, 5) for s in differs]}; gradient error by table, "
              f"each against its own norm "
              f"{ {n: float(f'{v:.3g}') for n, v in self.by_table.items()} }"
              f"; the reference's "
              f"gradient an element, by table "
              f"{ {n: float(f'{v:.3g}') for n, v in self.rms.items()} }",
              flush=True)
        return wrong
