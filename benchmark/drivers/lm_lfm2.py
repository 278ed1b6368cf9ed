"""Driver for the ninth family of language model trained through the
parameter server (multiverso_tpu/models/lm ``PSLMTrainer`` on an
``LMConfig`` whose mixer is a LAYER's and in three layers of four NOT
attention: LFM2-8B-A1B's block, a doubly gated short convolution of three
taps, models/lm/shortconv.py, beside grouped-query attention with heads of
64 lanes under head norms and a rotary turn, ``model.attention_vjp``; two
leading dense layers, then sparse ones under a sigmoid router that chooses
through a bias the server keeps, 8 of 32 experts held, no shared expert;
ONE table for embedding and head): drivers/lm.py's set-up and window,
drivers/lm_kda.py's Add-by-Add comparison (each bias's Add exactly, no
second Add, a tensor's layers of one kind together), with this model's
shapes and reference.

A round is one step on a fresh batch of ``sequences_per_step`` x
(``seq_len`` + 1) tokens. ``work["words"]`` is ``B T`` a step.

``check`` runs one more step at the cell's sizes through the trainer's own
programs and holds it to benchmark/reference/lm_lfm2_step.py on the same
device, given each token's experts from the program and the SAME share
(held experts, the vocabulary's slice):

- ``layout.differs``: the layers whose mixer's kind is not the
  configuration's ``layer_types``' (the reference is told its kinds by the
  FILE; a program whose layer is of another kind has other tables, and
  nothing more can be compared: the check stops there);
- ``loss``; every tensor's gradient against its own norm by kind
  (``KINDS``): ``gradient.conv`` what a convolution layer's mixer brings,
  ``gradient.scores`` what feeds an attention layer's scores,
  ``gradient.router``, ``gradient.experts`` the routed experts' three,
  ``gradient.table`` every other;
- ``gradient.tied``: the ONE table's Add against the SUM of the
  reference's two gradients, the head's and the rows' (an Add by rows is
  spread over the table first: a trainer that pushed the rows' part alone
  is read against the same sum);
- every table and both moments after the Add (``adam.moments``,
  ``adam.update``), ``bias.differs``, ``adds.extra`` (a table's second Add,
  or an Add to a table the configuration does not have), ``routing.differs``,
  ``layer.output`` (each layer ALONE, forward, on the program's own input:
  drivers/lm_kda.py's reason).
"""

import math

import numpy as np

from benchmark.drivers import lm, lm_kda
from benchmark.reference import lm_lfm2_step as ref

ROUTED = lm_kda.ROUTED      # a dense layer's MLP goes by ``dense.`` + these
TIED = "embedding"      # the one table's name among the trainer's
# A tensor's kind, by its name under its layer's kind of mixer (the
# configuration's ``limits`` has a limit a kind); ``W_v`` and ``W_o``, the
# dense MLPs' three, ``norm_ffn`` and the final norm are
# ``gradient.table``'s.
KINDS = {
    "gradient.conv": ("conv.w_in", "conv.w_out", "conv.conv_w",
                      "conv.norm_attn"),
    "gradient.scores": ("gqa.wq", "gqa.wk", "gqa.norm_q", "gqa.norm_k",
                        "gqa.norm_attn"),
    "gradient.router": ("router",),
    "gradient.experts": ROUTED,
    "gradient.tied": (TIED,)}


def kind_of(tensor: str) -> str:
    return next((k for k, names in KINDS.items() if tensor in names),
                "gradient.table")


class Driver(lm.Driver):
    def __init__(self, ctx):
        # a checkout whose model has no convolution layer fails here,
        # before any actor thread exists: at once and cleanly
        from multiverso_tpu.models.lm import shortconv  # noqa: F401
        super().__init__(ctx)

    def build(self):
        super().build()
        c = self.cfg
        assert "conv" in c.attention_layout and c.one_ffn_input \
            and c.scoring == "sigmoid_bias" and not c.shared_width
        sparse = sum(c.ffn_layout)
        # ``layers``: the layers with routed experts (what the experts' and
        # the routers' shared readers count by); ``parameters``: with ONE
        # table; no ``heads_all``, ``heads_layout``, ``kda_heads`` nor
        # ``mla_heads``: the readers that count another family's mixers find
        # nothing here (benchmark/lib/convshapes.py counts this one's)
        self.ctx.shapes.clear()
        self.ctx.shapes.update(
            family="conv", sequences=self.B, seq_len=self.T, hidden=c.hidden,
            attention_layout=list(c.attention_layout),
            conv_taps=c.conv_taps, heads=c.n_heads, kv_heads=c.n_kv_heads,
            head_dim=c.head_dim, router_outputs=c.n_experts, top_k=c.top_k,
            held=c.experts_held[1], expert_width=c.expert_width,
            dense_width=c.dense_width, vocab=c.vocab, layers=sparse,
            sparse_layers=sparse, dense_layers=c.n_layers - sparse,
            parameters=c.parameters())

    def check(self) -> list:
        """drivers/lm.py's check against this model's reference; see the
        module's docstring."""
        wrong = []
        if not all(math.isfinite(float(x)) for x in self.losses):
            wrong.append("non-finite step loss")
        self.compared["non_finite_losses"] = [len(wrong), 0]
        return wrong + _Check(self).run()


class _Check(lm_kda._Check):
    def __init__(self, driver):
        self.d = driver
        self.trainer, self.cfg = driver.trainer, driver.cfg
        self.c = ref.sizes(driver.config)
        self.worst, self.by_table, self.rms, self.grads = {}, {}, {}, {}
        self.norm2 = {}     # table -> its reference gradient's squared norm
        self.pooled = {}    # tensor -> its layers' (error^2, norm^2), kind

    def note(self, name, value, table):
        """drivers/lm_kda.py's, by this model's kinds."""
        if not name.startswith("gradient."):
            return lm._Check.note(self, name, value, table)
        tensor = self.tensor_of(table)
        kind = kind_of(tensor)
        weigh = self.norm2[table]
        error, norm, _ = self.pooled.get(tensor, (0.0, 0.0, kind))
        self.pooled[tensor] = (error + float(value) ** 2 * weigh,
                               norm + weigh, kind)
        self.worst[kind] = max(
            ((e / max(n, 1e-60)) ** 0.5, t)
            for t, (e, n, k) in self.pooled.items() if k == kind)

    # -- the reference, a sequence and a layer at a time --------------------
    def _reference(self, tokens, chosen):
        import jax
        import jax.numpy as jnp
        c, t, cfg = self.c, self.trainer, self.cfg
        ids, targets = tokens[:, :-1], tokens[:, 1:]
        total, sequences = targets.size, range(tokens.shape[0])

        def pull(i):
            shapes = cfg.layer_shapes(i)
            return {n: table.get_device().reshape(shapes[n])
                    for n, table in t.layers[i].items()}

        # one program a kind of layer: the FILE's kind of mixer, and what
        # the layer's tensors say of its feed-forward
        forward = jax.jit(lambda p, x, s, kind: ref.layer(
            c, kind, p, x, s, own=True), static_argnums=(3,))
        backward = jax.jit(lambda p, x, s, dy, kind: jax.vjp(
            lambda p, x: ref.layer(c, kind, p, x, s), p, x)[1](dy),
            static_argnums=(4,))
        # the ONE table: the rows' Get and the head's whole Get are of it
        table = t.embedding.get_device()
        x = table[ids]
        placed = x.sharding
        inputs, differs, moved = [], [], []
        for i, kind in enumerate(c["kinds"]):
            p, given = pull(i), chosen[i]

            def through(x, b):
                return forward(p, x[b], None if given is None else given[b],
                               kind)

            inputs.append(np.asarray(x))
            out = [through(x, b) for b in sequences]
            x = jnp.stack([y for y, _ in out])
            if given is not None:
                differs.append(float(np.mean([
                    jnp.mean(jnp.any(jnp.sort(own, -1)
                                     != jnp.sort(given[b], -1), axis=-1))
                    for b, (_, own) in enumerate(out)])))
            # the layer ALONE, on the program's own input: what it adds to
            # the stream against what the reference adds to the same
            theirs, after = (jax.device_put(a, placed)
                             for a in self.stream[i:i + 2])
            want = jnp.stack([through(theirs, b)[0] for b in sequences])
            moved.append(float(jnp.linalg.norm(after - want)
                               / jnp.linalg.norm(want - theirs)))
            del p, out, theirs, after, want
        self.worst["layer.output"] = (max(moved), f"layer{np.argmax(moved)}")
        self.moved = moved
        del self.stream
        norm = t.final_norm.get_device()
        block = min(cfg.loss_block, self.d.T)
        loss_block = jax.jit(jax.value_and_grad(
            lambda x, h, n, y: ref.head_loss(c, h, n, x, y, total),
            (0, 1, 2)))
        loss, d_head, d_norm, dx = 0.0, 0.0, 0.0, []
        flat, flat_y = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
        for at in range(0, total, block):
            part, (dxb, dh, dn) = loss_block(
                flat[at:at + block], table, norm, flat_y[at:at + block])
            loss, d_head, d_norm = loss + part, d_head + dh, d_norm + dn
            dx.append(dxb)
        self.grads["final_norm"] = np.asarray(d_norm)
        d_head = np.asarray(d_head)     # waits on the host for the rows'
        del table, flat
        dx = jnp.concatenate(dx).reshape(x.shape)
        del x
        for i in reversed(range(cfg.n_layers)):
            p, given, kind = pull(i), chosen[i], c["kinds"][i]
            x_in = jnp.asarray(inputs.pop())
            total_p, parts = None, []
            for b in sequences:
                dp, dxb = backward(p, x_in[b],
                                   None if given is None else given[b],
                                   dx[b], kind)
                total_p = dp if total_p is None else jax.tree_util.tree_map(
                    jnp.add, total_p, dp)
                parts.append(dxb)
            dx = jnp.stack(parts)
            for n, g in total_p.items():
                if n != "router_bias":  # no gradient: its Add is a step
                    self.grads[f"layer{i}.{n}"] = np.asarray(g).reshape(
                        t.layers[i][n].get_device().shape)
            if given is not None:
                # what the bias's Add has to carry, and (on_add) leave:
                # the step itself, from a bias of zeros
                self.grads[f"layer{i}.router_bias"] = np.asarray(
                    ref.bias_step(c, jnp.zeros(p["router_bias"].shape),
                                  ref.load_of(c, given)))
            del p, total_p, x_in
        # the one table's: the sum of both uses', whole
        self.grads[TIED] = np.asarray(ref.tied_gradient(
            jnp.asarray(d_head), ids, dx))
        self.ids = ids
        for n, g in self.grads.items():
            self.norm2[n] = float(np.sum(np.square(g, dtype=np.float64)))
            self.rms[n] = (self.norm2[n] / g.size) ** 0.5
        return float(loss), differs

    # -- the trainer's step, each Add looked at from both sides ------------
    def watch(self):
        """drivers/lm.py's, over BOTH of a matrix table's ways to be added
        to: the one table is added to whole, and an Add by rows to it (a
        trainer that pushed its two gradients apart) has to be seen."""
        wrapped = []
        for name, table in self.trainer.tables().items():
            for method in ("add_async", "add_rows_async"):
                send = getattr(table, method, None)
                if send is None:
                    continue

                def add(*args, _name=name, _table=table, _send=send,
                        _rows=method == "add_rows_async"):
                    ids, grad, option = args if _rows else (None,) + args
                    return self.on_add(_name, _table, grad, ids, option,
                                       lambda: _send(*args))

                setattr(table, method, add)
                wrapped.append((table, method))
        return lambda: [delattr(t, m) for t, m in wrapped]

    def on_add(self, name, table, grad, ids, opt, send):
        if name == TIED and name in self.grads and ids is not None:
            # by rows: read as the table's gradient that it is, spread over
            # the table, and the rule's state left alone (adds.extra or
            # gradient.tied say what is wrong)
            import jax.numpy as jnp
            want = self.grads.pop(name)
            got = ref.tied_gradient(jnp.zeros(want.shape, jnp.float32), ids,
                                    grad)
            self.by_table[name] = float(
                jnp.linalg.norm(got - want) / max(self.norm2[name] ** 0.5,
                                                  1e-30))
            return send()
        return super().on_add(name, table, grad, ids, opt, send)

    def run(self) -> list:
        """drivers/lm_kda.py's without a scan to carry, behind the layout's
        own comparison."""
        kinds = [self.cfg.attention_of(i) for i in range(self.cfg.n_layers)]
        differs = sum(a != b for a, b in zip(kinds, self.c["kinds"])) \
            + abs(len(kinds) - len(self.c["kinds"]))
        limit = self.d.config["limits"]["layout.differs"]
        self.d.compared["layout.differs"] = [differs, limit]
        if differs > limit:
            return [f"layout.differs {differs} > {limit} (the program's "
                    f"mixers {kinds}; layer_types say {self.c['kinds']})"]
        for name in ("bias.differs", "adds.extra"):
            self.worst[name] = (0, "")
        self.worst["layout.differs"] = (differs, "")
        wrong = lm._Check.run(self)
        for i, share in enumerate(self.moved):      # reported alone
            self.d.compared[f"layer.output.layer{i}"] = [share, 1.0]
        return wrong
