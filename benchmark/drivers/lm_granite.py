"""Driver for the tenth family of language model trained through the
parameter server (multiverso_tpu/models/lm ``PSLMTrainer`` on an ``LMConfig``
whose mixer is a LAYER's and in nine layers of ten a selective state-space
layer: granite-4.0-h-micro's block, Mamba-2's SSD mixer, models/lm/ssd.py,
beside ONE layer of grouped-query attention with no positions under a
softmax scale of 1/64, ``model.attention_vjp``; a dense MLP in every layer
and NO router; four scalar multipliers; ONE table for embedding and head):
drivers/lm.py's set-up and window, drivers/lm_lfm2.py's Add-by-Add
comparison (no second Add, a tensor's layers of one kind together, the one
table's Add against the sum of both uses' gradients), with this model's
shapes and reference.

A round is one step on a fresh batch of ``sequences_per_step`` x
(``seq_len`` + 1) tokens. ``work["words"]`` is ``B T`` a step.

``check`` runs one more step at the cell's sizes through the trainer's own
programs and holds it to benchmark/reference/lm_granite_step.py on the same
device (the state as the RECURRENCE position by position, in blocks), given
the SAME slice of the vocabulary:

- ``layout.differs``: the layers whose mixer's kind is not the
  configuration's ``layer_types``' (the check stops there: a program whose
  layer is of another kind has other tables);
- ``loss``; every tensor's gradient against its own norm by kind (``KINDS``):
  ``gradient.ssd`` a state-space layer's two matrices, ``gradient.ssd_small``
  four of its small tensors (``conv_w``, ``conv_b``, ``d``, ``norm_g``) and
  ``gradient.decay`` the two that the state's decay alone reaches
  (``dt_bias``, ``a_log``), each group read TOGETHER against its common norm
  over all nine layers (``a_log``'s gradient is 64 numbers a layer: alone its
  own norm is no yardstick), ``gradient.attention`` the attention layer's
  four, ``gradient.mlp`` every layer's three, ``gradient.table`` every norm;
- ``scan.carry``: not a reading of the step: ``ssd.scan`` itself against
  the reference's recurrence over 64 chunks on inputs that a bfloat16 state
  cannot follow (``carried``: a state of 1 that every chunk adds 2^-8 to
  under no decay, half of a bfloat16's last place, so a state kept in
  bfloat16 stands still while the float32 one reaches 1.25;
  the sound reading is the rounding of the state where the product with C
  reads it, 2e-3);
- ``gradient.tied``: the ONE table's Add against the SUM of the reference's
  two gradients, the head's and the rows' (times ``embedding_multiplier``);
- every table and both moments after the Add (``adam.moments``,
  ``adam.update``), ``adds.extra`` (a table's second Add, or an Add to a
  table the configuration does not have), ``layer.output`` (each layer ALONE,
  forward, on the program's own input: drivers/lm_kda.py's reason).
"""

import math

import numpy as np

from benchmark.drivers import lm, lm_lfm2
from benchmark.reference import lm_granite_step as ref

TIED = lm_lfm2.TIED
SSD_SMALL = ("conv_w", "conv_b", "d", "norm_g")
SSD_DECAY = ("dt_bias", "a_log")
# A tensor's kind, by its name under its layer's kind of mixer (the
# configuration's ``limits`` has a limit a kind); the norms are
# ``gradient.table``'s. The small tensors of the state-space layers pool
# into TWO tensors: ``ssd.decay`` the two that the state's decay alone
# reaches (at a fresh model the recurrence is a few hundredths of ``Y``
# beside the skip ``D X``, so nothing else shows a wrong decay), and
# ``ssd.small`` the other four.
KINDS = {
    "gradient.ssd": ("ssd.w_in", "ssd.w_out"),
    "gradient.ssd_small": ("ssd.small",),
    "gradient.decay": ("ssd.decay",),
    "gradient.attention": ("gqa.wq", "gqa.wk", "gqa.wv", "gqa.wo"),
    "gradient.mlp": ("w_gate", "w_up", "w_down"),
    "gradient.tied": (TIED,)}


def kind_of(tensor: str) -> str:
    return next((k for k, names in KINDS.items() if tensor in names),
                "gradient.table")


class Driver(lm.Driver):
    def __init__(self, ctx):
        # a checkout whose model has no state-space layer fails here,
        # before any actor thread exists: at once and cleanly
        from multiverso_tpu.models.lm import ssd  # noqa: F401
        super().__init__(ctx)

    def build(self):
        super().build()
        from multiverso_tpu.models.lm import ssd
        c = self.cfg
        assert "ssd" in c.attention_layout and c.one_ffn_input \
            and not c.n_experts and not any(c.ffn_layout)
        # ``layers`` 0, ``held`` 0: no layer has routed experts, and the
        # experts' and routers' shared readers are not asked here;
        # ``ssd_chunk``: the chunk the scan ran at
        # (benchmark/lib/ssdshapes.py counts the function at it)
        self.ctx.shapes.clear()
        self.ctx.shapes.update(
            family="ssd", sequences=self.B, seq_len=self.T, hidden=c.hidden,
            attention_layout=list(c.attention_layout),
            ssd_heads=c.ssd_heads, ssd_head_dim=c.ssd_head_dim,
            ssd_state=c.ssd_state, ssd_chunk=ssd.chunk_of(c, self.T),
            heads=c.n_heads, kv_heads=c.n_kv_heads,
            head_dim=c.head_dim, router_outputs=0, top_k=0, held=0,
            expert_width=0, dense_width=c.dense_width, vocab=c.vocab,
            layers=0, sparse_layers=0, dense_layers=c.n_layers,
            parameters=c.parameters())

    def check(self) -> list:
        """drivers/lm.py's check against this model's reference; see the
        module's docstring."""
        wrong = []
        if not all(math.isfinite(float(x)) for x in self.losses):
            wrong.append("non-finite step loss")
        self.compared["non_finite_losses"] = [len(wrong), 0]
        return wrong + _Check(self).run()


class _Check(lm_lfm2._Check):
    def __init__(self, driver):
        self.d = driver
        self.trainer, self.cfg = driver.trainer, driver.cfg
        self.c = ref.sizes(driver.config)
        self.worst, self.by_table, self.rms, self.grads = {}, {}, {}, {}
        self.norm2 = {}     # table -> its reference gradient's squared norm
        self.pooled = {}    # tensor -> its layers' (error^2, norm^2), kind

    def tensor_of(self, table: str) -> str:
        layer, _, tensor = table.rpartition(".")
        if not layer.startswith("layer") or tensor in KINDS["gradient.mlp"] \
                or tensor == "norm_ffn":
            return tensor
        kind = self.cfg.attention_of(int(layer.removeprefix("layer")))
        if kind == "ssd" and tensor in SSD_SMALL + SSD_DECAY:
            return "ssd.small" if tensor in SSD_SMALL else "ssd.decay"
        return f"{kind}.{tensor}"

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

    # -- the program's forward pass: its stream before and after each layer --
    def chosen(self, tokens):
        """No layer has experts to choose: a None a layer; the program's
        stream before and after each layer waits on the host
        (``layer.output``)."""
        t = self.trainer
        ids, _, _ = t._split(tokens)
        x = t.embedding.get_rows_device(ids)
        if t._enter:    # times ``embedding_multiplier``
            x, _ = t._enter(x)
        self.stream = [np.asarray(x)]
        for i, kind in enumerate(self.cfg.layer_kinds()):
            mats, small = t._pull_layer(i)
            x = t._forward[kind](mats, small, x)[0]
            self.stream.append(np.asarray(x))
        return [None] * self.cfg.n_layers

    def reference(self, tokens, chosen):
        with ref.PRECISION:
            return self._reference(tokens, chosen)

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

        # one program a kind of layer: the FILE's kind of mixer
        forward = jax.jit(lambda p, x, kind: ref.layer(c, kind, p, x),
                          static_argnums=(2,))
        backward = jax.jit(lambda p, x, dy, kind: jax.vjp(
            lambda p, x: ref.layer(c, kind, p, x), p, x)[1](dy),
            static_argnums=(3,))
        # the ONE table: the rows' Get and the head's whole Get are of it
        table = t.embedding.get_device()
        x = ref.embed(c, table, ids)
        placed = x.sharding
        inputs, moved = [], []
        for i, kind in enumerate(c["kinds"]):
            p = pull(i)
            inputs.append(np.asarray(x))
            x = jnp.stack([forward(p, x[b], kind) for b in sequences])
            # the layer ALONE, on the program's own input: what it adds to
            # the stream against what the reference adds to the same
            theirs, after = (jax.device_put(a, placed)
                             for a in self.stream[i:i + 2])
            want = jnp.stack([forward(p, theirs[b], kind)
                              for b in sequences])
            moved.append(float(jnp.linalg.norm(after - want)
                               / jnp.linalg.norm(want - theirs)))
            del p, theirs, after, want
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
            p, kind = pull(i), c["kinds"][i]
            x_in = jnp.asarray(inputs.pop())
            total_p, parts = None, []
            for b in sequences:
                dp, dxb = backward(p, x_in[b], dx[b], kind)
                total_p = dp if total_p is None else jax.tree_util.tree_map(
                    jnp.add, total_p, dp)
                parts.append(dxb)
            dx = jnp.stack(parts)
            for n, g in total_p.items():
                self.grads[f"layer{i}.{n}"] = np.asarray(g).reshape(
                    t.layers[i][n].get_device().shape)
            del p, total_p, x_in
        # the one table's: the sum of both uses', whole
        self.grads[TIED] = np.asarray(ref.tied_gradient(
            c, jnp.asarray(d_head), ids, dx))
        self.ids = ids
        for n, g in self.grads.items():
            self.norm2[n] = float(np.sum(np.square(g, dtype=np.float64)))
            self.rms[n] = (self.norm2[n] / g.size) ** 0.5
        return float(loss), []

    # -- the state from chunk to chunk ------------------------------------------
    def carried(self, chunks: int = 64) -> float:
        """The program's scan against the reference's recurrence on the
        inputs of the module's docstring: the outputs' relative error."""
        import jax
        import jax.numpy as jnp
        from multiverso_tpu.models.lm import ssd
        cfg = self.cfg
        chunk = cfg.ssd_chunk or ssd.CHUNK
        t, heads = chunks * chunk, cfg.ssd_heads
        x = jnp.ones((t, heads, cfg.ssd_head_dim), jnp.float32)
        # half of a bfloat16's last place at 1 a chunk, whatever the chunk
        dt = jnp.where(jnp.arange(t)[:, None] == 0, 1.0, 2.0 ** -8 / chunk) \
            * jnp.ones((t, heads))
        a_log = jnp.full((heads,), -30.0)       # exp(dt A) = 1 in float32
        first = jnp.zeros((t, cfg.ssd_state)).at[:, 0].set(1.0)
        got = jax.jit(lambda *a: ssd.scan(*a, chunk=chunk)[0])(
            x, dt, a_log, first, first)
        with ref.PRECISION:
            want = jax.jit(ref.recurrence)(x, dt, -jnp.exp(a_log), first,
                                           first)
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    def run(self) -> list:
        self.worst["scan.carry"] = (self.carried(), "")
        return super().run()
