"""Driver for the sixth family of language model trained through the
parameter server (multiverso_tpu/models/lm ``PSLMTrainer`` on an
``LMConfig`` whose attention's kind is a LAYER's: Kimi Linear's block,
three layers of four mixing the sequence by the gated delta rule's scan
behind short convolutions, models/lm/delta.py, the fourth by latent
attention without a query latent and without positions, models/lm/latent.py;
a dense layer and sparse ones with a shared expert under a sigmoid router
that chooses through a bias the server keeps, on the plain residual):
drivers/lm.py's set-up, window and Add-by-Add comparison, with this model's
shapes and reference.

A round is one step on a fresh batch of ``sequences_per_step`` x
(``seq_len`` + 1) tokens (drivers/lm.py's batches): the embedding rows'
Get by device keys, every other table Got whole on the device, the layer
programs, every table's Add (a router bias's under the plain rule).
``work["words"]`` is ``B T`` a step.

``check`` runs one more step at the cell's sizes through the trainer's
own programs and holds it to benchmark/reference/lm_kda_step.py on the
same device, given each token's experts from the program: the loss, every
tensor's gradient (its layers of one kind of attention together) against
its own norm by kind (``KINDS``), every table and both moments after the
Add (drivers/lm.py ``_Check.on_add``), each bias after its Add exactly
(``bias.differs``), that no table gets a second Add (``adds.extra``), the
worst layer's share of tokens whose eight experts are not the reference's
own choice (``routing.differs``), ``scan.carry`` (below), and
``layer.output``, which holds each
layer ALONE, forward: what the program's layer adds to its own input (``y
- x``) against what the reference's layer, the recurrence position by
position, adds to the same input given the same experts, relative L2, the
worst layer (drivers/lm_sparse.py's reason: a gradient's error has a floor
that every tensor of a step shares; a layer's own output has none).

``scan.carry`` holds the state's precision from chunk to chunk, which no
reading of a step can: a sound step rounds a COPY of the state to bfloat16
wherever a product reads it, so a state kept in bfloat16 differs from it by
what the roundings add up to over the chunks, inside every gradient's
floor. ``carried`` runs the program's own ``delta.scan`` (the function the
layers call, in this process, as the layers' programs traced it) on inputs
made to tell the two apart: one key for every position, a first write of
1, then writes of ``2^-16`` a position toward 2 with no decay, so that a
chunk adds ``2^-10`` to a state near 1: a float32 state takes every one
(1.06 after 64 chunks), a bfloat16 one, whose next number after 1 is ``1 +
2^-7``, none. Against the reference's recurrence, relative L2.
"""

import math

import numpy as np

from benchmark.drivers import lm
from benchmark.reference import lm_kda_step as ref

# A layer's tensors that its feed-forward brings; every other is its
# attention's, and goes by its name under the layer's kind of attention
# (``kda.wq`` and ``mla.wq`` are two tensors).
FEED_FORWARD = ("w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down",
                "router", "router_bias", "norm_ffn")
# A tensor's kind, by its name (the configuration's ``limits`` has a limit
# a kind; its ``limits.what`` the readings). A tensor's layers are taken
# TOGETHER (drivers/lm_bd.py's reason): their errors against their common
# norm, the worst tensor of a kind against the kind's limit.
# ``gradient.scan``: what feeds the recurrence; ``gradient.scores``: what
# feeds the latent layer's scores.
KINDS = {
    "gradient.gate": ("w_gate", "ws_gate", "norm_ffn"),
    "gradient.router": ("router",),
    "gradient.scores": ("mla.wq", "mla.wkv_a", "mla.norm_kv_a",
                        "mla.norm_attn"),
    "gradient.scan": tuple("kda." + n for n in (
        "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_fa", "w_fb",
        "a_log", "dt_bias", "w_beta", "norm_attn"))}
# The dense layer's MLP goes by the routed experts' names and is another
# tensor (drivers/lm_mixed.py's reason).
ROUTED = ("w_gate", "w_up", "w_down")
DENSE = "dense."


def kind_of(tensor: str) -> str:
    tensor = tensor.removeprefix(DENSE)
    return next((k for k, names in KINDS.items() if tensor in names),
                "gradient.table")


class Driver(lm.Driver):
    def __init__(self, ctx):
        # a checkout whose model has no scan over positions fails here,
        # before any actor thread exists: at once and cleanly
        from multiverso_tpu.models.lm import delta  # noqa: F401
        super().__init__(ctx)

    def build(self):
        super().build()
        c = self.cfg
        assert c.attention_layout and c.one_ffn_input \
            and c.scoring == "sigmoid_bias"
        sparse = sum(c.ffn_layout)
        # ``layers``: the layers with routed experts (what the experts' and
        # the routers' shared readers count by); no ``heads``, no
        # ``heads_held``, no ``heads_layout``: the readers that count
        # another family's attention find nothing here
        self.ctx.shapes.clear()
        self.ctx.shapes.update(
            family="kda", sequences=self.B, seq_len=self.T, hidden=c.hidden,
            attention_layout=list(c.attention_layout),
            kda_heads=c.kda_heads, kda_head_dim=c.kda_head_dim,
            kda_conv=c.kda_conv, mla_heads=c.n_heads, qk_dim=c.head_dim,
            v_dim=c.v_head_dim, kv_rank=c.kv_lora_rank,
            rope_dim=c.qk_rope_dim, ffn_layout=list(c.ffn_layout),
            router_outputs=c.n_experts, top_k=c.top_k,
            held=c.experts_held[1], expert_width=c.expert_width,
            shared_width=c.shared_width, dense_width=c.dense_width,
            vocab=c.vocab, layers=sparse, sparse_layers=sparse,
            dense_layers=c.n_layers - sparse, parameters=c.parameters())

    def check(self) -> list:
        """drivers/lm.py's check against this model's reference; see the
        module's docstring."""
        wrong = []
        if not all(math.isfinite(float(x)) for x in self.losses):
            wrong.append("non-finite step loss")
        self.compared["non_finite_losses"] = [len(wrong), 0]
        return wrong + _Check(self).run()


class _Check(lm._Check):
    def __init__(self, driver):
        self.d = driver
        self.trainer, self.cfg = driver.trainer, driver.cfg
        self.c = ref.sizes(driver.config)
        self.worst, self.by_table, self.rms, self.grads = {}, {}, {}, {}
        self.norm2 = {}     # table -> its reference gradient's squared norm
        self.pooled = {}    # tensor -> its layers' (error^2, norm^2), kind

    def tensor_of(self, table: str) -> str:
        """The tensor a table is a layer of: an attention's by its name
        under the layer's kind of attention, the dense layer's MLP under
        ``dense.``."""
        layer, _, tensor = table.rpartition(".")
        if not layer.startswith("layer"):
            return tensor
        i = int(layer.removeprefix("layer"))
        if tensor not in FEED_FORWARD:
            return f"{self.cfg.attention_of(i)}.{tensor}"
        if tensor in ROUTED and not self.cfg.ffn_layout[i]:
            return DENSE + tensor
        return tensor

    def note(self, name, value, table):
        """A tensor's layers together, each weighed by its reference
        gradient's squared norm; the worst tensor of a kind against the
        kind's limit."""
        if not name.startswith("gradient."):
            return super().note(name, value, table)
        tensor = self.tensor_of(table)
        kind = kind_of(tensor)
        weigh = self.norm2[table]
        error, norm, _ = self.pooled.get(tensor, (0.0, 0.0, kind))
        self.pooled[tensor] = (error + float(value) ** 2 * weigh,
                               norm + weigh, kind)
        self.worst[kind] = max(
            ((e / max(n, 1e-60)) ** 0.5, t)
            for t, (e, n, k) in self.pooled.items() if k == kind)

    # -- the program's forward pass, for each token's experts ---------------
    def chosen(self, tokens):
        """By layer: each token's experts [B, T, k], None in a dense
        layer; the program's stream before and after each layer waits on
        the host (``layer.output``)."""
        t = self.trainer
        ids, _, _ = t._split(tokens)
        x = t.embedding.get_rows_device(ids)
        chosen, self.stream = [], [np.asarray(x)]
        for i, kind in enumerate(self.cfg.layer_kinds()):
            mats, small = t._pull_layer(i)
            x, _, _, layer_ids, *_ = t._forward[kind](mats, small, x)
            chosen.append(layer_ids if kind[2] else None)
            self.stream.append(np.asarray(x))
        return chosen

    def loads(self, chosen):
        return super().loads([ids for ids in chosen if ids is not None])

    # -- the reference, a sequence and a layer at a time --------------------
    def reference(self, tokens, chosen):
        """Every product of the reference in float32 at "highest" (the
        trainer's own programs, compiled outside, keep theirs)."""
        with ref.PRECISION:
            loss, differs = self._reference(tokens, chosen)
        self.worst["routing.differs"] = (max(differs), "")
        return loss, differs

    def _reference(self, tokens, chosen):
        import jax
        import jax.numpy as jnp
        c, t, cfg = self.c, self.trainer, self.cfg
        ids, targets = tokens[:, :-1], tokens[:, 1:]
        total = targets.size

        def pull(i):
            shapes = cfg.layer_shapes(i)
            return {n: table.get_device().reshape(shapes[n])
                    for n, table in t.layers[i].items()}

        # one program a kind of layer (a layer's tensors say its kind)
        forward = jax.jit(lambda p, x, s: ref.layer(c, p, x, s, own=True))
        backward = jax.jit(lambda p, x, s, dy: jax.vjp(
            lambda p, x: ref.layer(c, p, x, s), p, x)[1](dy))
        embedding = t.embedding.get_device()
        x = embedding[ids]
        placed = x.sharding
        del embedding
        inputs, differs, moved = [], [], []
        for i in range(cfg.n_layers):
            p, given = pull(i), chosen[i]

            def through(x, b):
                return forward(p, x[b], None if given is None else given[b])

            inputs.append(np.asarray(x))
            out = [through(x, b) for b in range(x.shape[0])]
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
            want = jnp.stack([through(theirs, b)[0]
                              for b in range(x.shape[0])])
            moved.append(float(jnp.linalg.norm(after - want)
                               / jnp.linalg.norm(want - theirs)))
            del p, out, theirs, after, want
        self.worst["layer.output"] = (max(moved), f"layer{np.argmax(moved)}")
        self.moved = moved
        del self.stream
        head, norm = t.head.get_device(), t.final_norm.get_device()
        block = min(cfg.loss_block, self.d.T)
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
        del head, d_head, flat
        dx = jnp.concatenate(dx).reshape(x.shape)
        del x
        for i in reversed(range(cfg.n_layers)):
            p, given = pull(i), chosen[i]
            x_in = jnp.asarray(inputs.pop())
            total_p, parts = None, []
            for b in range(dx.shape[0]):
                dp, dxb = backward(p, x_in[b],
                                   None if given is None else given[b], dx[b])
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
        self.grads["embedding"] = np.asarray(dx)    # a row a position
        for n, g in self.grads.items():
            self.norm2[n] = float(np.sum(np.square(g, dtype=np.float64)))
            self.rms[n] = (self.norm2[n] / g.size) ** 0.5
        return float(loss), differs

    # -- the state from chunk to chunk ------------------------------------------
    def carried(self, chunks: int = 64) -> float:
        """The program's scan against the reference's recurrence on the
        inputs of the module's docstring: the outputs' relative error."""
        import jax
        import jax.numpy as jnp
        from multiverso_tpu.models.lm import delta
        heads, d = self.cfg.kda_heads, self.cfg.kda_head_dim
        t = chunks * delta.CHUNK
        one = jnp.zeros((t, heads, d), jnp.float32).at[..., 0].set(1.0)
        first = jnp.arange(t)[:, None] == 0
        v = jnp.where(first, 1.0, 2.0)[..., None] * jnp.ones((t, heads, d))
        beta = jnp.where(first, 1.0, 2.0 ** -16) * jnp.ones((t, heads))
        args = (one, one, v, jnp.zeros_like(one), beta)
        got = jax.jit(lambda *a: delta.scan(*a)[0])(*args)
        with ref.PRECISION:
            want = jax.jit(ref.recurrence)(*args)
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    # -- a bias's Add: no rule's state, no rounding; one Add a table ---------
    def on_add(self, name, table, grad, ids, opt, send):
        if name not in self.grads:      # a table's second Add of the step
            self.note("adds.extra", self.worst["adds.extra"][0] + 1, name)
            return send()
        if not name.endswith("router_bias"):
            return super().on_add(name, table, grad, ids, opt, send)
        want = self.grads.pop(name)
        before = np.asarray(table.get_device())
        msg_id = send()
        table.wait(msg_id)
        after = np.asarray(table.get_device())
        differs = int(np.sum(np.asarray(grad) != want)
                      + np.sum(after != before + want))
        self.note("bias.differs", differs, name)
        return msg_id

    def run(self) -> list:
        """drivers/lm.py's, with two counts that have to stay 0: a bias's
        Add that differs, a table's second Add of the step."""
        for name in ("bias.differs", "adds.extra"):
            self.worst[name] = (0, "")
        self.worst["scan.carry"] = (self.carried(), "")
        wrong = super().run()
        for i, share in enumerate(self.moved):      # reported alone
            self.d.compared[f"layer.output.layer{i}"] = [share, 1.0]
        return wrong
