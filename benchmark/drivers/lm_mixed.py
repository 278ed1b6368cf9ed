"""Driver for the fourth family of language model trained through the
parameter server (multiverso_tpu/models/lm ``PSLMTrainer`` on an
``LMConfig`` whose layers are described by kind: grouped-query attention
that is full on some layers and windowed on others, each kind with its own
query heads and rotary positions, a per-head output gate, a dense layer
and sparse ones with a shared expert under a sigmoid router, on the plain
residual): drivers/lm.py's set-up, window and Add-by-Add comparison, with
this model's shapes and reference.

A round is one step on a fresh batch of ``sequences_per_step`` x
(``seq_len`` + 1) tokens (drivers/lm.py's batches): the embedding rows'
Get by device keys, every other table Got whole on the device, the layer
programs, every table's Add. ``work["words"]`` is ``B T`` a step.

``check`` runs one more step at the cell's sizes through the trainer's
own programs and holds it to benchmark/reference/lm_mixed_step.py on the
same device, given each token's experts from the program: the loss, every
tensor's gradient (its layers together) against its own norm by kind
(``KINDS``), every table and both moments after the Add (drivers/lm.py
``_Check.on_add``), and the share of tokens whose eight experts are not
the reference's own choice: the worst layer's, and the mean of the sparse
layers after the first (``LATER``).
"""

import numpy as np

from benchmark.drivers import lm
from benchmark.reference import lm_mixed_step as ref

# A tensor's kind, by its name (the configuration's ``limits`` has a limit
# a kind; its ``limits.what`` the readings). A tensor's layers are taken
# TOGETHER (drivers/lm_bd.py's reason): their errors against their common
# norm, the worst tensor of a kind against the kind's limit.
KINDS = {
    "gradient.gate": ("w_gate", "ws_gate", "norm_ffn"),
    "gradient.router": ("router",),
    "gradient.attn_gate": ("w_attn_gate",),
    "gradient.scores": ("wq", "wk", "norm_attn")}


# The dense layer's MLP goes by the routed experts' names and is another
# tensor, whose gradient is some 600 times theirs in squared norm (chip
# runs, PR 41): pooled with them it would be all that is read.
ROUTED = ("w_gate", "w_up", "w_down")
DENSE = "dense."
# The sparse layers after the first route on a stream that routed experts
# have written into: the mean of their shares of tokens whose experts are
# not the reference's own choice reads the forward pass's precision there,
# and does not move with what every gradient of a step shares (the
# configuration's ``limits.what``: that floor swings by seed and rises
# with the steps trained, by more than float8-rounded expert inputs add
# to any gradient).
LATER = "routing.differs.later"


def kind_of(tensor: str) -> str:
    tensor = tensor.removeprefix(DENSE)
    return next((k for k, names in KINDS.items() if tensor in names),
                "gradient.table")


class Driver(lm.Driver):
    def __init__(self, ctx):
        # a checkout whose model has one rotary kind a model fails here,
        # before any actor thread exists: at once and cleanly
        from multiverso_tpu.models.lm.model import Rotary  # noqa: F401
        super().__init__(ctx)

    def build(self):
        super().build()
        c = self.cfg
        assert c.one_ffn_input and c.attn_gate == "head" and c.rotary_kinds
        sparse = sum(c.ffn_layout)
        # ``layers``: the layers with routed experts (what the experts'
        # and the routers' shared readers count by); ``family``: the
        # counting module the merged readers take (trainer.mfu.lm,
        # trainer.attn_roofline.lm: lib/mixedshapes.py, a head count a layer)
        self.ctx.shapes.clear()
        self.ctx.shapes.update(
            family="mixed", sequences=self.B, seq_len=self.T, hidden=c.hidden,
            heads_layout=list(c.heads_layout), kv_heads=c.n_kv_heads,
            head_dim=c.head_dim, window=c.window,
            windowed=list(c.window_layout),
            ffn_layout=list(c.ffn_layout), gate_heads=sum(c.heads_layout),
            router_outputs=c.n_experts, top_k=c.top_k,
            held=c.experts_held[1], expert_width=c.expert_width,
            shared_width=c.shared_width, dense_width=c.dense_width,
            vocab=c.vocab, layers=sparse, sparse_layers=sparse,
            dense_layers=c.n_layers - sparse, parameters=c.parameters())

    def check(self) -> list:
        """drivers/lm.py's check against this model's reference; see the
        module's docstring."""
        import math
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

    def note(self, name, value, table):
        """A tensor's layers together (each weighs by its reference
        gradient's squared norm: the full and the sliding layers' ``wq``
        differ in size), the worst tensor of a kind against the kind's
        limit."""
        if not name.startswith("gradient."):
            return super().note(name, value, table)
        layer, _, tensor = table.rpartition(".")
        if tensor in ROUTED and not self.cfg.ffn_layout[
                int(layer.removeprefix("layer"))]:
            tensor = DENSE + tensor
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
        layer."""
        t = self.trainer
        ids, _, _ = t._split(tokens)
        x = t.embedding.get_rows_device(ids)
        chosen = []
        for i, kind in enumerate(self.cfg.layer_kinds()):
            mats, small = t._pull_layer(i)
            x, _, _, layer_ids = t._forward[kind](mats, small, x)
            chosen.append(layer_ids if kind[2] else None)
        return chosen

    def loads(self, chosen):
        return super().loads([ids for ids in chosen if ids is not None])

    # -- the reference, a sequence and a layer at a time --------------------
    def reference(self, tokens, chosen):
        """Every product of the reference in float32 at "highest" (the
        trainer's own programs, compiled outside, keep theirs)."""
        with ref.PRECISION:
            loss, differs = self._reference(tokens, chosen)
        # the worst layer's share of tokens whose experts are not the
        # reference's own choice
        self.worst["routing.differs"] = (max(differs), "")
        later = differs[1:]
        self.worst[LATER] = (sum(later) / len(later), "")
        return loss, differs

    def _reference(self, tokens, chosen):
        import functools

        import jax
        import jax.numpy as jnp
        c, t, cfg = self.c, self.trainer, self.cfg
        ids, targets = tokens[:, :-1], tokens[:, 1:]
        total = targets.size

        def pull(i):
            shapes = cfg.layer_shapes(i)
            return {n: table.get_device().reshape(shapes[n])
                    for n, table in t.layers[i].items()}

        @functools.lru_cache(maxsize=None)
        def forward_of(windowed):       # one program a kind's tensors
            return jax.jit(lambda p, x, s: ref.layer(c, windowed, p, x, s,
                                                     own=True))

        @functools.lru_cache(maxsize=None)
        def backward_of(windowed):
            return jax.jit(lambda p, x, s, dy: jax.vjp(
                lambda p, x: ref.layer(c, windowed, p, x, s), p, x)[1](dy))

        embedding = t.embedding.get_device()
        x = embedding[ids]
        del embedding
        inputs, differs = [], []    # the layers' inputs wait on the host
        for i, windowed in enumerate(c["window_layout"]):
            p, given = pull(i), chosen[i]
            inputs.append(np.asarray(x))
            out = [forward_of(windowed)(p, x[b],
                                        None if given is None else given[b])
                   for b in range(x.shape[0])]
            x = jnp.stack([y for y, _ in out])
            if given is not None:
                differs.append(float(np.mean([
                    jnp.mean(jnp.any(jnp.sort(own, -1)
                                     != jnp.sort(given[b], -1), axis=-1))
                    for b, (_, own) in enumerate(out)])))
            del p, out
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
            backward = backward_of(c["window_layout"][i])
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
                self.grads[f"layer{i}.{n}"] = np.asarray(g).reshape(
                    t.layers[i][n].get_device().shape)
            del p, total_p, x_in
        self.grads["embedding"] = np.asarray(dx)    # a row a position
        for n, g in self.grads.items():
            self.norm2[n] = float(np.sum(np.square(g, dtype=np.float64)))
            self.rms[n] = (self.norm2[n] / g.size) ** 0.5
        return float(loss), differs
