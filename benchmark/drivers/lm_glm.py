"""Driver for DeepSeek-V3's block on the plain residual trained through the
parameter server (multiverso_tpu/models/lm ``PSLMTrainer`` on an
``LMConfig`` with rotary latent attention under no scaling, a sigmoid router
chosen through a bias the server keeps, a shared expert and a multi-token
module HELD: GLM-4.7-Flash's, ``model_type: glm4_moe_lite``): drivers/lm.py's
set-up, window and Add-by-Add comparison, with this model's batches and
reference.

A round is one step on a fresh batch of ``sequences_per_step`` x
(``seq_len`` + 2) tokens (Zipf over the vocabulary slice, drawn on the
device from ``--seed`` before the window), so that the next token and the
one after are targets: the embedding rows' Get by device keys, every other
table Got whole on the device, the layer programs, the module's three, both
head passes, every table's Add (the embedding's and the head's ONE each,
their two gradients summed first; a router bias's under the plain rule).
``work["words"]`` is ``B T`` a step.

``check`` runs one more step at the cell's sizes through the trainer's own
programs and holds it to benchmark/reference/lm_glm_step.py on the same
device, given each token's experts from the program: both losses' weighted
sum, every tensor's gradient (its layers and the module's together) against
its own norm by kind (``KINDS``), every table and both moments after the Add
(drivers/lm.py ``_Check.on_add``), each bias after its Add exactly
(``bias.differs``), that no table gets a second Add (``adds.extra``: embedding
and head get ONE each), the worst layer's share of tokens whose four experts
are not the reference's own choice (``routing.differs``), and
``layer.output``, which holds each layer ALONE, forward: what the program's
layer adds to its own input (``y - x``) against what the reference's layer
adds to the same input given the same experts, relative L2, the worst layer
(drivers/lm_sparse.py's reason: a gradient's error has a floor that every
tensor of a step shares; a layer's own output has none).
"""

import math

import numpy as np

from benchmark.drivers import lm, lm_mla
from benchmark.reference import lm_glm_step as ref

# A tensor's kind, by its name (the configuration's ``limits`` has a limit
# a kind; its ``limits.what`` the readings). A tensor's layers are taken
# TOGETHER, the module's among them (drivers/lm_bd.py's reason): their
# errors against their common norm, the worst tensor of a kind against the
# kind's limit. ``gradient.scores``: what feeds the attention's scores.
KINDS = {
    "gradient.gate": ("w_gate", "ws_gate", "norm_ffn"),
    "gradient.router": ("router",),
    "gradient.scores": ("wq_a", "wq_b", "norm_q_a", "wkv_a", "norm_kv_a",
                        "norm_attn")}
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
        # a checkout that cannot describe this model (latent attention under
        # no scaling, with a module, on the plain residual) fails here,
        # before ``mv.init`` and any actor thread: at once and cleanly
        from multiverso_tpu.models.lm import LMConfig
        super().__init__(ctx)
        self.cfg = LMConfig.from_dict(self.config)
        assert self.cfg.residual == "plain" and self.cfg.attention == "mla" \
            and not self.cfg.yarn and self.cfg.mtp_layers == 1

    def build(self):
        import jax
        import multiverso_tpu as mv
        from multiverso_tpu.models.lm import PSLMTrainer, zipf_tokens
        assert self.traffic["trainer"] == "ps"
        seed = self.ctx.seed % (2 ** 31 - 1)
        mv.init(["-updater_type=adam",
                 f"-rpc_timeout_s={self.ctx.deadline_s}"])
        opt = self.config["optimizer"]
        self.trainer = PSLMTrainer(
            self.cfg, self.T, self.B, seed=seed % (2 ** 24), lr=opt["lr"],
            beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["eps"],
            init_std=self.config["init_std"],
            embedding_std=self.config["embedding_init_std"],
            warmup_steps=opt["warmup_steps"])
        n = int(self.traffic["batches"])
        exponent = self.traffic["token_distribution"]["exponent"]
        c = self.cfg
        pool = jax.jit(lambda key: zipf_tokens(
            key, (n + 1, self.B, self.T + 1 + c.mtp_layers), c.vocab,
            exponent))(jax.random.PRNGKey(seed))
        self.batches = [pool[i] for i in range(n)]
        self.check_batch = pool[n]
        jax.block_until_ready(self.batches)
        sparse = sum(c.ffn_layout)
        # drivers/lm_mla.py's keys (mlashapes.py counts by them): ``layers``
        # the layers with routed experts, the module's among them; no
        # streams, so no mixer is counted; ``family``: xing's, the counting
        # module the merged readers take
        self.ctx.shapes.update(
            family="mla", sequences=self.B, seq_len=self.T, hidden=c.hidden,
            heads_held=c.n_heads_held, qk_dim=c.head_dim, v_dim=c.v_head_dim,
            q_rank=c.q_lora_rank, kv_rank=c.kv_lora_rank,
            rope_dim=c.qk_rope_dim, router_outputs=c.n_experts,
            top_k=c.top_k, held=c.experts_held[1],
            expert_width=c.expert_width, shared_width=c.shared_width,
            dense_width=c.dense_width, vocab=c.vocab,
            layers=sparse + c.mtp_layers, sparse_layers=sparse,
            dense_layers=c.n_layers - sparse, modules=c.mtp_layers,
            streams=0, parameters=c.parameters())

    def check(self) -> list:
        """drivers/lm.py's check against this model's reference; see the
        module's docstring."""
        wrong = []
        if not all(math.isfinite(float(x)) for x in self.losses):
            wrong.append("non-finite step loss")
        self.compared["non_finite_losses"] = [len(wrong), 0]
        return wrong + _Check(self).run()


class _Check(lm_mla._Check):
    """drivers/lm_mla.py's pooling by tensor, its sparse layers' choices
    with the module's last, its bias Adds held exactly and its count of
    second Adds; the forward pass, the reference and ``layer.output`` are
    this model's."""

    def __init__(self, driver):
        self.d = driver
        self.trainer, self.cfg = driver.trainer, driver.cfg
        self.c = ref.sizes(driver.config)
        self.worst, self.by_table, self.rms, self.grads = {}, {}, {}, {}
        self.norm2 = {}     # table -> its reference gradient's squared norm
        self.pooled = {}    # tensor -> its layers' (error^2, norm^2), kind

    def tensor_of(self, table: str) -> str:
        """The tensor a table is a layer of: the dense layer's MLP under
        ``dense.``, the module's layer's with the layers'."""
        layer, _, tensor = table.rpartition(".")
        if layer.startswith("layer") and tensor in ROUTED \
                and not self.cfg.ffn_layout[int(layer.removeprefix("layer"))]:
            return DENSE + tensor
        return tensor

    def note(self, name, value, table):
        """A tensor's layers together, each weighed by its reference
        gradient's squared norm; the worst tensor of a kind against the
        kind's limit."""
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

    # -- the program's forward pass, for each token's experts ---------------
    def chosen(self, tokens):
        """``{"layers": each token's experts [B, T, k] by layer, None in a
        dense one, "mtp": the module's}``; the program's stream before and
        after each layer waits on the host (``layer.output``)."""
        t, cfg = self.trainer, self.cfg
        ids, _, _ = t._split(tokens)
        x, e_next = t._enter(t.embedding.get_rows_device(ids))
        chosen, self.stream = {"layers": [], "mtp": None}, [np.asarray(x)]
        for i, kind in enumerate(cfg.layer_kinds()):
            mats, small = t._pull_layer(i)
            x, _, _, layer_ids, *_ = t._forward[kind](mats, small, x)
            chosen["layers"].append(layer_ids if kind[2] else None)
            self.stream.append(np.asarray(x))
        mats, small = t._pull_module()
        chosen["mtp"] = t._module[0](mats, small, x, e_next)[3]
        return chosen

    # -- the reference, a sequence and a layer at a time --------------------
    def _reference(self, tokens, chosen):
        import jax
        import jax.numpy as jnp
        c, t, cfg = self.c, self.trainer, self.cfg
        T = self.d.T
        first = tokens[:, 1:T + 1]
        total = first.size

        def pull(tables, shapes):
            return {n: table.get_device().reshape(shapes[n])
                    for n, table in tables.items()}

        # one program a kind of layer (a layer's tensors say its kind)
        forward = jax.jit(lambda p, x, s: ref.layer(c, p, x, s, own=True))
        backward = jax.jit(lambda p, x, s, dy: jax.vjp(
            lambda p, x: ref.layer(c, p, x, s), p, x)[1](dy))
        embedding = t.embedding.get_device()
        rows = embedding[tokens[:, :T + 1]]
        placed = rows.sharding
        del embedding
        x, e_next = rows[:, :T], rows[:, 1:]
        del rows
        inputs, differs, moved = [], [], []     # the inputs wait on the host
        for i in range(cfg.n_layers):
            p, given = pull(t.layers[i], cfg.layer_shapes(i)), \
                chosen["layers"][i]

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
        block = min(cfg.loss_block, T)
        loss_block = jax.jit(jax.value_and_grad(
            lambda x, h, n, y, over: ref.head_loss(c, h, n, x, y, over),
            (0, 1, 2)))

        def head_pass(x, norm, targets, over):
            """``(loss, dx, head gradient, norm gradient)`` of one head
            pass, a block of positions at a time."""
            loss, d_head, d_norm, dx = 0.0, 0.0, 0.0, []
            flat, flat_y = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
            for at in range(0, total, block):
                part, (dxb, dh, dn) = loss_block(
                    flat[at:at + block], head, norm, flat_y[at:at + block],
                    over)
                loss, d_head, d_norm = loss + part, d_head + dh, d_norm + dn
                dx.append(dxb)
            return loss, jnp.concatenate(dx).reshape(x.shape), d_head, d_norm

        loss, dx, d_head, d_norm = head_pass(x, norm, first,
                                             jnp.float32(total))
        self.grads["final_norm"] = np.asarray(d_norm)
        # the module: its loss and gradients weigh mtp_weight
        p = pull(t.module, {**cfg.layer_shapes(cfg.n_layers - 1),
                            **cfg.mtp_shapes()})
        layer = {n: v for n, v in p.items() if n != "final_norm"}
        through = jax.jit(lambda p, xs, e, s: ref.mtp(c, p, xs, e, s,
                                                      own=True))
        back = jax.jit(lambda p, xs, e, s, dy: jax.vjp(
            lambda p, xs, e: ref.mtp(c, p, xs, e, s), p, xs, e)[1](dy))
        given = chosen["mtp"]
        out = [through(layer, x[b], e_next[b], given[b])
               for b in range(x.shape[0])]
        differs.append(float(np.mean([
            jnp.mean(jnp.any(jnp.sort(own, -1) != jnp.sort(given[b], -1),
                             axis=-1)) for b, (_, own) in enumerate(out)])))
        y = jnp.stack([y for y, _ in out])
        del out
        second, dy, d_head_2, d_norm_2 = head_pass(
            y, p["final_norm"], tokens[:, 2:T + 2],
            jnp.float32(total / c["mtp_weight"]))
        loss, d_head = loss + second, d_head + d_head_2
        total_p, d_xs, d_e = None, [], []
        for b in range(x.shape[0]):
            dp, dxb, deb = back(layer, x[b], e_next[b], given[b], dy[b])
            total_p = dp if total_p is None else jax.tree_util.tree_map(
                jnp.add, total_p, dp)
            d_xs.append(dxb)
            d_e.append(deb)
        dx, de_next = dx + jnp.stack(d_xs), jnp.stack(d_e)
        self._keep(total_p, "mtp", t.module)
        self.grads["mtp.final_norm"] = np.asarray(d_norm_2)
        self.grads["head"] = np.asarray(d_head)
        del p, layer, total_p, y, dy, head, d_head, x, e_next
        for i in reversed(range(cfg.n_layers)):
            p, given = pull(t.layers[i], cfg.layer_shapes(i)), \
                chosen["layers"][i]
            x_in = jnp.asarray(inputs.pop())
            total_p, parts = None, []
            for b in range(dx.shape[0]):
                dp, dxb = backward(p, x_in[b],
                                   None if given is None else given[b], dx[b])
                total_p = dp if total_p is None else jax.tree_util.tree_map(
                    jnp.add, total_p, dp)
                parts.append(dxb)
            dx = jnp.stack(parts)
            self._keep(total_p, f"layer{i}", t.layers[i])
            del p, total_p, x_in
        # a row a position embedded: as the first layer's input, and as the
        # position before's next token
        self.grads["embedding"] = np.asarray(
            jnp.pad(dx, ((0, 0), (0, 1), (0, 0)))
            + jnp.pad(de_next, ((0, 0), (1, 0), (0, 0))))
        # what each bias's Add has to carry, and (on_add) leave: exactly
        for prefix, ids in self._sparse(chosen):
            table = (t.module if prefix.startswith("mtp") else
                     t.layers[int(prefix[5:])])["router_bias"]
            # the step itself, from a bias of zeros: the Add's delta
            self.grads[f"{prefix}.router_bias"] = np.asarray(ref.bias_step(
                c, jnp.zeros(table.get_device().shape), ref.load_of(c, ids)))
        for n, g in self.grads.items():
            self.norm2[n] = float(np.sum(np.square(g, dtype=np.float64)))
            self.rms[n] = (self.norm2[n] / g.size) ** 0.5
        return float(loss), differs

    def run(self) -> list:
        wrong = super().run()
        for i, share in enumerate(self.moved):      # reported alone
            self.d.compared[f"layer.output.layer{i}"] = [share, 1.0]
        return wrong
