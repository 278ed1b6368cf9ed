"""Driver for the third family of language model trained through the
parameter server (multiverso_tpu/models/lm ``PSLMTrainer`` on an
``LMConfig`` with latent attention, residual streams, a sigmoid router
chosen through a bias the server keeps, a shared expert and a multi-token
module): drivers/lm.py's set-up, window and Add-by-Add comparison, with
this model's batches and reference.

A round is one step on a fresh batch of ``sequences_per_step`` x
(``seq_len`` + 1 + the modules held) tokens (Zipf over the vocabulary
slice, drawn on the device from ``--seed`` before the window), so that
the next token and, with a module, the one after are targets: the
embedding rows' Get by device keys, every other table Got whole on the
device, the layer programs, the module's, both head passes, every table's
Add (the embedding's and the head's ONE each, their two gradients summed
first; a router bias's under the plain rule). ``work["words"]`` is
``B T`` a step.

``check`` runs one more step at the cell's sizes through the trainer's
own programs and holds it to benchmark/reference/lm_mla_step.py on the
same device, given each token's experts from the program: both losses'
weighted sum, every tensor's gradient (its layers together) against its
own norm by kind (``KINDS``), every table and both moments after the Add
(drivers/lm.py ``_Check.on_add``), each bias after its Add exactly.
"""

import math

import numpy as np

from benchmark.drivers import lm
from benchmark.reference import lm_mla_step as ref

# A tensor's kind, by its name (the configuration's ``limits`` has a limit
# a kind; its ``limits.what`` the readings). A tensor's layers are taken
# TOGETHER, the module's among them: their errors against their common
# norm (drivers/lm_bd.py's reason: a layer whose held experts saw few
# tokens keeps a gradient made of near-ties).
KINDS = {
    "gradient.gate": ("w_gate", "ws_gate", "norm_ffn"),
    "gradient.router": ("router",),
    "gradient.scores": ("wq_a", "wq_b", "norm_q_a", "wkv_a", "norm_kv_a",
                        "norm_attn"),
    "gradient.mixer": ("hc_attn_phi", "hc_attn_b", "hc_attn_a",
                       "hc_ffn_phi", "hc_ffn_b", "hc_ffn_a")}


def kind_of(tensor: str) -> str:
    return next((k for k, names in KINDS.items() if tensor in names),
                "gradient.table")


class Driver(lm.Driver):
    def __init__(self, ctx):
        # a checkout whose model has no residual streams fails here, before
        # any actor thread exists: at once and cleanly
        from multiverso_tpu.models.lm import streams  # noqa: F401
        super().__init__(ctx)

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
        assert self.cfg.residual == "mhc" and self.cfg.attention == "mla"
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
        # ``layers``: the layers with routed experts, the module's among
        # them (what the shared expert readers count by); ``family``:
        # the counting module the merged readers take (lib/mlashapes.py)
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
            streams=c.hc_mult, parameters=c.parameters())

    def check(self) -> list:
        """drivers/lm.py's check against this model's reference; see the
        module's docstring."""
        wrong = []
        if not all(math.isfinite(float(x)) for x in self.losses):
            wrong.append("non-finite step loss")
        self.compared["non_finite_losses"] = [len(wrong), 0]
        return wrong + _Check(self).run()


def _pull(tables, shapes):
    """``tables`` whole, each in its tensor's shape."""
    return {n: table.get_device().reshape(shapes[n])
            for n, table in tables.items()}


class _Check(lm._Check):
    def __init__(self, driver):
        self.d = driver
        self.trainer, self.cfg = driver.trainer, driver.cfg
        self.c = ref.sizes(driver.config)
        self.worst, self.by_table, self.rms, self.grads = {}, {}, {}, {}
        self.norm2 = {}     # table -> its reference gradient's squared norm
        self.pooled = {}    # tensor -> its layers' (error^2, norm^2), kind

    def note(self, name, value, table):
        """A tensor's layers together, the worst tensor of a kind against
        the kind's limit."""
        if not name.startswith("gradient."):
            return super().note(name, value, table)
        tensor = table.rsplit(".", 1)[-1]
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
        t, cfg = self.trainer, self.cfg
        ids, _, _ = t._split(tokens)
        x, e_next = t._enter(t.embedding.get_rows_device(ids))
        chosen = {"layers": [], "mtp": None}
        for i, kind in enumerate(cfg.layer_kinds()):
            mats, small = t._pull_layer(i)
            x, _, _, layer_ids, *_ = t._forward[kind](mats, small, x)
            chosen["layers"].append(layer_ids if kind[2] else None)
        if cfg.mtp_layers:
            mats, small = t._pull_module()
            chosen["mtp"] = t._module[0](mats, small, t._leave(x),
                                         e_next)[3]
        return chosen

    def _sparse(self, chosen):
        """The sparse layers' choices, the module's last: ``[(table
        prefix, ids)]``."""
        out = [(f"layer{i}", ids) for i, ids in enumerate(chosen["layers"])
               if ids is not None]
        return out + ([("mtp.layer", chosen["mtp"])]
                      if chosen["mtp"] is not None else [])

    def loads(self, chosen):
        return super().loads([ids for _, ids in self._sparse(chosen)])

    # -- the reference, a sequence and a layer at a time --------------------
    def reference(self, tokens, chosen):
        """Every product of the reference in float32 at "highest" (the
        trainer's own programs, compiled outside, keep theirs)."""
        with ref.PRECISION:
            loss, differs = self._reference(tokens, chosen)
        # the worst layer's share of tokens whose experts are not the
        # reference's own choice has a limit here: the bias the server
        # keeps is in that choice
        self.worst["routing.differs"] = (max(differs), "")
        return loss, differs

    def _reference(self, tokens, chosen):
        import jax
        import jax.numpy as jnp
        c, t, cfg = self.c, self.trainer, self.cfg
        T, module = self.d.T, cfg.mtp_layers
        first = tokens[:, 1:T + 1]
        total = first.size

        def pull(i):
            return _pull(t.layers[i], cfg.layer_shapes(i))

        # one program a kind of layer (a dense layer's tensors differ)
        forward = jax.jit(lambda p, x, s: ref.layer(c, p, x, s, own=True))
        backward = jax.jit(lambda p, x, s, dy: jax.vjp(
            lambda p, x: ref.layer(c, p, x, s), p, x)[1](dy))
        embedding = t.embedding.get_device()
        rows = embedding[tokens[:, :T + module]]
        del embedding
        x = ref.expand(c, rows[:, :T])
        e_next = rows[:, 1:] if module else None
        del rows
        inputs, differs = [], []    # the layers' inputs wait on the host
        for i in range(cfg.n_layers):
            p, given = pull(i), chosen["layers"][i]
            inputs.append(np.asarray(x))
            out = [forward(p, x[b], None if given is None else given[b])
                   for b in range(x.shape[0])]
            x = jnp.stack([y for y, _ in out])
            if given is not None:
                differs.append(float(np.mean([
                    jnp.mean(jnp.any(jnp.sort(own, -1)
                                     != jnp.sort(given[b], -1), axis=-1))
                    for b, (_, own) in enumerate(out)])))
            del p, out
        xs = ref.collapse(c, x)
        del x
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

        loss, dxs, d_head, d_norm = head_pass(xs, norm, first,
                                              jnp.float32(total))
        self.grads["final_norm"] = np.asarray(d_norm)
        de_next = None
        if module:      # its loss and gradients weigh mtp_weight
            p = _pull(t.module, {**cfg.layer_shapes(cfg.n_layers - 1),
                                 **cfg.mtp_shapes()})
            layer = {n: v for n, v in p.items() if n != "final_norm"}
            through = jax.jit(lambda p, xs, e, s: ref.mtp(c, p, xs, e, s))
            back = jax.jit(lambda p, xs, e, s, dy: jax.vjp(
                lambda p, xs, e: ref.mtp(c, p, xs, e, s), p, xs, e)[1](dy))
            given = chosen["mtp"]
            y = jnp.stack([through(layer, xs[b], e_next[b], given[b])
                           for b in range(xs.shape[0])])
            second, dy, d_head_2, d_norm_2 = head_pass(
                y, p["final_norm"], tokens[:, 2:T + 2],
                jnp.float32(total / c["mtp_weight"]))
            loss, d_head = loss + second, d_head + d_head_2
            total_p, d_xs, d_e = None, [], []
            for b in range(xs.shape[0]):
                dp, dxb, deb = back(layer, xs[b], e_next[b], given[b], dy[b])
                total_p = dp if total_p is None else jax.tree_util.tree_map(
                    jnp.add, total_p, dp)
                d_xs.append(dxb)
                d_e.append(deb)
            dxs, de_next = dxs + jnp.stack(d_xs), jnp.stack(d_e)
            self._keep(total_p, "mtp", t.module)
            self.grads["mtp.final_norm"] = np.asarray(d_norm_2)
            differs.append(float(np.mean([jnp.mean(jnp.any(
                jnp.sort(ref.layer(c, layer, ref.expand(c, self._projected(
                    layer, xs[b], e_next[b])), own=True)[1], -1)
                != jnp.sort(given[b], -1), axis=-1))
                for b in range(xs.shape[0])])))
            del p, layer, total_p, y, dy
        self.grads["head"] = np.asarray(d_head)
        del head, d_head, xs
        dx = ref.expand(c, dxs)     # the sum's gradient: every stream's
        for i in reversed(range(cfg.n_layers)):
            p, given = pull(i), chosen["layers"][i]
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
        d_rows = ref.collapse(c, dx)    # a row a position embedded
        if module:
            d_rows = jnp.pad(d_rows, ((0, 0), (0, 1), (0, 0))) \
                + jnp.pad(de_next, ((0, 0), (1, 0), (0, 0)))
        self.grads["embedding"] = np.asarray(d_rows)
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
        """drivers/lm.py's, with two counts that have to stay 0: a bias's
        Add that differs, a table's second Add of the step."""
        for name in ("bias.differs", "adds.extra"):
            self.worst[name] = (0, "")
        return super().run()

    def _projected(self, p, xs, e_next):
        """The module's layer's first stream: ``W_p`` of both norms."""
        import jax.numpy as jnp
        c = self.c
        return jnp.concatenate(
            [ref.rmsnorm(xs, p["norm_h"], c["eps"]),
             ref.rmsnorm(e_next, p["norm_e"], c["eps"])], -1) @ p["proj"]

    def _keep(self, grads, prefix, tables):
        """A layer's reference gradients to the host, shaped as its tables
        (a bias has none: its Add is held to ``bias_step`` instead)."""
        own = self.cfg.mtp_shapes() if prefix == "mtp" else ()
        for n, g in grads.items():    # the trainer's ``tables()`` names
            if n != "router_bias":
                name = f"{prefix}.{n}" if prefix != "mtp" or n in own \
                    else f"mtp.layer.{n}"
                self.grads[name] = np.asarray(g).reshape(
                    tables[n].get_device().shape)

    # -- a bias's Add: no rule's state, no rounding -----------------------------
    def on_add(self, name, table, grad, ids, opt, send):
        if name not in self.grads:      # a table's second Add of the step
            self.note("adds.extra", self.worst.get(
                "adds.extra", (0, ""))[0] + 1, name)
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


