"""Driver for a language model trained by block diffusion through the
parameter server (multiverso_tpu/models/lm ``PSLMTrainer`` under
``LMConfig.objective == "block_diffusion"``): drivers/lm.py's set-up,
window and Add-by-Add comparison, with this objective's batches and
reference.

A round is one step on a fresh batch of ``sequences_per_step`` x
``seq_len`` CLEAN tokens (Zipf over the vocabulary slice's ids but the
mask token's, drawn on the device from ``--seed`` before the window):
the trainer's noise program, the embedding rows' Get by device keys for
both copies, 74 whole-table device Gets, the layer programs over ``2 x
seq_len`` positions a sequence, 74 whole-table device Adds and the
embedding's device-key Add. ``work["words"]`` is clean tokens trained.

``check`` runs one more step at the cell's sizes through the trainer's
own programs and holds it to benchmark/reference/lm_bd_step.py on the same
device, given the step's noise (it is the traffic: the reference checks
it, ``check_noise``) and each position's experts from the program: the
loss, every tensor's gradient (its layers together) against its own norm
by kind (``_Check.note``), every table and both moments after the Add
(drivers/lm.py ``_Check.on_add``).
"""

import numpy as np

from benchmark.drivers import lm
from benchmark.reference import lm_bd_step as ref


class Driver(lm.Driver):
    def __init__(self, ctx):
        # a checkout whose model has no block-diffusion objective fails
        # here, before any actor thread exists: at once and cleanly
        from multiverso_tpu.models.lm.model import noise  # noqa: F401
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
        assert self.cfg.objective == "block_diffusion"
        assert self.cfg.block_length == int(self.traffic["block_length"])
        opt = self.config["optimizer"]
        self.trainer = PSLMTrainer(
            self.cfg, self.T, self.B, seed=seed % (2 ** 24), lr=opt["lr"],
            beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["eps"],
            init_std=self.config["init_std"],
            embedding_std=self.config["embedding_init_std"],
            warmup_steps=opt["warmup_steps"])
        n = int(self.traffic["batches"])
        exponent = self.traffic["token_distribution"]["exponent"]
        # every id but the last, the mask token's
        pool = jax.jit(lambda key: zipf_tokens(
            key, (n + 1, self.B, self.T), self.cfg.vocab - 1, exponent))(
                jax.random.PRNGKey(seed))
        self.batches = [pool[i] for i in range(n)]
        self.check_batch = pool[n]
        jax.block_until_ready(self.batches)
        c = self.cfg
        # ``family``: the counting module the merged readers take
        # (trainer.mfu.lm, trainer.attn_roofline.lm: lib/bdshapes.py)
        self.ctx.shapes.update(
            family="bd", sequences=self.B, seq_len=self.T, hidden=c.hidden,
            heads=c.n_heads, kv_heads=c.n_kv_heads, head_dim=c.head_dim,
            router_outputs=c.n_experts, held=c.experts_held[1],
            expert_width=c.expert_width, vocab=c.vocab, layers=c.n_layers,
            block_length=c.block_length, parameters=c.parameters())

    def check(self) -> list:
        """drivers/lm.py's check against this objective's reference; see
        the module's docstring."""
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
        self.pooled = {}    # tensor -> its layers' (error^2, norm^2), kind

    def note(self, name, value, table):
        """drivers/lm.py's kinds with the routers a kind of their own, and
        a tensor's six layers TOGETHER: their errors against their common
        norm (a tensor's layers are one size, so each weighs by its
        reference gradient's mean square), the worst tensor of a kind
        against the kind's limit. The loss reads masked positions alone
        and they all hold one token: where that token's experts are not
        held, the last layer's experts and router (and its q and k
        projections less so) keep a gradient a hundredth of the other
        layers', made of near-ties, which read alone is 1e-2 of nearly
        nothing (the configuration's ``limits.what``)."""
        if not name.startswith("gradient."):
            return super().note(name, value, table)
        tensor = table.rsplit(".", 1)[-1]
        kind = "gradient.router" if tensor == "router" else name
        weigh = self.rms[table] ** 2
        error, norm, _ = self.pooled.get(tensor, (0.0, 0.0, kind))
        self.pooled[tensor] = (error + float(value) ** 2 * weigh,
                               norm + weigh, kind)
        self.worst[kind] = max(
            ((e / max(n, 1e-60)) ** 0.5, t)
            for t, (e, n, k) in self.pooled.items() if k == kind)

    def noise(self, tokens):
        """The noise of the step about to run: ``(ids [B, 2L], masked,
        t)``, from the trainer's own program."""
        ids, _, _, _, _, masked, ts = self.trainer.noised(tokens)
        return ids, masked, ts

    # -- the program's forward pass, for each position's experts ------------
    def chosen(self, tokens):
        t = self.trainer
        ids, _, _ = self.noise(tokens)
        x = t.embedding.get_rows_device(ids)
        chosen = []
        for i, kind in enumerate(zip(self.cfg.rope_layout,
                                     self.cfg.window_layout)):
            mats, small = t._pull_layer(i)
            x, _, _, layer_ids = t._forward[kind](mats, small, x)
            chosen.append(layer_ids)
        return chosen

    # -- the reference, a sequence and a layer at a time --------------------
    def reference(self, tokens, chosen):
        import jax
        import jax.numpy as jnp
        c, t = self.c, self.trainer
        ids, masked, ts = self.noise(tokens)
        half = tokens.shape[1]
        self.noise_problems = ref.check_noise(c, tokens, ids[:, :half],
                                              masked, ts)
        total = tokens.size

        def pull(i):
            return {n: table.get_device().reshape(self.cfg.layer_shapes()[n])
                    for n, table in t.layers[i].items()}

        forward = jax.jit(lambda p, x, s: ref.layer(c, p, x, s, own=True))
        backward = jax.jit(lambda p, x, s, dy: jax.vjp(
            lambda p, x: ref.layer(c, p, x, s), p, x)[1](dy))
        embedding = t.embedding.get_device()
        inputs, x, differs = [], embedding[ids], []
        del embedding
        for i in range(c["layers"]):
            p = pull(i)
            inputs.append(x)
            out = [forward(p, x[b], chosen[i][b]) for b in range(x.shape[0])]
            x = jnp.stack([y for y, _ in out])
            differs.append(float(np.mean([
                jnp.mean(jnp.any(jnp.sort(own, -1)
                                 != jnp.sort(chosen[i][b], -1), axis=-1))
                for b, (_, own) in enumerate(out)])))
            del p, out
        head, norm = t.head.get_device(), t.final_norm.get_device()
        block = min(self.cfg.loss_block, half)
        loss_block = jax.jit(jax.value_and_grad(
            lambda x, h, n, y, w: ref.head_loss(c, h, n, x, y, w, total),
            (0, 1, 2)))
        loss, d_head, d_norm, dx = 0.0, 0.0, 0.0, []
        flat = x[:, :half].reshape(-1, x.shape[-1])
        flat_y = tokens.reshape(-1)
        flat_w = ref.loss_weights(c, masked, ts).reshape(-1)
        for at in range(0, total, block):
            part, (dxb, dh, dn) = loss_block(
                flat[at:at + block], head, norm, flat_y[at:at + block],
                flat_w[at:at + block])
            loss, d_head, d_norm = loss + part, d_head + dh, d_norm + dn
            dx.append(dxb)
        self.grads["head"] = np.asarray(d_head)
        self.grads["final_norm"] = np.asarray(d_norm)
        del head, d_head, flat, x
        dx = jnp.concatenate(dx).reshape(tokens.shape + (-1,))
        dx = jnp.concatenate([dx, jnp.zeros_like(dx)], axis=1)
        for i in reversed(range(c["layers"])):
            p = pull(i)
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

    def run(self) -> list:
        wrong = super().run()
        return wrong + [f"noise: {p}" for p in self.noise_problems]
