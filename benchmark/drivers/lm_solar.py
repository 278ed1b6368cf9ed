"""Driver for the eighth family of language model trained through the
parameter server (multiverso_tpu/models/lm ``PSLMTrainer`` on an
``LMConfig`` whose attention's kind is a LAYER's and whose heads are HELD AS
A SHARE: Solar-Open2-250B's block, of every four layers the first
grouped-query softmax attention with no positions under a gate a lane,
``model.attention_vjp``, the other three the gated delta rule's scan with
beta up to 2 behind short convolutions, models/lm/delta.py; every layer
sparse with a shared expert under a sigmoid router that chooses through a
bias the server keeps, 8 of 320 experts held): drivers/lm.py's set-up and
window, drivers/lm_kda.py's Add-by-Add comparison (each bias's Add exactly,
no second Add, a tensor's layers of one kind together), with this model's
shapes and reference.

A round is one step on a fresh batch of ``sequences_per_step`` x
(``seq_len`` + 1) tokens. ``work["words"]`` is ``B T`` a step.

``check`` runs one more step at the cell's sizes through the trainer's own
programs and holds it to benchmark/reference/lm_solar_step.py on the same
device, given each token's experts from the program and the SAME share
(held heads, held experts, the vocabulary's slice): the loss, every
tensor's gradient against its own norm by kind (``KINDS``), every table and
both moments after the Add, ``bias.differs``, ``adds.extra``,
``routing.differs``, ``layer.output`` (each layer ALONE, forward, on the
program's own input: drivers/lm_kda.py's reason) and ``scan.carry``.

``scan.carry`` runs the program's own ``delta.scan`` (the function the
layers call, in this process) over the held heads on two sets of inputs
that no step's batch gives, against the reference's recurrence, relative
L2, the worse of the two:
- ``scan.carry.state``: drivers/lm_kda.py's (one key for every position, a
  first write of 1, then writes of ``2^-16`` a position with no decay): a
  state kept in bfloat16 from chunk to chunk takes none of them;
- ``scan.carry.beta``: beta drawn on [1, 2] at every position, the keys of
  a chunk near one another (each the chunk's own direction plus half as
  much of its own, so ``k . k'`` about 0.8 and ``beta k . k'`` up to 1.6
  below the diagonal of every chunk's ``I + A``), a slow decay: the regime
  ``kda_allow_neg_eigval`` buys, where a solve that sums powers of ``A``
  cancels to nothing in float32 (PERF.md section 6, PR 60).
"""

import math

import numpy as np

from benchmark.drivers import lm, lm_kda
from benchmark.reference import lm_solar_step as ref

FEED_FORWARD = lm_kda.FEED_FORWARD
# A tensor's kind, by its name under its layer's kind of attention (the
# configuration's ``limits`` has a limit a kind). ``gradient.scan``: what
# feeds the recurrence; ``gradient.scores``: what feeds the softmax layer's
# scores; the lane gate, ``W_v`` and both ``W_o`` are ``gradient.table``'s.
KINDS = {
    "gradient.gate": ("w_gate", "ws_gate", "norm_ffn"),
    "gradient.router": ("router",),
    "gradient.scores": ("gqa.wq", "gqa.wk", "gqa.norm_attn"),
    "gradient.scan": lm_kda.KINDS["gradient.scan"]}


def kind_of(tensor: str) -> str:
    return next((k for k, names in KINDS.items() if tensor in names),
                "gradient.table")


class Driver(lm.Driver):
    def __init__(self, ctx):
        # a checkout whose model holds no share of a delta layer's heads
        # fails here, before any actor thread exists: at once and cleanly
        from multiverso_tpu.models.lm import LMConfig
        getattr(LMConfig, "kda_heads_held")
        super().__init__(ctx)

    def build(self):
        super().build()
        c = self.cfg
        assert c.attention_layout and c.one_ffn_input \
            and c.scoring == "sigmoid_bias" and c.attn_gate == "lane"
        # ``kda_heads``, ``heads``: the HELD ones, which the shared readers
        # (benchmark/lib/kdashapes.py) and solarshapes.py count by; no
        # ``heads_held``, ``heads_layout``, ``gate_heads`` nor ``mla_heads``:
        # the readers that count another family's attention find nothing
        self.ctx.shapes.clear()
        self.ctx.shapes.update(
            family="solar", sequences=self.B, seq_len=self.T, hidden=c.hidden,
            attention_layout=list(c.attention_layout),
            kda_heads=c.kda_heads_held, kda_heads_all=c.kda_heads,
            kda_head_dim=c.kda_head_dim, kda_conv=c.kda_conv,
            heads=c.n_heads_held, heads_all=c.n_heads,
            kv_heads=c.n_kv_heads_held, head_dim=c.head_dim,
            router_outputs=c.n_experts, top_k=c.top_k,
            held=c.experts_held[1], expert_width=c.expert_width,
            shared_width=c.shared_width, vocab=c.vocab, layers=c.n_layers,
            sparse_layers=c.n_layers, parameters=c.parameters())

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

    def tensor_of(self, table: str) -> str:
        layer, _, tensor = table.rpartition(".")
        if not layer.startswith("layer") or tensor in FEED_FORWARD:
            return tensor
        i = int(layer.removeprefix("layer"))
        return f"{self.cfg.attention_of(i)}.{tensor}"

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
    def reference(self, tokens, chosen):
        with ref.PRECISION:
            loss, differs = self._reference(tokens, chosen)
        self.worst["routing.differs"] = (max(differs), "")
        return loss, differs

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
            inputs.append(np.asarray(x))
            out = [forward(p, x[b], given[b]) for b in sequences]
            x = jnp.stack([y for y, _ in out])
            differs.append(float(np.mean([
                jnp.mean(jnp.any(jnp.sort(own, -1)
                                 != jnp.sort(given[b], -1), axis=-1))
                for b, (_, own) in enumerate(out)])))
            # the layer ALONE, on the program's own input: what it adds to
            # the stream against what the reference adds to the same
            theirs, after = (jax.device_put(a, placed)
                             for a in self.stream[i:i + 2])
            want = jnp.stack([forward(p, theirs[b], given[b])[0]
                              for b in sequences])
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
            for b in sequences:
                dp, dxb = backward(p, x_in[b], given[b], dx[b])
                total_p = dp if total_p is None else jax.tree_util.tree_map(
                    jnp.add, total_p, dp)
                parts.append(dxb)
            dx = jnp.stack(parts)
            for n, g in total_p.items():
                if n != "router_bias":  # no gradient: its Add is a step
                    self.grads[f"layer{i}.{n}"] = np.asarray(g).reshape(
                        t.layers[i][n].get_device().shape)
            # what the bias's Add has to carry, and (on_add) leave: the
            # step itself, from a bias of zeros
            self.grads[f"layer{i}.router_bias"] = np.asarray(
                ref.bias_step(c, jnp.zeros(p["router_bias"].shape),
                              ref.load_of(c, given)))
            del p, total_p, x_in
        self.grads["embedding"] = np.asarray(dx)    # a row a position
        for n, g in self.grads.items():
            self.norm2[n] = float(np.sum(np.square(g, dtype=np.float64)))
            self.rms[n] = (self.norm2[n] / g.size) ** 0.5
        return float(loss), differs

    # -- the scan alone: the state's precision, and beta up to 2 ------------------
    def carried(self, chunks: int = 64) -> float:
        """The program's scan against the reference's recurrence on the two
        sets of inputs of the module's docstring: the worse relative error
        (both are reported)."""
        import jax
        import jax.numpy as jnp
        from multiverso_tpu.models.lm import delta
        heads, d = self.cfg.kda_heads_held, self.cfg.kda_head_dim
        n = delta.CHUNK
        t = chunks * n
        one = jnp.zeros((t, heads, d), jnp.float32).at[..., 0].set(1.0)
        first = jnp.arange(t)[:, None] == 0
        state = (one, one,
                 jnp.where(first, 1.0, 2.0)[..., None]
                 * jnp.ones((t, heads, d)),
                 jnp.zeros_like(one),
                 jnp.where(first, 1.0, 2.0 ** -16) * jnp.ones((t, heads)))

        def unit(a):
            return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

        keys = jax.random.split(jax.random.PRNGKey(60), 6)
        own = jax.random.normal(keys[0], (chunks, 1, heads, d))
        k = unit(unit(own) + 0.5 * unit(jax.random.normal(
            keys[1], (chunks, n, heads, d)))).reshape(t, heads, d)
        beta = (unit(jax.random.normal(keys[2], (t, heads, d))) * d ** -0.5,
                k, jax.random.normal(keys[3], (t, heads, d)),
                -0.05 * jax.random.uniform(keys[4], (t, heads, d)),
                jax.random.uniform(keys[5], (t, heads), minval=1.0,
                                   maxval=2.0))
        scan = jax.jit(lambda *a: delta.scan(*a)[0])
        recurrence = jax.jit(ref.recurrence)
        worst = 0.0
        for name, args in (("state", state), ("beta", beta)):
            got = scan(*args)
            with ref.PRECISION:
                want = recurrence(*args)
            error = float(jnp.linalg.norm(got - want)
                          / jnp.linalg.norm(want))
            self.d.compared[f"scan.carry.{name}"] = [error, 1.0]
            worst = max(worst, error)
        return worst
