"""Driver for the fifth family of language model trained through the
parameter server (multiverso_tpu/models/lm ``PSLMTrainer`` on an
``LMConfig`` with a ``selection``: Qwen3-MoE's block whose attention runs
over the keys a learned indexer selects, models/lm/sparse.py):
drivers/lm.py's set-up, window and Add-by-Add comparison, with this
model's shapes and reference.

A round is one step on a fresh batch of ``sequences_per_step`` x
(``seq_len`` + 1) tokens (drivers/lm.py's batches): the embedding rows'
Get by device keys, every other table Got whole on the device, the layer
programs, every table's Add. ``work["words"]`` is ``B T`` a step.

``check`` runs one more step at the cell's sizes through the trainer's
own programs and holds it to benchmark/reference/lm_sparse_step.py on the
same device, given each token's experts and each query's keys from the
program (the selection by the program's own ``sparse.selection_of`` on
the layer's input): both losses, every tensor's gradient (its layers
together) against its own norm by kind (``KINDS``), every table and both
moments after the Add (drivers/lm.py ``_Check.on_add``), that no table
gets a second Add, and the worst layer's share of choices on which the
reference's OWN selection and routing depart from the program's
(``selection.differs``, ``routing.differs``: the reference's from the
float32 tables, so the products' bfloat16 inputs give them a floor). What
holds the selection to EXACT and its scores to float32 is
``selection.inexact``: the share of the program's choices that the
reference's top-k lacks when it scores the program's OWN index inputs
(brought back from ``sparse.selection_of``) by the stated arithmetic,
which leaves the order of the float32 sums alone between the two. And
``layer.output`` holds each layer ALONE, forward: what the program's layer
adds to its own input (``y - x``) against what the reference's layer adds
to the same input, given the same keys and experts, relative L2, the worst
layer. A gradient's error has a floor that every tensor of a step shares
and that swings by seed; a layer's own output has none, so a lower
precision inside one layer (float8-rounded expert inputs) reads here
where the gradients' limits, at twice their largest sound reading, let it
pass.
"""

import numpy as np

from benchmark.drivers import lm
from benchmark.reference import lm_sparse_step as ref

# A tensor's kind, by its name (the configuration's ``limits`` has a limit
# a kind; its ``limits.what`` the readings). A tensor's layers are taken
# TOGETHER (drivers/lm_bd.py's reason): their errors against their common
# norm, the worst tensor of a kind against the kind's limit.
KINDS = {
    "gradient.gate": ("w_gate", "norm_ffn"),
    "gradient.router": ("router",),
    "gradient.scores": ("wq", "wk", "norm_q", "norm_k", "norm_attn"),
    "gradient.indexer": ("wq_index", "wk_index", "w_index", "index_norm_g",
                         "index_norm_b")}


def kind_of(tensor: str) -> str:
    return next((k for k, names in KINDS.items() if tensor in names),
                "gradient.table")


class Driver(lm.Driver):
    def __init__(self, ctx):
        # a checkout whose model has no selection fails here, before any
        # actor thread exists: at once and cleanly
        from multiverso_tpu.models.lm import sparse  # noqa: F401
        super().__init__(ctx)

    def build(self):
        super().build()
        c = self.cfg
        assert c.selection == "topk_indexer" and c.one_ffn_input
        # ``family``: the counting module the merged readers take
        # (lib/sparseshapes.py)
        self.ctx.shapes.clear()
        self.ctx.shapes.update(
            family="sparse", sequences=self.B, seq_len=self.T, hidden=c.hidden,
            heads=c.n_heads, kv_heads=c.n_kv_heads, head_dim=c.head_dim,
            router_outputs=c.n_experts, top_k=c.top_k,
            held=c.experts_held[1], expert_width=c.expert_width,
            vocab=c.vocab, layers=c.n_layers, index_heads=c.index_heads,
            index_dim=c.index_dim, index_topk=c.index_topk,
            index_tile=c.index_tile, parameters=c.parameters())

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
        """A tensor's layers together, each weighed by its reference
        gradient's squared norm; the worst tensor of a kind against the
        kind's limit."""
        if not name.startswith("gradient."):
            return super().note(name, value, table)
        tensor = table.rpartition(".")[2]
        kind = kind_of(tensor)
        weigh = self.norm2[table]
        error, norm, _ = self.pooled.get(tensor, (0.0, 0.0, kind))
        self.pooled[tensor] = (error + float(value) ** 2 * weigh,
                               norm + weigh, kind)
        self.worst[kind] = max(
            ((e / max(n, 1e-60)) ** 0.5, t)
            for t, (e, n, k) in self.pooled.items() if k == kind)

    # -- the program's forward pass, for both of its choices ------------------
    def chosen(self, tokens):
        """By layer: ``(each token's experts [B, T, k], each query's keys
        [B, T, T / 8] as packed bits on the host, the index inputs they
        were selected by: qI [B, T, heads, dim], kI [B, T, dim], w [B, T,
        heads] on the host)``, the keys from the program's own selection
        of the layer's input."""
        import jax
        from multiverso_tpu.models.lm import sparse
        from multiverso_tpu.models.lm.ps_train import _kind
        t, cfg = self.trainer, self.cfg
        pos = _kind(cfg, 1, 0, self.d.T)[2]
        def selection_of(mats32, small, seq):
            tiles, _, inputs = sparse.selection_of(
                cfg, {n: w.astype(jax.numpy.bfloat16)
                      for n, w in mats32.items()}, small, seq, pos)
            return sparse._untiled(tiles), inputs

        select = jax.jit(lambda mats32, small, x: jax.lax.map(
            lambda seq: selection_of(mats32, small, seq), x))
        ids, _, _ = t._split(tokens)
        x = t.embedding.get_rows_device(ids)
        chosen, self.stream = [], [np.asarray(x)]
        for i, kind in enumerate(cfg.layer_kinds()):
            mats, small = t._pull_layer(i)
            keys, inputs = select(mats, small, x)
            keys = np.packbits(np.asarray(keys), axis=-1)
            x, _, _, layer_ids = t._forward[kind](mats, small, x)
            chosen.append((layer_ids, keys,
                           tuple(np.asarray(a) for a in inputs)))
            self.stream.append(np.asarray(x))
        return chosen

    def loads(self, chosen):
        return super().loads([c[0] for c in chosen])

    # -- the reference, a sequence and a layer at a time --------------------
    def reference(self, tokens, chosen):
        """Every product of the reference in float32 at "highest" (the
        trainer's own programs, compiled outside, keep theirs)."""
        with ref.PRECISION:
            return self._reference(tokens, chosen)

    def _reference(self, tokens, chosen):
        import jax
        import jax.numpy as jnp
        c, t, cfg = self.c, self.trainer, self.cfg
        ids, targets = tokens[:, :-1], tokens[:, 1:]
        total, seq = targets.size, ids.shape[1]
        pos = ref.positions(seq)

        def pull(i):
            shapes = cfg.layer_shapes(i)
            return {n: table.get_device().reshape(shapes[n])
                    for n, table in t.layers[i].items()}

        def keys_of(i, b):
            return jnp.asarray(np.unpackbits(chosen[i][1][b], axis=-1,
                                             count=seq).astype(bool))

        forward = jax.jit(lambda p, x, s, k, theirs: ref.layer(
            c, p, x, pos, s, k, own=True, program=theirs))
        backward = jax.jit(lambda p, x, s, k, dy: jax.vjp(
            lambda p, x: ref.layer(c, p, x, pos, s, k), p, x)[1](
                (dy, jnp.ones((), jnp.float32))))
        embedding = t.embedding.get_device()
        x = embedding[ids]
        placed = x.sharding
        del embedding
        inputs, differs, lacks, inexact, moved, inner = [], [], [], [], [], 0.0
        for i in range(cfg.n_layers):
            p, given = pull(i), chosen[i][0]
            inputs.append(np.asarray(x))
            out = [forward(p, x[b], given[b], keys_of(i, b),
                           tuple(jnp.asarray(a[b]) for a in chosen[i][2]))
                   for b in range(x.shape[0])]
            x = jnp.stack([o[0] for o in out])
            inner += sum(float(o[1]) for o in out)
            differs.append(float(np.mean([
                jnp.mean(jnp.any(jnp.sort(o[2], -1)
                                 != jnp.sort(given[b], -1), axis=-1))
                for b, o in enumerate(out)])))
            counts = np.sum([np.asarray(o[3]) for o in out], axis=0)
            lacks.append(float(counts[1]) / max(float(counts[0]), 1.0))
            inexact.append(float(counts[2]) / max(float(counts[0]), 1.0))
            # the layer ALONE, on the program's own input: what it adds to
            # the stream against what the reference adds to the same (by
            # `forward` again, its input placed as `x` is so that no second
            # executable is built: a program of its own for the one result
            # is 18 MB more in the compile cache, and the cell's no longer
            # fit the chip machine's 192 MiB together)
            theirs, after = (jax.device_put(a, placed)
                             for a in self.stream[i:i + 2])
            want = jnp.stack([
                forward(p, theirs[b], given[b], keys_of(i, b),
                        tuple(jnp.asarray(a[b]) for a in chosen[i][2]))[0]
                for b in range(x.shape[0])])
            moved.append(float(jnp.linalg.norm(after - want)
                               / jnp.linalg.norm(want - theirs)))
            del p, out, theirs, after, want
        self.inner_loss = inner
        self.worst["routing.differs"] = (max(differs), "")
        self.worst["selection.differs"] = (max(lacks), "")
        self.worst["selection.inexact"] = (max(inexact), "")
        self.worst["layer.output"] = (max(moved),
                                      f"layer{np.argmax(moved)}")
        self.lacks, self.inexact, self.moved = lacks, inexact, moved
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
            p, given = pull(i), chosen[i][0]
            x_in = jnp.asarray(inputs.pop())
            total_p, parts = None, []
            for b in range(dx.shape[0]):
                dp, dxb = backward(p, x_in[b], given[b], keys_of(i, b), dx[b])
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

    # -- one Add a table a step -------------------------------------------------
    def on_add(self, name, table, grad, ids, opt, send):
        if name not in self.grads:      # a table's second Add of the step
            self.note("adds.extra", self.worst["adds.extra"][0] + 1, name)
            return send()
        return super().on_add(name, table, grad, ids, opt, send)

    def watch(self):
        """drivers/lm.py's; what undoes it, right after the step, reads
        the step's inner loss beside the reference's."""
        undo = super().watch()

        def done():
            undo()
            self.inner = float(self.trainer.last_inner_loss)
            self.worst["loss.indexer"] = (
                abs(self.inner - self.inner_loss) / abs(self.inner_loss), "")

        return done

    def run(self) -> list:
        """drivers/lm.py's, with the inner loss beside the step's, and a
        count that has to stay 0: a table's second Add of the step."""
        self.worst["adds.extra"] = (0, "")
        wrong = super().run()
        for i, share in enumerate(self.lacks):      # reported alone
            self.d.compared[f"selection.differs.layer{i}"] = [share, 1.0]
            self.d.compared[f"selection.inexact.layer{i}"] = [
                self.inexact[i], 1.0]
            self.d.compared[f"layer.output.layer{i}"] = [self.moved[i], 1.0]
        print(f"[bench] check: the layers' inner losses {self.inner:.6f} "
              f"reference {self.inner_loss:.6f}; share of selected keys "
              f"that the reference's own selection lacks, by layer: "
              f"{[round(s, 6) for s in self.lacks]}; that its exact "
              f"selection of the program's own index inputs lacks: "
              f"{[float(f'{s:.3g}') for s in self.inexact]}", flush=True)
        return wrong
