"""Plain reference for one SGNS block, and the comparison that holds a
cell's trainer to it, outside the window.

``reference_block`` is skip-gram with negative sampling written out in
float32 ``jax.numpy`` at ``default_matmul_precision("highest")``, with
hand-written gradients and no kernels, bands-as-slices or autodiff
shared with the program (Mikolov et al. 2013, "Distributed
representations of words and phrases", eq. 4; upstream
Applications/WordEmbedding/src/wordembedding.cpp):

    loss = sum over valid (center c, context o) of -log sigma(v_c . u_o)
         + sum over centers c, negatives n of  -valid(c) * log sigma(-v_c . u_n)

where valid(c) is the number of c's valid contexts, and the update is
``-lr * dloss/drow`` summed over every occurrence of a row.

Departures from the publication, all the program's and kept so that the
two compute the same thing:
- logits are clipped to +-6 (MAX_EXP) and a clipped logit gives no
  gradient, where word2vec.c saturates the gradient instead;
- ``neg_block`` consecutive centers share one draw of negatives, each
  weighted by its own count of valid contexts;
- the whole block reads the rows as they were before it (one summed
  update a block, not one a pair).

The ids, the validity mask and the negatives of the checked block are
read back from the program's own id program; the reference takes only
the rows those ids name.

TOLERANCE (on-chip, PR 23). Compared are the loss and the change of
every row the block touches (rows after minus rows before, which sums
duplicates as the table did), leaving out rows that take part in a pair
whose logit is within NEAR_CLIP of the clip (about 1% of them after a
20 s window; with them in, a few switched gradients made 3e-2 to 5e-2 of
the change's norm in one seed of three). The program's negative-sample
products run on the MXU at JAX's default precision (bfloat16 passes,
float32 accumulation) and the reference at "highest", so the two differ
by about 2**-9 of the negative part of each gradient: measured 2.3e-3 to
2.8e-3 of the input rows' change and 1.1e-3 to 1.2e-3 of the output
rows', loss 2e-7 to 8e-6 (my chip runs, PR 23). The bound is 6e-3 of the
change's norm, about twice the largest measured, and 1e-3 of the loss.
It is tight enough that a table kept in bfloat16 fails: 8 bits of
mantissa round a row of size ~4e-3 by ~1e-5 where a block changes it by
~5e-4, which makes 5e-3 (input) and 2e-2 (output) of the change's norm
on rows at their initial scale, and more as the rows grow
(tests/test_sgns_reference.py).
"""

import numpy as np

MAX_EXP = 6.0
NEAR_CLIP = 0.1     # |logit| this close to MAX_EXP: either side may clip it
LOSS_RTOL = 1e-3
CHANGE_RTOL = 6e-3


def reference_block(v, u_band, u_neg, pmask, lr):
    """v [C, D] center rows (input table), u_band [C + 2W, D] the band's
    rows and u_neg [C // B, K, D] the negatives' rows (output table),
    pmask [C, 2W] validity. Returns (loss, d_v, d_band, d_neg, near): the
    deltas to ADD to those rows, and for each of the three row sets which
    rows take part in a pair whose logit is within NEAR_CLIP of the
    clip. There the program's matmul rounding and the reference's can
    fall on different sides and switch a whole gradient on or off, so
    the comparison leaves those rows out."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        C, W = pmask.shape[0], pmask.shape[1] // 2
        nb, K, D = u_neg.shape
        B = C // nb
        offsets = [o for o in range(-W, W + 1) if o != 0]
        sigma = jax.nn.sigmoid
        nvalid = pmask.sum(axis=1)
        loss = jnp.float32(0.0)
        g_v = jnp.zeros_like(v)
        g_band = jnp.zeros_like(u_band)
        near_v = jnp.zeros(C, bool)
        near_band = jnp.zeros(C + 2 * W, bool)

        def near_clip(x):
            return jnp.abs(jnp.abs(x) - MAX_EXP) < NEAR_CLIP

        for w, off in enumerate(offsets):
            ctx = u_band[W + off:W + off + C]
            x = jnp.sum(v * ctx, axis=-1)
            live = (jnp.abs(x) <= MAX_EXP) * pmask[:, w]
            xc = jnp.clip(x, -MAX_EXP, MAX_EXP)
            loss += jnp.sum(jax.nn.softplus(-xc) * pmask[:, w])
            g = (sigma(xc) - 1.0) * live            # d loss / d logit
            g_v += g[:, None] * ctx
            g_band = g_band.at[W + off:W + off + C].add(g[:, None] * v)
            close = near_clip(x) & (pmask[:, w] > 0)
            near_v |= close
            near_band = near_band.at[W + off:W + off + C].max(close)
        vb = v.reshape(nb, B, D)
        x = jnp.einsum("nbd,nkd->nbk", vb, u_neg)
        weight = nvalid.reshape(nb, B)[:, :, None]
        live = (jnp.abs(x) <= MAX_EXP) * weight
        xc = jnp.clip(x, -MAX_EXP, MAX_EXP)
        loss += jnp.sum(jax.nn.softplus(xc) * weight)
        g = sigma(xc) * live
        g_v += jnp.einsum("nbk,nkd->nbd", g, u_neg).reshape(C, D)
        g_neg = jnp.einsum("nbk,nbd->nkd", g, vb)
        close = near_clip(x) & (weight > 0)
        near_v |= close.any(axis=2).reshape(C)
        near = (near_v, near_band, close.any(axis=1))
        return loss, -lr * g_v, -lr * g_band, -lr * g_neg, near


def _summed(ids, deltas):
    """What adding ``deltas`` at ``ids`` does to each id's row, in the
    order of ``ids`` (duplicates get their sum)."""
    ids = np.asarray(ids).reshape(-1)
    uniq, inverse = np.unique(ids, return_inverse=True)
    sums = np.zeros((uniq.size, deltas.shape[-1]), np.float64)
    np.add.at(sums, inverse, np.asarray(deltas, np.float64).reshape(
        ids.size, -1))
    return sums[inverse]


def _relative(observed, expected, ids, near) -> float:
    """Relative L2 error of the rows' change, over the rows no
    near-clip pair touches (a row is out if any of its occurrences is)."""
    ids = np.asarray(ids).reshape(-1)
    out = np.isin(ids, ids[np.asarray(near).reshape(-1)])
    observed, expected = observed[~out], expected[~out]
    return float(np.linalg.norm(observed - expected)
                 / max(np.linalg.norm(expected), 1e-30)), int(out.sum())


def _explain(table, ids, observed, expected, out, pair_logits):
    """A failed comparison explains itself: the ten rows of ``table``
    whose change differs most, each with the logits of the pairs it takes
    part in that lie nearest the clip, their distance from it, and the
    rounding bound of each (2**-8 of sum |v_i u_i|: what a bfloat16 pass
    of the product can move it by). A switched gradient shows as a row
    whose nearest pair is about as far from the clip as its bound."""
    ids = np.asarray(ids).reshape(-1)
    off = np.linalg.norm(observed - expected, axis=1)
    off[out] = 0.0
    whole = max(np.linalg.norm(expected[~out]), 1e-30)
    seen = set()
    for at in np.argsort(-off):
        if len(seen) == 10 or not off[at]:
            break
        if int(ids[at]) in seen:
            continue
        seen.add(int(ids[at]))
        logits, bounds = (np.concatenate(x) for x in zip(*(
            pair_logits(int(i)) for i in np.flatnonzero(ids == ids[at]))))
        nearest = np.argsort(np.abs(np.abs(logits) - MAX_EXP))[:4]
        pairs = ", ".join(
            f"{logits[j]:+.4f} ({abs(abs(logits[j]) - MAX_EXP):.4f} from "
            f"the clip, rounding bound {bounds[j]:.4f})" for j in nearest)
        print(f"[bench] reference block: {table} row {int(ids[at])} "
              f"({int((ids == ids[at]).sum())} occurrences, {logits.size} "
              f"pairs) is off by {off[at]:.3e}, {off[at] / whole:.2e} of "
              f"the change's norm; its change {np.linalg.norm(expected[at]):.3e}"
              f"; logits nearest the clip: {pairs}", flush=True)


def _pair_logits(v, u_band, u_neg, pmask, W, B):
    """For the failure log: ``(of_center, of_context)``, each a function
    from an occurrence's position (in ``in_ids``; in ``out_ids`` = [band |
    negatives]) to the float64 logits of the live pairs it takes part in
    and their rounding bounds."""
    v, u_band, u_neg = (np.asarray(x, np.float64) for x in (v, u_band, u_neg))
    pmask = np.asarray(pmask) > 0
    C, (nb, K, _) = v.shape[0], u_neg.shape
    offsets = [o for o in range(-W, W + 1) if o != 0]
    live_center = pmask.any(axis=1)

    def dot(a, b):
        return np.sum(a * b, axis=-1), np.sum(np.abs(a * b), axis=-1) / 256

    def of_center(c):
        ctx = np.stack([u_band[W + off + c] for off in offsets])[pmask[c]]
        negs = u_neg[c // B] if live_center[c] else u_neg[c // B][:0]
        return [np.concatenate(x) for x in zip(dot(v[c], ctx),
                                               dot(v[c], negs))]

    def of_context(j):
        if j >= C + 2 * W:                       # a negative's row
            n, k = divmod(j - (C + 2 * W), K)
            centers = np.arange(n * B, (n + 1) * B)
            return dot(v[centers[live_center[centers]]], u_neg[n, k])
        centers = np.array([(j - W - off, w) for w, off in enumerate(offsets)
                            if 0 <= j - W - off < C], int).reshape(-1, 2)
        centers = centers[pmask[centers[:, 0], centers[:, 1]], 0]
        return dot(v[centers], u_band[j])

    return of_center, of_context


def check(driver) -> list:
    """One block after the window, through the trainer's own programs,
    against ``reference_block``. Returns what disagreed, and puts the
    numbers compared, each beside its limit, into ``driver.compared``."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.models.wordembedding import device_train as dt
    trainer, model, cfg = driver.trainer, driver.model, driver.config
    C, W = trainer._C, cfg["window"]
    K, B = cfg["negative"], cfg["neg_block"]
    key, prep_key = jax.random.split(jax.random.PRNGKey(driver.w2v.seed + 7))
    kept, ksent, n_kept_dev = trainer._corpus.prep_epoch(prep_key)
    n_kept = int(n_kept_dev)
    # The stream's first block: the pass over the vocabulary, whose
    # logits are far from the clip. In topic text the trained model has
    # logits at +-6, where a rounding difference between the program's
    # matmul and the reference's switches a whole gradient on or off
    # (seen: 4.7e-2 of the change's norm from a handful of pairs).
    base = np.int32(0)
    lr = np.float32(cfg["init_learning_rate"])
    kept_pad, ksent_pad = dt._pad_stream(C, W, kept, ksent)
    neg_prob, neg_alias = model._neg_prob_dev, model._neg_alias_dev

    if driver.use_ps:
        tin, tout = model._in_table, model._out_table
        in_ids, out_ids, pmask = trainer._ids(
            kept_pad, ksent_pad, neg_prob, neg_alias, key, base, n_kept_dev)
        v, u = tin.get_rows_device(in_ids), tout.get_rows_device(out_ids)
        d_v, d_u, loss, _ = trainer._step(
            (v,), (u,), pmask, jnp.asarray(lr),
            jnp.float32(1.0 / model._num_workers))
        tin.add_rows(in_ids, d_v)
        tout.add_rows(out_ids, d_u)
        v_after = tin.get_rows_device(in_ids)
        u_after = tout.get_rows_device(out_ids)
    else:
        # The group program splits its key once a step, then the step
        # splits three ways; padded steps (base = n_kept, lr 0) are the
        # trainer's own exact no-ops, so one call runs one block.
        _, sub = jax.random.split(key)
        k_shrink, k_idx, k_keep = jax.random.split(sub, 3)
        in_ids, band, pmask = dt._band_former(
            C, W, n_kept_dev, kept_pad, ksent_pad, k_shrink, base)
        negs = dt._draw_negs(C, K, B, neg_prob, neg_alias, k_idx, k_keep)
        out_ids = jnp.concatenate([band, negs.reshape(-1)])
        v, u = model._emb_in[in_ids], model._emb_out[out_ids]
        bases = np.full(trainer._G, n_kept, np.int32)
        lrs = np.zeros(trainer._G, np.float32)
        bases[0], lrs[0] = base, lr
        model._emb_in, model._emb_out, loss, _, _ = trainer._group(
            model._emb_in, model._emb_out, kept, ksent, neg_prob, neg_alias,
            key, jnp.asarray(bases), jnp.asarray(lrs), n_kept_dev)
        v_after, u_after = model._emb_in[in_ids], model._emb_out[out_ids]

    return compare(v, u, v_after, u_after, in_ids, out_ids, pmask, lr,
                   float(loss), W, K, B, driver.compared)


def compare(v, u, v_after, u_after, in_ids, out_ids, pmask, lr, loss,
            W, K, B, compared=None) -> list:
    """The rows a block read (``v`` at ``in_ids``, ``u`` at ``out_ids`` =
    [band | negatives]) and the same rows after it, against
    ``reference_block`` on the rows read. Returns what disagreed, and
    puts each number compared into ``compared`` as ``[value, limit]``."""
    C = pmask.shape[0]
    n_band = C + 2 * W
    ref_loss, r_v, r_band, r_neg, near = reference_block(
        v, u[:n_band], u[n_band:].reshape(C // B, K, -1), pmask, lr)
    r_u = np.concatenate([np.asarray(r_band),
                          np.asarray(r_neg).reshape(-1, r_band.shape[-1])])
    wrong = []
    ref_loss = float(ref_loss)
    loss_err = abs(loss - ref_loss) / max(abs(ref_loss), 1e-30)
    near_u = np.concatenate([np.asarray(near[1]),
                             np.asarray(near[2]).reshape(-1)])
    changes = {
        "input": (np.asarray(v_after, np.float64) - np.asarray(v, np.float64),
                  _summed(in_ids, np.asarray(r_v)), in_ids, near[0]),
        "output": (np.asarray(u_after, np.float64)
                   - np.asarray(u, np.float64),
                   _summed(out_ids, r_u), out_ids, near_u)}
    errs = {table: _relative(*change) for table, change in changes.items()}
    (err_in, out_in), (err_out, out_out) = errs["input"], errs["output"]
    print(f"[bench] reference block: loss {loss:.3f} vs {ref_loss:.3f} "
          f"(rel {loss_err:.2e}); row change rel L2 error input "
          f"{err_in:.2e} output {err_out:.2e}; rows left out as near the "
          f"clip: {out_in} of {np.asarray(in_ids).size} input, {out_out} of "
          f"{np.asarray(out_ids).size} output", flush=True)
    if compared is not None:
        compared.update(loss_rel=[loss_err, LOSS_RTOL],
                        input_change_rel=[err_in, CHANGE_RTOL],
                        output_change_rel=[err_out, CHANGE_RTOL])
    if not loss_err <= LOSS_RTOL:
        wrong.append(f"reference block: loss {loss} vs {ref_loss}")
    logits = _pair_logits(v, u[:n_band], u[n_band:].reshape(C // B, K, -1),
                          pmask, W, B)
    for (table, (err, _)), pair_logits in zip(errs.items(), logits):
        if not err <= CHANGE_RTOL:
            wrong.append(f"reference block: {table} rows' change is off by "
                         f"{err:.3e} of its norm (bound {CHANGE_RTOL})")
            observed, expected, ids, close = changes[table]
            ids = np.asarray(ids).reshape(-1)
            out = np.isin(ids, ids[np.asarray(close).reshape(-1)])
            _explain(table, ids, observed, expected, out, pair_logits)
    return wrong
