"""The multi-token module (DeepSeek-V3's report, section 2.2, whose keys
``num_nextn_predict_layers`` is), on either residual: after the last layer,
for position ``i`` with the model's stream ``x_i`` [C] before the final norm
(under ``residual: "mhc"`` the summed streams) and the NEXT token's embedding
row,

    h'_i = W_p [RMSNorm(x_i; g_h) ; RMSNorm(E[t_{i+1}]; g_e)]    W_p [2C, C]

``h'`` goes through one sparse layer of the module's own, of the last
layer's kinds: on the plain residual ``model.layer_vjp``'s ``x + F(RMSNorm
(x))`` as it is, with no expansion and no sum; under ``"mhc"`` it enters
every stream of ``streams.layer_vjp``, whose streams are summed again. A
final norm of the module's own and the model's OWN head then predict
``t_{i+2}`` (``PSLMTrainer`` runs that head pass, ``mv.lm.mtp.head``, and
sums the head's and the embedding's two gradients before their one Add each).

The module's tensors are its layer's under their own names plus ``proj``
(a matrix), ``norm_h`` and ``norm_e``. Scope ``mv.lm.mtp``: the two
norms, the projection, and the streams' expansion and sum where there are
streams; the layer's parts keep their own scopes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import model as lm
from . import streams
from .model import LMConfig

SCOPE = "mv.lm.mtp"
MATRICES = ("proj",)
SMALL = ("norm_h", "norm_e")


def project(cfg: LMConfig, proj, sink, norms, xs, e_next):
    """``W_p [norm(xs) ; norm(e_next)]``: [T, C], or expanded into every
    stream [n C, T]."""
    g_h, g_e = norms
    both = jnp.concatenate([lm.rmsnorm(xs, g_h, cfg.eps),
                            lm.rmsnorm(e_next, g_e, cfg.eps)], axis=-1)
    h = lm.mm(both, proj, sink)
    return streams.expand(cfg, h) if cfg.residual == "mhc" else h


def _layer_vjp(cfg: LMConfig, mats, small, x, pos):
    """``x`` through the module's sparse layer, of the last layer's kinds:
    ``(y [T, C], (stats, ids), pull)``, ``pull(dy) -> (dx, matrix
    gradients, small gradients)``."""
    if cfg.residual == "mhc":
        x, aux, pull_layer = streams.layer_vjp(cfg, 1, mats, small, x, pos)
        with jax.named_scope(SCOPE):
            y = streams.collapse(cfg, x)

        def pull(dy):
            with jax.named_scope(SCOPE):
                dx = streams.expand(cfg, dy)
            return pull_layer(dx)

        return y, lm.layer_stats(cfg, 1, aux), pull
    last = cfg.n_layers - 1
    rope, window = cfg.rope_layout[last], cfg.window_layout[last]
    return lm.layer_vjp(
        cfg, cfg.rotary(rope, window), cfg.layer_mask(window, x.shape[0]), 1,
        mats, small, x, pos,
        cfg.attention_layout[last] if cfg.attention_layout else None)


def module_vjp(cfg: LMConfig, mats, small, xs, e_next, pos=None):
    """One sequence's stream ``xs`` [T, C] and next-token rows ``e_next``
    [T, C] through the module up to its final norm: ``(y [T, C], (stats,
    ids), pull)``, the pair ``model.layer_stats``' of the module's layer,
    ``pull(dy) -> (dxs, de_next, matrix gradients, small gradients)``."""
    with jax.named_scope(SCOPE):
        x, pull_project = jax.vjp(
            lambda s, norms, xs, e: project(cfg, mats["proj"], s, norms, xs,
                                            e),
            jnp.zeros(mats["proj"].shape, lm.F32),
            tuple(small[n] for n in SMALL), xs, e_next)
    layer = {n: w for n, w in mats.items() if n not in MATRICES}
    y, stats, pull_layer = _layer_vjp(cfg, layer, small, x, pos)

    def pull(dy):
        dx, d_mats, d_small = pull_layer(dy)
        with jax.named_scope(SCOPE):
            d_proj, d_norms, dxs, de = pull_project(dx)
        return (dxs, de, {**d_mats, "proj": d_proj},
                {**d_small, **dict(zip(SMALL, d_norms))})

    return y, stats, pull
