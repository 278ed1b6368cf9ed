"""The third family's multi-token module (DeepSeek-V3's report, section
2.2, whose keys ``num_nextn_predict_layers`` is): after the last layer,
for position ``i`` with the summed streams ``x_i`` (before the final
norm) and the NEXT token's embedding row,

    h'_i = W_p [RMSNorm(x_i; g_h) ; RMSNorm(E[t_{i+1}]; g_e)]    W_p [2C, C]

``h'`` enters every stream of one sparse layer of its own
(streams.layer_vjp), whose streams are summed again; a final norm of the
module's own and the model's OWN head then predict ``t_{i+2}``
(``PSLMTrainer`` runs that head pass, ``mv.lm.mtp.head``, and sums the
head's and the embedding's two gradients before their one Add each).

The module's tensors are its layer's under their own names plus ``proj``
(a matrix), ``norm_h`` and ``norm_e``. Scope ``mv.lm.mtp``: the two
norms, the projection, the streams' expansion and sum; the layer's parts
keep their own scopes.
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
    """``expand(W_p [norm(xs) ; norm(e_next)])``: [n C, T]."""
    g_h, g_e = norms
    both = jnp.concatenate([lm.rmsnorm(xs, g_h, cfg.eps),
                            lm.rmsnorm(e_next, g_e, cfg.eps)], axis=-1)
    return streams.expand(cfg, lm.mm(both, proj, sink))


def module_vjp(cfg: LMConfig, mats, small, xs, e_next, pos=None):
    """One sequence's summed streams ``xs`` [T, C] and next-token rows
    ``e_next`` [T, C] through the module up to its final norm: ``(y [T,
    C], aux, pull)``, ``aux`` the layer's, ``pull(dy) -> (dxs, de_next,
    matrix gradients, small gradients)``."""
    with jax.named_scope(SCOPE):
        x, pull_project = jax.vjp(
            lambda s, norms, xs, e: project(cfg, mats["proj"], s, norms, xs,
                                            e),
            jnp.zeros(mats["proj"].shape, lm.F32),
            tuple(small[n] for n in SMALL), xs, e_next)
    layer = {n: w for n, w in mats.items() if n not in MATRICES}
    x, aux, pull_layer = streams.layer_vjp(cfg, 1, layer, small, x, pos)
    with jax.named_scope(SCOPE):
        y = streams.collapse(cfg, x)

    def pull(dy):
        with jax.named_scope(SCOPE):
            dx = streams.expand(cfg, dy)
        dx, d_mats, d_small = pull_layer(dx)
        with jax.named_scope(SCOPE):
            d_proj, d_norms, dxs, de = pull_project(dx)
        return (dxs, de, {**d_mats, "proj": d_proj},
                {**d_small, **dict(zip(SMALL, d_norms))})

    return y, aux, pull
