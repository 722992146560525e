"""Mixture-of-Experts FFN: top-k router + expert MLPs (+ arctic's dense
residual branch), with expert-parallel sharding in mind.

Dense-compute formulation: every token computes only its top-k experts via
a dispatch/combine einsum (reference) or the grouped-matmul Pallas kernel.
The dispatch tensors are laid out so GSPMD turns them into all-to-alls on
the expert axis when experts are sharded (EP = the paper's HBM channel
binding analogue: experts are bound to mesh slots by the floorplanner).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from .layers import _dense_init


def moe_init(cfg: ArchConfig, key):
    ks = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    p = {
        "router": _dense_init(ks[0], (d, e), scale=0.02).astype(jnp.float32),
        "w_up": _dense_init(ks[1], (e, d, f)),
        "w_down": _dense_init(ks[2], (e, f, d)),
    }
    if cfg.gated_mlp:
        p["w_gate"] = _dense_init(ks[3], (e, d, f))
    return p


@jax.named_scope("experts")
def moe_apply(p, cfg: ArchConfig, x):
    """x: (B, S, d) -> (y, aux_loss).

    Dropless top-k routing: probabilities renormalized over the selected
    experts; auxiliary load-balancing loss (Switch-style).
    """
    B, S, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(B * S, d)

    with jax.named_scope("router"):
        logits = (xf.astype(jnp.float32) @ p["router"])       # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)                 # (T, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        # dispatch one-hot: (T, k, E) -> combine weights (T, E)
        onehot = jax.nn.one_hot(top_i, e, dtype=xf.dtype)      # (T, k, E)
        combine = (onehot * top_p[..., None].astype(xf.dtype)).sum(1)

    # expert compute (dense dispatch einsum — GSPMD shards over E)
    xe = jnp.einsum("te,td->etd", (combine > 0).astype(xf.dtype), xf)
    up = jnp.einsum("etd,edf->etf", xe, p["w_up"])
    if cfg.gated_mlp:
        gate = jnp.einsum("etd,edf->etf", xe, p["w_gate"])
        up = jax.nn.silu(gate) * up
    else:
        up = jax.nn.silu(up)
    ye = jnp.einsum("etf,efd->etd", up, p["w_down"])           # (E, T, d)
    y = jnp.einsum("etd,te->td", ye, combine)

    # load-balance aux loss: E * sum_e (fraction routed * mean prob)
    frac = (onehot.sum(1)).mean(0)                             # (E,)
    mean_p = probs.mean(0)
    aux = e * jnp.sum(frac.astype(jnp.float32) * mean_p)
    return y.reshape(B, S, d), aux
