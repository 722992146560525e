"""Mamba-2 (SSD) block for the zamba2 hybrid architecture."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from .layers import PDTYPE, _dense_init, norm_init, rmsnorm


def mamba2_init(cfg: ArchConfig, key):
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    H = d_in // cfg.ssm_head_dim
    N = cfg.ssm_state
    ks = jax.random.split(key, 6)
    return {
        # fused input projection: [x, z, B, C, dt]
        "w_in": _dense_init(ks[0], (d, 2 * d_in + 2 * N + H)),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, d_in + 2 * N),
                                     jnp.float32) * 0.2).astype(PDTYPE),
        "A_log": jnp.zeros((H,), jnp.float32) + jnp.log(
            jnp.linspace(1.0, 16.0, H)),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "norm": norm_init(d_in),
        "w_out": _dense_init(ks[2], (d_in, d)),
    }


def _causal_conv(x, w, state=None):
    """Depthwise causal conv1d.  x: (B, S, C); w: (K, C);
    state: (B, K-1, C) trailing context or None."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                  # (B, S+K-1, C)
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None, :]
              for i in range(K))
    new_state = xp[:, -(K - 1):] if K > 1 else None
    return out, new_state


def mamba2_apply(p, cfg: ArchConfig, x, cache=None, *, kernel=False):
    """x: (B, S, d).  cache: {"conv": (B,K-1,C), "ssd": (B,H,P,N), "pos"}."""
    B, S, d = x.shape
    d_in = cfg.ssm_expand * d
    P = cfg.ssm_head_dim
    H = d_in // P
    N = cfg.ssm_state

    zxbcdt = x @ p["w_in"]
    z, xin, Bc, Cc, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + N, 2 * d_in + 2 * N], axis=-1)
    conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)       # (B,S,d_in+2N)
    conv_state = cache["conv"] if cache is not None else None
    conv_out, new_conv = _causal_conv(conv_in, p["conv_w"], conv_state)
    conv_out = jax.nn.silu(conv_out)
    xin, Bc, Cc = jnp.split(conv_out, [d_in, d_in + N], axis=-1)

    dtp = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xin.reshape(B, S, H, P)
    ssd_state = cache["ssd"] if cache is not None else None
    y, new_ssd = ops.mamba2_scan(xh, dtp, A, Bc, Cc, ssd_state,
                                 kernel=kernel)
    y = y + xh * p["D"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B, S, d_in)
    y = rmsnorm(y, p["norm"]) * jax.nn.silu(z)
    out = y @ p["w_out"]
    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv, "ssd": new_ssd,
                     "pos": cache["pos"] + S}
    return out, new_cache


def mamba2_cache_init(cfg: ArchConfig, batch, dtype=PDTYPE):
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, d_in + 2 * cfg.ssm_state),
                          dtype),
        "ssd": jnp.zeros((batch, H, cfg.ssm_head_dim, cfg.ssm_state),
                         jnp.float32),
        "pos": 0,
    }
