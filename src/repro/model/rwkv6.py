"""RWKV-6 (Finch) block: time-mix (WKV recurrence with data-dependent
decay) + channel-mix, attention-free."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from .layers import PDTYPE, _dense_init, norm_init, rmsnorm


def rwkv6_init(cfg: ArchConfig, key):
    d = cfg.d_model
    H = d // cfg.ssm_head_dim
    ks = jax.random.split(key, 12)
    lora = 64
    return {
        "time_mix": {
            # token-shift interpolation weights for r,k,v,w,g
            "mu": (jax.random.uniform(ks[0], (5, d), jnp.float32)
                   ).astype(PDTYPE),
            "wr": _dense_init(ks[1], (d, d)),
            "wk": _dense_init(ks[2], (d, d)),
            "wv": _dense_init(ks[3], (d, d)),
            "wg": _dense_init(ks[4], (d, d)),
            # data-dependent decay LoRA: w = base + (tanh(x A) B)
            "w_base": jnp.full((d,), -6.0, jnp.float32),
            "w_A": _dense_init(ks[5], (d, lora)),
            "w_B": _dense_init(ks[6], (lora, d), scale=0.01),
            "u": (jax.random.normal(ks[7], (H, cfg.ssm_head_dim), jnp.float32)
                  * 0.3).astype(jnp.float32),
            "wo": _dense_init(ks[8], (d, d)),
            "ln_x": norm_init(d),
        },
        "chan_mix": {
            "mu": (jax.random.uniform(ks[9], (2, d), jnp.float32)
                   ).astype(PDTYPE),
            "wk": _dense_init(ks[10], (d, cfg.d_ff)),
            "wv": _dense_init(ks[11], (cfg.d_ff, d)),
            "wr": _dense_init(ks[0], (d, d)),
        },
    }


def _token_shift(x, last):
    """shifted = concat(last, x[:-1]); last: (B, 1, d) previous token."""
    return jnp.concatenate([last, x[:, :-1]], axis=1)


def time_mix_apply(p, cfg: ArchConfig, x, shift, state, *, kernel=False):
    """x: (B,S,d); shift: (B,1,d) last token of previous chunk;
    state: (B,H,D,D) WKV state.  Returns y, new_shift, new_state."""
    B, S, d = x.shape
    D = cfg.ssm_head_dim
    H = d // D
    xs = _token_shift(x, shift)
    def mix(i):
        return x + (xs - x) * p["mu"][i][None, None]
    r = (mix(0) @ p["wr"]).reshape(B, S, H, D)
    k = (mix(1) @ p["wk"]).reshape(B, S, H, D)
    v = (mix(2) @ p["wv"]).reshape(B, S, H, D)
    g = jax.nn.silu(mix(3) @ p["wg"])
    w_raw = p["w_base"][None, None] + \
        jnp.tanh(mix(4).astype(jnp.float32) @ p["w_A"].astype(jnp.float32)) \
        @ p["w_B"].astype(jnp.float32)
    w = jnp.exp(-jnp.exp(w_raw)).reshape(B, S, H, D)         # decay in (0,1)
    y, new_state = ops.rwkv6_scan(r, k, v, w.astype(r.dtype), p["u"], state,
                                  kernel=kernel)
    y = y.reshape(B, S, d)
    y = rmsnorm(y, p["ln_x"]) * g
    return y @ p["wo"], x[:, -1:], new_state


def chan_mix_apply(p, cfg: ArchConfig, x, shift):
    xs = _token_shift(x, shift)
    def mix(i):
        return x + (xs - x) * p["mu"][i][None, None]
    k = jnp.square(jax.nn.relu(mix(0) @ p["wk"]))
    r = jax.nn.sigmoid(mix(1) @ p["wr"])
    return r * (k @ p["wv"]), x[:, -1:]


def rwkv6_cache_init(cfg: ArchConfig, batch, dtype=PDTYPE):
    d = cfg.d_model
    H = d // cfg.ssm_head_dim
    return {
        "tm_shift": jnp.zeros((batch, 1, d), dtype),
        "cm_shift": jnp.zeros((batch, 1, d), dtype),
        "wkv": jnp.zeros((batch, H, cfg.ssm_head_dim, cfg.ssm_head_dim),
                         jnp.float32),
        "pos": 0,
    }
