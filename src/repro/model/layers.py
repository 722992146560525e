"""Core layers (pure functional JAX; params are plain pytrees).

Everything is bf16 by default with fp32 norms/softmax internals.  The
attention / SSM hot spots route through ``repro.kernels.ops``; ``kernel``
picks the Pallas TPU kernel over the XLA reference (see ``lm.step``).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops

PDTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dense_init(key, shape, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(PDTYPE)


def norm_init(d):
    return {"w": jnp.ones((d,), jnp.float32)}


def rmsnorm(x, p, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * p["w"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_tables(positions, dim, theta):
    """cos/sin tables: positions (...,) -> (..., dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, style="neox"):
    """x: (B, S, H, D); cos/sin: (S, rot_dim//2) or (B, S, rot//2).

    "neox": rotate over the full head dim (half-split layout).
    "partial": chatglm-style 2d RoPE — rotary on the first half of the head
    dim only (interleaved pairs), rest passes through.
    """
    if style == "none" or style == "learned":
        return x
    D = x.shape[-1]
    rot = D if style == "neox" else D // 2
    xr, xp = x[..., :rot], x[..., rot:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]      # (1, S, 1, rot//2)
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    if style == "partial":
        # interleaved pairs (x0,x1), (x2,x3), ...
        x1 = xr[..., 0::2]
        x2 = xr[..., 1::2]
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        rotated = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    else:
        half = rot // 2
        x1, x2 = xr[..., :half], xr[..., half:]
        rotated = jnp.concatenate([x1 * cos - x2 * sin,
                                   x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), xp], axis=-1) \
        if rot < D else rotated.astype(x.dtype)


def rope_halfdim(cfg: ArchConfig) -> int:
    rot = cfg.head_dim if cfg.rope_style == "neox" else cfg.head_dim // 2
    return rot // 2


# ---------------------------------------------------------------------------
# attention layer (GQA; optional sliding window / softcap / qk-norm)
# ---------------------------------------------------------------------------

def attn_init(cfg: ArchConfig, key, cross=False):
    ks = jax.random.split(key, 6)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": _dense_init(ks[0], (d, qd)),
        "wk": _dense_init(ks[1], (d, kvd)),
        "wv": _dense_init(ks[2], (d, kvd)),
        "wo": _dense_init(ks[3], (qd, d)),
    }
    return p


@dataclasses.dataclass
class AttnSpec:
    """Static per-layer attention behaviour."""
    window: int | None = None
    softcap: float | None = None
    rope_theta: float = 10_000.0
    causal: bool = True


def attn_apply(p, cfg: ArchConfig, spec: AttnSpec, x, *, positions,
               cache=None, kv_from=None, kv_len=None, kernel=False):
    """x: (B, S, d).  cache: optional dict(k, v, pos) for decode.
    kv_from: cross-attention memory (B, Sm, d) — overrides self-KV."""
    B, S, _ = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, D)
    src = x if kv_from is None else kv_from
    Skv = src.shape[1]
    k = (src @ p["wk"]).reshape(B, Skv, Hkv, D)
    v = (src @ p["wv"]).reshape(B, Skv, Hkv, D)

    scale = cfg.query_scale
    if kv_from is None:
        cos, sin = rope_tables(positions, cfg.head_dim if cfg.rope_style ==
                               "neox" else cfg.head_dim // 2, spec.rope_theta)
        q = apply_rope(q, cos, sin, cfg.rope_style)
        k = apply_rope(k, cos, sin, cfg.rope_style)

    if cache is not None and S > 1:
        # prefill from scratch (pos assumed 0): full attention, then store
        # the last W tokens ring-aligned (token t lives at slot t % W)
        out = ops.attention(q, k, v, causal=spec.causal, window=spec.window,
                            softcap=spec.softcap, scale=scale, kernel=kernel)
        ck, cv = cache["k"], cache["v"]
        W = ck.shape[1]
        if S >= W:
            slots = (jnp.arange(W) + (S - W)) % W
            ck = jnp.zeros_like(ck).at[:, slots].set(
                k[:, S - W:].astype(ck.dtype))
            cv = jnp.zeros_like(cv).at[:, slots].set(
                v[:, S - W:].astype(cv.dtype))
        else:
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, 0, 0, 0))
        cache = {"k": ck, "v": cv, "pos": cache["pos"] + S}
    elif cache is not None:
        # decode: append k/v at cache["pos"] (ring-buffered for local layers)
        ck, cv, pos = cache["k"], cache["v"], cache["pos"]
        W = ck.shape[1]
        slot = pos if spec.window is None else pos % W
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, slot, 0, 0))
        cache = {"k": ck, "v": cv, "pos": pos + S}
        k, v = ck, cv
        if spec.window is None:
            kv_len = jnp.full((B,), pos + S) if kv_len is None else kv_len
            out = ops.attention(q, k, v, causal=False, softcap=spec.softcap,
                                scale=scale, q_offset=pos, kv_len=kv_len,
                                kernel=kernel)
        else:
            # ring buffer: valid entries = min(pos + S, W); no causal mask
            # needed (all cached tokens precede the query)
            valid = jnp.minimum(pos + S, W)
            out = ops.attention(q, k, v, causal=False, softcap=spec.softcap,
                                scale=scale, kv_len=jnp.full((B,), valid),
                                kernel=kernel)
    else:
        out = ops.attention(q, k, v, causal=spec.causal and kv_from is None,
                            window=spec.window, softcap=spec.softcap,
                            scale=scale, kv_len=kv_len, kernel=kernel)
    y = out.reshape(B, S, H * D) @ p["wo"]
    return y, cache


def attn_cache_init(cfg: ArchConfig, spec: AttnSpec, batch, max_seq,
                    dtype=PDTYPE):
    W = max_seq if spec.window is None else min(spec.window, max_seq)
    shape = (batch, W, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": 0}


# ---------------------------------------------------------------------------
# MLP (gated SiLU/GELU)
# ---------------------------------------------------------------------------

def mlp_init(cfg: ArchConfig, key, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_up": _dense_init(ks[0], (cfg.d_model, d_ff)),
         "w_down": _dense_init(ks[1], (d_ff, cfg.d_model))}
    if cfg.gated_mlp:
        p["w_gate"] = _dense_init(ks[2], (cfg.d_model, d_ff))
    return p


def mlp_apply(p, cfg: ArchConfig, x):
    act = jax.nn.silu if cfg.mlp_act == "silu" else \
        (lambda a: jax.nn.gelu(a, approximate=True))
    up = x @ p["w_up"]
    if cfg.gated_mlp:
        up = act(x @ p["w_gate"]) * up
    else:
        up = act(up)
    return up @ p["w_down"]
