"""Architecture assembly: config -> params / train forward / prefill /
decode, for all 10 assigned families.

Layers are organized as ``n_layers = n_groups * len(layer_pattern)``; the
forward pass scans over groups (keeping HLO size O(pattern), essential for
the 512-device dry-run) and unrolls the pattern within a group.  Pattern
characters:

  G  global attention block        L  sliding-window attention block
  X  attention block + cross-attention (vision memory)
  M  mamba2 block                  H  mamba2 + shared attention (zamba2)
  R  rwkv6 block (time-mix + channel-mix)

Whisper (enc-dec) is assembled from the same blocks but with an explicit
encoder stack and cross-attention decoder.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from . import mamba2 as m2
from . import moe as moe_mod
from . import rwkv6 as r6
from .layers import (AttnSpec, PDTYPE, _dense_init, attn_apply,
                     attn_cache_init, attn_init, mlp_apply, mlp_init,
                     norm_init, rmsnorm)


# ---------------------------------------------------------------------------
# per-position static specs
# ---------------------------------------------------------------------------

def build_specs(cfg: ArchConfig) -> list[AttnSpec]:
    specs = []
    for ch in cfg.layer_pattern:
        if ch == "L":
            specs.append(AttnSpec(window=cfg.sliding_window,
                                  softcap=cfg.attn_logit_softcap,
                                  rope_theta=cfg.rope_theta))
        elif ch in ("G", "X", "H"):
            # gemma3 uses a larger theta for its global layers
            theta = cfg.rope_theta * (50 if cfg.name.startswith("gemma3")
                                      else 1)
            specs.append(AttnSpec(window=None,
                                  softcap=cfg.attn_logit_softcap,
                                  rope_theta=theta))
        else:
            specs.append(AttnSpec())
    return specs


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _block_init(cfg: ArchConfig, kind: str, key):
    ks = jax.random.split(key, 8)
    p = {}
    if kind in ("G", "L", "X", "H"):
        if kind in ("G", "L", "X"):
            p["ln_attn"] = norm_init(cfg.d_model)
            p["attn"] = attn_init(cfg, ks[0])
            p["ln_mlp"] = norm_init(cfg.d_model)
            if cfg.post_norms:
                p["ln_attn_post"] = norm_init(cfg.d_model)
                p["ln_mlp_post"] = norm_init(cfg.d_model)
            if cfg.n_experts:
                p["moe"] = moe_mod.moe_init(cfg, ks[1])
                if cfg.dense_residual:
                    p["mlp"] = mlp_init(cfg, ks[2])
            else:
                p["mlp"] = mlp_init(cfg, ks[2])
        if kind == "X":
            p["ln_xattn"] = norm_init(cfg.d_model)
            p["xattn"] = attn_init(cfg, ks[3])
            p["xattn_gate"] = jnp.zeros((), jnp.float32)
        if kind == "H":
            p["mamba"] = m2.mamba2_init(cfg, ks[4])
            p["ln"] = norm_init(cfg.d_model)
            p["ln_shared_in"] = norm_init(2 * cfg.d_model)
            p["w_shared_in"] = _dense_init(ks[5],
                                           (2 * cfg.d_model, cfg.d_model))
            p["w_shared_out"] = _dense_init(ks[6], (cfg.d_model, cfg.d_model))
    elif kind == "M":
        p["ln"] = norm_init(cfg.d_model)
        p["mamba"] = m2.mamba2_init(cfg, ks[0])
    elif kind == "R":
        p["ln_tm"] = norm_init(cfg.d_model)
        p["ln_cm"] = norm_init(cfg.d_model)
        p["rwkv"] = r6.rwkv6_init(cfg, ks[0])
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return p


def init_params(cfg: ArchConfig, key) -> dict:
    ks = jax.random.split(key, 8)
    n_groups = cfg.n_layers // len(cfg.layer_pattern)
    assert n_groups * len(cfg.layer_pattern) == cfg.n_layers, \
        f"{cfg.name}: n_layers {cfg.n_layers} not divisible by pattern"
    params = {
        "embed": _dense_init(ks[0], (cfg.vocab_padded, cfg.d_model),
                             scale=0.02),
        "ln_f": norm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(ks[1],
                                        (cfg.d_model, cfg.vocab_padded))

    group_keys = jax.random.split(ks[2], n_groups)

    def one_group(k):
        kk = jax.random.split(k, len(cfg.layer_pattern))
        return [_block_init(cfg, ch, kk[i])
                for i, ch in enumerate(cfg.layer_pattern)]

    # vmapped over the groups' keys: the same draws as one call per key,
    # stacked as they are made (a loop would trace n_groups copies)
    params["groups"] = jax.vmap(one_group)(group_keys)

    if "H" in cfg.layer_pattern:
        # zamba2: two shared attention+mlp blocks, alternated
        params["shared"] = [
            {"attn": attn_init(cfg, jax.random.fold_in(ks[3], i)),
             "ln_mlp": norm_init(cfg.d_model),
             "mlp": mlp_init(cfg, jax.random.fold_in(ks[4], i))}
            for i in range(2)]
    if cfg.cross_attn_period or cfg.family in ("vlm", "audio"):
        params["frontend_proj"] = _dense_init(
            ks[5], (cfg.frontend_dim, cfg.d_model))
    if cfg.n_enc_layers:
        enc_keys = jax.random.split(ks[6], cfg.n_enc_layers)
        params["encoder"] = [_block_init(cfg, "G", k) for k in enc_keys]
        params["ln_enc"] = norm_init(cfg.d_model)
    return params


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _ffn(p, cfg: ArchConfig, h):
    """MLP / MoE / arctic parallel dense+MoE.  Returns (y, aux)."""
    if cfg.n_experts:
        y, aux = moe_mod.moe_apply(p["moe"], cfg, h)
        if cfg.dense_residual:
            y = y + mlp_apply(p["mlp"], cfg, h)
        return y, aux
    return mlp_apply(p["mlp"], cfg, h), 0.0


def _block_apply(p, cfg: ArchConfig, kind: str, spec: AttnSpec, x, *,
                 positions, x0=None, memory=None, cache=None, shared=None,
                 shared_idx=0, kernel=False):
    """One layer.  Returns (x, aux, new_cache).  ``kernel``: run the Pallas
    kernels (serving on a TPU only — they have no VJP)."""
    aux = 0.0
    if kind in ("G", "L", "X"):
        h = rmsnorm(x, p["ln_attn"])
        a, cache = attn_apply(p["attn"], cfg, spec, h, positions=positions,
                              cache=cache, kernel=kernel)
        if cfg.post_norms:
            a = rmsnorm(a, p["ln_attn_post"])
        x = x + a
        if kind == "X" and memory is not None:
            h = rmsnorm(x, p["ln_xattn"])
            xa, _ = attn_apply(p["xattn"], cfg, spec, h, positions=positions,
                               kv_from=memory, kernel=kernel)
            x = x + jnp.tanh(p["xattn_gate"]).astype(x.dtype) * xa
        h = rmsnorm(x, p["ln_mlp"])
        f, aux = _ffn(p, cfg, h)
        if cfg.post_norms:
            f = rmsnorm(f, p["ln_mlp_post"])
        x = x + f
    elif kind == "M":
        h = rmsnorm(x, p["ln"])
        y, cache = m2.mamba2_apply(p["mamba"], cfg, h, cache, kernel=kernel)
        x = x + y
    elif kind == "H":
        h = rmsnorm(x, p["ln"])
        mcache = cache["mamba"] if cache is not None else None
        y, mcache = m2.mamba2_apply(p["mamba"], cfg, h, mcache,
                                    kernel=kernel)
        x = x + y
        # shared attention block over concat(hidden, initial embeddings) —
        # the zamba2 skip stream (a reconvergent path in the task graph)
        sb = shared[shared_idx]
        acache = cache["attn"] if cache is not None else None
        hin = jnp.concatenate([x, x0], axis=-1)
        hin = rmsnorm(hin, p["ln_shared_in"]) @ p["w_shared_in"]
        a, acache = attn_apply(sb["attn"], cfg, spec, hin,
                               positions=positions, cache=acache,
                               kernel=kernel)
        a = a + mlp_apply(sb["mlp"], cfg, rmsnorm(a, sb["ln_mlp"]))
        x = x + a @ p["w_shared_out"]
        if cache is not None:
            cache = {"mamba": mcache, "attn": acache}
    elif kind == "R":
        tm_shift = cache["tm_shift"] if cache is not None else \
            jnp.zeros_like(x[:, :1])
        cm_shift = cache["cm_shift"] if cache is not None else \
            jnp.zeros_like(x[:, :1])
        wkv = cache["wkv"] if cache is not None else None
        h = rmsnorm(x, p["ln_tm"])
        y, new_tm, wkv = r6.time_mix_apply(p["rwkv"]["time_mix"], cfg, h,
                                           tm_shift, wkv, kernel=kernel)
        x = x + y
        h = rmsnorm(x, p["ln_cm"])
        y, new_cm = r6.chan_mix_apply(p["rwkv"]["chan_mix"], cfg, h, cm_shift)
        x = x + y
        if cache is not None:
            cache = {"tm_shift": new_tm, "cm_shift": new_cm, "wkv": wkv,
                     "pos": cache["pos"] + x.shape[1]}
    return x, aux, cache


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def _embed(params, cfg: ArchConfig, tokens, *, kernel=False):
    B, S = tokens.shape
    x = ops.burst_gather(params["embed"], tokens.reshape(-1), kernel=kernel)
    x = x.reshape(B, S, cfg.d_model)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _encode(params, cfg: ArchConfig, frames):
    """Whisper encoder over (stub) frame embeddings."""
    x = (frames @ params["frontend_proj"]).astype(PDTYPE)
    spec = AttnSpec(causal=False, rope_theta=cfg.rope_theta)
    positions = jnp.arange(x.shape[1])
    for p in params["encoder"]:
        x, _, _ = _block_apply(p, cfg, "G", spec, x, positions=positions)
    return rmsnorm(x, params["ln_enc"])


def _memory(params, cfg: ArchConfig, extra):
    if cfg.n_enc_layers and extra is not None and "frames" in extra:
        return _encode(params, cfg, extra["frames"])
    if extra is not None and "vision" in extra:
        return (extra["vision"] @ params["frontend_proj"]).astype(PDTYPE)
    return None


def apply_group(gp, cfg: ArchConfig, specs, x, *, positions, x0=None,
                memory=None, shared=None):
    """Apply one layer-group (len(cfg.layer_pattern) blocks, unrolled),
    without caches.  Returns (x, aux, None)."""
    aux = jnp.zeros((), jnp.float32)
    h_idx = 0
    for i, ch in enumerate(cfg.layer_pattern):
        x, a, _ = _block_apply(gp[i], cfg, ch, specs[i], x,
                               positions=positions, x0=x0, memory=memory,
                               shared=shared, shared_idx=h_idx % 2)
        if ch == "H":
            h_idx += 1
        aux = aux + a
    return x, aux, None


@jax.named_scope("head")
def lm_head(params, cfg: ArchConfig, x):
    """Final norm + (tied) LM head + optional softcap.  Returns logits over
    the PADDED vocab with pad rows masked to -inf (shard-friendly)."""
    x = rmsnorm(x, params["ln_f"])
    logits = x @ (params["embed"].T.astype(x.dtype)
                  if cfg.tie_embeddings else params["lm_head"])
    if cfg.final_logit_softcap:
        c = cfg.final_logit_softcap
        logits = jnp.tanh(logits / c) * c
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
        logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype),
                           logits)
    return logits


def chunked_ce(params, cfg: ArchConfig, x, targets, mask=None, *,
               n_chunks: int = 8):
    """Memory-bounded cross entropy: the (tokens, vocab) logits tensor is
    materialized one chunk at a time (vital for 256k vocabularies).

    The chunk loop is unrolled (fixed ``n_chunks``) rather than scanned:
    fp32 logits + an unrolled loop keep the TP all-reduces out of while
    bodies, dodging an XLA:CPU AllReducePromotion crash, and give XLA more
    freedom to overlap the head matmuls."""
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    tf = targets.reshape(T)
    mf = (mask.reshape(T).astype(jnp.float32) if mask is not None
          else jnp.ones((T,), jnp.float32))
    chunk = max(-(-T // n_chunks), 1)
    Tp = chunk * n_chunks
    xf = jnp.pad(xf, ((0, Tp - T), (0, 0)))
    tf = jnp.pad(tf, (0, Tp - T))
    mf = jnp.pad(mf, (0, Tp - T))

    total = jnp.zeros((), jnp.float32)
    for c in range(n_chunks):
        xc = xf[c * chunk:(c + 1) * chunk]
        tc = tf[c * chunk:(c + 1) * chunk]
        mc = mf[c * chunk:(c + 1) * chunk]
        # fp32 logits: better CE numerics, f32 TP all-reduces
        lg = lm_head(params, cfg, xc[None].astype(jnp.float32))[0]
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
        total = total + ((logz - ll) * mc).sum()
    return total / jnp.maximum(mf.sum(), 1.0)


def forward(params, cfg: ArchConfig, tokens, *, extra=None,
            remat: bool = False):
    """Training/prefill-style full-sequence forward -> logits (B, S, V)."""
    specs = build_specs(cfg)
    x = _embed(params, cfg, tokens)
    positions = jnp.arange(tokens.shape[1])
    memory = _memory(params, cfg, extra)
    shared = params.get("shared")
    x0 = x

    def group_fn(carry, gp):
        x, aux = carry
        h_idx = 0
        for i, ch in enumerate(cfg.layer_pattern):
            x, a, _ = _block_apply(
                gp[i], cfg, ch, specs[i], x,
                positions=positions, x0=x0, memory=memory, shared=shared,
                shared_idx=h_idx % 2)
            if ch == "H":
                h_idx += 1
            aux = aux + a
        return (x, aux), None

    if remat:
        group_fn = jax.checkpoint(group_fn)
    (x, aux), _ = jax.lax.scan(group_fn, (x, jnp.zeros((), jnp.float32)),
                               params["groups"])
    logits = lm_head(params, cfg, x)[..., :cfg.vocab]
    return logits, aux


def loss_fn(params, cfg: ArchConfig, batch, *, remat: bool = False):
    """Next-token CE + MoE aux loss.  batch: {tokens, (extra)}."""
    tokens = batch["tokens"]
    logits, aux = forward(params, cfg, tokens, extra=batch.get("extra"),
                          remat=remat)
    tgt = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    ce = (logz - ll).mean()
    return ce + 0.01 * aux


# ---------------------------------------------------------------------------
# KV-cache serving
# ---------------------------------------------------------------------------

def init_cache(params, cfg: ArchConfig, batch, max_seq, extra=None):
    """Empty caches, holding activations in the parameters' dtype (the
    embedding's), so a float32 model keeps float32 keys and values."""
    specs = build_specs(cfg)
    n_groups = cfg.n_layers // len(cfg.layer_pattern)
    dtype = params["embed"].dtype

    def one(spec, ch):
        if ch in ("G", "L", "X"):
            return attn_cache_init(cfg, spec, batch, max_seq, dtype)
        if ch == "M":
            return m2.mamba2_cache_init(cfg, batch, dtype)
        if ch == "H":
            return {"mamba": m2.mamba2_cache_init(cfg, batch, dtype),
                    "attn": attn_cache_init(cfg, specs[0], batch, max_seq,
                                            dtype)}
        if ch == "R":
            return r6.rwkv6_cache_init(cfg, batch, dtype)
        raise ValueError(ch)

    group_cache = [one(specs[i], ch)
                   for i, ch in enumerate(cfg.layer_pattern)]
    # lift python-int "pos" fields into arrays, then stack across groups
    group_cache = jax.tree.map(jnp.asarray, group_cache)
    stacked = jax.tree.map(
        lambda t: jnp.broadcast_to(t[None], (n_groups,) + t.shape),
        group_cache)
    mem = {"memory": _memory(params, cfg, extra)} if extra else {}
    return {"groups": stacked, "pos": jnp.zeros((), jnp.int32), **mem}


def kernels_here() -> bool:
    """Whether ``step`` runs the Pallas kernels: on a TPU, yes.  Elsewhere,
    and on every differentiated path, the model runs the XLA references
    (``repro.kernels.ops``)."""
    return jax.default_backend() == "tpu"


def step(params, cfg: ArchConfig, cache, tokens, *, unroll: bool = False):
    """Prefill (S>=1) or decode (S=1) step -> (logits_last, new_cache).
    Runs the Pallas kernels where ``kernels_here()``."""
    kernel = kernels_here()
    specs = build_specs(cfg)
    x = _embed(params, cfg, tokens, kernel=kernel)
    S = tokens.shape[1]
    pos0 = cache["pos"]
    positions = pos0 + jnp.arange(S)
    memory = cache.get("memory")
    shared = params.get("shared")
    x0 = x

    # the stacked caches ride in the loop's carry, so each layer reads and
    # writes its own in place: attention K/V stay whole (``attn_apply``
    # and the decode kernel index layer g), other states are sliced out
    # and written back
    def group_fn(carry, scanned):
        x, aux, groups = carry
        gp, g = scanned
        groups = list(groups)
        h_idx = 0
        for i, ch in enumerate(cfg.layer_pattern):
            ci = _with_pos(_layer_cache(groups[i], g), pos0)
            x, a, ci = _block_apply(gp[i], cfg, ch, specs[i], x,
                                    positions=positions, x0=x0,
                                    memory=memory, cache=ci, shared=shared,
                                    shared_idx=h_idx % 2, kernel=kernel)
            if ch == "H":
                h_idx += 1
            groups[i] = _put_layer_cache(groups[i], ci, g)
            aux = aux + a
        return (x, aux, groups), None

    n_groups = cfg.n_layers // len(cfg.layer_pattern)
    (x, _, new_groups), _ = jax.lax.scan(
        group_fn, (x, jnp.zeros((), jnp.float32), cache["groups"]),
        (params["groups"], jnp.arange(n_groups)),
        unroll=n_groups if unroll else 1)
    logits = lm_head(params, cfg, x[:, -1:])[:, 0]
    new_cache = dict(cache)
    new_cache["groups"] = new_groups
    new_cache["pos"] = pos0 + S
    return logits, new_cache


def _is_kv(d):
    return isinstance(d, dict) and "k" in d and "v" in d


def _layer_cache(stacked, g):
    """Layer g's view of a stacked cache: an attention cache keeps its
    whole K/V stacks and gains the layer index, every other leaf is
    sliced at g."""
    if _is_kv(stacked):
        return {**stacked, "pos": stacked["pos"][g], "layer": g}
    if isinstance(stacked, dict):
        return {k: _layer_cache(v, g) for k, v in stacked.items()}
    return stacked[g]


def _put_layer_cache(stacked, new, g):
    """Write layer g's updated cache back into the stack: attention K/V
    were written in place and are taken whole, every other leaf is
    written at g."""
    if _is_kv(stacked):
        return {"k": new["k"], "v": new["v"],
                "pos": stacked["pos"].at[g].set(new["pos"])}
    if isinstance(stacked, dict):
        return {k: _put_layer_cache(v, new[k], g) for k, v in stacked.items()}
    return jax.lax.dynamic_update_index_in_dim(
        stacked, new.astype(stacked.dtype), g, 0)


def _with_pos(cache_leaf, pos):
    """Replace per-layer 'pos' scalars with the global position counter
    (kept once at top level to avoid per-layer bookkeeping)."""
    def fix(d):
        if isinstance(d, dict):
            out = {k: fix(v) for k, v in d.items()}
            if "pos" in out:
                out["pos"] = pos
            return out
        return d
    return fix(cache_leaf)
