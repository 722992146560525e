"""Plain float32 reference of a decoder of attention and routed-expert
layers (the granite-moe family), and the benchmark's own weights for it.

Imports nothing of the program.  The parameter tree it makes has the
program's layout (``groups[0]`` holds every layer stacked on axis 0), so
the harness hands the same tree to the program and to this reference.

Layer equations, per position, with every published multiplier:

    x0 = embedding_multiplier * E[t]
    h  = rmsnorm(x);  q, k, v = h Wq, h Wk, h Wv;  rotary (half-split) on q, k
    a  = softmax(attention_multiplier * q k^T, causal) v Wo
    x  = x + residual_multiplier * a
    h  = rmsnorm(x);  p = softmax(h R);  top-k of p, renormalised
    x  = x + residual_multiplier * sum_top-k p_e (silu(h G_e) * (h U_e)) D_e
    logits = rmsnorm(x) E^T / logits_scaling

The program has no multipliers; the weights fold them in exactly:
``embed`` holds ``embedding_multiplier * E``, the final norm's weight is
``1 / (embedding_multiplier * logits_scaling)``, ``Wq`` carries
``attention_multiplier * sqrt(head_dim)`` and ``Wo``, ``D_e`` carry
``residual_multiplier``.

Routing codes.  A random router over random activations puts the 8th and
9th experts of 40 within a bf16 rounding of each other for a few tokens in
every layer, and each flip changes that token's state by about a tenth,
which flips more in the next layers: at 32 layers any two correct
implementations then disagree at O(1), and no comparison can tell bf16 from
fp8.  So the first ``n_experts`` channels of the embedding hold a routing
code (8 positive entries per token, the rest zero), no layer writes those
channels (their columns of ``Wo`` and ``D_e`` are zero), and each layer's
router reads them through its own permutation.  Every token then routes to
the same 8 experts in every implementation, with the margin of the code,
while the router, the top-k and all 40 experts still run at full width.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BF16, F32 = jnp.bfloat16, jnp.float32


def dims(spec: dict) -> dict:
    d = spec["hidden_size"]
    h = spec["num_attention_heads"]
    v = spec["vocab_size"]
    return {"L": spec["num_hidden_layers"], "d": d, "H": h,
            "Hkv": spec["num_key_value_heads"], "D": d // h,
            "E": spec["num_local_experts"], "k": spec["num_experts_per_tok"],
            "f": spec["intermediate_size"], "V": v,
            # the program's table: rows padded to a multiple of 256
            "Vp": -(-v // 256) * 256}


def make_params(spec: dict, key) -> dict:
    """Seeded weights in the program's layout and served dtypes (bf16
    matrices, float32 norms and router).  Jit it with ``spec`` static."""
    n = dims(spec)
    L, d, H, Hkv, D, E, k, f = (n[x] for x in "L d H Hkv D E k f".split())
    std = spec["initializer_range"]
    emb_mult = spec["embedding_multiplier"]
    resid = spec["residual_multiplier"]
    q_fold = spec["attention_multiplier"] * math.sqrt(D)
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, F32) * scale

    # routing code: k distinct experts per token, positive weights
    rows = n["Vp"]
    pick = jnp.argsort(jax.random.uniform(next(ks), (rows, E)), axis=1)[:, :k]
    level = 1.0 + jax.random.uniform(next(ks), (rows, k))
    code = jnp.zeros((rows, E), F32).at[
        jnp.arange(rows)[:, None], pick].set(level)
    embed = normal((rows, d), emb_mult * std)
    embed = embed.at[:, :E].set(code * emb_mult * std)

    # router of layer l: expert e reads code channel perm_l(e)
    perms = jax.vmap(lambda kk: jax.random.permutation(kk, E))(
        jax.random.split(next(ks), L))
    router = jnp.zeros((L, d, E), F32).at[
        jnp.arange(L)[:, None], perms, jnp.arange(E)[None, :]].set(
        spec["weights"]["router_scale"])

    def keep_code(w):          # no layer writes the code channels
        return w.at[..., :E].set(0.0)

    block = {
        "ln_attn": {"w": jnp.ones((L, d), F32)},
        "attn": {
            "wq": (normal((L, d, H * D), d ** -0.5) * q_fold).astype(BF16),
            "wk": normal((L, d, Hkv * D), d ** -0.5).astype(BF16),
            "wv": normal((L, d, Hkv * D), d ** -0.5).astype(BF16),
            "wo": keep_code(normal((L, H * D, d), (H * D) ** -0.5)
                            * resid).astype(BF16),
        },
        "ln_mlp": {"w": jnp.ones((L, d), F32)},
        "moe": {
            "router": router,
            "w_up": normal((L, E, d, f), d ** -0.5).astype(BF16),
            "w_down": keep_code(normal((L, E, f, d), f ** -0.5)
                                * resid).astype(BF16),
            "w_gate": normal((L, E, d, f), d ** -0.5).astype(BF16),
        },
    }
    return {"embed": embed.astype(BF16),
            "ln_f": {"w": jnp.full((d,), 1.0 / (emb_mult
                                                * spec["logits_scaling"]),
                                   F32)},
            "groups": [block]}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def fp8(w, axis):
    """``w`` through float8_e4m3fn with one scale per slice reduced over
    ``axis``, back in float32: the control's rounding (weights per output
    channel, activations per row)."""
    w = w.astype(F32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = pos[:, None].astype(F32) * inv                     # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(spec, n, x, lp, pos, act):
    """One layer; ``act`` rounds each matmul's activation input (the
    identity in the reference)."""
    eps = spec["rms_norm_eps"]
    B, S, d = x.shape
    H, Hkv, D, k = n["H"], n["Hkv"], n["D"], n["k"]
    h = act(_rmsnorm(x, lp["ln_attn"]["w"], eps))
    a = lp["attn"]
    q = _rope((h @ a["wq"]).reshape(B, S, H, D), pos, spec["rope_theta"])
    kk = _rope((h @ a["wk"]).reshape(B, S, Hkv, D), pos, spec["rope_theta"])
    v = (h @ a["wv"]).reshape(B, S, Hkv, D)
    g = H // Hkv
    kk, v = jnp.repeat(kk, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + act(o.reshape(B, S, H * D)) @ a["wo"]

    h = _rmsnorm(x, lp["ln_mlp"]["w"], eps).reshape(B * S, d)
    m = lp["moe"]
    p = jax.nn.softmax(h @ m["router"], -1)
    top_p, top_i = jax.lax.top_k(p, k)
    gate = jnp.zeros_like(p).at[jnp.arange(B * S)[:, None], top_i].set(
        top_p / top_p.sum(-1, keepdims=True))                # (T, E)
    h = act(h)
    up = jnp.einsum("td,edf->etf", h, m["w_up"])
    hidden = jax.nn.silu(jnp.einsum("td,edf->etf", h, m["w_gate"])) * up
    y = jnp.einsum("etf,efd,te->td", act(hidden), m["w_down"], gate)
    return x + y.reshape(B, S, d)


_QUANT_AXES = {"wq": -2, "wk": -2, "wv": -2, "wo": -2, "w_up": -2,
               "w_gate": -2, "w_down": -2}


def _layer_params(lp, quant):
    def leaf(path, w):
        name = path[-1].key
        if quant and name in _QUANT_AXES:
            return fp8(w, _QUANT_AXES[name])
        return w.astype(F32)
    return jax.tree_util.tree_map_with_path(leaf, lp)


def logits(params, spec: dict, tokens, n_out: int, quant: bool = False):
    """float32 logits (B, n_out, vocab_size) at the last ``n_out``
    positions of ``tokens`` (B, S).  ``quant``: the control, computed in
    fp8 (``fp8``): every matrix and every matmul's activation input (the
    router and the attention scores stay in float32)."""
    n = dims(spec)
    act = (lambda a: fp8(a, -1)) if quant else (lambda a: a)
    with jax.default_matmul_precision("highest"):
        table = params["embed"]
        table = fp8(table, -1) if quant else table.astype(F32)
        x = table[tokens]
        pos = jnp.arange(tokens.shape[1])

        def body(x, lp):
            return _layer(spec, n, x, _layer_params(lp, quant), pos,
                          act), None

        x, _ = jax.lax.scan(body, x, params["groups"][0])
        h = _rmsnorm(x[:, -n_out:], params["ln_f"]["w"],
                     spec["rms_norm_eps"])
        return act(h) @ table[:n["V"]].T
