"""Chunked RWKV-6 (Finch) WKV recurrence for TPU.

Recurrence (per head, state S in R^{DxD}):
    y_t = r_t . (S_{t-1} + diag(u) k_t^T v_t)
    S_t = diag(w_t) S_{t-1} + k_t^T v_t        (w_t in (0,1), per-channel)

Chunked dual form over a chunk of length T with per-channel log-decay
cumsum  c_t = sum_{j<=t} log w_j  (c in R^{T x D}):

    intra:  y_t = sum_{tau<t} (r_t * exp(c_{t-1} - c_tau)) . k_tau v_tau
                  + (r_t * u) . k_t v_t
            => masked (T x T) matmul with rescaled r~ = r * exp(c_prev),
               k~ = k * exp(-c)
    inter:  y_t += (r_t * exp(c_{t-1})) . S_in
    state:  S_out = diag(exp(c_T)) S_in + sum_tau (k_tau * exp(c_T - c_tau))^T v_tau

Chunk-local cumsums keep exp(+/-c) bounded (T <= 64 by default), the
standard numerical treatment for data-dependent decay.  The cumsum is a
lower-triangular-mask matmul over the chunk: Mosaic lowers no
``jnp.cumsum``.

Grid: (B, H, n_chunks), chunk axis sequential, state carried in VMEM
scratch (f32, D x D padded to 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _prefix_sum(x, *, T):
    """Inclusive prefix sum over the rows of a (T, D) tile as a lower-
    triangular matmul (``jnp.cumsum`` has no Mosaic lowering).  HIGHEST
    precision: the sums are log-decays that get exponentiated."""
    tril = (jax.lax.broadcasted_iota(jnp.int32, (T, T), 0) >=
            jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
            ).astype(jnp.float32)
    return jax.lax.dot(tril, x, precision=jax.lax.Precision.HIGHEST)


def _row_to_col(row, n):
    """(1, n) -> (n, 1) through a diagonal mask and a lane reduction (a
    vector transpose that every Mosaic version lowers)."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) ==
           jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
            y_ref, sout_ref, state_ref, *, T, D):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)            # (T, D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)          # (T, D) log decay (<0)
    u = u_ref[0, 0].astype(jnp.float32)            # (1, D)
    S = state_ref[...]                             # (D, D)

    c = _prefix_sum(lw, T=T)                       # (T, D) inclusive
    c_prev = c - lw                                # exclusive cumsum
    r_t = r * jnp.exp(c_prev)                      # (T, D)
    k_t = k * jnp.exp(-c)                          # (T, D)

    # intra-chunk, strictly-lower-triangular attention-like matmul
    att = jax.lax.dot_general(r_t, k_t, (((1,), (1,)), ((), ())))  # (T, T)
    tri = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    att = jnp.where(tri, att, 0.0)
    y = jax.lax.dot(att, v)                                         # (T, D)
    # diagonal bonus term: (r_t * u) . k_t v_t
    diag = ((r * u) * k).sum(-1, keepdims=True)                     # (T, 1)
    y = y + diag * v
    # inter-chunk
    y = y + jax.lax.dot(r_t, S)                                     # (T, D)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    cT = jnp.sum(lw, axis=0, keepdims=True)                         # (1, D)
    k_out = k * jnp.exp(cT - c)                                     # (T, D)
    S_new = S * jnp.exp(_row_to_col(cT, D)) + jax.lax.dot_general(
        k_out, v, (((0,), (0,)), ((), ())))                         # (D, D)
    state_ref[...] = S_new
    sout_ref[0, 0] = S_new


def rwkv6_scan(r, k, v, w, u, state=None, *, chunk=DEFAULT_CHUNK,
               interpret=False):
    """r,k,v,w: (B,S,H,D) (w = decay in (0,1)); u: (H,D);
    state: (B,H,D,D) or None -> (y (B,S,H,D), state (B,H,D,D))."""
    B, S, H, D = r.shape
    T = min(chunk, max(8, 1 << max(S - 1, 1).bit_length()))
    Sp = -(-S // T) * T
    Dp = max(128, -(-D // 128) * 128)
    nc = Sp // T

    def prep(a, pad_value=0.0):
        a = jnp.pad(a, ((0, 0), (0, Sp - S), (0, 0), (0, Dp - D)),
                    constant_values=pad_value)
        return a.transpose(0, 2, 1, 3)             # (B,H,S,D)

    rp, kp, vp = prep(r), prep(k), prep(v)
    # padded steps: w=1 (log w = 0) keeps the state unchanged; padded
    # channels also decay at 1 to avoid exp overflow in the +/- cumsums
    wp = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, Sp - S), (0, 0), (0, 0)),
                 constant_values=1.0)
    wp = jnp.pad(wp, ((0, 0), (0, 0), (0, 0), (0, Dp - D)),
                 constant_values=1.0)
    lwp = jnp.log(jnp.maximum(wp, 1e-30)).transpose(0, 2, 1, 3)
    up = jnp.pad(u.astype(jnp.float32), ((0, 0), (0, Dp - D)))[:, None, :]
    up = jnp.broadcast_to(up[None], (B, H, 1, Dp))
    s0 = (jnp.zeros((B, H, Dp, Dp), jnp.float32) if state is None else
          jnp.pad(state.astype(jnp.float32),
                  ((0, 0), (0, 0), (0, Dp - D), (0, Dp - D))))

    kernel = functools.partial(_kernel, T=T, D=Dp)
    y, sout = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, T, Dp), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, T, Dp), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, T, Dp), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, T, Dp), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Dp), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Dp, Dp), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, T, Dp), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Dp, Dp), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, Dp), r.dtype),
            jax.ShapeDtypeStruct((B, H, Dp, Dp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Dp, Dp), jnp.float32)],
        interpret=interpret,
    )(rp, kp, vp, lwp, up, s0)
    y = y.transpose(0, 2, 1, 3)[:, :S, :, :D]
    return y, sout[:, :, :D, :D]
