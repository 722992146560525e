"""Chunked SSD (Mamba-2) scan for TPU.

The sequential recurrence  h_t = a_t h_{t-1} + dt_t x_t B_t^T,
y_t = h_t C_t  (a_t = exp(dt_t A)) is reformulated per chunk of length T as
three MXU-friendly matmuls (the SSD "chunked dual form"):

  intra:  y = (mask(C B^T) * decay(t, tau)) @ (dt * x)
  inter:  y += decay(t, 0) * (C @ state^T)
  state': state * decay(T, 0) + ((dt * x) * decay(T, tau))^T @ B

Grid: (B, H, n_chunks) — the chunk axis is sequential on TPU, so the
(P, N) state is carried in f32 VMEM scratch across chunk iterations.  The
within-chunk cumsum of log-decays is a triangular-mask matmul: Mosaic
lowers no ``jnp.cumsum``.
Tiling: chunk T=128, P (head dim) and N (state dim) padded to 128.  VMEM
per program: x/B/C chunks (3 x 64 KiB f32) + decay tables + state
(64 KiB) — well under budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _row_to_col(row, n):
    """(1, n) -> (n, 1) through a diagonal mask and a lane reduction (a
    vector transpose that every Mosaic version lowers)."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) ==
           jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
            y_ref, hout_ref, state_ref, *, T):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)          # (T, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (1, T) row
    A = a_ref[0, 0].astype(jnp.float32)          # (1, 1)
    Bm = b_ref[0].astype(jnp.float32)            # (T, N)
    Cm = c_ref[0].astype(jnp.float32)            # (T, N)
    h = state_ref[...]                           # (P, N)

    seg = dt * A                                 # (1, T) log-decay increments
    # s_t = sum_{tau<=t} seg as a row: seg @ triu (Mosaic lowers no cumsum);
    # HIGHEST precision because the sums get exponentiated
    triu = (jax.lax.broadcasted_iota(jnp.int32, (T, T), 0) <=
            jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
            ).astype(jnp.float32)
    cum = jax.lax.dot(seg, triu, precision=jax.lax.Precision.HIGHEST)
    cum_c = _row_to_col(cum, T)                  # (T, 1)
    dt_c = _row_to_col(dt, T)                    # (T, 1)
    # decay(t, tau) = exp(s_t - s_tau) for tau <= t (the input at tau is
    # included from step tau itself)
    tri = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    decay = jnp.where(tri, jnp.exp(cum_c - cum), 0.0)

    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # (T, T)
    dx = x * dt_c                                               # (T, P)
    y = jax.lax.dot(G * decay, dx)                              # (T, P)
    # inter-chunk: h carries state BEFORE this chunk; contribution at step t
    # is C_t . (h * exp(s_t))
    y = y + jnp.exp(cum_c) * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())))                        # (T, P)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    total = jnp.sum(seg, axis=1, keepdims=True)                 # (1, 1)
    w = jnp.exp(total - cum_c)                                  # (T, 1)
    new_h = h * jnp.exp(total) + jax.lax.dot_general(
        dx * w, Bm, (((0,), (0,)), ((), ())))                   # (P, N)
    state_ref[...] = new_h
    hout_ref[0, 0] = new_h


def mamba2_scan(x, dt, A, B_, C, state=None, *, chunk=DEFAULT_CHUNK,
                interpret=False):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); B_,C: (B,S,N);
    state: (B,H,P,N) or None.  Returns (y (B,S,H,P), state (B,H,P,N))."""
    Bsz, S, H, P = x.shape
    N = B_.shape[-1]
    T = min(chunk, max(8, 1 << max(S - 1, 1).bit_length()))
    Sp = -(-S // T) * T
    Pp = max(128, -(-P // 128) * 128)
    Np = max(128, -(-N // 128) * 128)
    nc = Sp // T

    xp = jnp.pad(x, ((0, 0), (0, Sp - S), (0, 0), (0, Pp - P)))
    xp = xp.transpose(0, 2, 1, 3)                       # (B,H,S,P)
    # padded steps must be identity: dt = 0 there
    dtp = jnp.pad(dt, ((0, 0), (0, Sp - S), (0, 0)))
    dtp = dtp.transpose(0, 2, 1)[:, :, None, :]         # (B,H,1,S)
    Ar = A.astype(jnp.float32).reshape(1, H, 1, 1)
    Ar = jnp.broadcast_to(Ar, (Bsz, H, 1, 1))
    Bp = jnp.pad(B_, ((0, 0), (0, Sp - S), (0, Np - N)))
    Cp = jnp.pad(C, ((0, 0), (0, Sp - S), (0, Np - N)))
    h0 = (jnp.zeros((Bsz, H, Pp, Np), jnp.float32) if state is None else
          jnp.pad(state.astype(jnp.float32),
                  ((0, 0), (0, 0), (0, Pp - P), (0, Np - N))))

    kernel = functools.partial(_kernel, T=T)
    y, hout = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, T, Pp), lambda b, h, c: (b, h, c, 0)),   # x
            pl.BlockSpec((1, 1, 1, T), lambda b, h, c: (b, h, 0, c)),    # dt
            pl.BlockSpec((1, 1, 1, 1), lambda b, h, c: (b, h, 0, 0)),    # A
            pl.BlockSpec((1, T, Np), lambda b, h, c: (b, c, 0)),         # B
            pl.BlockSpec((1, T, Np), lambda b, h, c: (b, c, 0)),         # C
            pl.BlockSpec((1, 1, Pp, Np), lambda b, h, c: (b, h, 0, 0)),  # h0
        ],
        out_specs=[
            pl.BlockSpec((1, 1, T, Pp), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Pp, Np), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, Sp, Pp), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, Pp, Np), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Pp, Np), jnp.float32)],
        interpret=interpret,
        name="mamba2_scan",
    )(xp, dtp, Ar, Bp, Cp, h0)
    y = y.transpose(0, 2, 1, 3)[:, :S, :, :P]
    return y, hout[:, :, :P, :N]
