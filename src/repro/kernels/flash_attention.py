"""Blocked flash attention for TPU (pl.pallas_call + BlockSpec VMEM tiling).

Layout: grid (B, Hq, nQ, nK) — the trailing KV dimension is sequential on
TPU, so the online-softmax running state (m, l, acc) lives in VMEM scratch
that persists across KV iterations.  GQA is free: the K/V index map sends
query head h to KV head h // group.  Causal masking, sliding windows and
gemma logit soft-caps are fused; fully-maskable KV blocks are skipped via
``pl.when``.  The per-sequence KV length and query offset are scalar-
prefetched into SMEM (a (1, 1) VMEM block of a (B, 1) array is not a legal
TPU tile).

Tiling: Qb x D and Kb x D blocks, 128-aligned for the MXU; head dims that
are not multiples of 128 are zero-padded by the wrapper.  VMEM per program:
q/k/v blocks (3 x 32 KiB bf16) + f32 scratch (m, l: 2 x 64 KiB lane-padded;
acc: 64 KiB) — far under the ~16 MiB budget, leaving room for double
buffering of the K/V streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_QB = 128
DEFAULT_KB = 128
NEG_INF = -1e30


def _kernel(klen_ref, qoff_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *,
            causal, window, softcap, scale, nk, qb, kb, use_klen):
    b = pl.program_id(0)
    i = pl.program_id(2)
    j = pl.program_id(3)
    q_offset = qoff_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = q_offset + i * qb + jax.lax.broadcasted_iota(
        jnp.int32, (qb, kb), 0)
    kpos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)

    # block-level skip: causal blocks entirely in the future, window blocks
    # entirely in the past
    run = jnp.asarray(True)
    if causal:
        run = jnp.logical_and(run, j * kb <= q_offset + (i + 1) * qb - 1)
    if window is not None:
        run = jnp.logical_and(
            run, (j + 1) * kb - 1 > q_offset + i * qb - window)

    @pl.when(run)
    def _body():
        # MXU products in the input dtype, accumulated in float32; float32
        # inputs ask for full precision (the MXU's default rounds them to
        # bf16), bf16 inputs multiply exactly anyway
        v = v_ref[0, 0]
        prec = (jax.lax.Precision.HIGHEST if v.dtype == jnp.float32
                else jax.lax.Precision.DEFAULT)
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            precision=prec, preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        mask = jnp.ones((qb, kb), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if use_klen:
            mask &= kpos < klen_ref[b]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                   # (qb, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, precision=prec,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _fin():
        lsum = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(lsum > 0, lsum, 1.0)
                       ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, q_offset=0, kv_len=None,
                    qb=DEFAULT_QB, kb=DEFAULT_KB, interpret=False):
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale

    qb = min(qb, max(8, 1 << max(Sq - 1, 1).bit_length()))
    kb = min(kb, max(128, 1 << max(Skv - 1, 1).bit_length()))
    Sq_p = -(-Sq // qb) * qb
    Skv_p = -(-Skv // kb) * kb
    Dp = max(128, -(-D // 128) * 128)
    qp = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, Dp - D)))
    kp = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, Dp - D)))
    vp = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, Dp - D)))
    qp = qp.transpose(0, 2, 1, 3)      # (B, H, S, D)
    kp = kp.transpose(0, 2, 1, 3)
    vp = vp.transpose(0, 2, 1, 3)
    nq, nk = Sq_p // qb, Skv_p // kb

    if kv_len is None:
        klen = jnp.full((B,), Skv, jnp.int32)
        use_klen = Skv_p != Skv
    else:
        klen = jnp.broadcast_to(
            jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
        use_klen = True
    qoff = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1),
                            (B,))

    kernel = functools.partial(
        _kernel, causal=causal, window=window, softcap=softcap, scale=scale,
        nk=nk, qb=qb, kb=kb, use_klen=use_klen)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # klen, qoff -> SMEM
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qb, Dp), lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kb, Dp),
                         lambda b, h, i, j, *_: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, kb, Dp),
                         lambda b, h, i, j, *_: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, Dp),
                               lambda b, h, i, j, *_: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, Dp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, Dp), q.dtype),
        interpret=interpret,
    )(klen, qoff, qp, kp, vp)
    return out.transpose(0, 2, 1, 3)[:, :Sq, :, :D]


def decode_attention(q, k, v, *, softcap=None, scale=None, q_offset=0,
                     kv_len=None, window=None, interpret=False):
    """Single-token decode: q (B, 1, Hq, D) against a (possibly ring-
    buffered) KV cache.  Reuses the flash kernel with a padded query tile;
    causality is enforced through ``kv_len`` (every cached key is valid)."""
    B, Sq, Hq, D = q.shape
    assert Sq == 1
    qp = jnp.pad(q, ((0, 0), (0, 7), (0, 0), (0, 0)))
    out = flash_attention(qp, k, v, causal=False, window=None,
                          softcap=softcap, scale=scale, q_offset=q_offset,
                          kv_len=kv_len, qb=8, interpret=interpret)
    return out[:, :1]
