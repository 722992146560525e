"""Attention kernels for TPU (pl.pallas_call + BlockSpec VMEM tiling):
``flash_attention`` for prefill, ``decode_attention`` for one new token.

Prefill.  Grid (B, Hq, nQ, nK) — the trailing KV dimension is sequential
on TPU, so the online-softmax running state (m, l, acc) lives in VMEM
scratch that persists across KV iterations.  GQA is free: the K/V index
map sends query head h to KV head h // group.  Causal masking, sliding
windows and gemma logit soft-caps are fused; fully-maskable KV blocks are
skipped via ``pl.when``.  The per-sequence KV length and query offset are
scalar-prefetched into SMEM (a (1, 1) VMEM block of a (B, 1) array is not
a legal TPU tile).  Tiling: Qb x D and Kb x D blocks, 128-aligned for the
MXU; head dims that are not multiples of 128 are zero-padded by the
wrapper.  VMEM per program: q/k/v blocks (3 x 32 KiB bf16) + f32 scratch
(m, l: 2 x 64 KiB lane-padded; acc: 64 KiB) — far under the ~16 MiB
budget, leaving room for double buffering of the K/V streams.

Decode.  A kernel of its own, on the cache where it lies: K and V are a
stack of every layer's cache, (L, B, S, Hkv * D), a slot's heads side by
side in one row (``layers.attn_cache_init``); the layer index is
prefetched to SMEM and picks the blocks, so the stack is read from HBM
and no slice of it is copied first.  Grid (B / bb, S / kb): each step
takes bb sequences and kb slots of their cache, every KV head, with bb
and kb from the shapes and a VMEM budget (``decode_blocks``: a whole
cache per step where it fits 1 MiB, else KV blocks of a multiple of 128
slots; K and V double-buffered take 4 MiB).  Per sequence one MXU
product scores every query head against every KV head's keys,
(Hq, Hkv * D) x (kb, Hkv * D)^T, through a block-diagonal query that
holds q[h] in the lanes of KV head h // g and zeros elsewhere; the
probabilities times V give (Hq, Hkv * D), of which each row keeps its
own head's lanes.  The wasted products are Hkv times the needed ones,
which keeps decode memory-bound (at most Hq FLOP per byte of cache).  KV
blocks past a sequence block's longest ``kv_len`` are skipped and not
fetched again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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
                    qb=DEFAULT_QB, kb=DEFAULT_KB, interpret=False,
                    name="flash_attention"):
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D).
    ``name`` is the kernel's name in the compiled program and its trace."""
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
        name=name,
    )(klen, qoff, qp, kp, vp)
    return out.transpose(0, 2, 1, 3)[:, :Sq, :, :D]


# ---------------------------------------------------------------------------
# single-token decode
# ---------------------------------------------------------------------------

DECODE_BLOCK_BYTES = 1 << 20   # one K (or V) block; VMEM holds 4: K, V x 2
DECODE_MAX_SEQS = 8            # sequences a grid step unrolls


def decode_blocks(B, S, key_bytes):
    """(bb, kb): sequences and cache slots a decode grid step reads, from
    the shapes alone.  ``key_bytes`` is one slot of one sequence, all KV
    heads (Hkv * D * itemsize).  A whole cache takes one KV block when it
    fits the block budget; a longer one is cut into blocks of a multiple
    of 128 slots."""
    if S * key_bytes <= DECODE_BLOCK_BYTES:
        kb = S
    else:
        kb = max(128, DECODE_BLOCK_BYTES // key_bytes // 128 * 128)
    bb = max(1, min(B, DECODE_MAX_SEQS,
                    DECODE_BLOCK_BYTES // (kb * key_bytes)))
    return bb, kb


def _decode_kernel(layer_ref, klen_ref, nblk_ref, q_ref, own_ref, expand_ref,
                   k_ref, v_ref, o_ref, qbd_ref, m_ref, l_ref, acc_ref, *,
                   bb, kb, nk, seq, scale, softcap):
    i = pl.program_id(0)
    j = pl.program_id(1)
    dt = k_ref.dtype
    # float32 inputs ask for full precision (the MXU's default rounds them
    # to bf16); bf16 inputs multiply exactly anyway
    prec = (jax.lax.Precision.HIGHEST if dt == jnp.float32
            else jax.lax.Precision.DEFAULT)
    nt = (((1,), (1,)), ((), ()))          # contract the last dims

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # block-diagonal query: row h holds q[h] in the lanes of its KV head
        # h // g and zeros elsewhere (the 0/1 ``expand`` product is exact)
        for s in range(bb):
            qt = jax.lax.dot(q_ref[s], expand_ref[...], precision=prec,
                             preferred_element_type=jnp.float32)
            qbd_ref[s] = (qt * own_ref[...]).astype(dt)

    @pl.when(j < nblk_ref[i])
    def _body():
        kpos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
        for s in range(bb):
            valid = kpos < klen_ref[i * bb + s]                # (1, kb)
            # all query heads against all KV heads' keys: (Hq, Hkv*D) x
            # (kb, Hkv*D)^T; the zeros of the block-diagonal query leave
            # each head its own KV head's scores
            sc = jax.lax.dot_general(
                qbd_ref[s], k_ref[s], nt, precision=prec,
                preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                sc = jnp.tanh(sc / softcap) * softcap
            sc = jnp.where(valid, sc, NEG_INF)
            m_prev = m_ref[s]                                  # (Hq, 1)
            m_new = jnp.maximum(m_prev, sc.max(-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
            l_ref[s] = l_ref[s] * alpha + p.sum(-1, keepdims=True)
            v = v_ref[s]                                       # (kb, Hkv*D)
            if seq % kb:
                # the last block runs past the cache's end, and what lies
                # there is not data: zero it, since 0 * NaN is NaN
                rows = j * kb + jax.lax.broadcasted_iota(
                    jnp.int32, (kb, 1), 0)
                v = jnp.where(rows < seq, v, jnp.zeros_like(v))
            acc_ref[s] = acc_ref[s] * alpha + jax.lax.dot(
                p.astype(dt), v, precision=prec,
                preferred_element_type=jnp.float32)
            m_ref[s] = m_new

    @pl.when(j == nk - 1)
    def _fin():
        own = own_ref[...].astype(jnp.float32)
        expand = expand_ref[...].astype(jnp.float32)
        for s in range(bb):
            lsum = l_ref[s]
            x = acc_ref[s] / jnp.where(lsum > 0, lsum, 1.0) * own
            # each row's own KV head's lanes, folded back to (Hq, D)
            o_ref[s] = jax.lax.dot_general(
                x, expand, nt, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32).astype(o_ref.dtype)


def decode_attention(q, k, v, *, layer=0, kv_len=None, softcap=None,
                     scale=None, interpret=False):
    """Single-token decode: q (B, 1, Hq, D) against layer ``layer`` of a
    stack of (possibly ring-buffered) KV caches k, v (L, B, S, Hkv * D),
    a slot's heads side by side in one row, as ``layers.attn_cache_init``
    stores them -> (B, 1, Hq, D).  Every cached key precedes the query,
    so ``kv_len`` alone masks; ``kv_len=None`` attends to the whole cache.

    The kernel reads the layer in place: the stack stays in HBM and the
    layer index, prefetched to SMEM, picks its blocks, so no slice of it
    is copied first.  Grid (B / bb, S / kb) from ``decode_blocks``: each
    step takes bb sequences and kb slots of their cache, all KV heads; one
    product per sequence scores every query head against every KV head's
    keys through a block-diagonal query (q[h] in the lanes of KV head
    h // g), and the probabilities times V give (Hq, Hkv * D), of which
    each row keeps its own head's lanes.  The online softmax runs across
    KV blocks; a block at or past its sequences' longest ``kv_len`` is
    neither computed nor fetched (its index is clamped to the last needed
    block).  MXU inputs in the cache's dtype, scores, softmax and
    accumulation in float32."""
    B, Sq, Hq, D = q.shape
    _, _, S, HD = k.shape
    assert Sq == 1 and k.shape[1] == B and HD % D == 0
    Hkv = HD // D
    g = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale
    bb, kb = decode_blocks(B, S, HD * k.dtype.itemsize)
    nb, nk = -(-B // bb), -(-S // kb)

    klen = (jnp.full((B,), S, jnp.int32) if kv_len is None else
            jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,)))
    klen = jnp.pad(klen, (0, nb * bb - B))
    nblk = (klen.reshape(nb, bb).max(1) + kb - 1) // kb
    own = jnp.asarray(np.kron(np.eye(Hkv), np.ones((g, D))), k.dtype)
    expand = jnp.asarray(np.tile(np.eye(D), (1, Hkv)), k.dtype)

    def kv_block(i, j, layer, klen, nblk):
        return (layer[0], i, jnp.minimum(j, jnp.maximum(nblk[i] - 1, 0)), 0)

    kernel = functools.partial(
        _decode_kernel, bb=bb, kb=kb, nk=nk, seq=S, scale=scale,
        softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # layer, klen, nblk -> SMEM
        grid=(nb, nk),
        in_specs=[
            pl.BlockSpec((bb, Hq, D), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((Hq, HD), lambda i, j, *_: (0, 0)),
            pl.BlockSpec((D, HD), lambda i, j, *_: (0, 0)),
            pl.BlockSpec((pl.squeezed, bb, kb, HD), kv_block),
            pl.BlockSpec((pl.squeezed, bb, kb, HD), kv_block),
        ],
        out_specs=pl.BlockSpec((bb, Hq, D), lambda i, j, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bb, Hq, HD), k.dtype),
            pltpu.VMEM((bb, Hq, 1), jnp.float32),
            pltpu.VMEM((bb, Hq, 1), jnp.float32),
            pltpu.VMEM((bb, Hq, HD), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), klen, nblk,
      q.reshape(B, Hq, D).astype(k.dtype), own, expand, k, v)
    return out.reshape(B, 1, Hq, D)
