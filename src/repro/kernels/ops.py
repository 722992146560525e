"""Dispatch wrappers over the Pallas kernels and their XLA references.

The model layer calls these.  ``kernel`` selects the implementation:

  * False — the pure-jnp reference, compiled by XLA.  It has a VJP, so the
            differentiated path (``lm.loss_fn`` and the train steps) and
            every non-TPU backend use it.
  * True  — the compiled Pallas TPU kernel.  None of the kernels defines a
            VJP, so only the serving step (``lm.step``) on a TPU asks for
            it; ``lm.kernels_here()`` makes that choice from the platform.

Tests run the kernels on the CPU by calling ``repro.kernels.<name>``
directly with ``interpret=True``; the references in ``repro.kernels.ref``
are their oracles.
"""
from __future__ import annotations

from . import ref as _ref


def attention(q, k, v, *, causal=True, window=None, softcap=None, scale=None,
              q_offset=0, kv_len=None, kernel=False):
    if not kernel:
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale,
                                  q_offset=q_offset, kv_len=kv_len)
    from . import flash_attention as fa
    if q.shape[1] > 1:
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale,
                                  q_offset=q_offset, kv_len=kv_len)
    # single-token decode against keys that are no cache (cross-attention):
    # a stack of one layer with a slot's heads in one row
    B, S = k.shape[:2]
    return fa.decode_attention(q, k.reshape(1, B, S, -1),
                               v.reshape(1, B, S, -1), softcap=softcap,
                               scale=scale, kv_len=kv_len)


def cached_attention(q, k_cache, v_cache, layer, *, kv_len, softcap=None,
                     scale=None, kernel=False):
    """One new token (B, 1, Hq, D) against layer ``layer`` of stacked KV
    caches (L, B, W, Hkv * D) (``layers.attn_cache_init``).  The kernel
    reads the layer where it lies; the reference slices it out."""
    if not kernel:
        B, _, _, D = q.shape
        W = k_cache.shape[2]
        k = k_cache[layer].reshape(B, W, -1, D)
        v = v_cache[layer].reshape(B, W, -1, D)
        return _ref.attention_ref(q, k, v, causal=False, softcap=softcap,
                                  scale=scale, kv_len=kv_len)
    from . import flash_attention as fa
    return fa.decode_attention(q, k_cache, v_cache, layer=layer,
                               kv_len=kv_len, softcap=softcap, scale=scale)


def mamba2_scan(x, dt, A, B, C, state=None, *, kernel=False):
    if not kernel:
        return _ref.mamba2_scan_ref(x, dt, A, B, C, state)
    from . import mamba2_scan as m2
    return m2.mamba2_scan(x, dt, A, B, C, state)


def rwkv6_scan(r, k, v, w, u, state=None, *, kernel=False):
    if not kernel:
        return _ref.rwkv6_scan_ref(r, k, v, w, u, state)
    from . import rwkv6_scan as r6
    return r6.rwkv6_scan(r, k, v, w, u, state)


def burst_gather(table, idx, *, kernel=False):
    if not kernel:
        return _ref.burst_gather_ref(table, idx)
    from . import burst_gather as bg
    return bg.burst_gather(table, idx)


def moe_gmm(x, w, group_ids, *, kernel=False):
    if not kernel:
        return _ref.moe_gmm_ref(x, w, group_ids)
    from . import moe_gmm as gmm
    return gmm.moe_gmm(x, w, group_ids)
