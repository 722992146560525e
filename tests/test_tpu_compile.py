"""Compile every Pallas kernel of the model path, and the simulator sweep,
for a described (not attached) TPU v5e chip at the published widths of a
config the repository has.

Nothing runs: a compile that passes shows that the chip's compiler accepts
the kernel's tiling, memory spaces and lowering, which interpret mode
cannot show.  The topology is described inside a module-scoped fixture,
never at import time, because only one process at a time may load the TPU
library (see the verify notes in the README).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import sim_sweep
from repro.kernels.burst_gather import burst_gather
from repro.kernels.flash_attention import decode_attention, flash_attention
from repro.kernels.mamba2_scan import mamba2_scan
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.rwkv6_scan import rwkv6_scan

GRANITE_MOE = configs.get("granite-moe-3b-a800m")
RWKV6 = configs.get("rwkv6-1.6b")
ZAMBA2 = configs.get("zamba2-7b")
TOKENS = 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes, kernel=True):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # a Pallas kernel lowers to a Mosaic custom call, not to XLA ops
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    return compiled


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_prefill(one_chip, dtype):
    """bf16 is the served dtype; float32 asks the MXU for full precision."""
    c = GRANITE_MOE
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True), one_chip,
             ((1, TOKENS, c.n_heads, c.head_dim), dtype),
             ((1, TOKENS, c.n_kv_heads, c.head_dim), dtype),
             ((1, TOKENS, c.n_kv_heads, c.head_dim), dtype))


def test_decode_attention(one_chip):
    c = GRANITE_MOE
    bf = jnp.bfloat16
    _compile(lambda q, k, v, pos: decode_attention(
        q, k, v, q_offset=pos, kv_len=jnp.full((4,), pos + 1)), one_chip,
        ((4, 1, c.n_heads, c.head_dim), bf),
        ((4, TOKENS, c.n_kv_heads, c.head_dim), bf),
        ((4, TOKENS, c.n_kv_heads, c.head_dim), bf),
        ((), jnp.int32))


def test_rwkv6_scan(one_chip):
    H, D = RWKV6.d_model // RWKV6.ssm_head_dim, RWKV6.ssm_head_dim
    assert (H, D) == (32, 64)
    bf = jnp.bfloat16
    _compile(lambda r, k, v, w, u, s: rwkv6_scan(r, k, v, w, u, s), one_chip,
             *[((1, TOKENS, H, D), bf)] * 4, ((H, D), jnp.float32),
             ((1, H, D, D), jnp.float32))


def test_mamba2_scan(one_chip):
    c = ZAMBA2
    P = c.ssm_head_dim
    H, N = c.ssm_expand * c.d_model // P, c.ssm_state
    bf, f32 = jnp.bfloat16, jnp.float32
    _compile(lambda x, dt, A, B, C, s: mamba2_scan(x, dt, A, B, C, s),
             one_chip, ((1, TOKENS, H, P), bf), ((1, TOKENS, H), f32),
             ((H,), f32), ((1, TOKENS, N), bf), ((1, TOKENS, N), bf),
             ((1, H, P, N), f32))


def test_burst_gather(one_chip):
    c = GRANITE_MOE
    assert (c.vocab_padded, c.d_model) == (49_408, 1_536)
    _compile(burst_gather, one_chip,
             ((c.vocab_padded, c.d_model), jnp.bfloat16),
             ((TOKENS,), jnp.int32))


def test_moe_gmm(one_chip):
    c = GRANITE_MOE
    _compile(moe_gmm, one_chip, ((TOKENS, c.d_model), jnp.bfloat16),
             ((c.n_experts, c.d_model, c.moe_d_ff), jnp.bfloat16),
             ((TOKENS,), jnp.int32))


def test_sim_sweep(one_chip):
    """The jitted simulator sweep at a campaign-sized bucket: 1,024 jobs of
    up to 64 tasks and 128 streams with a 64-deep push-history ring."""
    V, T, S, H = 1024, 64, 128, 64
    i32, b = jnp.int32, jnp.bool_
    compiled = _compile(
        sim_sweep._sweep, one_chip,
        ((V, S), i32), ((V, S), i32), ((V, T), i32), ((V, T), b),
        ((V, T), b), ((V, S), i32), ((V, S), i32), ((V, S, H), i32),
        ((V, S), i32), ((V, S), i32), ((V, T), i32), ((V, T), i32),
        ((), i32), ((), i32), kernel=False)
    assert compiled.memory_analysis().argument_size_in_bytes > V * S * H * 4
