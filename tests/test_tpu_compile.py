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

import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import sim_sweep
from repro.launch import serve
from repro.kernels.burst_gather import burst_gather
from repro.kernels.flash_attention import decode_attention, flash_attention
from repro.kernels.mamba2_scan import mamba2_scan
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.model import lm

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


@pytest.mark.parametrize("B, S, cfg, softcap", [
    (64, 256, "granite-moe-3b-a800m", None),     # decode-heavy's step
    (4, 2056, "granite-moe-3b-a800m", None),     # chat-prefill's longest
    (4, TOKENS, "granite-moe-3b-a800m", None),
    (8, 4096, "gemma2-27b", 50.0),               # D 128, soft-capped
])
def test_decode_attention(one_chip, B, S, cfg, softcap):
    """Layer ``layer`` (traced) of a stack of every layer's cache, as the
    model's layer loop hands it over."""
    c = configs.get(cfg)
    bf = jnp.bfloat16
    cache = (c.n_layers, B, S, c.n_kv_heads * c.head_dim)
    _compile(lambda q, k, v, pos, layer: decode_attention(
        q, k, v, layer=layer, softcap=softcap,
        kv_len=jnp.full((B,), pos + 1)), one_chip,
        ((B, 1, c.n_heads, c.head_dim), bf), (cache, bf), (cache, bf),
        ((), jnp.int32), ((), jnp.int32))


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


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_serve_step_kernels_are_named(one_chip, monkeypatch, program):
    """The published-width serve step as ``generate`` compiles it on a TPU:
    each Pallas call is a custom call named by its kernel's ``name=``, the
    attention kernel under the ``attention`` scope and the embedding gather
    under ``embed`` (what ``bench/layer_time.py`` reads from a trace)."""
    monkeypatch.setattr(lm, "kernels_here", lambda: True)
    c, B, S = GRANITE_MOE, 8, 256

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(functools.partial(lm.init_params, c),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda p: lm.init_cache(p, c, B, max_seq=S + 8), params))
    step = dict(zip(("prefill", "decode"), serve.step_programs(c)))[program]
    tokens = jax.ShapeDtypeStruct((B, S if program == "prefill" else 1),
                                  jnp.int32, sharding=one_chip)
    text = step.lower(params, cache, tokens).compile().as_text()
    assert text.startswith(f"HloModule jit_{program},")
    attn = "flash_attention" if program == "prefill" else "decode_attention"
    kernels = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT )?%([\w\-]+)\.?\d*\s", line).group(1)
            kernels[name] = re.search(r'op_name="([^"]*)"', line).group(1)
    assert sorted(kernels) == sorted([attn, "burst_gather"])
    assert kernels[attn].startswith(f"jit({program})/while/")
    assert kernels[attn].endswith(f"/attention/{attn}/pallas_call")
    assert kernels["burst_gather"] == f"jit({program})/embed/burst_gather/" \
        "pallas_call"


def test_decode_step_reads_cache_in_place(one_chip, monkeypatch):
    """Decode-heavy's step (granite-moe, B 64, a 256-slot cache) as
    ``generate`` compiles it on a TPU: every layer's K (and V) stays one
    stack in the layer loop, and no operation of the whole program slices,
    copies, pads or transposes a layer's cache.  The only operations of
    that size besides the program's parameters and the loop's tuple are
    the two writes of the new token's K and V into the stacks, in place,
    and the decode kernel's K and V operands are those writes: it reads
    its layer from the stack in HBM, with no copy staged for it."""
    monkeypatch.setattr(lm, "kernels_here", lambda: True)
    c, B, S = GRANITE_MOE, 64, 256

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(functools.partial(lm.init_params, c),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda p: lm.init_cache(p, c, B, max_seq=S), params))
    decode = serve.step_programs(c)[1]
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    text = decode.lower(params, cache, tokens).compile().as_text()
    stack = f"bf16[{c.n_layers},{B},{S},{c.n_kv_heads * c.head_dim}]"
    big, kernel = {}, None
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\S* "
                     r"([\w-]+)\((.*)", line)
        if not m:
            continue
        dims = list(map(int, m.group(2).split(",")))
        if m.group(1).startswith("decode_attention"):
            kernel = re.findall(r"%([\w.\-]+)", m.group(4))
        # a layer's cache or more, in any layout: B and S among the dims
        if (B in dims and S in dims and math.prod(dims)
                >= B * S * c.n_kv_heads * c.head_dim):
            big[m.group(1)] = (m.group(3), line.split(" = ")[1].split("{")[0])
    writes = [n for n, (op, _) in big.items() if op == "dynamic-update-slice"]
    assert {op for op, _ in big.values()} == {
        "parameter", "get-tuple-element", "dynamic-update-slice"}, big
    assert len(writes) == 2
    assert all(big[n][1] == stack for n in writes)
    assert kernel is not None and kernel[-2:] == writes
