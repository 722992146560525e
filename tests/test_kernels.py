"""Pallas kernel sweeps vs the pure-jnp oracles (interpret mode on CPU).

Every kernel is swept over shapes (incl. non-aligned head dims / odd
lengths) and dtypes; tolerances scale with dtype.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.burst_gather import burst_gather
from repro.kernels.flash_attention import decode_attention, flash_attention
from repro.kernels.mamba2_scan import mamba2_scan
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.rwkv6_scan import rwkv6_scan


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    # (B, Sq, Skv, Hq, Hkv, D)
    (1, 16, 16, 2, 2, 16),     # MHA
    (2, 48, 48, 4, 2, 24),     # GQA, odd D
    (1, 33, 33, 4, 1, 64),     # non-tile-aligned S, MQA
])
@pytest.mark.parametrize("variant", ["causal", "window", "softcap", "full"])
def test_flash_attention_sweep(dtype, shape, variant):
    B, Sq, Skv, Hq, Hkv, D = shape
    key = jax.random.PRNGKey(hash((shape, variant)) % 2**31)
    q = rand(key, (B, Sq, Hq, D), dtype)
    k = rand(jax.random.fold_in(key, 1), (B, Skv, Hkv, D), dtype)
    v = rand(jax.random.fold_in(key, 2), (B, Skv, Hkv, D), dtype)
    kwargs = {
        "causal": dict(causal=True),
        "window": dict(causal=True, window=max(4, Sq // 3)),
        "softcap": dict(causal=True, softcap=20.0),
        "full": dict(causal=False),
    }[variant]
    want = ref.attention_ref(q, k, v, **kwargs)
    got = flash_attention(q, k, v, interpret=True, **kwargs)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_flash_attention_kv_len_and_offset():
    key = jax.random.PRNGKey(0)
    q = rand(key, (2, 8, 2, 16), jnp.float32)
    k = rand(jax.random.fold_in(key, 1), (2, 32, 2, 16), jnp.float32)
    v = rand(jax.random.fold_in(key, 2), (2, 32, 2, 16), jnp.float32)
    kv_len = jnp.array([20, 32])
    want = ref.attention_ref(q, k, v, causal=True, q_offset=12, kv_len=kv_len)
    got = flash_attention(q, k, v, causal=True, q_offset=12, kv_len=kv_len,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("softcap", [None, 30.0])
@pytest.mark.parametrize("B, S, Hq, Hkv, D, kv_len", [
    (2, 64, 4, 2, 32, [40, 64]),                      # the first case
    (3, 48, 4, 4, 64, [1, 17, 48]),                   # g 1 (MHA)
    (9, 40, 6, 2, 64, [1, 2, 3, 8, 9, 20, 39, 40, 40]),  # g 3; B 9 over 8
    (2, 64, 14, 2, 128, None),                        # g 7; whole cache
    (2, 136, 16, 1, 128, [1, 136]),                   # g 16
    (2, 24, 4, 2, 256, [5, 24]),                      # D 256
    (4, 1100, 12, 4, 128, [1, 512, 1024, 1100]),      # KV blocks, ragged end
])
def test_decode_attention(B, S, Hq, Hkv, D, kv_len, softcap, dtype):
    """Against the reference at group sizes 1-16 and head dims 64-256, with
    ragged ``kv_len`` (1, KV block boundaries, the full cache) or none, a
    batch that is no multiple of the sequence block and, in the last
    case, a cache cut into KV blocks of which the last runs past its end
    (bf16: 1024 and 76 slots; float32: 512, 512 and 76).  The cache is
    the second layer of a stack of two, as the model keeps it."""
    key = jax.random.PRNGKey(B * S + D)
    q = rand(key, (B, 1, Hq, D), dtype)
    k = rand(jax.random.fold_in(key, 1), (B, S, Hkv, D), dtype)
    v = rand(jax.random.fold_in(key, 2), (B, S, Hkv, D), dtype)
    other = rand(jax.random.fold_in(key, 3), (B, S, Hkv * D), dtype)
    kst = jnp.stack([other, k.reshape(B, S, Hkv * D)])
    vst = jnp.stack([other, v.reshape(B, S, Hkv * D)])
    kv_len = None if kv_len is None else jnp.array(kv_len)
    want = ref.attention_ref(q, k, v, causal=False, softcap=softcap,
                             kv_len=kv_len)
    got = decode_attention(q, kst, vst, layer=1, softcap=softcap,
                           kv_len=kv_len, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_decode_blocks():
    """Decode-heavy's cache (B 64 x 256 slots, 8 KV heads of 64, bf16)
    takes 16 grid steps of 4 sequences, whole caches; chat-prefill's
    longest (B 4 x 2,056) is cut into KV blocks of 1,024 slots."""
    from repro.kernels.flash_attention import decode_blocks
    assert decode_blocks(64, 256, 8 * 64 * 2) == (4, 256)
    assert decode_blocks(4, 2056, 8 * 64 * 2) == (1, 1024)
    assert decode_blocks(3, 16, 2 * 32 * 2) == (3, 16)


# ---------------------------------------------------------------------------
# mamba2 / rwkv6
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 16, 2, 8, 16), (2, 40, 3, 16, 20),
                                   (1, 65, 2, 64, 64)])
@pytest.mark.parametrize("with_state", [False, True])
def test_mamba2_scan_sweep(dtype, shape, with_state):
    B, S, H, P, N = shape
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    x = rand(key, (B, S, H, P), dtype)
    dt = jax.nn.softplus(rand(jax.random.fold_in(key, 1), (B, S, H),
                              jnp.float32))
    A = -jnp.exp(rand(jax.random.fold_in(key, 2), (H,), jnp.float32))
    Bm = rand(jax.random.fold_in(key, 3), (B, S, N), dtype)
    Cm = rand(jax.random.fold_in(key, 4), (B, S, N), dtype)
    state = rand(jax.random.fold_in(key, 5), (B, H, P, N), jnp.float32) \
        if with_state else None
    yr, hr = ref.mamba2_scan_ref(x, dt, A, Bm, Cm, state)
    yk, hk = mamba2_scan(x, dt, A, Bm, Cm, state, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 16, 2, 8), (2, 40, 3, 16),
                                   (1, 33, 2, 64)])
@pytest.mark.parametrize("with_state", [False, True])
def test_rwkv6_scan_sweep(dtype, shape, with_state):
    B, S, H, D = shape
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    r = rand(key, (B, S, H, D), dtype)
    k = rand(jax.random.fold_in(key, 1), (B, S, H, D), dtype)
    v = rand(jax.random.fold_in(key, 2), (B, S, H, D), dtype)
    w = jnp.exp(-jnp.exp(rand(jax.random.fold_in(key, 3), (B, S, H, D),
                              jnp.float32))).astype(dtype)
    u = 0.3 * rand(jax.random.fold_in(key, 4), (H, D), jnp.float32)
    state = rand(jax.random.fold_in(key, 5), (B, H, D, D), jnp.float32) \
        if with_state else None
    yr, sr = ref.rwkv6_scan_ref(r, k, v, w, u, state)
    yk, sk = rwkv6_scan(r, k, v, w, u, state, chunk=16, interpret=True)
    # chunked rescan vs the sequential reference: fp32 accumulation over the
    # longest (S=33, D=64) sweep legitimately drifts a few 1e-5, so the fp32
    # tolerance is looser than the generic 2e-5 used elsewhere.
    ytol = tol(dtype) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32), **ytol)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


# ---------------------------------------------------------------------------
# burst gather / moe gmm
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 60),
       st.sampled_from([0.0, 0.5, 1.0]))
def test_burst_gather_property(seed, n, seq_frac):
    """Any index pattern — fully sequential, mixed, or random — must match
    a plain gather (the burst detector is a pure optimization)."""
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(size=(64, 12)), jnp.float32)
    idx = np.empty(n, np.int32)
    i = 0
    while i < n:
        if rng.random() < seq_frac:
            run = min(int(rng.integers(2, 12)), n - i)
            start = int(rng.integers(0, 64 - run))
            idx[i:i + run] = np.arange(start, start + run)
            i += run
        else:
            idx[i] = rng.integers(0, 64)
            i += 1
    idx = jnp.asarray(idx)
    want = ref.burst_gather_ref(table, idx)
    got = burst_gather(table, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("pattern", ["aligned", "shifted", "random"])
def test_burst_gather_paths(dtype, pattern):
    """Both DMA paths, on a table whose rows and width are not tile
    multiples: tile-aligned runs take the one-DMA burst path, shifted runs
    and random rows the per-row path (which selects a row out of its 8-row
    HBM tile).  bf16 packs two rows per word, so row selection must not
    straddle a packed pair."""
    rng = np.random.default_rng(7)
    table = jnp.asarray(rng.normal(size=(101, 40)), dtype)
    idx = {"aligned": np.arange(16, 48),
           "shifted": np.arange(3, 35),
           "random": rng.integers(0, 101, 32)}[pattern]
    got = burst_gather(table, jnp.asarray(idx, jnp.int32), interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(table, np.float32)[idx])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(50, 24, 36, 5), (16, 8, 8, 2),
                                   (130, 64, 32, 8)])
def test_moe_gmm_sweep(dtype, shape):
    T, K, N, E = shape
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    x = rand(key, (T, K), dtype)
    w = rand(jax.random.fold_in(key, 1), (E, K, N), dtype) * 0.1
    gid = jnp.sort(jax.random.randint(jax.random.fold_in(key, 2), (T,), 0, E))
    want = ref.moe_gmm_ref(x, w, gid)
    got = moe_gmm(x, w, gid, tb=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))
