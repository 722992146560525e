"""Per-architecture smoke tests: reduced same-family config, one forward +
one train-grad step + prefill/decode on CPU; asserts shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.model import lm


def _extra(cfg, batch):
    if cfg.family == "vlm":
        return {"vision": jnp.ones((batch, cfg.frontend_tokens,
                                    cfg.frontend_dim), jnp.bfloat16) * 0.01}
    if cfg.family == "audio":
        return {"frames": jnp.ones((batch, cfg.frontend_tokens,
                                    cfg.frontend_dim), jnp.bfloat16) * 0.01}
    return None


@pytest.mark.parametrize("name", configs.ARCHS)
def test_forward_and_grad(name):
    cfg = configs.get_reduced(name)
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    B, S = 2, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens}
    extra = _extra(cfg, B)
    if extra is not None:
        batch["extra"] = extra

    logits, aux = jax.jit(
        lambda p, t: lm.forward(p, cfg, t, extra=extra))(params, tokens)
    assert logits.shape == (B, S, cfg.vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm.loss_fn(p, cfg, batch)))(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(not bool(jnp.isnan(g.astype(jnp.float32)).any()) for g in flat)


@pytest.mark.parametrize("name", configs.ARCHS)
def test_prefill_then_decode(name):
    cfg = configs.get_reduced(name)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    extra = _extra(cfg, B)
    cache = lm.init_cache(params, cfg, B, max_seq=64, extra=extra)
    logits, cache = jax.jit(lambda p, c, t: lm.step(p, cfg, c, t))(
        params, cache, tokens)
    assert logits.shape == (B, cfg.vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    # two decode steps
    for _ in range(2):
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        logits, cache = jax.jit(lambda p, c, t: lm.step(p, cfg, c, t))(
            params, cache, nxt)
        assert logits.shape == (B, cfg.vocab)
        assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    assert int(cache["pos"]) == S + 2


def test_decode_matches_forward_dense():
    """Teacher-forced decode logits must match the full forward pass."""
    cfg = configs.get_reduced("granite-8b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 1, 12
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab)
    full_logits, _ = lm.forward(params, cfg, tokens)
    cache = lm.init_cache(params, cfg, B, max_seq=32)
    outs = []
    for t in range(S):
        lg, cache = lm.step(params, cfg, cache, tokens[:, t:t + 1])
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(full_logits, np.float32), np.asarray(dec, np.float32),
        rtol=0.05, atol=0.05)


def test_sliding_window_ring_cache_consistency():
    """gemma-style local attention: decode through a ring buffer must match
    the full forward pass once context exceeds the window."""
    cfg = configs.get_reduced("gemma3-12b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 1, 48   # window is 32 in the reduced config
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, cfg.vocab)
    full_logits, _ = lm.forward(params, cfg, tokens)
    cache = lm.init_cache(params, cfg, B, max_seq=64)
    outs = []
    for t in range(S):
        lg, cache = lm.step(params, cfg, cache, tokens[:, t:t + 1])
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(full_logits, np.float32), np.asarray(dec, np.float32),
        rtol=0.06, atol=0.06)


def test_serve_generate_matches_forward():
    """The serving entry point: prefill, then greedy decode through
    the donated cache; every step's last-position logits match the full
    forward pass over the same tokens, and each token is the argmax of the
    logits before it."""
    from repro.launch import serve

    cfg = configs.get_reduced("granite-moe-3b-a800m")
    params = serve.init_params(cfg, 0)
    B, S, gen = 2, 16, 4
    prompts = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, cfg.vocab)
    out = serve.generate(params, cfg, prompts, gen=gen)
    assert out.tokens.shape == (B, gen) and len(out.logits) == gen + 1
    for i in range(gen):
        np.testing.assert_array_equal(
            np.asarray(out.tokens[:, i]),
            np.asarray(jnp.argmax(out.logits[i], -1)))
    seq = jnp.concatenate([prompts, out.tokens], axis=1)
    full, _ = lm.forward(params, cfg, seq)
    for i, lg in enumerate(out.logits):
        np.testing.assert_allclose(
            np.asarray(lg[:, :cfg.vocab], np.float32),
            np.asarray(full[:, S - 1 + i], np.float32), rtol=0.05, atol=0.05)


def test_kernels_only_in_the_serving_step_on_tpu(monkeypatch):
    """One path per op: the serving step runs the Pallas kernels where the
    platform is a TPU; off a TPU, and on the differentiated path always,
    the model runs the XLA references."""
    cfg = configs.get_reduced("granite-moe-3b-a800m")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    cache = lm.init_cache(params, cfg, 2, max_seq=32)
    toks = jnp.zeros((2, 16), jnp.int32)

    def kernels_in_step():     # a fresh function: no cached trace
        return str(jax.make_jaxpr(lambda p, c, t: lm.step(p, cfg, c, t))(
            params, cache, toks)).count("pallas_call")

    def kernels_in_grad():
        return str(jax.make_jaxpr(lambda p: jax.grad(
            lambda q: lm.loss_fn(q, cfg, {"tokens": toks}))(p))(
            params)).count("pallas_call")

    assert not lm.kernels_here()            # the tests run on the CPU
    assert kernels_in_step() == 0
    monkeypatch.setattr(lm, "kernels_here", lambda: True)
    assert kernels_in_step() > 0
    assert kernels_in_grad() == 0
