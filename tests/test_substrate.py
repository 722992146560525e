"""Substrate tests: optimizers, data pipeline, checkpointing, compression,
elastic replanning, FT restart."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import latest_step, restore_checkpoint, save_checkpoint
from repro.data import ShardedLoader, SyntheticTokens
from repro.distributed.collectives import (compress_grads, decompress_grads,
                                           init_error_buf)
from repro.optim import (adafactor_init, adafactor_update, adamw_init,
                         adamw_update, clip_by_global_norm, cosine_schedule,
                         zero1_specs)

#: the checkout root, wherever it was copied to
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quad_problem():
    key = jax.random.PRNGKey(0)
    target = jax.random.normal(key, (8, 8))
    params = {"w": jnp.zeros((8, 8)), "b": jnp.zeros((8,))}

    def loss(p):
        return jnp.sum((p["w"] + p["b"][None, :] - target) ** 2)
    return params, loss, target


@pytest.mark.parametrize("opt", ["adamw", "adafactor"])
def test_optimizers_descend(opt):
    params, loss, _ = _quad_problem()
    init, update = ((adamw_init, adamw_update) if opt == "adamw"
                    else (adafactor_init, adafactor_update))
    state = init(params)
    l0 = float(loss(params))
    for _ in range(150):
        g = jax.grad(loss)(params)
        params, state = update(params, g, state, lr=5e-2)
    assert float(loss(params)) < 0.05 * l0


def test_clip_and_schedule():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(20.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-3)
    lrs = [float(cosine_schedule(s, peak=1.0, warmup=10, total=100))
           for s in (0, 10, 100)]
    assert lrs[0] == 0.0 and lrs[1] == pytest.approx(1.0) \
        and lrs[2] == pytest.approx(0.1, rel=1e-2)


def test_grad_compression_error_feedback():
    key = jax.random.PRNGKey(1)
    g = {"w": jax.random.normal(key, (64, 64))}
    err = init_error_buf(g)
    acc_true = jnp.zeros((64, 64))
    acc_q = jnp.zeros((64, 64))
    for i in range(20):
        gi = {"w": jax.random.normal(jax.random.fold_in(key, i), (64, 64))}
        acc_true = acc_true + gi["w"]
        q, s, err = compress_grads(gi, err)
        acc_q = acc_q + decompress_grads(q, s)["w"]
    # error feedback keeps the ACCUMULATED estimate unbiased & tight
    rel = float(jnp.abs(acc_q - acc_true).max() /
                jnp.abs(acc_true).max())
    assert rel < 0.05


def test_zero1_specs_divisibility():
    from jax.sharding import PartitionSpec as P
    specs = {"w": P(None, "model"), "g": P(None, None)}
    structs = {"w": jax.ShapeDtypeStruct((36, 64), jnp.float32),
               "g": jax.ShapeDtypeStruct((32, 7), jnp.float32)}
    z = zero1_specs(specs, structs, data_axes=("data",), data_size=16)
    assert tuple(z["w"]) == (None, "model")        # 36 not divisible: skip
    assert tuple(z["g"])[0] in ("data", ("data",))  # 32 divisible


def test_data_pipeline_learnable_and_sharded():
    src = SyntheticTokens(vocab=512, seed=0)
    b0 = src.batch(0, shard=0, batch=4, seq=32)
    b1 = src.batch(0, shard=1, batch=4, seq=32)
    assert b0.shape == (4, 33) and b0.dtype == np.int32
    assert not np.array_equal(b0, b1)             # shards differ
    assert np.array_equal(b0, src.batch(0, 0, 4, 32))   # deterministic
    half = 33 // 2
    assert np.array_equal(b0[:, half:2 * half], b0[:, :half])  # structure
    loader = ShardedLoader(src, shard=0, batch=4, seq=32)
    a, b = next(loader), next(loader)
    assert a.shape == (4, 33)
    loader.close()


def test_checkpoint_roundtrip(tmp_path):
    tree = {"params": {"w": jnp.arange(12.0).reshape(3, 4)},
            "opt": {"m": jnp.ones((3, 4)), "step": jnp.asarray(7)}}
    save_checkpoint(str(tmp_path), 5, tree)
    save_checkpoint(str(tmp_path), 9, tree)
    assert latest_step(str(tmp_path)) == 9
    back = restore_checkpoint(str(tmp_path), 9, tree)
    np.testing.assert_array_equal(back["params"]["w"], tree["params"]["w"])
    assert int(back["opt"]["step"]) == 7


def test_compile_cache_dir_from_env(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the entry-point helper sets no
    other directory and compiled programs land there."""
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro import compile_cache\n"
        "print(compile_cache.configure())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64)))"
        ".block_until_ready()\n")
    cache = tmp_path / "jax-cache"
    env = dict(os.environ, PYTHONPATH="src",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(cache)]
    assert any(cache.iterdir())


def test_compile_cache_default_dir(monkeypatch):
    """Without it: one fixed, git-ignored directory inside the checkout."""
    from repro import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.configure()
        assert path == compile_cache.configure()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert path == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_train_restart_after_failure(tmp_path):
    """FT driver: crash at step 30, restart resumes from the checkpoint."""
    env = dict(os.environ, PYTHONPATH="src")
    base = [sys.executable, "-m", "repro.launch.train", "--arch",
            "granite-8b", "--reduced", "--steps", "60", "--batch", "2",
            "--seq", "32", "--ckpt-dir", str(tmp_path), "--ckpt-every",
            "20", "--log-every", "100"]
    r = subprocess.run(base + ["--fail-at", "30"], env=env, cwd=ROOT,
                       capture_output=True, text=True)
    assert r.returncode == 42, r.stderr[-2000:]
    assert latest_step(str(tmp_path)) == 20
    r = subprocess.run(base, env=env, cwd=ROOT,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "restoring from step 20" in r.stdout
    assert latest_step(str(tmp_path)) == 60


def test_elastic_replan_on_failure():
    """Losing a slot re-floorplans onto the survivors."""
    from repro import configs
    from repro.distributed.elastic import ClusterState, replan
    cfg = configs.get("granite-8b")
    healthy = replan(cfg, "train_4k",
                     ClusterState(pods=2, data=16, model=16))
    degraded = replan(cfg, "train_4k",
                      ClusterState(pods=2, data=16, model=16,
                                   failed_slots=frozenset({(1, 3)})))
    assert healthy.n_stages >= 1
    assert (1, 3) not in degraded.stage_slots
    assert degraded.n_stages >= 1


def test_straggler_derate():
    from repro import configs
    from repro.distributed.elastic import ClusterState, replan
    cfg = configs.get("granite-8b")
    slow = replan(cfg, "train_4k",
                  ClusterState(pods=1, data=16, model=16,
                               derate={(0, 0): 0.4}))
    # the derated slot must not carry a full compute stage
    if (0, 0) in slow.stage_slots:
        # acceptable only if stages shrank around it
        assert slow.n_stages >= 1
    assert slow.n_stages >= 1
