"""Dry-run machinery at test scale: 8 host devices, reduced configs.
(The 512-device production dry-run runs via `python -m repro.launch.dryrun`;
this test proves the same builders lower/compile in-process quickly.)"""
import os
import subprocess
import sys
import textwrap

import pytest

#: the checkout root, wherever it was copied to
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro import configs
    from repro.distributed.taskgraph import ShapeCell
    from repro.launch import steps as S
    from repro.launch.hlo_analysis import collective_summary

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cell = ShapeCell("train_tiny", seq_len=32, global_batch=8, kind="train")
    ok = []
    for arch in ("granite-8b", "granite-moe-3b-a800m", "zamba2-7b",
                 "rwkv6-1.6b", "whisper-tiny"):
        cfg = configs.get_reduced(arch)
        with mesh:
            step, args, ins, outs = S.build_baseline_train(cfg, mesh, cell,
                                                           n_micro=2)
            c = jax.jit(step, in_shardings=ins,
                        out_shardings=outs).lower(*args).compile()
        ca = c.cost_analysis()
        if isinstance(ca, (list, tuple)):   # jax 0.4.x returns [dict]
            ca = ca[0] if ca else {}
        assert ca.get("flops", 0) > 0
        coll = collective_summary(c.as_text(), pod_size=4)
        assert coll["count"] > 0, arch
        ok.append(arch)
        jax.clear_caches()
    # serve path
    cell_d = ShapeCell("decode_tiny", seq_len=64, global_batch=8,
                       kind="decode")
    cfg = configs.get_reduced("gemma3-12b")
    with mesh:
        step, args, ins, outs = S.build_baseline_serve(cfg, mesh, cell_d)
        c = jax.jit(step, in_shardings=ins,
                    out_shardings=outs).lower(*args).compile()
    ok.append("serve")
    # tapa pipeline path on a refined mesh
    from repro.distributed.sharding import TpuPlan, refined_mesh
    cfg = configs.get_reduced("granite-8b")
    plan = TpuPlan(mode="tapa", n_stages=2, groups_per_stage=1,
                   stage_slots=[(0, 0), (0, 1)], boundary_depth=[2], tp=1,
                   crossing_cost=0.0)
    with mesh:
        step, args, ins, outs, _ = S.build_tapa_train(
            cfg, mesh, cell, plan=plan, n_micro=2)
        c = jax.jit(step, in_shardings=ins,
                    out_shardings=outs).lower(*args).compile()
    txt = c.as_text()
    assert "collective-permute" in txt   # the pipeline's stage shifts
    ok.append("tapa")
    print("DRYRUN_SMALL_OK", ok)
""")


@pytest.mark.slow
def test_dryrun_small_8dev():
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=2400)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "DRYRUN_SMALL_OK" in r.stdout
