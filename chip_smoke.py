"""Bring-up check on the TPU: the main path of both halves of the system,
through the package's own entry functions, in one process.

    python chip_smoke.py             # one chip: device, kernels, search, serve
    python chip_smoke.py --chips 4   # four chips: baseline vs tapa train steps

Phases (one chip):

* device  — platform, device kind and count, the compile-cache directory.
* kernels — every Pallas kernel of the model path on the chip, at the
            published widths of a config the repository has, against its
            jnp reference run by XLA at full float32 precision.
* search  — the fast subset of ``benchmarks/fmax_suite.py`` with the
            jitted simulator sweep on the chip, then with the NumPy oracle:
            rows must be identical and no sweep may fall back.  Then a
            converged search with ``jobs=2`` (a worker pool forked from a
            process that holds the chip) against ``jobs=1``: identical rows.
* serve   — ``granite-moe-3b-a800m`` at its full published config, through
            ``repro.launch.serve.generate``: a batch of 4 prompts of 512
            tokens, then 16 greedy decode steps; finite logits of the
            expected shape.  Then the same path in float32 at full width,
            cut to 8 layers: the last-position logits of the prefill and of
            every decode step against ``lm.forward`` over the same tokens
            (XLA ops, same chip).  In bf16 a random-weight MoE is chaotic
            (one bf16 rounding flips near-tied top-8 routing choices, and
            the flips cascade through the layers), so the bf16 divergence
            from ``lm.forward`` is printed beside the divergence a one-ulp
            change of one prompt embedding causes, not gated.

``--chips 4`` runs only the path that exists across chips: the GSPMD
``baseline`` and the floorplanned ``tapa`` train steps of
``granite-moe-3b-a800m`` at published widths, cut to 8 of its 32 layers,
from one seeded init, on a 2x2 mesh; their losses must agree.

Any failed check raises, and the script exits nonzero without a result line.
The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Longer output goes to ``chiprun_out/smoke/``.  Times printed here are
one-off bring-up readings, not benchmark figures.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "smoke"
ARCH = "granite-moe-3b-a800m"
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, want) -> float:
    """max |got - want| over max |want|, in float32."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.maximum(jnp.abs(want).max(),
                                                         1e-30))


def check(name: str, err: float, tol: float, why: str) -> None:
    log(f"  {name}: rel err {err:.3e} (tol {tol:g}: {why})")
    if not err <= tol:
        raise AssertionError(f"{name}: rel err {err:.3e} > {tol:g}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(cache_dir: str):
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: jax found {dev}; stopping")
    log(f"[device] {dev} compile cache: {cache_dir}")
    return dev


def phase_kernels():
    """Each kernel at published widths vs its reference; tolerances are
    relative to the output's largest magnitude."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.kernels import ref
    from repro.kernels.burst_gather import burst_gather
    from repro.kernels.flash_attention import decode_attention, flash_attention
    from repro.kernels.mamba2_scan import mamba2_scan
    from repro.kernels.moe_gmm import moe_gmm
    from repro.kernels.rwkv6_scan import rwkv6_scan

    log("[kernels]")
    c = configs.get(ARCH)
    T = 2048
    bf, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 32))

    def rnd(shape, dtype=bf):
        return jax.random.normal(next(keys), shape, f32).astype(dtype)

    def reference(fn, *args, **kw):
        with jax.default_matmul_precision("highest"):
            return jax.jit(functools.partial(fn, **kw))(*args)

    bf16_out = ("bf16 output rounding (2^-9) plus float32 sums in another "
                "order; a kernel accumulating in bf16 over 2,048 terms "
                "would miss it")

    # attention: 24 query / 8 KV heads of 64, 2k tokens (granite-moe)
    q = rnd((1, T, c.n_heads, c.head_dim))
    k = rnd((1, T, c.n_kv_heads, c.head_dim))
    v = rnd((1, T, c.n_kv_heads, c.head_dim))
    got = jax.jit(flash_attention)(q, k, v)
    check("flash_attention", rel_err(got, reference(ref.attention_ref, q, k,
                                                    v, causal=True)),
          2e-2, bf16_out)
    qd = rnd((4, 1, c.n_heads, c.head_dim))
    kd = rnd((4, T, c.n_kv_heads, c.head_dim))
    vd = rnd((4, T, c.n_kv_heads, c.head_dim))
    klen = jnp.array([T, 1500, 700, 1], jnp.int32)
    got = jax.jit(decode_attention)(qd, kd, vd, q_offset=klen - 1,
                                    kv_len=klen)
    check("decode_attention", rel_err(got, reference(
        ref.attention_ref, qd, kd, vd, causal=False, kv_len=klen)),
        2e-2, bf16_out)

    # rwkv6-1.6b: 32 heads x 64; decays drawn like the model's
    # (w = exp(-exp(w_base + small)), w_base = -6)
    H, D = 32, 64
    r, kk, vv = (rnd((1, T, H, D)) for _ in range(3))
    w = jnp.exp(-jnp.exp(-6.0 + 0.5 * rnd((1, T, H, D), f32)))
    u = 0.3 * rnd((H, D), f32)
    s0 = rnd((1, H, D, D), f32)
    y, s = jax.jit(rwkv6_scan)(r, kk, vv, w, u, s0)
    yr, sr = reference(ref.rwkv6_scan_ref, r, kk, vv, w, u, s0)
    scan_why = ("chunked dual form vs the sequential recurrence: bf16 "
                "output, float32 chunk matmuls, 32 chunks")
    check("rwkv6_scan.y", rel_err(y, yr), 2e-2, scan_why)
    check("rwkv6_scan.state", rel_err(s, sr), 2e-2, scan_why)

    # zamba2-7b's mamba2: 112 heads x 64, state 64
    z = configs.get("zamba2-7b")
    P, N = z.ssm_head_dim, z.ssm_state
    H = z.ssm_expand * z.d_model // P
    x = rnd((1, T, H, P))
    dt = jax.nn.softplus(rnd((1, T, H), f32))
    A = -jnp.exp(jnp.log(jnp.linspace(1.0, 16.0, H)))
    Bm, Cm = rnd((1, T, N)), rnd((1, T, N))
    h0 = rnd((1, H, P, N), f32)
    y, h = jax.jit(mamba2_scan)(x, dt, A, Bm, Cm, h0)
    yr, hr = reference(ref.mamba2_scan_ref, x, dt, A, Bm, Cm, h0)
    check("mamba2_scan.y", rel_err(y, yr), 2e-2, scan_why)
    check("mamba2_scan.state", rel_err(h, hr), 2e-2, scan_why)

    # the embedding gather over granite-moe's (49,408 x 1,536) table:
    # random rows (per-row DMAs) and one aligned run (the burst DMA)
    table = rnd((c.vocab_padded, c.d_model))
    idx = jax.random.randint(next(keys), (T,), 0, c.vocab, jnp.int32)
    idx = idx.at[64:128].set(jnp.arange(4096, 4160, dtype=jnp.int32))
    got = jax.jit(burst_gather)(table, idx)
    check("burst_gather", rel_err(got, ref.burst_gather_ref(table, idx)),
          0.0, "a copy: exact")

    # granite-moe's experts: 40 x (1,536 -> 512), tokens sorted by expert
    xg = rnd((T, c.d_model))
    wg = 0.03 * rnd((c.n_experts, c.d_model, c.moe_d_ff), f32)
    wg = wg.astype(bf)
    gid = jnp.sort(jax.random.randint(next(keys), (T,), 0, c.n_experts))
    got = jax.jit(moe_gmm)(xg, wg, gid)
    check("moe_gmm", rel_err(got, reference(ref.moe_gmm_ref, xg, wg, gid)),
          1e-2, "one bf16 rounding of the output; float32 accumulation")


def phase_search():
    import fmax_suite
    from check_regression import check_jax_backend, check_parallel_frontier

    log("[search]")
    OUT.mkdir(parents=True, exist_ok=True)
    runs = {}
    for backend in ("jax", "numpy"):
        path = OUT / f"fmax_{backend}.json"
        t0 = time.perf_counter()
        fmax_suite.main(verbose=False, subset=fmax_suite.FAST_SUBSET,
                        json_path=str(path), backend=backend)
        runs[backend] = json.loads(path.read_text())
        sim = runs[backend]["sim"]
        log(f"  fast subset, backend={backend}: {len(runs[backend]['rows'])} "
            f"rows, counts={sim['counts']} sweep_wall={sim['wall_s']:.3f}s "
            f"suite_wall={time.perf_counter() - t0:.1f}s")
    counts = runs["jax"]["sim"]["counts"]
    if counts["jax"] < 1 or counts["fallback"] != 0:
        raise AssertionError(f"jax sweep did not run cleanly: {counts}")
    errors = check_jax_backend(runs["jax"], runs["numpy"])
    if errors:
        raise AssertionError("jax rows differ from the NumPy oracle:\n"
                             + "\n".join(errors))
    log("  jax rows == numpy rows; jit cache "
        f"{runs['jax']['sim']['jit_cache']}")

    # the worker pool forks from this process, which now holds the chip
    subset = ("stencil_x4", "bucket_sort")
    conv = {}
    for jobs in (1, 2):
        path = OUT / f"fmax_converged_jobs{jobs}.json"
        t0 = time.perf_counter()
        fmax_suite.main_converged(verbose=False, subset=subset,
                                  json_path=str(path), jobs=jobs,
                                  backend="jax")
        conv[jobs] = json.loads(path.read_text())
        log(f"  converged {subset} jobs={jobs}: "
            f"pool={ {k: conv[jobs]['sim']['pool'][k] for k in ('dispatched', 'merged', 'worker_solves', 'timed_out', 'pool_rebuilds')} } "
            f"wall={time.perf_counter() - t0:.1f}s")
    errors = check_parallel_frontier(conv[2], conv[1])
    if errors:
        raise AssertionError("jobs=2 rows differ from jobs=1:\n"
                             + "\n".join(errors))
    log("  jobs=2 rows == jobs=1 rows")


def phase_serve():
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.launch import serve
    from repro.model import lm

    log("[serve]")
    cfg = configs.get(ARCH)
    B, S, GEN = 4, 512, 16
    t0 = time.perf_counter()
    params = serve.init_params(cfg, SEED)
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"  {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, {cfg.n_experts} experts "
        f"top-{cfg.top_k}, vocab {cfg.vocab}: {n / 1e9:.2f} B params "
        f"(init {time.perf_counter() - t0:.1f}s); kernels: "
        f"{lm.kernels_here()}")
    prompts = jax.random.randint(jax.random.PRNGKey(SEED + 1), (B, S), 0,
                                 cfg.vocab, jnp.int32)
    out = serve.generate(params, cfg, prompts, gen=GEN)
    log(f"  compile {out.compile_s:.1f}s, prefill {B}x{S} "
        f"{out.prefill_s * 1e3:.1f} ms, decode {GEN} steps "
        f"{out.decode_s * 1e3:.1f} ms ({out.decode_s / GEN * 1e3:.2f} "
        f"ms/step) [one-off bring-up reading]")
    logits = jnp.stack(out.logits)                     # (GEN+1, B, Vp)
    if logits.shape != (GEN + 1, B, cfg.vocab_padded) or \
            not bool(jnp.isfinite(logits[..., :cfg.vocab]).all()):
        raise AssertionError(f"serve logits {logits.shape} not finite")
    if not bool((out.tokens == jnp.argmax(logits[:GEN], -1).T).all()):
        raise AssertionError("greedy tokens are not the logits' argmax")

    # bf16: divergence from the forward pass, beside the chaos floor
    seq = jnp.concatenate([prompts, out.tokens], axis=1)
    fwd = jax.jit(lambda p, t: lm.forward(p, cfg, t)[0])
    want = fwd(params, seq)[:, S - 1:]
    row = params["embed"][seq[0, 0]].astype(jnp.float32)
    bumped = dict(params, embed=params["embed"].at[seq[0, 0]].set(
        (row * (1 + 2 ** -7)).astype(params["embed"].dtype)))  # +1 ulp
    floor = _rel_l2(fwd(bumped, seq)[:, S - 1:], want)
    got = _rel_l2(logits[..., :cfg.vocab].transpose(1, 0, 2), want)
    log(f"  bf16, 32 layers: serve vs lm.forward rel L2 per step "
        f"{[round(e, 3) for e in got]}; lm.forward after a one-ulp change "
        f"of prompt 0's first embedding {[round(e, 3) for e in floor]} "
        f"(not gated: routing chaos)")
    del params, bumped, out

    # float32 at full width, 8 layers: one routing in both paths
    cfg8 = dataclasses.replace(cfg, n_layers=8)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                       serve.init_params(cfg8, SEED))
    B, S, GEN = 2, 256, 8
    prompts = prompts[:B, :S]
    with jax.default_matmul_precision("highest"):
        out = serve.generate(p32, cfg8, prompts, gen=GEN)
        seq = jnp.concatenate([prompts, out.tokens], axis=1)
        want = jax.jit(lambda p, t: lm.forward(p, cfg8, t)[0])(p32, seq)
    got = jnp.stack(out.logits)[..., :cfg.vocab].transpose(1, 0, 2)
    errs = _rel_l2(got, want[:, S - 1:])
    log(f"  float32, 8 layers: serve vs lm.forward rel L2 per step "
        f"{[float(f'{e:.2e}') for e in errs]}")
    check("serve logits vs lm.forward, float32 (worst step)", max(errs),
          1e-3, "float32 throughout: the paths differ in summation order "
          "(~1e-6), too little to flip a top-8 routing choice; any bf16 "
          "rounding (2^-9) on either path flips some and fails it")


def _rel_l2(got, want) -> list[float]:
    """Per step: ||got - want|| / ||want|| over (batch, vocab); both are
    (B, steps, V)."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    err = (jnp.linalg.norm(got - want, axis=(0, 2))
           / jnp.linalg.norm(want, axis=(0, 2)))
    return [float(e) for e in err]


def phase_train4():
    """Baseline GSPMD vs floorplanned tapa train steps on a 2x2 mesh."""
    import jax
    import numpy as np
    from repro import configs
    from repro.distributed import pipeline as pp
    from repro.distributed.taskgraph import ShapeCell
    from repro.launch import serve, steps
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw_init

    log("[train x4]")
    devs = jax.devices()
    if len(devs) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devs)}")
    cfg = dataclasses.replace(configs.get(ARCH), n_layers=8)
    cell = ShapeCell("smoke_train", seq_len=512, global_batch=16,
                     kind="train")
    n_micro, n_steps = 4, 3
    mesh = make_mesh((2, 2), ("data", "model"), devices=devs)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(SEED + 1), (n_steps, cell.global_batch,
                                       cell.seq_len + 1), 0, cfg.vocab))

    def run(step, ins, outs, layout, batches):
        """A few steps from the seeded init; ``layout`` maps the init to
        the step's parameter tree.  Parameters and optimizer state are
        donated, so each run makes its own."""
        fn = jax.jit(step, in_shardings=ins, out_shardings=outs,
                     donate_argnums=(0, 1))
        p = jax.device_put(layout(serve.init_params(cfg, SEED)), ins[0])
        o = jax.jit(adamw_init, out_shardings=ins[1])(p)
        t0 = time.perf_counter()
        fn = fn.lower(p, o, batches[0]).compile()
        compile_s = time.perf_counter() - t0
        losses = []
        t0 = time.perf_counter()
        for b in batches:
            p, o, m = fn(p, o, b)
            losses.append(float(m["loss"]))
        return losses, compile_s, time.perf_counter() - t0

    with mesh:
        step, _, ins, outs = steps.build_baseline_train(
            cfg, mesh, cell, n_micro=n_micro)
        batches = [jax.device_put({"tokens": t}, ins[2]) for t in tokens]
        base, bc, bs = run(step, ins, outs, lambda p: p, batches)
    log(f"  baseline: losses {base} (compile {bc:.1f}s, {n_steps} steps "
        f"{bs:.2f}s)")

    step, _, ins, outs, plan = steps.build_tapa_train(
        cfg, mesh, cell, n_micro=n_micro)
    log(f"  tapa plan: {plan.n_stages} stages at {plan.stage_slots}, "
        f"boundary depths {plan.boundary_depth}, tp {plan.tp}")
    rmesh = ins[0]["embed"].mesh
    with rmesh:
        mb = cell.global_batch // n_micro
        batches = [jax.device_put(
            {"tokens": t.reshape(n_micro, mb, -1)}, ins[2]) for t in tokens]
        tapa, tc, ts = run(
            step, ins, outs,
            lambda p: pp.to_pipeline_params(p, plan.n_stages), batches)
    log(f"  tapa:     losses {tapa} (compile {tc:.1f}s, {n_steps} steps "
        f"{ts:.2f}s)")
    why = ("same init, data and math; the sharding changes bf16 partial-sum "
           "order, and MoE routing flips near-tied experts for a few tokens")
    check("step-0 loss, tapa vs baseline", abs(tapa[0] - base[0]) / base[0],
          2e-3, why)
    check("all steps, tapa vs baseline",
          max(abs(a - b) / b for a, b in zip(tapa, base)), 1e-2,
          why + "; Adam steps amplify tiny gradient differences")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT / "benchmarks"):
        sys.path.insert(0, str(p))
    from repro import compile_cache
    cache_dir = compile_cache.configure()
    t0 = time.perf_counter()
    dev = phase_device(cache_dir)
    if args.chips == 4:
        phase_train4()
    else:
        for phase in (phase_kernels, phase_search, phase_serve):
            t = time.perf_counter()
            phase()
            log(f"  ({phase.__name__} took {time.perf_counter() - t:.1f}s)")
    log(f"[done] {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
