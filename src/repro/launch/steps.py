"""Step builders shared by dryrun/train/serve: given (arch, shape cell,
mesh, mode) produce the jitted step function, ShapeDtypeStruct input specs
and shardings — no device allocation."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed import baseline as bl
from repro.distributed import pipeline as pp
from repro.distributed.sharding import TpuPlan, plan_cell, refined_mesh
from repro.distributed.taskgraph import ShapeCell
from repro.model import lm
from repro.model.layers import PDTYPE
from repro.optim import (adafactor_init, adafactor_update, adamw_init,
                         adamw_update, clip_by_global_norm, zero1_specs)

N_MICRO = 8


def n_micro_for(cfg: ArchConfig) -> int:
    """Deeper microbatching for big models: activation footprint scales
    1/n_micro (the 16 GB/chip budget is the binding constraint)."""
    n = cfg.param_count()
    if n >= 100e9:
        return 32
    if n >= 20e9:
        return 16
    return N_MICRO


def input_specs(cfg: ArchConfig, cell: ShapeCell, *, mode: str = "baseline",
                n_micro: int = N_MICRO) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (weak-type-correct,
    shardable, no allocation)."""
    B, S = cell.global_batch, cell.seq_len
    if cell.kind == "train":
        if mode == "tapa":
            mb = max(B // n_micro, 1)
            toks = jax.ShapeDtypeStruct((n_micro, mb, S + 1), jnp.int32)
        else:
            toks = jax.ShapeDtypeStruct((B, S + 1), jnp.int32)
    elif cell.kind == "prefill":
        toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    else:  # decode: one new token against a seq_len-deep cache
        toks = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    batch = {"tokens": toks}
    if cfg.family == "vlm":
        batch["extra"] = {"vision": jax.ShapeDtypeStruct(
            (B if mode != "tapa" or cell.kind != "train" else toks.shape[1],
             cfg.frontend_tokens, cfg.frontend_dim), PDTYPE)}
    if cfg.family == "audio":
        batch["extra"] = {"frames": jax.ShapeDtypeStruct(
            (B if mode != "tapa" or cell.kind != "train" else toks.shape[1],
             cfg.frontend_tokens, cfg.frontend_dim), PDTYPE)}
    return batch


def param_structs(cfg: ArchConfig):
    return jax.eval_shape(functools.partial(lm.init_params, cfg),
                          jax.random.PRNGKey(0))


def _opt_fns(cfg: ArchConfig):
    if cfg.optimizer == "adafactor":
        return adafactor_init, adafactor_update
    return adamw_init, adamw_update


# ---------------------------------------------------------------------------
# baseline GSPMD train / serve
# ---------------------------------------------------------------------------

def build_baseline_train(cfg: ArchConfig, mesh: Mesh, cell: ShapeCell, *,
                         unroll: bool = False, n_micro: int | None = None):
    n_micro = n_micro or n_micro_for(cfg)
    opt_init, opt_update = _opt_fns(cfg)
    loss_fn = bl.build_loss(cfg, remat=True, unroll=unroll)

    p_structs0 = param_structs(cfg)
    specs0 = pp.param_specs(cfg, p_structs0, tp_axis="model",
                            tp_size=mesh.shape["model"])
    daxes0 = bl.data_axes(mesh)
    dsize0 = 1
    for a in daxes0:
        dsize0 *= mesh.shape[a]
    zspecs_c = zero1_specs(specs0, p_structs0, data_axes=daxes0,
                           data_size=dsize0)

    def train_step(params, opt_state, batch):
        # gradient accumulation over n_micro microbatches: global batch
        # activations never materialize at once (16 GB/chip budget)
        toks = batch["tokens"]
        B = toks.shape[0]
        mb = max(B // n_micro, 1)
        toks = toks[:mb * n_micro].reshape(n_micro, mb, -1)
        extra = batch.get("extra")
        if extra is not None:
            extra = jax.tree.map(
                lambda t: t[:mb * n_micro].reshape(
                    (n_micro, mb) + t.shape[1:]), extra)

        def mb_step(carry, xs):
            loss_a, grads_a = carry
            b = {"tokens": xs[0]}
            if extra is not None:
                b["extra"] = xs[1]
            loss, grads = jax.value_and_grad(loss_fn)(params, b)
            grads_a = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grads_a, grads)
            return (loss_a + loss, grads_a), None

        # ZeRO-2-style: the fp32 grad accumulator is replicated across the
        # data axes by construction (grads are post-allreduce), so shard it
        # there — 27B+ models cannot afford a replicated fp32 accumulator
        zero_g = jax.tree.map(
            lambda p, sp: jax.lax.with_sharding_constraint(
                jnp.zeros(p.shape, jnp.float32), NamedSharding(mesh, sp)),
            params, zspecs_c)
        xs = (toks, extra) if extra is not None else (toks, toks)
        (loss, grads), _ = jax.lax.scan(
            mb_step, (jnp.zeros((), jnp.float32), zero_g), xs,
            unroll=n_micro if unroll else 1)
        loss = loss / n_micro
        grads = jax.tree.map(lambda g: (g / n_micro).astype(jnp.float32),
                             grads)
        grads, gn = clip_by_global_norm(grads, 1.0)
        params, opt_state = opt_update(params, grads, opt_state, lr=3e-4)
        return params, opt_state, {"loss": loss, "grad_norm": gn}

    p_structs = param_structs(cfg)
    o_structs = jax.eval_shape(opt_init, p_structs)
    specs = pp.param_specs(cfg, p_structs, tp_axis="model",
                           tp_size=mesh.shape["model"])
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
    # optimizer state follows param specs + ZeRO-1 over data axes
    daxes = bl.data_axes(mesh)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    zspecs = zero1_specs(specs, p_structs, data_axes=daxes, data_size=dsize)
    zspecs_c = zspecs   # used by the grad accumulator inside train_step
    oshard = {
        k: (jax.tree.map(lambda s: NamedSharding(mesh, s), v,
                         is_leaf=lambda x: isinstance(x, P))
            if k != "step" else NamedSharding(mesh, P()))
        for k, v in _opt_spec_tree(o_structs, zspecs).items()}
    bshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          _batch_specs(cfg, cell, mesh, mode="baseline"),
                          is_leaf=lambda x: isinstance(x, P))
    in_shardings = (pshard, oshard, bshard)
    out_shardings = (pshard, oshard,
                     NamedSharding(mesh, P()))
    args = (p_structs, o_structs,
            input_specs(cfg, cell, mode="baseline"))
    return train_step, args, in_shardings, out_shardings


def _opt_spec_tree(o_structs, param_zspecs):
    """Optimizer-state spec tree mirroring its structure."""
    out = {}
    for k, v in o_structs.items():
        if k == "step":
            out[k] = P()
        else:
            # v mirrors params (adamw m/v) or nested dicts (adafactor)
            out[k] = _mirror_specs(v, param_zspecs)
    return out


def _mirror_specs(tree, pspecs):
    if isinstance(tree, dict) and not isinstance(pspecs, dict):
        # adafactor factored leaves {vr, vc} / {v} under a param leaf
        out = {}
        for k, v in tree.items():
            if k == "v":
                out[k] = pspecs
            else:  # vr / vc: drop one trailing dim of the param spec
                parts = tuple(pspecs)
                out[k] = P(*parts[:v.ndim]) if len(parts) >= v.ndim else \
                    P(*(parts + (None,) * (v.ndim - len(parts))))
        return out
    if isinstance(tree, dict):
        return {k: _mirror_specs(v, pspecs[k]) for k, v in tree.items()}
    if isinstance(tree, list):
        return [_mirror_specs(v, pspecs[i]) for i, v in enumerate(tree)]
    return pspecs


def _batch_specs(cfg: ArchConfig, cell: ShapeCell, mesh: Mesh, *, mode: str):
    daxes = bl.data_axes(mesh) if mode == "baseline" else ("data",)
    if mode == "tapa" and cell.kind == "train":
        toks = P(None, daxes, None)
    else:
        toks = P(daxes, None)
    out = {"tokens": toks}
    if cfg.family in ("vlm", "audio"):
        key = "vision" if cfg.family == "vlm" else "frames"
        out["extra"] = {key: P(daxes, None, None)}
    return out


def build_baseline_serve(cfg: ArchConfig, mesh: Mesh, cell: ShapeCell, *,
                         unroll: bool = False):
    p_structs = param_structs(cfg)
    specs = pp.param_specs(cfg, p_structs, tp_axis="model",
                           tp_size=mesh.shape["model"])
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
    B = cell.global_batch
    extra = None
    if cfg.family in ("vlm", "audio"):
        key = "vision" if cfg.family == "vlm" else "frames"
        extra = {key: jax.ShapeDtypeStruct(
            (B, cfg.frontend_tokens, cfg.frontend_dim), PDTYPE)}
    cache_structs = jax.eval_shape(
        lambda p, e: lm.init_cache(p, cfg, B, max_seq=cell.seq_len,
                                   extra=e), p_structs, extra)
    cshard = bl.cache_shardings(cfg, cache_structs, mesh)
    toks = input_specs(cfg, cell)["tokens"]
    daxes = bl.data_axes(mesh)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    bspec = daxes if B % dsize == 0 else None
    tshard = NamedSharding(mesh, P(bspec, None))
    logit_shard = NamedSharding(mesh, P(bspec, "model"))

    def serve_step(params, cache, tokens):
        return lm.step(params, cfg, cache, tokens, unroll=unroll)

    args = (p_structs, cache_structs, toks)
    in_shardings = (pshard, cshard, tshard)
    out_shardings = (logit_shard, cshard)
    return serve_step, args, in_shardings, out_shardings


# ---------------------------------------------------------------------------
# TAPA floorplanned pipeline train
# ---------------------------------------------------------------------------

def build_tapa_train(cfg: ArchConfig, mesh: Mesh, cell: ShapeCell, *,
                     plan: TpuPlan | None = None, n_micro: int | None = None,
                     seed: int = 0, unroll: bool = False):
    n_micro = n_micro or n_micro_for(cfg)
    mesh_shape = tuple(mesh.devices.shape)
    if plan is None:
        plan = plan_cell(cfg, cell, mesh_shape, seed=seed, mode="tapa")
    rmesh = refined_mesh(mesh, plan)
    opt_init, opt_update = _opt_fns(cfg)
    loss_fn = pp.build_train_loss(cfg, plan, rmesh, n_micro=n_micro,
                                  unroll=unroll)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, gn = clip_by_global_norm(grads, 1.0)
        params, opt_state = opt_update(params, grads, opt_state, lr=3e-4)
        return params, opt_state, {"loss": loss, "grad_norm": gn}

    p_structs = jax.eval_shape(
        lambda k: pp.to_pipeline_params(lm.init_params(cfg, k),
                                        plan.n_stages),
        jax.random.PRNGKey(0))
    o_structs = jax.eval_shape(opt_init, p_structs)
    specs = pp.param_specs(cfg, p_structs, tp_axis="tp",
                           tp_size=rmesh.shape["tp"],
                           stage_axis="stage")
    pshard = jax.tree.map(lambda s: NamedSharding(rmesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
    zspecs = zero1_specs(specs, p_structs, data_axes=("data",),
                         data_size=rmesh.shape["data"])
    oshard = {
        k: (jax.tree.map(lambda s: NamedSharding(rmesh, s),
                         _mirror_specs(v, zspecs),
                         is_leaf=lambda x: isinstance(x, P))
            if k != "step" else NamedSharding(rmesh, P()))
        for k, v in o_structs.items()}
    mb_sz = max(cell.global_batch // n_micro, 1)
    bspecs = _batch_specs(cfg, cell, rmesh, mode="tapa")
    if mb_sz % rmesh.shape["data"] != 0:   # small microbatches: replicate
        bspecs = jax.tree.map(
            lambda sp: P(*[None] * len(tuple(sp))), bspecs,
            is_leaf=lambda x: isinstance(x, P))
    bshard = jax.tree.map(lambda s: NamedSharding(rmesh, s), bspecs,
                          is_leaf=lambda x: isinstance(x, P))
    args = (p_structs, o_structs,
            input_specs(cfg, cell, mode="tapa", n_micro=n_micro))
    in_shardings = (pshard, oshard, bshard)
    out_shardings = (pshard, oshard, NamedSharding(rmesh, P()))
    return train_step, args, in_shardings, out_shardings, plan
