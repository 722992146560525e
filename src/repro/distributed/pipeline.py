"""Floorplanned pipeline runtime: GPipe-style scan-over-ticks via
shard_map + ppermute, with per-boundary buffer depths from the latency
balancer (the TPU realization of "pipeline every cross-slot stream, then
balance", paper §5).

Mechanics
  * refined mesh (stage, data, tp); only "stage" is a manual axis —
    data/tp sharding stays with GSPMD (the TP all-reduces happen *within*
    a slot, the whole point of the floorplan);
  * stage s holds groups [s*Gs, (s+1)*Gs) as locally-scanned params;
  * one microbatch advances one stage per tick; a boundary with buffer
    depth d contributes d skew ticks (deep cross-pod edges overlap their
    DCN transfer with compute — the register analogue);
  * zamba2's x0 skip stream and the (vlm/audio) memory stream travel with
    the activation through every boundary, with depths equalized by the
    balancer (throughput preservation);
  * the last stage computes chunked CE immediately — full logits are
    never shipped backwards;
  * autodiff through ppermute yields the reverse schedule for backward.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.model import lm
from repro.model.layers import PDTYPE
from .sharding import TpuPlan

def _stage_shard_map(f, mesh, in_specs, out_specs):
    """shard_map with only "stage" manual: data/tp stay with GSPMD."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False, axis_names={"stage"})


# parameter-name -> which matmul dim shards over tp
_COL = ("wq", "wk", "wv", "w_up", "w_gate", "w_in", "wr", "wg", "w_A",
        "w_shared_in")
_ROW = ("wo", "w_down", "w_out", "w_B", "w_shared_out")


def _leaf_spec(path: tuple[str, ...], leaf, *, tp_axis: str, tp_size: int,
               stage_axis: str | None, group_leaf: bool) -> P:
    """PartitionSpec for one parameter leaf from its tree path.

    Group-stacked leaves carry leading stack dims: (G, ...) in baseline
    layout, (S, Gs, ...) in pipeline layout."""
    name = path[-1]
    if group_leaf:
        pre = (stage_axis, None) if stage_axis else (None,)
    else:
        pre = ()
    nd = leaf.ndim - len(pre)
    if name == "embed":
        return P(*pre, tp_axis, None)
    if name == "lm_head":
        return P(*pre, None, tp_axis)
    if name in ("router",):
        return P(*(pre + (None,) * nd))
    # MoE expert stacks: (E, d, f) — expert-parallel over tp when E
    # divides (the HBM channel-binding analogue: experts bound to the
    # slot's chips); otherwise fall back to sharding the FFN dim
    if name in ("w_up", "w_down", "w_gate") and nd == 3:
        E = leaf.shape[len(pre)]
        if E % max(tp_size, 1) == 0:
            return P(*pre, tp_axis, None, None)
        if name == "w_down":                # (E, f, d): shard f
            return P(*pre, None, tp_axis, None)
        return P(*pre, None, None, tp_axis)  # (E, d, f): shard f
    if name in _COL and nd >= 2:
        return P(*(pre + (None,) * (nd - 1) + (tp_axis,)))
    if name in _ROW and nd >= 2:
        return P(*(pre + (tp_axis,) + (None,) * (nd - 1)))
    return P(*(pre + (None,) * nd))


def param_specs(cfg: ArchConfig, params, *, tp_axis: str = "model",
                tp_size: int = 16, stage_axis: str | None = None):
    """Pytree of PartitionSpecs.  With ``stage_axis`` set, 'groups' leaves
    get a leading (stage, group) stack spec; otherwise a (group,) stack."""
    def walk(tree, path, group_leaf):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,), group_leaf or k == "groups")
                    for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v, path, group_leaf) for v in tree]
        return _leaf_spec(path, tree, tp_axis=tp_axis, tp_size=tp_size,
                          stage_axis=stage_axis, group_leaf=group_leaf)
    return walk(params, (), False)


def to_pipeline_params(params: dict, n_stages: int) -> dict:
    """Reshape group-stacked leaves (G, ...) -> (S, G/S, ...)."""
    out = dict(params)
    out["groups"] = jax.tree.map(
        lambda t: t.reshape((n_stages, t.shape[0] // n_stages) + t.shape[1:]),
        params["groups"])
    return out


def from_pipeline_params(params: dict) -> dict:
    out = dict(params)
    out["groups"] = jax.tree.map(
        lambda t: t.reshape((t.shape[0] * t.shape[1],) + t.shape[2:]),
        params["groups"])
    return out


# ---------------------------------------------------------------------------

def build_train_loss(cfg: ArchConfig, plan: TpuPlan, rmesh: Mesh, *,
                     n_micro: int, remat: bool = True,
                     unroll: bool = False):
    """Returns loss_fn(params_pipeline, batch) running the floorplanned
    pipeline.  batch: {"tokens": (n_micro, mb, S+1), optional "extra"}."""
    S_stages = plan.n_stages
    Gs = plan.groups_per_stage
    specs = lm.build_specs(cfg)
    # cumulative skew offsets from the balanced boundary depths
    depths = plan.boundary_depth or [1] * (S_stages - 1)
    total_skew = int(sum(depths))
    perm = [(i, i + 1) for i in range(S_stages - 1)]

    # per-stage entry offsets (cumulative boundary depths)
    offs = [0]
    for d in depths:
        offs.append(offs[-1] + int(d))

    def loss_fn(params, batch):
        tokens = batch["tokens"]              # (n_micro, mb, S+1)
        extra = batch.get("extra") or {}

        def inner(groups_local, rest_local, tokens, extra):
            extra = extra or None
            stage = jax.lax.axis_index("stage")
            gp = jax.tree.map(lambda t: t[0], groups_local)   # (Gs, ...)
            rest = jax.tree.map(lambda t: t[0], rest_local)
            params_local = dict(rest, groups=gp)
            memory = lm._memory(params_local, cfg, extra)
            shared = params_local.get("shared")
            mb, seqp1 = tokens.shape[1], tokens.shape[2]
            seq = seqp1 - 1
            positions = jnp.arange(seq)

            def stage_compute(x, x0):
                def body(carry, g):
                    x, aux = carry
                    x, a, _ = lm.apply_group(
                        g, cfg, specs, x, positions=positions, x0=x0,
                        memory=memory, shared=shared)
                    return (x, aux + a), None
                if remat:
                    body = jax.checkpoint(body)
                (x, aux), _ = jax.lax.scan(
                    body, (x, jnp.zeros((), jnp.float32)), gp,
                    unroll=Gs if unroll else 1)
                return x, aux

            def tick(t, carry):
                buf_x, buf_x0, loss_acc, aux_acc, count = carry
                midx = jnp.clip(t, 0, n_micro - 1)
                toks = tokens[midx][:, :-1]
                x_in0 = lm._embed(params_local, cfg, toks)
                x = jnp.where(stage == 0, x_in0, buf_x[0])
                x0 = jnp.where(stage == 0, x_in0, buf_x0[0])
                x, aux = stage_compute(x, x0)
                # loss on the last stage, for the microbatch that entered
                # total_skew ticks ago
                out_idx = t - total_skew
                tgt_out = tokens[jnp.clip(out_idx, 0, n_micro - 1)][:, 1:]
                is_out = (stage == S_stages - 1) & (out_idx >= 0) & \
                    (out_idx < n_micro)
                ce = lm.chunked_ce(params_local, cfg, x, tgt_out)
                loss_acc = loss_acc + jnp.where(is_out, ce, 0.0)
                # a stage's compute at tick t belongs to microbatch
                # t - offs[stage]; mask fill/drain garbage
                my_off = jnp.asarray(offs, jnp.int32)[
                    jnp.clip(stage, 0, len(offs) - 1)]
                my_mb = t - my_off
                aux_acc = aux_acc + jnp.where(
                    (my_mb >= 0) & (my_mb < n_micro), aux, 0.0)
                count = count + jnp.where(is_out, 1.0, 0.0)
                # advance the boundary FIFOs (depth-1 modeled as the
                # carry slot itself; deeper boundaries shift through
                # their extra slots = skew ticks)
                send_x = jax.lax.ppermute(x, "stage", perm)
                send_x0 = jax.lax.ppermute(x0, "stage", perm)
                buf_x = jnp.concatenate(
                    [buf_x[1:], jnp.zeros_like(buf_x[:1])], 0)
                buf_x0 = jnp.concatenate(
                    [buf_x0[1:], jnp.zeros_like(buf_x0[:1])], 0)
                my_depth = _my_depth(stage, depths)
                buf_x = _push(buf_x, send_x, my_depth)
                buf_x0 = _push(buf_x0, send_x0, my_depth)
                return buf_x, buf_x0, loss_acc, aux_acc, count

            dmax = max(depths) if depths else 1
            buf_x = jnp.zeros((dmax, mb, seq, cfg.d_model), PDTYPE)
            buf_x0 = jnp.zeros_like(buf_x)
            z = jnp.zeros((), jnp.float32)
            carry = (buf_x, buf_x0, z, z, z)
            n_ticks = n_micro + total_skew
            if unroll:
                for t in range(n_ticks):
                    carry = tick(t, carry)
            else:
                carry = jax.lax.fori_loop(0, n_ticks, tick, carry)
            _, _, loss_acc, aux_acc, count = carry
            loss = jax.lax.psum(loss_acc, "stage") / \
                jnp.maximum(jax.lax.psum(count, "stage"), 1.0)
            # every stage adds its own groups' aux for each microbatch, so
            # the psum is the whole model's aux summed over microbatches
            aux = jax.lax.psum(aux_acc, "stage") / n_micro
            return loss + 0.01 * aux

        rest = {k: v for k, v in params.items() if k != "groups"}
        # Stage-stack the stage-shared params instead of passing them
        # replicated: their cotangent then arrives as a per-stage slice and
        # is summed by the broadcast_to transpose OUTSIDE the shard_map.
        # (Replicated-in params would need a cotangent psum inside the
        # manual region, whose transpose-built reduction computation has a
        # `copy` root that crashes XLA:CPU's all-reduce promotion pass.)
        rest_b = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (S_stages,) + t.shape), rest)
        fn = _stage_shard_map(
            inner, rmesh,
            in_specs=(P("stage"), P("stage"), P(), P()),
            out_specs=P())
        return fn(params["groups"], rest_b, tokens, extra)

    return loss_fn


def _my_depth(stage, depths):
    """Buffer depth of the INCOMING boundary of this stage (stage-1 ->
    stage); stage 0 has none."""
    if not depths:
        return jnp.ones((), jnp.int32)
    arr = jnp.asarray([1] + list(depths), jnp.int32)   # stage 0 unused
    return arr[jnp.clip(stage, 0, len(depths))]


def _push(buf, val, depth):
    """Insert ``val`` at FIFO position depth-1 (arrives after `depth`
    ticks).  buf: (dmax, ...)."""
    dmax = buf.shape[0]
    slot = jnp.clip(depth - 1, 0, dmax - 1)
    onehot = (jnp.arange(dmax) == slot).astype(buf.dtype)
    shape = (dmax,) + (1,) * (buf.ndim - 1)
    return buf + onehot.reshape(shape) * val[None]
