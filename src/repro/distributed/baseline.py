"""Baseline GSPMD execution (the "default tool flow" of the paper's
comparison): no floorplan — every layer sharded over the FULL model axis,
data parallelism over (pod, data) with ZeRO-1 optimizer sharding.

This is the TPU analogue of Vivado packing all logic together: local
latency is minimal but every layer's TP collectives span the whole model
axis (and, multi-pod, would span DCN if the model axis crossed pods).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.model import lm
from .pipeline import param_specs

DATA_AXES = ("pod", "data")


def data_axes(mesh: Mesh):
    return tuple(a for a in DATA_AXES if a in mesh.axis_names)


def make_shardings(cfg: ArchConfig, params, mesh: Mesh):
    specs = param_specs(cfg, params, tp_axis="model")
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_sharding(mesh: Mesh):
    return NamedSharding(mesh, P(data_axes(mesh)))


def build_loss(cfg: ArchConfig, *, remat: bool = True,
               unroll: bool = False):
    def loss_fn(params, batch):
        tokens = batch["tokens"]
        x_tokens, targets = tokens[:, :-1], tokens[:, 1:]
        # full forward without materializing logits: reuse group scan then
        # chunked CE
        specs = lm.build_specs(cfg)
        x = lm._embed(params, cfg, x_tokens)
        positions = jnp.arange(x_tokens.shape[1])
        memory = lm._memory(params, cfg, batch.get("extra"))
        shared = params.get("shared")
        x0 = x

        def group_fn(carry, gp):
            x, aux = carry
            x, a, _ = lm.apply_group(gp, cfg, specs, x, positions=positions,
                                     x0=x0, memory=memory, shared=shared)
            return (x, aux + a), None

        body = jax.checkpoint(group_fn) if remat else group_fn
        n_groups = cfg.n_layers // len(cfg.layer_pattern)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["groups"],
                                   unroll=n_groups if unroll else 1)
        ce = lm.chunked_ce(params, cfg, x, targets)
        return ce + 0.01 * aux

    return loss_fn


def build_serve_step(cfg: ArchConfig):
    """One serving step: prefill (S > 1) or decode (S = 1)."""
    def serve_step(params, cache, tokens):
        return lm.step(params, cfg, cache, tokens)
    return serve_step


def cache_shardings(cfg: ArchConfig, cache, mesh: Mesh):
    """KV caches: batch over (pod, data); heads over model when the KV-head
    count divides, otherwise the cache LENGTH is sharded over model
    (context parallelism — each chip holds a context slice); SSM states:
    batch over data axes, heads over model."""
    daxes = data_axes(mesh)
    tp = mesh.shape["model"]

    def cut(spec, nd):
        return P(*(tuple(spec)[:nd] + (None,) * max(0, nd - len(spec))))

    def axsize(entry):
        if entry is None:
            return 1
        names = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in names:
            n *= mesh.shape[a]
        return n

    def spec(path, leaf):
        if leaf.ndim == 0:
            return P()
        name = path[-1] if path else ""
        if name in ("k", "v"):            # (G, B, W, Hkv, D)
            if leaf.shape[3] % tp == 0:
                return cut(P(None, daxes, None, "model", None), leaf.ndim)
            if leaf.shape[2] % tp == 0:   # context parallelism fallback
                return cut(P(None, daxes, "model", None, None), leaf.ndim)
            return cut(P(None, daxes, None, None, None), leaf.ndim)
        if name in ("ssd", "wkv"):        # (G, B, H, P, N) / (G, B, H, D, D)
            if leaf.shape[2] % tp == 0:
                return cut(P(None, daxes, "model", None, None), leaf.ndim)
            return cut(P(None, daxes, None, None, None), leaf.ndim)
        if name == "conv":                # (G, B, K-1, C)
            if leaf.shape[3] % tp == 0:
                return cut(P(None, daxes, None, "model"), leaf.ndim)
            return cut(P(None, daxes, None, None), leaf.ndim)
        if name in ("tm_shift", "cm_shift"):
            return cut(P(None, daxes, None, None), leaf.ndim)
        if name == "memory":
            return cut(P(daxes), leaf.ndim)
        return P(*([None] * leaf.ndim))

    def fit(sp, leaf):
        """Drop spec entries that do not divide the dim (e.g. batch=1)."""
        parts = list(tuple(sp)) + [None] * (leaf.ndim - len(tuple(sp)))
        parts = [None if (p is not None and
                          leaf.shape[i] % axsize(p) != 0) else p
                 for i, p in enumerate(parts)]
        return P(*parts)

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v, path) for v in tree]
        if tree is None:
            return None
        return NamedSharding(mesh, fit(spec(path, tree), tree))

    return walk(cache, ())
