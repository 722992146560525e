"""burst_gather — the TPU adaptation of TAPA's async_mmap + runtime burst
detector (paper §3.4, Table 1).

The paper splits a memory port into request/response streams and inserts a
*burst detector* that watches the address stream and merges runs of
consecutive addresses into long burst transactions.  The TPU analogue: a
gather whose index stream is scanned for contiguous runs; a run of length
>= the tile size is serviced by ONE block DMA (HBM -> VMEM dynamic slice)
instead of per-row gathers.  Embedding lookups and KV-page fetches are
mostly-sequential with occasional jumps — exactly the access pattern Table
1 illustrates — so the common case is the burst path.

Implementation: grid over index tiles of size ``IB``.  The index tile is
prefetched to SMEM (PrefetchScalarGridSpec).  If the whole tile is one run
(idx[i] == idx[0] + i — checked on the scalar stream like the paper's
detector) starting on an HBM tile boundary, the kernel issues a single DMA
of IB consecutive table rows straight into the output tile; otherwise it
falls back to IB per-row DMAs, each of the aligned 8-row HBM tile holding
its row (a DMA cannot start mid-tile), and selects the row in VMEM.  The
table stays in ANY/HBM memory space — rows are DMA'd on demand with
``pltpu.make_async_copy``, which is the whole point (an FPGA would call
this "not buffering the burst in BRAM", Table 3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_IB = 8
#: rows per HBM tile: a DMA may only start at, and move whole, 8-row
#: tiles (sub-word dtypes pack within the tile, not across it)
_TILE_ROWS = 8


def _kernel(idx_ref, table_ref, o_ref, groups_ref, sems, *, ib):
    t = pl.program_id(0)
    base = idx_ref[t * ib]
    # ---- the burst detector: is this tile one tile-aligned run? ----------
    run = base % _TILE_ROWS == 0
    for i in range(1, ib):
        run = jnp.logical_and(run, idx_ref[t * ib + i] == base + i)

    @pl.when(run)
    def _burst():
        # one long transaction: IB consecutive rows in a single DMA
        start = pl.multiple_of(base, _TILE_ROWS)
        cp = pltpu.make_async_copy(table_ref.at[pl.ds(start, ib)], o_ref,
                                   sems.at[0])
        cp.start()
        cp.wait()

    @pl.when(jnp.logical_not(run))
    def _rows():
        # per-row transactions, all in flight at once: each fetches the
        # aligned 8-row tile holding its row, which is then selected out
        rows = [idx_ref[t * ib + i] for i in range(ib)]
        cps = []
        for i, r in enumerate(rows):
            start = pl.multiple_of(r - r % _TILE_ROWS, _TILE_ROWS)
            cps.append(pltpu.make_async_copy(
                table_ref.at[pl.ds(start, _TILE_ROWS)], groups_ref.at[i],
                sems.at[i]))
            cps[-1].start()
        D = o_ref.shape[1]
        sub = jax.lax.broadcasted_iota(jnp.int32, (_TILE_ROWS, D), 0)
        slot = jax.lax.broadcasted_iota(jnp.int32, (ib, D), 0)
        out = jnp.zeros((ib, D), jnp.float32)
        for i, r in enumerate(rows):
            cps[i].wait()
            tile = groups_ref[i].astype(jnp.float32)          # (8, D)
            row = jnp.sum(jnp.where(sub == r % _TILE_ROWS, tile, 0.0),
                          axis=0, keepdims=True)               # exact
            out = jnp.where(slot == i, row, out)
        o_ref[...] = out.astype(o_ref.dtype)


def burst_gather(table: jax.Array, idx: jax.Array, *, ib: int = DEFAULT_IB,
                 interpret: bool = False) -> jax.Array:
    """table: (R, D) floating; idx: (N,) int32 in [0, R) -> (N, D)."""
    assert ib % _TILE_ROWS == 0, ib
    R, D = table.shape
    N = idx.shape[0]
    Np = -(-N // ib) * ib
    idxp = jnp.pad(idx.astype(jnp.int32), (0, Np - N))
    Rp = -(-R // _TILE_ROWS) * _TILE_ROWS
    Dp = max(128, -(-D // 128) * 128)
    if (Rp, Dp) != (R, D):
        table = jnp.pad(table, ((0, Rp - R), (0, Dp - D)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Np // ib,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ib, Dp), lambda t, idx_ref: (t, 0)),
        scratch_shapes=[pltpu.VMEM((ib, _TILE_ROWS, Dp), table.dtype),
                        pltpu.SemaphoreType.DMA((ib,))],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ib=ib),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Np, Dp), table.dtype),
        interpret=interpret,
    )(idxp, table)
    return out[:N, :D]
