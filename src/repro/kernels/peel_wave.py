"""Pallas TPU kernel: fused peel wave — bitmap support + kill-frontier emission.

``bitmap_support.py`` reduces pre-gathered adjacency-bitmap rows to raw
support counts and leaves the peel threshold to a separate XLA pass.  This
kernel extends it: one VMEM pass over the ``[E, W]`` uint32 rows computes

    sup[i]  = popcount(rows_a[i] & rows_b[i]).sum()        (masked to alive)
    kill[i] = alive[i] and sup[i] < k - 2

so the peel loop's level-k frontier comes out of the same accumulation that
produced the counts — no second trip through the edge axis.  ``k`` rides in
as a one-element SMEM operand so one compiled kernel serves every peel
level.

Tiling is ``bitmap_support``'s (same blocks, same unpadded partial tiles):
grid = (cdiv(E, EB), cdiv(W, WB)) with the word axis minor (sequentially
revisited on TPU); the output blocks for edge-tile i accumulate partials
across j and the threshold fires on the last word tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitmap_support import COMPILER_PARAMS, tile_support, tiling


def _kernel(k_ref, a_ref, b_ref, alive_ref, sup_ref, kill_ref, *, w):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        sup_ref[...] = jnp.zeros_like(sup_ref)

    sup_ref[...] += tile_support(a_ref, b_ref, w)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        alive = alive_ref[...] != 0
        sup = jnp.where(alive, sup_ref[...], 0)
        sup_ref[...] = sup
        kill_ref[...] = (alive & (sup < k_ref[0] - 2)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "row_count"))
def peel_wave_kernel(rows_a: jax.Array, rows_b: jax.Array, alive: jax.Array,
                     k: jax.Array, *, interpret: bool = False,
                     row_offset=0, row_count: int | None = None):
    """Fused (support, kill-frontier) for uint32 bitmap rows [E, W].

    Returns ``(sup int32[E], kill bool[E])`` with sup masked to 0 and kill
    to False outside ``alive``.

    ``row_offset``/``row_count`` select one row block out of larger inputs
    (the mesh-sharded peel substrate's row-block addressing): the same
    kernel body then runs unchanged over rows
    ``[row_offset, row_offset + row_count)`` and the outputs cover only
    that block.  Concatenating the per-block outputs over a partition of
    the edge axis is bitwise-equal to the full-array call
    (``tests/test_sharded.py``) — the property that makes the sharded
    engine's per-shard calls exact; under ``shard_map`` the shard already
    holds its block, so those calls pass whole local arrays and the slab
    path serves full-array callers.
    """
    if row_count is not None:
        rows_a = jax.lax.dynamic_slice_in_dim(rows_a, row_offset, row_count)
        rows_b = jax.lax.dynamic_slice_in_dim(rows_b, row_offset, row_count)
        alive = jax.lax.dynamic_slice_in_dim(alive, row_offset, row_count)
    e, w = rows_a.shape
    eb, wb = tiling(e, w)
    k_arr = jnp.asarray(k, jnp.int32).reshape(1)

    sup, kill = pl.pallas_call(
        functools.partial(_kernel, w=w),
        grid=(pl.cdiv(e, eb), pl.cdiv(w, wb)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((eb, wb), lambda i, j: (i, j)),
            pl.BlockSpec((eb, wb), lambda i, j: (i, j)),
            pl.BlockSpec((eb,), lambda i, j: (i,)),
        ],
        out_specs=(
            pl.BlockSpec((eb,), lambda i, j: (i,)),
            pl.BlockSpec((eb,), lambda i, j: (i,)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((e,), jnp.int32),
            jax.ShapeDtypeStruct((e,), jnp.int32),
        ),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(k_arr, rows_a, rows_b, alive.astype(jnp.int32))
    return sup, kill != 0
