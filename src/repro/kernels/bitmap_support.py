"""Pallas TPU kernel: edge support via adjacency-bitmap AND + popcount.

The paper's hash-set intersection ``|n(a) ∩ n(b)|`` becomes, per edge, a
bitwise AND of two uint32 bitmap rows followed by a popcount-reduce — pure
VPU work with perfectly coalesced VMEM reads (DESIGN.md §2).

Inputs are the *pre-gathered* rows (``rows_a = bitmap[u]``, ``rows_b =
bitmap[v]``): the gather stays in XLA where it can fuse with the producing
scatter, and the kernel owns the hot elementwise-reduce loop.

Tiling: grid = (cdiv(E, EB), cdiv(W, WB)); the output block for edge-tile i
is revisited across the W dimension (sequential minor grid axis on TPU),
accumulating partial popcount sums in VMEM.

* ``EDGE_BLOCK = 1024``: XLA tiles a 1-D int32 array as ``T(1024)``, and
  Mosaic refuses a 1-D operand whose block tiles differently — so every
  1-D block (outputs, the alive mask) is 1024 rows, or the whole array
  when it is shorter.
* Neither axis is padded: ``jnp.pad`` of the ``[E, W]`` rows would copy
  gigabytes at real sizes.  Partial edge tiles past ``E`` compute rows
  that are never stored; partial word tiles past ``W`` are masked by
  column index, so out-of-range words never reach a popcount.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EDGE_BLOCK = 1024
WORD_BLOCK = 256


def tiling(e: int, w: int) -> tuple[int, int]:
    """(edge block, word block) for ``[e, w]`` rows: full-dimension blocks
    when an axis is shorter than its block, else the fixed tiles."""
    return (EDGE_BLOCK if e > EDGE_BLOCK else e,
            WORD_BLOCK if w > WORD_BLOCK else w)


def tile_support(a_ref, b_ref, w: int):
    """Popcount of ``a & b`` over one ``(eb, wb)`` tile, summed per row —
    columns at or past ``w`` (a partial last word tile) count zero."""
    inter = jax.lax.population_count(a_ref[...] & b_ref[...]).astype(jnp.int32)
    wb = inter.shape[1]
    if w % wb:
        col = (pl.program_id(1) * wb
               + jax.lax.broadcasted_iota(jnp.int32, inter.shape, 1))
        inter = jnp.where(col < w, inter, 0)
    return jnp.sum(inter, axis=1)


#: edge tiles are independent; the word axis accumulates into one block
COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _kernel(a_ref, b_ref, o_ref, *, w):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += tile_support(a_ref, b_ref, w)


@functools.partial(jax.jit, static_argnames=("interpret", "row_count",
                                             "word_count"))
def bitmap_support_kernel(rows_a: jax.Array, rows_b: jax.Array, *,
                          interpret: bool = False,
                          row_offset=0, row_count: int | None = None,
                          word_offset=0,
                          word_count: int | None = None) -> jax.Array:
    """sup[i] = popcount(rows_a[i] & rows_b[i]).sum() for uint32 rows [E, W].

    ``row_offset``/``row_count`` select one row block out of larger inputs
    (the mesh-sharded peel substrate's row-block addressing; see
    ``peel_wave_kernel``): the kernel runs unchanged over rows
    ``[row_offset, row_offset + row_count)`` and returns
    ``sup int32[row_count]``.

    ``word_offset``/``word_count`` select one **word slab** — the
    ``partition="nodes"`` addressing, where a device owns bitmap columns
    ``[word_offset, word_offset + word_count)``: the result is that slab's
    *partial* popcount, and summing the per-slab partials over a partition
    of the word axis equals the full-width call exactly (integer popcounts
    over disjoint columns — the invariant the partitioned peel engine's
    per-wave psum rests on, pinned by ``tests/test_scale.py``).
    """
    if row_count is not None:
        rows_a = jax.lax.dynamic_slice_in_dim(rows_a, row_offset, row_count)
        rows_b = jax.lax.dynamic_slice_in_dim(rows_b, row_offset, row_count)
    if word_count is not None:
        rows_a = jax.lax.dynamic_slice_in_dim(rows_a, word_offset, word_count,
                                              axis=1)
        rows_b = jax.lax.dynamic_slice_in_dim(rows_b, word_offset, word_count,
                                              axis=1)
    e, w = rows_a.shape
    eb, wb = tiling(e, w)
    return pl.pallas_call(
        functools.partial(_kernel, w=w),
        grid=(pl.cdiv(e, eb), pl.cdiv(w, wb)),
        in_specs=[
            pl.BlockSpec((eb, wb), lambda i, j: (i, j)),
            pl.BlockSpec((eb, wb), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((eb,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((e,), jnp.int32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(rows_a, rows_b)
