"""jit'd public wrappers around the Pallas kernels.

On a TPU backend the real kernels run; everywhere else (CPU hosts, unit
tests) they execute in ``interpret=True`` mode so the *same kernel body*
is validated numerically.  ``use_kernels(False)`` drops to the pure-jnp
references entirely (useful for A/B benchmarking and as an escape hatch).

Every dispatch decision asks ``on_tpu()``, so a test that compiles for a
described TPU from a CPU host patches that one function.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .bitmap_support import bitmap_support_kernel
from .peel_wave import peel_wave_kernel
from .cin import cin_layer_kernel
from .segment_matmul import segment_matmul_kernel
from .flash_attention import flash_attention_kernel

_USE_KERNELS = True


def use_kernels(flag: bool) -> None:
    global _USE_KERNELS
    _USE_KERNELS = flag


def on_tpu() -> bool:
    """Whether kernels compile for a TPU (else they interpret or fall back
    to the references) — the one backend probe of this module."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def _slab(row_offset, row_count, *arrays):
    """Row-block slab selection shared by every bitmap-row entry point.

    The mesh-sharded peel substrate addresses the edge axis as contiguous
    row blocks; under ``shard_map`` each shard already holds its block, so
    the engine's per-shard calls pass whole (local) arrays.  (offset,
    count) serve callers that hold the *full* arrays and want one block —
    row-blocked single-device execution, and the block-equivalence tests
    (``tests/test_sharded.py``) that pin down the property the per-shard
    calls rely on: a kernel call on a slab == the corresponding slice of
    the full-array call, bitwise."""
    if row_count is None:
        return arrays
    return tuple(jax.lax.dynamic_slice_in_dim(a, row_offset, row_count)
                 for a in arrays)


def _word_slab(word_offset, word_count, *arrays):
    """Word-axis twin of ``_slab``: the ``partition="nodes"`` addressing
    where a device owns one contiguous slab of bitmap columns.  Popcounts
    of disjoint word slabs sum to the full-width popcount exactly, so a
    slab call is a *partial* support — the partitioned peel engine's
    per-wave psum operand."""
    if word_count is None:
        return arrays
    return tuple(jax.lax.dynamic_slice_in_dim(a, word_offset, word_count,
                                              axis=1)
                 for a in arrays)


def bitmap_support(rows_a, rows_b, row_offset=0, row_count=None,
                   word_offset=0, word_count=None):
    if not _USE_KERNELS:
        rows_a, rows_b = _slab(row_offset, row_count, rows_a, rows_b)
        rows_a, rows_b = _word_slab(word_offset, word_count, rows_a, rows_b)
        return ref.bitmap_support_ref(rows_a, rows_b)
    return bitmap_support_kernel(rows_a, rows_b, interpret=_interpret(),
                                 row_offset=row_offset, row_count=row_count,
                                 word_offset=word_offset,
                                 word_count=word_count)


def bitmap_support_gathered(bitmap, eu, ev, chunk=None):
    """Support counts straight from a bitmap + endpoint ids: gather the
    rows and reduce them, in ``chunk``-row batches (``lax.map``) when
    asked, so the resident gather transient is [chunk, W] instead of
    [E, W] — what makes million-edge bitmaps (where ``bitmap[eu]`` alone
    is gigabytes) feasible, and the per-slab partial-support entry of the
    node-partitioned peel engine (``bitmap`` is then the device's word
    slab and the result a partial sum).

    Like ``peel_wave``, this sits inside the peel engine's while_loop (one
    call per wave), so the Pallas body runs on real TPU hardware only;
    everywhere else the fused XLA reference serves (interpret-mode
    emulation in the hot loop costs ~40x).
    """
    use_kernel = _USE_KERNELS and on_tpu()

    def one(a, b):
        rows_a, rows_b = bitmap[a], bitmap[b]
        if use_kernel:
            return bitmap_support_kernel(rows_a, rows_b)
        return ref.bitmap_support_ref(rows_a, rows_b)

    e = eu.shape[0]
    if chunk is None or chunk >= e:
        return one(eu, ev)
    nc = -(-e // chunk)
    pad = nc * chunk - e
    eup = jnp.pad(eu, (0, pad))
    evp = jnp.pad(ev, (0, pad))
    out = jax.lax.map(lambda ab: one(ab[0], ab[1]),
                      (eup.reshape(nc, chunk), evp.reshape(nc, chunk)))
    return out.reshape(-1)[:e]


def peel_wave(rows_a, rows_b, alive, k, row_offset=0, row_count=None):
    # Unlike the other wrappers, this one only runs the Pallas body on real
    # TPU hardware: it sits inside the peel engine's while_loop (one call
    # per wave), where interpret-mode emulation costs ~40x over the fused
    # XLA reference.  The kernel body itself is still validated in
    # interpret mode by tests/test_peel_engine.py.
    if _USE_KERNELS and on_tpu():
        return peel_wave_kernel(rows_a, rows_b, alive, k,
                                row_offset=row_offset, row_count=row_count)
    rows_a, rows_b, alive = _slab(row_offset, row_count, rows_a, rows_b, alive)
    return ref.peel_wave_ref(rows_a, rows_b, alive, k)


def segment_matmul(messages, seg_ids, num_segments: int):
    if not _USE_KERNELS:
        return ref.segment_matmul_ref(messages, seg_ids, num_segments)
    return segment_matmul_kernel(messages, seg_ids, num_segments,
                                 interpret=_interpret())


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None):
    if not _USE_KERNELS:
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                  interpret=_interpret())


def cin_layer(xk, x0, w):
    if not _USE_KERNELS:
        return ref.cin_layer_ref(xk, x0, w)
    return cin_layer_kernel(xk, x0, w, interpret=_interpret())
