"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.bitmap_support import bitmap_support_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.segment_matmul import segment_matmul_kernel


@pytest.mark.parametrize("e,w", [(1, 1), (7, 3), (64, 32), (130, 37), (513, 129),
                                 (1030, 1), (2500, 300)])
def test_bitmap_support_shapes(e, w):
    rng = np.random.default_rng(e * 1000 + w)
    a = jnp.asarray(rng.integers(0, 2**32, size=(e, w), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, size=(e, w), dtype=np.uint32))
    got = bitmap_support_kernel(a, b, interpret=True)
    exp = ref.bitmap_support_ref(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("e,d,n", [(10, 4, 3), (100, 16, 17), (1000, 64, 77),
                                   (513, 32, 128), (257, 8, 1)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_segment_matmul_shapes(e, d, n, dtype):
    rng = np.random.default_rng(e + d + n)
    m = jnp.asarray(rng.normal(size=(e, d)).astype(dtype))
    seg = jnp.asarray(rng.integers(0, n, size=(e,), dtype=np.int32))
    got = segment_matmul_kernel(m, seg, n, interpret=True)
    exp = ref.segment_matmul_ref(m, seg, n)
    tol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32), rtol=tol, atol=tol)


def test_segment_matmul_drops_oob_padding():
    m = jnp.ones((8, 4), jnp.float32)
    seg = jnp.asarray([0, 1, 2, 3, 4, 4, 4, 99], jnp.int32)  # 99 out of range
    got = segment_matmul_kernel(m, seg, 5, interpret=True)
    exp = jax.ops.segment_sum(m[:7], seg[:7], 5)  # oracle without the oob row
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("bh,sq,dh", [(1, 64, 16), (2, 300, 32), (4, 128, 64)])
@pytest.mark.parametrize("window", [None, 32])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_sweep(bh, sq, dh, window, dtype):
    rng = np.random.default_rng(bh * sq)
    q = jnp.asarray(rng.normal(size=(bh, sq, dh))).astype(dtype)
    k = jnp.asarray(rng.normal(size=(bh, sq, dh))).astype(dtype)
    v = jnp.asarray(rng.normal(size=(bh, sq, dh))).astype(dtype)
    got = flash_attention_kernel(q, k, v, causal=True, window=window,
                                 interpret=True, q_block=64, kv_block=64)
    exp = ref.attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-5 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32), rtol=tol, atol=tol)


def test_chunked_attention_matches_ref():
    """The XLA online-softmax path used off-TPU must equal the oracle too."""
    from repro.models.layers import _chunked_attention

    rng = np.random.default_rng(0)
    b, hq, hkv, s, dh = 2, 4, 2, 200, 16
    q = jnp.asarray(rng.normal(size=(b, hq, s, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, hkv, s, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, hkv, s, dh)).astype(np.float32))
    got = _chunked_attention(q, k, v, causal=True, window=None,
                             q_chunk=64, kv_chunk=64)
    kr = jnp.repeat(k, 2, axis=1).reshape(b * hq, s, dh)
    vr = jnp.repeat(v, 2, axis=1).reshape(b * hq, s, dh)
    exp = ref.attention_ref(q.reshape(b * hq, s, dh), kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(got).reshape(b * hq, s, dh),
                               np.asarray(exp), rtol=2e-5, atol=2e-5)


def test_bitmap_kernel_matches_graph_support():
    """Kernel path == searchsorted path on a real graph (integration)."""
    from repro.core import GraphSpec, from_edge_list, support_all, support_all_bitmap

    rng = np.random.default_rng(4)
    n = 40
    edges = [(i, j) for i in range(n) for j in range(i + 1, n) if rng.random() < 0.2]
    spec = GraphSpec(n_nodes=n, d_max=n, e_cap=len(edges))
    st = from_edge_list(spec, np.asarray(edges))
    alive = st.active
    np.testing.assert_array_equal(
        np.asarray(support_all(spec, st, alive)),
        np.asarray(support_all_bitmap(spec, st, alive)))
