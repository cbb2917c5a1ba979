"""Delta-peel engine (ISSUE-3): kernel unit tests + oracle equivalence.

The engine must be *bitwise* exact: delta-maintained support peeling equals
the from-scratch oracle on random graphs, after randomized update streams,
for both support methods, with and without the frozen boundary.  All graphs
share one pinned GraphSpec so the jit caches compile once per module.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (DynamicGraph, GraphSpec, build_bitmap, decompose,
                        delta_peel, from_edge_list, oracle)
from repro.core.batch import batch_maintain
from repro.data.streams import iter_batches, make_update_stream
from repro.kernels import ref
from repro.kernels.peel_wave import peel_wave_kernel

N = 13
D_MAX = 16
E_CAP = 160
SPEC = GraphSpec(n_nodes=N, d_max=D_MAX, e_cap=E_CAP)


def _random_graph(rng, p, n=N):
    return [(i, j) for i in range(n) for j in range(i + 1, n)
            if rng.random() < p]


def _scratch_phi(present, n=N):
    return oracle.scratch_phi(n, present)


_phi_dict = oracle.phi_snapshot


# ---------------------------------------------------------------------------
# peel_wave kernel (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("e,w", [(1, 1), (7, 3), (64, 32), (130, 37), (513, 129),
                                 (1030, 1), (2500, 300)])
def test_peel_wave_kernel_shapes(e, w):
    rng = np.random.default_rng(e * 1000 + w)
    a = jnp.asarray(rng.integers(0, 2**32, size=(e, w), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, size=(e, w), dtype=np.uint32))
    alive = jnp.asarray(rng.random(e) < 0.8)
    for k in (3, 5, 16 * w):
        sup, kill = peel_wave_kernel(a, b, alive, jnp.int32(k), interpret=True)
        sup_ref, kill_ref = ref.peel_wave_ref(a, b, alive, jnp.int32(k))
        np.testing.assert_array_equal(np.asarray(sup), np.asarray(sup_ref))
        np.testing.assert_array_equal(np.asarray(kill), np.asarray(kill_ref))


def test_peel_wave_kernel_threshold_and_masking():
    """kill fires exactly on alive & sup < k-2; dead rows emit 0/False."""
    a = jnp.asarray(np.array([[0b111], [0b111], [0b1], [0b111]], np.uint32))
    b = jnp.asarray(np.array([[0b111], [0b011], [0b1], [0b111]], np.uint32))
    alive = jnp.asarray([True, True, True, False])
    sup, kill = peel_wave_kernel(a, b, alive, jnp.int32(5), interpret=True)
    np.testing.assert_array_equal(np.asarray(sup), [3, 2, 1, 0])
    np.testing.assert_array_equal(np.asarray(kill), [False, True, True, False])


# ---------------------------------------------------------------------------
# engine equivalence (fast lane)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["sorted", "bitmap"])
def test_delta_peel_matches_oracle(method):
    """Full decomposition: delta engine == recompute engine == oracle."""
    for seed, p in ((0, 0.2), (1, 0.35), (2, 0.6), (3, 0.05)):
        rng = np.random.default_rng(seed)
        edges = _random_graph(rng, p)
        st = from_edge_list(SPEC, np.asarray(edges))
        ref_phi = _scratch_phi(set(edges))
        phi_d = decompose(SPEC, st, method, "delta")
        phi_r = decompose(SPEC, st, method, "recompute")
        assert _phi_dict(st, phi_d) == ref_phi, (method, seed)
        np.testing.assert_array_equal(np.asarray(phi_d), np.asarray(phi_r))


@pytest.mark.parametrize("widths,block", [((16, 128, 1024), 1 << 22),
                                          ((8, 32), 192)])
def test_support_pass_matches_partner_search(monkeypatch, widths, block):
    """``support`` counts common neighbors by comparing the first k entries
    of both endpoints' rows, grouped by degree class and run in blocks; it
    must equal the binary-search partner enumeration of
    ``triangle_partners`` for every class pair and block size, and the
    sorted peels on top of it must stay exact."""
    from repro.core import graph, support_all
    from repro.core.graph import triangle_partners

    monkeypatch.setattr(graph, "SUPPORT_WIDTHS", widths)
    monkeypatch.setattr(graph, "SUPPORT_BLOCK_ELEMS", block)
    n, d_max = 90, 96
    rng = np.random.default_rng(5)
    # two hubs joined to everyone (degree ~89) over a sparse remainder of
    # degrees ~2-16: edges fall in every class pair of (8, 32, 96)
    edges = sorted({(min(a, b), max(a, b)) for a in (0, 1)
                    for b in range(n) if a != b}
                   | {(i, j) for i in range(2, n) for j in range(i + 1, n)
                      if rng.random() < 0.08})
    spec = GraphSpec(n_nodes=n, d_max=d_max, e_cap=len(edges) + 10)
    st = from_edge_list(spec, np.asarray(edges))
    u = jnp.minimum(st.edges[:, 0], n - 1)
    v = jnp.minimum(st.edges[:, 1], n - 1)
    id1, id2, valid = triangle_partners(spec, st, u, v)
    for alive in (st.active, st.active & (jnp.arange(spec.e_cap) % 3 != 0)):
        al = np.append(np.asarray(alive), False)
        ok = (np.asarray(valid) & al[np.minimum(np.asarray(id1), spec.e_cap)]
              & al[np.minimum(np.asarray(id2), spec.e_cap)])
        exp = np.where(np.asarray(alive), ok.sum(axis=1), 0)
        np.testing.assert_array_equal(
            np.asarray(support_all(spec, st, alive)), exp)
    # a spec of its own, so the jitted peels trace under these constants
    spec = GraphSpec(n_nodes=n, d_max=d_max, e_cap=len(edges) + 11 + block)
    st = from_edge_list(spec, np.asarray(edges))
    for engine in ("recompute", "delta"):
        phi = decompose(spec, st, "sorted", engine)
        assert _phi_dict(st, phi) == _scratch_phi(set(edges), n), engine


def test_delta_peel_chunked_waves_and_stats():
    """A chunk smaller than the first wave forces multi-chunk levels; the
    result stays exact and the stats count every kill."""
    rng = np.random.default_rng(7)
    edges = _random_graph(rng, 0.5)
    st = from_edge_list(SPEC, np.asarray(edges))
    phi, stats = delta_peel(SPEC, st, st.active, method="sorted", chunk=4)
    assert _phi_dict(st, phi) == _scratch_phi(set(edges))
    assert int(stats.kills) == len(edges)
    assert int(stats.waves) >= int(stats.kills) // 4


def test_delta_peel_cached_bitmap_matches_engine_built():
    """A cached structural bitmap (DynamicGraph's incremental cache) must
    peel identically to the engine-built one, and the incremental
    bit-clearing waves must land on the oracle."""
    rng = np.random.default_rng(11)
    edges = _random_graph(rng, 0.4)
    st = from_edge_list(SPEC, np.asarray(edges))
    ref_phi = _scratch_phi(set(edges))
    bm = build_bitmap(SPEC, st, st.active)
    phi_a, _ = delta_peel(SPEC, st, st.active, method="bitmap")
    phi_b, _ = delta_peel(SPEC, st, st.active, bitmap=bm, method="bitmap")
    assert _phi_dict(st, phi_a) == ref_phi
    np.testing.assert_array_equal(np.asarray(phi_a), np.asarray(phi_b))
    # the cache itself is untouched (the engine clears bits functionally)
    np.testing.assert_array_equal(
        np.asarray(bm), np.asarray(build_bitmap(SPEC, st, st.active)))


def test_capacity_regrowth_invalidates_cached_bitmap():
    """Regression (ISSUE-5): a ``d_max``/``e_cap`` regrowth (``_grow``)
    must rebuild or invalidate the cached structural bitmap before the next
    maintenance call — on both the progressive insert path and the fused
    ``apply_batch`` path — so phi and bitmap-derived support never read a
    pre-growth cache."""
    from repro.core import support_all, support_all_bitmap

    def check_cache(g):
        bm_ref = build_bitmap(g.spec, g.state, g.state.active)
        np.testing.assert_array_equal(np.asarray(g._bitmap), np.asarray(bm_ref))
        sup_bm = support_all_bitmap(g.spec, g.state, g.state.active,
                                    bitmap=g._bitmap)
        sup_ref = support_all(g.spec, g.state, g.state.active)
        np.testing.assert_array_equal(np.asarray(sup_bm), np.asarray(sup_ref))

    # progressive inserts past both capacities (d_max=4, e_cap=6), with a
    # warm cache from a prior fused batch
    n = 10
    base = [(0, 1), (0, 2), (1, 2), (2, 3)]
    g = DynamicGraph(n, base, d_max=4, e_cap=6, support_method="bitmap")
    orc = oracle.Oracle(n, base)
    warm = [(1, 3, 4), (1, 4, 5)]
    g.apply_batch(warm, strategy="fused")
    orc.apply(warm)
    assert g._bitmap is not None  # cache is warm going into the regrowth
    spec0 = g.spec
    more = [(1, 0, 3), (1, 0, 4), (1, 1, 3), (1, 1, 4), (1, 5, 6),
            (1, 6, 7), (1, 0, 5), (1, 2, 4)]
    for op, a, b in more:
        g.insert(a, b)
        orc.apply([(op, a, b)])
    assert g.spec.e_cap > spec0.e_cap and g.spec.d_max > spec0.d_max
    assert g.phi_dict() == orc.phi
    # next maintenance call re-warms the cache; it must match a scratch build
    nxt = [(1, 7, 8), (1, 8, 9), (1, 7, 9), (0, 0, 1)]
    g.apply_batch(nxt, strategy="fused")
    orc.apply(nxt)
    assert g.phi_dict() == orc.phi
    check_cache(g)

    # fused-batch-triggered regrowth with a warm cache (grow happens inside
    # apply_batch, between netting and the re-peel)
    g2 = DynamicGraph(12, [(0, 1), (1, 2), (0, 2)], d_max=4, e_cap=4,
                      support_method="bitmap")
    orc2 = oracle.Oracle(12, [(0, 1), (1, 2), (0, 2)])
    b1 = [(1, 2, 3), (1, 3, 4)]
    g2.apply_batch(b1, strategy="fused")
    orc2.apply(b1)
    assert g2._bitmap is not None
    spec0 = g2.spec
    # blow past d_max on node 0 so _grow fires inside this apply_batch
    b2 = [(1, 0, k) for k in range(3, 12)] + [(1, 3, 5), (1, 4, 6)]
    g2.apply_batch(b2, strategy="fused")
    orc2.apply(b2)
    assert g2.spec.d_max > spec0.d_max
    assert g2.phi_dict() == orc2.phi
    check_cache(g2)


@pytest.mark.parametrize("method", ["sorted", "bitmap"])
def test_frozen_boundary_repeel_engines_agree(method):
    """batch_maintain's delta re-peel == recompute re-peel == oracle on a
    mixed netted batch (exercises frozen retires through the delta path)."""
    rng = np.random.default_rng(23)
    edges = _random_graph(rng, 0.35)
    present = set(edges)
    dels = sorted(present)[:3]
    absent = [(i, j) for i in range(N) for j in range(i + 1, N)
              if (i, j) not in present]
    rng.shuffle(absent)
    inss = absent[:3]

    bsz = 4

    def pad(pairs):
        a = np.zeros(bsz, np.int32)
        b = np.zeros(bsz, np.int32)
        m = np.zeros(bsz, bool)
        for i, (x, y) in enumerate(pairs):
            a[i], b[i], m[i] = x, y, True
        return jnp.asarray(a), jnp.asarray(b), jnp.asarray(m)

    ref_phi = _scratch_phi((present - set(dels)) | set(inss))
    outs = []
    for engine in ("delta", "recompute"):
        # batch_maintain donates its input state: hand each run a fresh one
        st = from_edge_list(SPEC, np.asarray(edges))
        st = st._replace(phi=decompose(SPEC, st))
        st1, _lo, _hi, stats = batch_maintain(
            SPEC, st, *pad(dels), *pad(inss), method=method, engine=engine)
        assert _phi_dict(st1, st1.phi) == ref_phi, (method, engine)
        outs.append(np.asarray(st1.phi))
        assert int(stats.waves) > 0
    np.testing.assert_array_equal(*outs)


@pytest.mark.parametrize("method", ["sorted", "bitmap"])
def test_delta_peel_after_update_stream(method):
    """DynamicGraph streams (fused flush path) stay exact under the engine,
    and the bitmap cache never drifts from a scratch build."""
    rng = np.random.default_rng(31)
    edges = _random_graph(rng, 0.3)
    g = DynamicGraph(N, edges, d_max=D_MAX, e_cap=E_CAP,
                     support_method=method)
    orc = oracle.Oracle(N, edges)
    stream = make_update_stream(np.asarray(edges), N, 24, seed=5)
    for chunk in iter_batches(stream, 8):
        g.apply_batch([tuple(map(int, r)) for r in chunk], strategy="fused")
        orc.apply(chunk)
        assert g.phi_dict() == orc.phi
        if method == "bitmap":
            np.testing.assert_array_equal(
                np.asarray(g._bitmap),
                np.asarray(build_bitmap(g.spec, g.state, g.state.active)))
    assert g.last_peel_stats is not None and int(g.last_peel_stats.waves) > 0


def test_flush_path_donates_state_buffers():
    """The per-generation GraphState copy is gone: the pre-flush buffers are
    consumed (donated) and the live-array count stays bounded across
    generations instead of growing with them."""
    rng = np.random.default_rng(41)
    edges = _random_graph(rng, 0.3)
    g = DynamicGraph(N, edges, d_max=D_MAX, e_cap=E_CAP)
    stream = make_update_stream(np.asarray(edges), N, 64, seed=6)
    counts = []
    for chunk in iter_batches(stream, 8):
        old = g.state
        g.apply_batch([tuple(map(int, r)) for r in chunk], strategy="fused")
        jax.block_until_ready(g.state)
        assert old.phi.is_deleted(), "input state survived the flush"
        counts.append(len(jax.live_arrays()))
    assert max(counts) - min(counts) <= len(g.state), \
        f"live buffers grew across generations: {counts}"


# ---------------------------------------------------------------------------
# property tests (full lane; guarded so the fast tests above still run when
# hypothesis is absent)
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings, strategies as st_
    _HAVE_HYPOTHESIS = True
except ImportError:  # CI full lane installs hypothesis; fast lane may not
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    SET = settings(max_examples=20, deadline=None,
                   suppress_health_check=[HealthCheck.too_slow,
                                          HealthCheck.data_too_large])

    def graph_strategy():
        return st_.sets(
            st_.tuples(st_.integers(0, N - 1), st_.integers(0, N - 1))
            .map(lambda e: (min(e), max(e))).filter(lambda e: e[0] != e[1]),
            min_size=4, max_size=N * (N - 1) // 2)

    @pytest.mark.slow
    @pytest.mark.parametrize("method", ["sorted", "bitmap"])
    @given(edges=graph_strategy())
    @SET
    def test_property_delta_peel_bitwise_oracle(method, edges):
        """Hypothesis: delta-peeled phi is bitwise-equal to the oracle."""
        edges = sorted(edges)
        st = from_edge_list(SPEC, np.asarray(edges))
        phi, _ = delta_peel(SPEC, st, st.active, method=method, chunk=8)
        assert _phi_dict(st, phi) == _scratch_phi(set(edges))

    @pytest.mark.slow
    @pytest.mark.parametrize("method", ["sorted", "bitmap"])
    @given(edges=graph_strategy(), seed=st_.integers(0, 2**16))
    @SET
    def test_property_delta_peel_after_stream(method, edges, seed):
        """Hypothesis: exactness holds after randomized insert/delete
        streams through the fused flush path (frozen-boundary delta
        re-peel)."""
        edges = sorted(edges)
        g = DynamicGraph(N, edges, d_max=D_MAX, e_cap=E_CAP,
                         support_method=method)
        orc = oracle.Oracle(N, edges)
        stream = make_update_stream(np.asarray(edges), N, 12, seed=seed)
        for chunk in iter_batches(stream, 6):
            g.apply_batch([tuple(map(int, r)) for r in chunk],
                          strategy="fused")
            orc.apply(chunk)
        assert g.phi_dict() == orc.phi
