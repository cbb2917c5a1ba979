"""Tier-1 equivalence tests for the fused batch-update engine (ISSUE-1).

Every test drives ``DynamicGraph.apply_batch`` on randomized streams and
compares phi edge-for-edge against the pure-Python oracle — the fused path
must be *exact*, not approximate, at every batch size.

All graphs share one pinned ``GraphSpec`` (N/D_MAX/E_CAP below) so the jit
caches for decompose / maintain / batch_maintain compile once for the whole
module — the suite stays fast-lane-fast.
"""
import numpy as np
import pytest

from repro.core import DynamicGraph, oracle
from repro.core.graph import (GraphSpec, apply_edge_batch_struct,
                              delete_edge_struct, from_edge_list,
                              insert_edge_struct)
from repro.data.streams import iter_batches, make_update_stream

N = 13        # nodes in every random test graph
D_MAX = 16    # shared degree capacity (max possible degree is N-1 = 12)
E_CAP = 160   # shared edge capacity (complete graph is 78 edges)


def _graph(edges):
    return DynamicGraph(N, edges, d_max=D_MAX, e_cap=E_CAP)


def _scratch_phi(present, n=N):
    adj = {i: set() for i in range(n)}
    for a, b in present:
        adj[a].add(b)
        adj[b].add(a)
    return oracle.truss_decomposition(adj)


def _random_graph(rng, p, n=N):
    return [(i, j) for i in range(n) for j in range(i + 1, n)
            if rng.random() < p]


@pytest.mark.parametrize("bsz", [1, 7, 64])
def test_fused_mixed_stream_matches_oracle(bsz):
    """Random mixed insert/delete streams, chunked at B, vs Oracle replay."""
    rng = np.random.default_rng(bsz)
    edges = _random_graph(rng, 0.3)
    stream = make_update_stream(np.asarray(edges), N, 48, seed=bsz + 1)
    g = _graph(edges)
    orc = oracle.Oracle(N, edges)
    for chunk in iter_batches(stream, bsz):
        g.apply_batch([tuple(map(int, r)) for r in chunk], strategy="fused")
        orc.apply(chunk)
        assert g.phi_dict() == orc.phi


@pytest.mark.parametrize("kind", ["insert", "delete"])
def test_fused_homogeneous_batches(kind):
    """Pure-insert / pure-delete batches exercise the Theorem-1/2 widened
    union range (no mixed-batch component fallback)."""
    for seed in range(3):
        rng = np.random.default_rng(seed)
        edges = _random_graph(rng, 0.35)
        if len(edges) < 12:
            continue
        g = _graph(edges)
        present = set(edges)
        if kind == "insert":
            absent = [(i, j) for i in range(N) for j in range(i + 1, N)
                      if (i, j) not in present]
            rng.shuffle(absent)
            batch = [(1, a, b) for a, b in absent[:8]]
        else:
            picks = rng.choice(len(edges), size=8, replace=False)
            batch = [(0, *sorted(edges)[i]) for i in picks]
        g.apply_batch(batch, strategy="fused")
        for op, a, b in batch:
            present.add((a, b)) if op == 1 else present.discard((a, b))
        assert g.phi_dict() == _scratch_phi(present), (kind, seed)


def test_fused_netting_cancels_inside_batch():
    """Insert-then-delete of one edge inside a batch is a no-op; the rest of
    the batch still applies."""
    base = [(0, 1), (1, 2), (0, 2), (2, 3)]
    g = _graph(base)
    ups = [(1, 4, 5), (0, 4, 5), (1, 0, 3), (0, 2, 3), (1, 2, 3)]
    g.apply_batch(ups, strategy="fused")
    assert g.phi_dict() == _scratch_phi(set(base) | {(0, 3)})


def test_strategies_agree_and_auto_dispatches():
    """fused == progressive == auto on the same stream."""
    rng = np.random.default_rng(3)
    edges = _random_graph(rng, 0.35)
    stream = make_update_stream(np.asarray(edges), N, 18, seed=9)
    results = []
    for strategy in ("fused", "progressive", "auto"):
        g = _graph(edges)
        for chunk in iter_batches(stream, 6):
            g.apply_batch([tuple(map(int, r)) for r in chunk],
                          strategy=strategy)
        results.append(g.phi_dict())
    assert results[0] == results[1] == results[2]


def test_apply_batch_grows_capacity():
    """A batch that overflows e_cap/d_max triggers host-side growth and
    still lands on exact phi."""
    g = DynamicGraph(10, [(0, 1)], e_cap=4, d_max=3)
    ups = [(1, i, j) for i in range(6) for j in range(i + 1, 6)
           if (i, j) != (0, 1)]
    g.apply_batch(ups, strategy="fused")
    present = {(0, 1)} | {(a, b) for _, a, b in ups}
    assert g.phi_dict() == _scratch_phi(present, n=10)


def test_non_canonical_constructor_edges():
    """Edges given as (v, u) with v > u must net/validate correctly."""
    g = DynamicGraph(4, [(2, 1), (1, 3), (2, 3)], d_max=D_MAX, e_cap=E_CAP)
    g.apply_batch([(0, 1, 2)])
    g.apply_batch([(1, 1, 2)])
    assert g.phi_dict() == _scratch_phi({(1, 2), (1, 3), (2, 3)}, n=4)


def test_apply_batch_rejects_invalid_updates():
    g = _graph([(0, 1), (1, 2)])
    with pytest.raises(ValueError):
        g.apply_batch([(1, 0, 1)])      # insert of present edge
    with pytest.raises(ValueError):
        g.apply_batch([(0, 0, 3)])      # delete of absent edge
    with pytest.raises(ValueError):
        g.apply_batch([(1, 2, 2)])      # self-loop


def test_vectorized_struct_matches_sequential():
    """apply_edge_batch_struct == sequential insert/delete_edge_struct on
    adjacency rows, degrees, and the active edge set."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    spec = GraphSpec(n_nodes=N, d_max=D_MAX, e_cap=E_CAP)
    for trial in range(5):
        edges = _random_graph(rng, 0.3)
        if len(edges) < 4:
            continue
        st = from_edge_list(spec, np.asarray(edges))
        present = sorted(edges)
        absent = [(i, j) for i in range(N) for j in range(i + 1, N)
                  if (i, j) not in set(edges)]
        rng.shuffle(absent)
        dels = [present[i] for i in
                rng.choice(len(present), size=min(4, len(present)),
                           replace=False)]
        inss = absent[:5]
        bsz = 8

        def pad(pairs):
            a = np.zeros(bsz, np.int32)
            b = np.zeros(bsz, np.int32)
            m = np.zeros(bsz, bool)
            for i, (x, y) in enumerate(pairs):
                a[i], b[i], m[i] = x, y, True
            return jnp.asarray(a), jnp.asarray(b), jnp.asarray(m)

        st2, _ = apply_edge_batch_struct(spec, st, *pad(dels), *pad(inss))
        ref = st
        for x, y in dels:
            ref, _ = delete_edge_struct(spec, ref, jnp.int32(x), jnp.int32(y))
        for x, y in inss:
            ref, _ = insert_edge_struct(spec, ref, jnp.int32(x), jnp.int32(y))

        def edgeset(s):
            act = np.asarray(s.active)
            return {tuple(e) for e in np.asarray(s.edges)[act]}

        assert edgeset(st2) == edgeset(ref), trial
        assert np.array_equal(np.asarray(st2.nbr), np.asarray(ref.nbr)), trial
        # both paths claim free slots in the same order, so eid (the slot
        # mapping triangle enumeration depends on) must match exactly too
        assert np.array_equal(np.asarray(st2.eid), np.asarray(ref.eid)), trial
        assert np.array_equal(np.asarray(st2.deg), np.asarray(ref.deg)), trial


# -- affected-set closure: bitmap probe == sorted-row search -------------------
PROBE_N = 64        # a multiple of 32: the sentinel N lands one word past W
PROBE_D = 24        # node 0's row is filled to d_max
PROBE_E = 512
PROBE_CHUNK = 16    # closure chunk: several iterations, a last one with
                    # sentinel rows


def _probe_graph(seed):
    rng = np.random.default_rng(seed)
    hub = [(0, j) for j in range(1, PROBE_D + 1)]
    rest = [(i, j) for i in range(1, PROBE_N) for j in range(i + 1, PROBE_N)
            if rng.random() < 0.12]
    return hub + rest, rng


@pytest.mark.parametrize("kind", ["mixed", "insert", "delete"])
def test_closure_bitmap_probe_matches_search(kind):
    """The closure's bitmap probe (a replicated post-update bitmap given)
    admits exactly the set the sorted-row search admits: the same |A|,
    iterations, range and fallback flag, and phi equal to the oracle's.
    Node 0's row is full, so it holds no sentinel; the batch leaves it
    alone."""
    import jax.numpy as jnp

    from repro.core import decompose
    from repro.core.batch import batch_maintain, closure_probes_bitmap
    from repro.core.graph import build_bitmap

    edges, rng = _probe_graph(7)
    present = set(edges)
    absent = [(i, j) for i in range(1, PROBE_N) for j in range(i + 1, PROBE_N)
              if (i, j) not in present]
    movable = sorted(e for e in edges if e[0] != 0)
    n_del, n_ins = {"mixed": (4, 4), "insert": (0, 8), "delete": (8, 0)}[kind]
    dels = [movable[i] for i in rng.choice(len(movable), n_del, replace=False)]
    inss = [absent[i] for i in rng.choice(len(absent), n_ins, replace=False)]
    post = (present - set(dels)) | set(inss)

    spec = GraphSpec(n_nodes=PROBE_N, d_max=PROBE_D, e_cap=PROBE_E)

    def pad(pairs, bsz=8):
        a, b, m = (np.zeros(bsz, np.int32), np.zeros(bsz, np.int32),
                   np.zeros(bsz, bool))
        for i, (x, y) in enumerate(pairs):
            a[i], b[i], m[i] = x, y, True
        return jnp.asarray(a), jnp.asarray(b), jnp.asarray(m)

    def run(method):
        st = from_edge_list(spec, np.asarray(edges))
        st = st._replace(phi=decompose(spec, st))
        bitmap = None
        if method == "bitmap":
            st_post = from_edge_list(spec, np.asarray(sorted(post)))
            bitmap = build_bitmap(spec, st_post, st_post.active)
        probed = closure_probes_bitmap(spec, bitmap)
        st1, lo, hi, stats = batch_maintain(
            spec, st, *pad(dels), *pad(inss), batch=PROBE_CHUNK,
            method=method, bitmap=bitmap)
        act = np.asarray(st1.active)
        phi = {(int(a), int(b)): int(p) for (a, b), p in
               zip(np.asarray(st1.edges)[act], np.asarray(st1.phi)[act])}
        assert int(np.asarray(st1.deg)[0]) == PROBE_D
        return probed, phi, (int(lo), int(hi)), stats

    probe, phi_p, range_p, s_p = run("bitmap")
    search, phi_s, range_s, s_s = run("sorted")
    assert probe and not search
    assert phi_p == phi_s == _scratch_phi(post, n=PROBE_N)
    assert range_p == range_s
    for field in ("frontier", "closure_iters", "closure_unfiltered"):
        assert int(getattr(s_p, field)) == int(getattr(s_s, field)), field
    assert int(s_p.closure_unfiltered) == (kind == "mixed")
    # some chunk ran with sentinel rows
    assert int(s_p.closure_iters) * PROBE_CHUNK > int(s_p.frontier) > 0
