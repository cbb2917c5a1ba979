"""Graph-scale leap (ISSUE-10): node-partitioned bitmap + vectorized data.

The ``partition="nodes"`` engine splits the adjacency bitmap's *word axis*
across the mesh — each device holds one contiguous column slab, support is
recovered exactly per wave as a psum of per-slab partial popcounts — and
must stay **bitwise** equal to the replicated engine (and the oracle) for
every consumer: decompose, the frozen-boundary re-peel (cached bitmap
included), and the service flush.  Multi-device tests shell out with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (same pattern as
tests/test_sharded.py).

In-process tests pin the two algebraic facts the engine rests on: popcounts
over disjoint word slabs sum to the full-width popcount, and owner-local
slab scatters (out-of-slab bits dropped) partition the full bitmap build /
incremental update exactly.
"""
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 4) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


# ---------------------------------------------------------------------------
# vectorized generator: structure + equivalence-of-distribution sanity
# ---------------------------------------------------------------------------

def test_powerlaw_simple_seeded_and_capped():
    from repro.data.synthetic import powerlaw_graph

    e1 = powerlaw_graph(300, 5, seed=9)
    e2 = powerlaw_graph(300, 5, seed=9)
    assert np.array_equal(e1, e2)                      # seeded-deterministic
    assert not np.array_equal(e1, powerlaw_graph(300, 5, seed=10))
    u, v = e1[:, 0], e1[:, 1]
    assert (u < v).all()                               # canonical orientation
    assert u.min() >= 0 and v.max() < 300
    assert len({(int(a), int(b)) for a, b in e1}) == len(e1)  # simple graph
    capped = powerlaw_graph(300, 5, seed=9, max_degree=12)
    deg = np.bincount(capped.ravel(), minlength=300)
    assert deg.max() <= 12


def test_powerlaw_matches_reference_distribution():
    """The vectorized generator replaces a per-node loop; it need not be
    bitwise-identical, but at small n its *distribution* must agree with
    the reference: same edge-count scale, same heavy tail, same clustered
    (triangle-rich) structure."""
    from repro.data.synthetic import powerlaw_graph, powerlaw_graph_reference

    n, m = 400, 4

    def stats(edges):
        deg = np.bincount(np.asarray(edges).ravel(), minlength=n)
        adj = {i: set() for i in range(n)}
        for a, b in edges:
            adj[int(a)].add(int(b))
            adj[int(b)].add(int(a))
        tris = sum(len(adj[a] & adj[b]) for a, b in edges)
        return len(edges), deg.max(), np.median(deg[deg > 0]), tris

    e_new, dmax_new, dmed_new, tri_new = stats(powerlaw_graph(n, m, seed=2))
    e_ref, dmax_ref, dmed_ref, tri_ref = stats(
        powerlaw_graph_reference(n, m, seed=2))
    assert abs(e_new - e_ref) / e_ref < 0.25           # same edge scale
    assert dmax_new > 4 * dmed_new                     # heavy tail (new)
    assert dmax_ref > 4 * dmed_ref                     # heavy tail (ref)
    assert tri_new > len(range(n)) // 2                # triangle-rich
    assert 0.3 < tri_new / max(tri_ref, 1) < 3.0       # same clustering scale


def test_powerlaw_scales_vectorized():
    """~10^5 edges in well under interpreter-loop time — the property the
    million-edge benchmark tier rests on (the full 10^6–10^7 points run in
    benchmarks/million_edge.py, not here)."""
    from repro.data.synthetic import powerlaw_graph

    edges = powerlaw_graph(8192, 16, seed=0, max_degree=512)
    assert len(edges) > 8 * 8192
    u, v = edges[:, 0], edges[:, 1]
    assert (u < v).all()
    ids = u.astype(np.int64) * 8192 + v
    assert len(np.unique(ids)) == len(ids)


# ---------------------------------------------------------------------------
# word-slab algebra (in-process, single device)
# ---------------------------------------------------------------------------

def test_word_slab_partials_sum_to_full_support():
    """popcount over disjoint word slabs sums to the full-width popcount —
    the invariant the partitioned engine's per-wave psum rests on — on
    both ops dispatch paths and through the chunked gather entry."""
    from repro.kernels import ops

    rng = np.random.default_rng(3)
    e, w = 96, 12
    a = jnp.asarray(rng.integers(0, 2**32, size=(e, w), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, size=(e, w), dtype=np.uint32))
    full = np.asarray(ops.bitmap_support(a, b))
    for use_kernels in (True, False):
        ops.use_kernels(use_kernels)
        try:
            for s in (2, 3, 4):
                blk = w // s
                parts = [np.asarray(ops.bitmap_support(
                    a, b, word_offset=i * blk, word_count=blk))
                    for i in range(s)]
                assert np.array_equal(np.sum(parts, axis=0), full), \
                    (use_kernels, s)
        finally:
            ops.use_kernels(True)

    bm = jnp.asarray(rng.integers(0, 2**32, size=(64, w), dtype=np.uint32))
    eu = jnp.asarray(rng.integers(0, 64, size=e))
    ev = jnp.asarray(rng.integers(0, 64, size=e))
    whole = np.asarray(ops.bitmap_support_gathered(bm, eu, ev))
    for chunk in (7, 16, 96, 1000):
        got = np.asarray(ops.bitmap_support_gathered(bm, eu, ev, chunk=chunk))
        assert np.array_equal(got, whole), chunk


def test_partition_geometry_and_validation():
    from repro.core import GraphSpec
    from repro.core.graph import with_mesh
    from repro.launch.mesh import make_shard_mesh

    mesh = make_shard_mesh(1)
    spec = with_mesh(GraphSpec(n_nodes=100, d_max=16, e_cap=64), mesh,
                     partition="nodes")
    # 100 nodes -> 4 raw words; padding keeps n_words a multiple of shards
    assert spec.n_words % spec.n_shards == 0
    assert spec.word_block * spec.n_shards == spec.n_words
    assert spec.bitmap_bytes_per_device == 100 * spec.word_block * 4
    rep = with_mesh(GraphSpec(n_nodes=100, d_max=16, e_cap=64), mesh)
    assert rep.partition == "replicated"
    assert rep.word_block == rep.n_words
    with pytest.raises(ValueError):
        GraphSpec(n_nodes=8, d_max=4, e_cap=8, partition="columns")


def test_partitioned_requires_mesh():
    from repro.core import DynamicGraph

    with pytest.raises(ValueError):
        DynamicGraph(16, [(0, 1), (1, 2), (0, 2)], partition="nodes")


def test_partial_bitmap_slabs_partition_build_and_update():
    """Owner-local slab scatters partition the full build/update exactly:
    concatenating per-slab calls == the full-width call, bitwise."""
    from repro.core import GraphSpec, from_edge_list, build_bitmap
    from repro.core.graph import partial_bitmap, update_bitmap
    from repro.data.synthetic import powerlaw_graph

    n = 200
    edges = powerlaw_graph(n, 4, seed=5)
    spec = GraphSpec(n_nodes=n, d_max=n, e_cap=len(edges))
    st = from_edge_list(spec, edges)
    full = np.asarray(build_bitmap(spec, st, st.active))
    w = spec.n_words
    for s in (2, 7):
        if w % s:
            continue
        blk = w // s
        slabs = [np.asarray(partial_bitmap(spec, st.edges, st.active,
                                           word_offset=i * blk,
                                           word_count=blk))
                 for i in range(s)]
        assert np.array_equal(np.concatenate(slabs, axis=1), full), s

    # owner-local incremental clear == full clear
    dead = np.zeros(spec.e_cap, bool)
    dead[::3] = True
    dead = jnp.asarray(dead) & st.active
    u, v = st.edges[:, 0], st.edges[:, 1]
    after = np.asarray(update_bitmap(spec, jnp.asarray(full), u, v, dead,
                                     set_bits=False))
    blk = w // 2 if w % 2 == 0 else w
    slabs = [np.asarray(update_bitmap(
        spec, jnp.asarray(full[:, i * blk:(i + 1) * blk]), u, v, dead,
        set_bits=False, word_offset=i * blk, word_count=blk))
        for i in range(w // blk)]
    assert np.array_equal(np.concatenate(slabs, axis=1), after)


# ---------------------------------------------------------------------------
# memory telemetry: gauges, exposition, service stats
# ---------------------------------------------------------------------------

def test_memory_gauges_and_exposition():
    from repro.core import DynamicGraph
    from repro.obs import metrics as obs_metrics
    from repro.obs import expo

    g = DynamicGraph(64, [(0, 1), (1, 2), (0, 2)])
    reg = obs_metrics.REGISTRY
    assert reg.value("truss_bitmap_bytes") == g.spec.bitmap_bytes_per_device
    assert reg.value("truss_state_bytes_per_device") == \
        g.spec.state_bytes_per_device
    text = expo.render(reg)
    assert "# TYPE truss_bitmap_bytes gauge" in text
    assert "# TYPE truss_state_bytes_per_device gauge" in text


def test_service_stats_memory_block():
    from repro.service import TrussService

    svc = TrussService(32, [(0, 1), (1, 2), (0, 2)], support_method="bitmap")
    mem = svc.stats()["memory"]
    assert mem["partition"] == "replicated" and mem["n_shards"] == 1
    assert mem["bitmap_bytes_per_device"] == svc.graph.spec.bitmap_bytes_per_device
    assert mem["state_bytes_per_device"] > mem["bitmap_bytes_per_device"]


# ---------------------------------------------------------------------------
# partitioned peel == replicated peel, bitwise, per device count (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("devices", [1, 2, 4])
def test_partitioned_peel_bitwise_equal(devices):
    """Full decompose (both engines), cached-bitmap frozen-boundary
    re-peel, partitioned build and owner-local update: all bitwise-equal
    to the replicated single-device engine, with each device holding a
    1/S word slab."""
    run_py(f"""
import numpy as np, jax.numpy as jnp
from repro.core import graph
from repro.core.peel import peel, recompute_peel
from repro.data.synthetic import powerlaw_graph
from repro.launch.mesh import make_shard_mesh

n = 700
edges = powerlaw_graph(n, 4, seed=11)
spec0 = graph.GraphSpec(n_nodes=n, d_max=n, e_cap=len(edges) + 64)
st0 = graph.from_edge_list(spec0, edges)
phi_ref, ps_ref = peel(spec0, st0, st0.active, method="bitmap", engine="delta")
phi_ref = np.asarray(phi_ref)
_, ps_rc = recompute_peel(spec0, st0, st0.active, method="bitmap")

mesh = make_shard_mesh({devices})
spec = graph.with_mesh(spec0, mesh, partition="nodes")
st = graph.shard_state(spec, graph.pad_state(spec0, st0, spec), mesh)
assert spec.n_words == {devices} * spec.word_block

for eng, ref_stats in (("delta", ps_ref), ("recompute", ps_rc)):
    phi, ps = peel(spec, st, st.active, method="bitmap", engine=eng, mesh=mesh)
    assert np.array_equal(np.asarray(phi)[:spec0.e_cap], phi_ref), eng
    assert all(int(a) == int(b) for a, b in zip(ps, ref_stats)), eng

# partitioned build == full build; each device holds one 1/S slab
bm = graph.build_bitmap_partitioned(spec, st, st.active, mesh)
bm_full = graph.build_bitmap(spec, st, st.active)
assert np.array_equal(np.asarray(bm), np.asarray(bm_full))
for sh in bm.addressable_shards:
    assert sh.data.shape == (spec.n_nodes, spec.word_block)

# cached-bitmap frozen-boundary re-peel (the fused batch path's shape)
st = st._replace(phi=jnp.asarray(
    np.pad(phi_ref, (0, spec.e_cap - spec0.e_cap))))
st0 = st0._replace(phi=jnp.asarray(phi_ref))
rng = np.random.default_rng(0)
for trial in range(3):
    mask = jnp.asarray(rng.random(spec.e_cap) < 0.4) & st.active
    p1, s1 = peel(spec0, st0, mask[:spec0.e_cap] & st0.active,
                  bitmap=bm_full, method="bitmap", engine="delta")
    p2, s2 = peel(spec, st, mask, bitmap=bm, method="bitmap",
                  engine="delta", mesh=mesh)
    assert np.array_equal(np.asarray(p2)[:spec0.e_cap], np.asarray(p1)), trial
    assert all(int(a) == int(b) for a, b in zip(s1, s2)), trial

# owner-local incremental update == full update
u, v = st.edges[:, 0], st.edges[:, 1]
dead = np.zeros(spec.e_cap, bool); dead[:50] = True
dead = jnp.asarray(dead) & st.active
bm2 = graph.update_bitmap_partitioned(spec, bm, u, v, dead, set_bits=False,
                                      mesh=mesh)
bm2_full = graph.update_bitmap(spec, bm_full, u, v, dead, set_bits=False)
assert np.array_equal(np.asarray(bm2), np.asarray(bm2_full))
print("ok")
""", devices=devices)


@pytest.mark.parametrize("devices", [2, 4])
def test_partitioned_service_flush_bitwise(devices):
    """A node-partitioned TrussService runs the identical write stream to
    the same phi as a replicated single-device service; restore and a
    cross-layout replica (replicated tailing partitioned) agree too."""
    run_py(f"""
import numpy as np, tempfile
from repro.data.synthetic import powerlaw_graph
from repro.service import TrussService, TrussStore
from repro.cluster.replica import Replica
from repro.launch.mesh import make_shard_mesh

n = 400
edges = powerlaw_graph(n, 4, seed=3)
base, extra = edges[:-60], edges[-60:]

def drive(svc):
    for (u, v) in extra:
        svc.submit(1, int(u), int(v))
    svc.flush()
    return np.asarray(svc.graph.state.phi)

phi_ref = drive(TrussService(n, base, flush_every=8,
                             support_method="bitmap"))
mesh = make_shard_mesh({devices})
root = tempfile.mkdtemp()
svc = TrussService(n, base, flush_every=8, support_method="bitmap",
                   mesh=mesh, partition="nodes", store=TrussStore(root))
phi = drive(svc)
assert np.array_equal(phi[:phi_ref.shape[0]], phi_ref)
mem = svc.stats()["memory"]
assert mem["partition"] == "nodes" and mem["n_shards"] == {devices}
svc.snapshot()

svc2 = TrussService.restore(TrussStore(root), support_method="bitmap",
                            mesh=mesh, partition="nodes")
assert np.array_equal(np.asarray(svc2.graph.state.phi), phi)
rep = Replica(root, support_method="bitmap", mesh=mesh, partition="nodes")
rep.poll()
assert np.array_equal(np.asarray(rep.svc.graph.state.phi), phi)
rep2 = Replica(root, support_method="bitmap")   # cross-layout tail
rep2.poll()
assert np.array_equal(np.asarray(rep2.svc.graph.state.phi)[:phi_ref.shape[0]],
                      phi_ref)
print("ok")
""", devices=devices)


@pytest.mark.parametrize("devices", [2, 4])
def test_partitioned_closure_searches_and_matches_replicated(devices):
    """Under ``partition="nodes"`` each device holds one word slab of the
    bitmap, and the fused closure still probes it: it gathers the chunk's
    endpoint rows from the slabs, where it once binary-searched the sorted
    rows.  The replicated graph probes its full bitmap.  Both reach the
    oracle's phi and the same value in every ``PeelStats`` field, the
    closure's included."""
    run_py(f"""
from repro.core import DynamicGraph, oracle
from repro.core.batch import closure_probes_bitmap
from repro.launch.mesh import make_shard_mesh

N = 40
BASE = [(i, j) for i in range(N) for j in range(i + 1, N) if (i * 7 + j) % 4 == 0]
present = set(BASE)
absent = [(i, j) for i in range(N) for j in range(i + 1, N)
          if (i, j) not in present]
batches = [[(0, *e) for e in BASE[:6]] + [(1, *e) for e in absent[:6]],
           [(1, *e) for e in absent[6:14]],
           [(0, *e) for e in BASE[6:14]]]
g1 = DynamicGraph(N, BASE, support_method="bitmap")
g2 = DynamicGraph(N, BASE, support_method="bitmap",
                  mesh=make_shard_mesh({devices}), partition="nodes")
assert g2.spec.word_block * {devices} == g2.spec.n_words
assert closure_probes_bitmap(g1.spec, g1._bitmap_cache())
assert closure_probes_bitmap(g2.spec, g2._bitmap_cache())
orc = oracle.Oracle(N, BASE)
for batch in batches:
    g1.apply_batch(batch, strategy="fused")
    g2.apply_batch(batch, strategy="fused")
    orc.apply(batch)
    assert g1.last_closure_probed and g2.last_closure_probed
    assert g1.phi_dict() == g2.phi_dict() == orc.phi
    s1, s2 = g1.last_peel_stats, g2.last_peel_stats
    assert [int(x) for x in s1] == [int(x) for x in s2], (s1, s2)
    assert int(s1.closure_iters) >= 1
print("ok")
""", devices=devices)


def test_partitioned_service_counts_probe_and_exchange():
    """A ``TrussService`` over four devices with the bitmap node-partitioned
    runs three 50/50 churn generations to the oracle's phi, compiling
    nothing after the first.  Each generation's closure probes the slabs,
    and the exchange counter
    advances by its collectives' payload computed from the shapes: a psum
    of ``int32[e_cap]`` per wave and two ``uint32[256, W]`` row gathers per
    closure iteration.  A replicated service probes too and exchanges
    nothing."""
    run_py("""
import numpy as np
from repro.core import oracle
from repro.data.synthetic import powerlaw_graph
from repro.launch.mesh import make_shard_mesh
from repro.obs import metrics
from repro.service import TrussService

N = 2000
edges = [(int(u), int(v)) for u, v in powerlaw_graph(N, 4, seed=5)]


def churn(seed):
    rng = np.random.default_rng(seed)
    present = set(edges)
    out = []
    for _ in range(3):
        cur = sorted(present)
        dels = [cur[i] for i in rng.choice(len(cur), 50, replace=False)]
        ins = set()
        while len(ins) < 50:
            a, b = sorted(int(x) for x in rng.choice(N, 2, replace=False))
            if (a, b) not in present and (a, b) not in dels:
                ins.add((a, b))
        present = (present - set(dels)) | ins
        out.append([(0, a, b) for a, b in dels]
                   + [(1, a, b) for a, b in sorted(ins)])
    return out, present


BATCHES, FINAL = churn(11)
PHI = oracle.scratch_phi(N, FINAL)


def counters():
    return (metrics.REGISTRY.value("truss_closure_bitmap_probe_total"),
            metrics.REGISTRY.value("truss_exchange_bytes_total"))


def drive(svc):
    probes0, bytes0 = counters()
    expected = 0
    for gen, batch in enumerate(BATCHES):
        if gen == 1:
            compiles = metrics.REGISTRY.value("truss_xla_compiles_total")
        svc.submit_many(batch)
        ps = svc.graph.last_peel_stats
        w = svc.graph.spec.n_words
        expected += 4 * (int(ps.waves) * svc.graph.spec.e_cap
                         + int(ps.closure_iters) * 2 * 256 * w)
    assert svc.gen == 3
    # the state comes back in the placement it went in: after the first
    # generation nothing compiles
    assert metrics.REGISTRY.value("truss_xla_compiles_total") == compiles
    assert svc.graph.phi_dict() == PHI
    probes1, bytes1 = counters()
    assert probes1 - probes0 == 3
    return bytes1 - bytes0, expected


svc = TrussService(N, edges, flush_every=100, support_method="bitmap",
                   mesh=make_shard_mesh(4), partition="nodes")
assert svc.graph.spec.n_shards == 4
exchanged, expected = drive(svc)
assert exchanged == expected > 0, (exchanged, expected)
assert svc.stats()["counters"]["exchange_bytes"] >= exchanged

rep = TrussService(N, edges, flush_every=100, support_method="bitmap")
exchanged, expected = drive(rep)
assert exchanged == 0 < expected
print("ok")
""", devices=4)


# ---------------------------------------------------------------------------
# hypothesis sweep: random update batches x partition modes (full lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("devices", [2, 4])
def test_partition_property_sweep(devices):
    """Random update batches through fused maintenance: the node-partitioned
    graph stays bitwise-equal (phi + peel stats) to replicated and exact vs
    the oracle, for both partition modes.  Hypothesis runs inside the
    subprocess so every example reuses the compiled engines."""
    pytest.importorskip("hypothesis")
    run_py(f"""
import numpy as np
from hypothesis import given, settings, strategies as st
from repro.core import DynamicGraph, oracle
from repro.launch.mesh import make_shard_mesh

N = 14
mesh = make_shard_mesh({devices})
BASE = [(i, j) for i in range(N) for j in range(i + 1, N) if (i * 7 + j) % 3 == 0]


@st.composite
def update_batches(draw):
    present = set(BASE)
    ops = []
    for _ in range(draw(st.integers(1, 3))):
        batch = []
        for _ in range(draw(st.integers(1, 12))):
            pool_del = sorted(present)
            pool_ins = [(i, j) for i in range(N) for j in range(i + 1, N)
                        if (i, j) not in present]
            if pool_del and (not pool_ins or draw(st.booleans())):
                e = pool_del[draw(st.integers(0, len(pool_del) - 1))]
                present.discard(e); batch.append((0, *e))
            elif pool_ins:
                e = pool_ins[draw(st.integers(0, len(pool_ins) - 1))]
                present.add(e); batch.append((1, *e))
        ops.append(batch)
    return ops


@settings(max_examples=20, deadline=None)
@given(update_batches(), st.sampled_from(["replicated", "nodes"]))
def check(batches, partition):
    g1 = DynamicGraph(N, BASE, support_method="bitmap")
    g2 = DynamicGraph(N, BASE, support_method="bitmap", mesh=mesh,
                      partition=partition)
    orc = oracle.Oracle(N, BASE)
    for batch in batches:
        if not batch:
            continue
        g1.apply_batch(batch, strategy="fused")
        g2.apply_batch(batch, strategy="fused")
        orc.apply(batch)
        assert g1.phi_dict() == g2.phi_dict() == orc.phi, partition
        if g1.last_peel_stats is not None and g2.last_peel_stats is not None:
            assert all(int(a) == int(b) for a, b in
                       zip(g1.last_peel_stats, g2.last_peel_stats))


check()
print("ok")
""", devices=devices)
