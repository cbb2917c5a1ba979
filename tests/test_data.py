"""Data pipeline: determinism/resumability, sampler validity, stream validity."""
import numpy as np

from repro.configs import get_config
from repro.data import sampler, streams, synthetic


def test_token_stream_resumable():
    s1 = synthetic.TokenStream(100, 4, 16, seed=3)
    b1 = [s1.next() for _ in range(5)]
    state = s1.state_dict()
    s2 = synthetic.TokenStream.from_state(100, 4, 16, {"seed": 3, "step": 2})
    np.testing.assert_array_equal(b1[2]["tokens"], s2.next()["tokens"])
    # full restart reproduces everything
    s3 = synthetic.TokenStream(100, 4, 16, seed=3)
    np.testing.assert_array_equal(b1[0]["targets"], s3.next()["targets"])
    del state


def test_click_stream_deterministic():
    cfg = get_config("xdeepfm").smoke
    a = synthetic.ClickStream(cfg, 8, seed=1).next()
    b = synthetic.ClickStream(cfg, 8, seed=1).next()
    np.testing.assert_array_equal(a["sparse_ids"], b["sparse_ids"])
    assert a["multihot_ids"].shape == (8, cfg.n_multihot, cfg.bag_size)


def test_powerlaw_graph_properties():
    edges = synthetic.powerlaw_graph(200, 4, seed=0)
    assert len(edges) > 200  # connected-ish, >= m per node
    assert (edges[:, 0] < edges[:, 1]).all()
    keys = edges[:, 0] * 200 + edges[:, 1]
    assert len(np.unique(keys)) == len(keys)  # simple graph
    deg = np.bincount(edges.reshape(-1), minlength=200)
    assert deg.max() > 3 * np.median(deg[deg > 0])  # heavy tail


def test_fanout_sampler_validity():
    edges = synthetic.powerlaw_graph(300, 4, seed=1)
    csr = sampler.CSRGraph(300, edges)
    seeds = np.asarray([0, 5, 9])
    nodes, src, dst = sampler.fanout_sample(csr, seeds, (5, 3), seed=2)
    assert len(nodes) == len(set(nodes.tolist()))
    eset = {(int(a), int(b)) for a, b in edges} | {(int(b), int(a)) for a, b in edges}
    for s, d in zip(src, dst):
        assert (int(nodes[s]), int(nodes[d])) in eset  # sampled edges exist
    # fanout bound: level-1 in-edges per seed <= 5
    lvl1 = dst[: min(len(dst), 3 * 5)]
    assert (np.bincount(lvl1, minlength=3)[:3] <= 5).all()


def test_triplets_share_pivot_node():
    edges = synthetic.powerlaw_graph(50, 3, seed=2)
    src = np.concatenate([edges[:, 0], edges[:, 1]])
    dst = np.concatenate([edges[:, 1], edges[:, 0]])
    t_kj, t_ji = sampler.build_triplets(src, dst, 50, max_per_edge=4, seed=0)
    assert len(t_kj)
    for kj, ji in zip(t_kj[:200], t_ji[:200]):
        assert dst[kj] == src[ji]          # share pivot j
        assert src[kj] != dst[ji]          # k != i (no degenerate angle)
    counts = np.bincount(t_ji, minlength=len(src))
    assert counts.max() <= 4               # cap respected


def test_update_stream_valid_in_order():
    edges = synthetic.powerlaw_graph(40, 3, seed=3)
    ups = streams.make_update_stream(edges, 40, 60, seed=4)
    present = {(int(a), int(b)) for a, b in edges}
    for op, a, b in ups:
        e = (int(a), int(b))
        if op == streams.OP_INSERT:
            assert e not in present
            present.add(e)
        else:
            assert e in present
            present.discard(e)


def test_graph_update_stream_resumable():
    edges = synthetic.powerlaw_graph(30, 3, seed=5)
    s1 = streams.GraphUpdateStream(edges, 30, chunk=4, seed=6)
    c1 = [s1.next() for _ in range(3)]
    s2 = streams.GraphUpdateStream(edges, 30, chunk=4, seed=6)
    c2 = [s2.next() for _ in range(3)]
    for a, b in zip(c1, c2):
        np.testing.assert_array_equal(a, b)


def _rank_sampled_chunk(present: set, n: int, chunk: int, seed, step):
    """Reference sampler: every delete sorts the live edge set afresh."""
    rng = np.random.default_rng((seed, step))
    out = []
    for _ in range(chunk):
        if rng.random() < 0.5 or not present:
            while True:
                a, b = rng.integers(0, n, size=2)
                a, b = int(min(a, b)), int(max(a, b))
                if a != b and (a, b) not in present:
                    break
            present.add((a, b))
            out.append((streams.OP_INSERT, a, b))
        else:
            e = sorted(present)[rng.integers(len(present))]
            present.discard(e)
            out.append((streams.OP_DELETE, *e))
    return out


def test_graph_update_stream_sorted_view_matches_per_delete_sort():
    """The stream keeps one sorted view of its edge set in step with every
    insert and delete; it must draw what sorting at each delete draws,
    across chunks and across a state_dict round trip."""
    edges = synthetic.powerlaw_graph(50, 3, seed=8)
    present = {(int(a), int(b)) for a, b in edges}
    s = streams.GraphUpdateStream(edges, 50, chunk=25, seed=9)
    for step in range(4):
        if step == 2:
            s = streams.GraphUpdateStream(edges, 50, chunk=25, seed=9)\
                .load_state_dict(s.state_dict())
        got = [tuple(map(int, r)) for r in s.next()]
        assert got == _rank_sampled_chunk(present, 50, 25, 9, step)
