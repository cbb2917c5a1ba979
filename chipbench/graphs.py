"""The deployments' graphs, made on the host.

``powerlaw_graph`` is a copy of the program's sound generator
(``repro.data.synthetic.powerlaw_graph``), kept here so that no change to
the program can move the data the benchmark runs on.  A configuration's
graph comes from its ``graph`` entry (``make_graph``), seeded by the
entry's own ``structure_seed``; a run's ``--seed`` only relabels its nodes
(``relabelled``), so every seed does the same work in another order.
"""
from __future__ import annotations

import numpy as np


GENERATORS = ("powerlaw_graph",)


def make_graph(spec: dict) -> np.ndarray:
    """The edges a configuration's ``graph`` entry names: its ``generator``
    and that generator's arguments; any other key is refused."""
    args = dict(spec)
    generator = args.pop("generator", None)
    if generator not in GENERATORS:
        raise ValueError(f"unknown graph generator {generator!r}")
    return powerlaw_graph(**args)


def powerlaw_graph(n_nodes: int, m_per_node: int, structure_seed: int,
                   triangle_p: float) -> np.ndarray:
    """Barabasi-Albert-style preferential attachment (triangle-rich variant:
    each new node also closes one triangle among its targets), producing the
    clustered power-law structure of the paper's social-network datasets.

    Vectorized Batagelj-Brandes construction (arXiv:cond-mat/0412004 idiom):
    instead of per-node rejection sampling over a growing occurrence list
    (O(n·m) interpreter time), every draw indexes the *virtual* occurrence
    array ``[seed pairs | (src, tgt) pairs]`` whose even slots are known up front; odd-slot references (a
    draw landing on an earlier draw's target) strictly decrease, so pointer
    doubling resolves them all in O(log) numpy passes.  Self-loop draws are
    dropped and duplicates deduped (multi-edge draws ARE the preferential
    bias in B-B), and triangle closing connects each new node's first two
    targets with probability ``triangle_p``.  Emits 10^6 edges in well
    under a second.  Seeded by ``structure_seed`` + deterministic.
    """
    n = n_nodes
    rng = np.random.default_rng(structure_seed)
    n0 = min(m_per_node + 1, n)
    seed_u, seed_v = (x.astype(np.int64) for x in np.triu_indices(n0, k=1))
    if n <= n0:
        return np.stack([seed_u, seed_v], 1)
    m = m_per_node
    nv = n - n0
    e0 = len(seed_u)
    l0 = 2 * e0                        # occurrence slots owned by the clique
    nd = m * nv                        # one (src, tgt) occurrence pair per draw
    src = n0 + np.arange(nd) // m      # the new node of each draw
    pos = l0 + 2 * np.arange(nd)       # occurrence count before draw i
    r = (rng.random(nd) * pos).astype(np.int64)
    # resolve r -> node id: seed slots and even draw slots are known; an odd
    # draw slot l0+2j+1 IS draw j's target, i.e. whatever r[j] points at —
    # pointer values strictly decrease, so doubling converges in O(log nd)
    while True:
        odd = (r >= l0) & ((r - l0) % 2 == 1)
        if not odd.any():
            break
        r[odd] = r[(r[odd] - l0) // 2]
    tgt = np.where(
        r < l0,
        np.where(r % 2 == 0, seed_u[np.minimum(r // 2, e0 - 1)],
                 seed_v[np.minimum(r // 2, e0 - 1)]),
        src[np.maximum(r - l0, 0) // 2])
    # triangle closing: connect each new node's first two targets (the
    # vectorized form of the reference generator's clustered variant)
    if m >= 2:
        t2 = tgt.reshape(nv, m)
        vnode = n0 + np.arange(nv)
        a, b = t2[:, 0], t2[:, 1]
        close = ((a != b) & (a != vnode) & (b != vnode)
                 & (rng.random(nv) < triangle_p))
        cu = np.minimum(a[close], b[close])
        cv = np.maximum(a[close], b[close])
    else:
        cu = cv = np.zeros(0, np.int64)
    ok = src != tgt
    allu = np.concatenate([seed_u, np.minimum(src[ok], tgt[ok]), cu])
    allv = np.concatenate([seed_v, np.maximum(src[ok], tgt[ok]), cv])
    _, first = np.unique(allu * n + allv, return_index=True)
    order = np.sort(first)
    allu, allv = allu[order], allv[order]
    out = np.stack([allu, allv], 1)
    return out[np.lexsort((out[:, 1], out[:, 0]))]


def relabelled(edges: np.ndarray, n: int,
               rng) -> tuple[np.ndarray, np.ndarray]:
    """The same graph with its node ids permuted by ``rng``: ``(edges, perm)``
    where node ``i`` of ``edges`` became ``perm[i]``; edges come back as
    sorted ``(u < v)`` rows."""
    perm = rng.permutation(n)
    out = np.sort(perm[np.asarray(edges)], axis=1)
    return out[np.lexsort((out[:, 1], out[:, 0]))], perm
