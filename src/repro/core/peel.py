"""Delta-peel engine — the shared support-maintenance core of every peel loop.

Every peel consumer in this repo (full ``decompose``, the fused batch
engine's frozen-boundary re-peel, and the service flush path behind both)
used to recompute the support of *all* alive edges on every wave — O(E·D)
searchsorted work (or a full [N, W] bitmap rebuild) per wave, O(waves·E·D)
per call.  This module now owns every peel loop through one entry point
(``peel``) with two wave disciplines — ``recompute_peel`` (the dense
baseline, generalized to the frozen boundary) and ``delta_peel``, the delta
structure of the truss literature (Wang & Cheng, arXiv:1205.6693; Jakkula &
Karypis, arXiv:1908.10550):

1. (``sorted``) support is computed **once** up front, then each wave
   enumerates the triangles of the *killed frontier only* and
   scatter-subtracts support deltas onto the surviving partner edges —
   O(wave·D) work per wave, O(E·D + Σ wave·D) per call;
2. (``bitmap``) the killed edges' bits are cleared out of the adjacency
   bitmap incrementally (``update_bitmap``, O(wave) real updates) instead
   of rebuilding the whole [N, W] array, and the fused ``peel_wave``
   Pallas kernel re-derives (support, kill-frontier) from the cleared
   bitmap in a single AND+popcount+threshold VMEM pass — no triangle
   enumeration at all, and no second trip over the edge axis for the
   threshold compare.

**The delta invariant.**  Support within the qualifying subgraph only ever
*decreases* during a peel, and every unit of decrease is witnessed by a
triangle that contains a killed edge.  So after the up-front pass it
suffices to walk killed edges' triangles: for a killed edge e in triangle
{e, f, g} (all three alive at wave start), each *surviving* member must lose
exactly one support unit for that triangle.  When several triangle members
die in the same wave the enumeration would double-subtract, so the scatter
is tie-broken by edge slot: the lowest-slot killed edge of the triangle owns
the update.  Frozen edges (the fused batch engine's unchanged boundary)
retire from the qualifying subgraph when the level passes their phi, and
their exits flow through the *same* removal machinery — a retire is a kill
that keeps its phi.

**When each method wins.**  ``sorted`` (searchsorted row intersection)
keeps memory at O(N·D) and its waves touch only [chunk, D] gathers — the
sparse-friendly default for huge N.  ``bitmap`` pays O(N·W) bitmap memory
but its waves are pure VPU AND+popcount over [E, W] words (the
``peel_wave`` kernel) with O(wave) incremental bit-clearing — it wins
whenever the bitmap fits (dense or mid-sized N, and on TPU where the
fused VMEM pass replaces gather-heavy searchsorted), especially with a
cached structural bitmap (``DynamicGraph``) making even the up-front pass
gather-only.

**Mesh partitioning.**  Every discipline above also runs edge-sharded under
a ``Mesh`` (``peel(..., mesh=...)``): edge-indexed arrays are row-blocked
along ``spec.shard_axis`` (``GraphSpec.n_shards`` blocks), each shard runs
the identical wave arithmetic on its block — per-shard AND+popcount support
through the same fused kernel, per-shard kill-frontier emission — and the
waves stay in lockstep through exactly **one all-reduce per wave for the
global frontier/threshold decision** (a packed 4-lane ``pmin`` carrying
min-support, min-frozen-phi, any-dead and any-work; the loop condition
reads the reduced flag, so ``cond`` itself is collective-free).  The bitmap
disciplines additionally exchange bitmap data: the delta engine psums only
the bits each shard *cleared* this wave (uint32 sums of disjoint-bit
partial bitmaps are exact bitwise-ors), the recompute engine psums partial
bitmaps of the full qualifying set.  All reductions are integer min/sum of
the same values the single-device loop computes, so the sharded engine is
**bitwise-equal** to ``mesh=None`` at every device count — enforced by
``tests/test_sharded.py``.

**Node-partitioned bitmap** (``spec.partition == "nodes"``).  The layouts
above replicate the [N, W] bitmap on every device; at million-edge scale
that allocation is the ceiling.  ``_partitioned_bitmap_peel`` instead
gives device ``s`` ownership of the word-column slab
``bm[:, s·W/S:(s+1)·W/S]`` and inverts the sharding: the *edge-indexed*
wave state is replicated inside the loop while the *bitmap* is split.
Per wave every shard computes the partial support of every peel edge
against its slab (popcounts over disjoint word slabs sum exactly) in
``gather_chunk``-row batches, and one integer ``psum`` of ``int32[E]``
partials recovers exact support — zero bitmap bytes on the wire.  The
kill/retire/phi/k arithmetic then runs identically on every shard, so the
loop condition needs no further collective; builds and incremental
clears scatter owner-locally (out-of-slab bits drop — every bit has one
owner).  Both engines (``delta``: incremental slab clearing;
``recompute``: per-wave slab rebuild) mirror their replicated twins'
arithmetic exactly, and ``phi`` lands sharded ``P(shard_axis)`` via a
per-shard block slice.  Bitwise-equal to ``partition="replicated"`` at
every device count — enforced end-to-end by ``tests/test_scale.py``;
the memory curve is ``benchmarks/million_edge.py``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .graph import (GraphSpec, GraphState, build_bitmap, partial_bitmap,
                    support, support_all, support_all_bitmap,
                    triangle_partners, update_bitmap)

_INF = jnp.int32(2**30)

# ---------------------------------------------------------------------------
# wave primitives — shared with maintenance.py (Algorithms 1/2 frontiers)
# and batch.py (affected-set BFS closure)
# ---------------------------------------------------------------------------

def gather_phi(phi: jax.Array, ids: jax.Array, e_cap: int) -> jax.Array:
    """phi gather with OOB/sentinel (e_cap) ids mapping to 0."""
    return jnp.where(ids < e_cap, phi[jnp.minimum(ids, e_cap - 1)], 0)


def gather_mask(mask: jax.Array, ids: jax.Array) -> jax.Array:
    """bool-mask gather with OOB/sentinel ids mapping to False."""
    e_cap = mask.shape[0]
    padded = jnp.concatenate([mask, jnp.zeros((1,), bool)])
    return padded[jnp.minimum(ids, e_cap)]


def scatter_or(mask: jax.Array, ids: jax.Array, cond: jax.Array) -> jax.Array:
    """mask |= cond scattered at ids (sentinel/e_cap ids dropped)."""
    e_cap = mask.shape[0]
    ids = jnp.where(cond, ids, e_cap)
    return mask.at[ids.reshape(-1)].set(True, mode="drop")


def chunk_partners(spec: GraphSpec, st: GraphState, idx: jax.Array,
                   alive: jax.Array):
    """Triangle partners of a compacted chunk of edge slots.

    ``idx`` is a fixed-size batch of edge slots (sentinel ``e_cap`` on dead
    rows).  Returns ``(p1, p2, tval)`` of shape [C, D]: partner-edge slot
    ids and a validity mask requiring a live row AND both partners in
    ``alive`` — i.e. ``tval`` marks exactly the triangles of the chunk edges
    that exist in the ``alive`` subgraph.  This is the one wave primitive
    behind the delta-peel engine and Algorithm 1/2 localSupport frontiers;
    the batch engine's affected-set closure uses it only when it has no
    replicated adjacency bitmap (``chunk_partner_edges_bitmap`` otherwise).
    """
    live = idx < spec.e_cap
    idxc = jnp.minimum(idx, spec.e_cap - 1)
    u = jnp.minimum(st.edges[idxc, 0], spec.n_nodes - 1)
    v = jnp.minimum(st.edges[idxc, 1], spec.n_nodes - 1)
    p1, p2, tval = triangle_partners(spec, st, u, v)
    tval = (tval & live[:, None]
            & gather_mask(alive, p1) & gather_mask(alive, p2))
    return p1, p2, tval


def chunk_partner_edges_bitmap(spec: GraphSpec, st: GraphState,
                               idx: jax.Array, bitmap: jax.Array):
    """Partner edges of a compacted chunk of edge slots, by bitmap probe.

    ``idx`` is a fixed-size batch of edge slots (sentinel ``e_cap`` on dead
    rows) and ``bitmap`` the full-width ``uint32[N, W]`` adjacency bitmap of
    the edge set ``st`` holds (``graph.partial_bitmap``'s layout: node w is
    bit ``w % 32`` of word ``w // 32``).  For a chunk edge (u, v), slot j of
    u's row names a partner edge iff its neighbor ``nbr[u, j]`` is a
    neighbor of v: one bit test in row ``bitmap[v]``, no search, and the
    bit also says that edge is present.  Returns ``((eid_u, val_u),
    (eid_v, val_v))``, each of shape [C, D]: the partner *edges* on u's
    side and on v's side, unpaired — slot (i, j) of one side and of the
    other need not close the same triangle, so this answers "which edges
    share a triangle with the chunk", not "which triangles".
    """
    n = spec.n_nodes
    live = idx < spec.e_cap
    idxc = jnp.minimum(idx, spec.e_cap - 1)
    u = jnp.minimum(st.edges[idxc, 0], n - 1)
    v = jnp.minimum(st.edges[idxc, 1], n - 1)

    def side(a, rows):
        w = st.nbr[a]                                   # [C, D]
        wc = jnp.minimum(w, n - 1)                      # sentinel N clamped
        words = jnp.take_along_axis(rows, wc >> 5, axis=1)   # word w // 32
        bit = (words >> (wc & 31).astype(jnp.uint32)) & jnp.uint32(1)
        return st.eid[a], live[:, None] & (w < n) & (bit == 1)

    return side(u, bitmap[v]), side(v, bitmap[u])


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class PeelStats(NamedTuple):
    """Instrumentation returned by every peel-engine call (int32 scalars).

    waves:    while-loop iterations (kill chunks + level advances)
    kills:    peelable edges assigned a phi
    deltas:   scatter-subtracted support updates (the work the recompute
              engine would have paid O(E·D) per wave for)
    frontier: peelable edges entering the peel (|peel_mask ∩ active| — the
              affected-set size on the fused batch path, E on a full
              decompose)
    closure_iters: the fused batch's affected-set closure iterations
    closure_unfiltered: 1 when that closure ran without the range filter
              (the mixed-batch fallback); ``batch.batch_maintain`` fills
              both in, a peel alone leaves them 0

    Every engine (delta/recompute, single-device/sharded) fills every
    field identically, so the sharded bitwise-equality tests compare these
    elementwise.  ``stats_dict`` converts to host ints for span attributes
    and the metrics registry; ``EMPTY_STATS`` is the no-peel record the
    progressive Algorithm-1/2 paths report (host ints, all zero).
    """
    waves: jax.Array
    kills: jax.Array
    deltas: jax.Array
    frontier: jax.Array = 0
    closure_iters: jax.Array = 0
    closure_unfiltered: jax.Array = 0


EMPTY_STATS = PeelStats(0, 0, 0, 0)


def stats_dict(ps: PeelStats) -> dict:
    """Host-int dict of a ``PeelStats`` (``int()`` blocks until device
    arrays land — call only after the peel's results are needed anyway)."""
    return {"waves": int(ps.waves), "kills": int(ps.kills),
            "deltas": int(ps.deltas), "frontier": int(ps.frontier),
            "closure_iters": int(ps.closure_iters),
            "closure_unfiltered": int(ps.closure_unfiltered)}


class _Carry(NamedTuple):
    alive: jax.Array   # bool[E] — current qualifying subgraph (peel + frozen)
    phi: jax.Array     # int32[E]
    sup: jax.Array     # int32[E] — support within alive, delta-maintained
    bm: jax.Array      # uint32[N, W] qual bitmap (bitmap method; else [1,1])
    k: jax.Array
    waves: jax.Array
    kills: jax.Array
    deltas: jax.Array


def peel(spec: GraphSpec, st: GraphState, peel_mask: jax.Array,
         bitmap: jax.Array | None = None, method: str = "sorted",
         engine: str = "auto", chunk: int = 64, mesh=None):
    """The one peel entry point every consumer routes through.

    ``engine='auto'`` picks the measured-faster wave discipline per method:
    ``bitmap`` → ``delta`` (incremental bit-clearing + the fused
    ``peel_wave`` kernel — the hot path), ``sorted`` → ``recompute`` (XLA's
    dense [E, D] searchsorted wave outruns sparse compaction/scatter on
    today's backends; the delta discipline stays selectable and is where
    the asymptotics point as E grows).  Returns ``(phi, PeelStats)``.

    ``mesh``: optional ``jax.sharding.Mesh`` — run the same wave discipline
    edge-sharded over ``mesh[spec.shard_axis]`` (bitwise-equal to
    ``mesh=None``; see the module docstring).  ``mesh=None`` is exactly the
    single-device engine.
    """
    if engine == "auto":
        engine = "delta" if method == "bitmap" else "recompute"
    if mesh is not None:
        return sharded_peel(spec, st, peel_mask, bitmap=bitmap, method=method,
                            engine=engine, mesh=mesh)
    if engine == "delta":
        return delta_peel(spec, st, peel_mask, bitmap=bitmap, method=method,
                          chunk=chunk)
    if engine != "recompute":
        raise ValueError(f"unknown engine {engine!r}")
    return recompute_peel(spec, st, peel_mask, method=method)


@partial(jax.jit, static_argnames=("spec", "method", "chunk"))
def delta_peel(spec: GraphSpec, st: GraphState, peel: jax.Array,
               bitmap: jax.Array | None = None, method: str = "sorted",
               chunk: int = 64):
    """Peel ``peel``-masked edges against a frozen boundary; returns
    ``(phi int32[E_cap], PeelStats)``.

    Active edges outside ``peel`` are *frozen*: at level k they support
    triangles iff their (unchanged) ``st.phi >= k``, and they retire from
    the qualifying subgraph — through the same removal machinery as kills —
    when k passes their phi.  ``peel = st.active`` is a full decomposition.

    ``sorted``: support is delta-maintained by killed-frontier triangle
    enumeration, chunked under a triangle budget (a dead edge's alive
    triangle count IS its maintained support, so the admitted sub-chunk's
    cumulative support bounds the compaction buffer exactly).

    ``bitmap``: the wave needs no triangle enumeration at all — the dead
    edges' bits are cleared out of the adjacency bitmap incrementally
    (O(wave) scatter instead of the per-wave O(E) rebuild), and the fused
    ``peel_wave`` kernel re-derives (support, kill-frontier) from the
    cleared bitmap in one AND+popcount+threshold pass.  ``bitmap``, when
    given, must be the adjacency bitmap of ``st.active`` (e.g.
    ``DynamicGraph``'s incrementally-maintained cache), which also skips
    the up-front O(E) build.
    """
    e_cap, n = spec.e_cap, spec.n_nodes
    peel = peel & st.active
    frozen = st.active & ~peel
    fphi = st.phi
    alive0 = peel | (frozen & (fphi >= 3))

    if method == "bitmap":
        phi, stats = _peel_bitmap(spec, st, peel, frozen, fphi, alive0, bitmap)
    elif method == "sorted":
        phi, stats = _peel_sorted(spec, st, peel, frozen, fphi, alive0, chunk)
    else:
        raise ValueError(f"unknown method {method!r}")
    return phi, stats._replace(frontier=jnp.sum(peel, dtype=jnp.int32))


@partial(jax.jit, static_argnames=("spec", "method"))
def recompute_peel(spec: GraphSpec, st: GraphState, peel: jax.Array,
                   method: str = "sorted"):
    """Per-wave full support recomputation against a frozen boundary — the
    engine's dense discipline (and the pre-delta baseline): every wave
    recomputes the support of the whole qualifying subgraph, O(waves·E·D)
    total.  Same contract as ``delta_peel``; ``PeelStats.deltas`` is 0."""
    e_cap = spec.e_cap
    peel = peel & st.active
    frozen = st.active & ~peel
    fphi = st.phi
    if method == "bitmap":
        sup_fn = lambda qual: support_all_bitmap(spec, st, qual)
    else:
        sup_fn = lambda qual: support_all(spec, st, qual)

    def cond(carry):
        alive, phi, k, waves, kills = carry
        return jnp.any(alive) & (waves < 8 * e_cap)

    def body(carry):
        alive, phi, k, waves, kills = carry
        # An edge counts toward level-k support iff it is an unpeeled member
        # of the peel set or a frozen edge whose (unchanged) phi keeps it in
        # the k-truss.
        qual = alive | (frozen & (fphi >= k))
        sup = sup_fn(qual)
        kill = alive & (sup < k - 2)
        any_kill = jnp.any(kill)
        phi = jnp.where(kill, k - 1, phi)
        alive = alive & ~kill
        # level fixpoint -> jump k past dead levels (see delta_peel)
        min_sup = jnp.min(jnp.where(alive, sup, _INF))
        j2 = jnp.min(jnp.where(frozen & (fphi >= k), fphi, _INF)) + 1
        k_jump = jnp.maximum(jnp.minimum(min_sup + 3, j2), k + 1)
        k = jnp.where(any_kill, k, k_jump)
        return (alive, phi, k, waves + 1,
                kills + jnp.sum(kill, dtype=jnp.int32))

    init = (peel, st.phi, jnp.int32(3), jnp.int32(0), jnp.int32(0))
    _, phi, _, waves, kills = jax.lax.while_loop(cond, body, init)
    return (jnp.where(st.active, phi, 0),
            PeelStats(waves, kills, jnp.int32(0),
                      jnp.sum(peel, dtype=jnp.int32)))


def _peel_bitmap(spec, st, peel, frozen, fphi, alive0, bitmap):
    """Kill-wave loop over the incrementally-cleared adjacency bitmap."""
    from ..kernels import ops as kernel_ops  # kernels never import core

    e_cap, n = spec.e_cap, spec.n_nodes
    eu = jnp.minimum(st.edges[:, 0], n - 1)
    ev = jnp.minimum(st.edges[:, 1], n - 1)

    if bitmap is None:
        bm0 = build_bitmap(spec, st, alive0)
    else:
        # the provided bitmap covers st.active: clear the bits of edges
        # outside the initial qualifying set (frozen with phi < 3)
        bm0 = update_bitmap(spec, bitmap, st.edges[:, 0], st.edges[:, 1],
                            st.active & ~alive0, set_bits=False)

    def cond(c: _Carry):
        return jnp.any(c.alive & peel) & (c.waves < 8 * e_cap)

    def body(c: _Carry):
        # one fused pass over the current bitmap: support of every peelable
        # edge + the level-k kill frontier (frozen support is never read —
        # frozen edges retire by level, not threshold)
        sup, kill = kernel_ops.peel_wave(c.bm[eu], c.bm[ev],
                                         c.alive & peel, c.k)
        retire = c.alive & frozen & (fphi < c.k)
        dead = kill | retire
        any_dead = jnp.any(dead)

        phi = jnp.where(kill, c.k - 1, c.phi)
        alive = c.alive & ~dead
        # clear the whole wave's bits at once — O(wave) real updates
        bm = update_bitmap(spec, c.bm, st.edges[:, 0], st.edges[:, 1],
                           dead, set_bits=False)

        # level fixpoint -> jump k past dead levels: nothing peels before an
        # alive edge's support bound (min sup + 3) or before the frozen
        # boundary next shrinks (min frozen phi exits at phi + 1)
        min_sup = jnp.min(jnp.where(alive & peel, sup, _INF))
        min_frz = jnp.min(jnp.where(alive & frozen, fphi, _INF))
        k_next = jnp.maximum(c.k + 1, jnp.minimum(min_sup + 3, min_frz + 1))
        k = jnp.where(any_dead, c.k, k_next)

        return _Carry(alive, phi, sup, bm, k, c.waves + 1,
                      c.kills + jnp.sum(kill, dtype=jnp.int32),
                      c.deltas + 2 * jnp.sum(dead, dtype=jnp.int32))

    init = _Carry(alive0, st.phi, jnp.zeros((e_cap,), jnp.int32), bm0,
                  jnp.int32(3), jnp.int32(0), jnp.int32(0), jnp.int32(0))
    out = jax.lax.while_loop(cond, body, init)
    return (jnp.where(st.active, out.phi, 0),
            PeelStats(out.waves, out.kills, out.deltas))


def _peel_sorted(spec, st, peel, frozen, fphi, alive0, chunk):
    """Killed-frontier triangle-delta loop (searchsorted row intersection)."""
    e_cap = spec.e_cap
    sup0 = support_all(spec, st, alive0)
    bm0 = jnp.zeros((1, 1), jnp.uint32)  # unused; keeps the carry uniform

    # Triangle-budget admission: scattering the raw [chunk, D] delta masks
    # would cost chunk·D scatter updates per wave even though ~all entries
    # are sentinel padding (D is sized by the hub degree).  A dead edge's
    # alive triangle count IS its maintained support (< k-2 for kills), so
    # the cumulative support of the admitted sub-chunk bounds the number of
    # real deltas — compact them into a fixed buffer and scatter only those.
    budget = max(chunk, 2 * spec.d_max)
    compact = 2 * (budget + spec.d_max)  # ≤ 2 decs per admitted triangle

    def cond(c: _Carry):
        return jnp.any(c.alive & peel) & (c.waves < 8 * e_cap)

    def body(c: _Carry):
        # dead set at level k: peelable edges below threshold + frozen edges
        # whose level has passed.  Kills evaluated before pending retire
        # deltas land are still sound: support only decreases, so an edge
        # under threshold on the stale (higher) value stays under it.
        retire = c.alive & frozen & (fphi < c.k)
        kill = c.alive & peel & (c.sup < c.k - 2)
        dead = kill | retire
        any_dead = jnp.any(dead)

        # admit dead edges in slot order while their cumulative triangle
        # count fits the compaction buffer (the first always fits: its
        # triangles are bounded by d_max); the rest stay pending — the
        # level cannot advance until every dead edge has been processed.
        w_e = jnp.where(dead, c.sup + 1, 0)
        csum = jnp.cumsum(w_e)
        dcount = jnp.cumsum(dead.astype(jnp.int32))
        admit = dead & ((csum <= budget) & (dcount <= chunk) | (dcount == 1))

        idx = jnp.nonzero(admit, size=chunk, fill_value=e_cap)[0].astype(jnp.int32)
        live = idx < e_cap
        idxc = jnp.minimum(idx, e_cap - 1)
        in_chunk = scatter_or(jnp.zeros((e_cap,), bool), idx, live)

        # triangles of the killed frontier only (both partners alive at wave
        # start); tie-break multi-kill triangles by slot so each surviving
        # partner loses exactly one unit per dead triangle
        p1, p2, tval = chunk_partners(spec, st, idx, c.alive)
        c1 = gather_mask(in_chunk, p1)
        c2 = gather_mask(in_chunk, p2)
        own = idx[:, None]
        dec1 = tval & ~c1 & (~c2 | (own < p2))
        dec2 = tval & ~c2 & (~c1 | (own < p1))
        flat = jnp.concatenate([jnp.where(dec1, p1, e_cap).reshape(-1),
                                jnp.where(dec2, p2, e_cap).reshape(-1)])
        upd = jnp.nonzero(flat < e_cap, size=compact, fill_value=flat.shape[0])[0]
        ids = jnp.where(upd < flat.shape[0],
                        flat[jnp.minimum(upd, flat.shape[0] - 1)], e_cap)
        sup = c.sup.at[ids].add(-1, mode="drop")

        kill_rows = live & kill[idxc]
        phi = c.phi.at[jnp.where(kill_rows, idx, e_cap)].set(c.k - 1, mode="drop")
        alive = c.alive & ~in_chunk

        # level fixpoint -> jump k past dead levels: nothing peels before an
        # alive edge's support bound (min sup + 3) or before the frozen
        # boundary next shrinks (min frozen phi exits at phi + 1)
        min_sup = jnp.min(jnp.where(alive & peel, sup, _INF))
        min_frz = jnp.min(jnp.where(alive & frozen, fphi, _INF))
        k_next = jnp.maximum(c.k + 1, jnp.minimum(min_sup + 3, min_frz + 1))
        k = jnp.where(any_dead, c.k, k_next)

        return _Carry(alive, phi, sup, c.bm, k, c.waves + 1,
                      c.kills + jnp.sum(kill_rows, dtype=jnp.int32),
                      c.deltas + jnp.sum(dec1, dtype=jnp.int32)
                      + jnp.sum(dec2, dtype=jnp.int32))

    init = _Carry(alive0, st.phi, sup0, bm0, jnp.int32(3),
                  jnp.int32(0), jnp.int32(0), jnp.int32(0))
    out = jax.lax.while_loop(cond, body, init)
    return (jnp.where(st.active, out.phi, 0),
            PeelStats(out.waves, out.kills, out.deltas))


# ---------------------------------------------------------------------------
# mesh-partitioned engine — the same wave disciplines, edge-sharded
# ---------------------------------------------------------------------------

class _ShardCarry(NamedTuple):
    alive: jax.Array   # bool[block] — local rows of the qualifying subgraph
    phi: jax.Array     # int32[block]
    sup: jax.Array     # int32[block]
    bm: jax.Array      # uint32[N, W] replicated qual bitmap (else [1, 1])
    k: jax.Array
    waves: jax.Array
    kills: jax.Array   # local kill count (psum'd on exit)
    deltas: jax.Array
    go: jax.Array      # bool — global any-work flag from the decision pmin


def _decision(min_sup_l, min_frz_l, any_dead_l, any_work_l, ax):
    """THE one all-reduce per wave: a packed 4-lane pmin carrying the
    global min peelable support, min frozen phi, any-dead and any-work
    flags (encoded 0 = true so min == logical any).  Returns
    ``(min_sup, min_frz, any_dead, go)``; the loop condition reads ``go``
    from the carry, so ``cond`` needs no collective of its own."""
    packed = jnp.stack([min_sup_l, min_frz_l,
                        1 - any_dead_l.astype(jnp.int32),
                        1 - any_work_l.astype(jnp.int32)])
    packed = jax.lax.pmin(packed, ax)
    return packed[0], packed[1], packed[2] == 0, packed[3] == 0


def sharded_peel(spec: GraphSpec, st: GraphState, peel_mask: jax.Array,
                 bitmap: jax.Array | None = None, method: str = "bitmap",
                 engine: str = "delta", mesh=None):
    """Mesh-partitioned ``peel``: same contract, same bits, many devices.

    Edge-indexed arrays enter sharded over ``mesh[spec.shard_axis]`` (one
    row block per shard, ``shard_state``); node-indexed tables and the
    adjacency bitmap are replicated.  Per wave each shard computes support
    and the kill frontier for its own block only; cross-shard coupling is
    the decision pmin plus, for the bitmap methods, a psum of disjoint-bit
    partial bitmaps (delta: cleared bits only; recompute: the full
    qualifying set) and, for sorted recompute, an all-gather of the local
    qualifying masks.  Wave-by-wave arithmetic is identical to the
    single-device loops, so phi and PeelStats are bitwise-equal.
    """
    if mesh is None:
        raise ValueError("sharded_peel requires a mesh (use peel otherwise)")
    if int(mesh.shape[spec.shard_axis]) != spec.n_shards:
        raise ValueError(
            f"mesh axis {spec.shard_axis!r} has "
            f"{int(mesh.shape[spec.shard_axis])} devices but spec declares "
            f"{spec.n_shards} shards (build the spec with graph.with_mesh)")
    if spec.partition == "nodes" and method == "bitmap":
        # node-partitioned bitmap: each device owns one word slab, supports
        # psum from per-slab partials (see _partitioned_bitmap_peel)
        if engine not in ("delta", "recompute"):
            raise ValueError(f"unknown engine {engine!r}")
        has_bitmap = bitmap is not None
        if bitmap is None:
            bitmap = jnp.zeros((1, spec.n_shards), jnp.uint32)  # placeholder
        phi, waves, kills, deltas, frontier = _partitioned_bitmap_peel(
            spec, st.edges, st.active, st.phi, peel_mask, bitmap,
            mesh=mesh, has_bitmap=has_bitmap, engine=engine)
        return phi, PeelStats(waves, kills, deltas, frontier)
    if engine == "delta":
        if method != "bitmap":
            raise ValueError(
                "the sorted delta discipline is not mesh-partitioned (its "
                "chunk-admission order is global); use engine='recompute' "
                "or method='bitmap'")
        has_bitmap = bitmap is not None
        if bitmap is None:
            bitmap = jnp.zeros((1, 1), jnp.uint32)  # placeholder, rebuilt inside
        phi, waves, kills, deltas, frontier = _sharded_delta_bitmap(
            spec, st.edges, st.active, st.phi, peel_mask, bitmap,
            mesh=mesh, has_bitmap=has_bitmap)
        return phi, PeelStats(waves, kills, deltas, frontier)
    if engine != "recompute":
        raise ValueError(f"unknown engine {engine!r}")
    phi, waves, kills, frontier = _sharded_recompute(
        spec, st.edges, st.active, st.phi, peel_mask, st.nbr, st.eid,
        mesh=mesh, method=method)
    return phi, PeelStats(waves, kills, jnp.int32(0), frontier)


@partial(jax.jit, static_argnames=("spec", "mesh", "has_bitmap"))
def _sharded_delta_bitmap(spec: GraphSpec, edges, active, phi0, peel_mask,
                          bitmap, *, mesh, has_bitmap):
    """Edge-sharded twin of ``_peel_bitmap``: incremental bit-clearing with
    the cleared bits psum'd across shards each wave (uint32 sums of
    disjoint-bit partials are exact bitwise-ors/clears), the fused
    ``peel_wave`` kernel running unchanged on each shard's row block."""
    from jax.sharding import PartitionSpec as P
    from ..kernels import ops as kernel_ops  # kernels never import core

    e_cap, n, ax = spec.e_cap, spec.n_nodes, spec.shard_axis

    def local_fn(edges, active, phi0, peelm, bitmap):
        peelm = peelm & active
        frozen = active & ~peelm
        fphi = phi0
        alive0 = peelm | (frozen & (fphi >= 3))
        if has_bitmap:
            # the provided bitmap covers st.active: clear the bits of edges
            # outside the initial qualifying set (frozen with phi < 3)
            bm0 = bitmap - jax.lax.psum(
                partial_bitmap(spec, edges, active & ~alive0), ax)
        else:
            bm0 = jax.lax.psum(partial_bitmap(spec, edges, alive0), ax)
        eu = jnp.minimum(edges[:, 0], n - 1)
        ev = jnp.minimum(edges[:, 1], n - 1)
        go0 = jax.lax.pmin(
            1 - jnp.any(peelm).astype(jnp.int32), ax) == 0

        def cond(c: _ShardCarry):
            return c.go & (c.waves < 8 * e_cap)

        def body(c: _ShardCarry):
            # the fused kernel on this shard's row block only
            sup, kill = kernel_ops.peel_wave(c.bm[eu], c.bm[ev],
                                             c.alive & peelm, c.k)
            retire = c.alive & frozen & (fphi < c.k)
            dead = kill | retire
            phi = jnp.where(kill, c.k - 1, c.phi)
            alive = c.alive & ~dead
            # data exchange: only the bits this wave cleared cross the wire
            bm = c.bm - jax.lax.psum(partial_bitmap(spec, edges, dead), ax)

            min_sup, min_frz, any_dead, go = _decision(
                jnp.min(jnp.where(alive & peelm, sup, _INF)),
                jnp.min(jnp.where(alive & frozen, fphi, _INF)),
                jnp.any(dead), jnp.any(alive & peelm), ax)
            k_next = jnp.maximum(c.k + 1, jnp.minimum(min_sup + 3, min_frz + 1))
            k = jnp.where(any_dead, c.k, k_next)
            return _ShardCarry(alive, phi, sup, bm, k, c.waves + 1,
                               c.kills + jnp.sum(kill, dtype=jnp.int32),
                               c.deltas + 2 * jnp.sum(dead, dtype=jnp.int32),
                               go)

        init = _ShardCarry(alive0, phi0, jnp.zeros_like(phi0), bm0,
                           jnp.int32(3), jnp.int32(0), jnp.int32(0),
                           jnp.int32(0), go0)
        out = jax.lax.while_loop(cond, body, init)
        return (jnp.where(active, out.phi, 0), out.waves,
                jax.lax.psum(out.kills, ax), jax.lax.psum(out.deltas, ax),
                jax.lax.psum(jnp.sum(peelm, dtype=jnp.int32), ax))

    mapped = jax.shard_map(local_fn, mesh=mesh,
                           in_specs=(P(ax, None), P(ax), P(ax), P(ax), P()),
                           out_specs=(P(ax), P(), P(), P(), P()),
                           check_vma=False)
    return mapped(edges, active, phi0, peel_mask, bitmap)


#: Row batch of the partitioned engine's per-wave support gathers: bounds
#: the [chunk, W/S] gather transient so million-edge bitmaps never
#: materialize an [E, W] intermediate (see kernels.ops.bitmap_support_gathered).
_GATHER_CHUNK = 8192


@partial(jax.jit, static_argnames=("spec", "mesh", "has_bitmap", "engine",
                                   "gather_chunk"))
def _partitioned_bitmap_peel(spec: GraphSpec, edges, active, phi0, peel_mask,
                             bitmap, *, mesh, has_bitmap, engine,
                             gather_chunk: int = _GATHER_CHUNK):
    """Node-partitioned twin of ``_peel_bitmap``/``recompute_peel``
    (``spec.partition == "nodes"``): device *s* holds only the bitmap word
    slab ``bm[:, s·Wb:(s+1)·Wb]`` — O(N·W/S) resident instead of the
    replicated engines' O(N·W) — and the edge-axis state (endpoints, masks,
    phi, k) is replicated inside the loop, so every device runs the exact
    single-device wave arithmetic.

    The per-wave exchange is **one psum of int32 partial supports**:
    ``sup(e) = popcount(bm[u] & bm[v]) = Σ_s popcount(slab_s[u] & slab_s[v])``
    decomposes exactly over word slabs (integer popcounts of disjoint
    columns), so the psum'd support is bitwise the replicated engines'
    support — no bitmap byte ever crosses the wire.  Kill/retire/phi/k then
    evaluate replicated on the psum'd value (no second collective; the loop
    condition is replicated too), and bit-clearing (delta) or slab rebuild
    (recompute) is owner-local — every bit has exactly one owner, the same
    disjoint-bits argument as ``partial_bitmap``.  phi AND PeelStats are
    therefore bitwise-equal to ``partition="replicated"`` at any device
    count (``tests/test_scale.py``).

    Support rows are gathered in ``gather_chunk``-row batches so the
    resident transient is [chunk, W/S], never [E, W] — the property that
    lets the scale tier run ≥1M-edge graphs per device.
    """
    from jax.sharding import PartitionSpec as P
    from ..kernels import ops as kernel_ops  # kernels never import core

    e_cap, n, ax = spec.e_cap, spec.n_nodes, spec.shard_axis
    wb = spec.word_block
    blk = e_cap // spec.n_shards

    def local_fn(edges, active, phi0, peelm, bitmap):
        off = jax.lax.axis_index(ax).astype(jnp.int32) * wb
        peelm = peelm & active
        frozen = active & ~peelm
        fphi = phi0
        alive0 = peelm | (frozen & (fphi >= 3))
        eu = jnp.minimum(edges[:, 0], n - 1)
        ev = jnp.minimum(edges[:, 1], n - 1)

        def psum_sup(slab):
            # THE one collective per wave: partial popcounts of this
            # device's word slab, summed into the exact full support
            part = kernel_ops.bitmap_support_gathered(slab, eu, ev,
                                                      chunk=gather_chunk)
            return jax.lax.psum(part, ax)

        if engine == "delta":
            if has_bitmap:
                # the provided (word-sharded) bitmap covers st.active:
                # drop the bits of edges outside the initial qualifying
                # set — owner-local, like every slab update
                bm0 = update_bitmap(spec, bitmap, edges[:, 0], edges[:, 1],
                                    active & ~alive0, set_bits=False,
                                    word_offset=off, word_count=wb)
            else:
                bm0 = partial_bitmap(spec, edges, alive0,
                                     word_offset=off, word_count=wb)

            def cond(c: _Carry):
                return jnp.any(c.alive & peelm) & (c.waves < 8 * e_cap)

            def body(c: _Carry):
                # the psum'd support is exactly the replicated engine's
                # peel_wave output; threshold AFTER the sum (a slab's
                # partial support must never meet k)
                sup = jnp.where(c.alive & peelm, psum_sup(c.bm), 0)
                kill = c.alive & peelm & (sup < c.k - 2)
                retire = c.alive & frozen & (fphi < c.k)
                dead = kill | retire
                any_dead = jnp.any(dead)

                phi = jnp.where(kill, c.k - 1, c.phi)
                alive = c.alive & ~dead
                bm = update_bitmap(spec, c.bm, edges[:, 0], edges[:, 1],
                                   dead, set_bits=False,
                                   word_offset=off, word_count=wb)

                min_sup = jnp.min(jnp.where(alive & peelm, sup, _INF))
                min_frz = jnp.min(jnp.where(alive & frozen, fphi, _INF))
                k_next = jnp.maximum(c.k + 1,
                                     jnp.minimum(min_sup + 3, min_frz + 1))
                k = jnp.where(any_dead, c.k, k_next)
                return _Carry(alive, phi, sup, bm, k, c.waves + 1,
                              c.kills + jnp.sum(kill, dtype=jnp.int32),
                              c.deltas + 2 * jnp.sum(dead, dtype=jnp.int32))

            init = _Carry(alive0, phi0, jnp.zeros_like(phi0), bm0,
                          jnp.int32(3), jnp.int32(0), jnp.int32(0),
                          jnp.int32(0))
            out = jax.lax.while_loop(cond, body, init)
            phi, waves = out.phi, out.waves
            kills, deltas = out.kills, out.deltas
        else:  # recompute: rebuild this device's slab from qual each wave
            def cond(carry):
                alive, phi, k, waves, kills = carry
                return jnp.any(alive) & (waves < 8 * e_cap)

            def body(carry):
                alive, phi, k, waves, kills = carry
                qual = alive | (frozen & (fphi >= k))
                slab = partial_bitmap(spec, edges, qual,
                                      word_offset=off, word_count=wb)
                sup = jnp.where(qual, psum_sup(slab), 0)
                kill = alive & (sup < k - 2)
                any_kill = jnp.any(kill)
                phi = jnp.where(kill, k - 1, phi)
                alive = alive & ~kill
                min_sup = jnp.min(jnp.where(alive, sup, _INF))
                j2 = jnp.min(jnp.where(frozen & (fphi >= k), fphi, _INF)) + 1
                k_jump = jnp.maximum(jnp.minimum(min_sup + 3, j2), k + 1)
                k = jnp.where(any_kill, k, k_jump)
                return (alive, phi, k, waves + 1,
                        kills + jnp.sum(kill, dtype=jnp.int32))

            init = (peelm, phi0, jnp.int32(3), jnp.int32(0), jnp.int32(0))
            _, phi, _, waves, kills = jax.lax.while_loop(cond, body, init)
            deltas = jnp.int32(0)

        frontier = jnp.sum(peelm, dtype=jnp.int32)
        phi = jnp.where(active, phi, 0)
        # hand phi back in the engine's edge-sharded placement (P(ax)):
        # every device computed the full replicated phi; emit its own block
        idx = jax.lax.axis_index(ax)
        phi_blk = jax.lax.dynamic_slice_in_dim(phi, idx * blk, blk)
        return phi_blk, waves, kills, deltas, frontier

    mapped = jax.shard_map(local_fn, mesh=mesh,
                           in_specs=(P(), P(), P(), P(), P(None, ax)),
                           out_specs=(P(ax), P(), P(), P(), P()),
                           check_vma=False)
    return mapped(edges, active, phi0, peel_mask, bitmap)


@partial(jax.jit, static_argnames=("spec", "mesh", "method"))
def _sharded_recompute(spec: GraphSpec, edges, active, phi0, peel_mask,
                       nbr, eid, *, mesh, method):
    """Edge-sharded twin of ``recompute_peel``: each wave recomputes the
    support of this shard's row block against the full qualifying subgraph
    — psum'd partial bitmaps (``bitmap``) or replicated adjacency rows
    against the all-gathered qualifying mask (``sorted``)."""
    from jax.sharding import PartitionSpec as P
    from ..kernels import ops as kernel_ops  # kernels never import core

    e_cap, n, ax = spec.e_cap, spec.n_nodes, spec.shard_axis
    if method not in ("sorted", "bitmap"):
        raise ValueError(f"unknown method {method!r}")

    def local_fn(edges, active, phi0, peelm, nbr, eid):
        peelm = peelm & active
        frozen = active & ~peelm
        fphi = phi0
        eu = jnp.minimum(edges[:, 0], n - 1)
        ev = jnp.minimum(edges[:, 1], n - 1)
        # node tables are replicated; triangle_partners/support only touch
        # nbr/eid, so the edge-axis fields can stay local-block sized
        nst = GraphState(edges=edges, active=active, phi=phi0,
                         nbr=nbr, eid=eid, deg=jnp.zeros((n,), jnp.int32))

        def sup_of(qual_l):
            if method == "bitmap":
                bm = jax.lax.psum(partial_bitmap(spec, edges, qual_l), ax)
                return jnp.where(qual_l, kernel_ops.bitmap_support(
                    bm[eu], bm[ev]), 0)
            qual_g = jax.lax.all_gather(qual_l, ax, tiled=True)
            return jnp.where(qual_l, support(spec, nst, eu, ev,
                                             alive=qual_g), 0)

        go0 = jax.lax.pmin(1 - jnp.any(peelm).astype(jnp.int32), ax) == 0

        def cond(carry):
            alive, phi, k, waves, kills, go = carry
            return go & (waves < 8 * e_cap)

        def body(carry):
            alive, phi, k, waves, kills, go = carry
            qual = alive | (frozen & (fphi >= k))
            sup = sup_of(qual)
            kill = alive & (sup < k - 2)
            phi = jnp.where(kill, k - 1, phi)
            alive = alive & ~kill
            min_sup, j2m, any_kill, go = _decision(
                jnp.min(jnp.where(alive, sup, _INF)),
                jnp.min(jnp.where(frozen & (fphi >= k), fphi, _INF)),
                jnp.any(kill), jnp.any(alive), ax)
            # level fixpoint -> jump k past dead levels (see recompute_peel)
            k_jump = jnp.maximum(jnp.minimum(min_sup + 3, j2m + 1), k + 1)
            k = jnp.where(any_kill, k, k_jump)
            return (alive, phi, k, waves + 1,
                    kills + jnp.sum(kill, dtype=jnp.int32), go)

        init = (peelm, phi0, jnp.int32(3), jnp.int32(0), jnp.int32(0), go0)
        alive, phi, _, waves, kills, _ = jax.lax.while_loop(cond, body, init)
        return (jnp.where(active, phi, 0), waves, jax.lax.psum(kills, ax),
                jax.lax.psum(jnp.sum(peelm, dtype=jnp.int32), ax))

    mapped = jax.shard_map(local_fn, mesh=mesh,
                           in_specs=(P(ax, None), P(ax), P(ax), P(ax), P(), P()),
                           out_specs=(P(ax), P(), P(), P()),
                           check_vma=False)
    return mapped(edges, active, phi0, peel_mask, nbr, eid)
