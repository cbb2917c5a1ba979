"""Fused batched truss maintenance — B updates, one frontier loop.

``maintenance.apply_updates`` replays a stream through ``lax.scan``, paying B
full frontier-loop launches.  This module applies the whole batch jointly
(the batch-processing idea of Jakkula & Karypis, "Streaming and Batch
Algorithms for Truss Decomposition"), in three fused stages:

1. **Structural pass** — one vectorized multi-edge
   ``apply_edge_batch_struct`` call edits every affected adjacency row in a
   single batched sort (graph.py).

2. **Affected set** — per-update Theorem 1/2 ranges seed a *single shared
   frontier*: deletion stats are taken on the pre-update graph (partner
   edges of a deleted edge must be enumerated before the triangles vanish),
   insertion stats on the post-update graph (so triangles formed between two
   edges of the same batch are seen).  A BFS closure over triangle adjacency
   collects every edge that could transitively change.  Its partner lookup
   follows what the call is given (``closure_probes_bitmap``): with the
   post-update adjacency bitmap (the bitmap method, either layout) a
   frontier edge's partner edges are one bit test per adjacency slot in its
   endpoints' bitmap rows (``peel.chunk_partner_edges_bitmap``); with no
   bitmap (``sorted``) it binary-searches the sorted rows
   (``peel.chunk_partners``).  Both admit the same set.  Under
   ``partition="nodes"`` the closure runs under ``shard_map``
   (``_sharded_closure``): the edge state enters replicated once, and each
   iteration all-gathers only the chunk's endpoint rows from the word
   slabs.

   Range soundness for batches: the per-update ranges compose across a
   *homogeneous* batch (insert-only or delete-only) because partner sets
   only grow (insert) or only shrink (delete) along the sequential replay,
   and per-edge phi drift is bounded by the batch size (Lemma 2); the union
   range is therefore widened by ``n_updates - 1`` on both ends.  A *mixed*
   batch stays range-filterable as long as no inserted edge shares a node
   with a deleted edge — only then can one update change another's partner
   *set* (e.g. an insertion handing a deletion a low-phi partner no
   pre-update statistic sees) rather than just drift phi values.  When that
   separability check fails, the engine falls back to the unfiltered
   closure — re-decomposition of the affected component, the always-sound
   path.

3. **Frozen-boundary re-peel** — the shared peel engine (``peel.py``)
   recomputes phi for the affected set A with every edge outside A
   *frozen*: at level k a frozen edge supports a triangle iff ``phi_old >=
   k`` and it retires from the qualifying subgraph when k passes its phi.
   ``engine='auto'`` (default) picks the wave discipline per method —
   incremental-bitmap delta waves for ``bitmap``, dense recompute waves
   for ``sorted``; 'delta'/'recompute' force one for A/B runs.  Peeling
   removes a frozen edge exactly at its true level, so for any A that
   contains every changed edge the result equals the from-scratch
   decomposition (maximality argument: survivors of level k restricted to A
   are exactly ``k-truss ∩ A``).  Inserted edges are always members of A,
   so their phi falls out of the same peel — no separate Algorithm-2
   new-edge fixpoint is needed.

``st`` is **donated**: the caller's pre-update GraphState buffers are reused
for the output instead of reallocated per generation (service flush path) —
do not read the passed-in state after the call.

Exactness at every batch size is enforced against ``oracle.py`` by the
tier-1 tests in ``tests/test_batch_maintenance.py``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .graph import (GraphSpec, GraphState, apply_edge_batch_struct,
                    lookup_edge, state_sharding, triangle_partners)
from .maintenance import _NEG, _POS
from .peel import (chunk_partner_edges_bitmap, chunk_partners, gather_phi,
                   peel as run_peel, scatter_or)


#: Frontier edges the closure expands per iteration (``batch_maintain``'s
#: ``batch``)
CLOSURE_CHUNK = 256


class _ExpandCarry(NamedTuple):
    affected: jax.Array   # bool[E_cap] — the affected set A so far
    frontier: jax.Array   # bool[E_cap] — edges whose partners are unexplored
    it: jax.Array


def closure_probes_bitmap(spec: GraphSpec, bitmap) -> bool:
    """Whether ``batch_maintain``'s closure tests partner edges against
    ``bitmap`` rather than binary-searching the sorted adjacency rows: for
    any bitmap handed in, on either layout.  Under ``partition="nodes"``
    each device holds one word slab, and the closure all-gathers the
    chunk's endpoint rows from the slabs (``_sharded_closure``), so the
    probe sees exactly the replicated bits.  Only ``sorted``, which keeps no
    bitmap, searches.  Fixed when the program is traced; the service counts
    the generations it holds for (``truss_closure_bitmap_probe_total``)."""
    return bitmap is not None


def exchange_bytes(spec: GraphSpec, waves: int, closure_iters: int) -> int:
    """Bytes a generation of the node-partitioned bitmap layout puts
    between chips, counted as the payload of its collectives from the
    shapes: each peel wave psums ``int32[e_cap]`` partial supports
    (``peel._partitioned_bitmap_peel``), and each closure iteration
    all-gathers the chunk's two endpoint-row blocks, ``uint32[CLOSURE_CHUNK,
    W]`` each (``_sharded_closure`` at ``batch_maintain``'s default
    chunk).  The one-off replication of the edge state at the entry of
    both, and what the partitioner inserts in ``ranges`` and
    ``structural``, are not counted."""
    return 4 * (waves * spec.e_cap
                + closure_iters * 2 * CLOSURE_CHUNK * spec.n_words)


def _admissible(active, phi, lo, hi, e_cap: int):
    """The closure's admission test: partner ids (sentinel ``e_cap``) of
    live edges whose phi lies in ``[lo, hi]``."""
    act_pad = jnp.concatenate([active, jnp.zeros((1,), bool)])
    phi_pad = jnp.concatenate([phi, jnp.zeros((1,), jnp.int32)])

    def admissible(ids, msk):
        idc = jnp.minimum(ids, e_cap)
        p = phi_pad[idc]
        return msk & (ids < e_cap) & act_pad[idc] & (p >= lo) & (p <= hi)

    return admissible


def _close(affected0, admissible, partners, e_cap: int, batch: int):
    """BFS over triangle adjacency from ``affected0``: each iteration
    expands up to ``batch`` frontier edges through ``partners(idx)`` (pairs
    of ``(partner ids, mask)``) and admits what ``admissible`` passes.
    Returns ``(affected, iterations)``."""
    def exp_cond(c: _ExpandCarry):
        return jnp.any(c.frontier) & (c.it < e_cap)

    def exp_body(c: _ExpandCarry):
        idx = jnp.nonzero(c.frontier, size=batch, fill_value=e_cap)[0]
        live = idx < e_cap
        sides = partners(idx)
        nxt = jnp.zeros((e_cap,), bool)
        for ids, msk in sides:
            nxt = scatter_or(nxt, ids, admissible(ids, msk))
        nxt = nxt & ~c.affected
        processed = scatter_or(jnp.zeros((e_cap,), bool), idx, live)
        return _ExpandCarry(c.affected | nxt,
                            (c.frontier & ~processed) | nxt, c.it + 1)

    out = jax.lax.while_loop(
        exp_cond, exp_body,
        _ExpandCarry(affected0, affected0, jnp.int32(0)))
    return out.affected, out.it


def _sharded_closure(spec: GraphSpec, st: GraphState, affected0, lo, hi,
                     bitmap, *, batch: int, mesh):
    """The closure over a word-sharded bitmap (``partition="nodes"``), under
    ``shard_map`` so the partitioner never gathers the bitmap: the edge
    state and the seeds enter replicated (one gather of the row-blocked
    edge arrays per generation), every device runs the replicated loop,
    and each iteration all-gathers the chunk's endpoint rows from the slabs
    (``chunk_partner_edges_bitmap(axis=...)``).  Same affected set and
    iteration count as the replicated probe."""
    from jax.sharding import PartitionSpec as P

    e_cap, ax = spec.e_cap, spec.shard_axis

    def local_fn(edges, active, phi, nbr, eid, deg, affected0, lo, hi,
                 slab):
        lst = GraphState(edges, active, phi, nbr, eid, deg)
        return _close(
            affected0, _admissible(active, phi, lo, hi, e_cap),
            lambda idx: chunk_partner_edges_bitmap(spec, lst, idx, slab,
                                                   axis=ax),
            e_cap, batch)

    return jax.shard_map(local_fn, mesh=mesh,
                         in_specs=(P(),) * 9 + (P(None, ax),),
                         out_specs=(P(), P()),
                         check_vma=False)(*st, affected0, lo, hi, bitmap)


@partial(jax.jit, static_argnames=("spec", "batch", "method", "engine",
                                   "mesh"),
         donate_argnames=("st",))
def batch_maintain(spec: GraphSpec, st: GraphState,
                   del_a, del_b, del_valid,
                   ins_a, ins_b, ins_valid,
                   batch: int = CLOSURE_CHUNK, method: str = "sorted",
                   engine: str = "auto",
                   bitmap: jax.Array | None = None, mesh=None):
    """Apply B deletions + B insertions jointly and maintain phi exactly.

    All arrays are length-B int32/bool (padded, masked).  Deletions and
    insertions must be disjoint, structurally valid edge sets (host-side
    netting in ``DynamicGraph.apply_batch`` guarantees this).  ``bitmap``,
    when given (bitmap method), must be the adjacency bitmap of the
    POST-update active set (``DynamicGraph`` maintains it incrementally);
    the closure then probes it for partner edges instead of
    binary-searching the sorted rows (``closure_probes_bitmap``) — same
    affected set, fewer per-element gathers — replicated or word-sharded.
    ``mesh`` (static, hashable) runs the frozen-boundary re-peel
    edge-sharded over ``mesh[spec.shard_axis]`` — the structural pass is
    O(B·D) one-shot work left to the partitioner; the wave loop is where
    the devices go.  Under ``partition="nodes"`` the closure runs under
    ``shard_map`` against the word slabs (``_sharded_closure``).

    Returns ``(state, lo, hi, stats)`` — the post-update state, the
    widened union affected range (int32 scalars; ``lo > hi`` means nothing
    beyond the inserted edges themselves could change; ``hi`` is ``_POS``
    when the mixed-batch fallback ran the closure unfiltered) for index
    invalidation, and the re-peel ``PeelStats`` with the closure's share
    filled in: ``frontier`` is the affected-set size |A|,
    ``closure_iters`` the closure's while-loop iterations (each expands at
    most ``batch`` frontier edges), and ``closure_unfiltered`` 1 when the
    mixed-batch fallback ran it without the range filter.

    The body's four stages run under ``jax.named_scope`` — ``ranges``
    (Theorem-1/2 stats, union range, separability, seeds), ``structural``,
    ``closure`` and ``repeel`` — so every op of the compiled program names
    its stage in its metadata (``op_name``, the profiler's ``tf_op``).
    """
    e_cap, n = spec.e_cap, spec.n_nodes
    bsz = del_a.shape[0]

    with jax.named_scope("ranges"):
        # ---- per-deletion Theorem-1 stats on the PRE-update graph --------
        du = jnp.minimum(del_a, del_b).astype(jnp.int32)
        dv = jnp.maximum(del_a, del_b).astype(jnp.int32)
        duc = jnp.where(del_valid, du, 0)
        dvc = jnp.where(del_valid, dv, 0)
        d_id1, d_id2, d_val = triangle_partners(spec, st, duc, dvc)  # [B, D]
        d_val = d_val & del_valid[:, None]
        dp = jnp.minimum(gather_phi(st.phi, d_id1, e_cap),
                         gather_phi(st.phi, d_id2, e_cap))
        d_kmin = jnp.min(jnp.where(d_val, dp, _POS), axis=1)
        d_slot, _ = jax.vmap(
            lambda a, b: lookup_edge(spec, st, a, b))(duc, dvc)
        d_phi = gather_phi(st.phi, d_slot, e_cap)
        d_has = jnp.any(d_val, axis=1)
        d_lo = jnp.where(d_has, d_kmin, _POS)
        d_hi = jnp.where(d_has, d_phi, _NEG)

    with jax.named_scope("structural"):
        # ---- one vectorized structural pass ------------------------------
        st1, ins_slots = apply_edge_batch_struct(
            spec, st, del_a, del_b, del_valid, ins_a, ins_b, ins_valid)

    with jax.named_scope("ranges"):
        # ---- per-insertion Theorem-2 stats on the POST-update graph ------
        iu = jnp.minimum(ins_a, ins_b).astype(jnp.int32)
        iv = jnp.maximum(ins_a, ins_b).astype(jnp.int32)
        iuc = jnp.where(ins_valid, iu, 0)
        ivc = jnp.where(ins_valid, iv, 0)
        i_id1, i_id2, i_val = triangle_partners(spec, st1, iuc, ivc)  # [B, D]
        i_val = i_val & ins_valid[:, None]

        slots_sorted = jnp.sort(jnp.where(ins_valid, ins_slots, e_cap))

        def is_new(ids):
            pos = jnp.minimum(jnp.searchsorted(slots_sorted, ids.reshape(-1)),
                              bsz - 1).reshape(ids.shape)
            return (ids < e_cap) & (slots_sorted[pos] == ids)

        new1, new2 = is_new(i_id1), is_new(i_id2)
        q1 = gather_phi(st1.phi, i_id1, e_cap)
        q2 = gather_phi(st1.phi, i_id2, e_cap)
        ex1 = i_val & ~new1
        ex2 = i_val & ~new2
        kmin_ex = jnp.minimum(jnp.min(jnp.where(ex1, q1, _POS), axis=1),
                              jnp.min(jnp.where(ex2, q2, _POS), axis=1))
        kmax_ex = jnp.maximum(jnp.max(jnp.where(ex1, q1, _NEG), axis=1),
                              jnp.max(jnp.where(ex2, q2, _NEG), axis=1))
        n_common = jnp.sum(i_val, axis=1).astype(jnp.int32)
        any_new = jnp.any(i_val & (new1 | new2), axis=1)
        i_has = jnp.any(i_val, axis=1)
        # A partner edge that is itself new has no pre-update phi: drop the
        # kmin/kmax refinements and keep the always-sound bounds [2, |S|+1].
        i_lo = jnp.where(i_has, jnp.where(any_new, jnp.int32(2), kmin_ex),
                         _POS)
        i_hi = jnp.where(i_has,
                         jnp.where(any_new, n_common + 1,
                                   jnp.minimum(n_common + 1, kmax_ex)), _NEG)

        # ---- union range, widened for sequential drift; mixed fallback ---
        n_del = jnp.sum(del_valid).astype(jnp.int32)
        n_ins = jnp.sum(ins_valid).astype(jnp.int32)
        slack = jnp.maximum(n_del + n_ins - 1, 0)
        lo_u = jnp.minimum(jnp.min(d_lo), jnp.min(i_lo)) - slack
        hi_u = jnp.maximum(jnp.max(d_hi), jnp.max(i_hi)) + slack
        # Range filtering stays sound for a mixed batch iff no inserted edge
        # touches a deleted edge's endpoint: only such an insertion can hand a
        # deletion a partner edge that no pre-update statistic sees (and vice
        # versa change a partner *set* rather than just drift phi, which the
        # slack already covers).  Otherwise fall back to the unfiltered closure
        # — re-decomposition of the affected component.
        del_nodes = jnp.zeros((n + 1,), bool)
        del_nodes = del_nodes.at[jnp.where(del_valid, du, n)].set(True)
        del_nodes = del_nodes.at[jnp.where(del_valid, dv, n)].set(True)
        touches = ins_valid & (del_nodes[jnp.where(ins_valid, iu, n)]
                               | del_nodes[jnp.where(ins_valid, iv, n)])
        separable = (n_del == 0) | (n_ins == 0) | ~jnp.any(touches)
        lo = jnp.where(separable, jnp.maximum(lo_u, 2), jnp.int32(2))
        hi = jnp.where(separable, hi_u, _POS)
        unfiltered = (~separable).astype(jnp.int32)
        # insert-only propagation still needs a seed; delete-only the same —
        # an empty union range (lo > hi) admits no seeds and no expansion.

        admissible = _admissible(st1.active, st1.phi, lo, hi, e_cap)

        # ---- shared frontier seeds ---------------------------------------
        seeds = jnp.zeros((e_cap,), bool)
        for ids, msk in ((d_id1, d_val), (d_id2, d_val),
                         (i_id1, i_val), (i_id2, i_val)):
            seeds = scatter_or(seeds, ids, admissible(ids, msk))
        seeds = seeds & st1.active
        # new edges are always in A
        affected0 = scatter_or(seeds, ins_slots, ins_valid)

    with jax.named_scope("closure"):
        # ---- BFS closure over triangle adjacency -------------------------
        probe = closure_probes_bitmap(spec, bitmap)
        if probe and spec.partition == "nodes" and mesh is not None:
            affected, iters = _sharded_closure(spec, st1, affected0, lo, hi,
                                               bitmap, batch=batch,
                                               mesh=mesh)
        else:
            def partners(idx):
                if probe:
                    return chunk_partner_edges_bitmap(spec, st1, idx, bitmap)
                p1, p2, tval = chunk_partners(spec, st1, idx, st1.active)
                return ((p1, tval), (p2, tval))

            affected, iters = _close(affected0, admissible, partners, e_cap,
                                     batch)

    with jax.named_scope("repeel"):
        # ---- frozen-boundary re-peel (shared engine, peel.py) ------------
        phi_final, stats = run_peel(spec, st1, affected, bitmap=bitmap,
                                    method=method, engine=engine, mesh=mesh)
    stats = stats._replace(closure_iters=iters,
                           closure_unfiltered=unfiltered)
    out = st1._replace(phi=phi_final)
    if mesh is not None:
        # hand the state back in the placement it came in
        # (``graph.shard_state``), so the next generation runs this same
        # program instead of compiling one for the partitioner's layout
        out = GraphState(*map(jax.lax.with_sharding_constraint, out,
                              state_sharding(spec, mesh)))
    return out, lo, hi, stats
