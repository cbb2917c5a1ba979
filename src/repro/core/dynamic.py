"""DynamicGraph — host-side wrapper around the jitted truss engine.

Owns capacity management (JAX arrays are fixed-shape; we re-allocate with
doubled capacity when edge slots or per-node degree headroom run out),
strategy selection (batchUpdate / progressiveUpdate / indexedUpdate, paper
Table 3), the update-range bookkeeping the index needs, and — for the
bitmap support method — a structural adjacency-bitmap cache that is updated
incrementally by every update path (``update_bitmap`` scatters, O(batch))
instead of being rebuilt from zero on each decompose / re-peel call.

The maintenance entry points (``insert/delete_edge_maintain``,
``batch_maintain``, ``apply_updates``) donate their input GraphState, so a
flush replaces ``self.state`` in-place at the buffer level — no
per-generation copy.

``mesh=...`` makes every peel this wrapper launches (the initial
decomposition, the fused batch re-peel, ``batch_update_then_decompose``)
run edge-sharded over ``mesh[shard_axis]`` — bitwise-equal to
``mesh=None``; ``e_cap`` is rounded up so the row blocks stay uniform
across regrowth.  The progressive single-update paths (Algorithms 1/2)
run no peel and stay single-device.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..obs import metrics as obs_metrics, trace as obs_trace
from . import batch, decomposition, maintenance
from .graph import (GraphSpec, GraphState, build_bitmap,
                    build_bitmap_partitioned, from_edge_list, lookup_edge,
                    pad_state, shard_state, update_bitmap,
                    update_bitmap_partitioned, with_mesh)
from .index import TrussIndex
from .peel import EMPTY_STATS

_PROGRESSIVE_N = obs_metrics.counter(
    "truss_progressive_updates_total",
    "single-edge Algorithm-1/2 maintenance operations")
_BITMAP_BYTES = obs_metrics.gauge(
    "truss_bitmap_bytes",
    "resident adjacency-bitmap bytes per device under the spec's bitmap "
    "partition (O(N*W) replicated, O(N*W/S) nodes)")
_STATE_BYTES = obs_metrics.gauge(
    "truss_state_bytes_per_device",
    "resident GraphState bytes per device: row-blocked edge arrays + "
    "replicated node tables + the per-device bitmap slab")


class DynamicGraph:
    """Mutable truss-maintained graph: owns a ``GraphState``, applies update
    batches (netted, auto progressive/fused), and serves phi/k-truss views."""

    def __init__(self, n_nodes: int, edges=(), d_max: int | None = None,
                 e_cap: int | None = None, support_method: str = "sorted",
                 tracked_ks: tuple[int, ...] = (), mesh=None,
                 shard_axis: str = "shard", partition: str = "replicated"):
        edges = np.asarray(list(edges), dtype=np.int64).reshape(-1, 2)
        deg = np.bincount(edges.reshape(-1), minlength=n_nodes) if edges.size else np.zeros(n_nodes)
        d_max = int(d_max or max(8, int(deg.max(initial=0)) * 2))
        e_cap = int(e_cap or max(16, len(edges) * 2))
        if partition != "replicated" and mesh is None:
            raise ValueError(
                f"partition={partition!r} needs a mesh (the bitmap slabs "
                "live one per device; pass mesh=... or keep 'replicated')")
        self.mesh = mesh
        self.spec = GraphSpec(n_nodes=n_nodes, d_max=d_max, e_cap=e_cap)
        if mesh is not None:
            # round e_cap up so edge arrays split into uniform row blocks;
            # every peel this wrapper launches then shards transparently
            self.spec = with_mesh(self.spec, mesh, shard_axis,
                                  partition=partition)
        # set-up spans end when their device work has landed, so the
        # start-up time is named by what it was spent on
        with obs_trace.span("graph.load", edges=len(edges)):
            self.state = (from_edge_list(self.spec, edges) if len(edges)
                          else None)
            if self.state is None:
                from .graph import empty_state
                self.state = empty_state(self.spec)
            jax.block_until_ready(self.state)
        if mesh is not None:
            # place the edge arrays on their shard row blocks up front so
            # the sharded peels skip the entry reshard
            with obs_trace.span("graph.place", shards=self.spec.n_shards):
                self.state = jax.block_until_ready(
                    shard_state(self.spec, self.state, mesh))
        self.support_method = support_method
        self._bitmap = None
        self._set_memory_gauges()
        bitmap = self._bitmap_cache()
        with obs_trace.span("graph.decompose", method=support_method):
            phi, stats = jax.block_until_ready(
                decomposition.decompose_with_stats(
                    self.spec, self.state, support_method, bitmap=bitmap,
                    mesh=self.mesh))
        self.state = self.state._replace(phi=phi)
        # every maintenance path records a PeelStats — never None (the
        # initial decomposition's peel counts as the first one) — and
        # whether it came from a fused closure that probed the bitmap
        self.last_peel_stats = stats
        self.last_closure_probed = False
        self.index = TrussIndex(self.spec, tracked_ks)
        # Host mirror of the present-edge set, kept in sync by every update
        # path so batch netting never forces a device->host transfer.
        self._present = {(int(min(u, v)), int(max(u, v))) for u, v in edges}

    @classmethod
    def from_state(cls, spec: GraphSpec, state: GraphState,
                   support_method: str = "sorted",
                   tracked_ks: tuple[int, ...] = (),
                   mesh=None, shard_axis: str = "shard",
                   partition: str = "replicated") -> "DynamicGraph":
        """Rebuild a wrapper around already-maintained arrays (checkpoint
        restore): phi is trusted as-is, no re-decomposition.  ``mesh``
        re-shards the restored state onto the mesh (padding the edge axis
        if the stored capacity doesn't split into uniform row blocks);
        ``partition`` selects the bitmap layout exactly as in ``__init__``
        (snapshots never store the bitmap, so a restore may change it)."""
        if partition != "replicated" and mesh is None:
            raise ValueError(
                f"partition={partition!r} needs a mesh (the bitmap slabs "
                "live one per device; pass mesh=... or keep 'replicated')")
        g = cls.__new__(cls)
        g.mesh = mesh
        g.spec = spec
        g.state = GraphState(*(jnp.asarray(x) for x in state))
        if mesh is not None:
            g.spec = with_mesh(spec, mesh, shard_axis, partition=partition)
            g.state = shard_state(g.spec, pad_state(spec, g.state, g.spec),
                                  mesh)
        g.support_method = support_method
        g._bitmap = None
        g._set_memory_gauges()
        g.last_peel_stats = EMPTY_STATS  # phi trusted as-is: no peel ran
        g.last_closure_probed = False
        g.index = TrussIndex(g.spec, tracked_ks)
        act = np.asarray(g.state.active)
        edges = np.asarray(g.state.edges)[act]
        g._present = {(int(min(u, v)), int(max(u, v))) for u, v in edges}
        return g

    # -- bitmap cache --------------------------------------------------------
    def _partitioned(self) -> bool:
        """Whether the cached bitmap lives word-sharded (one slab per
        device) rather than replicated."""
        return self.spec.partition == "nodes" and self.mesh is not None

    def _set_memory_gauges(self):
        """Publish the spec's per-device memory accounting — the same
        numbers BENCH_scale.json's memory curve reads, so the bench and
        operator dashboards can never disagree."""
        _BITMAP_BYTES.set(self.spec.bitmap_bytes_per_device)
        _STATE_BYTES.set(self.spec.state_bytes_per_device)

    def _bitmap_cache(self):
        """Adjacency bitmap of the active edge set (bitmap method only),
        built once and maintained incrementally by every update path.
        Under ``partition="nodes"`` it is built owner-local and placed
        word-sharded — O(N·W/S) resident per device."""
        if self.support_method != "bitmap":
            return None
        if self._bitmap is None:
            with obs_trace.span("graph.bitmap_build",
                                partition=self.spec.partition):
                if self._partitioned():
                    bm = build_bitmap_partitioned(
                        self.spec, self.state, self.state.active, self.mesh)
                else:
                    bm = build_bitmap(self.spec, self.state,
                                      self.state.active)
                self._bitmap = jax.block_until_ready(bm)
        return self._bitmap

    def exchange_bytes(self, stats: dict) -> int:
        """Bytes the node-partitioned bitmap layout put between chips for
        the peel that reported ``stats`` (``peel.stats_dict``): its waves'
        psums and its closure's row gathers (``batch.exchange_bytes``); 0
        on any other layout, which exchanges no bitmap work."""
        if self.support_method != "bitmap" or not self._partitioned():
            return 0
        return batch.exchange_bytes(self.spec, stats["waves"],
                                    stats["closure_iters"])

    def _bitmap_apply(self, dels, inss):
        """Fold structural edge changes into the cached bitmap (O(batch)
        scatter; no-op when the cache is cold or the method is sorted).
        Partitioned caches update owner-local — each device scatters only
        the bits landing in its word slab."""
        if self._bitmap is None:
            return

        def upd(bm, pairs, set_bits):
            if not len(pairs):
                return bm
            arr = np.asarray(pairs, np.int32).reshape(-1, 2)
            u, v = jnp.asarray(arr[:, 0]), jnp.asarray(arr[:, 1])
            valid = jnp.ones((len(arr),), bool)
            if self._partitioned():
                return update_bitmap_partitioned(self.spec, bm, u, v, valid,
                                                 set_bits=set_bits,
                                                 mesh=self.mesh)
            return update_bitmap(self.spec, bm, u, v, valid,
                                 set_bits=set_bits)

        self._bitmap = upd(upd(self._bitmap, dels, False), inss, True)

    # -- capacity ------------------------------------------------------------
    def _ensure_capacity(self, a: int, b: int, inserting: bool):
        need_realloc = False
        spec = self.spec
        if inserting:
            deg = np.asarray(self.state.deg)
            n_edges = int(np.asarray(self.state.active).sum())
            if n_edges + 1 > spec.e_cap or deg[a] + 1 > spec.d_max or deg[b] + 1 > spec.d_max:
                need_realloc = True
        if need_realloc:
            self._grow(extra_edge=(a, b))

    def _grow(self, extra_edge=None, min_d: int = 0, min_e: int = 0):
        """Double capacities and rebuild state (host path, rare)."""
        el = self.edge_list()
        deg = np.bincount(np.asarray(el).reshape(-1), minlength=self.spec.n_nodes) if len(el) else np.zeros(self.spec.n_nodes)
        if extra_edge is not None:
            deg[extra_edge[0]] += 1
            deg[extra_edge[1]] += 1
        s = self.spec.n_shards
        new_e = max(self.spec.e_cap * 2, len(el) + 16, min_e + 16)
        new_spec = GraphSpec(
            n_nodes=self.spec.n_nodes,
            d_max=max(self.spec.d_max * 2, int(deg.max(initial=0)) + 4, min_d + 4),
            e_cap=-(-new_e // s) * s,  # keep the shard row blocks uniform
            n_shards=s, shard_axis=self.spec.shard_axis,
            partition=self.spec.partition,
        )
        phi_old = self.phi_dict()
        self.spec = new_spec
        self.state = from_edge_list(new_spec, el) if len(el) else None
        if self.state is None:
            from .graph import empty_state
            self.state = empty_state(new_spec)
        # carry phi over (slot order is preserved by from_edge_list over el order)
        phi = np.zeros(new_spec.e_cap, np.int32)
        for i, (u, v) in enumerate(el):
            phi[i] = phi_old[(u, v)]
        self.state = self.state._replace(phi=jnp.asarray(phi))
        if self.mesh is not None:
            self.state = shard_state(self.spec, self.state, self.mesh)
        self._bitmap = None  # shape depends only on n_nodes, but rebuild anyway
        self._set_memory_gauges()
        self.index = TrussIndex(new_spec, self.index.tracked)
        self.index.invalidate_all()

    # -- updates ---------------------------------------------------------------
    def insert(self, a: int, b: int):
        """progressiveUpdate insertion (Algorithm 2)."""
        self._ensure_capacity(a, b, inserting=True)
        _lo, hi = self._range_of(a, b, inserting=True)
        self.state = maintenance.insert_edge_maintain(self.spec, self.state, a, b)
        # Algorithm-2 path: no peel ran — record the empty stats rather than
        # None so consumers (service stats, telemetry) never need guards
        self.last_peel_stats = EMPTY_STATS
        self.last_closure_probed = False
        _PROGRESSIVE_N.inc()
        # Other edges' phi moves only inside the Theorem-2 range, but the
        # inserted edge itself joins (and can merge components of) every
        # level k <= phi(e) <= hi + 1 — invalidate from the bottom.
        self.index.invalidate(2, max(hi, 1))
        self._present.add((min(a, b), max(a, b)))
        self._bitmap_apply((), [(min(a, b), max(a, b))])

    def delete(self, a: int, b: int):
        """progressiveUpdate deletion (Algorithm 1)."""
        _lo, hi = self._range_of(a, b, inserting=False)
        self.state = maintenance.delete_edge_maintain(self.spec, self.state, a, b)
        # Algorithm-1 path: no peel ran — empty stats, never None
        self.last_peel_stats = EMPTY_STATS
        self.last_closure_probed = False
        _PROGRESSIVE_N.inc()
        # The deleted edge leaves (and can split components of) every level
        # k <= phi(e), not just the Theorem-1 phi range.
        self.index.invalidate(2, max(hi, 1))
        self._present.discard((min(a, b), max(a, b)))
        self._bitmap_apply([(min(a, b), max(a, b))], ())

    def _range_of(self, a: int, b: int, inserting: bool):
        """Theorem 1/2 affected range for index invalidation."""
        id1, id2, valid, kmin, kmax, ns = maintenance._edge_partner_stats(
            self.spec, self.state, jnp.int32(a), jnp.int32(b))
        if not bool(jnp.any(valid)):
            return (1, 0)  # empty range
        kmin, kmax, ns = int(kmin), int(kmax), int(ns)
        if inserting:
            return (kmin, min(ns + 1, kmax))
        u, v = min(a, b), max(a, b)
        slot, found = lookup_edge(self.spec, self.state, jnp.int32(u), jnp.int32(v))
        phi_e = int(self.state.phi[int(slot)]) if bool(found) else 0
        return (kmin, phi_e)

    def apply_batch(self, updates, strategy: str = "auto",
                    fused_threshold: int = 8, defer_sync: bool = False,
                    engine: str = "auto"):
        """Apply a batch of (op, a, b) updates with truss maintenance.

        ``fusedBatchUpdate``: the batch is first *netted* on the host (an
        edge inserted then deleted inside one batch cancels — phi depends
        only on the final edge set), then applied either

        * ``progressive`` — Algorithms 1/2 per netted update (the paper's
          per-update path; best for tiny batches where per-update affected
          sets are small and disjoint), or
        * ``fused`` — one ``batch.batch_maintain`` call: one vectorized
          structural pass, one shared frontier, one delta-peel.

        ``auto`` picks fused once the netted batch reaches
        ``fused_threshold`` updates (paper Table 3 framing: progressive
        wins at small update counts, batch processing at large ones).

        ``defer_sync=True`` (the service's pipelined flush) returns without
        blocking on the device result: the fused path dispatches
        ``batch_maintain`` asynchronously and hands back the device-side
        invalidation bound ``hi`` (a 0-d int32 ``jax.Array``) *instead of*
        invalidating the index here — the caller must later run
        ``index.invalidate(2, max(int(hi), 1))`` (which blocks until the
        re-peel lands) before serving any label query from this state.
        Paths that already synchronized (progressive, netted no-op) return
        ``None``: their invalidation has been taken care of.

        ``engine`` selects the fused path's peel engine (``"auto"`` /
        ``"delta"`` / ``"recompute"``, forwarded to
        ``batch.batch_maintain``): the service's graceful-degradation path
        retries a failed delta peel with ``engine="recompute"`` before
        quarantining the generation.
        """
        ups = [(int(op), int(a), int(b)) for op, a, b in updates]
        if not ups:
            return
        with obs_trace.span("graph.net", updates=len(ups)):
            cur, dels, inss = self._net(ups)
            n_net = len(dels) + len(inss)
            if strategy == "auto":
                strategy = ("fused" if n_net >= fused_threshold
                            else "progressive")
            if n_net and strategy == "fused":
                padded = self._fused_args(cur, dels, inss)
        if n_net == 0:
            return None
        if strategy == "progressive":
            for a, b in dels:
                self.delete(a, b)
            for a, b in inss:
                self.insert(a, b)
            return None
        if strategy != "fused":
            raise ValueError(f"unknown strategy {strategy!r}")
        # warm the cache from the PRE-update state, then fold the structural
        # changes in: batch_maintain's delta-peel wants the POST-update
        # adjacency bitmap
        if self.support_method == "bitmap":
            with obs_trace.span("graph.bitmap_apply", dels=len(dels),
                                ins=len(inss)):
                self._bitmap_cache()
                self._bitmap_apply(dels, inss)
        try:
            # span covers the host-side apply window: the fused re-peel is
            # dispatched here and lands later (gen.wait below, or the
            # service's gen.land span with defer_sync)
            with obs_trace.span("graph.apply_batch", dels=len(dels),
                                ins=len(inss), defer=defer_sync):
                self.state, _lo, hi, stats = batch.batch_maintain(
                    self.spec, self.state, *padded,
                    method=self.support_method, engine=engine,
                    bitmap=self._bitmap, mesh=self.mesh)
        except BaseException:
            # the cache already describes the post-update edge set but
            # state/_present still the pre-update one — drop it rather than
            # let later bitmap-method peels read a diverged cache
            self._bitmap = None
            raise
        self.last_peel_stats = stats
        self.last_closure_probed = batch.closure_probes_bitmap(self.spec,
                                                               self._bitmap)
        self._present = cur
        if defer_sync:
            # async-dispatch mode: the re-peel is in flight; hand the device
            # scalar back so the caller can overlap host work and invalidate
            # once the result lands
            return hi
        # int(hi) blocks until the whole fused executable has landed.
        # Updated edges join/leave every level below the range too (they can
        # merge or split components there), so invalidate [2, hi + 1]; the
        # mixed-batch fallback returns hi = +inf, i.e. invalidate everything.
        with obs_trace.span("gen.wait"):
            hi = int(hi)
        self.index.invalidate(2, max(hi, 1))
        return None

    def _net(self, ups):
        """Net a batch against the present edge set: ``(cur, dels,
        inss)`` — the edge set after the batch and the sorted edges it
        removes and adds (an edge inserted then deleted cancels)."""
        present0 = self._present
        cur = set(present0)
        for op, a, b in ups:
            if a == b:
                raise ValueError("self-loops are not allowed")
            key = (min(a, b), max(a, b))
            if op == maintenance.OP_INSERT:
                if key in cur:
                    raise ValueError(f"insert of present edge {key}")
                cur.add(key)
            else:
                if key not in cur:
                    raise ValueError(f"delete of absent edge {key}")
                cur.discard(key)
        return cur, sorted(present0 - cur), sorted(cur - present0)

    def _fused_args(self, cur, dels, inss):
        """Grow capacities for the edge set ``cur`` if it needs more, and
        pad ``dels``/``inss`` to one power-of-two length: the six
        ``(a, b, valid)`` device arrays ``batch_maintain`` takes."""
        final = np.asarray(sorted(cur), np.int64).reshape(-1, 2)
        deg = (np.bincount(final.reshape(-1), minlength=self.spec.n_nodes)
               if len(final) else np.zeros(self.spec.n_nodes, np.int64))
        if len(cur) > self.spec.e_cap or deg.max(initial=0) > self.spec.d_max:
            self._grow(min_d=int(deg.max(initial=0)), min_e=len(cur))
        bsz = 1
        while bsz < max(len(dels), len(inss)):
            bsz <<= 1

        def pad(pairs):
            arr = np.zeros((bsz, 2), np.int32)
            msk = np.zeros(bsz, bool)
            if pairs:
                arr[:len(pairs)] = np.asarray(pairs, np.int32)
                msk[:len(pairs)] = True
            return (jnp.asarray(arr[:, 0]), jnp.asarray(arr[:, 1]),
                    jnp.asarray(msk))

        return (*pad(dels), *pad(inss))

    def batch_update_then_decompose(self, updates):
        """batchUpdate baseline: apply structural updates, re-decompose."""
        el = set(self._present)
        for op, a, b in updates:
            key = (min(a, b), max(a, b))
            if op == maintenance.OP_INSERT:
                el.add(key)
            else:
                el.discard(key)
        self._present = set(el)
        el = sorted(el)
        deg = np.bincount(np.asarray(el).reshape(-1), minlength=self.spec.n_nodes) if el else np.zeros(self.spec.n_nodes)
        if len(el) > self.spec.e_cap or deg.max(initial=0) > self.spec.d_max:
            s = self.spec.n_shards
            self.spec = GraphSpec(self.spec.n_nodes,
                                  max(self.spec.d_max, int(deg.max(initial=0)) + 4),
                                  -(-max(self.spec.e_cap, len(el) + 16) // s) * s,
                                  n_shards=s, shard_axis=self.spec.shard_axis,
                                  partition=self.spec.partition)
            self._set_memory_gauges()
        self.state = from_edge_list(self.spec, np.asarray(el).reshape(-1, 2))
        if self.mesh is not None:
            self.state = shard_state(self.spec, self.state, self.mesh)
        self._bitmap = None  # wholesale structural rebuild: cache is stale
        phi, stats = decomposition.decompose_with_stats(
            self.spec, self.state, self.support_method,
            bitmap=self._bitmap_cache(), mesh=self.mesh)
        self.state = self.state._replace(phi=phi)
        self.last_peel_stats = stats
        self.last_closure_probed = False
        self.index = TrussIndex(self.spec, self.index.tracked)
        self.index.invalidate_all()

    # -- views -----------------------------------------------------------------
    def edge_list(self) -> np.ndarray:
        """Active edges as an ``[m, 2]`` host array."""
        act = np.asarray(self.state.active)
        return np.asarray(self.state.edges)[act]

    def phi_dict(self) -> dict:
        """Host mapping ``(u, v) -> phi`` over active edges (test/oracle view)."""
        act = np.asarray(self.state.active)
        edges = np.asarray(self.state.edges)[act]
        phis = np.asarray(self.state.phi)[act]
        return {(int(u), int(v)): int(p) for (u, v), p in zip(edges, phis)}

    def k_truss(self, k: int) -> np.ndarray:
        """Edges of the k-truss (``phi >= k``) as an ``[m, 2]`` host array."""
        act = np.asarray(self.state.active) & (np.asarray(self.state.phi) >= k)
        return np.asarray(self.state.edges)[act]

    def max_truss(self) -> int:
        """Largest k with a non-empty k-truss (0 when the graph is empty)."""
        phis = np.asarray(self.state.phi)[np.asarray(self.state.active)]
        return int(phis.max(initial=0))
