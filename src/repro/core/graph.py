"""Capacity-bounded dynamic graph state for the truss engine.

JAX requires static shapes, so the evolving graph (paper §2: undirected,
unweighted, simple) lives in fixed-capacity arrays with validity masks:

* ``edges   int32[E_cap, 2]``  canonical (u < v) endpoints; sentinel ``(N, N)``
  on inactive slots.
* ``active  bool[E_cap]``      slot validity.
* ``phi     int32[E_cap]``     truss numbers (paper's ``phi(e)``); 0 inactive.
* ``nbr     int32[N, D_max]``  per-node **sorted** neighbor ids, padded with
  the sentinel ``N`` (sorts last, keeps rows sorted).
* ``eid     int32[N, D_max]``  edge-slot index aligned with ``nbr`` — this is
  what turns "neighbor intersection" into "gather both partner-edge ids".
* ``deg     int32[N]``         current degree.

The sorted-row + aligned-eid layout is the TPU adaptation of the paper's
adjacency hash-set: membership tests and partner-edge lookup become a
vectorized binary search (``searchsorted``) instead of pointer chasing.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class GraphSpec:
    """Static (hashable — usable as a jit static arg) graph capacities.

    ``n_shards``/``shard_axis`` declare the optional mesh partition geometry
    of the edge axis: every edge-indexed array (``edges``, ``active``,
    ``phi``) is row-blocked into ``n_shards`` contiguous blocks of
    ``block`` slots, block *s* owned by mesh position *s* along
    ``shard_axis``.  Node-indexed arrays (``nbr``/``eid``/``deg``) stay
    replicated.  ``n_shards == 1`` (the default) is the single-device
    layout; the spec stays hashable and the devices themselves never enter
    it — the ``Mesh`` is supplied at call time and validated against this
    geometry.

    ``partition`` declares where the **adjacency bitmap** lives:

    * ``"replicated"`` (default) — every device holds the full
      ``uint32[N, W]`` bitmap; bitwise-identical to the pre-partition
      engine at any device count, but per-device bitmap memory is O(N·W)
      regardless of shard count, so devices buy wave-time and zero
      capacity.
    * ``"nodes"`` — the bitmap's *word axis* (its columns index neighbor
      nodes: word ``w`` of row ``u`` holds membership bits for nodes
      ``32w..32w+31``) is blocked into ``n_shards`` contiguous slabs,
      device *s* holding only ``bm[:, s·Wb:(s+1)·Wb]`` — O(N·W/S) per
      device.  Support decomposes exactly across slabs
      (``sup(e) = Σ_s popcount(rows ∩ slab_s)``), so the partitioned peel
      engine exchanges one psum of int32 partial supports per wave and
      every bit keeps exactly one owner (construction and incremental
      clearing stay collective-free).  ``n_words`` rounds up to a multiple
      of ``n_shards`` so slabs are uniform (padding words are zero and
      contribute nothing to any popcount).
    """

    n_nodes: int
    d_max: int
    e_cap: int
    n_shards: int = 1
    shard_axis: str = "shard"
    partition: str = "replicated"

    def __post_init__(self):
        if self.e_cap % self.n_shards:
            raise ValueError(
                f"e_cap {self.e_cap} must divide into n_shards "
                f"{self.n_shards} row blocks (see with_mesh)")
        if self.partition not in ("replicated", "nodes"):
            raise ValueError(
                f"unknown bitmap partition {self.partition!r} "
                "(expected 'replicated' or 'nodes')")

    @property
    def n_words(self) -> int:
        """uint32 words per adjacency-bitmap row (padded to uniform
        per-shard word slabs under ``partition='nodes'``)."""
        w = (self.n_nodes + 31) // 32
        if self.partition == "nodes":
            w = -(-w // self.n_shards) * self.n_shards
        return w

    @property
    def word_block(self) -> int:
        """Words of one device's bitmap slab (``n_words`` when replicated)."""
        if self.partition == "nodes":
            return self.n_words // self.n_shards
        return self.n_words

    @property
    def bitmap_bytes_per_device(self) -> int:
        """Resident adjacency-bitmap bytes per device — THE number the
        partition exists to shrink (O(N·W) replicated, O(N·W/S) nodes)."""
        return self.n_nodes * self.word_block * 4

    @property
    def state_bytes_per_device(self) -> int:
        """Resident ``GraphState`` bytes per device under this geometry:
        edge-axis arrays row-blocked (edges/active/phi), node tables
        replicated (nbr/eid int32 + deg int32), bitmap per ``partition``."""
        e_blk = self.e_cap // self.n_shards
        edge_bytes = e_blk * (2 * 4 + 1 + 4)          # edges, active, phi
        node_bytes = self.n_nodes * (2 * self.d_max * 4 + 4)  # nbr, eid, deg
        return edge_bytes + node_bytes + self.bitmap_bytes_per_device


class GraphState(NamedTuple):
    """Device-resident graph: edge table, activity mask, phi, and CSR-ish
    fixed-width adjacency (``nbr``/``eid``/``deg``)."""

    edges: jax.Array   # int32[E_cap, 2]
    active: jax.Array  # bool[E_cap]
    phi: jax.Array     # int32[E_cap]
    nbr: jax.Array     # int32[N, D_max]
    eid: jax.Array     # int32[N, D_max]
    deg: jax.Array     # int32[N]


def empty_state(spec: GraphSpec) -> GraphState:
    """Fresh all-inactive state at the spec's capacities (sentinel = n_nodes)."""
    n, d, e = spec.n_nodes, spec.d_max, spec.e_cap
    return GraphState(
        edges=jnp.full((e, 2), n, dtype=jnp.int32),
        active=jnp.zeros((e,), dtype=bool),
        phi=jnp.zeros((e,), dtype=jnp.int32),
        nbr=jnp.full((n, d), n, dtype=jnp.int32),
        eid=jnp.full((n, d), e, dtype=jnp.int32),
        deg=jnp.zeros((n,), dtype=jnp.int32),
    )


def from_edge_list(spec: GraphSpec, edge_list: np.ndarray) -> GraphState:
    """Bulk-load (host-side, numpy) — the fast path for dataset ingestion.

    ``edge_list``: int array [m, 2]; duplicates/self-loops rejected.
    """
    el = np.asarray(edge_list, dtype=np.int64)
    if el.size == 0:
        return empty_state(spec)
    u = np.minimum(el[:, 0], el[:, 1])
    v = np.maximum(el[:, 0], el[:, 1])
    if (u == v).any():
        raise ValueError("self-loops are not allowed (simple graph)")
    keys = u * spec.n_nodes + v
    if len(np.unique(keys)) != len(keys):
        raise ValueError("duplicate edges are not allowed (simple graph)")
    m = len(u)
    if m > spec.e_cap:
        raise ValueError(f"{m} edges exceed capacity {spec.e_cap}")

    n, d = spec.n_nodes, spec.d_max
    nbr = np.full((n, d), n, dtype=np.int32)
    eid = np.full((n, d), spec.e_cap, dtype=np.int32)
    deg = np.zeros((n,), dtype=np.int32)
    # Build per-node rows (host loop; only used at ingestion time).
    half = np.concatenate([np.stack([u, v], 1), np.stack([v, u], 1)])
    eidx = np.concatenate([np.arange(m), np.arange(m)])
    order = np.lexsort((half[:, 1], half[:, 0]))
    half, eidx = half[order], eidx[order]
    src, dst = half[:, 0], half[:, 1]
    counts = np.bincount(src, minlength=n)
    if counts.max(initial=0) > d:
        raise ValueError(f"max degree {counts.max()} exceeds d_max {d}")
    starts = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    slot = np.arange(len(src)) - starts[src]
    nbr[src, slot] = dst
    eid[src, slot] = eidx
    deg[:] = counts

    edges = np.full((spec.e_cap, 2), n, dtype=np.int32)
    edges[:m, 0] = u
    edges[:m, 1] = v
    active = np.zeros((spec.e_cap,), dtype=bool)
    active[:m] = True
    phi = np.zeros((spec.e_cap,), dtype=np.int32)
    return GraphState(
        edges=jnp.asarray(edges),
        active=jnp.asarray(active),
        phi=jnp.asarray(phi),
        nbr=jnp.asarray(nbr),
        eid=jnp.asarray(eid),
        deg=jnp.asarray(deg),
    )


# ---------------------------------------------------------------------------
# Sharded-state constructors — the mesh-partitioned layout of the peel
# substrate.  Edge-indexed arrays are row-blocked over spec.shard_axis,
# node-indexed arrays replicated; mesh=None consumers ignore all of this.
# ---------------------------------------------------------------------------

def with_mesh(spec: GraphSpec, mesh, axis: str = "shard",
              partition: str | None = None) -> GraphSpec:
    """Spec with the partition geometry of ``mesh[axis]``: ``e_cap`` rounded
    up to a multiple of the axis size so the edge row blocks are uniform.
    ``partition`` optionally switches the bitmap layout (``"replicated"`` /
    ``"nodes"``); ``None`` keeps the spec's current one."""
    s = int(mesh.shape[axis])
    e_cap = -(-spec.e_cap // s) * s
    return dataclasses.replace(
        spec, e_cap=e_cap, n_shards=s, shard_axis=axis,
        partition=spec.partition if partition is None else partition)


def pad_state(old_spec: GraphSpec, st: GraphState, spec: GraphSpec) -> GraphState:
    """Grow the edge axis of ``st`` from ``old_spec.e_cap`` to
    ``spec.e_cap`` with sentinel slots (used when re-sharding restored or
    host-built state onto a mesh whose block size doesn't divide the old
    capacity).  The ``eid`` sentinel is the *value* ``e_cap`` ("no edge"),
    so every old-sentinel entry is remapped to the new capacity."""
    extra = spec.e_cap - old_spec.e_cap
    if extra < 0:
        raise ValueError(f"cannot shrink e_cap {old_spec.e_cap} -> {spec.e_cap}")
    eid = jnp.where(st.eid == old_spec.e_cap, spec.e_cap, st.eid)
    if extra == 0:
        return st._replace(eid=eid)
    return GraphState(
        edges=jnp.concatenate(
            [st.edges, jnp.full((extra, 2), spec.n_nodes, jnp.int32)]),
        active=jnp.concatenate([st.active, jnp.zeros((extra,), bool)]),
        phi=jnp.concatenate([st.phi, jnp.zeros((extra,), jnp.int32)]),
        nbr=st.nbr, eid=eid, deg=st.deg)


def state_sharding(spec: GraphSpec, mesh) -> GraphState:
    """The ``NamedSharding`` of each ``GraphState`` field on the mesh:
    edge-axis arrays in their row blocks along ``spec.shard_axis``,
    node-indexed arrays replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P  # lazy: host paths
    ax = spec.shard_axis
    row2 = NamedSharding(mesh, P(ax, None))
    row1 = NamedSharding(mesh, P(ax))
    rep = NamedSharding(mesh, P())
    return GraphState(edges=row2, active=row1, phi=row1, nbr=rep, eid=rep,
                      deg=rep)


def shard_state(spec: GraphSpec, st: GraphState, mesh) -> GraphState:
    """Place ``st`` for the mesh (``state_sharding``).  The placement is an
    optimization (shard_map reshards on entry regardless); values are
    unchanged."""
    return GraphState(*map(jax.device_put, st, state_sharding(spec, mesh)))


# ---------------------------------------------------------------------------
# Row edits (vectorized O(D_max) shift-insert / shift-delete on sorted rows).
# ---------------------------------------------------------------------------

def _row_insert(row: jax.Array, pos: jax.Array, val: jax.Array) -> jax.Array:
    i = jnp.arange(row.shape[0])
    shifted = row[jnp.maximum(i - 1, 0)]
    return jnp.where(i < pos, row, jnp.where(i == pos, val, shifted))


def _row_delete(row: jax.Array, pos: jax.Array, sentinel) -> jax.Array:
    i = jnp.arange(row.shape[0])
    nxt = jnp.where(i + 1 < row.shape[0], row[jnp.minimum(i + 1, row.shape[0] - 1)], sentinel)
    return jnp.where(i < pos, row, nxt)


def lookup_edge(spec: GraphSpec, st: GraphState, a: jax.Array, b: jax.Array):
    """Return (slot, found) for edge (a, b) via binary search of a's row."""
    row = st.nbr[a]
    p = jnp.searchsorted(row, b)
    pc = jnp.minimum(p, spec.d_max - 1)
    found = row[pc] == b
    slot = jnp.where(found, st.eid[a, pc], spec.e_cap)
    return slot, found


def insert_edge_struct(spec: GraphSpec, st: GraphState, a: jax.Array, b: jax.Array):
    """Structural insert (no phi maintenance). Returns (state, slot).

    Caller guarantees: edge absent, a != b, deg < d_max, a free slot exists.
    """
    u = jnp.minimum(a, b)
    v = jnp.maximum(a, b)
    slot = jnp.argmin(st.active).astype(jnp.int32)  # first False
    edges = st.edges.at[slot].set(jnp.stack([u, v]).astype(jnp.int32))
    active = st.active.at[slot].set(True)

    pa = jnp.searchsorted(st.nbr[u], v)
    nbr = st.nbr.at[u].set(_row_insert(st.nbr[u], pa, v))
    eid = st.eid.at[u].set(_row_insert(st.eid[u], pa, slot))
    pb = jnp.searchsorted(nbr[v], u)
    nbr = nbr.at[v].set(_row_insert(nbr[v], pb, u))
    eid = eid.at[v].set(_row_insert(eid[v], pb, slot))
    deg = st.deg.at[u].add(1).at[v].add(1)
    return st._replace(edges=edges, active=active, nbr=nbr, eid=eid, deg=deg), slot


def delete_edge_struct(spec: GraphSpec, st: GraphState, a: jax.Array, b: jax.Array):
    """Structural delete. Returns (state, slot_of_deleted_edge)."""
    u = jnp.minimum(a, b)
    v = jnp.maximum(a, b)
    slot, _found = lookup_edge(spec, st, u, v)
    slot_c = jnp.minimum(slot, spec.e_cap - 1)
    edges = st.edges.at[slot_c].set(jnp.full((2,), spec.n_nodes, jnp.int32))
    active = st.active.at[slot_c].set(False)
    phi = st.phi.at[slot_c].set(0)

    pa = jnp.searchsorted(st.nbr[u], v)
    nbr = st.nbr.at[u].set(_row_delete(st.nbr[u], pa, spec.n_nodes))
    eid = st.eid.at[u].set(_row_delete(st.eid[u], pa, spec.e_cap))
    pb = jnp.searchsorted(nbr[v], u)
    nbr = nbr.at[v].set(_row_delete(nbr[v], pb, spec.n_nodes))
    eid = eid.at[v].set(_row_delete(eid[v], pb, spec.e_cap))
    deg = st.deg.at[u].add(-1).at[v].add(-1)
    return st._replace(edges=edges, active=active, phi=phi, nbr=nbr, eid=eid, deg=deg), slot


def apply_edge_batch_struct(spec: GraphSpec, st: GraphState,
                            del_u: jax.Array, del_v: jax.Array, del_valid: jax.Array,
                            ins_u: jax.Array, ins_v: jax.Array, ins_valid: jax.Array):
    """Vectorized multi-edge structural update (no phi maintenance).

    All six arrays are length-B (padded; masked rows are ignored).  Instead of
    B sequential shift-edits, every affected adjacency row is rebuilt in one
    batched pass: deleted entries are overwritten with the sort-last sentinel,
    inserted neighbors are appended in a candidate block, and a single
    ``argsort`` per row restores the sorted-row invariant for ``nbr``/``eid``
    jointly.

    Caller guarantees (checked host-side by ``DynamicGraph.apply_batch``):
    valid deletions exist, valid insertions are absent, no edge pair appears
    twice across the batch, and the post-update graph fits (e_cap, d_max).

    Returns ``(state, ins_slots int32[B])`` (slot ``e_cap`` on masked rows).
    """
    n, d, e_cap = spec.n_nodes, spec.d_max, spec.e_cap
    bsz = del_u.shape[0]
    du = jnp.minimum(del_u, del_v).astype(jnp.int32)
    dv = jnp.maximum(del_u, del_v).astype(jnp.int32)
    iu = jnp.minimum(ins_u, ins_v).astype(jnp.int32)
    iv = jnp.maximum(ins_u, ins_v).astype(jnp.int32)

    # -- edge-slot table: free deleted slots, then claim slots for inserts --
    duc = jnp.where(del_valid, du, 0)
    dvc = jnp.where(del_valid, dv, 0)
    d_slot, d_found = jax.vmap(lambda a, b: lookup_edge(spec, st, a, b))(duc, dvc)
    vdel = del_valid & d_found
    tgt_d = jnp.where(vdel, d_slot, e_cap)
    edges = st.edges.at[tgt_d].set(n, mode="drop")
    active = st.active.at[tgt_d].set(False, mode="drop")
    phi = st.phi.at[tgt_d].set(0, mode="drop")

    free_idx = jnp.nonzero(~active, size=bsz, fill_value=e_cap)[0].astype(jnp.int32)
    rank = jnp.cumsum(ins_valid.astype(jnp.int32)) - 1
    ins_slots = jnp.where(ins_valid, free_idx[jnp.clip(rank, 0, bsz - 1)],
                          jnp.int32(e_cap))
    tgt_i = jnp.where(ins_valid, ins_slots, e_cap)
    edges = edges.at[tgt_i].set(jnp.stack([iu, iv], 1), mode="drop")
    active = active.at[tgt_i].set(True, mode="drop")

    # -- rebuild every affected adjacency row ------------------------------
    nodes = jnp.concatenate([jnp.where(vdel, du, n), jnp.where(vdel, dv, n),
                             jnp.where(ins_valid, iu, n),
                             jnp.where(ins_valid, iv, n)])
    uniq = jnp.unique(nodes, size=4 * bsz, fill_value=n)  # sorted, padded with n
    r = 4 * bsz
    rows_nbr = st.nbr[jnp.minimum(uniq, n - 1)]           # [R, D]
    rows_eid = st.eid[jnp.minimum(uniq, n - 1)]

    def row_of(x):
        return jnp.minimum(jnp.searchsorted(uniq, x), r - 1).astype(jnp.int32)

    delmask = jnp.zeros((r, d), bool)

    def mark_deleted(delmask, xs, others):
        i = row_of(xs)                                    # [B]
        pos = jax.vmap(jnp.searchsorted)(rows_nbr[i], others)
        posc = jnp.minimum(pos, d - 1)
        hit = vdel & (rows_nbr[i, posc] == others)
        return delmask.at[jnp.where(hit, i, r), posc].set(True, mode="drop")

    delmask = mark_deleted(delmask, du, dv)
    delmask = mark_deleted(delmask, dv, du)
    ext_nbr = jnp.where(delmask, n, rows_nbr)
    ext_eid = jnp.where(delmask, e_cap, rows_eid)

    cand_nbr = jnp.full((r, bsz), n, jnp.int32)
    cand_eid = jnp.full((r, bsz), e_cap, jnp.int32)
    col = jnp.arange(bsz)
    iu_row = jnp.where(ins_valid, row_of(iu), r)
    iv_row = jnp.where(ins_valid, row_of(iv), r)
    cand_nbr = cand_nbr.at[iu_row, col].set(iv, mode="drop")
    cand_nbr = cand_nbr.at[iv_row, col].set(iu, mode="drop")
    cand_eid = cand_eid.at[iu_row, col].set(ins_slots, mode="drop")
    cand_eid = cand_eid.at[iv_row, col].set(ins_slots, mode="drop")

    ext_nbr = jnp.concatenate([ext_nbr, cand_nbr], axis=1)  # [R, D+B]
    ext_eid = jnp.concatenate([ext_eid, cand_eid], axis=1)
    order = jnp.argsort(ext_nbr, axis=1)
    new_nbr = jnp.take_along_axis(ext_nbr, order, axis=1)[:, :d]
    new_eid = jnp.take_along_axis(ext_eid, order, axis=1)[:, :d]

    tgt_rows = jnp.where(uniq < n, uniq, n)
    nbr = st.nbr.at[tgt_rows].set(new_nbr, mode="drop")
    eid = st.eid.at[tgt_rows].set(new_eid, mode="drop")
    deg = st.deg.at[tgt_rows].set(
        jnp.sum(new_nbr < n, axis=1).astype(jnp.int32), mode="drop")
    st = st._replace(edges=edges, active=active, phi=phi, nbr=nbr, eid=eid,
                     deg=deg)
    return st, ins_slots


# ---------------------------------------------------------------------------
# Triangle partner enumeration — the shared primitive behind support,
# localSupport (Alg. 1 step 5) and localSupport2 (Alg. 3).
# ---------------------------------------------------------------------------

def triangle_partners(spec: GraphSpec, st: GraphState, u: jax.Array, v: jax.Array):
    """For each query edge (u[i], v[i]) enumerate common neighbors.

    Returns ``(id_uw, id_vw, valid)`` of shape [B, D_max]: slot ids of the two
    partner edges (u,w), (v,w) for every common neighbor w, and a validity
    mask. This is the vectorized form of the paper's ``n(v1) ∩ n(v2)`` scans.
    """
    w = st.nbr[u]                       # [B, D]
    id_uw = st.eid[u]                   # [B, D]
    valid_w = w < spec.n_nodes
    rows_v = st.nbr[v]                  # [B, D]
    pos = jax.vmap(jnp.searchsorted)(rows_v, w)      # [B, D]
    pos_c = jnp.minimum(pos, spec.d_max - 1)
    found = jnp.take_along_axis(rows_v, pos_c, axis=1) == w
    id_vw = jnp.take_along_axis(st.eid[v], pos_c, axis=1)
    valid = valid_w & found
    return id_uw, id_vw, valid


def phi_of(st: GraphState, e_cap: int, ids: jax.Array) -> jax.Array:
    """phi gather with OOB → 0 (sentinel slot e_cap means "no edge")."""
    return jnp.where(ids < e_cap, st.phi[jnp.minimum(ids, e_cap - 1)], 0)


#: Neighbor-list widths of the support pass's degree classes (plus
#: ``d_max``): an edge whose endpoints have degrees <= (ka, kc) is counted
#: by comparing the first ka and kc entries of their sorted rows.
SUPPORT_WIDTHS = (16, 128, 1024)

#: Elements of one ``[rows, ka, kc]`` compare block of the support pass.
SUPPORT_BLOCK_ELEMS = 1 << 22


def support(spec: GraphSpec, st: GraphState, u: jax.Array, v: jax.Array,
            alive: jax.Array | None = None) -> jax.Array:
    """Global support sup(e, G) for query edges; optionally restricted to an
    ``alive`` mask over edge slots (used by peeling).

    Each edge's common neighbors are found by comparing its endpoints'
    neighbor rows all against all, with no per-element search: rows hold
    their neighbors first and sentinels last, so an endpoint of degree
    <= k needs only its first k entries.  Edges are grouped by the degree
    classes (``SUPPORT_WIDTHS``) of their two endpoints and each group runs
    in blocks of ``SUPPORT_BLOCK_ELEMS`` compares, so the pass reads and
    compares about ``sum(k_a * k_c)`` entries instead of ``E * d_max``
    searches — under a heavy-tailed degree law almost every edge is in the
    smallest class.  Row gathers and compares are what a TPU does fast;
    per-element gathers (a binary search) are not."""
    n, e_cap = spec.n_nodes, spec.e_cap
    al = None
    if alive is not None:
        al = jnp.concatenate([alive, jnp.zeros((1,), bool)])  # slot e_cap → False
    widths = tuple(w for w in SUPPORT_WIDTHS if w < spec.d_max) + (spec.d_max,)
    n_cls = len(widths)

    deg = jnp.sum(st.nbr < n, axis=1, dtype=jnp.int32)
    du, dv = deg[u], deg[v]
    lo = jnp.where(du <= dv, u, v)        # lower-degree endpoint first
    hi = jnp.where(du <= dv, v, u)
    cls_of = partial(jnp.searchsorted, jnp.asarray(widths))
    cls = cls_of(jnp.minimum(du, dv)) * n_cls + cls_of(jnp.maximum(du, dv))
    order = jnp.argsort(cls).astype(jnp.int32)
    bounds = jnp.searchsorted(cls[order], jnp.arange(n_cls * n_cls + 1))
    b = u.shape[0]
    most = max(1, SUPPORT_BLOCK_ELEMS // (widths[0] * widths[0]))
    lo_s = jnp.pad(lo[order], (0, most))   # block reads never clamp
    hi_s = jnp.pad(hi[order], (0, most))
    pos_s = jnp.pad(order, (0, most), constant_values=b)

    def count(a, c, ka, kc):
        wa, ea = st.nbr[a, :ka], st.eid[a, :ka]          # [R, ka]
        wc, ec = st.nbr[c, :kc], st.eid[c, :kc]          # [R, kc]
        match = ((wa[:, :, None] == wc[:, None, :])
                 & (wa < n)[:, :, None])                 # [R, ka, kc]
        found = jnp.any(match, axis=2)
        # rows are sets: at most one match per entry, so the sum is its id
        id_cw = jnp.sum(jnp.where(match, ec[:, None, :], 0), axis=2)
        if al is not None:
            found = (found & al[jnp.minimum(ea, e_cap)]
                     & al[jnp.minimum(id_cw, e_cap)])
        return jnp.sum(found, axis=1, dtype=jnp.int32)

    out = jnp.zeros((b,), jnp.int32)
    for ia, ka in enumerate(widths):
        for ic in range(ia, n_cls):
            kc = widths[ic]
            rows = max(1, SUPPORT_BLOCK_ELEMS // (ka * kc))
            start = bounds[ia * n_cls + ic]
            end = bounds[ia * n_cls + ic + 1]

            # a block's rows past ``end`` belong to later classes, which
            # run later and overwrite them (or to padding, dropped)
            def block(i, out, start=start, rows=rows, ka=ka, kc=kc):
                off = start + i * rows
                a = jax.lax.dynamic_slice_in_dim(lo_s, off, rows)
                c = jax.lax.dynamic_slice_in_dim(hi_s, off, rows)
                pos = jax.lax.dynamic_slice_in_dim(pos_s, off, rows)
                return out.at[pos].set(count(a, c, ka, kc), mode="drop")

            out = jax.lax.fori_loop(0, (end - start + rows - 1) // rows,
                                    block, out)
    return out


def support_all(spec: GraphSpec, st: GraphState, alive: jax.Array) -> jax.Array:
    """Support of every edge slot within the ``alive`` subgraph. [E_cap]."""
    u = jnp.minimum(st.edges[:, 0], spec.n_nodes - 1)
    v = jnp.minimum(st.edges[:, 1], spec.n_nodes - 1)
    sup = support(spec, st, u, v, alive=alive)
    return jnp.where(alive, sup, 0)


# ---------------------------------------------------------------------------
# Adjacency bitmaps — TPU-native intersection via AND + popcount (DESIGN §2).
# ---------------------------------------------------------------------------

def partial_bitmap(spec: GraphSpec, edges: jax.Array, valid: jax.Array,
                   word_offset: jax.Array | int = 0,
                   word_count: int | None = None) -> jax.Array:
    """uint32[N, W] bitmap contribution of an edge subset ([B, 2], masked).

    Each valid edge contributes one distinct bit per direction, so
    scatter-add equals scatter-or (simple graph ⇒ no duplicate bits) — and,
    because disjoint edge sets own disjoint bits, **summing** the partial
    bitmaps of the per-shard edge blocks rebuilds the full bitmap
    (``psum`` == bitwise-or across a mesh) and uint32 subtraction of a
    partial bitmap clears exactly that subset's bits with no borrow.  This
    is the one bitmap-construction primitive behind ``build_bitmap`` and
    the sharded peel engine's per-wave delta exchange.

    ``(word_offset, word_count)`` select one **word slab** of the output —
    the ``partition="nodes"`` layout where a device owns columns
    ``[word_offset, word_offset + word_count)`` only: the result is
    ``uint32[N, word_count]`` holding exactly the full bitmap's slice (bits
    whose destination word falls outside the slab are dropped — they belong
    to another owner).  ``word_count=None`` is the full-width build,
    bit-for-bit the pre-partition behavior.
    """
    u = jnp.where(valid, edges[:, 0], spec.n_nodes)  # OOB rows are dropped
    v = jnp.where(valid, edges[:, 1], spec.n_nodes)
    w = spec.n_words if word_count is None else word_count
    bm = jnp.zeros((spec.n_nodes, w), dtype=jnp.uint32)
    one = jnp.uint32(1)

    def scatter_dir(bm, src, dst):
        word = (dst // 32).astype(jnp.int32)
        if word_count is not None:
            # out-of-slab words map past the slab edge -> mode="drop"
            word = jnp.where((word >= word_offset) & (word < word_offset + w),
                             word - word_offset, w)
        bit = (dst % 32).astype(jnp.uint32)
        val = jnp.left_shift(one, bit)
        return bm.at[src, word].add(val, mode="drop")

    bm = scatter_dir(bm, u, v)
    bm = scatter_dir(bm, v, u)
    return bm


def build_bitmap(spec: GraphSpec, st: GraphState, alive: jax.Array) -> jax.Array:
    """uint32[N, W] adjacency bitmap of the alive subgraph."""
    return partial_bitmap(spec, st.edges, alive)


def update_bitmap(spec: GraphSpec, bm: jax.Array, u: jax.Array, v: jax.Array,
                  valid: jax.Array, *, set_bits: bool,
                  word_offset: jax.Array | int = 0,
                  word_count: int | None = None) -> jax.Array:
    """Incrementally set (insert) or clear (delete/peel) per-edge bits.

    O(B) scatter instead of the O(E) rebuild of ``build_bitmap``.  Clearing
    relies on the simple-graph invariant: every (edge, direction) owns one
    distinct bit, and that bit is set iff the edge is present, so subtracting
    the bit value clears it with no borrow (the dual of build_bitmap's
    scatter-add-as-scatter-or).  Caller guarantees set bits are absent and
    cleared bits are present.

    ``(word_offset, word_count)`` make the update **owner-local** for a
    ``partition="nodes"`` word slab: ``bm`` is the device's
    ``uint32[N, word_count]`` slab and only the bits whose destination word
    falls inside it are applied — every bit has exactly one owner, so the
    per-slab updates compose to exactly the full-bitmap update with no
    collective (the same disjoint-bits argument as ``partial_bitmap``).
    """
    uu = jnp.where(valid, u, spec.n_nodes).astype(jnp.int32)  # OOB rows drop
    vv = jnp.where(valid, v, spec.n_nodes).astype(jnp.int32)
    one = jnp.uint32(1)
    w = spec.n_words if word_count is None else word_count

    def upd(bm, src, dst):
        if word_count is None:
            word = jnp.minimum(dst // 32, spec.n_words - 1).astype(jnp.int32)
        else:
            word = (dst // 32).astype(jnp.int32)
            word = jnp.where((word >= word_offset) & (word < word_offset + w),
                             word - word_offset, w)  # out-of-slab -> drop
        bit = (dst % 32).astype(jnp.uint32)
        val = jnp.left_shift(one, bit)
        val = val if set_bits else jnp.uint32(0) - val
        return bm.at[src, word].add(val, mode="drop")

    bm = upd(bm, uu, vv)
    bm = upd(bm, vv, uu)
    return bm


# ---------------------------------------------------------------------------
# Node-partitioned bitmap constructors (partition="nodes") — each device owns
# one word slab of the [N, W] bitmap; construction and incremental update are
# owner-local (no collective), placement is P(None, shard_axis).
# ---------------------------------------------------------------------------

def bitmap_sharding(spec: GraphSpec, mesh):
    """``NamedSharding`` of the adjacency bitmap under this spec's
    ``partition``: word-axis slabs for ``"nodes"``, replicated otherwise."""
    from jax.sharding import NamedSharding, PartitionSpec as P  # lazy: host paths
    if spec.partition == "nodes":
        return NamedSharding(mesh, P(None, spec.shard_axis))
    return NamedSharding(mesh, P())


@partial(jax.jit, static_argnames=("spec", "mesh"))
def build_bitmap_partitioned(spec: GraphSpec, st: GraphState,
                             alive: jax.Array, mesh) -> jax.Array:
    """Word-sharded ``uint32[N, W]`` adjacency bitmap of the alive subgraph:
    every device scatters the full edge table (replicated in) into its own
    slab and drops out-of-slab bits — value-equal to ``build_bitmap``, laid
    out ``P(None, shard_axis)`` with O(N·W/S) resident per device.  Jitted,
    like ``update_bitmap_partitioned``: a ``shard_map`` called eagerly runs
    its body op by op and compiles each op again on every call."""
    from jax.sharding import PartitionSpec as P

    ax, wb = spec.shard_axis, spec.word_block

    def local_fn(edges, valid):
        off = jax.lax.axis_index(ax).astype(jnp.int32) * wb
        return partial_bitmap(spec, edges, valid,
                              word_offset=off, word_count=wb)

    return jax.shard_map(local_fn, mesh=mesh, in_specs=(P(), P()),
                         out_specs=P(None, ax),
                         check_vma=False)(st.edges, alive)


@partial(jax.jit, static_argnames=("spec", "set_bits", "mesh"))
def update_bitmap_partitioned(spec: GraphSpec, bm: jax.Array, u: jax.Array,
                              v: jax.Array, valid: jax.Array, *,
                              set_bits: bool, mesh) -> jax.Array:
    """Owner-local incremental update of a word-sharded bitmap: each device
    applies only the bits landing in its slab, so the per-slab updates
    compose to exactly the ``update_bitmap`` result with zero exchange."""
    from jax.sharding import PartitionSpec as P

    ax, wb = spec.shard_axis, spec.word_block

    def local_fn(bm, u, v, valid):
        off = jax.lax.axis_index(ax).astype(jnp.int32) * wb
        return update_bitmap(spec, bm, u, v, valid, set_bits=set_bits,
                             word_offset=off, word_count=wb)

    return jax.shard_map(local_fn, mesh=mesh,
                         in_specs=(P(None, ax), P(), P(), P()),
                         out_specs=P(None, ax),
                         check_vma=False)(bm, u, v, valid)


def support_all_bitmap(spec: GraphSpec, st: GraphState, alive: jax.Array,
                       bitmap: jax.Array | None = None) -> jax.Array:
    """Support of every edge via bitmap popcount (Pallas kernel hot loop)."""
    from ..kernels import ops as kernel_ops  # local import: kernels never import core

    if bitmap is None:
        bitmap = build_bitmap(spec, st, alive)
    u = jnp.minimum(st.edges[:, 0], spec.n_nodes - 1)
    v = jnp.minimum(st.edges[:, 1], spec.n_nodes - 1)
    sup = kernel_ops.bitmap_support(bitmap[u], bitmap[v])
    return jnp.where(alive, sup, 0)
