"""The plain reference: truss decomposition in pure Python.

``truss_decomposition`` is a copy of the textbook peeling in the
program's oracle (``repro.core.oracle``), kept here so that no change to
the program can move the yardstick.  It imports nothing of the program and
takes nothing the program made: it is handed the edge set that the
operations a run acknowledged imply, computed here from the generated
graph and the generated writes.
"""
from __future__ import annotations

from collections import deque


def _canon(a: int, b: int):
    return (a, b) if a < b else (b, a)


def apply_writes(edges: set, writes) -> set:
    """The edge set after ``(op, a, b)`` writes (op 1 inserts, 0 deletes);
    a write that is invalid against the set raises."""
    out = set(edges)
    for op, a, b in writes:
        e = _canon(int(a), int(b))
        if int(op) == 1:
            if e in out:
                raise ValueError(f"insert of present edge {e}")
            out.add(e)
        else:
            if e not in out:
                raise ValueError(f"delete of absent edge {e}")
            out.discard(e)
    return out


def truss_decomposition(edges) -> dict[tuple[int, int], int]:
    """phi(e) for every edge of an edge set of canonical ``(u, v)``."""
    adj: dict[int, set[int]] = {}
    for a, b in edges:
        adj.setdefault(a, set()).add(b)
        adj.setdefault(b, set()).add(a)
    sup: dict[tuple[int, int], int] = {}
    for u in adj:
        for v in adj[u]:
            if u < v:
                sup[(u, v)] = len(adj[u] & adj[v])
    alive = {u: set(vs) for u, vs in adj.items()}
    phi: dict[tuple[int, int], int] = {}
    remaining = set(sup)
    k = 3
    while remaining:
        # strip everything with support < k-2 (cascading), then advance k
        queue = deque(e for e in remaining if sup[e] < k - 2)
        queued = set(queue)
        while queue:
            e = queue.popleft()
            queued.discard(e)
            if e not in remaining:
                continue
            u, v = e
            phi[e] = k - 1
            remaining.discard(e)
            for w in alive[u] & alive[v]:
                for f in (_canon(u, w), _canon(v, w)):
                    sup[f] -= 1
                    if f in remaining and sup[f] < k - 2 and f not in queued:
                        queue.append(f)
                        queued.add(f)
            alive[u].discard(v)
            alive[v].discard(u)
        k += 1
    return phi

