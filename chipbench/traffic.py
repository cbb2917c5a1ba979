"""One general traffic generator, driven by a mix's data file.

A mix is ``chipbench/traffic/<name>.json``.  One kind exists:

``ingest`` — closed-loop bulk ingest.  Every batch holds exactly ``batch``
updates: ``deletes`` uniform picks from the edges present when the batch
starts and ``batch - deletes`` inserts of uniform absent pairs, in a
shuffled order.  No insert re-adds an edge deleted in the same batch, so
every batch nets to the same counts: the service pads each generation to
the same shape for every seed.  ``batch`` is also the service's
generation size (``flush_every``): one batch, one generation.

A mix file holds ``kind``, ``about`` (one line saying what it stands for)
and the kind's parameters; any other key is refused.  The sampler follows
the program's own stream (``repro.data.streams.make_update_stream``, the
paper's random insert/delete protocol), copied here so that no change to
the program can move the traffic.  Everything is drawn from ``--seed``:
the same seed gives the same operations.
"""
from __future__ import annotations

import json
import os

import numpy as np

OP_DELETE = 0
OP_INSERT = 1

HERE = os.path.dirname(os.path.abspath(__file__))

#: the keys a mix of each kind holds
KINDS = {"ingest": {"kind", "about", "batch", "deletes"}}


def load_mix(name: str) -> dict:
    """The mix's parameters, from ``traffic/<name>.json``."""
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    keys = KINDS.get(mix.get("kind"))
    if keys is None:
        raise ValueError(f"traffic {name!r}: unknown kind {mix.get('kind')!r}")
    if set(mix) != keys:
        raise ValueError(f"traffic {name!r}: keys {sorted(mix)}, a "
                         f"{mix['kind']} mix holds {sorted(keys)}")
    return mix


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator keyed by the run's seed and a stream id (any whole
    number seeds it; negative ones wrap into 64 bits)."""
    return np.random.default_rng([int(seed) % 2**64, *stream])


class EdgeSet:
    """The evolving edge set that keeps a client's writes valid: uniform
    picks and removals in O(1)."""

    def __init__(self, n_nodes: int, edges):
        self.n = int(n_nodes)
        self.items = [(int(min(u, v)), int(max(u, v))) for u, v in edges]
        self.pos = {e: i for i, e in enumerate(self.items)}

    def __contains__(self, e) -> bool:
        return e in self.pos

    def __len__(self) -> int:
        return len(self.items)

    def add(self, e):
        self.pos[e] = len(self.items)
        self.items.append(e)

    def remove(self, e):
        i = self.pos.pop(e)
        last = self.items.pop()
        if i < len(self.items):
            self.items[i] = last
            self.pos[last] = i

    def pick(self, rng):
        return self.items[int(rng.integers(len(self.items)))]


def _uniform_absent(rng, es: EdgeSet, banned) -> tuple[int, int]:
    while True:
        a, b = (int(x) for x in rng.integers(0, es.n, size=2))
        e = (min(a, b), max(a, b))
        if a != b and e not in es and e not in banned:
            return e


class Ingest:
    """Batches of an ``ingest`` mix over an evolving edge set."""

    def __init__(self, mix: dict, n_nodes: int, edges, seed: int):
        self.batch = int(mix["batch"])
        self.deletes = int(mix["deletes"])
        if not 0 <= self.deletes <= self.batch:
            raise ValueError("deletes must lie in [0, batch]")
        self.edges = EdgeSet(n_nodes, edges)
        self.rng = rng_for(seed, 1)

    def next(self) -> list[tuple[int, int, int]]:
        rng, es = self.rng, self.edges
        dels = []
        for _ in range(self.deletes):
            e = es.pick(rng)
            es.remove(e)
            dels.append(e)
        gone = set(dels)
        ins = []
        for _ in range(self.batch - self.deletes):
            e = _uniform_absent(rng, es, gone)
            es.add(e)
            ins.append(e)
        ops = ([(OP_DELETE, u, v) for u, v in dels]
               + [(OP_INSERT, u, v) for u, v in ins])
        return [ops[i] for i in rng.permutation(len(ops))]
