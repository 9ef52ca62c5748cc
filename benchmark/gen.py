"""Data generation: the bulk log and the live tail. numpy only.

``--seed`` changes every value of the data and none of the work. The
R-MAT *topology* (which recursive cell each edge falls into) is drawn
from the configuration's fixed ``graph_seed``; the run's seed draws the
vertex ids (order-preserving, see ``vertex_ids``), every event time,
which pair happens when, and the whole tail. So every seed has exactly
the same number of vertex ids, of distinct (src, dst) pairs and of
events, and the same graph in the space of id ranks — hence the same
padded shapes — while no id, time or answer repeats.
"""

from __future__ import annotations

import numpy as np

# kind codes of the plain columns the reference reads (not the program's)
VADD, VDEL, EADD, EDEL = 0, 1, 2, 3


def rmat_pairs(scale: int, n_edges: int, abcd, graph_seed: int):
    """Graph500-style R-MAT: ``n_edges`` (src, dst) pairs over ``2**scale``
    topology ids. Duplicate pairs stay: a temporal log holds repeated
    interactions."""
    a, b, c, _d = abcd
    rng = np.random.default_rng([int(graph_seed), 0x524D4154])
    src = np.zeros(n_edges, np.int64)
    dst = np.zeros(n_edges, np.int64)
    ab, abc = a + b, a + b + c
    for _ in range(scale):
        r = rng.random(n_edges, np.float32)
        src = (src << 1) | (r >= ab)            # quadrants C, D: src bit 1
        dst = (dst << 1) | (((r >= a) & (r < ab)) | (r >= abc))   # B, D
    return src, dst


def vertex_ids(cfg: dict, seed: int):
    """Vertex id of each topology id. A fixed shuffle (``graph_seed``)
    scatters R-MAT's hubs, which sit at the low topology ids; then the
    run's seed draws ``2**scale`` distinct ids out of ``id_space`` and
    hands them out IN ORDER. So every id changes with the seed, while
    the order of the ids — and with it the graph as the engines see it,
    in the dense space of id ranks, and every padded size derived from
    it — is the same on every seed."""
    g = cfg["graph"]
    n_ids = 1 << g["scale"]
    shuffle = np.random.default_rng(
        [int(g["graph_seed"]), 0x53484646]).permutation(n_ids)
    rng = np.random.default_rng([int(seed), 0x564944])
    ids = np.sort(rng.choice(int(g["id_space"]), n_ids, replace=False))
    return ids[shuffle].astype(np.int64)


def bulk_log(cfg: dict, seed: int):
    """(times, src, dst) of the bulk edge-add log, time-sorted."""
    g = cfg["graph"]
    n_edges = (1 << g["scale"]) * g["edge_factor"]
    src, dst = rmat_pairs(g["scale"], n_edges, g["rmat_abcd"],
                          g["graph_seed"])
    vid = vertex_ids(cfg, seed)
    rng = np.random.default_rng([int(seed), 0x42554C4B])
    times = np.sort(rng.integers(0, g["t_span"], n_edges, dtype=np.int64))
    order = rng.permutation(n_edges)       # which pair happens when
    return times, vid[src[order]], vid[dst[order]]


def tail_events(cfg: dict, seed: int, n_events: int):
    """The reference paper's worst-case mix (§6.1) as plain columns
    ``(t, kind, s, d)``; event ``i`` has event time ``t_span + 1 + i``
    (strictly after the bulk, one event per unit of event time), so the
    newest tail event at or before a row's time is an index."""
    t = cfg["tail"]
    rng = np.random.default_rng([int(seed), 0x5441494C])
    mix = np.asarray(t["mix"], np.float64)
    kind = rng.choice(4, n_events, p=mix / mix.sum()).astype(np.uint8)
    s = rng.integers(0, t["id_pool"], n_events, dtype=np.int64)
    d = rng.integers(0, t["id_pool"], n_events, dtype=np.int64)
    d[(kind == VADD) | (kind == VDEL)] = -1
    times = cfg["graph"]["t_span"] + 1 + np.arange(n_events, dtype=np.int64)
    return times, kind, s, d
