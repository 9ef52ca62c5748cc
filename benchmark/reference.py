"""The plain reference of the store: which vertices and edges are alive
at T under a window. What an algorithm makes of that graph, and the
comparison that decides ``correct``, is ``algorithms/<module>.py``.

Copied from ``chip_smoke.py`` (PR 22). Written from the semantics the
store documents, not from its code: an entity is alive at T by its
latest history point <= T, a delete wins a tie, an edge add is a history
point of both endpoints, a vertex delete kills every incident edge, a
re-add revives, and a window w keeps what was last active in
[T - w, T]. Imports numpy only — nothing of the program, and nothing the
program has made.
"""

from __future__ import annotations

import numpy as np

NEG = -(2 ** 62)


class RefEvents:
    """The whole event stream, split by kind; each kind is in time order,
    so "events <= T" is a prefix of every array. ``k`` uses gen.py's
    codes (0 vertex add, 1 vertex delete, 2 edge add, 3 edge delete)."""

    def __init__(self, t, k, s, d, n_ids: int):
        self.n_ids = int(n_ids)
        if not bool(np.all(t[1:] >= t[:-1])):
            raise ValueError("event stream not time-sorted")
        is_vadd, is_vdel, is_eadd, is_edel = (k == i for i in range(4))
        edge = is_eadd | is_edel
        ukeys, edge_of = np.unique(s[edge] * np.int64(n_ids) + d[edge],
                                   return_inverse=True)
        self.us, self.ud = ukeys // n_ids, ukeys % n_ids
        self.va = (t[is_vadd], s[is_vadd])
        self.vd = (t[is_vdel], s[is_vdel])
        self.ea = (t[is_eadd], s[is_eadd], d[is_eadd],
                   edge_of[is_eadd[edge]])
        self.ed = (t[is_edel], edge_of[is_edel[edge]])

    def fold(self, T: int, window: int | None):
        """(vertex mask [n_ids], src, dst) of the graph at T under window."""
        def upto(cols):
            k = int(np.searchsorted(cols[0], T, side="right"))
            return tuple(c[:k] for c in cols)

        va_t, va_s = upto(self.va)
        vd_t, vd_s = upto(self.vd)
        ea_t, ea_s, ea_d, ea_e = upto(self.ea)
        ed_t, ed_e = upto(self.ed)
        v_live = np.full(self.n_ids, NEG, np.int64)
        np.maximum.at(v_live, va_s, va_t)
        np.maximum.at(v_live, ea_s, ea_t)      # an edge add is a history
        np.maximum.at(v_live, ea_d, ea_t)      # point of both endpoints
        v_dead = np.full(self.n_ids, NEG, np.int64)
        np.maximum.at(v_dead, vd_s, vd_t)
        v_alive = v_live > v_dead                  # delete wins a tie
        e_live = np.full(len(self.us), NEG, np.int64)
        np.maximum.at(e_live, ea_e, ea_t)
        e_dead = np.full(len(self.us), NEG, np.int64)
        np.maximum.at(e_dead, ed_e, ed_t)
        # a vertex delete kills every incident edge
        e_dead = np.maximum(e_dead, np.maximum(v_dead[self.us],
                                               v_dead[self.ud]))
        e_alive = e_live > e_dead
        if window is not None:
            v_alive &= v_live >= T - window
            e_alive &= e_live >= T - window
        return v_alive, self.us[e_alive], self.ud[e_alive]
