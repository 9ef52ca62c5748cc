"""Amortised range sweeps on a device mesh — static partition, O(delta) hops.

Round-3 finding: the mesh path re-ran ``partition_view`` (a per-shard Python
loop + halo construction + lexsorts) from scratch for EVERY hop of a range
sweep, while the single-chip path got incremental snapshots. The fix is the
same move that built ``engine/device_sweep``: work in the GLOBAL dense
space (every vertex/pair the pinned log ever mentions — positions never
change), so the partition layout, halo exchange structure and compiled
program are all STATIC across the sweep; each hop updates only the
fold-state values (latest/alive) at the delta's per-shard slots.

The reference re-runs its full per-timestamp handshake per range hop
(``RangeAnalysisTask.scala:18-35``); ``partition_view`` amortised nothing;
``ShardedSweep`` amortises everything but the O(delta) host fold.

Supports the same program class as ``DeviceSweep``: no occurrence arrays,
no host-materialised properties (``engine.device_sweep.supported``).
"""

from __future__ import annotations

import numpy as np

from ..core.events import EventLog
from ..core.snapshot import INT64_MIN
from ..core.sweep import fold_cache, log_fingerprint, seeded_fork
from ..engine.device_sweep import log_index, log_partition, supported
from ..obs.trace import TRACER
from . import sharded
from .sharded import ShardedView, _build_halo, _pow2


class StaticPartition:
    """What the vertex-sharded route derives from a log's pair table and
    a shard count ALONE: the range partition of the global pair table in
    both directions (per-shard index blocks, the engine-position ->
    (shard, slot) maps a hop's delta is patched through, the fold-state
    rank of every block row, which a seeded sweep fills its blocks by)
    and both halo layouts. Built once a log (``log_partition``: held on
    the log's index beside the pair tables it was cut from) and shared,
    read-only, by every ``ShardedSweep`` over that log; ``resident`` is
    ``sharded.run``'s cache of the device copies of what no hop changes.
    """

    def __init__(self, t, n_shards: int):
        if t.n_pad % n_shards:
            raise ValueError(
                f"vertex shards ({n_shards}) must divide the padded global "
                f"vertex count ({t.n_pad})")
        S = self.S = n_shards
        n_loc = self.n_loc = t.n_pad // n_shards
        sharded.note_partition_build()  # the ONE static build of this log
        rank_of_eng = np.empty(t.m, np.int64)
        rank_of_eng[t.eng_of_rank] = np.arange(t.m)

        def build(owner_of, local_of, global_of):
            owner = owner_of[: t.m] // n_loc
            order = np.lexsort((local_of[: t.m], owner))
            counts = np.bincount(owner, minlength=S)
            m_loc = _pow2(int(counts.max()) if t.m else 0)
            idx_g = np.full((S, m_loc), t.n_pad - 1, np.int32)
            idx_l = np.full((S, m_loc), n_loc - 1, np.int32)
            shard_of = np.empty(t.m, np.int32)   # engine pos -> (shard, slot)
            slot_of = np.empty(t.m, np.int32)
            off = 0
            for sh in range(S):
                c = int(counts[sh])
                rows = order[off: off + c]       # engine positions, sorted
                off += c
                idx_g[sh, :c] = global_of[rows]
                idx_l[sh, :c] = owner_of[rows] - sh * n_loc
                shard_of[rows] = sh
                slot_of[rows] = np.arange(c, dtype=np.int32)
            # shard sh's block rows, in order, hold the fold state of the
            # pair ranks ranks[sum(counts[:sh]):][:counts[sh]]
            return (m_loc, idx_g, idx_l, shard_of, slot_of,
                    rank_of_eng[order], counts)

        esrc = t.e_src.astype(np.int64)
        edst = t.e_dst.astype(np.int64)
        (self.m_d, self.d_src_g, self.d_dst_l, self.d_shard, self.d_slot,
         self.d_ranks, self.d_counts) = build(edst, edst % n_loc, esrc)
        (self.m_s, self.s_dst_g, self.s_src_l, self.s_shard, self.s_slot,
         self.s_ranks, self.s_counts) = build(esrc, esrc % n_loc, edst)
        self.h_d, self.d_src_h, self.d_send, halo_d = _build_halo(
            self.d_src_g, n_loc, S)
        self.h_s, self.s_dst_h, self.s_send, halo_s = _build_halo(
            self.s_dst_g, n_loc, S)
        # per-shard degree/halo skew of the ONE static partition every
        # sweep over this log amortises — same surface as partition_view
        self.skew = sharded.shard_skew(
            edges_dst=self.d_counts, edges_src=self.s_counts,
            halo_dst=halo_d, halo_src=halo_s)
        #: pair rows in both directions, the rows the blocks pad them to
        #: (every shard to the fullest one's next power of two) and the
        #: halo slots a device is sent a superstep on the halo route
        self.rows = 2 * int(t.m)
        self.pad_rows = S * (self.m_d + self.m_s)
        self.halo_rows = S * (self.h_d + self.h_s)
        #: device copies of the blocks no hop changes, by (mesh, name):
        #: ``sharded.run`` fills it at the first dispatch on a mesh
        self.resident: dict = {}
        sharded.note_partition_skew(self.skew, self.layout())

    @property
    def nbytes(self) -> int:
        """Host bytes of the partition's arrays."""
        return int(sum(a.nbytes for a in vars(self).values()
                       if isinstance(a, np.ndarray)))

    def layout(self) -> dict:
        """``partition.build``'s span arguments and ``/statusz``
        ``collectives.partition``."""
        return {"shards": self.S, "rows": self.rows,
                "pad_rows": self.pad_rows, "halo_rows": self.halo_rows,
                "pad_factor": round(self.pad_rows / max(self.rows, 1), 4)}


class ShardedSweep:
    """Ascending-time range sweep over a mesh with a static partition.

    ``run(program, T, ...)`` advances the host fold to T, patches the delta
    into the per-shard blocks, and dispatches the (cached) compiled SPMD
    program. Results are in the GLOBAL dense vertex space (row i is
    ``self.tables.uv[i]``), like ``DeviceSweep``. The partition is the
    log's (``log_partition``: ``partition_status`` says ``built`` or
    ``held``); the fold state and its blocks are this sweep's own, seeded
    at the first ``advance`` from the fold cache's nearest checkpoint.
    """

    def __init__(self, log: EventLog, n_shards: int):
        # the log's shared index: builder forked, tables read-only
        self.sw, t, self.index_status = log_index(log)
        self.t = self.tables = t
        with TRACER.span("partition.build") as sp:
            p, self.partition_status = log_partition(
                log, t, n_shards, StaticPartition)
            sp.set(status=self.partition_status, **p.layout())
        self.partition = p
        S, n_loc = p.S, p.n_loc
        self.S, self.n_loc = S, n_loc

        # mutable fold-state blocks (alive masks + latest times), all-dead
        def blk(m_loc, fill, dt):
            return np.full((S, m_loc), fill, dt)

        m_d, m_s = p.m_d, p.m_s
        self.sv = ShardedView(
            n_shards=S, n_loc=n_loc, m_loc_d=m_d, m_loc_s=m_s,
            vids=t.vids.reshape(S, n_loc),
            v_mask=np.zeros((S, n_loc), bool),
            v_latest=np.full((S, n_loc), INT64_MIN, np.int64),
            v_first=np.full((S, n_loc), INT64_MIN, np.int64),
            d_src_g=p.d_src_g, d_dst_l=p.d_dst_l,
            d_mask=blk(m_d, False, bool),
            d_time=blk(m_d, INT64_MIN, np.int64),
            d_first=blk(m_d, INT64_MIN, np.int64),
            s_dst_g=p.s_dst_g, s_src_l=p.s_src_l,
            s_mask=blk(m_s, False, bool),
            s_time=blk(m_s, INT64_MIN, np.int64),
            s_first=blk(m_s, INT64_MIN, np.int64),
            d_props={}, s_props={}, view=None,
            h_d=p.h_d, d_src_h=p.d_src_h, d_send=p.d_send,
            h_s=p.h_s, s_dst_h=p.s_dst_h, s_send=p.s_send,
            skew=p.skew, resident=p.resident,
        )
        self._shell = _Shell(time=0, n_pad=t.n_pad, vids=t.vids,
                             v_mask=self.sv.v_mask.reshape(-1),
                             v_latest_time=self.sv.v_latest.reshape(-1),
                             v_first_time=self.sv.v_first.reshape(-1))
        self.sv.view = self._shell
        self.t_now: int | None = None
        # Round-7 finding: ``sv.skew`` was computed ONCE above and never
        # again, so after a large ingest suffix the route chooser and the
        # advisor's shard-skew rule kept reading day-1 balance. Track edge
        # rows touched since the last skew publication and recompute
        # (sampled, O(S * min(m_loc, 64Ki))) once a quarter of the edge
        # table has churned.
        self._rows_since_skew = 0
        self._skew_refresh_rows = max(256, t.m // 4)

    # ---- sweep driving ----

    def advance(self, time: int) -> None:
        time = int(time)
        if self.t_now is not None and time < self.t_now:
            raise ValueError(
                f"ShardedSweep times must ascend (got {time} < {self.t_now})")
        if self.t_now is not None and time == self.t_now:
            return
        if self.t_now is None:
            self._seed(time)
        else:
            self.sw._advance(time)
            self._patch(self.sw.last_delta)
        self.t_now = time
        self._shell.time = time

    def _seed(self, time: int) -> None:
        """The first hop: the builder forked at ``time`` from the fold
        cache's nearest checkpoint (``core/sweep.seeded_fork``: the state
        it reaches is the next request's checkpoint), then every block
        filled from that absolute fold state — a gather through the
        partition's ranks, one ``partition.patch`` span with
        ``seed=true``. With no cache, or none behind ``time``, the
        builder advances from the log's first event."""
        cache = fold_cache()
        fp = log_fingerprint(self.sw.log) if cache is not None else None
        sw = self.sw = seeded_fork(self.sw, time, cache, fp,
                                   self.sw._config())
        p, sv, n = self.partition, self.sv, self.t.n
        with TRACER.span("partition.patch", seed=True,
                         rows=p.rows + n):
            sv.v_mask.reshape(-1)[:n] = sw.v_alive
            sv.v_latest.reshape(-1)[:n] = sw.v_lat
            sv.v_first.reshape(-1)[:n] = sw.v_first
            for ranks, counts, blocks in (
                    (p.d_ranks, p.d_counts,
                     (sv.d_mask, sv.d_time, sv.d_first)),
                    (p.s_ranks, p.s_counts,
                     (sv.s_mask, sv.s_time, sv.s_first))):
                off = 0
                for sh in range(self.S):
                    c = int(counts[sh])
                    r = ranks[off: off + c]
                    off += c
                    blocks[0][sh, :c] = sw.e_alive[r]
                    blocks[1][sh, :c] = sw.e_lat[r]
                    blocks[2][sh, :c] = sw.e_first[r]
        # every row was written: publish the live rows' skew, not the
        # partition's (which counts every pair the log ever held)
        sharded.refresh_partition_skew(sv)

    def _patch(self, d: dict) -> None:
        """A hop's delta written into the per-shard blocks."""
        sv, n_loc, p = self.sv, self.n_loc, self.partition
        with TRACER.span("partition.patch",
                         rows=2 * len(d["e_enc"]) + len(d["v_idx"])):
            vi = d["v_idx"]
            if len(vi):
                vs, vl = vi // n_loc, vi % n_loc
                sv.v_mask[vs, vl] = d["v_alive"]
                sv.v_latest[vs, vl] = d["v_lat"]
                sv.v_first[vs, vl] = d["v_first"]
            if not len(d["e_enc"]):
                return
            pos = self.t.eng_pos(d["e_enc"])
            for shard, slot, blocks in (
                    (p.d_shard, p.d_slot, (sv.d_mask, sv.d_time, sv.d_first)),
                    (p.s_shard, p.s_slot, (sv.s_mask, sv.s_time, sv.s_first))):
                sh, sl = shard[pos], slot[pos]
                blocks[0][sh, sl] = d["e_alive"]
                blocks[1][sh, sl] = d["e_lat"]
                blocks[2][sh, sl] = d["e_first"]
        self._rows_since_skew += len(pos)
        if self._rows_since_skew >= self._skew_refresh_rows:
            self._rows_since_skew = 0
            sharded.refresh_partition_skew(sv)

    # ---- dispatch ----

    def run(self, program, time: int | None = None, *, mesh,
            window: int | None = None, windows=None, comm: str = "auto",
            block: bool = True):
        """Advance to `time` and run `program` over `mesh` using the static
        partition. Result rows are global dense vertex indices."""
        if not supported(program):
            raise ValueError(
                "program needs occurrences or host-materialised properties — "
                "use jobs/bsp with per-view partitioning instead")
        if mesh.shape[sharded.V_AXIS] != self.S:
            raise ValueError(
                f"mesh vertex axis ({mesh.shape[sharded.V_AXIS]}) != "
                f"partition shards ({self.S})")
        if time is not None:
            self.advance(time)
        if self.t_now is None:
            raise ValueError("call advance(T) (or pass time=) before run()")
        return sharded.run(program, self._shell, mesh, window=window,
                           windows=windows, sharded_view=self.sv, comm=comm,
                           block=block)

    def mode_rows(self, program, mesh, k: int) -> int:
        """Rows one superstep of ``program`` over ``k`` windows hands to
        ``segment_mode``'s sort, padding included, summed over the
        shards: the padded block rows of every direction the program
        listens on, once a window (the window axis pads ``k`` to its
        size). 0 for a program whose exchange is no mode."""
        if not program.exchange_is_mode:
            return 0
        W = mesh.shape.get(sharded.W_AXIS, 1)
        rows = (self.sv.m_loc_d if program.direction in ("out", "both")
                else 0) + (self.sv.m_loc_s
                           if program.direction in ("in", "both") else 0)
        return self.S * rows * (-(-k // W) * W)

    def reduce_view(self):
        """A frozen host copy of the reducer-facing view fields at t_now —
        safe to keep across a later ``advance`` (the live shell mutates)."""
        return _Shell(time=int(self._shell.time), n_pad=self.t.n_pad,
                      vids=self.t.vids,
                      v_mask=self._shell.v_mask.copy(),
                      v_latest_time=self._shell.v_latest_time.copy(),
                      v_first_time=self._shell.v_first_time.copy())


class _Shell:
    """The reducer-facing slice of a GraphView over the global dense space:
    enough for ``sharded.run`` (time, n_pad) and host reducers
    (vids/v_mask/window_masks)."""

    def __init__(self, time, n_pad, vids, v_mask, v_latest_time,
                 v_first_time):
        self.time = time
        self.n_pad = n_pad
        self.vids = vids
        self.v_mask = v_mask
        self.v_latest_time = v_latest_time
        self.v_first_time = v_first_time

    def window_masks(self, windows):
        w = np.asarray(windows, np.int64).reshape(-1, 1)
        lo = self.time - w
        v = self.v_mask[None, :] & (self.v_latest_time[None, :] >= lo)
        return v, None  # edge masks live in the sharded blocks

    def vertex_prop(self, name, default=np.nan):  # pragma: no cover
        raise ValueError("ShardedSweep does not materialise properties — "
                         "programs with props use the per-view path")
