"""Column-sharded range sweeps — view-axis parallelism over a device mesh.

The hop-batched columnar engines (``engine/hopbatch``) evaluate every
(hop, window) view of a range query as an independent COLUMN of one
program. Independence makes the multi-chip mapping trivial and
collective-free: shard the COLUMN axis across all devices of the mesh
(graph tables replicate — they are the small, read-only part), and each
chip runs the same while-loop on its block of views. No halo exchange, no
psum in the superstep loop — the only cross-chip traffic is the initial
replicated-table broadcast. This is the temporal analogue of batch data
parallelism, complementing ``parallel/sharded.py``'s vertex sharding
(which exists for graphs too big for one chip's HBM).

The program is built once a process and key: ``_compiled_columns`` is an
``lru_cache``d factory, as every one-chip engine's runner is, keyed on
the kind, the devices in mesh order and every static the traced block
reads. A key's first request traces, lowers and loads it (the ``xla.*``
events under that request's ``comm.exchange``); every later request of
the key calls the same jit object (``/statusz``
``compile_caches.columns._compiled_columns``: ``misses`` / ``hits``).

Reference contrast: the reference cannot parallelise ACROSS the hops of a
Range query at all — each hop is a fresh sequential actor handshake
(``RangeAnalysisTask.scala:18-35``); here hops*windows spread over the
whole mesh.
"""

from __future__ import annotations

import functools
import time as _time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.hopbatch import (_bfs_columns, _cc_columns, _column_layout,
                               _column_masks, _pagerank_columns, _seed_mask,
                               _tile_budget_bytes)
from ..obs import ledger as _ledger
from ..obs.trace import TRACER
from .sharded import COLLECTIVES, _shard_map

C_AXIS = "columns"


@functools.lru_cache(maxsize=64)
def _compiled_columns(kind: str, devices: tuple, n_pad: int, tdt: str,
                      damping: float, tol: float, max_steps: int,
                      directed: bool, n_extra: int, tile_budget: int):
    """``(mesh, runner)`` of the column-sharded program, built once a key.

    The key holds every value the traced ``block`` reads and the mesh it
    is mapped over — ``devices`` in mesh order, the time dtype's name,
    ``n_extra`` 0 / 1 / 2 (none, the BFS seed mask, seed mask + weight
    columns) and the resolved ``RTPU_TILE_BUDGET_MB`` — so a process
    traces, lowers and loads the program once per key and argument
    shapes (``m_pad``, ``C`` and ``H`` are shapes: ``jax.jit`` keys on
    them itself); every later request calls the same jit object."""
    if kind not in ("pagerank", "cc", "bfs"):
        raise ValueError(f"unknown columnar kind {kind!r}")
    tdt = jnp.dtype(tdt)

    def block(e_src, e_dst, el, ea, vl, va, hoc, tc, wc, *extra):
        me, mv = _column_masks(tdt, el, ea, vl, va, hoc, tc, wc)
        if kind == "pagerank":
            out, steps = _pagerank_columns(me, mv, e_src, e_dst, n_pad,
                                           damping, tol, max_steps,
                                           tile_budget=tile_budget)
        elif kind == "cc":
            out, steps = _cc_columns(me, mv, e_src, e_dst, n_pad,
                                     max_steps, tile_budget=tile_budget)
        else:
            ew = extra[1][hoc].T if n_extra > 1 else 1.0
            out, steps = _bfs_columns(me, mv, e_src, e_dst, n_pad,
                                      max_steps, directed, extra[0], ew,
                                      tile_budget=tile_budget)
        return out, steps[None]   # scalar -> [1] so steps concatenates

    mesh = Mesh(np.asarray(devices), (C_AXIS,))
    # the registry wrapper gives the route its kernel-table row and
    # dispatch count; it harvests once per (kind, shapes)
    return mesh, _ledger.instrument(f"columns.{kind}", jax.jit(_shard_map(
        block, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(),   # tables replicate
                  P(C_AXIS), P(C_AXIS), P(C_AXIS), *([P()] * n_extra)),
        out_specs=(P(C_AXIS), P(C_AXIS)))))


def run_columns_sharded(tables, e_lat, e_alive, v_lat, v_alive, hop_times,
                        windows, devices, *, kind: str = "pagerank",
                        damping: float = 0.85, tol: float = 1e-7,
                        max_steps: int = 20, seeds=(),
                        directed: bool = False, weight_cols=None):
    """Columnar sweep with the (hop, window) axis sharded over ``devices``
    (any iterable of jax devices, e.g. ``mesh.devices.ravel()``).

    ``kind``: ``"pagerank"`` | ``"cc"`` | ``"bfs"`` (``seeds``/``directed``
    apply; pass ``weight_cols`` ([H, m_pad] f32) for weighted SSSP).
    Returns ``(result [C, n_pad] hop-major, steps)`` — identical values to
    the single-device ``hopbatch`` runners (tested); columns pad up to a
    device multiple internally and the pad is dropped before returning."""
    t_in = _time.perf_counter()
    devices = list(devices)
    n_dev = len(devices)
    H, C, hop_of_col, T_col, w_col = _column_layout(hop_times, windows)
    pad = (-C) % n_dev
    if pad:
        # replicate column 0 into the pad slots — cheapest valid views
        hop_of_col = np.concatenate([hop_of_col,
                                     np.repeat(hop_of_col[:1], pad)])
        T_col = np.concatenate([T_col, np.repeat(T_col[:1], pad)])
        w_col = np.concatenate([w_col, np.repeat(w_col[:1], pad)])

    n_pad = tables.n_pad
    extra_host = []
    if kind == "bfs":
        extra_host.append(_seed_mask(tables, seeds))
        if weight_cols is not None:
            extra_host.append(weight_cols)

    # the key holds every value the traced block reads, so only a key's
    # first request builds the program (the xla.* events under its
    # comm.exchange, obs/device.py); every later one is JAX's cached
    # call. The budget is resolved HERE, outside the traced block: an env
    # read at trace time would bake a budget the key doesn't carry
    # (rtpulint RT001)
    tdt = np.dtype(tables.tdtype).name
    mesh, shard = _compiled_columns(
        kind, tuple(devices), int(n_pad), tdt, float(damping), float(tol),
        int(max_steps), bool(directed), len(extra_host),
        _tile_budget_bytes())

    repl = NamedSharding(mesh, P())
    put = lambda a: jax.device_put(jnp.asarray(a), repl)
    # the only cross-chip traffic on this route is the one-time
    # replicated-table broadcast — account it as the "replicate" route
    # (rows = table rows, bytes = table payload x devices receiving it)
    repl_arrays = [tables.e_src, tables.e_dst, e_lat, e_alive, v_lat,
                   v_alive, *extra_host]
    repl_bytes = int(sum(np.asarray(a).nbytes for a in repl_arrays))
    repl_rows = int(sum(np.asarray(a).shape[-1] if np.asarray(a).ndim
                        else 1 for a in repl_arrays))
    proc = TRACER.process_index
    multi = len({d.process_index for d in mesh.devices.flat}) > 1
    # mesh-divergence sanitizer: fingerprint the dispatch before issuing
    # it (same contract as parallel/sharded.py — a hang still journals)
    from ..analysis.sanitizer import mesh_active

    msan = mesh_active()
    msite = f"parallel.columns.run_columns_sharded/{kind}"
    if msan is not None:
        msan.note_dispatch(msite, "replicate",
                           f"D{n_dev}C{C}n{n_pad}", str(tdt))
    # the column route has exactly one comm shape; record it in the same
    # route table as the vertex-sharded dispatches so /statusz shows the
    # full picture of what moved over the wire and why
    COLLECTIVES.note_route_decision({
        "algorithm": f"columns.{kind}", "route": "replicate",
        "requested": "replicate",
        "reason": "column-sharded dispatch replicates tables once",
        "est_bytes": {"replicate": repl_bytes * max(1, n_dev - 1)},
    })
    t0 = _time.perf_counter()
    with TRACER.span("comm.exchange", route="replicate",
                     direction="columns", process=proc,
                     shards=n_dev, rows=repl_rows * max(1, n_dev - 1),
                     bytes=repl_bytes * max(1, n_dev - 1)):
        # the job thread's DISPATCH of the puts of the replicated tables
        # and the column descriptors: ``device_put`` returns before the
        # bytes have moved, so the transfer is not in this span — its
        # seconds fall where the host next waits for the chips
        # (``comm.block_wait`` at the latest). The call's own seconds are
        # ``comm.exchange``'s self time: what is left of it beside this
        # span and the ``xla.*`` events (trace, lower, compile or cache
        # read) recorded under it
        with TRACER.span("comm.put", arrays=len(repl_arrays) + 3,
                         bytes=repl_bytes):
            args = [put(a) for a in repl_arrays]
            # the sharded column descriptors sit after the six tables
            args[6:6] = [jnp.asarray(hop_of_col), jnp.asarray(T_col),
                         jnp.asarray(w_col)]
        result, steps = shard(*args)
        barrier_wait = 0.0
        # rtpulint: spmd-uniform — `multi` derives from the mesh's device set, which every process builds from the same global device list; all processes take the same arm
        if multi:
            # the columns span processes' devices — replicate back to
            # every host (reducers are host code), like
            # parallel/sharded.py does. This wait is the per-process
            # straggler signal on the column-sharded route.
            from jax.experimental import multihost_utils

            jax.block_until_ready(result)
            t_bar = _time.perf_counter()
            watch = (msan.barrier_watch(msite, "replicate")
                     if msan is not None else None)
            try:
                with TRACER.span("comm.barrier_wait", route="replicate",
                                 process=proc):
                    result = multihost_utils.process_allgather(result,
                                                               tiled=True)
                    steps = multihost_utils.process_allgather(steps,
                                                              tiled=True)
            finally:
                if watch is not None:
                    watch.cancel()
            barrier_wait = _time.perf_counter() - t_bar
    COLLECTIVES.note_exchange(
        "replicate", "columns", rows=repl_rows * max(1, n_dev - 1),
        bytes_=repl_bytes * max(1, n_dev - 1),
        seconds=_time.perf_counter() - t0, supersteps=1,
        barrier_wait=barrier_wait)
    # the dispatch above only ENQUEUED the program: this read is where
    # the host waits for the chips (the same span, and the same ledger
    # phase, as the vertex-sharded route's local completion wait)
    b0 = _time.perf_counter()
    with TRACER.span("comm.block_wait", route="replicate", process=proc,
                     shards=n_dev):
        steps = int(np.max(np.asarray(steps)))
    led = _ledger.current()
    if led is not None:
        # the caller's thread, all of it: building and enqueueing the
        # program (and putting the tables) is compute, the read the wait
        led.add_phase("compute", b0 - t_in)
        led.add_phase("device_wait", _time.perf_counter() - b0)
    return result[:C], steps
