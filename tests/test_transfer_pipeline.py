"""Pipelined transfer engine + hop-lookahead prefetch correctness.

The tentpole contract: pipelining is TRANSPORT plumbing — results are
bit-identical to ``jax.device_put`` / the serial dispatch loops at every
depth, per-slice transport failures resume mid-array, and programming
errors surface immediately instead of burning backoff.
"""

import time

import numpy as np
import pytest

from raphtory_tpu.utils import transfer
from raphtory_tpu.utils.transfer import TransferEngine, _is_transient, _put_retry

from test_sweep import random_log


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_put_matches_device_put_across_chunk_boundaries(depth):
    """Every depth, shape, dtype, and (non-)divisible chunk split must be
    bit-identical to a plain device_put — including 2-D row groups, a
    non-contiguous view (forces a real staging copy), bool, and 0-d."""
    import jax

    rng = np.random.default_rng(0)
    cases = (
        rng.integers(-2**31, 2**31 - 1, 100_003, np.int64).astype(np.int32),
        rng.random((1001, 7)).astype(np.float32),   # odd rows, 2-D
        rng.random(4096)[::2].astype(np.float32),   # non-contiguous
        rng.integers(0, 2, 5000).astype(bool),
        np.float32(3.5),                            # 0-d passthrough
    )
    for a in cases:
        eng = TransferEngine(depth=depth, chunk_bytes=1 << 10)
        got = eng.put(a)
        want = jax.device_put(np.ascontiguousarray(a))
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert eng.stats.depth_high_water <= depth


def test_put_many_order_and_passthrough():
    """put_many preserves order, matches per-array puts bitwise, and
    passes already-device arrays through untouched."""
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    dev = jnp.arange(7)
    arrays = [rng.random((300, 5)).astype(np.float32), dev,
              np.arange(10, dtype=np.int32), np.array([True, False])]
    eng = TransferEngine(depth=2, chunk_bytes=1 << 10)
    outs = eng.put_many(arrays)
    assert outs[1] is dev   # no copy of device-resident inputs
    for a, o in zip(arrays, outs):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(a))


def test_transport_failure_resumes_mid_array(monkeypatch):
    """First attempt of EVERY slice flaps; each retry re-ships only that
    slice (total puts == 2 * slices), and the result is bit-identical."""
    import jax

    real = jax.device_put
    calls = {"n": 0}

    def flaky(a, device=None):
        calls["n"] += 1
        if calls["n"] % 2 == 1:
            raise RuntimeError("UNAVAILABLE: injected flap")
        return real(a, device)

    monkeypatch.setattr(jax, "device_put", flaky)
    rng = np.random.default_rng(2)
    a = rng.integers(0, 255, 50_000).astype(np.uint8)
    eng = TransferEngine(depth=2, chunk_bytes=1 << 12, backoff=0.0)
    got = eng.put(a)
    np.testing.assert_array_equal(np.asarray(got), a)
    n_slices = -(-a.nbytes // (1 << 12))
    assert eng.stats.retries == n_slices
    assert calls["n"] == 2 * n_slices   # completed slices never re-ship


def test_programming_error_raises_immediately(monkeypatch):
    """A shape/dtype bug must NOT be retried — no backoff sleeps, no
    retry counter, original exception type surfaces (the ~70 s/chunk
    pathology a round-5 review flagged)."""
    import jax

    def broken(a, device=None):
        raise TypeError("bad dtype for device_put")

    monkeypatch.setattr(jax, "device_put", broken)
    eng = TransferEngine(depth=2, chunk_bytes=1 << 10, backoff=30.0)
    t0 = time.perf_counter()
    with pytest.raises(TypeError, match="bad dtype"):
        eng.put(np.zeros(10_000, np.float32))
    assert time.perf_counter() - t0 < 5.0   # no exponential backoff burned
    assert eng.stats.retries == 0

    # same contract through the legacy helper
    monkeypatch.setattr(
        jax, "device_put",
        lambda a, device=None: (_ for _ in ()).throw(
            ValueError("INVALID_ARGUMENT: shape mismatch")))
    t0 = time.perf_counter()
    with pytest.raises(ValueError, match="INVALID_ARGUMENT"):
        _put_retry(np.zeros(8), retries=4, backoff=30.0, device=None)
    assert time.perf_counter() - t0 < 5.0


def test_transient_classifier():
    assert _is_transient(RuntimeError("UNAVAILABLE: TPU backend setup"))
    assert _is_transient(RuntimeError("DEADLINE_EXCEEDED while copying"))
    assert not _is_transient(TypeError("cannot convert"))
    assert not _is_transient(ValueError("INVALID_ARGUMENT: rank"))

    class XlaRuntimeError(Exception):
        pass

    # a runtime/compile error the device reports is its answer, not a
    # transport flap: it must surface on the first attempt
    assert not _is_transient(XlaRuntimeError("INTERNAL: stream failed"))
    assert not _is_transient(XlaRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel"))
    assert not _is_transient(XlaRuntimeError("RESOURCE_EXHAUSTED: OOM"))
    assert _is_transient(XlaRuntimeError("UNAVAILABLE: connection lost"))


def test_metrics_mirror():
    """A put shows up in the Prometheus bundle (bytes + slices)."""
    from raphtory_tpu.obs.metrics import METRICS

    before = METRICS.registry.get_sample_value("raphtory_h2d_bytes_total")
    TransferEngine(depth=2, chunk_bytes=1 << 10).put(
        np.zeros(10_000, np.float32))
    after = METRICS.registry.get_sample_value("raphtory_h2d_bytes_total")
    assert after is not None and after - (before or 0.0) >= 40_000


def test_device_sweep_pipelined_matches_serial():
    """run_sweep(prefetch=True) — fold i+1 in the worker while hop i
    computes — must be BIT-identical to the serial advance/run loop,
    independent of transfer depth."""
    from raphtory_tpu.algorithms import PageRank
    from raphtory_tpu.engine.device_sweep import DeviceSweep

    rng = np.random.default_rng(7)
    log = random_log(rng, n_events=700, n_ids=45, t_span=90)
    times = [10, 30, 31, 55, 70, 89]
    windows = [1000, 20]
    pr = PageRank(max_steps=20, tol=1e-7)

    ds = DeviceSweep(log)
    want = []
    for T in times:
        ds.advance(T)
        want.append(np.asarray(ds.run(pr, windows=windows)[0]))

    for depth in ("1", "3"):
        import os

        os.environ["RTPU_TRANSFER_DEPTH"] = depth
        try:
            transfer._SHARED = None   # rebuild with the env depth
            got, _ = DeviceSweep(log).run_sweep(pr, times, windows=windows)
        finally:
            os.environ.pop("RTPU_TRANSFER_DEPTH", None)
            transfer._SHARED = None
        assert len(got) == len(want)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, np.asarray(g))


def test_device_sweep_recovers_after_mid_sweep_failure():
    """A dispatch failure mid-pipelined-sweep leaves t_now ahead of the
    device buffers (the lookahead fold keeps moving) — the NEXT hop must
    take the full-refresh path and produce correct results, not scatter
    deltas onto (or noop over) stale buffers.

    Driven through the ``device.dispatch`` failpoint (resilience/faults)
    rather than a monkeypatch: the chaos the bench injects in production
    code paths is the SAME failure this recovery test proves, so the two
    can never drift apart."""
    from raphtory_tpu.algorithms import PageRank
    from raphtory_tpu.core.snapshot import build_view
    from raphtory_tpu.engine import bsp
    from raphtory_tpu.engine.device_sweep import DeviceSweep
    from raphtory_tpu.resilience import faults

    rng = np.random.default_rng(9)
    log = random_log(rng, n_events=600, n_ids=40, t_span=80)
    pr = PageRank(max_steps=20, tol=1e-7)
    ds = DeviceSweep(log)

    faults.arm("device.dispatch=error:1.0:1")
    try:
        with pytest.raises(faults.FaultError,
                           match="injected fault at device.dispatch"):
            ds.run_sweep(pr, [10, 30, 50, 70], windows=[100], prefetch=True)
    finally:
        faults.disarm()

    # continue the sweep: hop 50 (already folded by the lookahead) and a
    # fresh hop must both match the per-view reference exactly
    for T in (50, 70):
        got, _ = ds.run(pr, T, windows=[100])
        view = build_view(log, T)
        want, _ = bsp.run(pr, view, windows=[100])
        mask = view.window_masks([100])[0][0]
        pos = np.searchsorted(ds.uv, view.vids[mask])
        np.testing.assert_allclose(np.asarray(got[0])[pos],
                                   np.asarray(want[0])[mask], atol=1e-5)


def test_device_sweep_rejects_descending_sweep():
    from raphtory_tpu.algorithms import PageRank
    from raphtory_tpu.engine.device_sweep import DeviceSweep

    rng = np.random.default_rng(8)
    log = random_log(rng, n_events=200, n_ids=20, t_span=50)
    with pytest.raises(ValueError, match="ascend"):
        DeviceSweep(log).run_sweep(PageRank(max_steps=5), [30, 10])


@pytest.mark.parametrize("warm", [False, True])
def test_hopbatch_prefetch_independent_of_pipeline(monkeypatch, warm):
    """Chunked columnar sweeps must return bitwise-identical results with
    the hop-lookahead prefetcher on and off (the prefetcher only moves
    WHERE the fold runs, never what it computes), at any transfer depth."""
    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    rng = np.random.default_rng(11)
    log = random_log(rng, n_events=800, n_ids=50, t_span=100)
    hops = [20, 40, 60, 80, 85, 99]
    windows = [1000, 25]

    def run():
        return np.asarray(HopBatchedPageRank(log, tol=1e-7, max_steps=20)
                          .run(hops, windows, chunks=3,
                               warm_start=warm)[0])

    monkeypatch.setenv("RTPU_PREFETCH", "0")
    serial = run()
    monkeypatch.setenv("RTPU_PREFETCH", "1")
    pipelined = run()
    np.testing.assert_array_equal(serial, pipelined)
    monkeypatch.setenv("RTPU_TRANSFER_DEPTH", "3")
    transfer._SHARED = None
    try:
        deeper = run()
    finally:
        transfer._SHARED = None
    np.testing.assert_array_equal(serial, deeper)


def test_hopbatch_prefetch_failure_drops_residency():
    """A hop_callback exploding mid-sweep (inside the prefetch worker)
    must propagate AND reset the running bases, exactly like the serial
    path — the next batch re-materialises instead of scattering onto a
    stale device state."""
    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    rng = np.random.default_rng(13)
    log = random_log(rng, n_events=600, n_ids=40, t_span=80)
    hb = HopBatchedPageRank(log, tol=1e-7, max_steps=10)

    calls = {"n": 0}

    def boom(T, sw):
        calls["n"] += 1
        if calls["n"] == 4:
            raise RuntimeError("hop callback exploded")

    with pytest.raises(RuntimeError, match="exploded"):
        hb.run([10, 20, 30, 40, 50, 60], [100], chunks=3,
               hop_callback=boom)
    assert hb._dev_base is None and hb._delta_base is None


def test_tile_budget_part_of_compiled_cache_key():
    """Changing RTPU_TILE_BUDGET_MB mid-process must produce a DIFFERENT
    compiled program object — the budget is in the lru_cache key, not
    read once at first trace (a round-5 review finding)."""
    from raphtory_tpu.engine import hopbatch as hb

    args = (1 << 10, 1 << 10, 2, 4, 0.85, 1e-7, 20, "int32", False)
    f_small = hb._compiled(*args, 64 << 20)
    f_big = hb._compiled(*args, 256 << 20)
    assert f_small is not f_big
    assert hb._compiled(*args, 64 << 20) is f_small   # still cached

    # and the resolver actually reads the env var per call
    import os

    os.environ["RTPU_TILE_BUDGET_MB"] = "17"
    try:
        assert hb._tile_budget_bytes() == 17 << 20
    finally:
        del os.environ["RTPU_TILE_BUDGET_MB"]
