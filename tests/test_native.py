"""Native C++ kernels agree exactly with the pure-numpy reference paths."""

import numpy as np
import pytest

from raphtory_tpu.core import snapshot as ss
from raphtory_tpu.core.events import EventLog
from raphtory_tpu.native import lib as native


pytestmark = pytest.mark.skipif(
    not native.available(), reason="native lib failed to build")


def _numpy_fold(keys, times, alive):
    order = np.lexsort((~alive, times) + tuple(reversed(keys)))
    sk = [k[order] for k in keys]
    st = times[order]
    sa = alive[order]
    ng = np.zeros(len(st), bool)
    ng[0] = True
    same = np.ones(len(st) - 1, bool)
    for k in sk:
        same &= k[1:] == k[:-1]
    ng[1:] = ~same
    last = ss._last_per_group(order, ng)
    first = np.flatnonzero(ng)
    return tuple(k[last] for k in sk), st[last], sa[last], st[first]


@pytest.mark.parametrize("nkeys", [1, 2])
def test_fold_latest_parity_random(nkeys):
    rng = np.random.default_rng(7)
    n = 50_000
    keys = tuple(rng.integers(0, 900, n) for _ in range(nkeys))
    times = rng.integers(0, 500, n)  # dense: many exact (key, time) ties
    alive = rng.random(n) < 0.6
    got = native.fold_latest(keys, times, alive)
    want = _numpy_fold(keys, times, alive)
    for g, w in zip(got[0], want[0]):
        np.testing.assert_array_equal(g, w)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(got[3], want[3])


def test_fold_latest_delete_wins_tie():
    # same entity, same time, add + delete → dead wins, regardless of order
    keys = (np.array([5, 5], np.int64),)
    times = np.array([10, 10], np.int64)
    for alive in ([True, False], [False, True]):
        _, lat, al, fst = native.fold_latest(keys, times, np.array(alive))
        assert lat[0] == 10 and fst[0] == 10 and not al[0]


def test_fold_latest_empty():
    out = native.fold_latest((np.empty(0, np.int64),),
                             np.empty(0, np.int64), np.empty(0, bool))
    assert len(out[1]) == 0


def test_build_view_native_matches_numpy(monkeypatch):
    rng = np.random.default_rng(3)
    log = EventLog()
    n_ev = 4000
    t = rng.integers(0, 1000, n_ev)
    for i in range(n_ev):
        r = rng.random()
        a, b = int(rng.integers(0, 60)), int(rng.integers(0, 60))
        if r < 0.15:
            log.add_vertex(int(t[i]), a, {"w": float(i)} if i % 7 == 0 else None)
        elif r < 0.7:
            log.add_edge(int(t[i]), a, b, {"amt": float(i)} if i % 5 == 0 else None)
        elif r < 0.85:
            log.delete_edge(int(t[i]), a, b)
        else:
            log.delete_vertex(int(t[i]), a)

    v_native = ss.build_view(log, 800, include_occurrences=True)

    monkeypatch.setattr(ss._native, "fold_latest", lambda *a: None)
    monkeypatch.setattr(ss._native, "lex_lookup2", lambda *a: None)
    v_numpy = ss.build_view(log, 800, include_occurrences=True)

    for f in ("vids", "v_mask", "v_latest_time", "v_first_time", "e_src",
              "e_dst", "e_mask", "e_latest_time", "e_first_time",
              "in_indptr", "out_indptr", "out_deg", "in_deg",
              "occ_src", "occ_dst", "occ_time", "occ_mask"):
        np.testing.assert_array_equal(
            getattr(v_native, f), getattr(v_numpy, f), err_msg=f)
    np.testing.assert_array_equal(
        v_native.edge_prop("amt"), v_numpy.edge_prop("amt"))
    np.testing.assert_array_equal(
        v_native.vertex_prop("w"), v_numpy.vertex_prop("w"))


def test_lex_lookup2_parity():
    rng = np.random.default_rng(11)
    pairs = np.unique(rng.integers(0, 200, (3000, 2)), axis=0)
    q1 = rng.integers(0, 250, 5000)
    q2 = rng.integers(0, 250, 5000)
    got = native.lex_lookup2(pairs[:, 0], pairs[:, 1], q1, q2)
    # numpy fallback path
    want = np.full(len(q1), -1, np.int64)
    for i in range(len(q1)):
        lo = np.searchsorted(pairs[:, 0], q1[i])
        hi = np.searchsorted(pairs[:, 0], q1[i], side="right")
        if lo < hi:
            j = lo + np.searchsorted(pairs[lo:hi, 1], q2[i])
            if j < hi and pairs[j, 1] == q2[i]:
                want[i] = j
    np.testing.assert_array_equal(got, want)


def test_parse_int_csv():
    # int() semantics: whitespace + CRLF tolerated, floats rejected
    data = b"1,2,300\n4,5,600\nbad,row,x\n7,8,900.0\n -1 , 0 ,5\r\n\n9,9"
    arr = native.parse_int_csv(data, ",", (0, 1, 2))
    np.testing.assert_array_equal(
        arr, [[1, 4, -1], [2, 5, 0], [300, 600, 5]])


def test_bulk_csv_pipeline_matches_row_path(tmp_path):
    from raphtory_tpu.ingestion.parser import IntCsvEdgeListParser
    from raphtory_tpu.ingestion.pipeline import IngestionPipeline
    from raphtory_tpu.ingestion.source import FileSource

    rng = np.random.default_rng(5)
    path = tmp_path / "edges.csv"
    with open(path, "w", newline="") as f:
        f.write("src,dst,time\r\n")  # CRLF: both paths must agree
        for _ in range(500):
            f.write(f"{rng.integers(0, 40)},{rng.integers(0, 40)},"
                    f"{rng.integers(0, 100)}\r\n")

    def ingest(use_bulk: bool):
        pipe = IngestionPipeline()
        parser = IntCsvEdgeListParser()
        if not use_bulk:
            parser.bulk_parse = lambda data: None
        pipe.add_source(FileSource(str(path), name="f", skip_header=True),
                        parser)
        pipe.run()
        return pipe

    a, b = ingest(True), ingest(False)
    assert a.counts["f"] == b.counts["f"] == 500
    for col in ("time", "kind", "src", "dst"):
        np.testing.assert_array_equal(a.log.column(col), b.log.column(col))
    assert a.watermarks.safe_time() == b.watermarks.safe_time()


def test_parse_int_csv_underscore_grouping_matches_python_int():
    # int("1_0") == 10; "_1", "1_", "1__0" all raise — bulk path must agree
    data = b"1_0,2,3\n_1,2,3\n1_,2,3\n1__0,2,3\n5,6,7"
    arr = native.parse_int_csv(data, ",", (0, 1, 2))
    np.testing.assert_array_equal(arr, [[10, 5], [2, 6], [3, 7]])


def test_multibyte_separator_falls_back_to_row_path(tmp_path):
    from raphtory_tpu.ingestion.parser import IntCsvEdgeListParser
    from raphtory_tpu.ingestion.pipeline import IngestionPipeline
    from raphtory_tpu.ingestion.source import FileSource

    assert native.parse_int_csv(b"1||2||3", "||", (0, 1, 2)) is None
    path = tmp_path / "pipes.csv"
    path.write_text("1||2||3\n4||5||6\n")
    pipe = IngestionPipeline()
    pipe.add_source(FileSource(str(path), name="p"),
                    IntCsvEdgeListParser(sep="||", src_col=0, dst_col=1,
                                         time_col=2))
    pipe.run()
    assert not pipe.errors
    assert pipe.counts["p"] == 2


def test_append_batch_props_atomic():
    log = EventLog()
    log.append_batch(
        np.array([1, 2], np.int64),
        np.array([0, 2], np.uint8),   # VERTEX_ADD, EDGE_ADD kinds
        np.array([10, 10], np.int64),
        np.array([-1, 20], np.int64),
        props=[(0, {"w": 1.5}), (1, {"x": 2.5})],
    )
    assert log.props.n == 2
    # props reference the right event rows
    np.testing.assert_array_equal(log.props.column("event"), [0, 1])


def test_device_put_chunked_matches_device_put(monkeypatch):
    """Chunked resilient upload is bit-identical to a plain device_put,
    including non-divisible row counts, 2-D arrays, and scalars — and
    retries transient failures instead of dying."""
    import numpy as np

    from raphtory_tpu.utils import transfer

    rng = np.random.default_rng(0)
    for a in (rng.integers(-2**31, 2**31 - 1, 100_003, np.int32),
              rng.random((1000, 7)).astype(np.float32),
              np.float32(3.5)):
        got = transfer.device_put_chunked(a, chunk_bytes=1 << 10)
        np.testing.assert_array_equal(np.asarray(got), a)

    # flaky transport: first attempt of each slice fails, retry succeeds
    import jax

    real = jax.device_put
    calls = {"n": 0}

    def flaky(a, device=None):
        calls["n"] += 1
        if calls["n"] % 2 == 1:
            raise RuntimeError("UNAVAILABLE: injected flap")
        return real(a, device)

    monkeypatch.setattr(jax, "device_put", flaky)
    a = rng.integers(0, 255, 5000, np.uint8)
    got = transfer.device_put_chunked(a, chunk_bytes=1 << 10, backoff=0.0)
    np.testing.assert_array_equal(np.asarray(got), a)


def test_native_radix_and_searchsorted_match_numpy():
    rng = np.random.default_rng(4)
    keys = rng.integers(0, 2**63, 50_000, dtype=np.uint64)
    order = native.radix_argsort_u64(keys)
    np.testing.assert_array_equal(keys[order], np.sort(keys))
    # stability on heavy duplicates
    dup = (rng.integers(0, 7, 20_000).astype(np.uint64) << np.uint64(32))
    o = native.radix_argsort_u64(dup)
    for b in range(7):
        idx = o[dup[o] == (np.uint64(b) << np.uint64(32))]
        assert np.all(np.diff(idx) > 0)
    base = np.sort(keys)
    q = rng.integers(0, 2**63, 10_000, dtype=np.uint64)
    for side in ("left", "right"):
        np.testing.assert_array_equal(
            native.searchsorted_u64(base, q, side),
            np.searchsorted(base, q, side=side))
