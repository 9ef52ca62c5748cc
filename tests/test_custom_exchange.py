"""Generic (non-combiner) message exchange: segment_mode + LabelPropagation.

The sum/min/max combiners cannot express a per-label histogram; the
sort-based custom-exchange path must — against a pure-host reference with
identical tie-breaking, on both engines."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raphtory_tpu import EventLog, build_view
from raphtory_tpu.algorithms import CDLP, LabelPropagation
from raphtory_tpu.engine import bsp
from raphtory_tpu.engine.program import custom_exchange, takes_mode_counts
from raphtory_tpu.obs.trace import TRACER
from raphtory_tpu.ops.segment import (segment_counts, segment_counts_at,
                                      segment_ends_pos, segment_mode)
from raphtory_tpu.parallel import sharded


# ---------------------------------------------------------------- primitive


def test_segment_mode_basic():
    vals = jnp.asarray([5, 5, 7, 7, 7, 2], jnp.int32)
    segs = jnp.asarray([0, 0, 0, 1, 1, 1], jnp.int32)
    out = segment_mode(vals, segs, 3)
    # seg0: {5:2, 7:1} -> 5; seg1: {7:2, 2:1} -> 7; seg2: empty -> -1
    np.testing.assert_array_equal(np.asarray(out), [5, 7, -1])


def test_segment_mode_tie_breaks_to_smallest():
    vals = jnp.asarray([9, 3, 3, 9], jnp.int32)
    segs = jnp.asarray([0, 0, 0, 0], jnp.int32)
    assert int(segment_mode(vals, segs, 1)[0]) == 3


def test_segment_mode_mask_and_default():
    vals = jnp.asarray([1, 1, 8], jnp.int32)
    segs = jnp.asarray([0, 0, 1], jnp.int32)
    mask = jnp.asarray([False, True, False])
    out = segment_mode(vals, segs, 2, mask, default=-7)
    np.testing.assert_array_equal(np.asarray(out), [1, -7])


def test_segment_mode_out_of_range_values_degrade_to_no_message():
    """Values outside [0, 2**31) must not alias into other segments through
    the packed sort key — they degrade to 'no message' for their segment."""
    vals = np.array([5, -3, 2**31 + 1, 5], np.int64)
    segs = np.array([0, 1, 1, 2], np.int32)
    out = np.asarray(segment_mode(jnp.asarray(vals), jnp.asarray(segs), 3,
                                  default=-1))
    assert out.tolist() == [5, -1, 5]  # seg 1 sees only bad rows -> default


def test_segment_mode_randomised_vs_host():
    rng = np.random.default_rng(0)
    for _ in range(10):
        m, n = 300, 40
        vals = rng.integers(0, 15, m).astype(np.int32)
        segs = rng.integers(0, n, m).astype(np.int32)
        mask = rng.random(m) < 0.8
        got = np.asarray(segment_mode(
            jnp.asarray(vals), jnp.asarray(segs), n, jnp.asarray(mask)))
        for s in range(n):
            rows = vals[(segs == s) & mask]
            if len(rows) == 0:
                assert got[s] == -1
            else:
                counts = np.bincount(rows)
                best = counts.max()
                want = int(np.flatnonzero(counts == best)[0])  # smallest
                assert got[s] == want, (s, rows, got[s], want)


# ------------------------------------------------------------------ LPA


def _host_lpa(view, steps, window=None):
    """Synchronous LPA with the program's exact rule: adopt the most
    frequent in-neighbour label (ties -> smallest), keep when inbox empty."""
    if window is None:
        vm = np.asarray(view.v_mask)
        em = np.asarray(view.e_mask)
    else:
        vm, em = view.window_masks([window])
        vm, em = vm[0], em[0]
    labels = np.where(vm, np.arange(view.n_pad), np.iinfo(np.int32).max)
    src = view.e_src[em]
    dst = view.e_dst[em]
    for _ in range(steps):
        new = labels.copy()
        changed = False
        for v in np.flatnonzero(vm):
            inbox = labels[src[dst == v]]
            if len(inbox) == 0:
                continue
            counts = np.bincount(inbox)
            best = counts.max()
            pick = int(np.flatnonzero(counts == best)[0])
            new[v] = pick
        changed = (new != labels).any()
        labels = new
        if not changed:
            break
    return labels


def _lpa_log(seed, n_ids=40, n_events=300):
    rng = np.random.default_rng(seed)
    log = EventLog()
    for _ in range(n_events):
        t = int(rng.integers(0, 100))
        a, b = (int(x) for x in rng.integers(0, n_ids, 2))
        if rng.random() < 0.85:
            log.add_edge(t, a, b)
        else:
            log.delete_edge(t, a, b)
    return log


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lpa_matches_host_reference(seed):
    view = build_view(_lpa_log(seed), 90)
    prog = LabelPropagation(max_steps=8)
    got, steps = bsp.run(prog, view)
    want = _host_lpa(view, 8)
    np.testing.assert_array_equal(
        np.asarray(got)[view.v_mask], want[view.v_mask])


def test_lpa_windowed_matches_host_reference():
    view = build_view(_lpa_log(3), 90)
    prog = LabelPropagation(max_steps=6)
    got, _ = bsp.run(prog, view, window=30)
    want = _host_lpa(view, 6, window=30)
    vm = view.window_masks([30])[0][0]
    np.testing.assert_array_equal(np.asarray(got)[vm], want[vm])


class InboundLabels(LabelPropagation):
    """Labels flow dst -> src: the histogram lies at the source, whose
    ids ``bsp`` holds unsorted."""

    direction = "in"


class PlainExchange(LabelPropagation):
    """A user's program: the four-argument ``exchange`` and no
    ``exchange_is_mode`` — handed ``counts=`` it would raise."""

    exchange_is_mode = False

    def exchange(self, payload, seg_ids, num_segments, mask):
        return segment_mode(payload, seg_ids, num_segments, mask, default=-1)


@pytest.mark.parametrize("prog", [
    LabelPropagation(max_steps=8), CDLP(max_steps=6),
    InboundLabels(max_steps=8), PlainExchange(max_steps=8)],
    ids=lambda p: type(p).__name__)
@pytest.mark.parametrize("comm", ["halo", "all_gather"])
def test_lpa_sharded_matches_single(comm, prog):
    """Both engines hand a mode exchange its segments' row counts from
    before their loops (``takes_mode_counts``); a program that takes none
    runs as it always did. Either way: the host's labels, on ``bsp`` and
    on the mesh, whole view and batched windows alike."""
    view = build_view(_lpa_log(4), 90)
    mesh = sharded.make_mesh(8, 1, devices=jax.devices()[:8])
    assert takes_mode_counts(prog) == (type(prog) is not PlainExchange)
    was = TRACER.enabled
    TRACER.enable()
    try:
        with TRACER.span("job") as root:
            got, _ = sharded.run(prog, view, mesh, comm=comm)
        (span,) = (e for e in TRACER.for_trace(root.trace)
                   if e["ph"] == "X" and e["name"] == "comm.exchange")
    finally:
        (TRACER.enable if was else TRACER.disable)()
    want, _ = bsp.run(prog, view)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if type(prog) in (LabelPropagation, PlainExchange):
        host = _host_lpa(view, prog.max_steps)
        np.testing.assert_array_equal(np.asarray(want)[view.v_mask],
                                      host[view.v_mask])
    # the span says where the counts came from, and only where some did
    assert span["args"].get("mode_counts") == (
        "plan" if takes_mode_counts(prog) else None)
    got_w, _ = sharded.run(prog, view, mesh, comm=comm, windows=[60, 25])
    want_w, _ = bsp.run(prog, view, windows=[60, 25])
    np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))


# -------------------------------------------- counts, once a dispatch


def _block(rng, n, rows, pad, lo=0):
    """One direction's flat rows as the engines lay them out: sorted
    segment ids with ``pad`` padding rows (id ``n - 1``, masked) at the
    block's end, masked rows among the others, segments ``< lo`` and a
    few more empty."""
    ids = np.sort(rng.choice(np.arange(lo, n - 3), rows)).astype(np.int32)
    ids = np.concatenate([ids, np.full(pad, n - 1, np.int32)])
    mask = np.concatenate([rng.random(rows) < 0.7, np.zeros(pad, bool)])
    mask[ids == lo + 2] = False                   # one wholly masked
    vals = rng.integers(0, 7, rows + pad).astype(np.int32)
    return jnp.asarray(vals), jnp.asarray(ids), jnp.asarray(mask)


@pytest.mark.parametrize("parts", [1, 2])
@pytest.mark.parametrize("prog", [LabelPropagation(), PlainExchange()],
                         ids=lambda p: type(p).__name__)
def test_custom_exchange_with_counts_is_bit_for_bit_the_one_without(
        parts, prog):
    """``counts`` off the sorted ids' plans — masked rows and padding
    rows included, two directions' added — are ``segment_counts`` of the
    ids ``custom_exchange`` concatenates, and the exchange with them
    returns what it returns counting for itself. A program without the
    flag is never handed them."""
    rng = np.random.default_rng(11)
    n = 29
    blocks = [_block(rng, n, 120, 8), _block(rng, n, 90, 38, lo=4)][:parts]
    counts = sum(segment_counts_at(*segment_ends_pos(ids, n))
                 for _, ids, _ in blocks)
    every = jnp.concatenate([ids for _, ids, _ in blocks])
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(segment_counts(every, n)))
    assert counts.dtype == segment_counts(every, n).dtype
    assert int(counts.sum()) == len(every) and int(counts[n - 1]) >= 8
    assert int((np.asarray(counts) == 0).sum()) >= 2
    plain = custom_exchange(prog, blocks, n)
    given = jax.jit(lambda b, c: custom_exchange(prog, b, n, c))(
        blocks, counts)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(given))
    assert plain.dtype == given.dtype
    vals, ids, mask = (np.concatenate([np.asarray(b[i]) for b in blocks])
                       for i in range(3))
    for seg in range(n):
        rows = vals[(ids == seg) & mask]
        want = -1 if len(rows) == 0 else int(np.argmax(np.bincount(rows)))
        assert int(plain[seg]) == want, seg
    if not takes_mode_counts(prog):
        return
    # counts that are NOT the ids' would show: the exchange reads them
    wrong = custom_exchange(prog, blocks, n, jnp.roll(counts, 1))
    assert not np.array_equal(np.asarray(wrong), np.asarray(plain))


def _computations(hlo: str) -> dict[str, str]:
    """``{name: body}`` of an HLO module's computations (the text
    ``lowered.as_text(dialect="hlo")`` gives, before any optimisation)."""
    return {m[1]: m[2] for m in re.finditer(
        r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)^\}", hlo, re.M | re.S)}


def _ops_in_loops(hlo: str) -> set[str]:
    """Opcodes of every instruction a ``while`` body runs, through the
    computations it calls."""
    comps = _computations(hlo)
    todo = [b for text in comps.values()
            for b in re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", text)]
    assert todo, "no while loop in the program"
    seen, ops = set(), set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        ops |= set(re.findall(r"= [^=\n]*?\b([a-z][\w\-]*)\(", comps[name]))
        todo += re.findall(
            r"(?:to_apply|calls|body|condition|branch_computations)="
            r"\{?%?([\w.\-]+)", comps[name])
    return ops


def _lower_sharded(prog, comm, S=4, K=2, n_loc=16, m_d=32, m_s=64, h=4):
    """The vertex-sharded runner's lowered program at small shapes on the
    virtual mesh: ``_sharded_runner``'s twenty arguments as shapes."""
    mesh = sharded.make_mesh(S, 1, devices=jax.devices()[:S])
    h = h if comm == "halo" else 0
    runner = sharded._sharded_runner(prog, mesh, n_loc, m_d, m_s, K,
                                     S * n_loc, (), comm, h, h)

    def a(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, i64 = jnp.int32, jnp.int64
    halo = {} if comm != "halo" else {
        "d_src_h": a(i32, S, m_d), "d_send": a(i32, S, S * h),
        "s_dst_h": a(i32, S, m_s), "s_send": a(i32, S, S * h)}
    return runner.lower(
        a(bool, K, S, n_loc), a(i64, S, n_loc), a(i64, S, n_loc),
        a(i64, S, n_loc),
        a(i32, S, m_d), a(i32, S, m_d), a(bool, K, S, m_d), a(i64, S, m_d),
        a(i64, S, m_d),
        a(i32, S, m_s), a(i32, S, m_s), a(bool, K, S, m_s), a(i64, S, m_s),
        a(i64, S, m_s),
        halo, {}, {}, {}, a(i64), a(i64, K))


def _lower_bsp(prog, n=64, m=256, k=2):
    def a(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, i64 = jnp.int32, jnp.int64
    return jax.jit(bsp.make_mask_runner(prog, n, m, k)).lower(
        a(bool, k, n), a(bool, k, m), a(i64, n), a(i64, n), a(i64, n),
        a(i32, m), a(i32, m), a(i64, m), a(i64, m), a(i64), a(i64, k),
        {}, {})


@pytest.mark.parametrize("prog", [CDLP(max_steps=10),
                                  LabelPropagation(max_steps=8)],
                         ids=lambda p: type(p).__name__)
@pytest.mark.parametrize("engine", ["all_gather", "halo", "bsp"])
def test_no_round_of_a_mode_exchange_scatters_over_the_rows(engine, prog):
    """The mechanism's witness. ``segment_mode`` without ``counts`` runs
    a ``segment_sum`` of ones over every padded row — XLA's row-by-row
    scatter-add — and did so inside the ``while`` body, every round,
    until the engines computed the counts before the loop: the loop of a
    lowered mode exchange now holds the sort and no scatter. The
    vertex-sharded program, whose ids are sorted both ways, holds none at
    all; ``bsp`` keeps its unsorted direction's, before the loop."""
    if engine == "bsp":
        hlo = _lower_bsp(prog).as_text(dialect="hlo")
    else:
        hlo = _lower_sharded(prog, engine).as_text(dialect="hlo")
    in_loops = _ops_in_loops(hlo)
    assert "sort" in in_loops, in_loops         # the walk reached the mode
    assert not {op for op in in_loops if "scatter" in op}, in_loops
    if engine != "bsp":
        assert not re.search(r"\bscatter\(", hlo)


def test_a_program_without_the_flag_still_counts_its_rows_every_round():
    """...and the witness can fail: the same walk over a program that
    takes no counts finds the scatter where the parent held it."""
    hlo = _lower_sharded(PlainExchange(max_steps=8), "all_gather").as_text(
        dialect="hlo")
    assert "scatter" in _ops_in_loops(hlo)


def test_custom_combiner_rejects_direction_both():
    class Bad(LabelPropagation):
        direction = "both"

    view = build_view(_lpa_log(5), 90)
    with pytest.raises(ValueError, match="custom"):
        bsp.run(Bad(), view)
    mesh = sharded.make_mesh(8, 1, devices=jax.devices()[:8])
    with pytest.raises(ValueError, match="custom"):
        sharded.run(Bad(), view, mesh)


def test_lpa_reduce_shape():
    view = build_view(_lpa_log(6), 90)
    prog = LabelPropagation(max_steps=8)
    got, _ = bsp.run(prog, view)
    out = prog.reduce(got, view)
    assert out["vertices"] > 0
    assert out["communities"] >= 1
    assert sum(out["top5"]) <= out["vertices"]
