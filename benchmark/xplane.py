"""Reduction of a JAX profiler trace (``.xplane.pb``) to the device
numbers: busy and idle seconds, seconds per device operation, and the
idle gaps named by the host span that covered them.

Layout of a TPU trace (jax 0.9.0, recorded in PR 24, see
``testdata/recorded_v5e.xplane.pb``): one plane ``/device:TPU:<i>`` per
chip with the lines ``XLA Modules`` (one event per program run, named
``jit_<fn>(<hash>)``) and ``XLA Ops`` (one event per operation, named by
its HLO text ``%fusion.8 = f32[...] fusion(...)``); the plane
``/host:CPU`` holds one line per host thread, where the program's spans
(``jax.profiler.TraceAnnotation``) appear by name. All on one clock, in
nanoseconds. An operation is reported as ``<program>/<op>``, e.g.
``jit_run/fusion.8``.
"""

from __future__ import annotations

import bisect
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"


def newest_trace(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read(path: str) -> dict:
    """``{"devices": {plane: {"modules": [...], "ops": [...]}}, "host":
    [...]}`` with every event as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX) \
                and plane.name[len(DEVICE_PREFIX):].isdigit():
            dev = out["devices"].setdefault(plane.name,
                                            {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] += [(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events]
    return out


def union(intervals):
    """Merged, sorted ``[start, end]`` list of possibly overlapping ones."""
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def op_label(hlo: str) -> str:
    """``%fusion.8 = f32[..] fusion(..)`` -> ``fusion.8``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def program_label(module: str) -> str:
    """``jit_run(2384715398708818389)`` -> ``jit_run``."""
    return module.split("(", 1)[0]


def name_ops(dev: dict):
    """Each op as ``(<program>/<op>, start, end)``: the program is the
    module run that encloses the op's start."""
    mods = sorted(dev["modules"], key=lambda m: m[1])
    starts = [m[1] for m in mods]
    named = []
    for hlo, lo, hi in dev["ops"]:
        i = bisect.bisect_right(starts, lo) - 1
        prog = program_label(mods[i][0]) \
            if i >= 0 and lo <= mods[i][2] else "no_program"
        named.append((f"{prog}/{op_label(hlo)}", lo, hi))
    return named


def gaps_by_span(busy, lo, hi, host_spans, span_names):
    """Seconds of the idle gaps of ``[lo, hi]`` (complement of ``busy``)
    by the innermost (shortest) host span of ``span_names`` covering
    each piece; what no such span covers is ``no_span``."""
    spans = sorted(((s_hi - s_lo, s_lo, s_hi, name)
                    for name, s_lo, s_hi in host_spans
                    if name in span_names))
    gaps, at = [], lo
    for b_lo, b_hi in busy:
        if b_lo > at:
            gaps.append((at, min(b_lo, hi)))
        at = max(at, b_hi)
    if at < hi:
        gaps.append((at, hi))
    out: dict[str, float] = {}
    for g_lo, g_hi in gaps:
        # cut the gap at every span edge, then name each piece
        cuts = sorted({g_lo, g_hi} | {e for _, s_lo, s_hi, _ in spans
                                     for e in (s_lo, s_hi)
                                     if g_lo < e < g_hi})
        for p_lo, p_hi in zip(cuts, cuts[1:]):
            mid = (p_lo + p_hi) / 2
            name = next((n for _, s_lo, s_hi, n in spans
                         if s_lo <= mid <= s_hi), "no_span")
            out[name] = out.get(name, 0.0) + (p_hi - p_lo) / 1e9
    return out


def reduce_trace(path: str, span_names=(), window_s: float | None = None,
                 top: int = 10) -> dict:
    """The device numbers of one trace. ``window_s`` is the length of the
    traced window by the host's clock (default: first to last event of
    the trace); busy seconds are averaged over the chips found."""
    tr = read(path)
    if not tr["devices"]:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}<i> plane — "
                         "not a trace of a TPU")
    every = [(lo, hi) for d in tr["devices"].values()
             for _, lo, hi in d["ops"] + d["modules"]]
    if not every:
        raise ValueError(f"{path}: no operation ran on the device")
    t_lo, t_hi = min(e[0] for e in every), max(e[1] for e in every)
    host_lo = min((s for _, s, _ in tr["host"]), default=t_lo)
    host_hi = max((e for _, _, e in tr["host"]), default=t_hi)
    lo, hi = min(t_lo, host_lo), max(t_hi, host_hi)
    if window_s is None:
        window_s = (hi - lo) / 1e9
    op_seconds: dict[str, float] = {}
    program_seconds: dict[str, float] = {}
    busy_s, gaps = [], {}
    chips = len(tr["devices"])
    for dev in tr["devices"].values():
        ops = name_ops(dev)
        by_program: dict[str, list] = {}
        for name, o_lo, o_hi in ops:
            op_seconds[name] = op_seconds.get(name, 0.0) + (o_hi - o_lo) / 1e9
            by_program.setdefault(name.split("/", 1)[0], []).append(
                (o_lo, o_hi))
        # a `while` spans the operations of its body, so a program's
        # seconds are the union of its operations, not their sum
        for prog, spans in by_program.items():
            program_seconds[prog] = program_seconds.get(prog, 0.0) + sum(
                hi_ - lo_ for lo_, hi_ in union(spans)) / 1e9 / chips
        busy = union([(o_lo, o_hi) for _, o_lo, o_hi in ops]
                     or [(m[1], m[2]) for m in dev["modules"]])
        busy_s.append(sum(b_hi - b_lo for b_lo, b_hi in busy) / 1e9)
        for name, s in gaps_by_span(busy, lo, hi, tr["host"],
                                    set(span_names)).items():
            gaps[name] = gaps.get(name, 0.0) + s / len(tr["devices"])
    by_time = sorted(op_seconds.items(), key=lambda kv: -kv[1])
    return {"chips": chips, "busy_s": sum(busy_s) / chips,
            "window_s": float(window_s), "trace_span_s": (hi - lo) / 1e9,
            "op_seconds": op_seconds, "program_seconds": program_seconds,
            "device_ops": [[n, s] for n, s in by_time[:top]],
            "idle_gaps": [[n, s] for n, s in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top]]}
