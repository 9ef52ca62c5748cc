"""Reducers: from a run's record (requests with their ledgers and spans,
epochs, counters, the trace reduction) to metric values.

A metric is one JSON file (``end_to_end/<name>.json`` or
``layer_metrics/<name>.json``) naming a reducer below and its
parameters, so a later PR's metric that reads a new span or ledger
phase is a new file and no code. A reducer that finds nothing to read
returns None and the harness leaves the metric out of the line.
"""

from __future__ import annotations

import statistics


def _dig(obj, path: str):
    for key in path.split("."):
        if not isinstance(obj, dict) or key not in obj:
            return None
        obj = obj[key]
    return obj


def quantile(values, q: float):
    """The q-th percentile, by the nearest rank at or above it."""
    vals = sorted(values)
    if not vals:
        return None
    rank = max(1, -(-len(vals) * q // 100))            # ceil
    return float(vals[int(rank) - 1])


def r_fact(spec, rec):
    """A number the harness measured itself: ``path`` into the record."""
    v = _dig(rec, spec["path"])
    return None if v is None else float(v) * spec.get("scale", 1.0)


def r_rate(spec, rec):
    """Work of the requests that completed inside the window over the
    time from the window's start to the last such completion."""
    done = rec.get("requests") or []
    if not done:
        return None
    t_last = max(r["t_done"] for r in done)
    return sum(r[spec["count"]] for r in done) / (t_last - rec["t_window"])


def r_quantile(spec, rec):
    """Percentile ``q`` of field ``of`` over every item of ``over``
    (requests or epochs) that ended inside the window."""
    vals = [it[spec["of"]] for it in rec.get(spec["over"]) or []
            if it.get(spec["of"]) is not None]
    return quantile(vals, spec["q"])


def r_ledger_phase_share(spec, rec):
    """Seconds of the named ledger phases over the client's wall, in %,
    summed over the window's ledgers (one per request, or the
    subscription's one). ``complement``: the share that no
    phase of the ledger claims (REST, queueing, polling, reduce)."""
    done = rec.get("ledgers") or []
    wall = sum(r["wall_s"] for r in done)
    if not done or wall <= 0:
        return None
    names = spec.get("phases")
    secs = 0.0
    for r in done:
        ph = r["ledger"]["phase_seconds"]
        secs += sum(v for k, v in ph.items()
                    if k != "other" and (names is None or k in names))
    share = 100.0 * secs / wall
    return 100.0 - share if spec.get("complement") else share


def r_ledger_sum_per(spec, rec):
    """A ledger counter (``path``) summed over the window's requests,
    per unit of ``per`` (a request field, e.g. views)."""
    done = rec.get("ledgers") or []
    units = sum(r[spec["per"]] for r in done)
    if not done or units <= 0:
        return None
    vals = [_dig(r["ledger"], spec["path"]) for r in done]
    if any(v is None for v in vals):
        return None
    return float(sum(vals)) / units


def _spans(spec, rec):
    return [s for s in rec.get("spans") or [] if s["name"] == spec["span"]]


def r_span_share(spec, rec):
    """Seconds inside spans named ``span`` over the wall of the window's
    requests (or of its epochs), in %."""
    wall = rec.get("work_wall_s")
    found = _spans(spec, rec)
    if not found or not wall:
        return None
    return 100.0 * sum(s["dur"] for s in found) / 1e6 / wall


def r_span_count(spec, rec):
    """How many spans named ``span`` the window's work recorded. The
    harness has to have looked (``spans`` present), so 0 is a reading."""
    if rec.get("spans") is None:
        return None
    return float(len(_spans(spec, rec)))


def r_span_arg_share(spec, rec):
    """Share of the spans named ``span`` whose ``arg`` equals ``equals``."""
    found = _spans(spec, rec)
    if not found:
        return None
    hit = sum(1 for s in found
              if s.get("args", {}).get(spec["arg"]) == spec["equals"])
    return 100.0 * hit / len(found)


def r_histogram_quantile(spec, rec):
    """Upper bound of the bucket holding percentile ``q`` of a
    ``{buckets, counts}`` histogram found at ``path``."""
    h = _dig(rec, spec["path"])
    if not h or not sum(h["counts"]):
        return None
    need, seen = sum(h["counts"]) * spec["q"] / 100.0, 0
    for bound, n in zip(list(h["buckets"]) + [float("inf")], h["counts"]):
        seen += n
        if seen >= need:
            return float(bound)
    return None


def r_trace_idle_share(spec, rec):
    x = rec.get("xplane")
    if not x or not x.get("window_s"):
        return None
    return 100.0 * (1.0 - x["busy_s"] / x["window_s"])


def r_trace_kernel_roofline(spec, rec):
    """Least time the chip could take for the traced supersteps (bytes
    the algorithm must move, its module's ``least_bytes``, over the peak
    HBM rate; the
    bound is memory: a superstep does two flops an edge) over the device
    time of the ops of the superstep program in the trace, in %."""
    x = rec.get("xplane")
    if not x or not x.get("least_bytes"):
        return None
    secs = x["program_seconds"].get(spec["program"])
    if not secs:
        return None
    least_s = x["least_bytes"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / secs


REDUCERS = {name[2:]: fn for name, fn in list(globals().items())
            if name.startswith("r_")}


def reduce_metric(spec: dict, rec: dict):
    return REDUCERS[spec["reducer"]](spec, rec)


def spread(values) -> float:
    """Inter-quartile distance as a share of the median, as the
    builder's contract defines a spread."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
