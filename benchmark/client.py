"""The client's side: REST calls and the requests a traffic file
describes. The drivers of a window are ``loops/<loop>.py``. stdlib only;
nothing here imports the program.
"""

from __future__ import annotations

import json
import urllib.request

ACTIVE = ("pending", "running")


class ScheduleUsedUp(ValueError):
    """The traffic file's schedule holds no request ``k``: its last hop
    would lie past the end of the log's span. A window that gets there
    closes (``loops/closed.py``); it is no fault of the run."""


class Rest:
    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"

    def get(self, path: str):
        with urllib.request.urlopen(self.base + path, timeout=120) as r:
            return json.loads(r.read())

    def post(self, path: str, body: dict):
        req = urllib.request.Request(
            self.base + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())

    def results(self, job_id: str):
        return self.get(f"/AnalysisResults?jobID={job_id}")

    def spans(self, trace_id: str):
        return self.get(f"/tracez?trace_id={trace_id}")["spans"]

    def kill(self, job_id: str):
        return self.get(f"/KillTask?jobID={job_id}")


def _grid(cfg: dict, traffic: dict) -> tuple[int, int, int]:
    """Request 0's first hop time, the hops a request, a hop's seconds."""
    return (int(traffic["start_frac"] * cfg["graph"]["t_span"]),
            int(traffic["hops_per_request"]), int(cfg["hop_s"]))


def hop_times(cfg: dict, traffic: dict, k: int) -> list[int]:
    """Hop times of request ``k`` (negative k: warm-up requests, which
    end where request 0 starts): consecutive hops, ascending."""
    t0, h, jump = _grid(cfg, traffic)
    times = [t0 + (k * h + j) * jump for j in range(h)]
    if times[-1] > cfg["graph"]["t_span"]:
        raise ScheduleUsedUp(
            f"request {k} would ask for T={times[-1]}, past the end of the "
            f"log's span {cfg['graph']['t_span']}: the traffic file's "
            "schedule is used up (a faster system needs a longer one)")
    return times


def schedule_requests(cfg: dict, traffic: dict):
    """How many requests (k = 0, 1, ...) the traffic file's schedule
    holds on this configuration before ``hop_times`` raises; None for
    traffic that has no schedule (a subscription)."""
    if "hops_per_request" not in traffic:
        return None
    t0, h, jump = _grid(cfg, traffic)
    hops = (int(cfg["graph"]["t_span"]) - t0) // jump + 1   # hop times <= span
    return max(hops // h, 0)


def request_body(cfg: dict, traffic: dict, k: int) -> dict:
    alg = cfg["algorithm"]
    body = {"analyserName": alg["analyserName"], "params": alg["params"],
            "explain": 1}
    wt = traffic["window_type"]
    if wt == "batched":
        body.update(windowType="batched", windowSet=list(cfg["windows"]))
    elif wt == "single":
        body.update(windowType="single", windowSize=cfg["windows"][0])
    if traffic["endpoint"] == "/LiveAnalysisRequest":
        body["repeatTime"] = traffic["repeat_time_s"]
        return body
    times = hop_times(cfg, traffic, k)
    if traffic["endpoint"] == "/ViewAnalysisRequest":
        body["timestamp"] = times[0]
    else:
        body.update(start=times[0], end=times[-1], jump=int(cfg["hop_s"]))
    return body


def rows_expected(cfg: dict, traffic: dict) -> int:
    per_hop = len(cfg["windows"]) if traffic["window_type"] == "batched" else 1
    return int(traffic["hops_per_request"]) * per_hop
