"""One client, closed loop: the next request goes out when the reply to
the last one has been read. A latency runs from the POST to the read
that shows the rows; the client blocks on the job's own completion event
(``Job.wait``, the package's Python surface) and then reads the results
once over REST, so no poll period quantises it."""

from __future__ import annotations

import time

from benchmark import BenchFailure, client


class Loop:
    def __init__(self, run):
        self.run, self.reads = run, 0

    def boot(self):
        pass

    def stop(self):
        pass

    def request(self, k: int, deadline=None) -> dict:
        """Request ``k``, POST to the read that sees it ended. Past
        ``deadline`` the job is killed and waited for: ``t_done`` None.
        Raises ``client.ScheduleUsedUp``, with nothing posted, where the
        schedule holds no request ``k``."""
        run = self.run
        t0 = time.perf_counter()
        body = client.request_body(run.cfg, run.traffic, k)
        job_id = run.rest.post(run.traffic["endpoint"], body)["jobID"]
        job, cut = run.rt.manager.get(job_id), False
        while True:
            job.wait(None if deadline is None or cut
                     else max(deadline - time.perf_counter(), 0.0))
            doc = run.rest.results(job_id)
            now = time.perf_counter()
            self.reads += 1
            if doc["status"] not in client.ACTIVE:
                break
            if not cut and deadline is not None and now >= deadline:
                run.rest.kill(job_id)
                cut = True
        rows = doc.get("results", [])
        return {"k": k, "t_post": t0, "t_done": None if cut else now,
                "status": doc["status"], "error": doc.get("error"),
                "degraded": bool(doc.get("degraded")), "rows": rows,
                "ledger": doc.get("ledger"), "trace_id": doc.get("traceID"),
                "ok": doc["status"] == "done" and not doc.get("degraded")
                and len(rows) == client.rows_expected(run.cfg, run.traffic)}

    def warm(self):
        for k in range(-int(self.run.traffic["warmup_requests"]), 0):
            req = self.request(k)
            if not req["ok"]:
                raise BenchFailure(
                    f"warm-up request {k}: status {req['status']} degraded="
                    f"{req['degraded']} error={req['error']}")
            self.run.say("warmup", k=k,
                         seconds=round(req["t_done"] - req["t_post"], 3),
                         phase_seconds=req["ledger"]["phase_seconds"])

    def window(self):
        """Issue requests until the window closes; only requests that
        completed inside it count. The one in flight at the close is
        killed and waited for. A schedule that holds no request ``k``
        closes the window too, before anything is posted: the rate runs
        to the last completion, so a faster system is measured over the
        same requests in less time."""
        run, rec = self.run, self.run.rec
        t_w = rec["t_window"] = time.perf_counter()
        deadline = t_w + run.args.seconds
        done, traced, attempted, failed, k = [], [], 0, 0, 0
        trace_left = int(run.traffic.get("trace_requests", 1))
        rec["schedule_used_up"] = False
        while time.perf_counter() < deadline:
            if run.args.trace and k == 1:          # request 0 runs untraced
                run.trace_start()
            try:
                req = self.request(k, deadline)
            except client.ScheduleUsedUp:      # nothing is in flight
                rec["schedule_used_up"] = True
                break
            k += 1
            if run.tracing:
                traced += [req] if req["ok"] else []
                trace_left -= 1
                if trace_left == 0 or req["t_done"] is None:
                    run.trace_stop(traced)
            if req["t_done"] is None:          # cut by the window's close
                break
            attempted += 1
            if not req["ok"]:
                failed += 1
                run.say("request_failed", k=req["k"], status=req["status"],
                        degraded=req["degraded"], error=req["error"],
                        rows=len(req["rows"]))
                continue
            req["latency_s"] = req["t_done"] - req["t_post"]
            req["views"] = len(req["rows"])
            done.append(req)
        run.trace_stop(traced)
        rec.update(requests=done, attempted=attempted, failed=failed,
                   window_s=time.perf_counter() - t_w)

    def collect(self):
        rec = self.run.rec
        for r in rec["requests"]:
            r["spans"] = self.run.rest.spans(r["trace_id"])
        rec["spans"] = [s for r in rec["requests"] for s in r["spans"]]
        rec["work_wall_s"] = sum(r["latency_s"] for r in rec["requests"])
        rec["ledgers"] = [{"ledger": r["ledger"], "wall_s": r["latency_s"],
                           "views": r["views"]} for r in rec["requests"]]

    def done(self):
        return self.run.rec["requests"]

    def rows(self, item):
        return item["rows"]

    def jobs(self):
        return self.run.rec["requests"]

    def events(self):
        return None

    def work(self) -> dict:
        done = self.done()
        run = self.run
        return {"schedule_requests": client.schedule_requests(
                    run.cfg, run.traffic),
                "schedule_used_up": run.rec["schedule_used_up"],
                "requests_completed": len(done),
                "views_completed": sum(r["views"] for r in done),
                "result_reads": self.reads, "poll_period_ms": None,
                "request_seconds": [round(r["latency_s"], 4)
                                    for r in done[:64]]}
