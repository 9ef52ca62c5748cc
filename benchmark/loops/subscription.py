"""A standing Live subscription under an open-loop tail. The tail's
events are stamped with their due times by the generator here, which
runs on its own schedule whether or not the system keeps up; the client
polls the subscription (the traffic file's ``poll_ms``) and one sample
is taken per epoch: the wall time of the read that first showed the row
minus the due time of the newest tail event at or before the row's
``time``."""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import BenchFailure, client, gen, layers


class TailSchedule:
    """The open-loop tail: event ``i`` is due at ``t0 + (i + 1) / rate``.
    Batches of ``batch`` events are handed over when their last event is
    due, whether or not the system kept up; how late each hand-over ran
    is recorded (a starved generator must not read as a fast server)."""

    def __init__(self, columns, rate: float, batch: int):
        self.cols, self.rate, self.batch = columns, float(rate), int(batch)
        self.t0 = None
        self.late: list[float] = []
        self.sent = 0
        self._stop = threading.Event()

    def stop(self):
        self._stop.set()

    def due(self, i):
        """Due wall time of event index ``i`` (array or scalar)."""
        return self.t0 + (np.asarray(i, np.float64) + 1.0) / self.rate

    def batches(self):
        n = len(self.cols[0])
        self.t0 = time.perf_counter()
        for off in range(0, n, self.batch):
            end = min(off + self.batch, n)
            due = self.t0 + end / self.rate
            while True:
                wait = due - time.perf_counter()
                if wait <= 0 or self._stop.is_set():
                    break
                time.sleep(min(wait, 0.05))
            if self._stop.is_set():
                return
            self.late.append(time.perf_counter() - due)
            self.sent = end
            yield tuple(c[off:end] for c in self.cols)


class Loop:
    def __init__(self, run):
        self.run, self.reads, self.period_ms = run, 0, None
        self.period_s = run.traffic["poll_ms"] / 1e3
        self.tail = None
        self.seen: list[dict] = []       # every epoch's row, as first read

    def boot(self):
        """The tail's events, made from the seed, and their schedule;
        enough of them for the warm-up, the window and a wide margin."""
        run = self.run
        n = int(run.traffic["tail_rate_per_s"] * (run.args.seconds + 240))
        self.cols = gen.tail_events(run.cfg, run.seed, n)
        tt, tk, ts, td = self.cols
        self.tail = TailSchedule(
            (tt, run.program_kinds(tk), ts, td),
            run.traffic["tail_rate_per_s"], run.traffic["tail_batch_events"])

    def warm(self):
        """Start the tail, subscribe, and read the warm-up epochs."""
        run, rt = self.run, self.run.rt
        rt.add_source(run.source("tail", self.tail.batches))
        rt.ingest(wait=False)                 # starts the late joiner alone
        if not rt.graph.watermarks.wait_for(self.t_span + 1, timeout=120):
            raise BenchFailure("tail never advanced the watermark")
        self.job = run.rest.post(run.traffic["endpoint"], client.request_body(
            run.cfg, run.traffic, 0))["jobID"]
        t0 = time.perf_counter()
        while len(self.seen) < int(run.traffic["warmup_epochs"]):
            self.poll()
            if time.perf_counter() - t0 > 600:
                raise BenchFailure("no warm-up epoch within 600 s")
            time.sleep(self.period_s)

    @property
    def t_span(self) -> int:
        return self.run.cfg["graph"]["t_span"]

    def poll(self) -> int:
        """One read of the subscription; stamps each row new to the
        client with the wall time of the read that showed it."""
        doc = self.run.rest.results(self.job)
        now = time.perf_counter()
        self.reads += 1
        if doc["status"] not in client.ACTIVE:
            raise BenchFailure(f"live job ended: {doc['status']} "
                               f"{doc.get('error')}")
        rows = doc["results"]
        for row in rows[len(self.seen):]:
            idx = min(int(row["time"]) - self.t_span - 1, self.tail.sent - 1)
            self.seen.append({
                "row": row, "t_read": now,
                "staleness_s": None if idx < 0
                else now - float(self.tail.due(idx))})
        return len(rows)

    def window(self):
        run, rec = self.run, self.run.rec
        t_w = rec["t_window"] = time.perf_counter()
        deadline = t_w + run.args.seconds
        first, reads0 = len(self.seen), self.reads
        n_trace = int(run.traffic.get("trace_epochs", 2))
        traced_from = None          # rows read when the trace started
        while time.perf_counter() < deadline:
            n = self.poll()
            if run.args.trace and traced_from is None and n > first:
                traced_from = n     # from the first epoch read in the window
                run.trace_start()
            if run.tracing and n >= traced_from + n_trace:
                run.trace_stop(self.seen[traced_from:n])
            time.sleep(self.period_s)
        self.period_ms = 1e3 * (time.perf_counter() - t_w) \
            / max(self.reads - reads0, 1)
        if run.tracing:             # window too short for n_trace epochs
            run.trace_stop(self.seen[traced_from:])
        epochs = self.seen[first:]
        rec.update(epochs=epochs, attempted=len(epochs), failed=0,
                   window_s=time.perf_counter() - t_w)
        run.rest.kill(self.job)
        while run.rest.results(self.job)["status"] in client.ACTIVE:
            time.sleep(0.05)
        self.doc = run.rest.results(self.job)
        rec["freshz"] = run.rest.get("/freshz")
        self.stop()
        run.rt.pipeline.stop()
        if run.rt.pipeline.errors:
            raise BenchFailure(f"ingest errors: {run.rt.pipeline.errors}")
        late = self.tail.late
        rec["tail"] = {
            "sent": self.tail.sent, "batches": len(late),
            "generator_late_p95_s": layers.quantile(late, 95),
            "generator_late_max_s": max(late, default=None)}

    def stop(self):
        if self.tail is not None:
            self.tail.stop()

    def collect(self):
        rec, doc = self.run.rec, self.doc
        # a job has no trace where the recorder is off (RTPU_TRACE=0):
        # then no span is read, and every span metric is left out
        every = self.run.rest.spans(doc["traceID"]) \
            if doc.get("traceID") else []
        want = {int(e["row"]["time"]) for e in rec["epochs"]}
        ep = [s for s in every if s["name"] == "live.epoch"
              and int(s["args"].get("time", -1)) in want]
        rec["spans"] = []
        if ep:
            lo = min(s["ts"] for s in ep)
            hi = max(s["ts"] + s["dur"] for s in ep)
            rec["spans"] = [s for s in every if s["name"] != "job"
                            and lo <= s["ts"] <= hi]
        modes = {int(s["args"]["time"]): s["args"].get("mode") for s in ep}
        for e in rec["epochs"]:
            e["mode"] = modes.get(int(e["row"]["time"]))
        rec["work_wall_s"] = sum(s["dur"] for s in ep) / 1e6
        # the subscription's one ledger (it also holds the warm-up
        # epochs) against the wall of all its epochs, so the
        # ledger-phase reducers read it the way they read a request
        rec["ledgers"] = [{
            "ledger": doc["ledger"], "views": len(doc["results"]),
            "wall_s": sum(s["dur"] for s in every
                          if s["name"] == "live.epoch") / 1e6}] \
            if doc.get("ledger") else []

    def done(self):
        return self.run.rec["epochs"]

    def rows(self, item):
        return [item["row"]]

    def jobs(self):
        return [{"k": "live", "ledger": self.doc.get("ledger"),
                 "spans": self.run.rec["spans"]}]

    def events(self):
        tt, tk, ts, td = (c[:self.tail.sent] for c in self.cols)
        return tt, tk, ts, np.maximum(td, 0)

    def span_medians(self) -> dict:
        """Median seconds of each span name the window's epochs wrote:
        where a run's level comes from, on the work line of every run."""
        by_name: dict = {}
        for s in self.run.rec.get("spans") or []:
            if "dur" in s:                      # an instant has none
                by_name.setdefault(s["name"], []).append(s["dur"] / 1e6)
        return {n: round(layers.quantile(v, 50), 4)
                for n, v in sorted(by_name.items())
                if len(v) >= len(self.done()) // 2}

    def work(self) -> dict:
        done = self.done()
        return {"epochs_completed": len(done),
                "span_median_seconds": self.span_medians(),
                "views_completed": len(done),
                "epoch_modes": [e.get("mode") for e in done],
                "result_reads": self.reads,
                "poll_period_ms": round(self.period_ms, 3),
                "tail": self.run.rec.get("tail"),
                "staleness_seconds": [round(e["staleness_s"], 3)
                                      for e in done
                                      if e["staleness_s"] is not None]}
