"""Load from one process with few threads: closed-loop query clients, the
live scraper underneath them, and the poller that times each container's
way into the store.
"""

from __future__ import annotations

import threading
import time

from . import served


class Scraper(threading.Thread):
    """Publishes the next scrape of every series at the deployment's rate:
    ``containers_per_scrape`` containers spread evenly over the scrape
    interval (as Prometheus spreads its targets), data time one column per
    scrape. ``sent`` holds one record per acknowledged container."""

    def __init__(self, writers, deploy: dict, seed: int, first_col: int):
        super().__init__(name="bench-scraper", daemon=True)
        self.slots = [(w, j) for w in writers for j in range(len(w.templates))]
        self.period = deploy["scrape_interval_ms"] / 1000.0 / len(self.slots)
        self.seed, self.col = seed, first_col
        self.sent: list[dict] = []
        self.late_s = 0.0
        self._halt = threading.Event()
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            due = time.perf_counter()
            while True:
                for w, j in self.slots:
                    wait = due - time.perf_counter()
                    if wait > 0 and self._halt.wait(wait):
                        return
                    if self._halt.is_set():
                        return
                    self.late_s = max(self.late_s, -wait)
                    lo, hi, _ = w.templates[j]
                    w.publish(j, self.col, self.seed)
                    self.sent.append({"t": time.perf_counter(), "writer": w,
                                      "row": lo, "col": self.col,
                                      "rows": hi - lo, "landed": None})
                    due += self.period
                self.col += 1
        except BaseException as e:   # noqa: BLE001 — reported by the harness
            self.error = e

    def halt(self) -> None:
        self._halt.set()


class LagPoller(threading.Thread):
    """Every 10 ms: which acknowledged containers does the store hold now?
    (The data module says whether a container's first row has its scrape.)"""

    def __init__(self, scraper: Scraper):
        super().__init__(name="bench-lag", daemon=True)
        self.scraper = scraper
        self._halt = threading.Event()

    def poll(self) -> int:
        pending = 0
        now = time.perf_counter()
        for rec in list(self.scraper.sent):
            if rec["landed"] is None:
                w = rec["writer"]
                if w.data.landed(w.shard, rec["row"], rec["col"]):
                    rec["landed"] = now
                else:
                    pending += 1
        return pending

    def run(self) -> None:
        while not self._halt.wait(0.01):
            self.poll()

    def halt(self) -> None:
        self._halt.set()


class Clients:
    """``n`` closed-loop clients over one generator. Each record:
    {"req", "t0", "t1", "code", "path", "rows" (the parsed answer, kept for
    the first ``keep`` answers)}."""

    def __init__(self, gen, port: int, dataset: str, keep: int = 4096):
        self.gen, self.port, self.dataset, self.keep = gen, port, dataset, keep
        self.records: list[dict] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.threads = [threading.Thread(target=self._run, args=(c,),
                                         name=f"bench-client-{c}", daemon=True)
                        for c in range(gen.clients)]

    def issue(self, req) -> dict:
        t0 = time.perf_counter()
        r = served.query_range(self.port, self.dataset, req.promql,
                               req.start_ms, req.end_ms, req.step_ms,
                               req.tenant)
        t1 = time.perf_counter()
        body = r["body"]
        ok = r["code"] == 200 and body.get("status") == "success"
        rec = {"req": req, "t0": t0, "t1": t1, "code": r["code"], "ok": ok,
               "path": body["stats"]["exec_path"] if ok else None,
               "body": None}
        with self._lock:
            if ok and len(self.records) < self.keep:
                rec["body"] = body
            self.records.append(rec)
        return rec

    def _run(self, client: int) -> None:
        while not self._stop.is_set():
            self.issue(self.gen.next(client))

    def start(self) -> None:
        for t in self.threads:
            t.start()

    def stop(self, timeout_s: float) -> int:
        """No new queries; wait for those in flight. Returns how many
        clients were still waiting for an answer at the deadline."""
        self._stop.set()
        deadline = time.perf_counter() + timeout_s
        for t in self.threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        return sum(t.is_alive() for t in self.threads)
