"""A second served path, as a later one is added: the compressor's
serving of a cell, and beside its chunks a wire ``STATUS`` request every
``PERIOD`` seconds on the same link.  Its ``latency_p50_ms`` is over the
``STATUS`` requests, and its check has one limit of its own."""

from __future__ import annotations

import time

from bench import stats
from bench.paths import compress

PERIOD = 0.05
LIMITS = {"status_unanswered": 0}


class Status(compress.Requests):
    name = "status"

    def send(self, link, due, wake):
        link.status()
        self.records.append((due, time.monotonic()))


class Path(compress.Path):
    def serve(self, **kw):
        self.status = Status(PERIOD)
        served = super().serve(extra=[self.status], **kw)
        w0, w1 = served.window
        latencies = [(due, done - due) for due, done in self.status.records if w0 <= due <= w1]
        e2e = dict(served.e2e, latency_p50_ms=stats.percentile([x for _, x in latencies], 50) * 1e3)
        return served._replace(e2e=e2e, latencies=latencies)

    def check(self):
        missing = self.status.sent - len(self.status.records)
        return {"status_unanswered": {"value": missing, "limit": LIMITS["status_unanswered"]}}
