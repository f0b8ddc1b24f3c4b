"""The load generator: client threads of the benchmark's own that drive
the server over HTTP (``POST /v1/submit``, ``GET /v1/stream/<rid>``) and
time every request on the client's monotonic clock."""
from __future__ import annotations

import http.client
import json
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class Record:
    """What the client saw of one request. Times are ``time.monotonic``
    seconds; ``due`` is when the request was due to be sent (in a closed
    loop: when its client was free to send it)."""
    idx: int
    due: float
    sent: float = 0.0
    rid: int = -1
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    state: Optional[str] = None
    error: Optional[str] = None


def send(host: str, port: int, req, rec: Record, timeout: float = 300.0):
    """Submit one request and read its NDJSON stream to the end."""
    rec.sent = time.monotonic()
    try:
        c = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            c.request("POST", "/v1/submit", json.dumps(req.body()),
                      {"Content-Type": "application/json"})
            rec.rid = int(json.loads(c.getresponse().read())["rid"])
        finally:
            c.close()
        c = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            c.request("GET", f"/v1/stream/{rec.rid}")
            resp = c.getresponse()
            while True:
                line = resp.readline()
                if not line:
                    break
                now = time.monotonic()
                if not line.strip():
                    continue
                ev = json.loads(line)
                if "token" in ev:
                    rec.token_times.append(now)
                    rec.tokens.append(int(ev["token"]))
                elif ev.get("done"):
                    rec.state = ev.get("state")
                elif "error" in ev:
                    rec.error = str(ev["error"])
        finally:
            c.close()
    except Exception as e:          # counted as failed, never raised
        rec.error = repr(e)


class OpenLoop:
    """Sends each request at its due time (``t0 + req.due``) whatever the
    server's state, on a thread of its own (a daemon: one still waiting
    when the run ends does not hold the process)."""

    def __init__(self, host, port, reqs, t0: float):
        self.host, self.port, self.reqs, self.t0 = host, port, reqs, t0
        self.records = [Record(idx=r.idx, due=t0 + r.due) for r in reqs]
        self.thread = threading.Thread(target=self._dispatch, daemon=True)
        self.stopped = threading.Event()

    def start(self):
        self.thread.start()
        return self

    def _dispatch(self):
        for req, rec in zip(self.reqs, self.records):
            wait = rec.due - time.monotonic()
            if wait > 0 and self.stopped.wait(wait):
                return
            threading.Thread(target=send, daemon=True,
                             args=(self.host, self.port, req, rec)).start()

    def finish(self, deadline: float):
        """Stop sending and wait (until ``deadline``) for every request
        sent so far to end."""
        self.stopped.set()
        self.thread.join()
        while time.monotonic() < deadline and any(
                r.sent and r.state is None and r.error is None
                for r in self.records):
            time.sleep(0.05)


class ClosedLoop:
    """``clients`` threads; each sends its next request when its last one
    has ended, until ``stop_at``."""

    def __init__(self, host, port, reqs, clients: int, stop_at: float):
        self.host, self.port, self.reqs = host, port, reqs
        self.clients, self.stop_at = clients, stop_at
        self.records: List[Record] = []
        self._next = 0
        self._lock = threading.Lock()
        self.threads = [threading.Thread(target=self._client, daemon=True)
                        for _ in range(clients)]

    def start(self):
        for t in self.threads:
            t.start()
        return self

    def _client(self):
        while True:
            now = time.monotonic()
            with self._lock:
                if now >= self.stop_at or self._next >= len(self.reqs):
                    return
                req = self.reqs[self._next]
                self._next += 1
                rec = Record(idx=req.idx, due=now)
                self.records.append(rec)
            send(self.host, self.port, req, rec)

    def finish(self, deadline: float):
        for t in self.threads:
            t.join(max(0.0, deadline - time.monotonic()))
