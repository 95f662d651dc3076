"""The client's side of the served path (from ``chip_smoke.py``).

A request's clock stops when the response body has been read; decoding the
body for comparison happens later and outside any latency.
"""

import json
import time
import urllib.error
import urllib.request
from collections import Counter


class Client:
    """urllib client of the in-process server; remembers every status."""

    def __init__(self, port: int, deadline_ms: int):
        self.base = f"http://127.0.0.1:{port}"
        self.deadline_ms = int(deadline_ms)
        self.statuses: Counter = Counter()

    def raw(self, path: str, payload=None, trace_id: str = ""):
        """``(status, body bytes, wall ms)`` of one round trip; never raises
        for an HTTP status."""
        headers = {"Content-Type": "application/json"}
        if trace_id:
            headers["X-Kolibrie-Trace-Id"] = trace_id
        data = None if payload is None else json.dumps(payload).encode()
        req = urllib.request.Request(self.base + path, data=data, headers=headers)
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=self.deadline_ms / 1000) as r:
                status, body = r.status, r.read()
        except urllib.error.HTTPError as e:
            status, body = e.code, e.read()
        except (urllib.error.URLError, TimeoutError, ConnectionError) as e:
            status, body = 0, repr(e).encode()
        ms = (time.perf_counter() - t0) * 1000.0
        self.statuses[status] += 1
        return status, body, ms

    def _ok(self, path, payload=None):
        status, body, _ = self.raw(path, payload)
        if status != 200:
            raise RuntimeError(f"{path} -> HTTP {status}: {body[:400]!r}")
        return body

    def post(self, path: str, payload: dict) -> dict:
        return json.loads(self._ok(path, payload))

    def get_json(self, path: str) -> dict:
        return json.loads(self._ok(path))

    def get_text(self, path: str) -> str:
        return self._ok(path).decode()

    def query(self, store_id: str, sparql: str, trace_id: str = ""):
        """``(status, body bytes, wall ms)`` of one ``/store/query``."""
        return self.raw(
            "/store/query",
            {"store_id": store_id, "sparql": sparql,
             "deadline_ms": self.deadline_ms},
            trace_id,
        )

