"""The load generator: closed-loop reader clients and probe clients, one
thread and one persistent connection each, in the parent process (so client
threads do not share the server's interpreter lock). Times are
``time.monotonic()``, which parent and child share on Linux."""

from __future__ import annotations

import http.client
import json
import threading
import time

from . import check

REQUEST_TIMEOUT_S = 120.0


def request_body(text: str, k: int, fields: dict) -> str:
    """The bytes of one request: ``fields`` is what a scoped mix adds (nothing,
    for a request with no filter)."""
    return json.dumps({"query": text, "k": k, **fields})


class Client(threading.Thread):
    """Sends its next request when the last reply is read, no think time,
    until ``until()`` says stop. ``next_query()`` gives (query id, text,
    folder): the folder a scoped mix (``scope``, a ``traffic.Scope``) confines
    the request to, None for a request with no filter."""

    def __init__(self, name: str, port: int, route: str, k: int, t0: float,
                 next_query, until, scope=None):
        super().__init__(name=name, daemon=True)
        self.port, self.route, self.k, self.t0 = port, route, k, t0
        self.next_query, self.until, self.scope = next_query, until, scope
        self.records: list[dict] = []
        self._conn: http.client.HTTPConnection | None = None

    def _post(self, text: str, fields: dict):
        """(status, body); status 0 is no answer. One reconnect if the server
        closed a kept connection between requests."""
        payload = request_body(text, self.k, fields)
        for attempt in (0, 1):
            try:
                if self._conn is None:
                    self._conn = http.client.HTTPConnection(
                        "127.0.0.1", self.port, timeout=REQUEST_TIMEOUT_S)
                self._conn.request("POST", self.route, body=payload,
                                   headers={"Content-Type": "application/json"})
                resp = self._conn.getresponse()
                return resp.status, resp.read()
            except (http.client.RemoteDisconnected, ConnectionResetError,
                    BrokenPipeError) as e:
                self._close()
                if attempt:
                    return 0, repr(e).encode()
            except OSError as e:
                self._close()
                return 0, repr(e).encode()
        return 0, b""

    def _close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def run(self) -> None:
        time.sleep(max(0.0, self.t0 - time.monotonic()))
        try:
            while not self.until():
                qid, text, folder = self.next_query()
                fields, fewest, most = {}, self.k, self.k
                if folder is not None:
                    fields = self.scope.body_fields(folder)
                    fewest, most = self.scope.rows_wanted(folder, self.k)
                send = time.monotonic()
                status, body = self._post(text, fields)
                recv = time.monotonic()
                rows = None
                if status == 200:
                    try:
                        rows = check.reply_rows(json.loads(body), fewest, most)
                    except ValueError:
                        rows = None
                rec = {"client": self.name, "qid": qid, "query": text, "scope": folder,
                       "send": send, "recv": recv, "status": status, "rows": rows}
                if rows is None:
                    rec["error"] = body[:1500].decode("utf-8", "replace")
                self.records.append(rec)
        finally:
            self._close()


def run_clients(clients: list[Client]) -> list[dict]:
    """Start all, wait for all (each ends by its own ``until`` and its
    request's timeout; the run's deadline, ``run.py``'s one timer, cuts the
    wait); records of every client, by send time."""
    for c in clients:
        c.start()
    for c in clients:
        c.join()
    out = [r for c in clients for r in list(c.records)]
    out.sort(key=lambda r: r["send"])
    return out
