"""A plain JSON-RPC 2.0 client over one keep-alive HTTP connection.

One `Rpc` object is one connection and belongs to one thread. The
benchmark keeps its own client so that the request a cell times is the
bytes written here, not whatever the program's SDK grows into.
"""

from __future__ import annotations

import http.client
import itertools
import json


class RpcError(Exception):
    pass


class Rpc:
    def __init__(self, port: int, timeout: float = 60.0,
                 host: str = "127.0.0.1"):
        self.host, self.port, self.timeout = host, port, timeout
        self._conn: http.client.HTTPConnection | None = None
        self._ids = itertools.count(1)

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def _post(self, body: bytes) -> bytes:
        """One POST; a connection the server closed while idle is reopened
        once (sendTransaction dedups by hash, reads are idempotent)."""
        for attempt in (0, 1):
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=self.timeout)
            try:
                self._conn.request("POST", "/", body=body, headers={
                    "Content-Type": "application/json"})
                resp = self._conn.getresponse()
                data = resp.read()
            except TimeoutError:
                self.close()
                raise
            except (http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    raise
                continue
            if resp.will_close:
                self.close()
            if resp.status != 200:
                raise RpcError(f"HTTP {resp.status}: {data[:200]!r}")
            return data
        raise AssertionError("unreachable")

    def call(self, method: str, params: list):
        out = json.loads(self._post(json.dumps({
            "jsonrpc": "2.0", "id": next(self._ids), "method": method,
            "params": params}).encode()))
        if "error" in out:
            raise RpcError(f"{method}: {out['error']}")
        return out.get("result")

    def batch(self, calls: list) -> list:
        """[(method, params)] -> the response objects in request order;
        each holds `result` or `error`."""
        ids = [next(self._ids) for _ in calls]
        raw = self._post(json.dumps([
            {"jsonrpc": "2.0", "id": i, "method": m, "params": p}
            for i, (m, p) in zip(ids, calls)]).encode())
        out = json.loads(raw)
        if isinstance(out, dict):
            raise RpcError(f"batch refused: {out.get('error')}")
        by_id = {r.get("id"): r for r in out}
        if len(by_id) != len(ids) or any(i not in by_id for i in ids):
            raise RpcError("batch answer does not match its requests")
        return [by_id[i] for i in ids]

    def results(self, calls: list, chunk: int = 256) -> list:
        """`batch` in chunks the default [rpc] max_batch takes; an entry's
        error raises."""
        out = []
        for o in range(0, len(calls), chunk):
            for r in self.batch(calls[o:o + chunk]):
                if "error" in r:
                    raise RpcError(f"{calls[o][0]}: {r['error']}")
                out.append(r["result"])
        return out
