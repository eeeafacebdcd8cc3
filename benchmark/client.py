"""The HTTP client (copied from `chip_smoke.py`): one request per call on
`/v1/sql` or `/v1/prometheus/api/v1/query_range`, the JSON parsed into
rows inside the timed call."""

from __future__ import annotations

import json
import urllib.parse
import urllib.request


class Client:
    def __init__(self, address: str, timeout_s: float = 600.0):
        self.base, self.timeout_s = f"http://{address}", timeout_s

    def _open(self, path: str, params: dict | None, body: bytes | None) -> bytes:
        url = self.base + path
        if params:
            url += "?" + urllib.parse.urlencode(params)
        req = urllib.request.Request(
            url, data=body, method="POST" if body is not None else "GET"
        )
        with urllib.request.urlopen(req, timeout=self.timeout_s) as resp:
            if resp.status != 200:
                raise RuntimeError(f"{path} -> {resp.status}")
            return resp.read()

    def fetch(self, request: dict) -> bytes:
        """The response's body, as it came over the socket."""
        if "sql" in request:
            body = urllib.parse.urlencode({"sql": request["sql"]}).encode()
            return self._open("/v1/sql", None, body)
        return self._open("/v1/prometheus/api/v1/query_range", request, None)

    def send(self, request: dict) -> list:
        return parse(request, self.fetch(request))


def parse(request: dict, body: bytes) -> list:
    """The answer as rows.  SQL: the records' rows.  PromQL: the matrix
    flattened to (hostname, step ms, value) in series order."""
    doc = json.loads(body)
    if "sql" in request:
        return doc["output"][0]["records"]["rows"]
    if doc["status"] != "success":
        raise RuntimeError(f"query_range: {doc}")
    return [
        (s["metric"]["hostname"], int(ts) * 1000, float(v))
        for s in doc["data"]["result"] for ts, v in s["values"]
    ]
