"""Loopback REST client: JSON in, JSON out, over one persistent connection
per client object (one client per thread). Copied in spirit from
``chip_smoke.Rest``; it polls ``/3/Jobs`` every 20 ms, not every 200 ms,
because a GLM job lasts a couple of seconds.
"""

from __future__ import annotations

import http.client
import json
import time
import urllib.parse

JOB_POLL_S = 0.02
JOB_TIMEOUT_S = 1000.0


class Rest:
    def __init__(self, port: int, timeout: float = JOB_TIMEOUT_S):
        self.port = int(port)
        self.timeout = timeout
        self._conn = None

    def _connection(self) -> http.client.HTTPConnection:
        if self._conn is None:
            self._conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                                    timeout=self.timeout)
        return self._conn

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def request(self, method: str, path: str, data=None, query=None):
        """-> (status, parsed JSON or None). Never raises on an HTTP status;
        a broken connection is retried once on a fresh one."""
        if query:
            path += "?" + urllib.parse.urlencode(query)
        body = json.dumps(data).encode() if data is not None else None
        headers = {"Content-Type": "application/json"} if body else {}
        for attempt in (0, 1):
            conn = self._connection()
            try:
                conn.request(method, path, body=body, headers=headers)
                resp = conn.getresponse()
                text = resp.read()
                break
            except (http.client.HTTPException, ConnectionError, OSError):
                self.close()
                if attempt:
                    raise
        try:
            parsed = json.loads(text.decode()) if text else None
        except ValueError:
            parsed = None
        return resp.status, parsed

    def __call__(self, method: str, path: str, data=None, query=None):
        """The strict form: anything but 200 is an error with the body."""
        status, parsed = self.request(method, path, data, query)
        if status != 200:
            raise RuntimeError(f"{method} {path} -> HTTP {status}: "
                               f"{json.dumps(parsed)[:2000]}")
        return parsed

    def run_job(self, algo: str, body: dict, annotate=None) -> dict:
        """POST /3/ModelBuilders/{algo}, poll /3/Jobs to the end.
        -> {"status", "seconds", "model_id", "exception"}; status is DONE,
        FAILED, CANCELLED or HTTP<code>. ``annotate(name)`` may return a
        context manager that names the phase in a profiler trace."""
        import contextlib

        note = annotate or (lambda name: contextlib.nullcontext())
        t0 = time.perf_counter()
        with note("job_post"):
            status, out = self.request("POST", f"/3/ModelBuilders/{algo}",
                                       data=body)
        if status != 200:
            return {"status": f"HTTP{status}", "model_id": None,
                    "seconds": time.perf_counter() - t0,
                    "exception": json.dumps(out)[:2000]}
        key = out["job"]["key"]["name"]
        with note("job_poll"):
            while True:
                job = self("GET", f"/3/Jobs/{key}")["jobs"][0]
                if job["status"] in ("DONE", "FAILED", "CANCELLED"):
                    break
                if time.perf_counter() - t0 > JOB_TIMEOUT_S:
                    job = dict(job, status="TIMEOUT")
                    break
                time.sleep(JOB_POLL_S)
        return {"status": job["status"], "model_id": job["dest"]["name"],
                "seconds": time.perf_counter() - t0,
                "exception": job.get("exception")}
