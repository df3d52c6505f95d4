"""Observability: TimeLine ring, boot probes, profiler REST surfaces.

Reference: water/TimeLine.java:22,
water/init/Linpack.java / MemoryBandwidth.java / NetworkBench.java,
water/api/TimelineHandler + ProfilerHandler.
"""

import os

import numpy as np
import pytest

from h2o3_tpu.utils import timeline


class TestRing:
    def test_record_and_fetch(self):
        timeline.clear()
        timeline.record("test", "hello", ms=1.5, extra=7)
        evs = timeline.events()
        assert evs[-1]["kind"] == "test" and evs[-1]["extra"] == 7


class TestBootProbes:
    def test_self_benchmark(self, cl):
        b = cl.self_benchmark(size=256)
        assert b["matmul_gflops"] > 0
        assert b["membw_gbps"] > 0
        assert b["psum_latency_us"] > 0
        assert any(e["kind"] == "self_benchmark" for e in timeline.events())


class TestDeviceMemory:
    def test_gauges_shape(self, cl):
        mem = timeline.device_memory()
        assert len(mem) >= 1
        assert "device" in mem[0]


class TestRESTSurfaces:
    def test_timeline_and_profiler(self, cl):
        from h2o3_tpu import client
        from h2o3_tpu.api.server import start_server

        srv = start_server(port=0)
        try:
            client.connect(port=srv.port)
            timeline.record("marker", "from_test")
            body = client._req("GET", "/3/Timeline")
            kinds = {e.get("kind") for e in body["events"]}
            assert "marker" in kinds and "rest" in kinds
            body = client._req("GET", "/3/Profiler")
            assert body["nodes"]
        finally:
            srv.stop()


class TestXLATrace:
    def test_trace_writes_files(self, cl, tmp_path):
        import jax.numpy as jnp

        d = str(tmp_path / "prof")
        with timeline.trace(d):
            (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
        assert os.path.isdir(d) and os.listdir(d)
        assert any(e["kind"] == "xla_trace" for e in timeline.events())


def test_tls_rest_bind(tmp_path, cl):
    """TLS on the REST bind (water/network/SSLProperties analog): https
    serves, plain http against the TLS port fails."""
    import json
    import ssl
    import subprocess
    import urllib.request

    from h2o3_tpu.api.server import start_server

    cert = tmp_path / "cert.pem"
    key = tmp_path / "key.pem"
    subprocess.run(["openssl", "req", "-x509", "-newkey", "rsa:2048",
                    "-keyout", str(key), "-out", str(cert), "-days", "1",
                    "-nodes", "-subj", "/CN=localhost"],
                   check=True, capture_output=True)
    srv = start_server(port=0, ssl_certfile=str(cert), ssl_keyfile=str(key))
    try:
        assert srv.scheme == "https"
        sctx = ssl.create_default_context()
        sctx.check_hostname = False
        sctx.verify_mode = ssl.CERT_NONE        # self-signed test cert
        with urllib.request.urlopen(f"https://127.0.0.1:{srv.port}/3/Cloud",
                                    context=sctx, timeout=30) as r:
            cloud = json.loads(r.read())
        assert cloud["cloud_healthy"] is True
        import pytest as _pytest

        with _pytest.raises(Exception):
            urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/3/Cloud",
                                   timeout=5)
    finally:
        srv.stop()


def test_pluggable_login_module(tmp_path, cl, monkeypatch):
    """H2O_TPU_LOGIN_MODULE (JAAS login-module analog, h2o-security
    LDAP/PAM realms): any module:callable authenticates Basic creds."""
    import json
    import sys
    import types
    import urllib.request

    from h2o3_tpu.api.server import start_server

    mod = types.ModuleType("_test_authmod")
    mod.check = lambda user, pw: user == "ldapuser" and pw == "s3cret"
    sys.modules["_test_authmod"] = mod
    monkeypatch.setenv("H2O_TPU_LOGIN_MODULE", "_test_authmod:check")
    srv = start_server(port=0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        import base64

        def get(creds=None):
            req = urllib.request.Request(base + "/3/Cloud")
            if creds:
                req.add_header("Authorization", "Basic "
                               + base64.b64encode(creds.encode()).decode())
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, None

        assert get()[0] == 401                      # no creds
        assert get("ldapuser:wrong")[0] == 401
        code, cloud = get("ldapuser:s3cret")
        assert code == 200 and cloud["cloud_healthy"] is True
    finally:
        srv.stop()
        del sys.modules["_test_authmod"]
