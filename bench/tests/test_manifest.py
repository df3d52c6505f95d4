"""BENCHMARK.json against the contract's rules that a test can hold."""

import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_tokens():
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    names = []
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[sec]:
            assert NAME.match(e["name"]), e["name"]
            names.append((sec in ("end_to_end", "per_layer"), e["name"]))
    assert len(set(names)) == len(names)
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(e["layer"]), e["layer"]
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(len(m["workloads"]) // 4, 1)


def test_every_cell_finds_its_files_and_every_metric_its_reader():
    m = _manifest()
    cfgs = {c["name"]: c for c in m["configs"]}
    used = set()
    for w in m["workloads"]:
        c = cfgs[w["config"]]
        used.add(c["name"])
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"])
        with open(os.path.join(ROOT, "bench", "mixes",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        importlib.import_module(f"bench.drivers.{mix['driver']}")
        importlib.import_module(f"bench.reference.{cfg['reference']}")
        for prog in cfg["roofline"].values():
            importlib.import_module(f"bench.roofline.{prog}")
    assert used == set(cfgs)
    for e in m["per_layer"]:
        mod = importlib.import_module(
            "bench.layer_metrics." + e["name"].split(".")[0])
        assert callable(mod.read)


def test_cells_report_what_their_layer_metrics_move():
    m = _manifest()
    cells = [w["name"] for w in m["workloads"]]
    e2e = {e["name"]: e.get("workloads", cells) for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    for w in m["workloads"]:
        with open(os.path.join(ROOT, "bench", "mixes",
                               w["traffic"] + ".json")) as f:
            reports = set(json.load(f)["reports"])
        mine = {n for n, ws in e2e.items() if w["name"] in ws} - {"setup_s"}
        assert mine and mine == reports, (w["name"], mine, reports)
        assert any(w["name"] in p.get("workloads", cells)
                   for p in m["per_layer"])
    for p in m["per_layer"]:
        assert p["moves"] in e2e and p["moves"] != "setup_s"
        for c in p.get("workloads", e2e[p["moves"]]):
            assert c in e2e[p["moves"]], (p["name"], c)
    layers = {}
    for p in m["per_layer"]:
        layers.setdefault(p["layer"], []).append(p["name"])
    assert "step" in layers and any("mfu" in n for n in layers["step"])
