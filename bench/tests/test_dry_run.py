"""--cpu-dry-run of every cell at 20,000 rows as the driver would start it
(a process of its own), once on four virtual CPU devices; and the refusals."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _bench(*args, devices=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)


REHEARSAL = "bench/tests/manifest_rehearsal.json"  # cells not admitted: PERF 7


@pytest.mark.parametrize("cell,manifest", [(c, "BENCHMARK.json")
                                           for c in _cells()]
                         + [("gbm_lookup", REHEARSAL), ("glm_train", REHEARSAL)])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_dry_run(cell, manifest, trace):
    p = _bench("--workload", cell, "--seed", "3000000029", "--seconds", "4",
               "--trace", trace, "--manifest", manifest, "--cpu-dry-run")
    assert p.returncode == 0, p.stderr[-3000:]
    assert "platform=cpu DRY RUN" in p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["compared"]
    assert out["device"]["platform"] == "cpu" and out["failed"] == 0
    if manifest == "BENCHMARK.json":    # the lookup path compiles per shape
        assert out["info"]["window_compiles"] == 0
    last = [ln for ln in p.stderr.splitlines() if ln.startswith("compared ")]
    assert len(last) == len(out["compared"])
    if trace == "0":
        assert "setup_s" in out["metrics"] and len(out["metrics"]) == 2
    else:
        assert "setup_s" not in out["metrics"] and out["metrics"]


def test_dry_run_on_four_virtual_devices():
    """What a chips: 4 cell needs of the harness: mesh from jax.devices(),
    data row-sharded over it, the reference over the sharded columns."""
    p = _bench("--workload", "gbm_train", "--seed", "3000000031",
               "--seconds", "4", "--trace", "0", "--cpu-dry-run", devices=4)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4 and out["correct"] is True, out


def test_no_chip_no_result():
    p = _bench("--workload", _cells()[0], "--seed", "1", "--seconds", "1",
               "--trace", "0")
    assert p.returncode != 0 and not p.stdout.strip()


def test_unknown_workload_is_refused():
    p = _bench("--workload", "no_such_cell", "--seed", "1", "--seconds", "1",
               "--trace", "0", "--cpu-dry-run")
    assert p.returncode != 0 and not p.stdout.strip()
