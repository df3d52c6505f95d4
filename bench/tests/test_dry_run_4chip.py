"""--cpu-dry-run of the four-chip cell on four virtual CPU devices, as the
driver would start it (beside test_dry_run.py, whose parametrisation runs
the cell on one device): the frame and the bin matrix over the mesh, the
tree program's all-reduces counted, the sharded reference over each
device's rows."""

import json

import pytest

from bench.tests.test_dry_run import _bench

CELL = "airline_gbm_train_4chip"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_dry_run_of_the_four_chip_cell_on_four_virtual_devices(trace):
    p = _bench("--workload", CELL, "--seed", "3400000031", "--seconds", "4",
               "--trace", trace, "--cpu-dry-run", devices=4)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4 and out["correct"] is True, out
    assert out["failed"] == 0 and out["info"]["window_compiles"] == 0
    if trace == "1":
        # two trees' level sums, leaf sums and mean, and the metrics pass
        assert out["metrics"]["tree_psum_mb_per_job"]["value"] == 22.067356
        assert out["metrics"]["tree_route_select_pct"]["value"] == 90.0
    else:
        assert set(out["metrics"]) == {"train_rows_per_s", "setup_s"}
