"""The control and the faults come out NOT correct, sound runs come out
correct — at a size a test run can hold (the chip readings at the cells' own
sizes are in PERF.md). The judge is the cell's own: bench/reference/*.check_*
and the configuration's limits."""

import json
import os

import numpy as np
import pytest

from bench.harness import data as recipe
from bench.harness import phases
from bench.reference import gbm, glm

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 20_000


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rows():
    out = recipe.device_columns(3_000_000_011, ROWS)
    return out[:-1], out[-1]


def _verdict(numbers, cfg):
    compared = phases.compare(numbers, cfg["limits"])
    assert compared
    return all(v <= lim for v, lim in compared.values()), compared


@pytest.mark.parametrize("precision,fault,want", [
    ("reference", None, True),
    ("stated", None, True),
    ("control", None, False),
    ("reference", "state_unchanged", False),
    ("reference", "half_batch", False),
])
def test_gbm_control_and_faults(rows, precision, fault, want):
    cfg = _cfg("higgs_gbm_d5")
    cols, y = rows
    forest = gbm.grow(cols, y, cfg["params"], ntrees=3, precision=precision,
                      fault=fault)
    ok, compared = _verdict(gbm.check_forest(cols, y, cfg["params"], forest),
                            cfg)
    assert ok is want, compared


def test_gbm_altered_answer_is_caught(rows):
    cfg = _cfg("higgs_gbm_d5")
    cols, y = rows
    forest = gbm.grow(cols, y, cfg["params"], ntrees=3)
    X = np.stack([np.asarray(c[:512]) for c in cols], axis=1)
    want = gbm.predict(forest, cfg, X=X)
    again = gbm.predict(forest, cfg, cols=tuple(c[:512] for c in cols))
    assert float(np.max(np.abs(want - again))) <= cfg["limits"]["pred_gap"]
    bent = dict(forest, leaf=forest["leaf"] * 1.01)
    off = gbm.predict(bent, cfg, X=X)
    assert float(np.max(np.abs(want - off))) > cfg["limits"]["pred_gap"]


@pytest.mark.parametrize("precision,want", [("reference", True),
                                            ("control", False)])
def test_glm_control(rows, precision, want):
    cfg = _cfg("higgs_glm_binomial")
    cols, y = rows
    fitted = glm.fit(cols, y, cfg["params"], precision)
    produced = {"coef": fitted["coef"],
                "reported": {"logloss": fitted["logloss"]}}
    ok, compared = _verdict(glm.check_model(cols, y, cfg, produced), cfg)
    assert ok is want, compared
