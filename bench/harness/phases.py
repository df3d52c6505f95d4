"""The steps the drivers share: the record of a run, the training frame, one
training job, the pools of scoring frames, and the comparison with the plain
reference that decides ``correct``."""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import time

import numpy as np

from bench.harness import data as recipe
from bench.harness import traffic
from bench.harness.rest import Rest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN_KEY = "bench_train.hex"


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


class Run:
    """Everything one run knows. Drivers and layer-metric readers get it."""

    def __init__(self, args, cell: dict, cfg: dict, mix: dict, system,
                 watch, annotate):
        self.args = args
        self.cell = cell
        self.cfg = cfg
        self.mix = mix
        self.system = system
        self.watch = watch
        self.annotate = annotate          # name -> context manager
        self.seed = int(args.seed)
        self.rows = int(cfg["dry_run_rows"] if system.dry_run
                        else cfg["rows"])
        self.rest = Rest(system.port)
        self.state = {}                   # what set-up leaves for the window
        self.window = {}                  # what the window leaves behind
        self.trace = None                 # reduced profiler trace, if taken
        self.window_compiles = None
        self.setup_parts = {}             # seconds per set-up phase
        self.peak = None                  # row of bench/peaks.json

    @contextlib.contextmanager
    def timed(self, name: str):
        """Seconds of a set-up phase, summed under its name."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.setup_parts[name] = self.setup_parts.get(name, 0.0) \
                + time.perf_counter() - t0


def training_frame(run: Run) -> None:
    """The training rows, on the device from the seed, as a resident frame."""
    with run.timed("data"):
        out = recipe.device_columns(run.seed, run.rows,
                                    sharding=run.system.row_sharding())
        run.state["cols"], run.state["y"] = out[:-1], out[-1]
        run.system.install_training_frame(TRAIN_KEY, run.state["cols"],
                                          run.state["y"])
        run.system.jax.block_until_ready(run.state["y"])


def job_body(run: Run) -> dict:
    lo, _hi = recipe.fold_seed(run.seed)
    return {"training_frame": TRAIN_KEY,
            "response_column": recipe.RESPONSE_NAME,
            "model_id": run.mix["model_id"], "seed": lo,
            **run.cfg["params"]}


def train_once(run: Run, rest: Rest = None) -> dict:
    """One whole job of the configuration's spec through the REST routes."""
    rec = (rest or run.rest).run_job(run.cfg["algo"], job_body(run),
                                     annotate=run.annotate)
    if rec["status"] == "DONE":
        rec["builder_ms"] = run.system.builder_ms(rec["model_id"])
    return rec


def warm_up_job(run: Run) -> dict:
    with run.timed("warm_up_job"):
        rec = train_once(run)
    if rec["status"] != "DONE":
        raise RuntimeError(f"warm-up job ended {rec['status']}: "
                           f"{rec['exception']}")
    run.state["warm_job"] = rec
    return rec


def serving_pools(run: Run) -> None:
    """The two pools of scoring frames (features only), installed before
    the window: the small ones from the host, the large ones made on the
    device."""
    pools = load_json("mixes", run.mix["pools_file"] + ".json")
    with run.timed("pools"):
        rng = recipe.host_rng(run.seed, stream=21)
        small = []
        for i, n in enumerate(traffic.pool_sizes(pools["small"], run.seed)):
            X = recipe.host_features(rng, n)
            key = f"bench_small_{i}.hex"
            run.system.install_feature_frame(
                key, [np.ascontiguousarray(X[:, j])
                      for j in range(X.shape[1])])
            small.append({"key": key, "rows": n, "X": X,
                          "dest": f"bench_small_{i}.pred"})
        large = []
        spec = pools["large"]
        n_large = int(spec["dry_run_rows"] if run.system.dry_run
                      else spec["rows"])
        for i in range(int(spec["count"])):
            cols = recipe.device_columns(
                run.seed, n_large, stream=100 + i, with_response=False,
                sharding=run.system.row_sharding())
            key = f"bench_large_{i}.hex"
            run.system.install_feature_frame(key, cols)
            large.append({"key": key, "rows": n_large, "cols": cols,
                          "dest": f"bench_large_{i}.pred"})
        run.system.jax.block_until_ready(large[-1]["cols"])
    run.state["pools"] = {"small": small, "large": large}


def serving_setup(run: Run) -> list:
    """The serving set-up, one and the same for every serving cell: the
    training frame, the model of one whole job, both pools, and one request
    on every frame of the mix's own pool (its row bucket). -> that pool."""
    training_frame(run)
    warm_up_job(run)
    serving_pools(run)
    pool = run.state["pools"][run.mix["pool"]]
    with run.timed("warm_up_requests"):
        for fr in pool:
            good, status = predict_once(run.rest, run.mix["model_id"], fr)
            if not good:
                raise RuntimeError(f"warm-up request on {fr['key']} -> "
                                   f"HTTP {status}")
    return pool


def predict_once(rest: Rest, model_id: str, frame: dict):
    """POST /3/Predictions for one pool frame -> (ok, status)."""
    status, _out = rest.request(
        "POST", f"/3/Predictions/models/{model_id}/frames/{frame['key']}",
        data={"predictions_frame": frame["dest"]})
    return status == 200, status


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------

def reference_module(run: Run):
    return importlib.import_module(f"bench.reference.{run.cfg['reference']}")


def read_produced(run: Run) -> dict:
    """The model the timed path left under the mix's model id, with the
    training metrics its REST document reports."""
    model_id = run.mix["model_id"]
    produced = run.system.read_model(run.cfg["algo"], model_id)
    doc = run.rest("GET", f"/3/Models/{model_id}")["models"][0]
    produced["reported"] = doc["output"].get("training_metrics") or {}
    return produced


def check_model(run: Run, produced: dict) -> dict:
    """The reference's numbers for the trained model, on the training rows
    (after the program's state is gone from the device)."""
    ref = reference_module(run)
    return ref.check_model(run.state["cols"], run.state["y"], run.cfg,
                           produced)


def compare(numbers: dict, limits: dict) -> dict:
    """name -> [value, limit] for every number that has a limit."""
    return {k: [float(numbers[k]), float(limits[k])]
            for k in limits if k in numbers}
