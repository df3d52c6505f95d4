"""ModelBuilder: param validation, train/valid adaptation, CV orchestration.

Reference: hex/ModelBuilder.java — trainModel() (:359) launches a Job running
the algo Driver; n-fold CV builds fold models then the main model
(cv_computeAndSetOptimalParameters, CVModelBuilder.java); early stopping via
hex/ScoreKeeper.java.

TPU-native: the Driver is a host loop around jitted steps; fold models are
trained sequentially on row-subset frames (device gathers); the "cloud" never
changes shape so there is no work-stealing to schedule — XLA owns the chip.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from h2o3_tpu.core.dkv import DKV
from h2o3_tpu.core.frame import Frame, T_CAT
from h2o3_tpu.core.job import Job
from h2o3_tpu.models import metrics as M
from h2o3_tpu.models.model import Model, ModelCategory
from h2o3_tpu.obs import tracing


def random_seed() -> int:
    """Fresh 31-bit seed — the one seed-derivation policy (builders' seed=-1
    fallback, AutoML's pinned shared seed)."""
    return int(np.random.SeedSequence().entropy % (2 ** 31))


class ModelBuilder:
    """Base estimator. Subclass contract:
    - class attrs: `algo_name`, `model_class`
    - `_fit(train: Frame) -> Model` — train on the (already adapted) frame
      using self.params; must set model._output.{names,domains,response_*,
      model_category} (helper `_init_output` does the common part).
    """

    algo_name = "base"
    model_class = Model
    supervised = True
    # Whether two training runs of this builder may execute device programs
    # concurrently. Collective-bearing programs (tree histograms, DL) can
    # deadlock the XLA CPU runtime when interleaved, so the default is
    # False and the AutoML/grid search engine serializes them on a device
    # lane; collective-free builders opt in.
    parallel_safe = False

    def __init__(self, **params):
        self.params: Dict[str, Any] = self.default_params()
        unknown = [k for k in params if k not in self.params]
        if unknown:
            raise ValueError(f"unknown {self.algo_name} parameters: {unknown}")
        self.params.update({k: v for k, v in params.items() if v is not None})
        self.job: Optional[Job] = None
        self.model: Optional[Model] = None
        # crash-survivable training: the externally-visible Job durable
        # progress is persisted against (set by the REST handler / recovery
        # watchdog — None keeps library-mode training cost-free), and the
        # restored loop state a resumed dispatch fast-forwards from
        self._progress_job: Optional[Job] = None
        self._resume_state: Optional[dict] = None

    # -- param surface ----------------------------------------------------
    @classmethod
    def translate_param(cls, name: str) -> str:
        """Map an external param spelling to the canonical one (overridden
        by XGBoost for eta/n_estimators/... — used by the REST layer)."""
        return name

    @classmethod
    def default_params(cls) -> Dict[str, Any]:
        return {
            "response_column": None,
            "ignored_columns": [],
            "weights_column": None,
            "offset_column": None,
            "fold_column": None,
            "nfolds": 0,
            "fold_assignment": "AUTO",   # AUTO/Random/Modulo/Stratified
            "keep_cross_validation_models": True,
            "keep_cross_validation_predictions": False,
            "seed": -1,
            "max_runtime_secs": 0.0,
            "stopping_rounds": 0,
            "stopping_metric": "AUTO",
            "stopping_tolerance": 1e-3,
            "model_id": None,
            "validation_frame": None,
            "training_frame": None,
            "categorical_encoding": "AUTO",
            # training continuation (hex/Model.java:365 _checkpoint; param
            # compatibility rules in hex/util/CheckpointUtils.java) and
            # automatic model export (hex/Model.java:387 _export_checkpoints_dir)
            "checkpoint": None,
            "export_checkpoints_dir": None,
        }

    def _out_of_time(self) -> bool:
        d = getattr(self, "_deadline", None)
        return d is not None and time.time() > d

    def _seed(self) -> int:
        s = int(self.params.get("seed", -1) or -1)
        return s if s >= 0 else random_seed()

    # -- h2o-py style entry ----------------------------------------------
    def train(self, x: Optional[Sequence[str]] = None, y: Optional[str] = None,
              training_frame: Optional[Frame] = None,
              validation_frame: Optional[Frame] = None, **kw) -> Model:
        """Synchronous train (reference trainModel().get()). x = predictor
        names (default: all minus response/weights/fold)."""
        unknown = [k for k in kw if k not in self.params]
        if unknown:
            raise ValueError(f"unknown {self.algo_name} parameters: {unknown}")
        self.params.update({k: v for k, v in kw.items() if v is not None})
        train = training_frame or self.params.get("training_frame")
        if train is None:
            raise ValueError("training_frame required")
        if y is not None:
            self.params["response_column"] = y
        valid = validation_frame or self.params.get("validation_frame")
        resp = self.params.get("response_column")
        if self.supervised and not resp:
            raise ValueError(f"{self.algo_name}: response_column required")
        if self.supervised and resp not in train:
            raise ValueError(f"response column {resp!r} not in training frame")

        if x is not None:
            keep = list(x) + [c for c in (resp, self.params.get("weights_column"),
                                          self.params.get("offset_column"),
                                          self.params.get("fold_column")) if c]
            train = train.subframe([c for c in train.names if c in keep])

        self.job = Job(description=f"{self.algo_name} train", dest=self.params.get("model_id"))
        t0 = time.time()
        # wall-clock budget (hex/ModelBuilder _max_runtime_secs): iterative
        # fit loops poll _out_of_time() and keep the model built so far
        mrt = float(self.params.get("max_runtime_secs") or 0.0)
        self._deadline = (t0 + mrt) if mrt > 0 else None
        # locked transitions: the cloud supervisor can fail() this job from
        # another thread at any instant — status check+set must be atomic
        # or a dead cloud's job reports DONE (the fail()/completion race)
        if not self.job.begin():
            raise RuntimeError(
                f"Job {self.job.key} was failed before training started:\n"
                f"{self.job.exception}")
        try:
            model = self._train_impl(train, valid)
        except Exception:
            import traceback

            self.job.fail_local(traceback.format_exc())
            raise
        if self.job.complete():
            # only a completion that WON the verdict supersedes the durable
            # progress — when an external FAILED landed first, the progress
            # file is exactly what the watchdog needs to resume the job
            self._clear_job_progress()
        model._output.run_time_ms = int((time.time() - t0) * 1000)
        self.model = model
        return model

    # -- orchestration ----------------------------------------------------
    # builders that implement training continuation set this True; everyone
    # else must REJECT the param rather than silently train from scratch
    supports_checkpoint = False
    # builders whose fit loops persist durable per-iteration progress and
    # can fast-forward from it (_tick_job_progress / _take_resume_state)
    supports_iteration_resume = False

    # -- durable job progress (crash-survivable training) -----------------
    def _job_ckpt_every(self) -> int:
        """Chunk/persist interval in completed iterations; 0 when the env
        knob is unset or this builder cannot resume. Derived from the ENV
        + capability ONLY — the value shapes the fit loop itself (chunked
        IRLS / Lloyd), and every process of a multi-process cloud must
        walk identical device program sequences whether or not it is the
        one persisting (followers replaying a broadcast train carry no
        ``_progress_job``). Whether a tick actually SAVES is decided in
        ``_tick_job_progress``."""
        if not self.supports_iteration_resume:
            return 0
        from h2o3_tpu.parallel import ckpt

        return max(ckpt.job_ckpt_iters(), 0)

    def _tick_job_progress(self, done: int, state_fn) -> None:
        """Called by iterative fit loops after `done` completed iterations;
        every ``H2O_TPU_JOB_CKPT_ITERS`` it persists ``state_fn()`` through
        the job-progress store. Saves happen only on the dispatching
        process (the one holding the REST-visible job) — everyone else
        pays a couple of int compares. Best-effort by contract: a failed
        write logs and training continues (durability must never fail the
        build)."""
        every = self._job_ckpt_every()
        if every <= 0 or done <= 0 or done % every != 0:
            return
        job = self._progress_job
        if job is None or not getattr(job, "resume_spec", None):
            return
        if done == getattr(self, "_jp_last", 0):
            return
        from h2o3_tpu.parallel import ckpt

        try:
            ckpt.save_job_progress(str(self._progress_job.key), done,
                                   self._progress_job.resume_spec, state_fn())
            self._jp_last = done
        except Exception as e:   # noqa: BLE001 — best-effort by contract
            from h2o3_tpu.utils.log import get_logger

            get_logger().warning(
                "job %s: progress persist at iteration %d failed "
                "(training continues): %s", self._progress_job.key, done, e)

    def _clear_job_progress(self) -> None:
        """A completed build supersedes its partial progress — GC it.
        Checked+deleted under the REST job's status lock: the supervisor's
        external FAILED targets the REST-visible job (a different object
        from the builder's internal one), and if that verdict already
        landed, the progress file IS the watchdog's resume input."""
        from h2o3_tpu.core.job import Job
        from h2o3_tpu.parallel import ckpt

        job = self._progress_job
        if job is None or not getattr(job, "resume_spec", None):
            return
        try:
            with job._status_lock:
                if job.status == Job.FAILED and job.failed_externally:
                    return
                ckpt.delete_job_progress(str(job.key))
        except Exception:   # noqa: BLE001 — GC stays best-effort
            pass

    def _take_resume_state(self, phase: str) -> Optional[dict]:
        """Hand the restored loop state to the fit loop that saved it (the
        `phase` tag guards against an algo/loop mismatch after a param
        drift) — consumed once, so CV submodels never see it."""
        rs = self._resume_state
        if isinstance(rs, dict) and rs.get("phase") == phase:
            self._resume_state = None
            return rs
        return None

    def _train_impl(self, train: Frame, valid: Optional[Frame]) -> Model:
        nfolds = int(self.params.get("nfolds") or 0)
        fold_col = self.params.get("fold_column")
        if self.params.get("calibrate_model"):
            # fail BEFORE training: these use only params + response type
            if self.params.get("calibration_frame") is None:
                raise ValueError("calibrate_model=True requires a "
                                 "calibration_frame")
            rc = train.col(self.params.get("response_column"))
            if not (rc.is_categorical and len(rc.domain or []) == 2):
                raise ValueError("model calibration supports binomial models")
        if self.params.get("checkpoint"):
            if not self.supports_checkpoint:
                raise ValueError(
                    f"{self.algo_name} does not support checkpoint continuation")
            # must fire BEFORE CV: fold models resuming from a full-data
            # checkpoint would leak every holdout into training
            if nfolds > 1 or fold_col:
                raise ValueError(
                    "checkpoint cannot be combined with cross-validation")
        cv_models: List[Model] = []
        cv_metrics: List = []
        cv_preds = None
        fold_digest = None
        if nfolds > 1 or fold_col:
            cv_models, cv_metrics, cv_preds, fold_digest = \
                self._cross_validate(train, nfolds, fold_col)

        self._valid_frame_ref = valid      # in-training validation scoring
        try:
            model = self._fit(train)
        finally:
            self._valid_frame_ref = None
        if cv_preds is not None:
            model._output.cross_validation_holdout_predictions = cv_preds
        if fold_digest is not None:
            model._output.fold_assignment_digest = fold_digest
        # stage spans ``metrics``: each ends where the metrics' host values
        # are read, which is where this pass has always blocked
        with tracing.span("metrics", frame="train", rows=train.nrows):
            model._output.training_metrics = self._score_on(model, train)
        if valid is not None:
            with tracing.span("metrics", frame="valid", rows=valid.nrows):
                model._output.validation_metrics = self._score_on(model,
                                                                  valid)
        if cv_metrics:
            model._output.cv_fold_metrics = cv_metrics
            model._output.cross_validation_metrics = _mean_metrics(cv_metrics)
            if not self.params.get("keep_cross_validation_models", True):
                for m in cv_models:
                    m.delete()
        # drop fit-time scratch refs so the builder doesn't pin the training
        # frame / full-N device buffers after the model is done
        self._train_frame_ref = None
        self._oob_raw = None
        self._maybe_calibrate(model)
        ed = self.params.get("export_checkpoints_dir")
        if ed:
            # hex/Model.java:387 exportBinaryModel into _export_checkpoints_dir
            # when training completes (AutoML uses this to retain every model)
            import os

            os.makedirs(ed, exist_ok=True)
            model.save(os.path.join(ed, f"{model.key}.bin"))
        return model

    # -- probability calibration (hex/tree CalibrationHelper: Platt scaling
    #    or isotonic regression fit on a held-out calibration_frame) -------
    def _maybe_calibrate(self, model: Model) -> None:
        # preconditions (frame present, binomial response) were validated in
        # _train_impl BEFORE training started — the only caller
        if not self.params.get("calibrate_model"):
            return
        frame = self.params.get("calibration_frame")
        from h2o3_tpu.models.data_info import DataInfo

        raw = model._predict_raw(model.adapt_test(frame))
        p = np.asarray(raw["probs"])[: frame.nrows, 1].astype(np.float64)
        y_col = model._adapt_response(frame.col(model._output.response_name))
        y = np.asarray(DataInfo.clean_response(y_col.data))[: frame.nrows]
        wc = self.params.get("weights_column")
        w_user = (frame.col(wc).data if wc and wc in frame else None)
        w = np.asarray(DataInfo.response_weight(y_col.data, w_user))[: frame.nrows]
        ok = w > 0
        method = str(self.params.get("calibration_method")
                     or "PlattScaling").lower()
        if method in ("auto", "plattscaling", "platt"):
            model._calibrator = ("platt", _fit_platt(p[ok], y[ok], w=w[ok]))
        elif method in ("isotonicregression", "isotonic"):
            from h2o3_tpu.models.isotonic import pava

            model._calibrator = ("isotonic",
                                 pava(p[ok], y[ok].astype(float), w[ok]))
        else:
            raise ValueError(f"unknown calibration_method {method!r}")
        # the calibration frame must not ride along in the model artifact
        # (it would pin HBM and bloat pickles); keep its key for provenance
        model._parms["calibration_frame"] = str(getattr(frame, "key", ""))

    # -- checkpoint (training continuation) -------------------------------
    # params a continuation may change (hex/util/CheckpointUtils.java keeps a
    # whitelist per algo; this is the union that matters here)
    _CHECKPOINT_MODIFIABLE = frozenset({
        "checkpoint", "model_id", "training_frame", "validation_frame",
        "ntrees", "epochs", "max_runtime_secs", "seed",
        "stopping_rounds", "stopping_metric", "stopping_tolerance",
        "score_each_iteration", "score_tree_interval",
        "export_checkpoints_dir", "keep_cross_validation_models",
        "keep_cross_validation_predictions",
    })

    def _resolve_checkpoint(self) -> Optional[Model]:
        """Fetch + validate the checkpoint model named by params['checkpoint'].
        Non-modifiable params must match the original run (CheckpointUtils
        analog); CV and checkpointing are mutually exclusive as in the
        reference."""
        ck = self.params.get("checkpoint")
        if not ck:
            return None
        if int(self.params.get("nfolds") or 0) > 1 or self.params.get("fold_column"):
            raise ValueError("checkpoint cannot be combined with cross-validation")
        prev = ck if isinstance(ck, Model) else DKV.get(str(ck))
        if prev is None:
            raise ValueError(f"checkpoint model {ck!r} not found")
        if prev.algo_name != self.algo_name:
            raise ValueError(
                f"checkpoint model is a {prev.algo_name}, not a {self.algo_name}")
        for k, v in self.params.items():
            if k in self._CHECKPOINT_MODIFIABLE or k not in prev._parms:
                continue
            pv = prev._parms[k]
            if isinstance(pv, (list, tuple)) or isinstance(v, (list, tuple)):
                same = list(pv or []) == list(v or [])
            else:
                same = pv == v
            if not same:
                raise ValueError(
                    f"checkpoint: parameter {k!r} cannot be modified "
                    f"(was {pv!r}, now {v!r})")
        return prev

    def _cross_validate(self, train: Frame, nfolds: int, fold_col: Optional[str]):
        """hex/ModelBuilder CV: assign folds, train N fold models on
        out-of-fold rows, score each on its holdout. With
        keep_cross_validation_predictions, holdout predictions are scattered
        back into one full-length array (the StackedEnsemble level-one data,
        reference CVModelBuilder + StackedEnsemble.java)."""
        from h2o3_tpu.ops.filters import take_rows

        n = train.nrows
        if fold_col:
            assign = train.col(fold_col).to_numpy().astype(int)
            folds = sorted(set(assign.tolist()))
        else:
            scheme = (self.params.get("fold_assignment") or "AUTO").lower()
            if scheme in ("auto", "random"):
                rng = np.random.default_rng(self._seed())
                assign = rng.integers(0, nfolds, n)
            elif scheme == "stratified":
                # per-class round-robin over shuffled rows, so every fold sees
                # every response level (hex/ModelBuilder StratifiedAssignment)
                rng = np.random.default_rng(self._seed())
                resp = self.params.get("response_column")
                if not resp or not train.col(resp).is_categorical:
                    raise ValueError("fold_assignment='Stratified' requires a "
                                     "categorical response")
                y = train.col(resp).to_numpy()
                assign = rng.integers(0, nfolds, n)  # NA responses: random fold
                for cls in np.unique(y[y >= 0]):
                    idx = np.nonzero(y == cls)[0]
                    rng.shuffle(idx)
                    # random start offset so fold 0 doesn't collect every
                    # class's round-robin remainder
                    assign[idx] = (np.arange(len(idx)) + rng.integers(nfolds)) % nfolds
            elif scheme == "modulo":
                assign = np.arange(n) % nfolds
            else:
                raise ValueError(f"unknown fold_assignment {scheme!r}")
            folds = list(range(nfolds))
        keep_preds = bool(self.params.get("keep_cross_validation_predictions"))
        models, mets = [], []
        preds_buf = None
        for fi, f in enumerate(folds):
            ho_idx = np.nonzero(assign == f)[0]
            tr = take_rows(train, np.nonzero(assign != f)[0])
            ho = take_rows(train, ho_idx)
            sub = type(self)(**{k: v for k, v in self.params.items()
                                if k not in ("nfolds", "fold_column", "training_frame",
                                             "validation_frame", "model_id",
                                             "checkpoint", "export_checkpoints_dir")})
            # fold fits bypass train(), so the wall-clock budget must be
            # handed down — CV is the dominant cost under AutoML allocations
            sub._deadline = getattr(self, "_deadline", None)
            m = sub._fit(tr)
            # one predict pass serves both the fold metrics and the stacked
            # holdout predictions (review: avoid scoring each holdout twice)
            raw = m._predict_raw(m.adapt_test(ho))
            mets.append(m._make_metrics(ho, raw))
            if keep_preds:
                vals = np.asarray(raw["probs"] if "probs" in raw else raw["value"])
                vals = vals[: len(ho_idx)]        # drop shard padding
                if preds_buf is None:
                    shape = (n,) + vals.shape[1:]
                    preds_buf = np.zeros(shape, np.float32)
                preds_buf[ho_idx] = vals
            models.append(m)
            if self.job:
                self.job.update(progress=0.5 * (fi + 1) / len(folds),
                                msg=f"CV fold {fi + 1}/{len(folds)}")
            tr.delete()
            ho.delete()
        import hashlib

        digest = hashlib.sha1(np.ascontiguousarray(assign, np.int64)).hexdigest()
        return models, mets, preds_buf, digest

    def _score_on(self, model: Model, frame: Frame):
        raw = model._predict_raw(model.adapt_test(frame))
        return model._make_metrics(frame, raw)

    # -- shared init ------------------------------------------------------
    def _init_output(self, model: Model, train: Frame):
        resp = self.params.get("response_column")
        out = model._output
        skip = {resp, self.params.get("weights_column"),
                self.params.get("offset_column"), self.params.get("fold_column")}
        skip |= set(self.params.get("ignored_columns") or [])
        out.names = [c for c in train.names if c not in skip
                     and not train.col(c).is_string]
        out.domains = {c: list(train.col(c).domain) for c in out.names
                       if train.col(c).is_categorical}
        if resp:
            rc = train.col(resp)
            out.response_name = resp
            if rc.is_categorical:
                out.response_domain = list(rc.domain or [])
                out.model_category = (ModelCategory.Binomial if len(out.response_domain) == 2
                                      else ModelCategory.Multinomial)
            else:
                out.model_category = ModelCategory.Regression
        return out

    def _fit(self, train: Frame) -> Model:
        raise NotImplementedError


def _fit_platt(p: np.ndarray, y: np.ndarray,
               w: Optional[np.ndarray] = None, iters: int = 30):
    """Platt scaling: fit sigmoid(a*z + b) on z = logit(p) by Newton on the
    WEIGHTED 2-parameter logistic log-likelihood (CalibrationHelper's GLM
    collapses to exactly this 1-feature fit)."""
    z = np.log(np.clip(p, 1e-7, 1 - 1e-7) / (1 - np.clip(p, 1e-7, 1 - 1e-7)))
    if w is None:
        w = np.ones_like(z)
    a, b = 1.0, 0.0
    for _ in range(iters):
        mu = 1.0 / (1.0 + np.exp(-(a * z + b)))
        g = np.array([np.sum(w * (mu - y) * z), np.sum(w * (mu - y))])
        s = np.maximum(mu * (1 - mu), 1e-9) * w
        H = np.array([[np.sum(s * z * z), np.sum(s * z)],
                      [np.sum(s * z), np.sum(s)]])
        try:
            step = np.linalg.solve(H + 1e-9 * np.eye(2), g)
        except np.linalg.LinAlgError:
            break
        a, b = a - step[0], b - step[1]
        if np.abs(step).max() < 1e-10:
            break
    return float(a), float(b)


def _mean_metrics(mets: List):
    """Combine fold metrics (reference computes CV metrics on pooled holdout
    predictions; mean-of-folds is the documented approximation)."""
    mets = [m for m in mets if m is not None]
    if not mets:
        return None
    import copy
    import dataclasses

    out = copy.copy(mets[0])
    for f in dataclasses.fields(type(mets[0])):
        vals = [getattr(m, f.name) for m in mets]
        if all(isinstance(v, (int, float)) for v in vals):
            valid = [v for v in vals if v == v]
            if valid:
                setattr(out, f.name, float(np.mean(valid)))
    out.description = f"{len(mets)}-fold cross-validation (mean of folds)"
    return out


# registry: algo name -> builder class (water/api ModelBuilders listing)
BUILDERS: Dict[str, type] = {}


def register(cls):
    BUILDERS[cls.algo_name] = cls
    return cls
