"""XGBoost-compatible booster — the native-dependency replacement.

Reference: h2o-extensions/xgboost — H2O wraps the C++ XGBoost library over
JNI (NativeLibraryLoaderChain), moves Frames into off-heap DMatrix buffers,
and rebuilds the Rabit all-reduce tracker in Java (RabitTrackerH2O.java:14);
the GPU path is CUDA grow_gpu_hist (XGBoostModel.java:384-389).

TPU-native design (SURVEY.md §2.10 item 1): no external native library at
all — the SAME one-tree device program as GBM (device_tree.py) IS the
booster (hist == gpu_hist == our device histogram build), and the gradient
all-reduce is the mesh psum the histogram already performs. This class maps
the XGBoost parameter vocabulary (eta, colsample_*, reg_lambda, ...) onto
that engine, so `H2OXGBoostEstimator` users keep their param names.
"""

from __future__ import annotations

import numpy as np

from h2o3_tpu.models.model_builder import register
from h2o3_tpu.models.tree.gbm import GBM, GBMModel
from h2o3_tpu.obs import tracing


_STEP_FNS_DART = {}


class XGBoostModel(GBMModel):
    algo_name = "xgboost"


# xgboost param name -> shared-tree param name
_ALIASES = {
    "eta": "learn_rate",
    "learn_rate": "learn_rate",
    "max_depth": "max_depth",
    "ntrees": "ntrees",
    "n_estimators": "ntrees",
    "subsample": "sample_rate",
    "sample_rate": "sample_rate",
    "colsample_bytree": "col_sample_rate_per_tree",
    "col_sample_rate_per_tree": "col_sample_rate_per_tree",
    "colsample_bylevel": "col_sample_rate",
    "col_sample_rate": "col_sample_rate",
    "min_child_weight": "min_rows",
    "min_rows": "min_rows",
    "max_bins": "nbins",
    "gamma": "min_split_improvement",
    "min_split_improvement": "min_split_improvement",
}


@register
class XGBoost(GBM):
    algo_name = "xgboost"
    model_class = XGBoostModel

    @classmethod
    def default_params(cls):
        p = super().default_params()
        p.update({
            # xgboost-flavored knobs kept for API parity; reg_alpha/reg_lambda
            # act through leaf-value shrinkage like the reference's booster
            "reg_lambda": 1.0,
            "reg_alpha": 0.0,
            "booster": "gbtree",          # gbtree | dart | gblinear
            "rate_drop": 0.0,             # dart: per-tree dropout prob
            "skip_drop": 0.0,             # dart: prob of skipping dropout
            "tree_method": "hist",     # always hist — that IS the TPU kernel
            # XGBoost defaults, not GBM's (XGBoostModel.XGBoostParameters):
            # eta=0.3, min_child_weight=1, subsample/colsample=1, max_depth=6
            "learn_rate": 0.3,
            "min_rows": 1.0,
            "max_depth": 6,
            "sample_rate": 1.0,
            "col_sample_rate_per_tree": 1.0,
            "nbins": 256,
            "min_split_improvement": 0.0,   # gamma default

        })
        return p

    def __init__(self, **params):
        mapped = {}
        for k, v in params.items():
            mapped[_ALIASES.get(k, k)] = v
        super().__init__(**mapped)

    @classmethod
    def translate_param(cls, name: str) -> str:
        return _ALIASES.get(name, name)

    # -- boosters ---------------------------------------------------------
    def _fit(self, train):
        booster = (self.params.get("booster") or "gbtree").lower()
        if booster not in ("gbtree", "dart", "gblinear"):
            raise ValueError(f"unknown booster {booster!r} "
                             "(gbtree | dart | gblinear)")
        if booster == "dart":
            resp = train.col(self.params["response_column"])
            if resp.is_categorical and len(resp.domain or []) > 2:
                raise ValueError("booster='dart' supports binomial/"
                                 "regression responses only")
        if booster == "gblinear":
            return self._fit_gblinear(train)
        return super()._fit(train)

    def _fit_gblinear(self, train):
        """booster='gblinear' (XGBoost's boosted linear model): the limit of
        linear boosting IS the elastic-net GLM solution, so this delegates
        to the GLM solver with reg_alpha/reg_lambda mapped onto the
        elastic-net (alpha ratio, per-row-normalized lambda)."""
        from h2o3_tpu.models.glm import GLM

        ra = float(self.params.get("reg_alpha", 0.0) or 0.0)
        rl = float(self.params.get("reg_lambda", 1.0) or 0.0)
        tot = ra + rl
        resp = train.col(self.params["response_column"])
        fam = "binomial" if (resp.is_categorical and
                             len(resp.domain or []) == 2) else \
            ("multinomial" if resp.is_categorical else "gaussian")
        glm = GLM(family=fam,
                  alpha=(ra / tot) if tot > 0 else 0.0,
                  lambda_=tot / max(train.nrows, 1),
                  seed=self._seed(),
                  response_column=self.params["response_column"],
                  weights_column=self.params.get("weights_column"),
                  offset_column=self.params.get("offset_column"),
                  fold_column=self.params.get("fold_column"),
                  ignored_columns=self.params.get("ignored_columns") or [])
        model = glm._fit(train)
        model._parms["booster"] = "gblinear"
        return model

    def _fit_single(self, model, binned, y, w, offset, spec, dist, rng,
                    ntrees):
        if (self.params.get("booster") or "gbtree").lower() == "dart":
            return self._fit_single_dart(model, binned, y, w, offset, spec,
                                         dist, rng, ntrees)
        return super()._fit_single(model, binned, y, w, offset, spec, dist,
                                   rng, ntrees)

    def _fit_single_dart(self, model, binned, y, w, offset, spec, dist, rng,
                         ntrees):
        """booster='dart' (Rashmi & Gilad-Bachrach; XGBoost DartBooster,
        normalize_type='tree'): each iteration drops a random subset D of
        the existing trees, fits the new tree against the margin WITHOUT
        them, then rescales — new tree by eta/(|D|+1), dropped trees by
        |D|/(|D|+1). Per-tree contribution vectors stay on device so the
        drop/rescale is pure arithmetic, no re-traversal."""
        import jax
        import jax.numpy as jnp

        from h2o3_tpu.models.tree.compressed import CompressedForest
        from h2o3_tpu.models.tree.device_tree import (assemble_trees,
                                                      build_feat_masks,
                                                      grow_tree_device,
                                                      stash_packed)
        from h2o3_tpu.models.tree.shared_tree import _pre_fn

        if self._ckpt_start(ntrees):
            raise ValueError("booster='dart' does not support checkpoints")

        N = binned.shape[0]
        num = float(jnp.sum(dist.init_f_num(w, y, offset)))
        den = float(jnp.sum(dist.init_f_denom(w, y, offset)))
        init_f = float(dist.link(jnp.float32(num / max(den, 1e-12))))
        if dist.name in ("bernoulli", "quasibinomial"):
            init_f = float(np.clip(init_f, -19, 19))
        f = jnp.full(N, init_f, jnp.float32) + offset

        rate_drop = float(self.params.get("rate_drop", 0.0) or 0.0)
        skip_drop = float(self.params.get("skip_drop", 0.0) or 0.0)
        leaf_clip = self._leaf_clip()
        max_depth = int(self.params["max_depth"])
        min_rows = float(self.params["min_rows"])
        msi = float(self.params["min_split_improvement"])
        sample_rate = float(self.params.get("sample_rate", 1.0) or 1.0)
        pre = _pre_fn(dist, sample_rate < 1.0)
        post = _STEP_FNS_DART.get("post")
        if post is None:
            def _post(leaf4, row_leaf, gamma):
                contrib = jnp.where(row_leaf >= 0,
                                    gamma[jnp.maximum(row_leaf, 0)], 0.0)
                return contrib

            post = jax.jit(_post)
            _STEP_FNS_DART["post"] = post
        root_key = jax.random.PRNGKey(self._seed())

        # in-training validation margin mirrors the drop/rescale arithmetic
        # so stopping_rounds works on validation deviance like gbtree
        from h2o3_tpu.models.tree.device_tree import apply_packed

        vs = self._vstate
        maxB = int(spec.nbins.max())
        f_valid = (init_f + vs["offset"] if vs is not None else None)
        vcontribs = []
        stop_metric = []
        packs, leaf_vals, leaf_wys, contribs = [], [], [], []
        history = []
        for t in range(ntrees):
            # dropout set over EXISTING trees
            drop = []
            if t > 0 and rate_drop > 0 and rng.random() >= skip_drop:
                drop = [i for i in range(t) if rng.random() < rate_drop]
            f_used = f
            for d in drop:
                f_used = f_used - contribs[d]
            z, w_t, num_r, den_r, _m = pre(y, f_used, w, root_key,
                                           np.int32(t), sample_rate)
            feat_mask_fn = self._feat_mask_fn(rng, spec)
            masks = build_feat_masks(max_depth, feat_mask_fn,
                                     spec.F, int(spec.nbins.max()))
            packed, leaf4, row_leaf = grow_tree_device(
                binned, w_t, z, spec, max_depth=max_depth, min_rows=min_rows,
                min_split_improvement=msi, num=num_r, den=den_r,
                feat_masks=masks)
            gamma = self._leaf_gamma(leaf4[:, 2], leaf4[:, 3])
            gamma = jnp.clip(gamma, -leaf_clip, leaf_clip)
            k = len(drop)
            lr_t = float(self._tree_lr(t))     # honors learn_rate_annealing
            # XGBoost DartBooster normalize_type='tree': the new tree gets
            # lr/(k+lr) of a full step, dropped trees keep k/(k+lr)
            scale_new = lr_t / (k + lr_t) if k else lr_t
            factor_old = k / (k + lr_t) if k else 1.0
            gamma = (gamma * scale_new).astype(jnp.float32)
            contrib_new = post(leaf4, row_leaf, gamma)
            vcontrib_new = (apply_packed(vs["binned"], packed, gamma,
                                         max_depth, maxB)
                            if vs is not None else None)
            if k:
                f_new = f_used + contrib_new
                for d in drop:
                    contribs[d] = contribs[d] * factor_old
                    leaf_vals[d] = leaf_vals[d] * factor_old
                    f_new = f_new + contribs[d]
                f = f_new
                if vs is not None:
                    # rescale dropped terms, then rebuild the margin sum
                    for d in drop:
                        vcontribs[d] = vcontribs[d] * factor_old
                    f_valid = (init_f + vs["offset"] + sum(vcontribs)
                               + vcontrib_new)
            else:
                f = f + contrib_new
                if vs is not None:
                    f_valid = f_valid + vcontrib_new
            packs.append(stash_packed(packed, max_depth))
            leaf_vals.append(gamma)
            leaf_wys.append(leaf4[:, :2])
            contribs.append(contrib_new)
            if vs is not None:
                vcontribs.append(vcontrib_new)
            if self._should_score(t, ntrees):
                dev = float(jnp.sum(dist.deviance(w, y, f)) /
                            jnp.maximum(jnp.sum(w), 1e-12))
                entry = {"tree": t + 1, "training_deviance": dev,
                         "dropped": len(drop)}
                if f_valid is not None:
                    vdev = float(jnp.sum(dist.deviance(
                        vs["w"], vs["y"], f_valid)) /
                        jnp.maximum(jnp.sum(vs["w"]), 1e-12))
                    entry["validation_deviance"] = vdev
                    stop_metric.append(vdev)
                else:
                    stop_metric.append(dev)
                history.append(entry)
                if self._early_stop(stop_metric):
                    break
            if self._out_of_time():
                break
            if self.job:
                self.job.update(progress=(t + 1) / ntrees, msg=f"tree {t + 1}")

        tracing.advance("assemble", trees=len(packs))
        trees = assemble_trees(packs, leaf_vals, leaf_wys, spec, max_depth)
        varimp = {}
        for tree in trees:
            self._accumulate_varimp(tree, varimp, model)
        model._output.scoring_history = history
        self._finalize_varimp(model, varimp)
        forest = CompressedForest.from_host_trees(
            trees, spec, max_depth=max_depth, init_f=init_f, nclasses=1)
        return forest, f

    def _leaf_den_offset(self) -> float:
        # xgboost leaf weight = G / (H + λ): λ lands on the summed hessian
        return float(self.params.get("reg_lambda", 1.0) or 0.0)

    def _leaf_gamma(self, ln, ld):
        # xgboost L1: soft-threshold the gradient sum by reg_alpha before
        # dividing by (H + λ) — device math (training never syncs per tree)
        import jax.numpy as jnp

        alpha = float(self.params.get("reg_alpha", 0.0) or 0.0)
        num = (jnp.sign(ln) * jnp.maximum(jnp.abs(ln) - alpha, 0.0)
               if alpha > 0 else ln)
        den = ld + self._leaf_den_offset()
        return jnp.where(ld > 1e-12, num / jnp.maximum(den, 1e-12), 0.0)
