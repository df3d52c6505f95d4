"""DRF — distributed random forest.

Reference: hex/tree/drf/DRF.java — SharedTree with per-tree row
subsampling (sample_rate 0.632), per-node feature subsampling (mtries),
leaf = node mean, ensemble = average over trees, OOB scoring
(doOOBScoring), binomial_double_trees (one tree per class).

TPU-native: trees are grown on the raw response (no boosting); sampled-out
rows keep routing with w=0 so their leaf assignments give OOB predictions
with no extra traversal. Averaging happens by scaling each tree's leaf
values by 1/ntrees at compression time, so scoring reuses the same summed
traversal as GBM. Training metrics are OUT-OF-BAG, like the reference.
"""

from __future__ import annotations

import numpy as np

from h2o3_tpu.models.model import ModelCategory
from h2o3_tpu.models.model_builder import register
from h2o3_tpu.models.tree.compressed import CompressedForest
from h2o3_tpu.models.tree.shared_tree import SharedTree, SharedTreeModel
from h2o3_tpu.obs import tracing


_DRF_STEPS = {}


def _drf_step_fns(sampling: bool):
    """Jitted bagging pre (sample mask) + post (leaf means and OOB
    accumulation) — one dispatch each per tree instead of ~10 eager
    ops."""
    key = ("drf", sampling)
    fns = _DRF_STEPS.get(key)
    if fns is None:
        import jax
        import jax.numpy as jnp

        def pre(w, rkey, t, rate):
            mask = jax.random.uniform(jax.random.fold_in(rkey, t),
                                      w.shape) < rate
            return mask, jnp.where(mask, w, 0.0)

        def post(leaf4, row_leaf, mask, w, oob_sum, oob_cnt):
            ln, ld = leaf4[:, 2], leaf4[:, 3]
            mean = jnp.where(ld > 1e-12, ln / jnp.maximum(ld, 1e-12), 0.0)
            pred_t = jnp.where(row_leaf >= 0,
                               mean[jnp.maximum(row_leaf, 0)], 0.0)
            oob = (~mask) & (w > 0)
            oob_sum = oob_sum + jnp.where(oob, pred_t, 0.0)
            oob_cnt = oob_cnt + oob.astype(jnp.float32)
            return mean.astype(jnp.float32), oob_sum, oob_cnt

        from h2o3_tpu.obs import compiles

        fns = (compiles.ledgered_jit("tree", pre, program="drf_pre"),
               compiles.ledgered_jit("tree", post, program="drf_post"))
        _DRF_STEPS[key] = fns
    return fns


def _node_feat_mask_fn(rng, F: int, mtries: int):
    """Fresh random mtries-subset of features PER NODE (DTree semantics).
    Vectorized: one rank-of-randoms draw per level, not a Python loop of
    rng.choice per node."""

    def fn(S):
        r = rng.random((S, F))
        rank = np.argsort(np.argsort(r, axis=1), axis=1)
        return rank < mtries

    return fn


class DRFModel(SharedTreeModel):
    algo_name = "drf"

    def _margin_to_raw(self, f):
        # f = mean leaf response across trees; _predict_raw stays the
        # inherited margin→raw pipeline so DRF rides the serving fast path
        import jax.numpy as jnp

        cat = self._output.model_category
        if cat == ModelCategory.Binomial:
            if f.ndim == 2:          # binomial_double_trees: per-class votes
                p = jnp.clip(f, 0.0, 1.0)
                p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-12)
                return {"probs": p}
            p = jnp.clip(f, 0.0, 1.0)
            return {"probs": jnp.stack([1 - p, p], axis=-1)}
        if cat == ModelCategory.Multinomial:
            p = jnp.clip(f, 0.0, 1.0)
            p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-12)
            return {"probs": p}
        return {"value": f}


@register
class DRF(SharedTree):
    algo_name = "drf"
    model_class = DRFModel
    # validation-frame stopping supported in _fit_single (reference
    # ScoreKeeper prefers validation metrics over OOB when a frame is given)
    _intrain_valid = True

    @classmethod
    def default_params(cls):
        p = super().default_params()
        p.update({
            "ntrees": 50, "max_depth": 20, "min_rows": 1.0,
            "sample_rate": 0.632, "mtries": -1,
            "binomial_double_trees": False,
        })
        return p

    def _mtries(self, F: int, classification: bool) -> int:
        m = int(self.params.get("mtries", -1) or -1)
        if m > 0:
            return min(m, F)
        # DRF.java defaults: sqrt(p) classification, p/3 regression
        return max(1, int(np.sqrt(F)) if classification else F // 3)

    def _score_on(self, model, frame):
        """Training metrics are OOB (DRF.java doOOBScoring): when scoring the
        training frame right after fit, use the accumulated OOB predictions;
        rows that were never out-of-bag are weight-0 excluded."""
        oob = getattr(self, "_oob_raw", None)
        if oob is not None and frame is getattr(self, "_train_frame_ref", None):
            raw, mask = oob
            self._oob_raw = None      # single-use; frees the (N,) device bufs
            return model._make_metrics(frame, raw, extra_weight=mask)
        return super()._score_on(model, frame)

    def _fit_single(self, model, binned, y, w, offset, spec, dist, rng, ntrees):
        """Bagged trees on the raw response: leaf = weighted mean of y.
        Device-resident like SharedTree._fit_single: one dispatch per tree,
        OOB/validation margins on device, single end-of-loop fetch."""
        import jax.numpy as jnp

        from h2o3_tpu.models.tree.device_tree import (apply_packed,
                                                      build_feat_masks,
                                                      grow_tree_device,
                                                      stash_packed)

        classification = model._output.model_category == ModelCategory.Binomial
        if classification and self.params.get("binomial_double_trees"):
            return self._fit_multinomial(model, binned, y, w, offset, spec,
                                         2, rng, ntrees)
        mtries = self._mtries(spec.F, classification)
        feat_mask_fn = _node_feat_mask_fn(rng, spec.F, mtries)

        max_depth = int(self.params["max_depth"])
        maxB = int(spec.nbins.max())
        min_rows = float(self.params["min_rows"])
        msi = float(self.params["min_split_improvement"])
        history = []
        stop_metric = []
        vs = self._vstate
        # checkpoint resume: prev forest leaves are stored pre-divided by its
        # tree count, so its traversal yields the MEAN — times t_start gives
        # the running validation SUM. OOB accumulators restart at zero (the
        # per-tree bagging masks are not part of the model artifact), so
        # post-resume OOB training metrics cover the NEW trees only.
        t_base = self._ckpt_start(ntrees)
        if vs is None:
            v_sum = None
        elif t_base:
            v_sum = (self._ckpt.forest.predict_binned(vs["binned"])
                     .astype(jnp.float32) * t_base)
        else:
            v_sum = jnp.zeros_like(vs["y"])
        # OOB accumulation: sum of oob predictions and counts per row,
        # row-sharded like y (shared_tree._fit)
        oob_sum = jnp.zeros_like(y)
        oob_cnt = jnp.zeros_like(y)
        sample_rate = float(self.params.get("sample_rate", 0.632) or 1.0)
        sampling = sample_rate < 1.0
        pre, post = _drf_step_fns(sampling)
        import jax

        root_key = jax.random.PRNGKey(self._seed())
        packs, leaf_means, leaf_wys = [], [], []
        mask = None
        t_start = t_base
        rs = self._take_resume_state("drf_single")
        if rs is not None:
            # durable-progress fast-forward: exact loop state incl. the OOB
            # accumulators and the host RNG stream feeding the per-node
            # mtries masks — the continued run is bitwise-identical
            t_start = int(rs["t_done"])
            oob_sum = jnp.asarray(rs["oob_sum"])
            oob_cnt = jnp.asarray(rs["oob_cnt"])
            if v_sum is not None and rs.get("v_sum") is not None:
                v_sum = jnp.asarray(rs["v_sum"])
            stop_metric = [v for v in rs["stop_metric"]]
            history = [dict(h) for h in rs["history"]]
            packs, leaf_means, leaf_wys = self._load_tree_progress(
                rs, vals_key="leaf_means")
            if rs.get("rng_state") is not None:
                rng.bit_generator.state = rs["rng_state"]
        jp_every = self._job_ckpt_every()
        for t in range(t_start, ntrees):
            mask, w_t = pre(w, root_key, np.int32(t), sample_rate) \
                if sampling else (None, w)
            masks = build_feat_masks(max_depth, feat_mask_fn, spec.F, maxB)
            packed, leaf4, row_leaf = grow_tree_device(
                binned, w_t, y, spec, max_depth=max_depth, min_rows=min_rows,
                min_split_improvement=msi, feat_masks=masks)
            if mask is not None:
                mean, oob_sum, oob_cnt = post(leaf4, row_leaf, mask, w,
                                              oob_sum, oob_cnt)
            else:
                ln, ld = leaf4[:, 2], leaf4[:, 3]  # defaults: (w·y, w) sums
                mean = jnp.where(ld > 1e-12, ln / jnp.maximum(ld, 1e-12), 0.0)
            packs.append(stash_packed(packed, max_depth))
            leaf_means.append(mean)
            leaf_wys.append(leaf4[:, :2])
            if v_sum is not None:
                v_sum = v_sum + apply_packed(vs["binned"], packed, mean,
                                             max_depth, maxB)
            if (mask is not None or v_sum is not None) \
                    and self._should_score(t, ntrees):
                entry = {"tree": t + 1}
                mse = None
                if mask is not None:
                    # running OOB squared error (DRF.java scores OOB each interval)
                    fcur = jnp.where(oob_cnt > 0, oob_sum / jnp.maximum(oob_cnt, 1.0), 0.0)
                    wm = w * (oob_cnt > 0)
                    mse = float(jnp.sum(wm * (y - fcur) ** 2) /
                                jnp.maximum(jnp.sum(wm), 1e-12))
                    entry["training_rmse"] = float(np.sqrt(mse))
                if v_sum is not None:
                    fv = v_sum / (t + 1)
                    if classification:
                        fv = jnp.clip(fv, 0.0, 1.0)
                    vmse = float(jnp.sum(vs["w"] * (vs["y"] - fv) ** 2) /
                                 jnp.maximum(jnp.sum(vs["w"]), 1e-12))
                    entry["validation_rmse"] = float(np.sqrt(vmse))
                    stop_metric.append(vmse)
                else:
                    stop_metric.append(mse)
                history.append(entry)
                if self._early_stop(stop_metric):
                    break
            if self._out_of_time():
                break
            if self.job:
                self.job.update(progress=(t + 1) / ntrees, msg=f"tree {t + 1}")
            if jp_every and (t + 1) % jp_every == 0:
                done = t + 1
                self._tick_job_progress(done, lambda: {
                    "phase": "drf_single", "t_done": done,
                    "oob_sum": np.asarray(oob_sum),
                    "oob_cnt": np.asarray(oob_cnt),
                    "v_sum": None if v_sum is None else np.asarray(v_sum),
                    "stop_metric": list(stop_metric),
                    "history": [dict(h) for h in history],
                    **self._tree_progress_ref(packs, leaf_means, leaf_wys),
                    "rng_state": rng.bit_generator.state})

        # one batched fetch; scale leaves by the ACTUAL tree count (early
        # stopping may truncate) so the summed traversal averages correctly
        from h2o3_tpu.models.tree.device_tree import assemble_trees

        tracing.advance("assemble", trees=len(packs))
        total = t_base + len(packs)
        trees = assemble_trees(packs, leaf_means, leaf_wys, spec, max_depth,
                               scale=1.0 / total)
        varimp = self._ckpt_varimp0()
        for tree in trees:
            self._accumulate_varimp(tree, varimp, model)
        model._output.scoring_history = history
        self._finalize_varimp(model, varimp)
        forest = CompressedForest.from_host_trees(
            trees, spec, max_depth=max_depth, init_f=0.0, nclasses=1)
        if t_base:
            # rescale: prev leaves are /t_base, target is /total
            forest = CompressedForest.concat(self._ckpt.forest, forest,
                                             scale_a=t_base / total)
        f = jnp.where(oob_cnt > 0, oob_sum / jnp.maximum(oob_cnt, 1.0), 0.0)
        self._oob_raw = None
        if float(jnp.max(oob_cnt)) > 0:
            oob_mask = (oob_cnt > 0).astype(jnp.float32)
            if classification:
                p = jnp.clip(f, 0.0, 1.0)
                self._oob_raw = ({"probs": jnp.stack([1 - p, p], axis=-1)}, oob_mask)
            else:
                self._oob_raw = ({"value": f}, oob_mask)
        return forest, f

    def _fit_multinomial(self, model, binned, y, w, offset, spec, K, rng, ntrees):
        """One tree per class per iteration voting class indicator means."""
        import jax
        import jax.numpy as jnp

        from h2o3_tpu.models.tree.device_tree import (build_feat_masks,
                                                      grow_tree_device,
                                                      stash_packed)

        N = binned.shape[0]
        yi = y.astype(jnp.int32)
        onehot = jax.nn.one_hot(yi, K, dtype=jnp.float32)
        mtries = self._mtries(spec.F, True)
        feat_mask_fn = _node_feat_mask_fn(rng, spec.F, mtries)

        max_depth = int(self.params["max_depth"])
        maxB = int(spec.nbins.max())
        min_rows = float(self.params["min_rows"])
        msi = float(self.params["min_split_improvement"])
        tree_class = []
        t_base = self._ckpt_start(ntrees, per_iter=K)
        oob_sum = jnp.zeros_like(onehot)
        oob_cnt = jnp.zeros_like(onehot[:, 0])
        packs, leaf_means, leaf_wys = [], [], []
        t_start = t_base
        rs = self._take_resume_state("drf_multi")
        if rs is not None:
            # durable-progress fast-forward (same contract as drf_single)
            t_start = int(rs["t_done"])
            oob_sum = jnp.asarray(rs["oob_sum"])
            oob_cnt = jnp.asarray(rs["oob_cnt"])
            tree_class = list(rs["tree_class"])
            packs, leaf_means, leaf_wys = self._load_tree_progress(
                rs, vals_key="leaf_means")
            if rs.get("rng_state") is not None:
                rng.bit_generator.state = rs["rng_state"]
        jp_every = self._job_ckpt_every()
        for t in range(t_start, ntrees):
            mask, w_t = self._sample_rows(rng, N, w)
            for k in range(K):
                masks = build_feat_masks(max_depth, feat_mask_fn,
                                         spec.F, maxB)
                packed, leaf4, row_leaf = grow_tree_device(
                    binned, w_t, onehot[:, k], spec, max_depth=max_depth,
                    min_rows=min_rows, min_split_improvement=msi,
                    feat_masks=masks)
                mean = jnp.where(leaf4[:, 3] > 1e-12,
                                 leaf4[:, 2] / jnp.maximum(leaf4[:, 3], 1e-12),
                                 0.0)
                packs.append(stash_packed(packed, max_depth))
                leaf_means.append(mean.astype(jnp.float32))
                leaf_wys.append(leaf4[:, :2])
                tree_class.append(k)
                if mask is not None:
                    pred_t = jnp.where(row_leaf >= 0,
                                       mean[jnp.maximum(row_leaf, 0)], 0.0)
                    oob = (~mask) & (w > 0)
                    oob_sum = oob_sum.at[:, k].add(jnp.where(oob, pred_t, 0.0))
            if mask is not None:
                oob_cnt = oob_cnt + ((~mask) & (w > 0)).astype(jnp.float32)
            if self._out_of_time():
                break
            if self.job:
                self.job.update(progress=(t + 1) / ntrees, msg=f"iter {t + 1}")
            if jp_every and (t + 1) % jp_every == 0:
                done = t + 1
                self._tick_job_progress(done, lambda: {
                    "phase": "drf_multi", "t_done": done,
                    "oob_sum": np.asarray(oob_sum),
                    "oob_cnt": np.asarray(oob_cnt),
                    "tree_class": list(tree_class),
                    **self._tree_progress_ref(packs, leaf_means, leaf_wys),
                    "rng_state": rng.bit_generator.state})
        from h2o3_tpu.models.tree.device_tree import assemble_trees

        tracing.advance("assemble", trees=len(packs))
        total = t_base + len(packs) // K
        trees = assemble_trees(packs, leaf_means, leaf_wys, spec, max_depth,
                               scale=1.0 / total)
        varimp = self._ckpt_varimp0()
        for tree in trees:
            self._accumulate_varimp(tree, varimp, model)
        self._finalize_varimp(model, varimp)
        forest = CompressedForest.from_host_trees(
            trees, spec, tree_class=tree_class, max_depth=max_depth,
            nclasses=K)
        if t_base:
            forest = CompressedForest.concat(self._ckpt.forest, forest,
                                             scale_a=t_base / total)
        self._oob_raw = None
        if float(jnp.max(oob_cnt)) > 0:
            p = jnp.clip(oob_sum / jnp.maximum(oob_cnt, 1.0)[:, None], 0.0, 1.0)
            p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-12)
            self._oob_raw = ({"probs": p}, (oob_cnt > 0).astype(jnp.float32))
        return forest, None

