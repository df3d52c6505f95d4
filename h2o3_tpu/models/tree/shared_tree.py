"""SharedTree: the common driver for GBM / DRF / IsolationForest.

Reference: hex/tree/SharedTree.java:29 — Driver.computeImpl (:187) loops
scoreAndBuildTrees (:439): per tree-level a distributed histogram build
(ScoreBuildHistogram2) then host-side best-split decisions (DTree), with
early stopping via ScoreKeeper.

TPU-native design: one tree is ONE device program
(device_tree.grow_tree_device: histogram, split search, routing and the
GammaPass leaf sums, at any depth), with one jitted step before it
(gradients, row sampling) and one after (leaf Newton steps, margin
update). Row→leaf assignments stay on device for the whole tree; every
tree's tables are fetched once, after the last tree (deeper than 10, a
tree at a time: device_tree.stash_packed). Sampled-out rows carry w=0 in
the histogram but keep routing (OOB scoring reads their leaves for free).
IsolationForest alone keeps a host loop a level (isofor.py over
histogram.py): its splits are random and need no search.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from h2o3_tpu.core.frame import Frame
from h2o3_tpu.models.data_info import DataInfo
from h2o3_tpu.models.distribution import get_distribution, auto_distribution
from h2o3_tpu.models.model import Model, ModelCategory
from h2o3_tpu.models.model_builder import ModelBuilder, register
from h2o3_tpu.models.tree.binning import BinSpec
from h2o3_tpu.models.tree.compressed import CompressedForest
from h2o3_tpu.obs import tracing

# jitted per-tree glue, cached across train() calls — every eager jnp op in
# the boosting loop is a separate device dispatch; fusing the
# gradient/sampling (pre) and gamma/f-update (post) into one jit each cuts a
# tree's host-side round count from ~40 to 3
_STEP_FNS: Dict[tuple, object] = {}


def _pre_fn(dist, sample: bool):
    """(y, f, w, key, rate) -> (z, w_t, num, den)."""
    import jax

    k = ("pre", dist.name, getattr(dist, "tweedie_power", None),
         getattr(dist, "quantile_alpha", None), sample)
    fn = _STEP_FNS.get(k)
    if fn is None:
        def pre(y, f, w, key, t, rate):
            import jax.numpy as jnp

            z = dist.neg_half_gradient(y, f)
            if sample:
                mask = jax.random.uniform(jax.random.fold_in(key, t),
                                          y.shape) < rate
                w_t = jnp.where(mask, w, 0.0)
            else:
                mask = None
                w_t = w
            num = dist.gamma_num(w_t, y, z, f)
            den = dist.gamma_denom(w_t, y, z, f)
            return z, w_t, num, den, mask

        from h2o3_tpu.obs import compiles

        fn = compiles.ledgered_jit("tree", pre, program="tree_pre")
        _STEP_FNS[k] = fn
    return fn


def _post_fn(builder, clip: float):
    """(leaf4, row_leaf, f) + lr -> (gamma, f_new); gamma math comes from the
    builder's _leaf_gamma hook, traced once per (class, scalar-params)
    config. The cache key covers EVERY scalar/str param, so any override
    reading self.params gets the right values; overrides must not read
    non-param instance state (it is not part of the key)."""
    import jax

    cls = type(builder)
    sig = tuple(sorted((str(k), v) for k, v in builder.params.items()
                       if isinstance(v, (int, float, str, bool, type(None)))))
    k = ("post", cls.__name__, clip, sig)
    fn = _STEP_FNS.get(k)
    if fn is None:
        proto = cls.__new__(cls)
        proto.params = dict(builder.params)

        def post(leaf4, row_leaf, f, lr):
            import jax.numpy as jnp

            gamma = proto._leaf_gamma(leaf4[:, 2], leaf4[:, 3])
            gamma = jnp.clip(gamma, -clip, clip) * lr
            f_new = f + jnp.where(row_leaf >= 0,
                                  gamma[jnp.maximum(row_leaf, 0)], 0.0)
            return gamma.astype(jnp.float32), f_new

        from h2o3_tpu.obs import compiles

        fn = compiles.ledgered_jit("tree", post, program="tree_post")
        _STEP_FNS[k] = fn
    return fn


class SharedTreeModel(Model):
    """Trained forest; scoring bins the (adapted) frame with the training
    BinSpec then runs the lockstep traversal."""

    def __init__(self, parms=None):
        super().__init__(parms=parms)
        self.forest: Optional[CompressedForest] = None
        self.spec: Optional[BinSpec] = None
        self._distribution = None

    def _margin(self, frame: Frame):
        binned = self.spec.bin_columns(frame)
        return self.forest.predict_binned(binned)

    def predict_leaf_node_assignment(self, frame: Frame, type: str = "Path",
                                     key=None) -> Frame:
        """Per-tree leaf assignment (ModelBase.predict_leaf_node_assignment;
        hex/tree SharedTreeModel.scoreLeafNodeAssignment): 'Path' = the
        L/R root-to-leaf walk string, 'Node_ID' = the node index. One
        column per tree (T<k>.C<cls> for per-class forests)."""
        import numpy as np

        from h2o3_tpu.core.frame import Column, T_CAT

        if type not in ("Path", "Node_ID"):
            raise ValueError(f"leaf assignment type {type!r} "
                             "(Path or Node_ID)")
        adapted = self.adapt_test(frame)
        from h2o3_tpu import scoring

        if scoring.supports(self):
            # explainability fast path (ISSUE 13): the fused bucketed
            # bin+leaf program from the model's ScoringSession — compiled
            # once per row bucket (and persisted in the compile cache)
            # instead of one jit trace per request shape. Bitwise-equal
            # to the eager bin_columns + leaf_index pass below.
            leaf = scoring.session_for(self).leaf_matrix(adapted,
                                                         frame.nrows)
        else:
            binned = self.spec.bin_columns(adapted)
            leaf_dev = self.forest.leaf_index(binned)
            if not getattr(leaf_dev, "is_fully_addressable", True):
                # multi-process cloud: every process reaches this inside
                # its mirrored op (REST turn / follower replay), so the
                # allgather is in lockstep
                from jax.experimental import multihost_utils

                leaf_dev = multihost_utils.process_allgather(leaf_dev,
                                                             tiled=True)
            leaf = np.asarray(leaf_dev)[: frame.nrows]
        fo = self.forest
        tcls = np.asarray(fo.tree_class)
        per_class = fo.per_class_trees
        counters: dict = {}
        out = Frame(key=key)
        for t in range(fo.n_trees):
            if per_class:
                k = int(tcls[t])
                g = counters.get(k, 0)
                counters[k] = g + 1
                name = f"T{g + 1}.C{k + 1}"
            else:
                name = f"T{t + 1}"
            if type == "Node_ID":
                # int32 (T_INT) keeps ids exact — float64 would honor a
                # cluster bf16 opt-in and round ids above 256
                out.add(name, Column.from_numpy(
                    leaf[:, t].astype(np.int32)))
                continue
            # root-to-leaf L/R strings per node, derived once per tree
            feat = np.asarray(fo.feat[t])
            left = np.asarray(fo.left[t])
            right = np.asarray(fo.right[t])
            paths = [""] * feat.shape[0]

            def walk(node, prefix):
                paths[node] = prefix
                if feat[node] >= 0:
                    walk(int(left[node]), prefix + "L")
                    walk(int(right[node]), prefix + "R")

            walk(0, "")
            vals = np.asarray([paths[i] or "(root)" for i in leaf[:, t]],
                              object)
            out.add(name, Column.from_numpy(vals, ctype=T_CAT))
        return out

    def _predict_raw(self, frame: Frame):
        return self._margin_to_raw(self._margin(frame))

    def _margin_to_raw(self, f):
        """Margin(s) → raw prediction dict — split from _predict_raw so the
        serving fast path (scoring.py) can post-process margins computed by
        its fused bucketed program. Must stay pure margin math (no frame
        access): anything frame-dependent belongs in a _predict_raw
        override, which also opts the model OUT of the fast path."""
        import jax.numpy as jnp

        cat = self._output.model_category
        if cat == ModelCategory.Binomial:
            p = self._distribution.linkinv(f)
            return {"probs": jnp.stack([1 - p, p], axis=-1)}
        if cat == ModelCategory.Multinomial:
            import jax

            return {"probs": jax.nn.softmax(f, axis=-1)}
        if cat == ModelCategory.AnomalyDetection:
            return {"score": f}
        if self._distribution is not None:
            return {"value": self._distribution.linkinv(f)}
        return {"value": f}


class SharedTree(ModelBuilder):
    """Base builder: binning, sampling, tree loop, scoring history, early
    stopping, variable importances."""

    model_class = SharedTreeModel
    supports_checkpoint = True
    # crash-survivable builds: the fit loops persist durable per-tree
    # progress (margins, packed tables, RNG stream) and fast-forward from
    # it bitwise-identically (model_builder._tick_job_progress)
    supports_iteration_resume = True
    # GBM consumes the in-training validation state; DRF/IF override the fit
    # loops without reading it (DRF's stopping metric is OOB, reference
    # doOOBScoring), so they skip building it
    _intrain_valid = True

    @classmethod
    def default_params(cls):
        p = super().default_params()
        p.update({
            "ntrees": 50, "max_depth": 5, "min_rows": 10.0,
            "nbins": 20, "nbins_cats": 1024,
            "min_split_improvement": 1e-5,
            "sample_rate": 1.0, "col_sample_rate_per_tree": 1.0,
            "score_each_iteration": False, "score_tree_interval": 0,
            "calibrate_model": False, "calibration_frame": None,
            "calibration_method": "AUTO", "distribution": "AUTO",
            "tweedie_power": 1.5, "quantile_alpha": 0.5,
            "huber_alpha": 0.9,
        })
        return p

    # subclass hooks ------------------------------------------------------
    def _leaf_num_den(self, w, y, z, f, dist):
        """Device (num, den) rows for the leaf-value segment sum."""
        return dist.gamma_num(w, y, z, f), dist.gamma_denom(w, y, z, f)


    def _tree_lr(self, t: int) -> float:
        """Shrinkage applied to tree t's leaves (GBM: learn_rate with
        learn_rate_annealing^t; DRF/IF: 1)."""
        return 1.0

    def _leaf_clip(self) -> float:
        """Leaf-value bound: max_abs_leafnode_pred when the user set one,
        else a numeric-safety bound (GBM.java fitBestConstants clamps)."""
        clip = float(self.params.get("max_abs_leafnode_pred", 1e30) or 1e30)
        return clip if clip < 1e30 else 1e4

    def _leaf_den_offset(self) -> float:
        """Additive leaf-denominator regularizer (XGBoost's λ on the hessian
        sum); 0 for plain GBM/DRF."""
        return 0.0

    def _leaf_gamma(self, ln, ld):
        """Leaf Newton step from the (num, den) segment sums — DEVICE math
        (jnp), so training never syncs per tree; XGBoost overrides to apply
        its α soft-threshold."""
        import jax.numpy as jnp

        return jnp.where(ld > 1e-12,
                         ln / jnp.maximum(ld + self._leaf_den_offset(), 1e-12),
                         0.0)

    # append-only tree-progress persistence --------------------------------
    def _tree_progress_ref(self, packs, leaf_vals, leaf_wys) -> Dict:
        """Durable-progress state for the per-tree tables WITHOUT
        re-serializing the whole forest: entries grown since the last save
        are appended as one suffix chunk (parallel/ckpt.py, artifact
        packed-forest codec) and the state carries only the chunk paths —
        each checkpoint's tree cost is O(new trees), not O(forest).
        Called from inside a state_fn, i.e. only when a save is actually
        happening on the dispatching process."""
        from h2o3_tpu.parallel import ckpt

        saved = getattr(self, "_jp_entries", 0)
        chunks = list(getattr(self, "_jp_chunks", []))
        if len(packs) > saved:
            path = ckpt.append_job_tree_chunk(
                str(self._progress_job.key), len(chunks),
                packs[saved:], leaf_vals[saved:], leaf_wys[saved:])
            chunks.append(path)
            self._jp_chunks = chunks
            self._jp_entries = len(packs)
        return {"tree_chunks": chunks, "n_tree_entries": len(packs)}

    def _load_tree_progress(self, rs: Dict, vals_key: str = "leaf_vals"):
        """Re-hydrate (packs, leaf values, leaf w/y) from a resume state —
        chunked suffix files (current format) or the inline lists older
        progress files carry. Seeds the appender cursor so a resumed run
        keeps appending instead of rewriting history."""
        import jax.numpy as jnp

        if rs.get("tree_chunks") is not None:
            from h2o3_tpu.parallel import ckpt

            packs, lv, lw = ckpt.load_job_tree_chunks(rs["tree_chunks"])
            n = int(rs.get("n_tree_entries", len(packs)))
            if len(packs) != n:
                raise RuntimeError(
                    f"tree-progress chunks hold {len(packs)} trees but the "
                    f"state expects {n} — durable progress is torn")
            self._jp_chunks = list(rs["tree_chunks"])
            self._jp_entries = n
        else:
            packs, lv, lw = rs["packs"], rs[vals_key], rs["leaf_wys"]
        return ([np.asarray(p) for p in packs],
                [jnp.asarray(v) for v in lv],
                [jnp.asarray(w) for w in lw])

    # checkpoint helpers ---------------------------------------------------
    def _ckpt_start(self, ntrees: int, per_iter: int = 1) -> int:
        """Iterations the checkpoint forest already holds (0 when training
        fresh). ntrees is the TOTAL tree count and must exceed it
        (hex/util/CheckpointUtils.java enforces the same)."""
        prev = getattr(self, "_ckpt", None)
        if prev is None:
            return 0
        done = prev.forest.n_trees // per_iter
        if ntrees <= done:
            raise ValueError(
                f"checkpoint model already has {done} iterations; ntrees "
                f"({ntrees}) must be greater")
        return done

    def _ckpt_varimp0(self) -> Dict[str, float]:
        """Resume split-gain accumulation from the checkpoint model's raw
        (unnormalized) importances."""
        prev = getattr(self, "_ckpt", None)
        return dict(getattr(prev, "_varimp_raw", {}) or {}) if prev else {}

    # driver --------------------------------------------------------------
    def _fit(self, train: Frame) -> SharedTreeModel:
        import jax
        import jax.numpy as jnp

        model: SharedTreeModel = self.model_class(parms=dict(self.params))
        out = self._init_output(model, train)
        resp = self.params["response_column"]
        y_col = train.col(resp)
        nclasses = out.nclasses
        dist_name = (self.params.get("distribution") or "AUTO").lower()
        if dist_name == "auto":
            dist_name = auto_distribution(y_col.ctype, nclasses)
        multinomial = dist_name == "multinomial"
        dist = get_distribution(dist_name,
                                tweedie_power=float(self.params["tweedie_power"]),
                                quantile_alpha=float(self.params["quantile_alpha"]))
        model._distribution = dist

        # training continuation (hex/Model.java:365): reuse the checkpoint
        # model's BinSpec so continued trees bin identically, start margins
        # from its forest, and append the new trees to it
        prev = self._resolve_checkpoint()
        if prev is not None:
            if not isinstance(prev, SharedTreeModel) or prev.forest is None:
                raise ValueError("checkpoint model has no forest to continue")
            if prev._output.names != out.names \
                    or prev._output.domains != out.domains:
                raise ValueError(
                    "checkpoint: training frame columns/domains differ from "
                    f"the original run ({prev._output.names} vs {out.names})")
        # stage span ``bin``: the host waits in it for the quantile edges;
        # the bin matrix is only dispatched and drains into ``trees``
        nbins, nbins_cats = (int(self.params["nbins"]),
                             int(self.params["nbins_cats"]))
        with tracing.span("bin", rows=train.nrows) as bin_span:
            spec = prev.spec if prev is not None else BinSpec.build(
                train, out.names, nbins=nbins, nbins_cats=nbins_cats,
                seed=self._seed())
            binned = spec.bin_columns(train)
            bin_span.set(bin_dtype=str(binned.dtype),
                         max_bins=int(spec.nbins.max()))
        self._ckpt = prev
        model.spec = spec

        w_user = None
        if self.params.get("weights_column"):
            w_user = train.col(self.params["weights_column"]).data
        w = DataInfo.response_weight(y_col.data, w_user)
        y = DataInfo.clean_response(y_col.data).astype(jnp.float32)
        # per-row state is made from a row-sharded sibling (zeros_like keeps
        # its sharding): jnp.zeros(N) is a whole-length array on ONE device,
        # 320 MB at 80M rows, which the first jitted step then has to spread
        offset = jnp.zeros_like(y)
        if self.params.get("offset_column"):
            oc = train.col(self.params["offset_column"]).data
            offset = jnp.where(jnp.isnan(oc), 0.0, oc).astype(jnp.float32)

        # resumed runs seed the host RNG stream with (seed, trees_done) —
        # reusing the bare seed would replay the original run's bootstrap /
        # feature-mask draws and append byte-identical duplicate trees
        rng = (np.random.default_rng([self._seed(), prev.forest.n_trees])
               if prev is not None else np.random.default_rng(self._seed()))
        ntrees = int(self.params["ntrees"])
        self._train_frame_ref = train      # OOB metric routing (DRF)
        # in-training validation state for early stopping (ScoreKeeper stops
        # on the validation metric when a validation_frame is given)
        self._vstate = None
        valid = getattr(self, "_valid_frame_ref", None)
        # only pay for the per-tree validation traversal when intermediate
        # scores are observable (stopping or per-iteration scoring); the
        # final validation metrics come from _score_on's full predict anyway
        wants_scores = bool(self.params.get("stopping_rounds")
                            or self.params.get("score_each_iteration")
                            or self.params.get("score_tree_interval"))
        if valid is not None and self._intrain_valid and wants_scores \
                and resp in valid:
            va = model.adapt_test(valid)
            yv_col = model._adapt_response(valid.col(resp))
            wv_user = None
            if self.params.get("weights_column") and \
                    self.params["weights_column"] in valid:
                wv_user = valid.col(self.params["weights_column"]).data
            # validation state stays ON DEVICE: per-tree validation margins
            # update via the packed-tree traversal (device_tree.apply_packed)
            # with no host scans (round-2 weakness W3)
            binned_v = spec.bin_columns(va)
            y_v = DataInfo.clean_response(yv_col.data).astype(jnp.float32)
            off_v = jnp.zeros_like(y_v)
            ocn = self.params.get("offset_column")
            if ocn and ocn in valid:
                oc = valid.col(ocn).data
                off_v = jnp.where(jnp.isnan(oc), 0.0, oc).astype(jnp.float32)
            self._vstate = {
                "binned": binned_v,
                "y": y_v,
                "w": DataInfo.response_weight(yv_col.data, wv_user),
                "offset": off_v,
            }
        t0 = time.time()
        try:
            # stage span ``trees``; the fit loop moves it on to ``assemble``
            # (tracing.advance) once its last tree's metric has been read
            with tracing.span("trees", ntrees=ntrees, rows=train.nrows,
                              max_depth=int(self.params["max_depth"])):
                if multinomial:
                    forest, f = self._fit_multinomial(
                        model, binned, y, w, offset, spec, nclasses, rng,
                        ntrees)
                else:
                    forest, f = self._fit_single(model, binned, y, w, offset,
                                                 spec, dist, rng, ntrees)
        finally:
            self._vstate = None
            self._ckpt = None
        model.forest = forest
        model._output.run_time_ms = int((time.time() - t0) * 1000)
        return model

    # single-margin families (regression, bernoulli) ----------------------
    def _fit_single(self, model, binned, y, w, offset, spec, dist, rng, ntrees):
        """Device-resident boosting loop: ONE dispatch per tree (growth +
        leaf stats fused, device_tree.py), gamma/clip/f-update on device, and
        the per-tree split tables fetched in a single end-of-loop transfer —
        no per-tree host syncs.

        Any depth runs in this one-dispatch program: the dense-frontier
        grower (device_tree.py, round 4) renumbers live nodes per level, so
        depth-20 DRF no longer falls back to a per-level host loop."""
        import jax.numpy as jnp

        from h2o3_tpu.models.tree.device_tree import (apply_packed,
                                                      build_feat_masks,
                                                      grow_tree_device,
                                                      stash_packed)

        t_base = self._ckpt_start(ntrees)   # trees already in a user
        if t_base:                          # checkpoint model (concat below)
            # resume: margins restart from the checkpoint forest's predictions
            pf = self._ckpt.forest
            init_f = pf.init_f
            f = pf.predict_binned(binned) + offset
        else:
            # init f0: weighted argmin of deviance at constant margin
            num = float(jnp.sum(dist.init_f_num(w, y, offset)))
            den = float(jnp.sum(dist.init_f_denom(w, y, offset)))
            init_f = float(dist.link(jnp.float32(num / max(den, 1e-12))))
            if dist.name in ("bernoulli", "quasibinomial"):
                # only the log-odds prior needs clamping (GBM.java
                # getInitialValue); identity/log links keep large means intact
                init_f = float(np.clip(init_f, -19, 19))
            f = jnp.float32(init_f) + offset

        leaf_clip = self._leaf_clip()
        history = []
        max_depth = int(self.params["max_depth"])
        maxB = int(spec.nbins.max())
        min_rows = float(self.params["min_rows"])
        msi = float(self.params["min_split_improvement"])
        stop_metric: List[float] = []
        vs = self._vstate
        if vs is None:
            f_valid = None
        elif t_base:
            f_valid = self._ckpt.forest.predict_binned(vs["binned"]) + vs["offset"]
        else:
            f_valid = init_f + vs["offset"]
        sample_rate = float(self.params.get("sample_rate", 1.0) or 1.0)
        sampling = sample_rate < 1.0
        pre = _pre_fn(dist, sampling)
        post = _post_fn(self, leaf_clip)
        import jax

        root_key = jax.random.PRNGKey(self._seed())
        packs, leaf_vals, leaf_wys = [], [], []
        t_start = t_base
        rs = self._take_resume_state("tree_single")
        if rs is not None:
            # durable-progress fast-forward: restore the EXACT loop state
            # (margins, per-tree tables, host RNG stream) so the continued
            # run is bitwise-identical to an uninterrupted one
            t_start = int(rs["t_done"])
            init_f = float(rs["init_f"])
            f = jnp.asarray(rs["f"])
            if f_valid is not None and rs.get("f_valid") is not None:
                f_valid = jnp.asarray(rs["f_valid"])
            stop_metric = [float(v) for v in rs["stop_metric"]]
            history = [dict(h) for h in rs["history"]]
            packs, leaf_vals, leaf_wys = self._load_tree_progress(rs)
            if rs.get("rng_state") is not None:
                rng.bit_generator.state = rs["rng_state"]
        jp_every = self._job_ckpt_every()
        from h2o3_tpu.core.failure import faultpoint

        from h2o3_tpu.obs import metrics as obs_metrics

        for t in range(t_start, ntrees):
            faultpoint("tree.fit_tree")     # chaos hook (core/failure.py)
            z, w_t, num_r, den_r, _mask = pre(y, f, w, root_key,
                                              np.int32(t), sample_rate)
            feat_mask_fn = self._feat_mask_fn(rng, spec)
            masks = build_feat_masks(max_depth, feat_mask_fn, spec.F, maxB)
            packed, leaf4, row_leaf = grow_tree_device(
                binned, w_t, z, spec, max_depth=max_depth, min_rows=min_rows,
                min_split_improvement=msi, num=num_r, den=den_r,
                feat_masks=masks)
            gamma, f = post(leaf4, row_leaf, f, self._tree_lr(t))
            obs_metrics.inc("h2o3_tree_trees_built_total")
            packs.append(stash_packed(packed, max_depth))
            leaf_vals.append(gamma)
            leaf_wys.append(leaf4[:, :2])
            if f_valid is not None:
                f_valid = f_valid + apply_packed(vs["binned"], packed, gamma,
                                                 max_depth, maxB)
            if self._should_score(t, ntrees):
                dev = float(jnp.sum(dist.deviance(w, y, f)) /
                            jnp.maximum(jnp.sum(w), 1e-12))
                entry = {"tree": t + 1, "training_deviance": dev}
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
            if jp_every and (t + 1) % jp_every == 0:
                done = t + 1
                self._tick_job_progress(done, lambda: {
                    "phase": "tree_single", "t_done": done,
                    "init_f": float(init_f),
                    "f": np.asarray(f),
                    "f_valid": (None if f_valid is None
                                else np.asarray(f_valid)),
                    "stop_metric": list(stop_metric),
                    "history": [dict(h) for h in history],
                    **self._tree_progress_ref(packs, leaf_vals, leaf_wys),
                    "rng_state": rng.bit_generator.state})

        # ONE batched fetch for every tree's tables + leaf values
        from h2o3_tpu.models.tree.device_tree import assemble_trees

        tracing.advance("assemble", trees=len(packs))
        trees = assemble_trees(packs, leaf_vals, leaf_wys, spec, max_depth)
        varimp: Dict[str, float] = self._ckpt_varimp0()
        for tree in trees:
            self._accumulate_varimp(tree, varimp, model)
        model._output.scoring_history = history
        self._finalize_varimp(model, varimp)
        forest = CompressedForest.from_host_trees(
            trees, spec, max_depth=max_depth, init_f=init_f, nclasses=1)
        if t_base:
            forest = CompressedForest.concat(self._ckpt.forest, forest)
        return forest, f

    # multinomial: K trees per iteration ----------------------------------
    def _fit_multinomial(self, model, binned, y, w, offset, spec, K, rng, ntrees):
        import jax
        import jax.numpy as jnp

        from h2o3_tpu.models.tree.device_tree import (apply_packed,
                                                      build_feat_masks,
                                                      grow_tree_device,
                                                      stash_packed)

        N = binned.shape[0]
        yi = y.astype(jnp.int32)
        t_base = self._ckpt_start(ntrees, per_iter=K)
        vs = self._vstate
        if t_base:
            pf = self._ckpt.forest
            init = np.asarray(pf.init_class, np.float32)
            f = pf.predict_binned(binned).astype(jnp.float32)
            f_valid = (pf.predict_binned(vs["binned"]).astype(jnp.float32)
                       if vs is not None else None)
        else:
            # init: log class priors — explicit args, NOT a closure over
            # (yi, w): the cached wrapper would bake the first train's
            # arrays into every later K-class fit
            from h2o3_tpu.obs import compiles

            kprior = _STEP_FNS.get(("prior", K))
            if kprior is None:
                def prior(yi, w):
                    return jnp.zeros(K).at[yi].add(w, mode="drop")

                kprior = compiles.ledgered_jit("tree", prior,
                                               program="tree_prior")
                _STEP_FNS[("prior", K)] = kprior
            pri = np.asarray(kprior(yi, jnp.asarray(w, jnp.float32)))
            pri = np.maximum(pri / max(pri.sum(), 1e-12), 1e-9)
            init = np.log(pri).astype(np.float32)
            # (rows, K) of the priors, row-sharded like y (see _fit)
            f = jnp.zeros_like(y)[:, None] + jnp.asarray(init)
            f_valid = (jnp.zeros_like(vs["y"])[:, None] + jnp.asarray(init)
                       if vs is not None else None)

        leaf_clip = self._leaf_clip()
        tree_class, history = [], []
        max_depth = int(self.params["max_depth"])
        maxB = int(spec.nbins.max())
        min_rows = float(self.params["min_rows"])
        msi = float(self.params["min_split_improvement"])
        stop_metric: List[float] = []
        onehot = jax.nn.one_hot(yi, K, dtype=jnp.float32)
        # jitted per-class glue (same dispatch-latency motivation as _pre_fn)
        kpre = _STEP_FNS.get(("premk", K))
        if kpre is None:
            def premk(f, onehot, w, key, t, rate, k):
                P = jax.nn.softmax(f, axis=-1)
                z = onehot[:, k] - P[:, k]
                w_t = jnp.where(
                    jax.random.uniform(jax.random.fold_in(key, t),
                                       z.shape) < rate, w, 0.0)
                az = jnp.abs(z)
                return z, w_t, w_t * z, w_t * az * (1 - az)

            from h2o3_tpu.obs import compiles

            kpre = compiles.ledgered_jit("tree", premk, program="tree_premk")
            _STEP_FNS[("premk", K)] = kpre
        kpost = _STEP_FNS.get(("postmk", K, leaf_clip))
        if kpost is None:
            def postmk(leaf4, row_leaf, f, lr, k):
                ln, ld = leaf4[:, 2], leaf4[:, 3]
                gamma = jnp.where(ld > 1e-12,
                                  (K - 1) / K * ln / jnp.maximum(ld, 1e-12),
                                  0.0)
                gamma = jnp.clip(gamma, -leaf_clip, leaf_clip) * lr
                upd = jnp.where(row_leaf >= 0,
                                gamma[jnp.maximum(row_leaf, 0)], 0.0)
                return gamma.astype(jnp.float32), f.at[:, k].add(upd)

            from h2o3_tpu.obs import compiles

            kpost = compiles.ledgered_jit("tree", postmk,
                                          program="tree_postmk")
            _STEP_FNS[("postmk", K, leaf_clip)] = kpost

        root_key = jax.random.PRNGKey(self._seed())
        sample_rate = float(self.params.get("sample_rate", 1.0) or 1.0)
        packs, leaf_vals, leaf_wys = [], [], []
        t_start = t_base
        rs = self._take_resume_state("tree_multi")
        if rs is not None:
            # durable-progress fast-forward (same contract as tree_single)
            t_start = int(rs["t_done"])
            init = np.asarray(rs["init"], np.float32)
            f = jnp.asarray(rs["f"])
            if f_valid is not None and rs.get("f_valid") is not None:
                f_valid = jnp.asarray(rs["f_valid"])
            stop_metric = [float(v) for v in rs["stop_metric"]]
            history = [dict(h) for h in rs["history"]]
            tree_class = list(rs["tree_class"])
            packs, leaf_vals, leaf_wys = self._load_tree_progress(rs)
            if rs.get("rng_state") is not None:
                rng.bit_generator.state = rs["rng_state"]
        jp_every = self._job_ckpt_every()
        from h2o3_tpu.obs import metrics as obs_metrics

        for t in range(t_start, ntrees):
            feat_mask_fn = self._feat_mask_fn(rng, spec)
            masks = build_feat_masks(max_depth, feat_mask_fn, spec.F, maxB)
            for k in range(K):
                # multinomial leaf gamma (GBM.java fitBestConstants, K-class):
                # (K-1)/K * Σz / Σ|z|(1-|z|)
                z, w_t, num_r, den_r = kpre(f, onehot, w, root_key,
                                            np.int32(t), sample_rate,
                                            np.int32(k))
                packed, leaf4, row_leaf = grow_tree_device(
                    binned, w_t, z, spec, max_depth=max_depth,
                    min_rows=min_rows, min_split_improvement=msi,
                    num=num_r, den=den_r, feat_masks=masks)
                gamma, f = kpost(leaf4, row_leaf, f,
                                 np.float32(self._tree_lr(t)), np.int32(k))
                packs.append(stash_packed(packed, max_depth))
                leaf_vals.append(gamma)
                leaf_wys.append(leaf4[:, :2])
                tree_class.append(k)
                obs_metrics.inc("h2o3_tree_trees_built_total")
                if f_valid is not None:
                    f_valid = f_valid.at[:, k].add(
                        apply_packed(vs["binned"], packed, gamma,
                                     max_depth, maxB))
            if self._should_score(t, ntrees):
                ll = float(jnp.sum(-w * jnp.log(jnp.maximum(
                    jax.nn.softmax(f, axis=-1)[jnp.arange(N), yi], 1e-15))) /
                    jnp.maximum(jnp.sum(w), 1e-12))
                entry = {"tree": t + 1, "training_logloss": ll}
                if f_valid is not None:
                    pv = jax.nn.softmax(f_valid, axis=-1)
                    yv = jnp.maximum(vs["y"].astype(jnp.int32), 0)
                    vll = float(jnp.sum(-vs["w"] * jnp.log(jnp.maximum(
                        pv[jnp.arange(pv.shape[0]), yv], 1e-15))) /
                        jnp.maximum(jnp.sum(vs["w"]), 1e-12))
                    entry["validation_logloss"] = vll
                    stop_metric.append(vll)
                else:
                    stop_metric.append(ll)
                history.append(entry)
                if self._early_stop(stop_metric):
                    break
            if self._out_of_time():
                break
            if self.job:
                self.job.update(progress=(t + 1) / ntrees, msg=f"iter {t + 1}")
            if jp_every and (t + 1) % jp_every == 0:
                done = t + 1
                self._tick_job_progress(done, lambda: {
                    "phase": "tree_multi", "t_done": done,
                    "init": np.asarray(init),
                    "f": np.asarray(f),
                    "f_valid": (None if f_valid is None
                                else np.asarray(f_valid)),
                    "stop_metric": list(stop_metric),
                    "history": [dict(h) for h in history],
                    "tree_class": list(tree_class),
                    **self._tree_progress_ref(packs, leaf_vals, leaf_wys),
                    "rng_state": rng.bit_generator.state})

        from h2o3_tpu.models.tree.device_tree import assemble_trees

        tracing.advance("assemble", trees=len(packs))
        trees = assemble_trees(packs, leaf_vals, leaf_wys, spec, max_depth)
        varimp: Dict[str, float] = self._ckpt_varimp0()
        for tree in trees:
            self._accumulate_varimp(tree, varimp, model)
        model._output.scoring_history = history
        self._finalize_varimp(model, varimp)
        forest = CompressedForest.from_host_trees(
            trees, spec, tree_class=tree_class, max_depth=max_depth,
            init_f=0.0, nclasses=K)
        forest.init_class = init          # added per-class at scoring
        if t_base:
            forest = CompressedForest.concat(self._ckpt.forest, forest)
        return forest, f


    # sampling ------------------------------------------------------------
    def _sample_rows(self, rng, N, w):
        import jax.numpy as jnp

        rate = float(self.params.get("sample_rate", 1.0))
        if rate >= 1.0:
            return None, w
        mask = jnp.asarray(rng.random(N) < rate)
        return mask, jnp.where(mask, w, 0.0)

    def _feat_mask_fn(self, rng, spec):
        """Combine per-tree column sampling (col_sample_rate_per_tree) with
        per-node sampling (col_sample_rate — GBM.java's per-split rate)."""
        tree_rate = float(self.params.get("col_sample_rate_per_tree", 1.0))
        node_rate = float(self.params.get("col_sample_rate", 1.0))
        if tree_rate >= 1.0 and node_rate >= 1.0:
            return None
        keep = rng.random(spec.F) < tree_rate if tree_rate < 1.0 \
            else np.ones(spec.F, bool)
        if not keep.any():
            keep[rng.integers(spec.F)] = True

        def fn(S):
            mask = np.broadcast_to(keep, (S, spec.F)).copy()
            if node_rate < 1.0:
                mask &= rng.random((S, spec.F)) < node_rate
                for s in np.nonzero(~mask.any(axis=1))[0]:
                    mask[s, rng.choice(np.nonzero(keep)[0])] = True
            return mask

        return fn

    # scoring cadence / early stop ----------------------------------------
    def _should_score(self, t, ntrees):
        if t == ntrees - 1 or self.params.get("score_each_iteration"):
            return True
        interval = int(self.params.get("score_tree_interval") or 0)
        if interval > 0:
            return (t + 1) % interval == 0
        return bool(self.params.get("stopping_rounds"))

    def _early_stop(self, series: List[float]) -> bool:
        """ScoreKeeper.stopEarly: moving-average of the last k scores must
        improve on the previous k by stopping_tolerance (relative)."""
        k = int(self.params.get("stopping_rounds") or 0)
        if k <= 0 or len(series) < 2 * k:
            return False
        tol = float(self.params.get("stopping_tolerance") or 1e-3)
        recent = np.mean(series[-k:])
        prev = np.mean(series[-2 * k:-k])
        return recent >= prev * (1 - tol)

    # varimp ---------------------------------------------------------------
    def _accumulate_varimp(self, tree: HostTree, varimp: Dict[str, float], model):
        names = model._output.names
        for n in tree.nodes:
            if n.split is not None:
                nm = names[n.split.feat]
                varimp[nm] = varimp.get(nm, 0.0) + max(n.split.gain, 0.0)

    def _finalize_varimp(self, model, varimp: Dict[str, float]):
        model._varimp_raw = dict(varimp)    # checkpoint continuation source
        if varimp:
            top = max(varimp.values()) or 1.0
            model._output.variable_importances = {
                k: v / top for k, v in sorted(varimp.items(),
                                              key=lambda kv: -kv[1])}
