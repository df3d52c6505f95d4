"""DeepLearning — multilayer perceptron with JAX autodiff.

Reference: hex/deeplearning/ — hand-coded fprop/bprop per layer
(Neurons.java:184-229; Tanh :633, Maxout :684, Rectifier, dropout variants),
ADADELTA adaptive rate (DeepLearningModel.java), momentum ramp, L1/L2,
input/hidden dropout, autoencoder mode, async per-node model averaging
(DeepLearningTask.java:19,180 — reduce = weighted average of replicas).

TPU-native design: Neurons.fprop/bprop collapse into one jitted
loss-and-grad over the whole minibatch (jax.grad; the MXU eats the batched
matmuls). Training is data-parallel SYNCHRONOUS SGD: the batch is gathered
from the row-sharded design matrix and the gradient all-reduce is inserted
by the SPMD partitioner — equivalent to the reference's model averaging with
averaging period = 1 batch, but deterministic. An entire epoch of steps runs
inside a single lax.scan, so host↔device traffic is one call per epoch.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np

from h2o3_tpu.compat import shard_map as _compat_shard_map
from h2o3_tpu.core.frame import Column, Frame, T_NUM
from h2o3_tpu.models.data_info import DataInfo
from h2o3_tpu.models.model import Model, ModelCategory
from h2o3_tpu.models.model_builder import ModelBuilder, register

ACTIVATIONS = ("tanh", "tanhwithdropout", "rectifier", "rectifierwithdropout",
               "maxout", "maxoutwithdropout")


def _activation_fn(name: str):
    import jax
    import jax.numpy as jnp

    base = name.replace("withdropout", "")
    if base == "tanh":
        return jnp.tanh
    if base == "rectifier":
        return jax.nn.relu
    if base == "maxout":
        # Maxout pairs (Neurons.java:684): units are max over 2 linear pieces;
        # we model it as max(x, 0.5x) — a cheap 2-piece approximation that
        # keeps the layer widths as declared (full maxout doubles weights)
        return lambda x: jnp.maximum(x, 0.5 * x)
    raise ValueError(f"unknown activation {name!r}")


def _forward(params, X, activation, dropout_key=None, input_dropout=0.0,
             hidden_dropout=None, train=False):
    """MLP forward. params = [(W,b), ...]; returns last-layer linear output."""
    import jax
    import jax.numpy as jnp

    act = _activation_fn(activation)
    use_dropout = train and dropout_key is not None
    h = X
    if use_dropout and input_dropout > 0:
        dropout_key, sub = jax.random.split(dropout_key)
        keep = jax.random.bernoulli(sub, 1.0 - input_dropout, h.shape)
        h = jnp.where(keep, h / (1.0 - input_dropout), 0.0)
    n_hidden = len(params) - 1
    for li, (W, b) in enumerate(params[:-1]):
        h = act(h @ W + b)
        if use_dropout and hidden_dropout is not None:
            rate = hidden_dropout[li] if li < len(hidden_dropout) else 0.0
            if rate > 0:
                dropout_key, sub = jax.random.split(dropout_key)
                keep = jax.random.bernoulli(sub, 1.0 - rate, h.shape)
                h = jnp.where(keep, h / (1.0 - rate), 0.0)
    W, b = params[-1]
    return h @ W + b


# rows per block of a whole-frame pass: a block's (rows, hidden) activations
# are tens of MB, where a whole 8M-row frame's are 6 GB a layer
_ROW_BLOCK = 1 << 16


def _blocked_rows(fn):
    """jit of a row-local ``fn(consts, *blocks) -> per-row outputs`` run
    over whole columns: under shard_map each device walks ITS row shard in
    blocks of _ROW_BLOCK rows, so the (rows, hidden) activations of a whole
    frame never exist at once (found on the chip: the eager full-frame
    loss pass asked for 5.96 GB at 8M rows and exhausted HBM). `consts`
    (weights) ride as replicated arguments, not closure constants, so one
    compile serves every epoch. Call as ``run(consts, cols_tuple)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.core.runtime import cluster

    def local(consts, cols):
        n = cols[0].shape[0]
        blk = min(_ROW_BLOCK, n)
        nfull = n // blk
        out = jax.lax.map(
            lambda block: fn(consts, *block),
            tuple(c[: nfull * blk].reshape((nfull, blk) + c.shape[1:])
                  for c in cols))
        out = jax.tree.map(
            lambda a: a.reshape((nfull * blk,) + a.shape[2:]), out)
        if n % blk:
            tail = fn(consts, *(c[nfull * blk:] for c in cols))
            out = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                               out, tail)
        return out

    return jax.jit(_compat_shard_map(
        local, mesh=cluster().mesh, in_specs=(P(), P("rows")),
        out_specs=P("rows")))


class DeepLearningModel(Model):
    algo_name = "deeplearning"

    def __init__(self, key=None, parms=None):
        super().__init__(key, parms)
        self.params_tree: Optional[List] = None
        self.data_info: Optional[DataInfo] = None
        self.activation: str = "rectifier"
        self.nclasses: int = 1
        self.autoencoder: bool = False
        self.epochs_trained: int = 0

    def _predict_raw(self, frame: Frame):
        import jax
        import jax.numpy as jnp

        di = self.data_info
        act = self.activation
        autoencoder, nclasses = self.autoencoder, self.nclasses

        def block(params, *arrs):
            X = di.expand(*arrs)
            out = _forward(params, X, act, train=False)
            if autoencoder:
                return out, jnp.mean((out - X) ** 2, axis=-1)
            if nclasses > 1:
                return jax.nn.softmax(out, axis=-1)
            return out[:, 0]

        res = _blocked_rows(block)(
            self.params_tree, tuple(c.data for c in di.cols(frame)))
        if autoencoder:
            out, err = res
            return {"reconstruction": out, "score": err, "value": err}
        if nclasses > 1:
            return {"probs": res}
        return {"value": res}

    def _make_metrics(self, frame, raw, extra_weight=None):
        if not self.autoencoder:
            return super()._make_metrics(frame, raw, extra_weight)
        import numpy as np

        from h2o3_tpu.models import metrics as M

        per_row = np.asarray(raw["score"])[: frame.nrows]
        mse = float(np.nanmean(per_row))
        return M.ModelMetricsAutoEncoder(
            mse=mse, rmse=float(np.sqrt(mse)), nobs=float(frame.nrows),
            description="autoencoder reconstruction error")

    def anomaly(self, frame: Frame) -> Frame:
        """Per-row reconstruction MSE (autoencoder anomaly detection —
        reference DeepLearningModel.scoreAutoEncoder)."""
        raw = self._predict_raw(self.adapt_test(frame))
        out = Frame()
        out.add("Reconstruction.MSE", Column(raw["score"], T_NUM, frame.nrows))
        return out

    def deepfeatures(self, frame: Frame, layer: int) -> Frame:
        """Hidden-layer activations (reference deepfeatures endpoint)."""
        import jax
        import jax.numpy as jnp

        di = self.data_info
        arrays = tuple(c.data for c in di.cols(self.adapt_test(frame)))
        params = self.params_tree
        act_fn = _activation_fn(self.activation)

        @jax.jit
        def fwd(*arrs):
            h = di.expand(*arrs)
            for W, b in params[:layer + 1]:
                h = act_fn(h @ W + b)
            return h

        H = fwd(*arrays)
        out = Frame()
        for j in range(H.shape[1]):
            out.add(f"DF.L{layer+1}.C{j+1}", Column(H[:, j], T_NUM, frame.nrows))
        return out


@register
class DeepLearning(ModelBuilder):
    algo_name = "deeplearning"
    model_class = DeepLearningModel
    supports_checkpoint = True
    # crash-survivable builds: per-epoch durable progress (weights,
    # optimizer moments, RNG key) and exact continuation from it
    supports_iteration_resume = True

    @classmethod
    def default_params(cls):
        p = super().default_params()
        p.update({
            "hidden": [200, 200],
            "activation": "Rectifier",
            "epochs": 10.0,
            "mini_batch_size": 32,          # reference default 1; batched for MXU
            "adaptive_rate": True,
            "rho": 0.99, "epsilon": 1e-8,   # ADADELTA
            "rate": 0.005, "rate_annealing": 1e-6, "rate_decay": 1.0,
            "momentum_start": 0.0, "momentum_ramp": 1e6, "momentum_stable": 0.0,
            "l1": 0.0, "l2": 0.0,
            "input_dropout_ratio": 0.0,
            "hidden_dropout_ratios": None,
            "loss": "Automatic",            # Automatic/CrossEntropy/Quadratic/Absolute/Huber
            "distribution": "AUTO",
            "standardize": True,
            "autoencoder": False,
            # reference DeepLearningTask model averaging: nodes train local
            # replicas for ~train_samples_per_iteration samples, then
            # average. 0/-1/-2 (auto modes) = synchronous data-parallel SGD
            # (averaging period of one batch, the deterministic equivalent)
            "train_samples_per_iteration": 0,
            "use_all_factor_levels": True,
            "initial_weight_distribution": "UniformAdaptive",
            "initial_weight_scale": 1.0,
            "score_each_iteration": False,
            "variable_importances": True,
        })
        return p

    def __init__(self, **params):
        self.supervised = not bool(params.get("autoencoder"))
        super().__init__(**params)

    def _fit(self, train: Frame) -> DeepLearningModel:
        import jax
        import jax.numpy as jnp
        import optax

        p = self.params
        autoencoder = bool(p.get("autoencoder"))
        resp = p.get("response_column") if not autoencoder else None
        # training continuation (hex/Model.java:365; DL keeps the whole
        # weight state in the model, so resume = start from its params_tree
        # and its DataInfo — the standardization stats must be the ORIGINAL
        # run's, or the resumed weights see shifted inputs)
        prev = self._resolve_checkpoint()
        if prev is not None:
            if prev.params_tree is None:
                raise ValueError("checkpoint model has no weights to continue")
            # the resumed weights are only meaningful against the ORIGINAL
            # expanded layout: predictor names and categorical domains must
            # match (same guard SharedTree._fit applies)
            skip = {resp, p.get("weights_column"), p.get("offset_column"),
                    p.get("fold_column")} | set(p.get("ignored_columns") or [])
            names = [c for c in train.names
                     if c not in skip and not train.col(c).is_string]
            doms = {c: list(train.col(c).domain) for c in names
                    if train.col(c).is_categorical}
            if names != prev._output.names or doms != prev._output.domains:
                raise ValueError(
                    "checkpoint: training frame columns/domains differ from "
                    f"the original run ({prev._output.names} vs {names})")
            di = prev.data_info
        else:
            di = DataInfo(train, response=resp,
                          ignored=p.get("ignored_columns") or (),
                          weights=p.get("weights_column"),
                          standardize=bool(p.get("standardize", True)),
                          use_all_factor_levels=bool(p.get("use_all_factor_levels", True)))
        n = train.nrows
        arrays = tuple(c.data for c in di.cols(train))
        activation = (p.get("activation") or "Rectifier").lower()
        if activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {p['activation']!r}")
        hidden = [int(h) for h in (p.get("hidden") or [200, 200])]
        seed = self._seed()

        # response setup
        nclasses = 1
        y_dev = None
        if not autoencoder:
            y_col = train.col(resp)
            if y_col.is_categorical:
                nclasses = max(y_col.cardinality, 2)
            y_dev = y_col.data
        w_dev = train.col(p["weights_column"]).data if p.get("weights_column") else None

        X = jax.jit(di.expand)(*arrays)
        padded = X.shape[0]
        row_w = (jnp.arange(padded) < n).astype(jnp.float32)
        if not autoencoder:
            yw = DataInfo.response_weight(y_dev, w_dev)
            row_w = row_w * yw
            y = DataInfo.clean_response(y_dev)
            y = y.astype(jnp.int32) if nclasses > 1 else y.astype(jnp.float32)
        else:
            y = jnp.zeros(padded, jnp.float32)

        out_dim = di.fullN if autoencoder else (nclasses if nclasses > 1 else 1)
        if prev is not None:
            params0 = prev.params_tree
            if params0[-1][0].shape[1] != out_dim:
                raise ValueError(
                    "checkpoint: response cardinality changed "
                    f"({params0[-1][0].shape[1]} vs {out_dim})")
        else:
            params0 = _init_params(di.fullN, hidden, out_dim, seed,
                                   p.get("initial_weight_distribution", "UniformAdaptive"),
                                   float(p.get("initial_weight_scale", 1.0)))

        loss_name = (p.get("loss") or "Automatic").lower()
        if loss_name == "automatic":
            loss_name = "crossentropy" if nclasses > 1 else "quadratic"
        if nclasses > 1 and loss_name != "crossentropy":
            loss_name = "crossentropy"
        l1 = float(p.get("l1", 0.0))
        l2 = float(p.get("l2", 0.0))
        in_drop = float(p.get("input_dropout_ratio", 0.0))
        hid_drop = p.get("hidden_dropout_ratios")
        if hid_drop is None and "withdropout" in activation:
            hid_drop = [0.5] * len(hidden)
        hid_drop = tuple(float(h) for h in (hid_drop or []))

        batch = max(int(p.get("mini_batch_size", 32)), 1)
        epochs = float(p.get("epochs", 10.0))
        steps_per_epoch = max(int(math.ceil(n / batch)), 1)
        n_epochs = max(int(math.ceil(epochs)), 1)
        ep_start = 0
        if prev is not None:
            # epochs is the TOTAL target and must exceed the checkpoint's
            ep_start = int(getattr(prev, "epochs_trained", 0) or 0)
            if n_epochs <= ep_start:
                raise ValueError(
                    f"checkpoint model already trained {ep_start} epochs; "
                    f"epochs ({n_epochs}) must be greater")

        if p.get("adaptive_rate", True):
            opt = optax.adadelta(learning_rate=1.0, rho=float(p.get("rho", 0.99)),
                                 eps=float(p.get("epsilon", 1e-8)))
        else:
            rate = float(p.get("rate", 0.005))
            anneal = float(p.get("rate_annealing", 1e-6))
            m_start = float(p.get("momentum_start", 0.0))
            m_stable = float(p.get("momentum_stable", 0.0))
            ramp = max(float(p.get("momentum_ramp", 1e6)), 1.0)

            def lr_sched(step):
                return rate / (1.0 + anneal * step * batch)

            mom = max(m_start, m_stable)
            opt = (optax.sgd(learning_rate=lr_sched, momentum=mom)
                   if mom > 0 else optax.sgd(learning_rate=lr_sched))

        def row_loss(params, xb, yb, key):
            out = _forward(params, xb, activation, dropout_key=key,
                           input_dropout=in_drop, hidden_dropout=hid_drop,
                           train=True)
            if autoencoder:
                return jnp.mean((out - xb) ** 2, axis=-1)
            if nclasses > 1:
                logp = jax.nn.log_softmax(out, axis=-1)
                return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
            f = out[:, 0]
            if loss_name == "absolute":
                return jnp.abs(yb - f)
            if loss_name == "huber":
                d = jnp.abs(yb - f)
                return jnp.where(d <= 1.0, 0.5 * d * d, d - 0.5)
            return 0.5 * (yb - f) ** 2

        def penalty(params):
            reg = 0.0
            if l1 > 0 or l2 > 0:
                for W, _ in params:
                    reg = reg + l1 * jnp.sum(jnp.abs(W)) + l2 * 0.5 * jnp.sum(W * W)
            return reg

        def loss_fn(params, xb, yb, wb, key):
            per_row = row_loss(params, xb, yb, key)
            data_loss = jnp.sum(per_row * wb) / jnp.maximum(jnp.sum(wb), 1.0)
            return data_loss + penalty(params)

        grad_fn = jax.grad(loss_fn)
        # the per-epoch training loss over ALL rows, walked in row blocks
        # and expanded from the COLUMNS block by block: slicing the (rows,
        # fullN) matrix X into blocks instead cost XLA:TPU 239 s of compile
        # at 8M x 28 (measured on a v5e, PR 21) against 7 s for this form
        def weighted_block_loss(params, *block):
            *feats, yb, wb = block
            return row_loss(params, di.expand(*feats), yb, None) * wb

        weighted_row_loss = _blocked_rows(weighted_block_loss)

        def full_loss(params):
            lw = weighted_row_loss(params, arrays + (y, row_w))
            return float(jnp.sum(lw) / jnp.maximum(jnp.sum(row_w), 1.0)
                         + penalty(params))

        @jax.jit
        def _epoch_impl(params, opt_state, key, Xa, ya, wa):
            # data arrives as ARGUMENTS, not closed-over globals: on a
            # multi-process cloud closing over an array that spans
            # non-addressable devices is an error (jax multi-controller)
            def step(carry, _):
                params, opt_state, key = carry
                key, kidx, kdrop = jax.random.split(key, 3)
                idx = jax.random.randint(kidx, (batch,), 0, padded)
                xb, yb, wb = Xa[idx], ya[idx], wa[idx]
                grads = grad_fn(params, xb, yb, wb, kdrop)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state, key), None

            (params, opt_state, key), _ = jax.lax.scan(
                step, (params, opt_state, key), None, length=steps_per_epoch)
            return params, opt_state, key

        def run_epoch(params, opt_state, key):
            return _epoch_impl(params, opt_state, key, X, y, row_w)

        # per-device model averaging (DeepLearningTask.java:19,180 — local
        # replicas train independently, reduce = weighted average): each
        # mesh device runs `avg_period` minibatches on ITS row shard, then
        # params (and optimizer moments) pmean over the rows axis
        tspi = int(p.get("train_samples_per_iteration", 0) or 0)
        from h2o3_tpu.core.runtime import cluster as _cluster

        n_dev = int(_cluster().mesh.shape["rows"])
        avg_period = max(1, tspi // max(batch * n_dev, 1)) if tspi > 0 else 1
        if avg_period > 1 and n_dev > 1:
            from jax.sharding import PartitionSpec as P

            shard_rows = padded // n_dev
            n_rounds = max(int(math.ceil(steps_per_epoch / avg_period)), 1)

            def epoch_avg_body(params, opt_state, sub, Xs, ys, ws):
                key_l = jax.random.fold_in(sub, jax.lax.axis_index("rows"))

                def local(carry, _):
                    params, opt_state, key_l = carry
                    key_l, kidx, kdrop = jax.random.split(key_l, 3)
                    idx = jax.random.randint(kidx, (batch,), 0, shard_rows)
                    grads = grad_fn(params, Xs[idx], ys[idx], ws[idx], kdrop)
                    updates, opt_state = opt.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
                    return (params, opt_state, key_l), None

                def sync_round(carry, _):
                    (params, opt_state, key_l), _ = jax.lax.scan(
                        local, carry, None, length=avg_period)
                    # average weights AND float moments so the carried state
                    # is mesh-invariant (the reference averages the whole
                    # DeepLearningModelInfo, momenta included). Integer
                    # leaves (optax step counters) must keep their dtype —
                    # pmean would float-ify them and break the scan carry
                    params, opt_state = jax.tree.map(
                        lambda v: (jax.lax.pmean(v, "rows")
                                   if jnp.issubdtype(v.dtype, jnp.floating)
                                   else v),
                        (params, opt_state))
                    return (params, opt_state, key_l), None

                (params, opt_state, _), _ = jax.lax.scan(
                    sync_round, (params, opt_state, key_l), None,
                    length=n_rounds)
                return params, opt_state

            epoch_avg = jax.jit(_compat_shard_map(
                epoch_avg_body, mesh=_cluster().mesh,
                in_specs=(P(), P(), P(), P("rows", None), P("rows"), P("rows")),
                out_specs=(P(), P())))

            def run_epoch(params, opt_state, key):  # noqa: F811 — override
                key, sub = jax.random.split(key)
                params, opt_state = epoch_avg(params, opt_state, sub,
                                              X, y, row_w)
                return params, opt_state, key

        opt_state = opt.init(params0)
        key = jax.random.PRNGKey(seed)
        if ep_start:
            # resumed runs must not replay the original epochs' batch/dropout
            # draws (same reseeding rule as the tree path's host RNG)
            key = jax.random.fold_in(key, ep_start)
        params_t = params0

        model = DeepLearningModel(parms=dict(p))
        self._init_output(model, train)
        if autoencoder:
            model._output.model_category = ModelCategory.AutoEncoder
            model._output.response_name = None
        model.data_info = di
        model.activation = activation
        model.nclasses = nclasses
        model.autoencoder = autoencoder

        stop_rounds = int(p.get("stopping_rounds", 0) or 0)
        tol = float(p.get("stopping_tolerance", 1e-3))
        history: List[float] = []
        ep_done = ep_start
        rs = self._take_resume_state("dl_epochs")
        if rs is not None:
            # durable-progress fast-forward: weights, optimizer moments and
            # the LIVE RNG key (all epoch splits already consumed), so the
            # continued run walks the identical batch/dropout draws
            ep_start = int(rs["epoch"])
            ep_done = ep_start
            params_t = jax.tree.map(jnp.asarray, rs["params"])
            opt_state = jax.tree.map(jnp.asarray, rs["opt_state"])
            key = jnp.asarray(rs["key"])
            history = [float(v) for v in rs["history"]]
            model._output.scoring_history = [dict(h)
                                             for h in rs["scoring_history"]]
        jp_every = self._job_ckpt_every()
        for ep in range(ep_start, n_epochs):
            params_t, opt_state, key = run_epoch(params_t, opt_state, key)
            ep_done = ep + 1
            tr_loss = full_loss(params_t)
            model._output.scoring_history.append(
                {"epoch": ep + 1, "training_loss": tr_loss})
            history.append(tr_loss)
            if self.job:
                self.job.update(progress=(ep + 1) / n_epochs,
                                msg=f"epoch {ep+1}/{n_epochs} loss={tr_loss:.5f}")
            if jp_every and (ep + 1) % jp_every == 0:
                self._tick_job_progress(ep + 1, lambda: {
                    "phase": "dl_epochs", "epoch": ep_done,
                    "params": jax.tree.map(np.asarray, params_t),
                    "opt_state": jax.tree.map(np.asarray, opt_state),
                    "key": np.asarray(key),
                    "history": list(history),
                    "scoring_history":
                        [dict(h) for h in model._output.scoring_history]})
            if stop_rounds > 0 and len(history) > stop_rounds:
                best_recent = min(history[-stop_rounds:])
                best_before = min(history[:-stop_rounds])
                if best_recent > best_before * (1.0 - tol):
                    break
            if self._out_of_time():
                break

        model.epochs_trained = ep_done
        model.params_tree = jax.tree.map(np.asarray, params_t)
        model.params_tree = [(jnp.asarray(W), jnp.asarray(b))
                             for W, b in model.params_tree]
        if p.get("variable_importances", True) and not autoencoder:
            model._output.variable_importances = _garson_importance(
                model.params_tree, di)
        return model


def _init_params(in_dim: int, hidden: List[int], out_dim: int, seed: int,
                 dist: str, scale: float):
    """UniformAdaptive init (reference Neurons.randomize): U(±√(6/(fan_in+fan_out)))."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    dims = [in_dim] + hidden + [out_dim]
    params = []
    for fan_in, fan_out in zip(dims[:-1], dims[1:]):
        d = (dist or "UniformAdaptive").lower()
        if d == "uniformadaptive":
            lim = math.sqrt(6.0 / (fan_in + fan_out))
            W = rng.uniform(-lim, lim, size=(fan_in, fan_out))
        elif d == "uniform":
            W = rng.uniform(-scale, scale, size=(fan_in, fan_out))
        elif d == "normal":
            W = rng.normal(0.0, scale, size=(fan_in, fan_out))
        else:
            raise ValueError(f"unknown initial_weight_distribution {dist!r}")
        params.append((jnp.asarray(W, jnp.float32),
                       jnp.zeros(fan_out, jnp.float32)))
    return params


def _garson_importance(params, di: DataInfo) -> Dict[str, float]:
    """First-layer |weight| mass per ORIGINAL column (expanded one-hot columns
    fold back onto their categorical), normalized to max 1 — the spirit of the
    reference's Gedeon method (DeepLearningModelInfo.computeVariableImportances)."""
    W1 = np.abs(np.asarray(params[0][0])).sum(axis=1)  # (fullN,)
    imp: Dict[str, float] = {}
    for i, cname in enumerate(di.cat_names):
        s, e = di.cat_offsets[i], di.cat_offsets[i + 1]
        imp[cname] = float(W1[s:e].sum())
    for j, nname in enumerate(di.num_names):
        imp[nname] = float(W1[di.num_offset + j])
    mx = max(imp.values()) if imp else 1.0
    return {k: v / mx for k, v in sorted(imp.items(), key=lambda kv: -kv[1])}
