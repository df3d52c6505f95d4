"""DeepLearning — multilayer perceptron with JAX autodiff.

Reference: hex/deeplearning/ — hand-coded fprop/bprop per layer
(Neurons.java:184-229; Tanh :633, Maxout :684, Rectifier, dropout variants),
ADADELTA adaptive rate (DeepLearningModel.java), momentum ramp, L1/L2,
input/hidden dropout, autoencoder mode, async per-node model averaging
(DeepLearningTask.java:19,180 — reduce = weighted average of replicas).

TPU-native design: Neurons.fprop/bprop collapse into one jitted
loss-and-grad over the whole minibatch (jax.grad; the MXU eats the batched
matmuls). Training is data-parallel SYNCHRONOUS SGD: each step gathers its
minibatch's rows of the stored columns (codes and numerics) and computes the
first hidden layer from them (data_info.first_layer), so no (rows, inputs)
design exists (the autoencoder's reconstruction target alone expands its
minibatch's own rows, and its predictions are a reconstruction of every
row); the gradient all-reduce is inserted
by the SPMD partitioner — equivalent to the reference's model averaging
with averaging period = 1 batch, but deterministic. A whole epoch of steps
is one while loop in one program, so host<->device traffic is one call per
epoch. The programs are specialised on static shapes (`_Net`) and take the
data's moments as arguments: a job on a new frame of the same shape
compiles nothing.

`epochs` is honoured as H2O does: round(epochs x rows / mini_batch_size)
steps in all, the last epoch partial, `epochs_trained` a float.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from h2o3_tpu.compat import pcast
from h2o3_tpu.compat import shard_map as _compat_shard_map
from h2o3_tpu.core.frame import Column, Frame, T_NUM
from h2o3_tpu.models.data_info import (DataInfo, DesignLayout, design_rows,
                                       first_layer)
from h2o3_tpu.models.model import Model, ModelCategory
from h2o3_tpu.models.model_builder import ModelBuilder, register
from h2o3_tpu.obs import metrics, tracing

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


class _Net(NamedTuple):
    """What a DeepLearning program is specialised on: shapes and the
    hyper-parameters, never the data."""
    layout: DesignLayout
    activation: str
    nclasses: int               # 1: regression or autoencoder
    autoencoder: bool
    loss: str = "crossentropy"
    l1: float = 0.0
    l2: float = 0.0
    in_drop: float = 0.0
    hid_drop: Tuple[float, ...] = ()
    batch: int = 32
    opt: Tuple = ("adadelta", 0.99, 1e-8)
    max_w2: float = math.inf     # inf: no rescale is traced at all

    @property
    def n_cols(self) -> int:
        return len(self.layout.cards) + self.layout.n_num


def _dense(h, W, b):
    """A dense layer at f32 (H2O's arithmetic), not a TPU's default bf16
    operands."""
    import jax
    import jax.numpy as jnp

    return jnp.dot(h, W, precision=jax.lax.Precision.HIGHEST) + b


def _design_block(layout: DesignLayout, moments, cols):
    """The (rows, inputs) design of a block's own rows, as `expand` gives
    it: the autoencoder's reconstruction target."""
    import jax.numpy as jnp

    O, D = design_rows(layout, moments, cols)
    parts = [] if O is None else [O[layout.lane_coef()].astype(jnp.float32)]
    return jnp.concatenate(parts + [D]).T


def _forward(net: _Net, params, moments, cols, dropout_key=None,
             train=False):
    """MLP forward of a block of rows from their stored columns; returns
    the last layer's linear output. params = [(W, b), ...]."""
    import jax
    import jax.numpy as jnp

    act = _activation_fn(net.activation)
    use_dropout = train and dropout_key is not None
    W0, b0 = params[0]
    if use_dropout and net.in_drop > 0:
        # dropping an input column of a row: x . W is linear in x, so the
        # kept inputs' 1/(1 - p) scale applies to the product, not the bias
        dropout_key, sub = jax.random.split(dropout_key)
        keep = jax.random.bernoulli(sub, 1.0 - net.in_drop,
                                    (net.n_cols, cols[0].shape[0]))
        h = (first_layer(net.layout, moments, cols, W0, b0, keep=keep)
             - b0) / (1.0 - net.in_drop) + b0
    else:
        h = first_layer(net.layout, moments, cols, W0, b0)
    for li, (W, b) in enumerate(params[1:]):
        h = act(h)
        if use_dropout and li < len(net.hid_drop) and net.hid_drop[li] > 0:
            rate = net.hid_drop[li]
            dropout_key, sub = jax.random.split(dropout_key)
            keep = jax.random.bernoulli(sub, 1.0 - rate, h.shape)
            h = jnp.where(keep, h / (1.0 - rate), 0.0)
        h = _dense(h, W, b)
    return h


def _row_loss(net: _Net, params, moments, cols, yb, key=None, train=False):
    import jax
    import jax.numpy as jnp

    out = _forward(net, params, moments, cols, key, train)
    if net.autoencoder:
        return jnp.mean((out - _design_block(net.layout, moments, cols)) ** 2,
                        axis=-1)
    if net.nclasses == 2:
        # the two-class cross-entropy on the margin m = o1 - o0,
        # log(1 + e^m) - y m: a TPU's log_softmax reads p up to 3.5e-5 off
        # a float64 forward pass where the margin form reads 9e-7, and the
        # gradient carries that error into every step
        m = out[:, 1] - out[:, 0]
        return jnp.logaddexp(0.0, m) - yb.astype(jnp.float32) * m
    if net.nclasses > 1:
        logp = jax.nn.log_softmax(out, axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
    f = out[:, 0]
    if net.loss == "absolute":
        return jnp.abs(yb - f)
    if net.loss == "huber":
        d = jnp.abs(yb - f)
        return jnp.where(d <= 1.0, 0.5 * d * d, d - 0.5)
    return 0.5 * (yb - f) ** 2


def _penalty(net: _Net, params):
    import jax.numpy as jnp

    reg = 0.0
    if net.l1 > 0 or net.l2 > 0:
        for W, _ in params:
            reg = reg + net.l1 * jnp.sum(jnp.abs(W)) \
                + net.l2 * 0.5 * jnp.sum(W * W)
    return reg


def _batch_loss(net: _Net, params, moments, cols, yb, wb, key):
    import jax.numpy as jnp

    per_row = _row_loss(net, params, moments, cols, yb, key, train=True)
    return jnp.sum(per_row * wb) / jnp.maximum(jnp.sum(wb), 1.0) \
        + _penalty(net, params)


def _optimizer(net: _Net):
    import optax

    if net.opt[0] == "adadelta":
        _, rho, eps = net.opt
        return optax.adadelta(learning_rate=1.0, rho=rho, eps=eps)
    _, rate, anneal, mom = net.opt
    batch = net.batch

    def lr_sched(step):
        return rate / (1.0 + anneal * step * batch)

    return (optax.sgd(learning_rate=lr_sched, momentum=mom)
            if mom > 0 else optax.sgd(learning_rate=lr_sched))


def _max_w2(max_w2: float, params):
    """H2O's ``max_w2`` (Neurons.java): after an update, a unit whose
    incoming weights' squared norm r2 (a column of W, fan_in x fan_out)
    exceeds max_w2 has them scaled by sqrt(max_w2 / r2); biases untouched.
    -> (params, units rescaled)."""
    import jax.numpy as jnp

    out, rescaled = [], 0
    for W, b in params:
        r2 = jnp.sum(W * W, axis=0)
        over = r2 > max_w2
        W = W * jnp.where(over, jnp.sqrt(max_w2 / jnp.where(over, r2, 1.0)),
                          1.0)
        out.append((W, b))
        rescaled = rescaled + jnp.sum(over, dtype=jnp.int32)
    return out, rescaled


def _sgd_step(net: _Net, opt, carry, idx, moments, arrays, y, w, kdrop):
    """One minibatch: the rows ``idx`` of the stored columns, their
    gradient, the optimizer's update, then ``max_w2``'s rescale where it is
    finite. -> (params, opt_state, units rescaled: 0 without the rescale)."""
    import jax
    import optax

    params, opt_state = carry
    grads = jax.grad(functools.partial(_batch_loss, net))(
        params, moments, tuple(a[idx] for a in arrays), y[idx], w[idx],
        kdrop)
    updates, opt_state = opt.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    if math.isinf(net.max_w2):
        return params, opt_state, 0
    params, rescaled = _max_w2(net.max_w2, params)
    return params, opt_state, rescaled


# steps a run of the training program takes at most: 76 ms of a v5e at the
# airline network. The runs of an epoch are enqueued back to back (nothing
# is read on the host between them), so the device does not wait on the
# host; a profile of a fraction of a second then holds whole runs.
DL_STEPS_A_DISPATCH = 2048


@functools.partial(__import__("jax").jit, static_argnames=("net",))
def _dl_train_steps(params, opt_state, key, steps, nrows, arrays, moments, y,
                    w, rescaled=None, *, net):
    """``steps`` minibatch steps in one program (a while loop, so one
    compile serves whole and partial epochs). A step's rows are
    ``jax.random.randint(kidx, (batch,), 0, nrows)`` with ``key, kidx,
    kdrop = jax.random.split(key, 3)``: only the frame's real rows are
    drawn. Where ``net.max_w2`` is finite, ``rescaled`` (an int32 scalar)
    carries the count of units its rescale touched and comes back as a
    fourth output; otherwise it is None and the program holds no trace of
    the rescale."""
    import jax

    opt = _optimizer(net)

    def body(_i, carry):
        params, opt_state, key, *count = carry
        key, kidx, kdrop = jax.random.split(key, 3)
        idx = jax.random.randint(kidx, (net.batch,), 0, nrows)
        params, opt_state, n = _sgd_step(net, opt, (params, opt_state), idx,
                                         moments, arrays, y, w, kdrop)
        return (params, opt_state, key) + tuple(c + n for c in count)

    init = (params, opt_state, key) + (
        () if rescaled is None else (rescaled,))
    return jax.lax.fori_loop(0, steps, body, init)


@functools.lru_cache(maxsize=16)
def _dl_averaging_program(net: _Net, mesh, avg_period: int):
    """Per-device model averaging (DeepLearningTask.java:19,180 — local
    replicas train independently, reduce = weighted average): each mesh
    device runs `avg_period` minibatches on ITS row shard, then params (and
    optimizer moments) pmean over the rows axis; ``rounds`` of that."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    opt = _optimizer(net)

    def body(params, opt_state, sub, rounds, nrows, arrays, moments, y, w):
        shard = arrays[0].shape[0]
        d = jax.lax.axis_index("rows")
        # the real rows of this shard: the frame's padding is never drawn
        real = jnp.clip(nrows - d * shard, 0, shard)
        key_l = jax.random.fold_in(sub, d)

        def local(_i, carry):
            params, opt_state, key_l = carry
            key_l, kidx, kdrop = jax.random.split(key_l, 3)
            idx = jax.random.randint(kidx, (net.batch,), 0,
                                     jnp.maximum(real, 1))
            wl = jnp.where(real > 0, w, 0.0)
            params, opt_state, _ = _sgd_step(net, opt, (params, opt_state),
                                             idx, moments, arrays, y, wl,
                                             kdrop)
            return params, opt_state, key_l

        def sync_round(_r, carry):
            carry = jax.lax.fori_loop(0, avg_period, local, carry)
            params, opt_state, key_l = carry
            # average weights AND float moments so the carried state is
            # mesh-invariant (the reference averages the whole
            # DeepLearningModelInfo, momenta included). Integer leaves
            # (optax step counters) must keep their dtype — pmean would
            # float-ify them and break the loop carry
            params, opt_state = jax.tree.map(
                lambda v: (jax.lax.pmean(v, "rows")
                           if jnp.issubdtype(v.dtype, jnp.floating) else v),
                (params, opt_state))
            return params, opt_state, key_l

        params, opt_state, _ = jax.lax.fori_loop(
            0, rounds, sync_round, (params, opt_state, key_l))
        return params, opt_state

    return jax.jit(_compat_shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P("rows"), P(), P("rows"),
                  P("rows")),
        out_specs=(P(), P())))


# bytes a whole-frame pass's row block may hold (its one-hot, activations
# and outputs): 8,192 rows of the airline network, where a whole 48M-row
# frame's activations would be 38 GB a layer
DL_BLOCK_BYTES = 1 << 26


def dl_block_rows(n_shard: int, net: _Net, width: int) -> int:
    """Rows of one block of a whole-frame pass, from shapes alone: the
    largest power of two whose one-hot (3 B a lane: bool and bf16) and
    f32 activations (8 B a unit: before and after the activation) stay
    under DL_BLOCK_BYTES, at most the shard. ``width`` is the units of the
    hidden and output layers, summed."""
    row = 3 * sum(net.layout.padded) + 8 * (width + net.layout.n_num)
    blk = 1 << max((DL_BLOCK_BYTES // max(row, 1)).bit_length() - 1, 8)
    return int(min(blk, max(n_shard, 1)))


@functools.lru_cache(maxsize=32)
def _dl_pass(net: _Net, mesh, kind: str, width: int):
    """A whole-frame pass over the row shards, a block of rows at a time
    (dl_block_rows), as one program: under shard_map each device walks ITS
    shard, so the (rows, hidden) activations of a whole frame never exist
    at once (found on the chip: the eager full-frame loss pass asked for
    5.96 GB at 8M rows and exhausted HBM). The weights and moments ride as
    arguments, not closure constants, so one compile serves every epoch and
    every frame of the shape. ``kind``:

      "loss"      -> (sum of w x row loss, sum of w) over the frame
      "predict"   -> per row: class probabilities, the regression value, or
                     the autoencoder's (reconstruction, error)
      "features<k>" -> per row: hidden layer k's activations

    A block is a dynamic slice of the columns, so the pass holds one
    block's temporaries and no copy of the frame.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    act = _activation_fn(net.activation)

    def block_fn(params, moments, *block):
        if kind == "loss":
            *cols, yb, wb = block
            lw = _row_loss(net, params, moments, tuple(cols), yb) * wb
            return jnp.sum(lw), jnp.sum(wb)
        if kind.startswith("features"):
            layer = int(kind[len("features"):])
            W0, b0 = params[0]
            h = act(first_layer(net.layout, moments, block, W0, b0))
            for W, b in params[1:layer + 1]:
                h = act(_dense(h, W, b))
            return h
        out = _forward(net, params, moments, block)
        if net.autoencoder:
            X = _design_block(net.layout, moments, block)
            return out, jnp.mean((out - X) ** 2, axis=-1)
        if net.nclasses > 1:
            return jax.nn.softmax(out, axis=-1)
        return out[:, 0]

    def local(params, moments, cols):
        n = cols[0].shape[0]
        blk = dl_block_rows(n, net, width)

        def block(i):
            """Rows [i*blk, (i+1)*blk) of the shard; the last block starts
            early enough to be whole (it rewrites the rows it shares with
            the one before it, and gives them no weight in a sum)."""
            start = jnp.minimum(i * blk, n - blk)
            return start, tuple(jax.lax.dynamic_slice_in_dim(c, start, blk)
                                for c in cols)

        if kind == "loss":
            def body(i, acc):
                start, b = block(i)
                fresh = start + jnp.arange(blk) >= i * blk
                lw, sw = block_fn(params, moments, *b[:-1],
                                  jnp.where(fresh, b[-1], 0.0))
                return acc[0] + lw, acc[1] + sw

            zero = pcast(jnp.float32(0), ("rows",), to="varying")
            tot = jax.lax.fori_loop(0, -(-n // blk), body, (zero, zero))
            return tuple(jax.lax.psum(t, "rows") for t in tot)

        def body(i, out):
            start, b = block(i)
            return jax.tree.map(
                lambda o, r: jax.lax.dynamic_update_slice_in_dim(o, r, start,
                                                                 0),
                out, block_fn(params, moments, *b))

        shapes = jax.eval_shape(lambda: block_fn(params, moments,
                                                 *block(0)[1]))
        out = jax.tree.map(lambda sd: pcast(
            jnp.zeros((n,) + sd.shape[1:], sd.dtype), ("rows",),
            to="varying"), shapes)
        return jax.lax.fori_loop(0, -(-n // blk), body, out)

    local.__name__ = f"_dl_{kind}_pass"
    return jax.jit(_compat_shard_map(
        local, mesh=mesh, in_specs=(P(), P(), P("rows")),
        out_specs=P() if kind == "loss" else P("rows")))


def _run_pass(net: _Net, kind: str, params, moments, cols):
    from h2o3_tpu.core.runtime import cluster

    width = sum(int(W.shape[1]) for W, _ in params)
    return _dl_pass(net, cluster().mesh, kind, width)(params, moments,
                                                      tuple(cols))


class DeepLearningModel(Model):
    algo_name = "deeplearning"

    def __init__(self, key=None, parms=None):
        super().__init__(key, parms)
        self.params_tree: Optional[List] = None
        self.data_info: Optional[DataInfo] = None
        self.activation: str = "rectifier"
        self.nclasses: int = 1
        self.autoencoder: bool = False
        self.epochs_trained: float = 0.0

    def _net(self) -> _Net:
        return _Net(self.data_info.layout(), self.activation, self.nclasses,
                    self.autoencoder)

    def _predict_raw(self, frame: Frame):
        di = self.data_info
        res = _run_pass(self._net(), "predict", self.params_tree,
                        di.moments(), (c.data for c in di.cols(frame)))
        if self.autoencoder:
            out, err = res
            return {"reconstruction": out, "score": err, "value": err}
        if self.nclasses > 1:
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
        di = self.data_info
        H = _run_pass(self._net(), f"features{int(layer)}", self.params_tree,
                      di.moments(),
                      (c.data for c in di.cols(self.adapt_test(frame))))
        out = Frame()
        for j in range(H.shape[1]):
            out.add(f"DF.L{layer+1}.C{j+1}", Column(H[:, j], T_NUM, frame.nrows))
        return out


def _steps_of(epochs: float, nrows: int, batch: int) -> int:
    """Minibatch steps of ``epochs`` over ``nrows`` rows: H2O trains
    epochs x rows samples."""
    return int(round(float(epochs) * nrows / batch))


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
            # H2O's Neurons: a unit's incoming squared weights are capped
            # after every update; +inf (or Float.MAX_VALUE) is off
            "max_w2": math.inf,
            # H2O's ModelBuilder: predictors with min == max or all NA
            # leave the design (their names on the model's output)
            "ignore_const_cols": True,
        })
        return p

    def __init__(self, **params):
        self.supervised = not bool(params.get("autoencoder"))
        super().__init__(**params)

    def _fit(self, train: Frame) -> DeepLearningModel:
        import jax
        import jax.numpy as jnp

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
                    p.get("fold_column")} | set(p.get("ignored_columns") or []) \
                | set(getattr(prev._output, "const_cols_dropped", ()))
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
            # stage span ``design``: DataInfo reads the rollups and modes of
            # the predictors on the host (cached on the columns after a
            # frame's first job), which is where it has always blocked
            with tracing.span("design", rows=train.nrows) as sp:
                di = DataInfo(train, response=resp,
                              ignored=p.get("ignored_columns") or (),
                              weights=p.get("weights_column"),
                              standardize=bool(p.get("standardize", True)),
                              use_all_factor_levels=bool(
                                  p.get("use_all_factor_levels", True)),
                              ignore_const_cols=bool(
                                  p.get("ignore_const_cols", True)))
                sp.set(inputs=di.fullN,
                       const_cols_dropped=len(di.const_cols))
        n = train.nrows
        arrays = tuple(c.data for c in di.cols(train))
        moments = di.moments()
        padded = int(arrays[0].shape[0]) if arrays else n
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
        hid_drop = p.get("hidden_dropout_ratios")
        if hid_drop is None and "withdropout" in activation:
            hid_drop = [0.5] * len(hidden)
        batch = max(int(p.get("mini_batch_size", 32)), 1)
        if p.get("adaptive_rate", True):
            opt_spec = ("adadelta", float(p.get("rho", 0.99)),
                        float(p.get("epsilon", 1e-8)))
        else:
            opt_spec = ("sgd", float(p.get("rate", 0.005)),
                        float(p.get("rate_annealing", 1e-6)),
                        max(float(p.get("momentum_start", 0.0)),
                            float(p.get("momentum_stable", 0.0))))
        net = _Net(di.layout(), activation, nclasses, autoencoder,
                   loss=loss_name, l1=float(p.get("l1", 0.0)),
                   l2=float(p.get("l2", 0.0)),
                   in_drop=float(p.get("input_dropout_ratio", 0.0)),
                   hid_drop=tuple(float(h) for h in (hid_drop or [])),
                   batch=batch, opt=opt_spec, max_w2=_max_w2_of(p))

        epochs = float(p.get("epochs", 10.0))
        total = max(_steps_of(epochs, n, batch), 1)
        per_epoch = max(-(-n // batch), 1)
        done = 0
        if prev is not None:
            # epochs is the TOTAL target and must exceed the checkpoint's
            done = _steps_of(getattr(prev, "epochs_trained", 0) or 0, n, batch)
            if total <= done:
                raise ValueError(
                    f"checkpoint model already trained {prev.epochs_trained} "
                    f"epochs; epochs ({epochs}) must be greater")

        def full_loss(params):
            lw, sw = _run_pass(net, "loss", params, moments,
                               arrays + (y, row_w))
            return float(lw / jnp.maximum(sw, 1.0) + _penalty(net, params))

        # per-device model averaging: each mesh device runs `avg_period`
        # minibatches on ITS row shard between averages
        tspi = int(p.get("train_samples_per_iteration", 0) or 0)
        from h2o3_tpu.core.runtime import cluster as _cluster

        mesh = _cluster().mesh
        n_dev = int(mesh.shape["rows"])
        avg_period = max(1, tspi // max(batch * n_dev, 1)) if tspi > 0 else 1
        averaging = avg_period > 1 and n_dev > 1

        dispatches = 0
        # units max_w2 rescaled, summed on the device across the runs of
        # the training program and read once after the last
        rescaled = None if math.isinf(net.max_w2) else jnp.int32(0)

        def run_steps(params, opt_state, key, k):
            nonlocal dispatches, rescaled
            if averaging:
                key, sub = jax.random.split(key)
                params, opt_state = _dl_averaging_program(
                    net, mesh, avg_period)(params, opt_state, sub,
                                           -(-k // avg_period), n, arrays,
                                           moments, y, row_w)
                dispatches += 1
                return params, opt_state, key
            for lo in range(0, k, DL_STEPS_A_DISPATCH):
                params, opt_state, key, *count = _dl_train_steps(
                    params, opt_state, key, min(DL_STEPS_A_DISPATCH, k - lo),
                    n, arrays, moments, y, row_w, rescaled, net=net)
                rescaled = count[0] if count else None
                dispatches += 1
            return params, opt_state, key

        opt_state = _optimizer(net).init(params0)
        key = jax.random.PRNGKey(seed)
        if done:
            # resumed runs must not replay the original steps' batch/dropout
            # draws (same reseeding rule as the tree path's host RNG)
            key = jax.random.fold_in(key, done)
        params_t = params0

        model = DeepLearningModel(parms=dict(p))
        self._init_output(model, train)
        # the constant predictors are ignored columns from here on (H2O's
        # warning lists them): scoring a frame takes no notice of them
        dropped = set(di.const_cols)
        model._output.names = [c for c in model._output.names
                               if c not in dropped]
        model._output.domains = {c: d for c, d in
                                 model._output.domains.items()
                                 if c not in dropped}
        model._output.const_cols_dropped = list(di.const_cols)
        if autoencoder:
            model._output.model_category = ModelCategory.AutoEncoder
            model._output.response_name = None
        model.data_info = di
        model.activation = activation
        model.nclasses = nclasses
        model.autoencoder = autoencoder

        def epochs_at(steps: int) -> float:
            return epochs if steps == total else steps * batch / n

        stop_rounds = int(p.get("stopping_rounds", 0) or 0)
        tol = float(p.get("stopping_tolerance", 1e-3))
        history: List[float] = []
        ep = 0                      # epochs (whole or partial) run here
        rs = self._take_resume_state("dl_epochs")
        if rs is not None:
            # durable-progress fast-forward: weights, optimizer moments and
            # the LIVE RNG key (all splits already consumed), so the
            # continued run walks the identical batch/dropout draws
            ep, done = int(rs["epoch"]), int(rs["steps"])
            params_t = jax.tree.map(jnp.asarray, rs["params"])
            opt_state = jax.tree.map(jnp.asarray, rs["opt_state"])
            key = jnp.asarray(rs["key"])
            history = [float(v) for v in rs["history"]]
            model._output.scoring_history = [dict(h)
                                             for h in rs["scoring_history"]]
        jp_every = self._job_ckpt_every()
        n_epochs = ep + -(-(total - done) // per_epoch)
        first = done

        def progress():
            """The durable progress of the epochs run so far (read only
            when a save is due: it fetches the weights to the host)."""
            return {"phase": "dl_epochs", "epoch": ep, "steps": done,
                    "params": jax.tree.map(np.asarray, params_t),
                    "opt_state": jax.tree.map(np.asarray, opt_state),
                    "key": np.asarray(key), "history": list(history),
                    "scoring_history":
                        [dict(h) for h in model._output.scoring_history]}

        # stage span ``epochs``: from the first dispatch of the training
        # program to the last epoch's loss read, where the host has always
        # blocked
        with tracing.span("epochs", batch=batch) as sp:
            while done < total:
                k = min(per_epoch, total - done)
                params_t, opt_state, key = run_steps(params_t, opt_state,
                                                     key, k)
                done += k
                ep += 1
                tr_loss = full_loss(params_t)
                model._output.scoring_history.append(
                    {"epoch": epochs_at(done), "training_loss": tr_loss})
                history.append(tr_loss)
                if self.job:
                    self.job.update(progress=done / total,
                                    msg=f"epoch {ep}/{n_epochs} "
                                        f"loss={tr_loss:.5f}")
                if jp_every and ep % jp_every == 0:
                    self._tick_job_progress(ep, progress)
                if stop_rounds > 0 and len(history) > stop_rounds:
                    best_recent = min(history[-stop_rounds:])
                    best_before = min(history[:-stop_rounds])
                    if best_recent > best_before * (1.0 - tol):
                        break
                if self._out_of_time():
                    break
            sp.set(steps=done - first, samples=(done - first) * batch,
                   epochs=epochs_at(done), dispatches=dispatches)
        metrics.inc("h2o3_dl_steps_total", done - first)
        metrics.inc("h2o3_dl_samples_total", (done - first) * batch)
        metrics.inc("h2o3_dl_dispatches_total", dispatches)
        if rescaled is not None:
            metrics.inc("h2o3_dl_max_w2_rescaled_total", int(rescaled))

        model.epochs_trained = epochs_at(done)
        model.params_tree = jax.tree.map(np.asarray, params_t)
        model.params_tree = [(jnp.asarray(W), jnp.asarray(b))
                             for W, b in model.params_tree]
        if p.get("variable_importances", True) and not autoencoder:
            model._output.variable_importances = _garson_importance(
                model.params_tree, di)
        return model


def _max_w2_of(p: dict) -> float:
    """The ``max_w2`` parameter; H2O's clients send Float.MAX_VALUE for
    its default, which is no cap either."""
    v = float(p.get("max_w2", math.inf))
    return math.inf if v >= 3.4028235e38 else v


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
