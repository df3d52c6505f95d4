"""DataInfo: columns → numeric design matrix for linear/NN algos.

Reference: hex/DataInfo.java:23 — categorical one-hot offsets (_catOffsets
:116), standardization, missing-value policy — plus hex/FrameTask.java which
streams `Row` objects to the algo.

TPU-native design: no row iterator. DataInfo precomputes host-side metadata
(offsets, means, sigmas, domains) and exposes `expand(*shard_arrays)` — a
pure jnp function used INSIDE jitted training steps that turns this shard's
raw column slices into a dense (rows, p) float32 block: one-hot via
jax.nn.one_hot (fused into the following matmul by XLA; the MXU eats dense
one-hots far better than a CPU eats sparse rows), standardized numerics,
mean/mode-imputed NAs, pad rows zero-weighted via the returned weight vector.

At a few hundred levels that block is the wall (2,676 B a row at 668
columns), so GLM's IRLS and its scoring do not call `expand`: `design_rows`
builds the same zeros and ones for a block of rows with the rows on lanes,
straight from the codes, and `linear_predictor` reads a row's coefficients
without any design at all. DeepLearning's first hidden layer is
`first_layer`: the one-hot against the layer's weights on the MXU, a block
of rows at a time. `DesignLayout` is the static shape they are
compiled for; the moments ride as arrays.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from h2o3_tpu.core.frame import Column, Frame, T_CAT


_SUBLANES = 8               # sublanes of a 32-bit TPU tile


class DesignLayout(NamedTuple):
    """The static shape of a design: what a jitted program is specialised
    on. Everything that moves with the data (modes, means, sigmas) rides as
    arrays (`DataInfo.moments`), so two jobs on frames of one shape share
    one compiled program.

    A categorical column's levels lie on `width` lanes rounded up to whole
    sublane tiles (`padded`), as models/tree/device_tree.hist_matmul lays a
    feature's bins: nothing is placed at an offset inside a tile."""
    cards: Tuple[int, ...]      # levels a categorical column has
    base: int                   # 1 = the first level is dropped, 0 = kept
    n_num: int
    standardize: bool

    @property
    def widths(self) -> Tuple[int, ...]:
        return tuple(max(c - self.base, 1) for c in self.cards)

    @property
    def padded(self) -> Tuple[int, ...]:
        return tuple(-(-w // _SUBLANES) * _SUBLANES for w in self.widths)

    @property
    def n_cat_coefs(self) -> int:
        return int(sum(self.widths))

    @property
    def n_coefs(self) -> int:
        return self.n_cat_coefs + self.n_num

    def lane_levels(self) -> List[np.ndarray]:
        """Per categorical column the (padded, 1) level each lane stands
        for, -1 in the padding: no code has it."""
        return [np.where(np.arange(pd) < wd, np.arange(pd) + self.base, -1)
                .astype(np.int32)[:, None]
                for wd, pd in zip(self.widths, self.padded)]

    def lane_coef(self) -> np.ndarray:
        """(sum(widths),) the padded lane of each categorical coefficient,
        in coefficient order."""
        offs = np.cumsum((0,) + self.padded[:-1]) if self.cards else ()
        return np.concatenate(
            [o + np.arange(w) for o, w in zip(offs, self.widths)]
            or [np.zeros(0)]).astype(np.int32)


def design_rows(layout: DesignLayout, moments, arrays):
    """The design of a block of rows with the rows on the LANE axis and no
    (rows, p) matrix: -> (O, D). O is the (sum(padded), rows) bool one-hot
    of the categorical columns (None without any), one compare a column of
    its imputed codes against the static level of each lane; D the
    (n_num, rows) f32 numerics, imputed and standardized. The zeros and
    ones are `DataInfo.expand`'s."""
    import jax.numpy as jnp

    cat_modes = moments[0]
    ncat = len(layout.cards)
    O = None
    if ncat:
        levels = layout.lane_levels()
        parts = []
        for i in range(ncat):
            codes = arrays[i].astype(jnp.int32)
            codes = jnp.where(codes < 0, cat_modes[i], codes)
            parts.append(codes[None, :] == levels[i])
        O = jnp.concatenate(parts) if ncat > 1 else parts[0]
    return O, _numeric_rows(layout, moments, arrays)


def _numeric_rows(layout: DesignLayout, moments, arrays):
    """(n_num, rows) f32 numerics, imputed and standardized."""
    import jax.numpy as jnp

    _modes, impute, means, sigmas = moments
    nums = arrays[len(layout.cards):len(layout.cards) + layout.n_num]
    if not nums:
        return jnp.zeros((0, arrays[0].shape[0]), jnp.float32)
    D = jnp.stack(nums).astype(jnp.float32)
    D = jnp.where(jnp.isnan(D), impute[:, None], D)
    if layout.standardize:
        D = (D - means[:, None]) / sigmas[:, None]
    return D


def lane_beta(layout: DesignLayout, beta):
    """(n_cat_coefs,) categorical coefficients -> (sum(padded), 1) on the
    lanes of `design_rows`' one-hot, zeros in the padding."""
    import jax.numpy as jnp

    return jnp.zeros(int(sum(layout.padded)), beta.dtype).at[
        layout.lane_coef()].set(beta[: layout.n_cat_coefs])[:, None]


def linear_predictor(layout: DesignLayout, moments, arrays, beta):
    """x . beta + intercept for every row from codes and coefficients
    (beta: n_coefs + 1, intercept last), without the expanded matrix: a
    categorical column reads its row's coefficient by select over its own
    levels (levels on sublanes, rows on lanes; a column at a time, so that
    the compare feeds its sum and no (levels, rows) array is kept); nine
    terms a row where the design is 668 wide."""
    import jax.numpy as jnp

    k = layout.n_cat_coefs
    # the numerics by a dot, not by multiply-adds a compiler may or may not
    # contract: the sum is then the same bits in every program that holds it
    eta = beta[k:k + layout.n_num] @ _numeric_rows(layout, moments, arrays) \
        + beta[-1]
    off = 0
    for i, wd in enumerate(layout.widths):
        codes = arrays[i].astype(jnp.int32)
        codes = jnp.where(codes < 0, moments[0][i], codes)
        hit = codes[None, :] == (np.arange(wd, dtype=np.int32)
                                 + layout.base)[:, None]
        eta = eta + jnp.sum(jnp.where(hit, beta[off:off + wd, None], 0.0),
                            axis=0)
        off += wd
    return eta


def first_layer(layout: DesignLayout, moments, arrays, W, b, keep=None):
    """x @ W + b for a block of rows from the stored codes and numerics,
    without the expanded matrix: -> (rows, H) f32. W is (n_coefs, H) in
    `expand`'s column order, b is (H,). The conventions are `design_rows'`:
    an NA code reads the mode's row, a code with no lane (the dropped first
    level, an unseen code) reads none; NA numerics take the mean.

    The categorical part is `design_rows`' one-hot against W's rows laid on
    its lanes: a one-hot is exact in bf16, so three bf16 passes over W's
    bf16 pieces give f32 products on the MXU, and the gradient of W comes
    back the same way (`_onehot_dot`). ``keep``, an optional (columns, rows)
    bool, zeroes a column's input in a row (input dropout)."""
    import jax
    import jax.numpy as jnp

    O, D = design_rows(layout, moments, arrays)
    k, ncat = layout.n_cat_coefs, len(layout.cards)
    out = b[None, :]
    if layout.n_num:
        if keep is not None:
            D = jnp.where(keep[ncat:], D, 0.0)
        out = out + jax.lax.dot_general(
            D, W[k:k + layout.n_num], (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)
    if O is not None:
        if keep is not None:
            O = O & jnp.repeat(keep[:ncat], np.asarray(layout.padded),
                               axis=0, total_repeat_length=O.shape[0])
        # W's rows on the one-hot's lanes by static slices and zero pads: a
        # scatter to `lane_coef` (and its gather back in the gradient) was
        # 25 of a minibatch step's 62 us on a v5e
        lanes, off = [], 0
        for wd, pd in zip(layout.widths, layout.padded):
            lanes.append(jnp.pad(W[off:off + wd], ((0, pd - wd), (0, 0))))
            off += wd
        out = out + _onehot_dot(O, jnp.concatenate(lanes))
    return out


def _onehot_dot(O, V):
    """O' V for a (lanes, rows) bool one-hot O and (lanes, H) f32 V ->
    (rows, H), with f32 products: three bf16 passes over V's pieces. V's
    gradient, O times the (rows, H) cotangent, is formed the same way from
    the cotangent's pieces: autodiff would round the cotangent to bf16."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.ops.elementwise import bf16_pieces

    def passes(Ob, X, dims):
        hi, mid, lo = (jax.lax.dot_general(Ob, p.astype(jnp.bfloat16), dims,
                                           preferred_element_type=jnp.float32)
                       for p in bf16_pieces(X))
        return lo + mid + hi

    @jax.custom_vjp
    def dot(O, V):
        return passes(O.astype(jnp.bfloat16), V, (((0,), (0,)), ((), ())))

    def fwd(O, V):
        return dot(O, V), O

    def bwd(O, ct):
        return None, passes(O.astype(jnp.bfloat16), ct,
                            (((1,), (0,)), ((), ())))

    dot.defvjp(fwd, bwd)
    return dot(O, V)


def _is_const(col: Column) -> bool:
    r = col.rollups
    return r.rows == 0 or r.min == r.max


class DataInfo:
    """Expansion plan for a predictor set + response.

    use_all_factor_levels: False drops the first level per categorical
    (reference DataInfo 'useAllFactorLevels' — GLM drops, DL keeps).

    ignore_const_cols: True leaves out every predictor whose rollups read
    min == max or no valid row (H2O ModelBuilder's ``ignore_const_cols``):
    imputed, such a column is one value in every row. Their names, in frame
    order, are ``const_cols``.
    """

    const_cols: Tuple[str, ...] = ()

    def __init__(self, frame: Frame, response: Optional[str] = None,
                 *, ignored: Sequence[str] = (),
                 weights: Optional[str] = None, offset: Optional[str] = None,
                 standardize: bool = True, use_all_factor_levels: bool = False,
                 missing_values_handling: str = "MeanImputation",
                 ignore_const_cols: bool = False):
        self.response_name = response
        self.weights_name = weights
        self.offset_name = offset
        self.standardize = standardize
        self.use_all_factor_levels = use_all_factor_levels
        self.missing_values_handling = missing_values_handling

        skip = set(ignored) | {response, weights, offset} - {None}
        self.cat_names: List[str] = []
        self.num_names: List[str] = []
        const = []
        for n in frame.names:
            c = frame.col(n)
            if n in skip or c.is_string:
                continue
            if ignore_const_cols and _is_const(c):
                const.append(n)
                continue
            (self.cat_names if c.is_categorical else self.num_names).append(n)
        self.const_cols = tuple(const)
        # categoricals first, then numerics — reference column ordering
        self.predictor_names = self.cat_names + self.num_names

        self.domains = {n: list(frame.col(n).domain or []) for n in self.cat_names}
        self.cards = [len(self.domains[n]) for n in self.cat_names]
        self._recompute_layout(use_all_factor_levels)

        # standardization moments from rollups (computed lazily, cached on col)
        means, sigmas, modes = [], [], []
        for n in self.num_names:
            r = frame.col(n).rollups
            means.append(r.mean)
            s = r.sigma
            sigmas.append(s if s and s > 0 else 1.0)
        for n in self.cat_names:
            modes.append(frame.col(n).mode)
        self.num_means = np.asarray(means, np.float32) if means else np.zeros(0, np.float32)
        self.num_sigmas = np.asarray(sigmas, np.float32) if sigmas else np.ones(0, np.float32)
        self.cat_modes = np.asarray(modes, np.int32) if modes else np.zeros(0, np.int32)
        # NA fill on the RAW scale — stays the column mean even when a caller
        # (pca.make_data_info) rewrites num_means to change the affine transform
        self.impute_values = self.num_means.copy()

    def _recompute_layout(self, use_all_factor_levels: bool) -> None:
        """(Re)derive the expanded layout. Callers that flip
        use_all_factor_levels after construction (GLRM, Aggregator) MUST go
        through set_use_all_factor_levels so cat_offsets/num_offset/fullN
        stay consistent with what expand() actually emits."""
        self.use_all_factor_levels = use_all_factor_levels
        base = 0 if use_all_factor_levels else 1
        self.cat_widths = [max(c - base, 1) for c in self.cards]
        # _catOffsets (DataInfo.java:116): running start index per categorical
        self.cat_offsets = np.concatenate(
            [[0], np.cumsum(self.cat_widths)]).astype(int)
        self.num_offset = int(self.cat_offsets[-1])
        self.fullN = self.num_offset + len(self.num_names)

    def set_use_all_factor_levels(self, flag: bool) -> None:
        self._recompute_layout(flag)

    # -- names of expanded coefficients (GLM coefficient table) -----------
    def coef_names(self) -> List[str]:
        out = []
        base = 0 if self.use_all_factor_levels else 1
        for n, card in zip(self.cat_names, self.cards):
            dom = self.domains[n]
            for lvl in range(base, max(card, base + 1)):
                out.append(f"{n}.{dom[lvl] if lvl < len(dom) else lvl}")
        out.extend(self.num_names)
        return out

    def cols(self, frame: Frame) -> List[Column]:
        return [frame.col(n) for n in self.predictor_names]

    # -- the design without the expanded matrix -----------------------------
    def layout(self) -> DesignLayout:
        return DesignLayout(tuple(int(c) for c in self.cards),
                            0 if self.use_all_factor_levels else 1,
                            len(self.num_names), bool(self.standardize))

    def moments(self) -> Tuple[np.ndarray, ...]:
        """What `design_rows` needs of the data: (cat_modes, impute_values,
        num_means, num_sigmas)."""
        return (self.cat_modes, self.impute_values, self.num_means,
                self.num_sigmas)

    def linear_predictor(self, arrays, beta):
        return linear_predictor(self.layout(), self.moments(), arrays, beta)

    # -- device-side expansion (traced inside jit) ------------------------
    def expand(self, *arrays):
        """Shard slices (one per predictor, cats first) → (rows, fullN) f32.

        Pure jnp; NAs imputed (mean for numeric, mode for cat codes when
        MeanImputation — matching DataInfo.imputeMissing), one-hot with
        optional first-level drop, numerics standardized."""
        import jax.numpy as jnp

        ncat = len(self.cat_names)
        parts = []
        base = 0 if self.use_all_factor_levels else 1
        for i in range(ncat):
            codes = arrays[i].astype(jnp.int32)
            codes = jnp.where(codes < 0, self.cat_modes[i], codes)
            card = max(self.cards[i], base + 1)
            oh = jnp.take(jnp.eye(card, dtype=jnp.float32), codes, axis=0)
            parts.append(oh[:, base:] if base else oh)
        if self.num_names:
            nums = jnp.stack([arrays[ncat + j] for j in range(len(self.num_names))], axis=-1)
            nums = jnp.where(jnp.isnan(nums), self.impute_values[None, :], nums)
            if self.standardize:
                nums = (nums - self.num_means[None, :]) / self.num_sigmas[None, :]
            parts.append(nums.astype(jnp.float32))
        if not parts:
            raise ValueError("no predictors")
        return jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]

    def na_row_mask(self, *arrays):
        """1.0 where ANY predictor is NA (for missing_values_handling='Skip':
        those rows get weight 0, DataInfo.java Skip policy)."""
        import jax.numpy as jnp

        ncat = len(self.cat_names)
        any_na = jnp.zeros(arrays[0].shape[0], bool)
        for i in range(ncat):
            any_na = any_na | (arrays[i] < 0)
        for j in range(len(self.num_names)):
            any_na = any_na | jnp.isnan(arrays[ncat + j])
        return any_na.astype(jnp.float32)

    @staticmethod
    def response_weight(y, w=None):
        """Effective row weight: user weights × response-valid mask. Pad rows
        carry NA responses (NaN / -1 code), so they drop out here — the
        TPU-static-shape replacement for H2O's skipped NA-response rows."""
        import jax.numpy as jnp

        valid = (y >= 0) if jnp.issubdtype(y.dtype, jnp.integer) \
            else ~jnp.isnan(y)
        base = jnp.where(valid, 1.0, 0.0).astype(jnp.float32)
        if w is not None:
            base = base * jnp.where(jnp.isnan(w), 0.0, w).astype(jnp.float32)
        return base

    @staticmethod
    def clean_response(y):
        """Replace NA/pad sentinel with 0 so math stays finite (weights are
        already 0 there)."""
        import jax.numpy as jnp

        if jnp.issubdtype(y.dtype, jnp.integer):   # any code width (int8/16/32)
            return jnp.maximum(y, 0)
        return jnp.where(jnp.isnan(y), 0.0, y)
