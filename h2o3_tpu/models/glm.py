"""GLM — generalized linear models.

Reference: hex/glm/GLM.java + GLMTask.java (GLMIterationTask:1496 builds the
Gram matrix in a distributed pass; gram/Gram.java:15 cholesky :452), solvers
IRLSM / L-BFGS / coordinate descent (GLMModel.java:659), families
(GLMModel.java:649), elastic-net via ADMM (optimization/ADMM.java).

TPU-native design:
- The design matrix X (one-hot cats + standardized nums, hex/DataInfo.java)
  never exists for IRLS or for scoring one coefficient vector. The whole fit
  is ONE XLA program (`_irls_fit`): a shard_map over the mesh's rows whose
  while_loop walks the shard in row blocks; a block builds its design from
  the raw columns with the rows on lanes (data_info.design_rows), its part
  of Gram = XᵀWX as an MXU matmul (a one-hot is exact in bf16, so three
  bf16 passes against the weights' three bf16 pieces give f32 products) and
  of the score; the blocks' parts are summed compensated and psum'd over
  the shards — the GLMIterationTask MRTask and its tree-reduce.
  Multinomial, ordinal, the lambda path's lambda_max and p-values still
  expand the design (DataInfo.expand).
- Solve is a device Cholesky (jax.scipy cho_factor/cho_solve) on the (p+1)²
  Gram — H2O's gram/Gram.java:452 single-node solve, unchanged in spirit.
- L1 (elastic net) uses ADMM around the cached Cholesky factor, exactly the
  reference strategy (GLM.java IRLSM+ADMM), but each ADMM sweep is a jitted
  soft-threshold — no per-coefficient host loop.
- Multinomial uses full-batch L-BFGS (optax) on the softmax NLL — the
  reference's L_BFGS.java path (optimization/L_BFGS.java).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from h2o3_tpu.core.frame import Frame, T_CAT
from h2o3_tpu.core.runtime import cluster
from h2o3_tpu.models.data_info import DataInfo
from h2o3_tpu.models.model import Model, ModelCategory
from h2o3_tpu.models.model_builder import ModelBuilder, register
from h2o3_tpu.obs import metrics, tracing
from h2o3_tpu.ops.elementwise import bf16_pieces

EPS = 1e-10


# ---------------------------------------------------------------------------
# families (GLMModel.GLMParameters.Family, GLMModel.java:649)
# ---------------------------------------------------------------------------

class _Family:
    name = "gaussian"
    default_link = "identity"

    def variance(self, mu):
        import jax.numpy as jnp

        return jnp.ones_like(mu)

    def deviance(self, w, y, mu):
        return w * (y - mu) ** 2

    def init_mu(self, y, w):
        import jax.numpy as jnp

        ybar = jnp.sum(w * y) / jnp.maximum(jnp.sum(w), EPS)
        return jnp.broadcast_to(ybar, y.shape)


class _Gaussian(_Family):
    pass


class _Binomial(_Family):
    name = "binomial"
    default_link = "logit"

    def variance(self, mu):
        return mu * (1 - mu)

    def deviance(self, w, y, mu):
        import jax.numpy as jnp

        mu = jnp.clip(mu, EPS, 1 - EPS)
        return -2 * w * (y * jnp.log(mu) + (1 - y) * jnp.log1p(-mu))

    def init_mu(self, y, w):
        import jax.numpy as jnp

        ybar = jnp.sum(w * y) / jnp.maximum(jnp.sum(w), EPS)
        return jnp.broadcast_to(jnp.clip(ybar, 0.01, 0.99), y.shape)


class _Quasibinomial(_Binomial):
    name = "quasibinomial"


class _FractionalBinomial(_Binomial):
    name = "fractionalbinomial"


class _Poisson(_Family):
    name = "poisson"
    default_link = "log"

    def variance(self, mu):
        import jax.numpy as jnp

        return jnp.maximum(mu, EPS)

    def deviance(self, w, y, mu):
        import jax.numpy as jnp

        mu = jnp.maximum(mu, EPS)
        ylogy = jnp.where(y > 0, y * jnp.log(y / mu), 0.0)
        return 2 * w * (ylogy - (y - mu))

    def init_mu(self, y, w):
        import jax.numpy as jnp

        ybar = jnp.sum(w * y) / jnp.maximum(jnp.sum(w), EPS)
        return jnp.broadcast_to(jnp.maximum(ybar, 0.1), y.shape)


class _Gamma(_Family):
    name = "gamma"
    default_link = "log"  # reference default is inverse; log is the safe one

    def variance(self, mu):
        import jax.numpy as jnp

        return jnp.maximum(mu, EPS) ** 2

    def deviance(self, w, y, mu):
        import jax.numpy as jnp

        mu = jnp.maximum(mu, EPS)
        yy = jnp.maximum(y, EPS)
        return 2 * w * (-jnp.log(yy / mu) + (yy - mu) / mu)

    init_mu = _Poisson.init_mu


class _Tweedie(_Family):
    name = "tweedie"
    default_link = "tweedie"

    def __init__(self, var_power=1.5):
        self.var_power = float(var_power)

    def variance(self, mu):
        import jax.numpy as jnp

        return jnp.maximum(mu, EPS) ** self.var_power

    def deviance(self, w, y, mu):
        import jax.numpy as jnp

        p = self.var_power
        mu = jnp.maximum(mu, EPS)
        y0 = jnp.maximum(y, 0.0)
        return 2 * w * (y0 ** (2 - p) / ((1 - p) * (2 - p))
                        - y * mu ** (1 - p) / (1 - p) + mu ** (2 - p) / (2 - p))

    init_mu = _Poisson.init_mu


class _NegativeBinomial(_Family):
    name = "negativebinomial"
    default_link = "log"

    def __init__(self, theta=1.0):
        self.theta = float(theta)  # inverse dispersion

    def variance(self, mu):
        import jax.numpy as jnp

        return mu + self.theta * mu * mu

    def deviance(self, w, y, mu):
        import jax.numpy as jnp

        t = 1.0 / self.theta
        mu = jnp.maximum(mu, EPS)
        ylogy = jnp.where(y > 0, y * jnp.log(y / mu), 0.0)
        return 2 * w * (ylogy - (y + t) * jnp.log((y + t) / (mu + t)))

    init_mu = _Poisson.init_mu


# links (hex/LinkFunction.java)
class _Link:
    @staticmethod
    def of(name: str, tweedie_link_power: float = 0.0):
        import jax.numpy as jnp

        if name == "identity":
            return (lambda mu: mu, lambda eta: eta, lambda mu: jnp.ones_like(mu))
        if name == "log":
            return (lambda mu: jnp.log(jnp.maximum(mu, EPS)),
                    lambda eta: jnp.exp(jnp.clip(eta, -30, 30)),
                    lambda mu: 1.0 / jnp.maximum(mu, EPS))
        if name == "logit":
            return (lambda mu: jnp.log(jnp.clip(mu, EPS, 1 - EPS) / (1 - jnp.clip(mu, EPS, 1 - EPS))),
                    lambda eta: 1.0 / (1.0 + jnp.exp(-eta)),
                    lambda mu: 1.0 / jnp.maximum(mu * (1 - mu), EPS))
        if name == "inverse":
            return (lambda mu: 1.0 / jnp.where(jnp.abs(mu) < EPS, EPS, mu),
                    lambda eta: 1.0 / jnp.where(jnp.abs(eta) < EPS, EPS, eta),
                    lambda mu: -1.0 / jnp.maximum(mu * mu, EPS))
        if name == "tweedie":
            lp = tweedie_link_power
            if lp == 0.0:
                return _Link.of("log")
            return (lambda mu: jnp.maximum(mu, EPS) ** lp,
                    lambda eta: jnp.maximum(eta, EPS) ** (1.0 / lp),
                    lambda mu: lp * jnp.maximum(mu, EPS) ** (lp - 1))
        raise ValueError(f"unknown link {name}")


def _make_family(name: str, params: dict) -> _Family:
    name = name.lower()
    if name == "tweedie":
        return _Tweedie(params.get("tweedie_variance_power", 1.5))
    if name == "negativebinomial":
        return _NegativeBinomial(params.get("theta", 1.0))
    m = {"gaussian": _Gaussian, "binomial": _Binomial, "quasibinomial": _Quasibinomial,
         "fractionalbinomial": _FractionalBinomial, "poisson": _Poisson, "gamma": _Gamma}
    if name not in m:
        raise ValueError(f"unknown GLM family {name!r}")
    return m[name]()


# ---------------------------------------------------------------------------
# jitted solver cores
# ---------------------------------------------------------------------------

TIKHONOV_REFINEMENTS = 2       # of the jittered solve, on the true residual
IRLS_BLOCK_ELEMS = 1 << 26     # design entries a row block may hold
IRLS_BLOCK_MAX = 1 << 17


def irls_block_rows(n_shard: int, lanes: int) -> int:
    """Rows of one IRLS block, from shape alone: the largest power of two
    whose (lanes, rows) design stays under IRLS_BLOCK_ELEMS entries, at most
    IRLS_BLOCK_MAX and at most the shard (a shard no longer than a block is
    one block: whole and blocked are one program). 65,536 rows at the
    airline design's 696 lanes: the bf16 operands of a block are 0.36 GB,
    and the 1.9 MB Gram update a block is a twentieth of its matmul."""
    blk = 1 << max((IRLS_BLOCK_ELEMS // max(lanes, 1)).bit_length() - 1, 10)
    return int(min(blk, IRLS_BLOCK_MAX, max(n_shard, 1)))


def gram_form(layout) -> str:
    """How a block's Gram is computed, from the design's shape: `onehot3`
    where it has categorical columns (the one-hot is exact in bf16, so
    three bf16 passes against the weights' three bf16 pieces give the f32
    products), `dense` (f32 at `highest`) where it has none."""
    return "onehot3" if layout.cards else "dense"


def _kahan_add(acc, x):
    """Compensated sum of the blocks' partials: (sum, carry) + x. The error
    of the total is a rounding or two whatever the number of blocks; a
    pairwise sum would bound it by their logarithm but has to keep a
    partial Gram a level, and a plain sum's error grows with the blocks."""
    s, c = acc
    y = x - c
    t = s + y
    return t, (t - s) - y


@functools.partial(__import__("jax").jit, static_argnames=(
    "layout", "blk", "mesh", "famname", "linkname", "max_iter", "var_power",
    "link_power", "with_intercept", "non_negative"))
def _irls_fit(arrays, moments, y, w, offset, beta0, lam_l2, lam_l1, beta_eps,
              *, layout, blk, mesh, famname, linkname, max_iter,
              var_power=1.5, link_power=0.0, with_intercept=True,
              non_negative=False):
    """Full IRLS in one XLA program (lax.while_loop). Returns (beta, iters,
    deviance).

    The design never exists as a (rows, p) matrix. Inside a shard_map over
    the mesh's "rows" axis an iteration walks its shard in blocks of `blk`
    rows (irls_block_rows); a block builds its design from the raw columns
    with the rows on lanes (data_info.design_rows), computes eta, mu, the
    IRLS weights and its part of the Gram G = X'WX and of the score
    g = X'W(y - mu)g'(mu), and the parts are summed compensated
    (_kahan_add), then psum'd over the shards (the GLMIterationTask
    analog). The step solves G b = q with q = G beta + g, which is X'Wz of
    the textbook working response z = X beta + (y - mu)g'(mu) with the part
    that cancels taken out of the f32 sum over the rows: the fixed point is
    g = 0 whatever rounding G has."""
    import jax
    import jax.numpy as jnp
    import jax.scipy.linalg as jsl
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.compat import pcast, shard_map
    from h2o3_tpu.models.data_info import design_rows, lane_beta

    fam = _make_family(famname, {"tweedie_variance_power": var_power})
    link, linkinv, dlink = _Link.of(linkname, link_power)
    hi = jax.lax.Precision.HIGHEST

    p = layout.n_coefs
    pi = p + 1                                # intercept last
    kc, nn = layout.n_cat_coefs, layout.n_num
    Lc = int(sum(layout.padded))              # lanes of the one-hot
    nd = nn + 1                               # dense columns: numerics, ones
    lane_coef = layout.lane_coef()
    icpt = 1.0 if with_intercept else 0.0
    form = gram_form(layout)

    Lp = -(-(Lc + nd + 1) // 8) * 8           # rows of one bf16 piece

    def local_fit(arrays, moments, y, w, offset, b_init, lam_l2, lam_l1,
                  beta_eps):
        n = y.shape[0]
        nblk = -(-n // blk)

        def block(i):
            """Rows [i*blk, (i+1)*blk) of the shard; the last block starts
            early enough to be whole and gives the rows it shares with the
            one before it no weight."""
            start = jnp.minimum(i * blk, n - blk)
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, blk)
            fresh = start + jnp.arange(blk) >= i * blk
            return (tuple(sl(a) for a in arrays), sl(y),
                    jnp.where(fresh, sl(w), 0.0), sl(offset))

        def eta_of(O, D, beta, lb):
            """x . beta of a block (no offset): numerics and intercept, and
            a categorical column's coefficient by select over its lanes."""
            eta = beta[kc:kc + nn] @ D + beta[p] * icpt
            if O is not None:
                eta = eta + jnp.sum(jnp.where(O, lb, 0.0), axis=0)
            return eta

        def gram_pass(beta):
            """-> (G, g): X'WX and the score at beta, over the shards."""
            lb = lane_beta(layout, beta)

            def body(i, acc):
                cols, yb, wb, ob = block(i)
                with jax.named_scope("irls/design"):
                    O, D = design_rows(layout, moments, cols)
                    Di = jnp.concatenate(
                        [D, jnp.full((1, blk), icpt, jnp.float32)])
                    eta = eta_of(O, D, beta, lb) + ob
                    mu = linkinv(eta)
                    gp = dlink(mu)
                    # the variance floored where the link's derivative is:
                    # a row whose f32 mu rounds to 1 has variance 0, and
                    # 0 * (1/EPS)^2 under the floor below would weigh it
                    # 1/EPS = 1e10 rows' worth where it should weigh none
                    var = jnp.maximum(fam.variance(mu), EPS)
                    wls = wb / jnp.maximum(var * gp * gp, EPS)
                    u = wls * (yb - mu) * gp
                with jax.named_scope("irls/gram"):
                    # dense x dense, and the dense side of the score
                    R = jnp.concatenate([Di * wls[None, :], u[None, :]])
                    dd = jax.lax.dot_general(
                        Di, R, (((1,), (1,)), ((), ())), precision=hi)
                    parts = [dd]
                    if form == "onehot3":
                        # one-hot (exact in bf16) x three bf16 pieces of the
                        # weighted dense columns and of the score's values:
                        # products exact, f32 accumulation
                        Rz = jnp.pad(R, ((0, Lp - Lc - nd - 1), (0, 0)))
                        V = jnp.concatenate(
                            [jnp.concatenate([jnp.where(O, wk[None, :], 0.0),
                                              rk])
                             for wk, rk in zip(bf16_pieces(wls),
                                               bf16_pieces(Rz))])
                        A = jax.lax.dot_general(
                            O.astype(jnp.bfloat16), V.astype(jnp.bfloat16),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        A = A.reshape(Lc, 3, Lp)             # (3 Lp, blk) V
                        parts.append(A[:, 2] + A[:, 1] + A[:, 0])
                return tuple(_kahan_add(a, x) for a, x in zip(acc, parts))

            zeros = lambda *s: pcast(jnp.zeros(s, jnp.float32), ("rows",),
                                     to="varying")
            acc0 = [(zeros(nd, nd + 1), zeros(nd, nd + 1))]
            if form == "onehot3":
                acc0.append((zeros(Lc, Lp), zeros(Lc, Lp)))
            acc = jax.lax.fori_loop(0, nblk, body, tuple(acc0))
            dd = jax.lax.psum(acc[0][0], "rows")
            G_dd, g_d = dd[:, :nd], dd[:, nd]
            if form != "onehot3":
                return G_dd, g_d
            A = jax.lax.psum(acc[1][0], "rows")[lane_coef]     # (kc, ...)
            G_cc, G_cd, g_c = A[:, :Lc][:, lane_coef], A[:, Lc:Lc + nd], \
                A[:, Lc + nd]
            G = jnp.concatenate([jnp.concatenate([G_cc, G_cd], axis=1),
                                 jnp.concatenate([G_cd.T, G_dd], axis=1)])
            return G, jnp.concatenate([g_c, g_d])

        def dev_of(beta):
            lb = lane_beta(layout, beta)

            def body(i, acc):
                cols, yb, wb, ob = block(i)
                O, D = design_rows(layout, moments, cols)
                mu = linkinv(eta_of(O, D, beta, lb) + ob)
                return _kahan_add(acc, jnp.sum(fam.deviance(wb, yb, mu)))

            with jax.named_scope("irls/deviance"):
                zero = pcast(jnp.float32(0), ("rows",), to="varying")
                dev, _ = jax.lax.fori_loop(0, nblk, body, (zero, zero))
                return jax.lax.psum(dev, "rows")

        def admm_solve(G, q, l1, rho=1.0, sweeps=50):
            """min ½βᵀGβ - qᵀβ + l1·|β|₁ (+ β≥0 when non_negative; no penalty
            or bound on the intercept) via ADMM (optimization/ADMM.java — the
            reference handles the non-negative bound inside the same ADMM):
            cached Cholesky of G+ρI, jitted sweeps. Unlike a coordinate clip
            of the Newton step, the projection INSIDE ADMM converges to the
            true constrained optimum."""
            Grho = G + rho * jnp.eye(pi, dtype=G.dtype)
            cf = jsl.cho_factor(Grho)
            pen = jnp.concatenate([jnp.full(p, l1), jnp.zeros(1)])

            def sweep(carry, _):
                z, u = carry
                b = jsl.cho_solve(cf, q + rho * (z - u))
                z2 = jnp.sign(b + u) * jnp.maximum(jnp.abs(b + u) - pen / rho,
                                                   0.0)
                if non_negative:
                    z2 = z2.at[:p].set(jnp.maximum(z2[:p], 0.0))
                return (z2, u + b - z2), None

            (z, _), _ = jax.lax.scan(
                sweep, (jnp.zeros(pi, G.dtype), jnp.zeros(pi, G.dtype)),
                None, length=sweeps)
            return z

        def body(carry):
            beta, it, _prev = carry
            G, g = gram_pass(beta)
            with jax.named_scope("irls/solve"):
                # intercept=False: the zeroed ones-column gives G[p] = 0 and
                # q[p] = 0, and the ridge eps pins beta[p] to exactly 0, so
                # downstream scoring needs no special case
                pen = jnp.concatenate([jnp.ones(p), jnp.zeros(1)])
                Greg = G + lam_l2 * jnp.diag(pen)
                use_admm = (lam_l1 > 0) | non_negative
                # jitter scaled to the Gram's magnitude: collinear designs
                # (e.g. one-hot groups summing to the intercept) stay
                # solvable in f32, their free direction pinned to zero
                jitter = 1e-6 * (jnp.trace(Greg) / pi + 1.0)

                def newton():
                    """Greg b = G beta + g by iterated Tikhonov, as steps
                    from beta. The first solve is (Greg + jitter I)^-1 of
                    the right-hand side, a ridge that shrinks a direction
                    of curvature c by jitter / (c + jitter): 1e-3 of a rare
                    level's coefficient on a 300-level column. Each
                    refinement on the true system's residual takes that
                    share to its next power, and leaves a free direction
                    where the ridge pinned it. Solving for the step keeps
                    the f32 Cholesky's error a share of the step, not of
                    beta."""
                    cf = jsl.cho_factor(
                        Greg + jitter * jnp.eye(pi, dtype=G.dtype))
                    rhs = g - lam_l2 * pen * beta
                    step = jsl.cho_solve(cf, rhs - jitter * beta)
                    for _ in range(TIKHONOV_REFINEMENTS):
                        step = step + jsl.cho_solve(
                            cf, rhs - jnp.dot(Greg, step, precision=hi))
                    return beta + step

                beta_new = jax.lax.cond(
                    use_admm,
                    lambda: admm_solve(
                        Greg, jnp.dot(G, beta, precision=hi) + g, lam_l1),
                    newton)
            return beta_new, it + 1, beta

        def cond(carry):
            beta, it, prev = carry
            delta = jnp.max(jnp.abs(beta - prev))
            return (it < max_iter) & (delta > beta_eps)

        beta, iters, _ = jax.lax.while_loop(
            cond, body, (b_init, jnp.int32(0), b_init + 1e3))
        return beta, iters, dev_of(beta)

    mu0 = fam.init_mu(y, w)
    init_icpt = jnp.mean(link(mu0)) if with_intercept else 0.0
    b_init = jnp.where(jnp.any(beta0 != 0), beta0,
                       jnp.zeros(pi).at[p].set(init_icpt))
    rows, rep = P("rows"), P()
    fn = shard_map(local_fit, mesh=mesh,
                   in_specs=(tuple(rows for _ in arrays),
                             tuple(rep for _ in moments), rows, rows, rows,
                             rep, rep, rep, rep),
                   out_specs=(rep, rep, rep))
    return fn(tuple(arrays), tuple(moments), y, w, offset, b_init, lam_l2,
              lam_l1, beta_eps)


@functools.partial(__import__("jax").jit, static_argnames=("expand", "nclasses", "max_iter"))
def _multinomial_fit(arrays, y, w, beta0, lam_l2, *, expand, nclasses, max_iter):
    """Softmax regression via full-batch L-BFGS (optimization/L_BFGS.java)."""
    import jax
    import jax.numpy as jnp
    import optax

    X = expand(*arrays)
    N, p = X.shape
    Xi = jnp.concatenate([X, jnp.ones((N, 1), X.dtype)], axis=1)
    yi = y.astype(jnp.int32)
    wsum = jnp.maximum(jnp.sum(w), EPS)

    def loss(B):
        logits = Xi @ B                        # (N, K)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        rows = jnp.arange(N)
        nll = jnp.sum(w * (lse - logits[rows, yi])) / wsum
        return nll + 0.5 * lam_l2 * jnp.sum(B[:-1] ** 2) / wsum

    opt = optax.lbfgs()
    B0 = beta0

    def step(carry):
        B, state, it = carry
        value, grad = optax.value_and_grad_from_state(loss)(B, state=state)
        updates, state = opt.update(grad, state, B, value=value, grad=grad, value_fn=loss)
        return optax.apply_updates(B, updates), state, it + 1

    # optax<0.2.3 spells the l2 norm tree_l2_norm; newer optax tree_norm
    _tree_norm = getattr(optax.tree_utils, "tree_norm",
                         getattr(optax.tree_utils, "tree_l2_norm", None))

    def cond(carry):
        B, state, it = carry
        g = optax.tree_utils.tree_get(state, "grad")
        # state grad is zeros before the first step — always take step 0
        return (it < max_iter) & ((it == 0) | (_tree_norm(g) > 1e-6))

    B, state, iters = jax.lax.while_loop(cond, step, (B0, opt.init(B0), jnp.int32(0)))
    return B, iters, loss(B) * wsum


def _ordinal_class_probs(X, v):
    """Shared fit/predict math: parameter vector (p coefs, K-1 raw
    threshold params) -> (N, K) class probabilities. Thresholds resolve as
    theta_0 + cumsum(softplus(d_j)) — ordered by construction."""
    import jax
    import jax.numpy as jnp

    p = X.shape[1]
    beta, traw = v[:p], v[p:]
    th = traw[0] + jnp.concatenate(
        [jnp.zeros(1), jnp.cumsum(jax.nn.softplus(traw[1:]))])
    eta = X @ beta
    cum = jax.nn.sigmoid(th[None, :] - eta[:, None])           # (N, K-1)
    N = X.shape[0]
    cf = jnp.concatenate([jnp.zeros((N, 1), cum.dtype), cum,
                          jnp.ones((N, 1), cum.dtype)], 1)
    return cf[:, 1:] - cf[:, :-1]                              # (N, K)


@functools.partial(__import__("jax").jit,
                   static_argnames=("expand", "nclasses", "max_iter"))
def _ordinal_fit(arrays, y, w, lam_l2, *, expand, nclasses, max_iter):
    """Proportional-odds cumulative-logit fit (hex/glm Family.ordinal,
    GLM.java ordinal solver): P(y <= k) = sigmoid(theta_k - x*beta) with
    monotone thresholds, one shared beta, full-batch L-BFGS like
    multinomial."""
    import jax
    import jax.numpy as jnp
    import optax

    X = expand(*arrays)
    N, p = X.shape
    K = nclasses
    yi = y.astype(jnp.int32)
    wsum = jnp.maximum(jnp.sum(w), EPS)

    def loss(v):
        pk = _ordinal_class_probs(X, v)
        nll = -jnp.sum(w * jnp.log(jnp.maximum(
            pk[jnp.arange(N), yi], 1e-12))) / wsum
        return nll + 0.5 * lam_l2 * jnp.sum(v[:p] ** 2) / wsum

    v0 = jnp.zeros(p + K - 1, jnp.float32)
    # spread initial thresholds so classes start distinguishable
    v0 = v0.at[p].set(-1.0)
    opt = optax.lbfgs()

    def step(carry):
        v, state, it = carry
        value, grad = optax.value_and_grad_from_state(loss)(v, state=state)
        updates, state = opt.update(grad, state, v, value=value, grad=grad,
                                    value_fn=loss)
        return optax.apply_updates(v, updates), state, it + 1

    # optax<0.2.3 spells the l2 norm tree_l2_norm; newer optax tree_norm
    _tree_norm = getattr(optax.tree_utils, "tree_norm",
                         getattr(optax.tree_utils, "tree_l2_norm", None))

    def cond(carry):
        v, state, it = carry
        g = optax.tree_utils.tree_get(state, "grad")
        return (it < max_iter) & ((it == 0) | (_tree_norm(g) > 1e-6))

    v, state, iters = jax.lax.while_loop(cond, step,
                                         (v0, opt.init(v0), jnp.int32(0)))
    return v, iters, loss(v) * wsum


@functools.partial(__import__("jax").jit, static_argnames=("expand",))
def _ordinal_predict(arrays, v, *, expand):
    import jax.numpy as jnp

    X = expand(*arrays)
    return jnp.maximum(_ordinal_class_probs(X, v), 0.0)


@functools.partial(__import__("jax").jit, static_argnames=("dinfo", "linkname", "link_power", "nclasses"))
def _glm_predict(arrays, beta, offset, *, dinfo, linkname, link_power=0.0, nclasses=1):
    """mu of every row. One coefficient vector needs no expanded matrix:
    eta comes from codes and coefficients (DataInfo.linear_predictor), the
    DataInfo's moments closed over as program constants. The multinomial
    matrix of coefficients still goes through `expand`."""
    import jax
    import jax.numpy as jnp

    if nclasses > 2:
        X = dinfo.expand(*arrays)
        Xi = jnp.concatenate([X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)
        return jax.nn.softmax(Xi @ beta, axis=-1)
    _, linkinv, _ = _Link.of(linkname, link_power)
    return linkinv(dinfo.linear_predictor(arrays, beta) + offset)


# ---------------------------------------------------------------------------
# model + builder
# ---------------------------------------------------------------------------

def _interaction_frame(frame: Frame, interactions, response=None) -> Frame:
    """Append pairwise interaction columns (hex/DataInfo interaction/Wrapped
    Vec analog): every unordered pair of the listed columns gets a device
    product column.  numeric x numeric -> product; pairs involving an enum
    get per-LEVEL slicing (numeric masked by level / indicator products),
    the reference's expanded-interaction semantics."""
    import jax.numpy as jnp

    from h2o3_tpu.core.frame import Column, T_NUM

    cols = [c for c in interactions if c != response]
    missing = [c for c in cols if c not in frame]
    if missing:
        raise ValueError(f"interactions column(s) {missing} not in frame")
    out = Frame()
    for nm in frame.names:
        out.add(nm, frame.col(nm))
    nan = jnp.float32(jnp.nan)
    for i in range(len(cols)):
        for j in range(i + 1, len(cols)):
            a, b = cols[i], cols[j]
            ca, cb = frame.col(a), frame.col(b)
            if ca.is_categorical and cb.is_categorical:
                # NA in either factor propagates as NA (reference NA rules),
                # not as an all-zero indicator row
                na = (ca.data < 0) | (cb.data < 0)
                for la, lev_a in enumerate(ca.domain or []):
                    for lb, lev_b in enumerate(cb.domain or []):
                        v = ((ca.data == la) & (cb.data == lb)).astype(jnp.float32)
                        out.add(f"{a}_{lev_a}:{b}_{lev_b}",
                                Column(jnp.where(na, nan, v), T_NUM, frame.nrows))
            elif ca.is_categorical or cb.is_categorical:
                cat, num = (ca, cb) if ca.is_categorical else (cb, ca)
                catn, numn = (a, b) if ca.is_categorical else (b, a)
                na = cat.data < 0
                for li, lev in enumerate(cat.domain or []):
                    v = jnp.where(cat.data == li, num.data, 0.0)
                    out.add(f"{catn}_{lev}:{numn}",
                            Column(jnp.where(na, nan, v), T_NUM, frame.nrows))
            else:
                out.add(f"{a}:{b}",
                        Column(ca.data * cb.data, T_NUM, frame.nrows))
    return out


class GLMModel(Model):
    algo_name = "glm"

    def predict(self, frame: Frame, key=None) -> Frame:
        # munge→score splice: a frame fed by a still-pending lazy Rapids
        # pipeline scores through ONE `pipeline`-family program over the
        # fused feature plans — no engineered Column materializes. Any
        # frame the splice cannot hold takes the staged adapt→expand path.
        from h2o3_tpu import pipeline

        try:
            raw = pipeline.try_glm_raw(self, frame)
        except Exception:   # noqa: BLE001 — staged path is the contract
            raw = None
        if raw is not None:
            return self._raw_to_frame(raw, frame.nrows, key)
        return super().predict(frame, key)

    def adapt_test(self, test: Frame) -> Frame:
        ints = self._parms.get("interactions")
        if ints:
            # remap interaction enums onto the TRAINING domains FIRST, so
            # the expansion emits every training level's column (a level
            # absent from the test frame must become an all-zero indicator,
            # not an NA-backfilled missing column)
            pre = Frame()
            for nm in test.names:
                c = test.col(nm)
                if nm in ints:
                    c = self._remap_col(c, self._output.domains.get(nm))
                pre.add(nm, c)
            test = _interaction_frame(pre, list(ints),
                                      self._output.response_name)
        return super().adapt_test(test)

    def __init__(self, parms=None):
        super().__init__(parms=parms)
        self.beta: Optional[np.ndarray] = None       # device array (p+1,) or (p+1,K)
        self.dinfo: Optional[DataInfo] = None
        self.linkname: str = "identity"
        self.link_power: float = 0.0
        self.null_deviance = float("nan")
        self.residual_deviance = float("nan")
        self.aic = float("nan")
        self.iterations = 0
        self.p_values: Optional[np.ndarray] = None
        self.std_errors: Optional[np.ndarray] = None

    def _predict_raw(self, frame: Frame):
        import jax.numpy as jnp

        cols = self.dinfo.cols(frame)
        arrays = tuple(c.data for c in cols)
        K = self._output.nclasses
        if K > 2:
            if self.linkname == "ordinal":
                return {"probs": _ordinal_predict(arrays, self.beta,
                                                  expand=self.dinfo.expand)}
            probs = _glm_predict(arrays, self.beta, 0.0, dinfo=self.dinfo,
                                 linkname=self.linkname, nclasses=K)
            return {"probs": probs}
        offset = 0.0
        if self._parms.get("offset_column") and self._parms["offset_column"] in frame:
            offset = frame.col(self._parms["offset_column"]).data
        mu = _glm_predict(arrays, self.beta, offset, dinfo=self.dinfo,
                          linkname=self.linkname, link_power=self.link_power)
        if K == 2:
            return {"probs": jnp.stack([1 - mu, mu], axis=-1)}
        return {"value": mu}

    def coef(self) -> Dict[str, float]:
        """De-standardized coefficients keyed by expanded name + Intercept
        (GLMModel.coefficients())."""
        if self.linkname == "ordinal":
            return self._coef_ordinal(destandardize=True)
        names = self.dinfo.coef_names() + ["Intercept"]
        b = np.asarray(self.beta, np.float64)
        if self.dinfo.standardize:
            b = b.copy()
            k = self.dinfo.num_offset
            s = np.asarray(self.dinfo.num_sigmas, np.float64)
            m = np.asarray(self.dinfo.num_means, np.float64)
            nn = len(self.dinfo.num_names)
            if nn:
                if b.ndim == 2:  # multinomial: per-class columns
                    b[-1, :] -= (b[k:k + nn, :] * (m / s)[:, None]).sum(axis=0)
                    b[k:k + nn, :] = b[k:k + nn, :] / s[:, None]
                else:
                    b[-1] -= float(np.sum(b[k:k + nn] * m / s))
                    b[k:k + nn] = b[k:k + nn] / s
        if b.ndim == 2:
            return {n: b[i].tolist() for i, n in enumerate(names)}
        return {n: float(b[i]) for i, n in enumerate(names)}

    def _coef_ordinal(self, destandardize: bool) -> Dict[str, float]:
        """Ordinal layout is (p coefs, K-1 raw threshold params); report
        coefs + RESOLVED thresholds theta_k. De-standardization: the cum
        logit is theta_k - x·beta, so beta_j /= sigma_j and every theta
        shifts by +sum(beta_j mu_j / sigma_j) (spacings unchanged)."""
        p = len(self.dinfo.coef_names())
        v = np.asarray(self.beta, np.float64)
        beta, traw = v[:p].copy(), v[p:]
        th = traw[0] + np.concatenate(
            [[0.0], np.cumsum(np.logaddexp(0.0, traw[1:]))])   # softplus
        if destandardize and self.dinfo.standardize:
            k = self.dinfo.num_offset
            s = np.asarray(self.dinfo.num_sigmas, np.float64)
            m = np.asarray(self.dinfo.num_means, np.float64)
            nn = len(self.dinfo.num_names)
            if nn:
                th = th + float(np.sum(beta[k:k + nn] * m / s))
                beta[k:k + nn] = beta[k:k + nn] / s
        out = {n: float(beta[i])
               for i, n in enumerate(self.dinfo.coef_names())}
        for j, t in enumerate(th):
            out[f"theta_{j}"] = float(t)
        return out

    def coef_norm(self) -> Dict[str, float]:
        if self.linkname == "ordinal":
            return self._coef_ordinal(destandardize=False)
        names = self.dinfo.coef_names() + ["Intercept"]
        b = np.asarray(self.beta, np.float64)
        return {n: float(b[i]) for i, n in enumerate(names)}


@register
class GLM(ModelBuilder):
    algo_name = "glm"
    model_class = GLMModel
    # crash-survivable builds: the single-lambda IRLS runs in warm-started
    # chunks with durable beta between them, and the lambda-search path
    # persists per-lambda progress (model_builder._tick_job_progress)
    supports_iteration_resume = True
    # IRLS device programs are collective-free, so concurrent GLM builds
    # are safe to interleave (long proven by the parallel-grid path)
    parallel_safe = True

    @classmethod
    def default_params(cls):
        p = super().default_params()
        p.update({
            "family": "AUTO", "link": "family_default", "solver": "AUTO",
            "alpha": None, "lambda_": None, "lambda_search": False,
            "nlambdas": 30, "lambda_min_ratio": 1e-4,
            "standardize": True, "intercept": True,
            "max_iterations": 50, "beta_epsilon": 1e-4,
            "tweedie_variance_power": 1.5, "tweedie_link_power": 0.0,
            "theta": 1.0, "missing_values_handling": "MeanImputation",
            "compute_p_values": False, "remove_collinear_columns": False,
            "interactions": None, "non_negative": False,
        })
        return p

    def _resolve_family(self, train: Frame) -> str:
        fam = (self.params.get("family") or "AUTO").lower()
        resp = train.col(self.params["response_column"])
        if fam == "auto":
            if resp.is_categorical:
                fam = "binomial" if len(resp.domain or []) == 2 else "multinomial"
            else:
                fam = "gaussian"
        return fam

    def _fit(self, train: Frame) -> GLMModel:
        import jax
        import jax.numpy as jnp

        ints = self.params.get("interactions")
        if ints:
            # expanded interaction columns join the design BEFORE the output
            # schema is captured, so scoring's adapt_test re-expands test
            # frames identically (GLMModel.adapt_test)
            train = _interaction_frame(train, list(ints),
                                       self.params.get("response_column"))
        fam = self._resolve_family(train)
        resp = self.params["response_column"]
        # validate BEFORE constructing the model (Keyed.__init__ installs it
        # into the DKV; failing later would leak a half-built key)
        resp_dom = train.col(resp).domain if train.col(resp).is_categorical else None
        if (fam in ("binomial", "quasibinomial", "fractionalbinomial")
                and resp_dom is not None and len(resp_dom) > 2):
            raise ValueError(
                f"family={fam} requires a binary response; "
                f"{resp!r} has {len(resp_dom)} levels (use family='multinomial')")
        lam_pre = self.params.get("lambda_")
        if isinstance(lam_pre, (list, tuple)):
            lam_pre = lam_pre[0]
        if self.params.get("compute_p_values") and (
                self.params.get("lambda_search") or (lam_pre or 0) != 0):
            # reference forbids p-values on penalized fits (GLM.java
            # compute_p_values validation): shrunken coefficients make the
            # information-matrix std errors statistically invalid
            raise ValueError("compute_p_values requires lambda=0 and no lambda_search")

        if fam == "ordinal" and (resp_dom is None or len(resp_dom) < 3):
            raise ValueError("family='ordinal' needs a categorical response "
                             "with at least 3 ordered levels")
        model = GLMModel(parms=dict(self.params))
        self._init_output(model, train)
        if fam in ("multinomial", "ordinal"):
            model._output.model_category = ModelCategory.Multinomial
        elif fam in ("binomial", "quasibinomial", "fractionalbinomial"):
            # numeric 0/1 response is accepted for binomial (GLM.java allows
            # quasibinomial numerics); surface it as a 2-class classifier
            model._output.model_category = ModelCategory.Binomial
            if model._output.response_domain is None:
                model._output.response_domain = ["0", "1"]
        # no intercept ⇒ keep ALL factor levels (GLM.java:540 forces
        # useAllFactorLevels) and fit in RAW space: mean-centering would pin
        # the prediction to linkInv(0) at the feature MEANS, a meaningless
        # constraint that also breaks coef() de-standardization
        with_icpt = bool(self.params.get("intercept", True))
        # stage span ``design``: DataInfo reads the rollups and modes of the
        # predictors on the host (cached on the columns after a frame's
        # first job), which is where it has always blocked
        with tracing.span("design", rows=train.nrows):
            dinfo = DataInfo(train, response=resp,
                             ignored=self.params.get("ignored_columns") or (),
                             weights=self.params.get("weights_column"),
                             offset=self.params.get("offset_column"),
                             standardize=(bool(self.params.get("standardize",
                                                               True))
                                          and with_icpt),
                             use_all_factor_levels=not with_icpt)
        model.dinfo = dinfo

        cols = dinfo.cols(train)
        arrays = tuple(c.data for c in cols)
        y_col = train.col(resp)
        y_raw = y_col.data
        w = None
        if self.params.get("weights_column"):
            w = train.col(self.params["weights_column"]).data
        wts = DataInfo.response_weight(y_raw, w)
        if str(self.params.get("missing_values_handling", "")).lower() == "skip":
            wts = wts * (1.0 - dinfo.na_row_mask(*arrays))
        y = DataInfo.clean_response(y_raw).astype(jnp.float32)
        offset = jnp.zeros_like(y)
        if self.params.get("offset_column"):
            oc = train.col(self.params["offset_column"]).data
            offset = jnp.where(jnp.isnan(oc), 0.0, oc)

        alpha = self.params.get("alpha")
        alpha = 0.5 if alpha is None else (alpha[0] if isinstance(alpha, (list, tuple)) else float(alpha))
        lam = self.params.get("lambda_")
        if isinstance(lam, (list, tuple)):
            lam = lam[0]
        nobs = float(jnp.sum(wts))

        if fam == "ordinal":
            if not bool(self.params.get("intercept", True)) or \
                    bool(self.params.get("non_negative")):
                raise ValueError("intercept=False / non_negative are not "
                                 "supported for family='ordinal'")
            if self.params.get("offset_column"):
                raise ValueError("offset_column is not supported for "
                                 "family='ordinal'")
            K = len(y_col.domain or [])
            lam = 0.0 if lam is None else float(lam)
            v, iters, dev = _ordinal_fit(
                arrays, y, wts, lam * (1 - alpha) * nobs,
                expand=dinfo.expand, nclasses=K,
                max_iter=int(self.params["max_iterations"]))
            model.beta = v
            model.iterations = int(iters)
            model.residual_deviance = 2 * float(dev)
            model.linkname = "ordinal"
            return model

        if fam == "multinomial":
            if not bool(self.params.get("intercept", True)) or \
                    bool(self.params.get("non_negative")):
                raise ValueError("intercept=False / non_negative are not "
                                 "supported for family='multinomial'")
            K = len(y_col.domain or [])
            lam = 0.0 if lam is None else float(lam)
            B0 = jnp.zeros((dinfo.fullN + 1, K), jnp.float32)
            B, iters, dev = _multinomial_fit(
                arrays, y, wts, B0, lam * (1 - alpha) * nobs,
                expand=dinfo.expand, nclasses=K,
                max_iter=int(self.params["max_iterations"]))
            model.beta = B
            model.iterations = int(iters)
            model.residual_deviance = 2 * float(dev)
            model.linkname = "multinomial"
            return model

        linkname = self.params.get("link") or "family_default"
        if linkname in ("family_default", None, "AUTO"):
            linkname = _make_family(fam, self.params).default_link
        model.linkname = linkname
        model.link_power = float(self.params.get("tweedie_link_power", 0.0))

        if lam is None and not self.params.get("lambda_search"):
            lam = 0.0 if self.params.get("compute_p_values") else 1e-5
        max_iter = int(self.params["max_iterations"])

        layout = dinfo.layout()
        mesh = cluster().mesh
        n_shard = int(y.shape[0]) // cluster().row_shards
        blk = irls_block_rows(n_shard,
                              sum(layout.padded) + layout.n_num + 2)
        form = gram_form(layout)

        static = dict(
            layout=layout, blk=blk, mesh=mesh, famname=fam, linkname=linkname,
            var_power=float(self.params["tweedie_variance_power"]),
            link_power=model.link_power,
            with_intercept=bool(self.params.get("intercept", True)),
            non_negative=bool(self.params.get("non_negative", False)))
        beta_eps = jnp.float32(self.params.get("beta_epsilon", 1e-4))

        def fit_one(lam_val, beta_init, max_it=None):
            """One IRLS program -> (beta, iterations as an int, deviance).
            Stage span ``irls``: from the dispatch to the iteration count's
            fetch, where the host has always blocked."""
            l2 = jnp.float32(float(lam_val) * (1 - alpha) * nobs)
            l1 = jnp.float32(float(lam_val) * alpha * nobs)
            with tracing.span("irls", p=dinfo.fullN + 1, gram_form=form,
                              row_blocks=-(-n_shard // blk)) as sp:
                beta, iters, dev = _irls_fit(
                    arrays, dinfo.moments(), y, wts, offset, beta_init,
                    l2, l1, beta_eps,
                    max_iter=max_iter if max_it is None else int(max_it),
                    **static)
                iters = int(iters)
                sp.set(iterations=iters)
            metrics.inc("h2o3_glm_iterations_total", iters)
            metrics.inc("h2o3_glm_gram_passes_total", iters, form=form)
            return beta, iters, dev

        pi = dinfo.fullN + 1
        b0 = jnp.zeros(pi, jnp.float32)
        if self.params.get("lambda_search"):
            # lambda path: geometric from lambda_max (smallest lambda that
            # zeros all coefs, GLM.java lambda_max) with warm starts. Training
            # deviance decreases monotonically along the path, so selection
            # uses the reference's no-holdout rule: stop when the relative
            # deviance improvement stalls (GLM.java devExplained early stop)
            # and keep the last lambda that still improved meaningfully.
            X0 = dinfo.expand(*arrays)
            g = np.abs(np.asarray((X0 * wts[:, None]).T @ (y - float(jnp.sum(wts * y) / nobs))))
            lam_max = float(g.max()) / max(alpha, 1e-3) / nobs
            nl = int(self.params.get("nlambdas", 30))
            path = lam_max * np.power(float(self.params["lambda_min_ratio"]), np.linspace(0, 1, nl))
            beta, prev_dev, chosen = b0, np.inf, path[0]
            fitted = 0
            null_dev_est = None
            start_i = 0
            rs = self._take_resume_state("glm_lambda_path")
            if rs is not None:
                # durable-progress fast-forward: warm-start beta and the
                # stall-stop bookkeeping at the saved path position (the
                # path itself re-derives deterministically from the data)
                beta = jnp.asarray(rs["beta"])
                prev_dev = float(rs["prev_dev"])
                chosen = float(rs["chosen"])
                fitted = int(rs["fitted"])
                null_dev_est = rs.get("null_dev_est")
                start_i = int(rs["next_index"])
            jp_every = self._job_ckpt_every()
            for li in range(start_i, len(path)):
                lv = path[li]
                beta_new, iters, dev = fit_one(lv, beta)
                fitted += 1
                dev = float(dev)
                if null_dev_est is None:
                    null_dev_est = dev     # at lambda_max all coefs are 0
                # stall-stop only AFTER the path has started explaining
                # deviance — near lambda_max nothing is active yet and the
                # improvement is legitimately ~0 (GLM.java walks on)
                started = dev < null_dev_est * 0.999
                if (prev_dev < np.inf and started
                        and dev > prev_dev * (1 - 1e-4)):
                    break  # improvement stalled: keep previous lambda's fit
                beta, prev_dev, chosen = beta_new, dev, lv
                if jp_every and (li + 1) % jp_every == 0:
                    self._tick_job_progress(li + 1, lambda: {
                        "phase": "glm_lambda_path",
                        "beta": np.asarray(beta),
                        "prev_dev": float(prev_dev),
                        "chosen": float(chosen), "fitted": fitted,
                        "null_dev_est": null_dev_est,
                        "next_index": li + 1})
                if self._out_of_time():
                    break  # wall budget: keep the path fit so far
            dev = prev_dev
            model.iterations = fitted
            self.params["lambda_"] = float(chosen)
        else:
            jp_every = self._job_ckpt_every()
            rs = self._take_resume_state("glm_irls")
            if jp_every > 0 or rs is not None:
                # chunked IRLS: warm-started segments of jp_every Newton
                # steps with durable beta between them — a resumed dispatch
                # continues the same trajectory from the last chunk instead
                # of refitting from zero
                beta, it_done, dev = b0, 0, 0.0
                if rs is not None:
                    beta = jnp.asarray(rs["beta"])
                    it_done = int(rs["iters_done"])
                    dev = float(rs.get("dev", 0.0))
                chunk = jp_every if jp_every > 0 else max_iter
                while it_done < max_iter:
                    step = min(chunk, max_iter - it_done)
                    beta, its, dev = fit_one(lam, beta, max_it=step)
                    it_done += int(its)
                    self._tick_job_progress(it_done, lambda: {
                        "phase": "glm_irls", "beta": np.asarray(beta),
                        "iters_done": it_done, "dev": float(dev)})
                    if int(its) < step:
                        break            # converged inside the chunk
                    if self._out_of_time():
                        break
                iters = it_done
                model.iterations = int(iters)
            else:
                beta, iters, dev = fit_one(lam, b0)
                model.iterations = int(iters)

        model.beta = beta
        model.residual_deviance = float(dev)
        # regression metrics report mean_residual_deviance in the family's
        # deviance, not MSE (hex/ModelMetricsRegression); Tweedie only where
        # the shared Distribution supports the variance power
        tvp = float(self.params["tweedie_variance_power"])
        if fam in ("gaussian", "poisson", "gamma") or (fam == "tweedie" and 1.0 < tvp < 2.0):
            from h2o3_tpu.models.distribution import get_distribution

            model._distribution = get_distribution(fam, tweedie_power=tvp)
        # null deviance: intercept-only model — for every supported family the
        # MLE of a constant mean is the weighted response mean, so this is a
        # closed form (GLMModel nullDeviance), no second fit needed
        family = _make_family(fam, self.params)
        if bool(self.params.get("intercept", True)):
            null_mu = jnp.sum(wts * y) / jnp.maximum(jnp.sum(wts), EPS)
        else:
            # no-intercept null model predicts linkInv(0) (GLM.java:609 _ymu)
            _, _linkinv, _ = _Link.of(linkname, model.link_power)
            null_mu = _linkinv(jnp.float32(0.0))
        model.null_deviance = float(jnp.sum(family.deviance(
            wts, y, jnp.broadcast_to(null_mu, y.shape))))
        rank = int(np.sum(np.abs(np.asarray(beta)) > 1e-10))
        model.aic = model.residual_deviance + 2 * rank

        if self.params.get("compute_p_values") and (lam or 0) == 0:
            self._p_values(model, arrays, y, wts, offset, dinfo, fam, linkname)
        return model

    def _p_values(self, model, arrays, y, wts, offset, dinfo, fam, linkname):
        """z-scores/p-values from the unregularized information matrix
        (GLM.java compute_p_values; needs lambda=0)."""
        import jax.numpy as jnp
        from scipy import stats

        family = _make_family(fam, self.params)
        link, linkinv, dlink = _Link.of(linkname, model.link_power)
        X = dinfo.expand(*arrays)
        Xi = jnp.concatenate([X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)
        eta = Xi @ model.beta + offset
        mu = linkinv(eta)
        gp = dlink(mu)
        wls = wts / jnp.maximum(family.variance(mu) * gp * gp, EPS)
        G = np.asarray((Xi * wls[:, None]).T @ Xi, np.float64)
        try:
            cov = np.linalg.inv(G)
        except np.linalg.LinAlgError:
            return
        se = np.sqrt(np.maximum(np.diag(cov), 0))
        b = np.asarray(model.beta, np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            z = b / se
        model.std_errors = se
        model.p_values = 2 * (1 - stats.norm.cdf(np.abs(z)))
