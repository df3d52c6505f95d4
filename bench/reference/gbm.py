"""Plain reference for the bernoulli histogram GBM a configuration states.

Straight ``jax.numpy`` in float32 (histogram matmuls at ``HIGHEST``), row
block by row block, with every sum over blocks taken in float64 on the host.
It imports nothing of the program and takes nothing the program made as an
input: data come from the benchmark's own recipe, bin edges from its own
quantiles, gradients and margins from its own arithmetic.

Two uses:

* ``check_forest`` judges a forest somebody else grew (the program's, or the
  control's) the way a served token is judged: teacher-forced. It follows the
  forest's first ``k_follow`` trees node by node on the reference's own
  margins and reads, for every node, how far the split that was chosen lies
  below the reference's best split (``split_gain_gap``, the widest; and
  ``split_gain_loss``, all the gain forgone over all the gain to be had), how
  far each leaf
  value lies from the reference's Newton step (``leaf_gap``) and each node's
  row count from the reference's (``cover_gap``); then it runs the whole
  forest over every training row and compares the log loss that was reported
  with its own (``logloss_gap``). Bin edges and the prior are compared
  directly (``edge_gap``, ``init_gap``).
* ``grow`` is the reference put in the program's place: a free-running
  level-wise grower of the same algorithm, with the precision of each stage
  an argument (``PRECISIONS``). At ``control`` it is the lower-precision
  control, and ``fault=`` plants the faults a training cell can have.

The algorithm (H2O-3 GBM, bernoulli; squared-error splits on the gradient):
  f0 = logit(mean y), clipped to +-19
  per tree: z = y - sigmoid(f); per node and (feature, edge) candidate
    gain = GL^2/nL + GR^2/nR - G^2/n, both sides >= min_rows rows,
    split where the best gain > min_split_improvement, down to max_depth;
    leaf = learn_rate * sum(z) / sum(p(1-p)); f += leaf(row)
Bin edges: the nbins-quantiles (k/nbins, k=1..nbins-1, linear) of every
(n // 200000)-th row of a feature, duplicates dropped; x <= edge goes left.
"""

from __future__ import annotations

import functools
import math

import numpy as np

EDGE_SAMPLE = 200_000
MAX_BLOCK = 131_072
LEAF_CLIP = 1e4

# stage -> dtype name (None = float32). "stated" is what the configuration
# states (bf16 histogram operands, f32 elsewhere); "control" is the nearest
# precision below it at every stage.
PRECISIONS = {
    "reference": {"hist": None, "leaf": None, "edge": None, "margin": None},
    "stated": {"hist": "bfloat16", "leaf": None, "edge": None,
               "margin": None},
    "control": {"hist": "float8_e4m3fn", "leaf": "bfloat16",
                "edge": "bfloat16", "margin": "bfloat16"},
}


def block_rows(n: int) -> int:
    """Largest divisor of n that is at most MAX_BLOCK (blocks tile n)."""
    for b in range(min(n, MAX_BLOCK), 0, -1):
        if n % b == 0:
            return b
    return n


def n_nodes(max_depth: int) -> int:
    return 2 ** (max_depth + 1) - 1


def _round_np(a, dtype):
    if dtype is None:
        return np.asarray(a, np.float32)
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(a, jnp.float32).astype(dtype)
                      .astype(jnp.float32))


# ---------------------------------------------------------------------------
# bin edges
# ---------------------------------------------------------------------------

def quantile_edges(cols, nbins: int, dtype=None) -> list:
    """Per feature: ascending unique float32 edges (at most nbins - 1)."""
    qs = np.linspace(0.0, 1.0, nbins + 1)[1:-1]
    out = []
    for c in cols:
        n = c.shape[0]
        step = max(n // EDGE_SAMPLE, 1) if n > EDGE_SAMPLE else 1
        sample = np.asarray(c[::step], np.float64)
        e = np.quantile(sample, qs)
        e = _round_np(e[np.isfinite(e)], dtype)
        out.append(np.unique(e).astype(np.float32))
    return out


def pad_edges(edges: list, width: int) -> np.ndarray:
    ep = np.full((len(edges), width), np.inf, np.float32)
    for i, e in enumerate(edges):
        ep[i, : len(e)] = e
    return ep


# ---------------------------------------------------------------------------
# block programs
# ---------------------------------------------------------------------------

def _rounded(x, dtype):
    import jax.numpy as jnp

    if dtype is None:
        return x
    return x.astype(getattr(jnp, dtype)).astype(jnp.float32)


def _block_X(cols, start, B):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jax.lax.dynamic_slice(c, (start,), (B,))
                      for c in cols], axis=1)


def _step(X, node, feat, thr, left, right):
    """One level of raw-threshold traversal for a block: where each row
    stands (internal or not), which way it goes, and where it lands."""
    import jax
    import jax.numpy as jnp

    fe = feat[node]
    internal = fe >= 0
    pick = jax.nn.one_hot(jnp.maximum(fe, 0), X.shape[1], dtype=jnp.bool_)
    xv = jnp.sum(jnp.where(pick, X, 0.0), axis=1)
    go_left = xv <= thr[node]
    nxt = jnp.where(go_left, left[node], right[node])
    return internal, go_left, jnp.where(internal, nxt, node)


@functools.lru_cache(maxsize=16)
def _tree_pass_fn(B: int, F: int, depth: int, nb: int, hist_dtype, leaf_dtype):
    """(cols, y, f, start, limit, one tree, edges) -> sums of this block's
    rows below row `limit`:
    hist (F*nb, 2M) of [1, z] by (feature bin) x (node visited above the
    last level); split (M, 2) of [1, z] over rows going LEFT at each internal
    node; tot (M, 2); leaf (M, 3) of [1, z, p(1-p)] by final node; and the
    final node of every row."""
    import jax
    import jax.numpy as jnp

    M = n_nodes(depth)

    def tree_pass(cols, y, f, start, limit, feat, thr, left, right, edges):
        X = _block_X(cols, start, B)
        yb = jax.lax.dynamic_slice(y, (start,), (B,)).astype(jnp.float32)
        # rows at or past `limit` are left out of every sum
        ones = ((start + jnp.arange(B)) < limit).astype(jnp.float32)
        p = jax.nn.sigmoid(f)
        z = (yb - p) * ones
        h = p * (1.0 - p) * ones
        vals = jnp.stack([ones, _rounded(z, hist_dtype)], axis=1)   # (B, 2)
        bins = jnp.sum(X[:, :, None] > edges[None, :, :], axis=2)
        O = jax.nn.one_hot(bins, nb, dtype=jnp.float32).reshape(B, F * nb)
        node = jnp.zeros(B, jnp.int32)
        arrived = jnp.ones(B, jnp.bool_)
        V = jnp.zeros((B, M, 2), jnp.float32)
        S = jnp.zeros((M, 2), jnp.float32)
        for _ in range(depth):
            internal, go_left, nxt = _step(X, node, feat, thr, left, right)
            oh = jax.nn.one_hot(node, M, dtype=jnp.float32) \
                * arrived[:, None].astype(jnp.float32)
            ohv = oh[:, :, None] * vals[:, None, :]
            V = V + ohv
            S = S + jnp.sum(
                ohv * (internal & go_left)[:, None, None].astype(jnp.float32),
                axis=0)
            node = nxt
            arrived = internal
        hist = jnp.dot(O.T, V.reshape(B, 2 * M),
                       precision=jax.lax.Precision.HIGHEST)
        tot = jnp.sum(V, axis=0)
        lv = jnp.stack([ones, _rounded(z, leaf_dtype),
                        _rounded(h, leaf_dtype)], axis=1)           # (B, 3)
        L = jnp.sum(jax.nn.one_hot(node, M, dtype=jnp.float32)[:, :, None]
                    * lv[:, None, :], axis=0)
        return hist, S, tot, L, node

    return jax.jit(tree_pass)


@functools.lru_cache(maxsize=16)
def _margin_pass_fn(B: int, depth: int, margin_dtype):
    """(cols, f, start, trees stacked (T, M)) -> f after those trees."""
    import jax
    import jax.numpy as jnp

    def margin_pass(cols, f, start, feat, thr, left, right, leaf):
        X = _block_X(cols, start, B)

        def one(f, tree):
            fe, th, le, ri, lf = tree
            node = jnp.zeros(B, jnp.int32)
            for _ in range(depth):
                _i, _g, node = _step(X, node, fe, th, le, ri)
            return _rounded(f + lf[node], margin_dtype), None

        f, _ = jax.lax.scan(one, f, (feat, thr, left, right, leaf))
        return f

    return jax.jit(margin_pass)


@functools.lru_cache(maxsize=4)
def _logloss_fn():
    import jax
    import jax.numpy as jnp

    def ll(f, y):
        yb = y.astype(jnp.float32)
        return jnp.sum(jnp.logaddexp(0.0, f) - yb * f)

    return jax.jit(ll)


# ---------------------------------------------------------------------------
# host arithmetic (float64)
# ---------------------------------------------------------------------------

def _se_gain(nL, gL, n, g):
    nR, gR = n - nL, g - gL
    with np.errstate(divide="ignore", invalid="ignore"):
        return (np.where(nL > 0, gL * gL / np.maximum(nL, 1e-300), 0.0)
                + np.where(nR > 0, gR * gR / np.maximum(nR, 1e-300), 0.0)
                - (g * g / n if n > 0 else 0.0))


def best_split(hist_node: np.ndarray, tot: np.ndarray, n_edges: np.ndarray,
               min_rows: float):
    """hist_node (F, nb, 2) float64 of [1, z]; -> (gain, feature, edge index)
    of the best valid candidate, gain -inf if none."""
    n, g = float(tot[0]), float(tot[1])
    pre = np.cumsum(hist_node, axis=1)[:, :-1, :]          # split after bin t
    nL, gL = pre[..., 0], pre[..., 1]
    gains = _se_gain(nL, gL, n, g)
    t_idx = np.arange(pre.shape[1])[None, :]
    ok = (nL >= min_rows) & (n - nL >= min_rows) & (t_idx < n_edges[:, None])
    gains = np.where(ok, gains, -np.inf)
    flat = int(np.argmax(gains))
    fi, ti = divmod(flat, gains.shape[1])
    return float(gains[fi, ti]), fi, ti


def leaf_value(sums: np.ndarray, learn_rate: float) -> float:
    """sums = [n, sum z, sum p(1-p)] -> learn_rate * Newton step."""
    den = float(sums[2])
    gamma = float(sums[1]) / max(den, 1e-12) if den > 1e-12 else 0.0
    return learn_rate * float(np.clip(gamma, -LEAF_CLIP, LEAF_CLIP))


def prior_margin(y) -> float:
    """logit(mean y), clipped to +-19 (the count of ones is exact in int32)."""
    import jax.numpy as jnp

    mean = float(jnp.sum(y.astype(jnp.int32))) / int(y.shape[0])
    mean = min(max(mean, 1e-12), 1 - 1e-12)
    return float(np.clip(math.log(mean / (1.0 - mean)), -19.0, 19.0))


# ---------------------------------------------------------------------------
# running a tree over all blocks
# ---------------------------------------------------------------------------

class _Data:
    """The training rows as the reference holds them: 28 device columns, the
    0/1 response, per-block margins."""

    def __init__(self, cols, y, params: dict, precision: dict, edges: list):
        import jax.numpy as jnp

        self.cols = tuple(cols)
        self.y = y
        self.n = int(y.shape[0])
        self.F = len(self.cols)
        self.B = block_rows(self.n)
        self.nblocks = self.n // self.B
        self.depth = int(params["max_depth"])
        self.M = n_nodes(self.depth)
        self.prec = precision
        self.edges = edges
        self.n_edges = np.array([len(e) for e in edges], np.int64)
        self.nb = int(params["nbins"])              # value bins per feature
        self.edges_dev = jnp.asarray(pad_edges(edges, self.nb - 1))
        self.f = None

    def start_margins(self, init_f: float):
        import jax.numpy as jnp

        self.f = [jnp.full(self.B, init_f, jnp.float32)
                  for _ in range(self.nblocks)]

    def tree_sums(self, tree: dict, rows_used: float = 1.0):
        """Sums of one (partial) tree over every block, in float64, and the
        final node of every row per block. ``rows_used`` < 1 leaves the last
        rows out (the half-batch fault)."""
        import jax.numpy as jnp

        fn = _tree_pass_fn(self.B, self.F, self.depth, self.nb,
                           self.prec["hist"], self.prec["leaf"])
        arrs = [jnp.asarray(tree[k]) for k in ("feat", "thr", "left", "right")]
        limit = max(int(round(self.n * rows_used)), 1)
        outs = [fn(self.cols, self.y, self.f[b], b * self.B, limit, *arrs,
                   self.edges_dev) for b in range(self.nblocks)]
        hist = np.zeros((self.F * self.nb, 2 * self.M))
        S = np.zeros((self.M, 2))
        tot = np.zeros((self.M, 2))
        L = np.zeros((self.M, 3))
        nodes = []
        for h_, s_, t_, l_, nd in outs:
            hist += np.asarray(h_, np.float64)
            S += np.asarray(s_, np.float64)
            tot += np.asarray(t_, np.float64)
            L += np.asarray(l_, np.float64)
            nodes.append(nd)
        hist = hist.reshape(self.F, self.nb, self.M, 2).transpose(2, 0, 1, 3)
        return hist, S, tot, L, nodes

    def add_leaves(self, nodes, leaf: np.ndarray):
        import jax.numpy as jnp

        lf = jnp.asarray(leaf, jnp.float32)
        for b, nd in enumerate(nodes):
            self.f[b] = _rounded(self.f[b] + lf[nd], self.prec["margin"])

    def add_trees(self, forest: dict, t0: int, t1: int):
        import jax.numpy as jnp

        if t1 <= t0:
            return
        fn = _margin_pass_fn(self.B, self.depth, self.prec["margin"])
        arrs = [jnp.asarray(forest[k][t0:t1])
                for k in ("feat", "thr", "left", "right", "leaf")]
        for b in range(self.nblocks):
            self.f[b] = fn(self.cols, self.f[b], b * self.B, *arrs)

    def logloss(self) -> float:
        import jax

        fn = _logloss_fn()
        tot = 0.0
        for b in range(self.nblocks):
            yb = jax.lax.dynamic_slice(self.y, (b * self.B,), (self.B,))
            tot += float(fn(self.f[b], yb))
        return tot / self.n


def empty_tree(M: int) -> dict:
    return {"feat": np.full(M, -1, np.int32), "thr": np.zeros(M, np.float32),
            "left": np.zeros(M, np.int32), "right": np.zeros(M, np.int32),
            "leaf": np.zeros(M, np.float32), "cover": np.zeros(M, np.float64)}


def pad_forest(forest: dict, M: int) -> dict:
    """Node tables padded to M slots (a forest may carry fewer)."""
    out = dict(forest)
    fills = {"feat": -1, "thr": 0.0, "left": 0, "right": 0, "leaf": 0.0,
             "cover": 0.0}
    for k, fill in fills.items():
        a = np.asarray(forest[k])
        if a.shape[1] > M:
            raise ValueError(f"forest has {a.shape[1]} node slots, the "
                             f"configured depth allows {M}")
        if a.shape[1] < M:
            pad = np.full((a.shape[0], M - a.shape[1]), fill, a.dtype)
            a = np.concatenate([a, pad], axis=1)
        out[k] = a
    return out


# ---------------------------------------------------------------------------
# the reference in the program's place
# ---------------------------------------------------------------------------

def grow(cols, y, params: dict, *, ntrees: int = None,
         precision: str = "reference", fault: str = None) -> dict:
    """Grow a forest of the configured algorithm, freely, level by level.

    ``fault``: None | "state_unchanged" (margins never move, so every tree
    is the first one) | "half_batch" (the second half of the rows is left
    out of every sum, leaf means taken over the rest)."""
    prec = PRECISIONS[precision]
    ntrees = int(ntrees or params["ntrees"])
    lr = float(params["learn_rate"])
    min_rows = float(params["min_rows"])
    msi = float(params["min_split_improvement"])
    edges = quantile_edges(cols, int(params["nbins"]), prec["edge"])
    d = _Data(cols, y, params, prec, edges)
    init_f = prior_margin(y)
    d.start_margins(init_f)
    rows_used = 0.5 if fault == "half_batch" else 1.0
    trees = []
    for _t in range(ntrees):
        tree = empty_tree(d.M)
        frontier, n_used = [0], 1
        for _level in range(d.depth):
            hist, _S, tot, _L, _nodes = d.tree_sums(tree, rows_used)
            nxt = []
            for nid in frontier:
                tree["cover"][nid] = tot[nid, 0]
                gain, fi, ti = best_split(hist[nid], tot[nid], d.n_edges,
                                          min_rows)
                if not gain > msi:
                    continue
                tree["feat"][nid] = fi
                tree["thr"][nid] = edges[fi][ti]
                tree["left"][nid], tree["right"][nid] = n_used, n_used + 1
                nxt += [n_used, n_used + 1]
                n_used += 2
            frontier = nxt
            if not frontier:
                break
        _h, _S, _tot, L, nodes = d.tree_sums(tree, rows_used)
        for nid in range(d.M):
            if tree["feat"][nid] < 0 and L[nid, 0] > 0:
                tree["cover"][nid] = L[nid, 0]
                tree["leaf"][nid] = _round_np(leaf_value(L[nid], lr),
                                              prec["leaf"])
        if fault != "state_unchanged":
            if rows_used < 1.0:      # the margins of all rows still move
                _h, _S, _tot, _L, nodes = d.tree_sums(tree, 1.0)
            d.add_leaves(nodes, tree["leaf"])
        trees.append(tree)
    forest = {k: np.stack([t[k] for t in trees]) for k in trees[0]}
    forest.update(init_f=float(_round_np(init_f, prec["margin"])),
                  edges=edges, max_depth=d.depth,
                  logloss=d.logloss())
    return forest


# ---------------------------------------------------------------------------
# judging a forest
# ---------------------------------------------------------------------------

def check_forest(cols, y, params: dict, forest: dict, *,
                 k_follow: int = 3) -> dict:
    """The numbers compared, by name. ``forest``: feat/thr/left/right/leaf/
    cover (T, M), init_f, edges (list per feature), logloss (as reported)."""
    lr = float(params["learn_rate"])
    min_rows = float(params["min_rows"])
    prec = PRECISIONS["reference"]
    edges = quantile_edges(cols, int(params["nbins"]))
    d = _Data(cols, y, params, prec, edges)
    forest = pad_forest(forest, d.M)
    T = int(forest["feat"].shape[0])
    out = {}

    # edges and prior, compared directly
    gap = 0.0
    for mine, theirs in zip(edges, forest["edges"]):
        theirs = np.asarray(theirs, np.float32)
        gap = max(gap, float(np.max(np.abs(mine - theirs)))
                  if len(mine) == len(theirs) else float("inf"))
    out["edge_gap"] = gap
    init_ref = prior_margin(y)
    out["init_gap"] = abs(float(forest["init_f"]) - init_ref)

    # the first trees, node by node, on the reference's own margins
    d.start_margins(init_ref)
    split_gap = leaf_gap = cover_gap = 0.0
    gain_lost = gain_best = 0.0
    k_follow = min(k_follow, T)
    for t in range(k_follow):
        tree = {k: forest[k][t] for k in ("feat", "thr", "left", "right")}
        hist, S, tot, L, nodes = d.tree_sums(tree)
        root_gain = None
        leaf_diffs = []
        for nid in range(d.M):
            internal = tree["feat"][nid] >= 0
            n_here = tot[nid, 0] if tot[nid, 0] > 0 else L[nid, 0]
            if n_here <= 0:
                if internal:            # a split nobody reaches
                    split_gap = max(split_gap, 1.0)
                continue
            cover_gap = max(cover_gap,
                            abs(float(forest["cover"][t, nid]) - n_here)
                            / n_here)
            if tot[nid, 0] > 0:         # above the last level: has a histogram
                best, _fi, _ti = best_split(hist[nid], tot[nid], d.n_edges,
                                            min_rows)
                if nid == 0:
                    root_gain = best
                if internal:
                    nL, gL = S[nid]
                    theirs = float(_se_gain(nL, gL, tot[nid, 0], tot[nid, 1]))
                    if min(nL, tot[nid, 0] - nL) < min_rows \
                            or not np.isfinite(best) or best <= 0:
                        split_gap = max(split_gap, 1.0)
                    else:
                        split_gap = max(split_gap,
                                        max(best - theirs, 0.0) / best)
                        gain_lost += max(best - theirs, 0.0)
                        gain_best += best
                elif np.isfinite(best) and root_gain \
                        and best > 1e-3 * root_gain:
                    split_gap = max(split_gap, 1.0)   # stopped where it pays
            if not internal:
                ref = leaf_value(L[nid], lr)
                leaf_diffs.append((abs(float(forest["leaf"][t, nid]) - ref),
                                   abs(ref)))
        scale = float(np.median([r for _d, r in leaf_diffs])) \
            if leaf_diffs else 1.0
        for diff, ref in leaf_diffs:
            leaf_gap = max(leaf_gap, diff / max(ref, scale, 1e-30))
        d.add_leaves(nodes, forest["leaf"][t])
    out["split_gain_gap"] = split_gap
    out["split_gain_loss"] = gain_lost / gain_best if gain_best > 0 else 1.0
    out["leaf_gap"] = leaf_gap
    out["cover_gap"] = cover_gap

    # the whole forest over every row
    d.add_trees(forest, k_follow, T)
    ll = d.logloss()
    out["logloss_gap"] = abs(float(forest["logloss"]) - ll) / ll
    out["logloss_ref"] = ll
    return out


def predict_rows(forest: dict, X: np.ndarray, max_depth: int,
                 block: int = 8192) -> np.ndarray:
    """p(Y) for host rows X (n, F) through every tree of ``forest``."""
    import jax.numpy as jnp

    forest = pad_forest(forest, n_nodes(max_depth))
    n, F = X.shape
    padded = -(-n // block) * block
    Xp = np.zeros((padded, F), np.float32)
    Xp[:n] = X
    cols = tuple(jnp.asarray(Xp[:, i]) for i in range(F))
    return predict_columns(forest, cols, max_depth, block)[:n]


def predict_columns(forest: dict, cols, max_depth: int,
                    block: int = None, dtype=None) -> np.ndarray:
    """p(Y) for device columns, block by block (``dtype``: leaf values and
    margins rounded to it — the scoring control)."""
    import jax
    import jax.numpy as jnp

    forest = pad_forest(forest, n_nodes(max_depth))
    n = int(cols[0].shape[0])
    B = block or block_rows(n)
    fn = _margin_pass_fn(B, max_depth, dtype)
    arrs = [jnp.asarray(forest[k])
            for k in ("feat", "thr", "left", "right")]
    arrs.append(_rounded(jnp.asarray(forest["leaf"]), dtype))
    out = []
    for b in range(n // B):
        f0 = jnp.full(B, float(forest["init_f"]), jnp.float32)
        f = fn(tuple(cols), f0, b * B, *arrs)
        out.append(np.asarray(jax.nn.sigmoid(f)))
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# the interface every reference module gives the harness
# ---------------------------------------------------------------------------

def check_model(cols, y, cfg: dict, produced: dict) -> dict:
    forest = dict(produced)
    forest["logloss"] = float(produced["reported"]["logloss"])
    return check_forest(cols, y, cfg["params"], forest,
                        k_follow=int(cfg.get("k_follow", 3)))


def controls(cols, y, cfg: dict,
             which=("control", "state_unchanged", "half_batch",
                    "pred_control")):
    """The reference in the program's place, broken on purpose: yields
    (label, numbers as the judge reads them). ``control`` is the lower
    precision; the others are the faults a cell can have; ``pred_control``
    scores rows with leaf values and margins in bfloat16."""
    k = int(cfg.get("k_follow", 3))
    params = cfg["params"]
    for label in which:
        if label == "pred_control":
            forest = grow(cols, y, params, ntrees=k)
            n = min(int(y.shape[0]), block_rows(int(y.shape[0])))
            head = tuple(c[:n] for c in cols)
            want = predict_columns(forest, head, int(params["max_depth"]))
            got = predict_columns(forest, head, int(params["max_depth"]),
                                  dtype="bfloat16")
            yield label, {"pred_gap": float(np.max(np.abs(got - want)))}
            continue
        forest = grow(cols, y, params, ntrees=k,
                      precision="control" if label == "control"
                      else "reference",
                      fault=None if label == "control" else label)
        yield label, check_forest(cols, y, params, forest, k_follow=k)


def predict(produced: dict, cfg: dict, *, X=None, cols=None) -> np.ndarray:
    """p(Y) of host rows ``X`` (n, F) or of device columns ``cols``."""
    depth = int(cfg["params"]["max_depth"])
    if X is not None:
        return predict_rows(produced, X, depth)
    return predict_columns(produced, cols, depth)
