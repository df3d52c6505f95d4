"""Plain reference for the bernoulli histogram GBM over enum and numeric
columns (the airline deployment): a reference that knows subset splits.

Straight ``jax.numpy`` in float32 (sums of real numbers through matmuls at
``HIGHEST``; products of two 0/1 matrices at the default precision, which is
exact for them), row block by row block, with every sum over blocks taken in
float64 on the host. It imports nothing of the program and takes nothing the
program made as an input: data come from the benchmark's own recipe, bin
edges from its own quantiles, gradients and margins from its own arithmetic.
What it shares with ``bench/reference/gbm.py`` is copied, not imported.

Features, as the configuration's ``columns`` state them:
  numeric: the configuration's stated binning (global quantile edges,
    ``x <= edge`` goes left, as ``gbm.py``), NaN in a bin of its own;
  enum: one bin a level (the level's code), missing (code < 0) or unseen
    (code >= levels) in a bin of its own.
For a node, per feature, the statistics (n, sum z) a bin; numeric candidates
in natural order; **categorical candidates in order of the node's mean
response a level (sum z / n; levels without rows last), the best prefix of
that order being the split: a subset of levels**; the missing bin tried on
both sides; both sides >= min_rows rows; gain = reduction in squared error
GL^2/nL + GR^2/nR - G^2/n > min_split_improvement; leaf = learn_rate *
sum(z) / sum(p(1-p)); f0 = logit(mean y) clipped to +-19.

Two uses, as in ``gbm.py``:

* ``check_forest`` judges a forest somebody else grew, teacher-forced: it
  follows the forest's first ``k_follow`` trees level by level on the
  reference's own margins, takes each split as a (feature, threshold or set
  of left levels, side of the missing bin), routes its own rows by it, and
  reads ``split_gain_gap`` / ``split_gain_loss`` (how far the chosen split's
  gain, reckoned from the forest's own subset, lies under the reference's
  best over all features and all subsets in sorted order: the share one
  split in a hundred forgoes or more, and all gain forgone over all to be
  had), ``split_rule_breaks`` (a count: splits with a side under
  ``min_rows``, splits no row reaches, leaves that stop where a split still
  pays), ``leaf_gap``, ``cover_gap`` (row counts a node), then
  ``logloss_gap`` over all rows after all trees, and ``edge_gap`` for the
  numeric columns and ``init_gap`` for the prior margin.
* ``grow`` is the reference put in the program's place, level-wise, with the
  precision of each stage an argument (``PRECISIONS``) and ``fault=``
  planting the faults an enum cell can have.

The walk. A tree is laid out level by level: the nodes a level holds, in
the order their parents hold them, and per level one table a node
(is it a split, feature, threshold, side of the missing bin, is it a subset
split, where its children stand in the next level, its id) and one 0/1 row a
node over the flat (feature, bin) axis that marks the bins going left. A row
stands at position ``pos`` of its level; a one-hot of ``pos`` times a table
reads the node's entries with no gather, and a row goes left under a subset
split iff the (feature, bin) cell it occupies is marked in its node's row.
So that a run compiles a handful of programs and not one a level, a level's
histograms are taken ``HIST_W`` nodes at a time by one program, and its
routing by the narrowest of a few widths that holds its nodes.
"""

from __future__ import annotations

import functools
import math

import numpy as np

EDGE_SAMPLE = 200_000
MAX_BLOCK = 131_072
LEAF_CLIP = 1e4

# stage -> dtype name (None = float32): as bench/reference/gbm.py
PRECISIONS = {
    "reference": {"hist": None, "leaf": None, "edge": None, "margin": None},
    "stated": {"hist": "bfloat16", "leaf": None, "edge": None,
               "margin": None},
    "control": {"hist": "float8_e4m3fn", "leaf": "bfloat16",
                "edge": "bfloat16", "margin": "bfloat16"},
}
FAULTS = ("code_order", "na_flipped", "level_dropped", "state_unchanged",
          "depth_cut", "min_rows_ignored")
DEPTH_CUT = 3       # the depth the fault "depth_cut" stops a tree at
# split_gain_gap: the share of its best gain that one followed split in
# GAP_ONE_IN forgoes or more (a quantile, not the widest: the widest belongs
# to one weak node whose best gain is a ten-thousandth of the root's, swings
# eightfold from seed to seed and reads the same for the program and for fp8
# statistics; it is reported beside as split_gain_widest). What rounding of
# the statistics makes a node forgo falls as 1 / sqrt(its rows), so only
# nodes of at least GAP_MIN_ROWS rows count: one limit then judges a
# 20,000-row dry run and the cell's own 16M rows alike
GAP_ONE_IN = 100
GAP_MIN_ROWS = 1000
PAYS = 1e-3         # a leaf above the last level whose best split would
#                     gain this share of the root's stopped where it pays
TAB = 8     # columns of a level's node table (see _level_tables)
HIST_W = 64         # nodes a dispatch of the histogram program takes


def route_width(n: int) -> int:
    """Narrowest of 16, 128, 1024, ... that holds a level of n nodes."""
    w = 16
    while w < n:
        w *= 8
    return w


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


def _rounded(x, dtype):
    import jax.numpy as jnp

    if dtype is None:
        return x
    return x.astype(getattr(jnp, dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# the layout of the flat (feature, bin) axis
# ---------------------------------------------------------------------------

class Layout:
    """Per feature: is it an enum, its value bins (levels, or ``nbins``), and
    where its bins start on the flat axis; the bin after a feature's value
    bins is its missing / unseen bin."""

    def __init__(self, is_cat: tuple, value_bins: tuple, nbins: int):
        self.is_cat = tuple(is_cat)
        self.value_bins = tuple(value_bins)
        self.nbins = int(nbins)
        self.F = len(self.is_cat)
        self.width = tuple(v + 1 for v in self.value_bins)
        self.off = tuple(int(o) for o in
                         np.concatenate([[0], np.cumsum(self.width)[:-1]]))
        self.TB = int(sum(self.width))
        self.numeric = tuple(i for i in range(self.F) if not self.is_cat[i])

    @classmethod
    def of(cls, cfg: dict) -> "Layout":
        """From a configuration's ``columns`` and ``params.nbins``."""
        nbins = int(cfg["params"]["nbins"])
        cols = cfg["columns"]
        return cls(tuple(c["type"] == "enum" for c in cols),
                   tuple(int(c["levels"]) if c["type"] == "enum" else nbins
                         for c in cols), nbins)

    def key(self) -> tuple:
        """What a compiled block program is keyed by; Layout(*key) is it."""
        return (self.is_cat, self.value_bins, self.nbins)


# ---------------------------------------------------------------------------
# bin edges of the numeric columns
# ---------------------------------------------------------------------------

def quantile_edges(cols, layout: Layout, dtype=None) -> list:
    """Per feature: ascending unique float32 edges (at most nbins - 1) for a
    numeric column, an empty array for an enum one."""
    qs = np.linspace(0.0, 1.0, layout.nbins + 1)[1:-1]
    out = []
    for i, c in enumerate(cols):
        if layout.is_cat[i]:
            out.append(np.zeros(0, np.float32))
            continue
        n = c.shape[0]
        step = max(n // EDGE_SAMPLE, 1) if n > EDGE_SAMPLE else 1
        sample = np.asarray(c[::step], np.float64)
        e = np.nanquantile(sample, qs)
        e = _round_np(e[np.isfinite(e)], dtype)
        out.append(np.unique(e).astype(np.float32))
    return out


def pad_edges(edges: list, layout: Layout) -> np.ndarray:
    ep = np.full((layout.F, layout.nbins - 1), np.inf, np.float32)
    for i, e in enumerate(edges):
        ep[i, : len(e)] = e
    return ep


# ---------------------------------------------------------------------------
# block programs: one level of one tree for one block of rows
# ---------------------------------------------------------------------------

def _block(cols, start, B, layout: Layout, edges):
    """A block's rows as the walk reads them: the one-hot O (B, TB) of the
    (feature, bin) cells a row occupies, its bin a feature (B, F), and its
    value a feature as float32 with NaN as 0 (B, F)."""
    import jax
    import jax.numpy as jnp

    onehots, bins, vals = [], [], []
    for i, c in enumerate(cols):
        x = jax.lax.dynamic_slice(c, (start,), (B,))
        vb = layout.value_bins[i]
        if layout.is_cat[i]:
            code = x.astype(jnp.int32)
            b = jnp.where((code < 0) | (code >= vb), vb, code)
            v = code.astype(jnp.float32)
        else:
            b = jnp.sum(x[:, None] > edges[i][None, :], axis=1,
                        dtype=jnp.int32)
            b = jnp.where(jnp.isnan(x), vb, b)
            v = jnp.where(jnp.isnan(x), 0.0, x)
        onehots.append(jax.nn.one_hot(b, vb + 1, dtype=jnp.float32))
        bins.append(b)
        vals.append(v)
    return (jnp.concatenate(onehots, axis=1), jnp.stack(bins, axis=1),
            jnp.stack(vals, axis=1))


def _gradient(y, f, start, B):
    import jax
    import jax.numpy as jnp

    yb = jax.lax.dynamic_slice(y, (start,), (B,)).astype(jnp.float32)
    p = jax.nn.sigmoid(f)
    return yb - p, p * (1.0 - p)


@functools.lru_cache(maxsize=64)
def _hist_fn(B: int, layout_key, hist_dtype):
    """(cols, y, f, start, pos, alive, base, edges) -> for the HIST_W nodes
    of a level that stand at positions base .. base + HIST_W - 1: rows and
    sum z a (feature, bin) cell, (TB, HIST_W) each, and a node (HIST_W, 2)."""
    import jax
    import jax.numpy as jnp

    layout = Layout(*layout_key)
    hi = jax.lax.Precision.HIGHEST

    def hist(cols, y, f, start, pos, alive, base, edges):
        O, _bins, _vals = _block(cols, start, B, layout, edges)
        z, _h = _gradient(y, f, start, B)
        z = _rounded(z, hist_dtype)
        P = jax.nn.one_hot(pos - base, HIST_W, dtype=jnp.float32) \
            * alive[:, None].astype(jnp.float32)
        Pz = P * z[:, None]
        # O and P hold only 0 and 1: their product is exact at any precision
        hist_n = jnp.dot(O.T, P)
        hist_g = jnp.dot(O.T, Pz, precision=hi)
        tot = jnp.stack([jnp.sum(P, axis=0), jnp.sum(Pz, axis=0)], axis=1)
        return hist_n, hist_g, tot

    return jax.jit(hist)


@functools.lru_cache(maxsize=64)
def _route_fn(B: int, layout_key, W: int, hist_dtype, leaf_dtype):
    """(cols, y, f, start, pos, alive, fin, tab (W, TAB), lt (W, TB), edges)
    -> rows and sum z going LEFT at each split (W, 2); [rows, sum z, sum
    p(1-p)] of the rows that stop at each node (W, 3); and where every row
    stands in the next level (pos, alive) or stopped (fin = node id)."""
    import jax
    import jax.numpy as jnp

    layout = Layout(*layout_key)
    hi = jax.lax.Precision.HIGHEST
    na_bin = jnp.asarray(layout.value_bins, jnp.int32)

    def route(cols, y, f, start, pos, alive, fin, tab, lt, edges):
        O, bins, vals = _block(cols, start, B, layout, edges)
        z, h = _gradient(y, f, start, B)
        P = jax.nn.one_hot(pos, W, dtype=jnp.float32) \
            * alive[:, None].astype(jnp.float32)
        at = jnp.dot(P, tab, precision=hi)              # the node's entries
        internal = at[:, 0] > 0.5
        fe = jnp.round(at[:, 1]).astype(jnp.int32)
        thr = at[:, 2]
        na_left = at[:, 3] > 0.5
        subset = at[:, 4] > 0.5
        lpos = jnp.round(at[:, 5]).astype(jnp.int32)
        rpos = jnp.round(at[:, 6]).astype(jnp.int32)
        nid = jnp.round(at[:, 7]).astype(jnp.int32)
        pick = jax.nn.one_hot(fe, layout.F, dtype=jnp.bool_)
        xv = jnp.sum(jnp.where(pick, vals, 0.0), axis=1)
        b = jnp.sum(jnp.where(pick, bins, 0), axis=1)
        is_na = b == jnp.sum(jnp.where(pick, na_bin[None, :], 0), axis=1)
        in_set = jnp.sum(O * jnp.dot(P, lt), axis=1) > 0.5   # 0/1: exact
        go_left = jnp.where(is_na, na_left,
                            jnp.where(subset, in_set, xv <= thr))
        moved = alive & internal
        stop = alive & ~internal
        gl = (moved & go_left).astype(jnp.float32)
        v2 = jnp.stack([jnp.ones_like(z), _rounded(z, hist_dtype)], axis=1)
        S = jnp.dot((P * gl[:, None]).T, v2, precision=hi)
        lv = jnp.stack([jnp.ones_like(z), _rounded(z, leaf_dtype),
                        _rounded(h, leaf_dtype)], axis=1)
        L = jnp.dot((P * stop[:, None].astype(jnp.float32)).T, lv,
                    precision=hi)
        return (S, L, jnp.where(go_left, lpos, rpos), moved,
                jnp.where(stop, nid, fin))

    return jax.jit(route)


@functools.lru_cache(maxsize=4)
def _add_leaf_fn(margin_dtype):
    import jax

    def add(f, fin, leaf):
        return _rounded(f + leaf[fin], margin_dtype)

    return jax.jit(add)


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
                - np.where(n > 0, g * g / np.maximum(n, 1e-300), 0.0))


def best_splits(hist_n, hist_g, tot, layout: Layout, n_edges, min_rows,
                code_order: bool = False):
    """hist_n, hist_g (W, TB) float64, tot (W, 2) -> per node of the level
    the best valid candidate over all features: gain (W,) (-inf if none),
    feature (W,), position in the feature's candidate order (W,), missing
    bin goes left (W,) and, for enum features, the order the levels were
    taken in {feature: (W, levels)}. ``code_order`` is the planted fault:
    levels in code order instead of mean-response order."""
    W = hist_n.shape[0]
    n, g = tot[:, 0:1], tot[:, 1:2]
    best = np.full(W, -np.inf)
    feat = np.zeros(W, np.int64)
    cut = np.zeros(W, np.int64)
    na_left = np.zeros(W, bool)
    orders = {}
    for i in range(layout.F):
        o, vb = layout.off[i], layout.value_bins[i]
        vn, vg = hist_n[:, o:o + vb], hist_g[:, o:o + vb]
        na_n, na_g = hist_n[:, o + vb:o + vb + 1], hist_g[:, o + vb:o + vb + 1]
        if layout.is_cat[i]:
            with np.errstate(divide="ignore", invalid="ignore"):
                mean = np.where(vn > 0, vg / np.maximum(vn, 1e-300), np.inf)
            order = (np.broadcast_to(np.arange(vb), (W, vb)) if code_order
                     else np.argsort(mean, axis=1, kind="stable"))
            orders[i] = order
            vn = np.take_along_axis(vn, order, axis=1)
            vg = np.take_along_axis(vg, order, axis=1)
            ok_t = np.ones(vb - 1, bool)
        else:
            ok_t = np.arange(vb - 1) < n_edges[i]
        pn = np.cumsum(vn, axis=1)[:, :-1]        # left = first t + 1 bins
        pg = np.cumsum(vg, axis=1)[:, :-1]
        for na_dir in (False, True):
            nL = pn + (na_n if na_dir else 0.0)
            gL = pg + (na_g if na_dir else 0.0)
            gains = _se_gain(nL, gL, n, g)
            ok = (nL >= min_rows) & (n - nL >= min_rows) & ok_t[None, :]
            gains = np.where(ok, gains, -np.inf)
            t = np.argmax(gains, axis=1)
            top = gains[np.arange(W), t]
            better = top > best
            best = np.where(better, top, best)
            feat = np.where(better, i, feat)
            cut = np.where(better, t, cut)
            na_left = np.where(better, na_dir, na_left)
    return best, feat, cut, na_left, orders


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
# a tree laid out level by level
# ---------------------------------------------------------------------------

def empty_tree(M: int) -> dict:
    return {"feat": np.full(M, -1, np.int32), "thr": np.zeros(M, np.float32),
            "na_left": np.zeros(M, bool),
            "left": np.zeros(M, np.int32), "right": np.zeros(M, np.int32),
            "leaf": np.zeros(M, np.float32), "cover": np.zeros(M, np.float64),
            "cat_split": np.full(M, -1, np.int32)}


def _level_tables(tree: dict, cat_rows: list, nodes: list, layout: Layout):
    """The table and the left-marks of one level whose nodes are ``nodes``
    (ids, in order) -> (tab (W, TAB), lt (W, TB), the next level's nodes),
    W the routing width that holds them."""
    W = route_width(len(nodes))
    tab = np.zeros((W, TAB), np.float32)
    lt = np.zeros((W, layout.TB), np.float32)
    nxt = []
    for pos, nid in enumerate(nodes):
        tab[pos, 7] = nid
        fe = int(tree["feat"][nid])
        if fe < 0:
            continue
        subset = int(tree["cat_split"][nid]) >= 0
        tab[pos, :7] = (1.0, fe, tree["thr"][nid], tree["na_left"][nid],
                        subset, len(nxt), len(nxt) + 1)
        if subset:
            row = np.asarray(cat_rows[int(tree["cat_split"][nid])], bool)
            o = layout.off[fe]
            lt[pos, o:o + min(len(row), layout.value_bins[fe])] = \
                row[: layout.value_bins[fe]]
        nxt += [int(tree["left"][nid]), int(tree["right"][nid])]
    return tab, lt, nxt


class _Data:
    """The training rows as the reference holds them: device columns, the
    0/1 response, per-block margins."""

    def __init__(self, cols, y, params: dict, layout: Layout,
                 precision: dict, edges: list):
        import jax.numpy as jnp

        self.cols = tuple(cols)
        self.y = y
        self.n = int(y.shape[0])
        self.layout = layout
        self.B = block_rows(self.n)
        self.nblocks = self.n // self.B
        self.depth = int(params["max_depth"])
        self.M = n_nodes(self.depth)
        self.prec = precision
        self.edges = edges
        self.n_edges = np.array([len(e) for e in edges], np.int64)
        self.edges_dev = jnp.asarray(pad_edges(edges, layout))
        self.f = None

    def start_margins(self, init_f: float):
        import jax.numpy as jnp

        self.f = [jnp.full(self.B, init_f, jnp.float32)
                  for _ in range(self.nblocks)]

    def start_walk(self):
        """Every row at the root."""
        import jax.numpy as jnp

        return [(jnp.zeros(self.B, jnp.int32), jnp.ones(self.B, jnp.bool_),
                 jnp.zeros(self.B, jnp.int32)) for _ in range(self.nblocks)]

    def level_hist(self, state, n: int):
        """Sums over every block, in float64: rows and sum z a (feature,
        bin) cell and a node, for a level of n nodes -> (n, TB), (n, TB),
        (n, 2)."""
        fn = _hist_fn(self.B, self.layout.key(), self.prec["hist"])
        groups = -(-n // HIST_W)
        outs = [[fn(self.cols, self.y, self.f[b], b * self.B, state[b][0],
                    state[b][1], g * HIST_W, self.edges_dev)
                 for b in range(self.nblocks)] for g in range(groups)]
        hn = np.zeros((groups, self.layout.TB, HIST_W))
        hg = np.zeros((groups, self.layout.TB, HIST_W))
        tot = np.zeros((groups, HIST_W, 2))
        for g, per_block in enumerate(outs):
            for n_, g_, t_ in per_block:
                hn[g] += np.asarray(n_, np.float64)
                hg[g] += np.asarray(g_, np.float64)
                tot[g] += np.asarray(t_, np.float64)
        flat = lambda a: a.transpose(0, 2, 1).reshape(     # noqa: E731
            groups * HIST_W, -1)[:n]
        return flat(hn), flat(hg), tot.reshape(groups * HIST_W, 2)[:n]

    def level_route(self, state, tab, lt):
        """Move every row one level down. -> (S (W, 2) left sums of the
        splits, L (W, 3) sums of the rows that stop, the next state)."""
        import jax.numpy as jnp

        W = tab.shape[0]
        fn = _route_fn(self.B, self.layout.key(), W, self.prec["hist"],
                       self.prec["leaf"])
        tab_d, lt_d = jnp.asarray(tab), jnp.asarray(lt)
        outs = [fn(self.cols, self.y, self.f[b], b * self.B, *state[b],
                   tab_d, lt_d, self.edges_dev) for b in range(self.nblocks)]
        S = np.zeros((W, 2))
        L = np.zeros((W, 3))
        for s_, l_, *_rest in outs:
            S += np.asarray(s_, np.float64)
            L += np.asarray(l_, np.float64)
        return S, L, [tuple(o[2:]) for o in outs]

    def walk(self, tree: dict, cat_rows: list):
        """Every row through a finished tree -> where it stops, per block."""
        state, nodes = self.start_walk(), [0]
        for _d in range(self.depth + 1):
            tab, lt, nodes = _level_tables(tree, cat_rows, nodes, self.layout)
            _S, _L, state = self.level_route(state, tab, lt)
        return [s[2] for s in state]

    def add_leaves(self, fins, leaf: np.ndarray):
        import jax.numpy as jnp

        fn = _add_leaf_fn(self.prec["margin"])
        lf = jnp.asarray(leaf, jnp.float32)
        for b, fin in enumerate(fins):
            self.f[b] = fn(self.f[b], fin, lf)

    def logloss(self) -> float:
        import jax

        fn = _logloss_fn()
        tot = 0.0
        for b in range(self.nblocks):
            yb = jax.lax.dynamic_slice(self.y, (b * self.B,), (self.B,))
            tot += float(fn(self.f[b], yb))
        return tot / self.n


# ---------------------------------------------------------------------------
# the reference in the program's place
# ---------------------------------------------------------------------------

def grow(cols, y, cfg: dict, *, ntrees: int = None,
         precision: str = "reference", fault: str = None) -> dict:
    """Grow a forest of the configured algorithm, freely, level by level.

    ``fault``: None | "code_order" (the levels of an enum feature are taken
    in code order, not in order of mean response) | "na_flipped" (a split
    is recorded with the missing bin on the other side than its rows went)
    | "level_dropped" (a subset split is recorded without one of its left
    levels) | "state_unchanged" (margins never move: every tree is the
    first one) | "depth_cut" (a tree stops at depth ``DEPTH_CUT`` whatever
    ``max_depth`` says) | "min_rows_ignored" (a side of a split may hold a
    single row)."""
    params = cfg["params"]
    prec = PRECISIONS[precision]
    layout = Layout.of(cfg)
    ntrees = int(ntrees or params["ntrees"])
    lr = float(params["learn_rate"])
    min_rows = 1.0 if fault == "min_rows_ignored" \
        else float(params["min_rows"])
    msi = float(params["min_split_improvement"])
    split_depth = min(int(params["max_depth"]), DEPTH_CUT) \
        if fault == "depth_cut" else int(params["max_depth"])
    edges = quantile_edges(cols, layout, prec["edge"])
    d = _Data(cols, y, params, layout, prec, edges)
    init_f = prior_margin(y)
    d.start_margins(init_f)
    trees, cat_rows = [], []
    for _t in range(ntrees):
        tree = empty_tree(d.M)
        state, nodes, n_used = d.start_walk(), [0], 1
        first_row = len(cat_rows)
        for level in range(d.depth + 1):
            if level < split_depth and nodes:
                hn, hg, tot = d.level_hist(state, len(nodes))
                gain, fe, cut, na_left, orders = best_splits(
                    hn, hg, tot, layout, d.n_edges, min_rows,
                    code_order=fault == "code_order")
                for pos, nid in enumerate(nodes):
                    if not gain[pos] > msi:
                        continue
                    f_ = int(fe[pos])
                    tree["feat"][nid] = f_
                    tree["na_left"][nid] = bool(na_left[pos])
                    if layout.is_cat[f_]:
                        row = np.zeros(layout.value_bins[f_], bool)
                        row[orders[f_][pos, : int(cut[pos]) + 1]] = True
                        tree["cat_split"][nid] = len(cat_rows)
                        cat_rows.append(row)
                    else:
                        tree["thr"][nid] = edges[f_][int(cut[pos])]
                    tree["left"][nid], tree["right"][nid] = n_used, n_used + 1
                    n_used += 2
            tab, lt, nxt = _level_tables(tree, cat_rows, nodes, layout)
            _S, L, state = d.level_route(state, tab, lt)
            for pos, nid in enumerate(nodes):
                if tree["feat"][nid] < 0:
                    tree["cover"][nid] = L[pos, 0]
                    tree["leaf"][nid] = _round_np(leaf_value(L[pos], lr),
                                                  prec["leaf"])
                else:
                    tree["cover"][nid] = tot[pos, 0]
            nodes = nxt
        if fault != "state_unchanged":
            d.add_leaves([s[2] for s in state], tree["leaf"])
        # faults of the record: the rows went one way, the forest says another
        if fault == "na_flipped":
            tree["na_left"] = ~tree["na_left"]
        if fault == "level_dropped":
            for r in range(first_row, len(cat_rows)):
                on = np.nonzero(cat_rows[r])[0]
                if len(on):
                    cat_rows[r] = cat_rows[r].copy()
                    cat_rows[r][on[0]] = False
        trees.append(tree)
    forest = {k: np.stack([t[k] for t in trees]) for k in trees[0]}
    forest.update(cat_rows=cat_rows, init_f=float(_round_np(init_f,
                                                            prec["margin"])),
                  edges=edges, max_depth=d.depth, logloss=d.logloss())
    return forest


# ---------------------------------------------------------------------------
# judging a forest
# ---------------------------------------------------------------------------

def pad_forest(forest: dict, M: int) -> dict:
    """Node tables padded to M slots (a forest may carry fewer)."""
    out = dict(forest)
    fills = {"feat": -1, "thr": 0.0, "na_left": False, "left": 0, "right": 0,
             "leaf": 0.0, "cover": 0.0, "cat_split": -1}
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


def check_forest(cols, y, cfg: dict, forest: dict, *,
                 k_follow: int = 2) -> dict:
    """The numbers compared, by name. ``forest``: feat / thr / na_left /
    left / right / leaf / cover / cat_split (T, M), cat_rows (a bool array
    of left levels per subset split), init_f, edges (list per feature),
    logloss (as reported)."""
    params = cfg["params"]
    lr = float(params["learn_rate"])
    min_rows = float(params["min_rows"])
    layout = Layout.of(cfg)
    edges = quantile_edges(cols, layout)
    d = _Data(cols, y, params, layout, PRECISIONS["reference"], edges)
    forest = pad_forest(forest, d.M)
    cat_rows = forest["cat_rows"]
    T = int(forest["feat"].shape[0])
    out = {}

    # edges of the numeric columns and the prior, compared directly
    gap = 0.0
    for i in layout.numeric:
        mine, theirs = edges[i], np.asarray(forest["edges"][i], np.float32)
        gap = max(gap, float(np.max(np.abs(mine - theirs)))
                  if len(mine) == len(theirs) and len(mine) else float("inf"))
    out["edge_gap"] = gap
    init_ref = prior_margin(y)
    out["init_gap"] = abs(float(forest["init_f"]) - init_ref)

    # the first trees, level by level, on the reference's own margins
    d.start_margins(init_ref)
    leaf_gap = cover_gap = 0.0
    gain_lost = gain_best = 0.0
    n_splits = n_subset = breaks = 0
    shares = []
    k_follow = min(k_follow, T)
    for t in range(k_follow):
        tree = {k: forest[k][t] for k in ("feat", "thr", "na_left", "left",
                                          "right", "cat_split")}
        state, nodes = d.start_walk(), [0]
        root_gain = None
        leaf_diffs = []
        for level in range(d.depth + 1):
            if len(nodes) > 2 ** level:
                raise ValueError(f"level {level} of tree {t} holds "
                                 f"{len(nodes)} nodes")
            best = None
            if level < d.depth and nodes:
                hn, hg, tot = d.level_hist(state, len(nodes))
                best = best_splits(hn, hg, tot, layout, d.n_edges,
                                   min_rows)[0]
            tab, lt, nxt = _level_tables(tree, cat_rows, nodes, layout)
            S, L, state = d.level_route(state, tab, lt)
            for pos, nid in enumerate(nodes):
                internal = tree["feat"][nid] >= 0
                n_here = L[pos, 0] if not internal else \
                    (tot[pos, 0] if best is not None else 0.0)
                if n_here <= 0:
                    breaks += int(internal)     # a split no row reaches
                    continue
                cover_gap = max(cover_gap,
                                abs(float(forest["cover"][t, nid]) - n_here)
                                / n_here)
                if best is not None:    # above the last level: a histogram
                    b = float(best[pos])
                    if nid == 0:
                        root_gain = b
                    if internal:
                        n_splits += 1
                        n_subset += int(tree["cat_split"][nid] >= 0)
                        nL, gL = S[pos]
                        theirs = float(_se_gain(nL, gL, tot[pos, 0],
                                                tot[pos, 1]))
                        if min(nL, tot[pos, 0] - nL) < min_rows \
                                or not np.isfinite(b) or b <= 0:
                            breaks += 1         # a side under min_rows
                        else:
                            if tot[pos, 0] >= GAP_MIN_ROWS:
                                shares.append(max(b - theirs, 0.0) / b)
                            gain_lost += max(b - theirs, 0.0)
                            gain_best += b
                    elif np.isfinite(b) and root_gain \
                            and b > PAYS * root_gain:
                        breaks += 1             # stopped where it pays
                if not internal:
                    ref = leaf_value(L[pos], lr)
                    leaf_diffs.append(
                        (abs(float(forest["leaf"][t, nid]) - ref), abs(ref)))
            nodes = nxt
        scale = float(np.median([r for _d, r in leaf_diffs])) \
            if leaf_diffs else 1.0
        for diff, ref in leaf_diffs:
            leaf_gap = max(leaf_gap, diff / max(ref, scale, 1e-30))
        d.add_leaves([s[2] for s in state], forest["leaf"][t])
    out["split_gain_gap"] = float(np.quantile(
        shares, 1.0 - 1.0 / GAP_ONE_IN)) if shares else 0.0
    out["split_gain_widest"] = max(shares, default=0.0)
    out["split_rule_breaks"] = float(breaks)
    out["split_gain_loss"] = gain_lost / gain_best if gain_best > 0 else 1.0
    out["leaf_gap"] = leaf_gap
    out["cover_gap"] = cover_gap
    out["subset_split_share"] = n_subset / n_splits if n_splits else 0.0

    # the whole forest over every row
    for t in range(k_follow, T):
        tree = {k: forest[k][t] for k in ("feat", "thr", "na_left", "left",
                                          "right", "cat_split")}
        d.add_leaves(d.walk(tree, cat_rows), forest["leaf"][t])
    ll = d.logloss()
    out["logloss_gap"] = abs(float(forest["logloss"]) - ll) / ll
    out["logloss_ref"] = ll
    return out


# ---------------------------------------------------------------------------
# the interface every reference module gives the harness
# ---------------------------------------------------------------------------

def check_model(cols, y, cfg: dict, produced: dict) -> dict:
    forest = dict(produced)
    forest["logloss"] = float(produced["reported"]["logloss"])
    return check_forest(cols, y, cfg, forest,
                        k_follow=int(cfg.get("k_follow", 2)))


def controls(cols, y, cfg: dict, which=("control",) + FAULTS):
    """The reference in the program's place, broken on purpose: yields
    (label, numbers as the judge reads them). A label of ``PRECISIONS``
    (``control`` is the next lower precision) grows at that precision; the
    others are the faults an enum cell can have, grown at the reference's."""
    k = int(cfg.get("k_follow", 2))
    for label in which:
        lower = label in PRECISIONS
        forest = grow(cols, y, cfg, ntrees=k,
                      precision=label if lower else "reference",
                      fault=None if lower else label)
        yield label, check_forest(cols, y, cfg, forest, k_follow=k)
