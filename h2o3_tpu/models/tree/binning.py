"""Feature binning for histogram tree building.

Reference: hex/tree/DHistogram.java:47 — per-feature histograms with
adaptive min/max re-binning per level (DHistogram.java:33-44), nbins /
nbins_cats split points picked per chunk pass.

TPU-native design: GLOBAL quantile binning computed ONCE before training
(the gpu_hist / quantile-sketch strategy the reference's XGBoost extension
uses on CUDA — …/xgboost/XGBoostModel.java:384 grow_gpu_hist). Static bin
edges mean every level's histogram is the same fused scatter-add program —
no data-dependent re-binning inside the compiled loop, which is exactly
what XLA wants. Accuracy loss vs adaptive refinement is the same tradeoff
(LightGBM/XGBoost-hist) the industry made for GPU trees.

Bins for feature f: 0..B_f-2 are value bins, B_f-1 is the NA bin.
Numeric bin b holds x in (edge[b-1], edge[b]]; bin = searchsorted(edges, x).
Categorical bin = category code (capped at nbins_cats).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from h2o3_tpu.core.frame import Column, Frame


class BinSpec:
    """Per-feature bin layout + device binning function.

    Attributes:
      names: feature names in order
      is_cat: (F,) bool
      nbins: (F,) int — B_f INCLUDING the NA bin (last index per feature)
      offsets: (F+1,) int — start of each feature's bin range in the
               flattened histogram row (tot_bins = offsets[-1])
      edges: list of per-feature float arrays (numeric: ascending unique
             quantile edges, len B_f-2; categorical: empty)
    """

    def __init__(self, names, is_cat, nbins, edges, cards):
        self.names: List[str] = list(names)
        self.is_cat = np.asarray(is_cat, bool)
        self.nbins = np.asarray(nbins, np.int64)
        self.offsets = np.concatenate([[0], np.cumsum(self.nbins)]).astype(np.int64)
        self.tot_bins = int(self.offsets[-1])
        self.edges = edges
        self.cards = np.asarray(cards, np.int64)
        self.F = len(self.names)

    @staticmethod
    def build(frame: Frame, feature_names: Sequence[str], *,
              nbins: int = 20, nbins_cats: int = 1024,
              sample: int = 200_000, seed: int = 0,
              strategy: str = "quantile") -> "BinSpec":
        """Edges per numeric feature (device quantiles, or equal-width for
        strategy='uniform' — isolation forests split uniformly in VALUE
        space, IsolationForest.java random split point), identity bins per
        categorical."""
        import jax.numpy as jnp

        is_cat, B, edges, cards = [], [], [], []
        for name in feature_names:
            c = frame.col(name)
            if c.is_categorical:
                card = min(max(c.cardinality, 1), nbins_cats)
                is_cat.append(True)
                B.append(card + 1)
                edges.append(np.zeros(0, np.float32))
                cards.append(card)
            else:
                data = c.data
                n = data.shape[0]
                if n > sample:
                    # stride sample keeps the quantile pass O(sample log sample)
                    step = max(n // sample, 1)
                    data = data[::step]
                if strategy == "uniform":
                    lo = float(jnp.nanmin(data))
                    hi = float(jnp.nanmax(data))
                    e = (np.linspace(lo, hi, nbins + 1)[1:-1]
                         if np.isfinite(lo) and np.isfinite(hi) and hi > lo
                         else np.zeros(0))
                    e = np.asarray(e, np.float64)
                else:
                    qs = np.linspace(0, 1, nbins + 1)[1:-1]
                    e = np.asarray(jnp.nanquantile(data, jnp.asarray(qs)), np.float64)
                e = np.unique(e[np.isfinite(e)]).astype(np.float32)
                is_cat.append(False)
                B.append(len(e) + 2)        # len(e)+1 value bins + NA bin
                edges.append(e)
                cards.append(0)
        return BinSpec(feature_names, is_cat, B, edges, cards)

    def padded_edges(self) -> np.ndarray:
        """(F, emax) float32 dense edge table, +inf beyond each feature's
        real edges — the shared binning operand of the fused scorers
        (compressed._fused_margins) and the sharded bin pack
        (sharded_frame._pack_binned_fn); +inf lanes never count, so the
        padded table bins identically to the ragged per-feature arrays."""
        emax = max((len(e) for e in self.edges), default=0) or 1
        ep = np.full((self.F, emax), np.inf, np.float32)
        for i, e in enumerate(self.edges):
            ep[i, : len(e)] = e
        return ep

    # -- device binning ----------------------------------------------------
    def bin_columns(self, frame: Frame):
        """-> (N, F) row-sharded bin matrix (within-feature indices).

        Packs through the sharded data plane (core/sharded_frame): ONE
        fused program whose output carries the named-row-axis sharding, so
        each process bins only its addressable row shards and tree
        training never stages full columns on the coordinator (ROADMAP
        open item 1 — previously eager per-column ops plus a re-homing
        device_put could materialize coordinator-resident intermediates).
        Frames the view cannot hold (ragged layouts, plane off) keep the
        legacy eager path below.

        Memory safety: the sharded pack consults the HBM budget planner
        (h2o3_tpu/memory) — a frame whose (N, F) bin matrix working set
        exceeds the free budget streams through row-chunk windows
        (bitwise-identical bins, see _pack_binned_window_fn) instead of
        dispatching one doomed full-size program, and a genuine
        RESOURCE_EXHAUSTED walks the degradation ladder before anything
        surfaces to the caller.

        Narrowest integer dtype that fits max(nbins): the bin matrix is the
        biggest operand STREAMED from HBM on every histogram pass of every
        level, so uint8 (nbins ≤ 256, the common case — default numeric
        nbins=20) cuts that traffic 4× vs int32; high-cardinality
        categorical specs (nbins_cats up to 1024+NA) fall back to int16.
        Integer compares/gathers promote losslessly downstream."""
        import jax
        import jax.numpy as jnp

        from h2o3_tpu.core.runtime import cluster
        from h2o3_tpu.core.sharded_frame import ShardedFrame

        sf = ShardedFrame.of(frame, self.names)
        if sf is not None:
            return sf.pack_binned(self)
        # legacy path: eager per-column ops can stage coordinator-resident
        # intermediates, so the frame's rows count as gathered — the
        # counter contract has no silent holes on the tree input path
        from h2o3_tpu.core import sharded_frame as _sfmod

        _sfmod.note_gathered(int(frame.nrows))
        max_bins = int(self.nbins.max()) if len(self.nbins) else 1
        dtype = (jnp.uint8 if max_bins <= 256
                 else jnp.int16 if max_bins <= 32767 else jnp.int32)
        cl = cluster()
        cols = [frame.col(n) for n in self.names]
        parts = []
        for i, c in enumerate(cols):
            na_bin = int(self.nbins[i]) - 1
            if self.is_cat[i]:
                codes = c.data.astype(jnp.int32)
                b = jnp.where((codes < 0) | (codes >= na_bin), na_bin, codes)
            else:
                x = c.data
                e = jnp.asarray(self.edges[i])
                b = _sfmod.edges_below(e, x)
                b = jnp.where(jnp.isnan(x), na_bin, b)
            parts.append(b.astype(dtype))
        binned = jnp.stack(parts, axis=-1)          # (N, F)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(binned, NamedSharding(cl.mesh, P("rows", None)))

    def threshold_value(self, f: int, t: int) -> float:
        """Real-valued threshold for numeric split 'bin <= t' (x <= edge[t])."""
        e = self.edges[f]
        if t < len(e):
            return float(e[t])
        return float("inf")
