"""Fully device-side tree growth — histogram, split search, routing and leaf
statistics in ONE compiled program per tree, at ANY depth.

Reference: hex/tree/ScoreBuildHistogram2.java:60 (per-row histogram build,
CAS adds into DHistogram._vals, DHistogram.java:62-90) + DTree.decideBestSplit
+ GBM.java:416 GammaPass. The reference alternates a distributed histogram
build with a host-side split search a level; here nothing of a tree leaves
the device: a per-level device->host fetch is a sync the device waits
behind, and a scatter-add a level serializes on a TPU (57% of training
time when every level was built that way, profiled on a v5e).

TPU-native design:
- Histograms are MXU matmuls, not scatters:  hist = Oᵀ·V  with
  O (rows, F·maxB) the per-feature bin one-hot and V (rows, 3·S) the
  (w, w·y, w·y²) triples crossed with the node one-hot. Operands are cast
  to bf16 (the one-hot is exact in bf16; the MXU accumulates in f32 via
  preferred_element_type), halving HBM traffic — the bandwidth, not the
  FLOPs, is the roofline here. Blocked over row chunks.
  Both operands are built with the rows on lanes and nothing placed at an
  offset inside a tile: a TPU keeps the (N, F) bin matrix rows-minor, so a
  (blk, lanes) one-hot of 21-lane pieces laid side by side was a
  concatenation at sublane offsets 21, 42, … of 28 materialised pieces —
  257 µs a block of 32,768 rows x 588 lanes, 79% of the histogram and 57%
  of a depth-5 job on a v5e. hist_matmul now broadcasts a feature's row of
  bins over that feature's lanes, padded to whole 8-sublane tiles, against
  a static per-lane bin, and builds the values by one compare over 3S
  lanes in (3, S) order: a whole block, operands and dot, fell from 327 to
  47 µs there and from about 250 to 53 µs on airline's 880 ragged lanes at
  S <= 16, about half of what is left the MXU's own pass over blk/128 x
  lanes tiles; the sums are bit for bit what they were.
- The split search runs on device, vectorized over (node, feature, bin):
  categorical bins are ordered by per-node mean response (argsort) — the
  sorted-subset optimum for squared loss — numeric bins keep
  natural order via an iota sort key. NA direction is tried both ways.
- DENSE-FRONTIER slots, not heap positions: level d holds
  S_d = min(2^d, frontier_cap) slots; nodes that split are renumbered by a
  device prefix-sum and record explicit child-slot links in their packed
  row. Memory is O(depth · frontier_cap) instead of O(2^depth), so DRF's
  default depth 20 runs in the SAME one-dispatch program. When a level
  wants more than S_{d+1}/2 splits, the lowest-gain candidates terminalize
  (greedy-best under a width budget, frontier_cap()).
- Two histogram lowerings, chosen a level from its static width alone
  (hist_lowering): the matmul up to MATMUL_S_LIMIT slots, a scatter-add
  beyond — O(N·F) work per level where the matmul's O(N·lanes·S) FLOPs
  stop being free once the node one-hot is thousands wide. Both benchmark
  configurations (depth 5 and depth 10) stay on the matmul at every level;
  only forests deeper than 10 reach the scatter.
- Routing (`_route`, the one step tree_program and apply_packed share)
  gathers from no operand that carries the rows: a per-row gather has no
  hardware on a TPU (19 ns a row a level, 70% of a depth-5 job when the
  level read `binned` and `left_table` that way). A level reads the row's
  bin at its slot's split feature by compare-and-select over the feature
  axis (compressed._bin_at), the slot's split_feat / left_slot / right_slot
  by compressed._at_node (select over the slot axis up to
  _SELECT_MAX_NODES entries, a gather from the (S,) table beyond), and
  left_table[slot, bin] as one bit of one uint32 word: the (S, maxB) bool
  table is packed into ceil(maxB / 32) words a slot and the word read
  through _at_node too, so thresholds and enum subsets take one form. The
  last level reads nothing: every slot there is terminal.
- The GammaPass inputs (num, den) are computed BEFORE the tree from
  (w, y, z, f) and segment-summed per leaf inside the same program, so leaf
  Newton steps need no extra dispatch. The leaf pass left the scatter for
  the MXU too (leaf_sums): a block's sums are one dot of the four columns'
  three bf16 pieces each (12 lanes; the pieces sum to the f32 value)
  against the leaf one-hot, rows on lanes, f32 accumulation, so every
  product is exact and the sums are f32 sums in blocks. The scatter-add of
  an (N, 4) array it replaces took the rows one by one (123 ms a tree at
  16M rows whatever the slots, 272 ms at 40,960; the largest device op of a
  depth-5 job), padded the 4 to 128 lanes (8.2 GB at 16M rows: what capped
  a chip at 20M rows) and stood 0.3-0.7% off the exact Newton step. Past a
  tile's 128 slots the slot splits into hi·lo + low, the one-hot carries
  the low part and the values, laid out (12, H), are masked by the high
  part: (12·H, blk) · (lo, blk)ᵀ, lo the power of two at or above
  sqrt(12·slots) (leaf_split, the one rule). Swept on a v5e at 16M rows:
  6.0 ms a tree at 64 slots, 8.9 at 2,048, 23.9 at 8,192, 42 at 16,384,
  102 at 40,960 (depth 20), the old scatter 2.7 times that at its best, so
  there is no second lowering.
- All per-level tables pack into ONE (depth+1, S_max, 4+maxB+3+2) f32
  array; training keeps it on device and fetches every tree's tables in a
  single end-of-training transfer (one round trip in total, not one per
  level per tree).
"""

from __future__ import annotations

from h2o3_tpu.compat import pcast as _compat_pcast
from h2o3_tpu.compat import shard_map as _compat_shard_map
from h2o3_tpu.ops import segsum
import functools
from typing import List, Optional, Tuple

import numpy as np

EPS_W = 1e-12
MATMUL_S_LIMIT = 1024       # widest node one-hot the MXU path should carry
DEFAULT_FRONTIER_CAP = 4096


def _mesh():
    from h2o3_tpu.core.runtime import cluster

    return cluster().mesh


def frontier_cap(F: Optional[int] = None, maxB: Optional[int] = None) -> int:
    """Frontier width budget. With feature geometry given, the cap shrinks
    so the scatter histogram buffer (S·F·maxB·3 f32) stays under ~512 MB —
    a >=1024-level enum would otherwise blow HBM at the default."""
    cap = DEFAULT_FRONTIER_CAP
    if F and maxB:
        budget_slots = (512 * 1024 * 1024) // (F * maxB * 12)
        mem_cap = 1 << max(int(budget_slots).bit_length() - 1, 8)
        cap = min(cap, mem_cap)
    return cap


def stash_packed(packed, max_depth: int):
    """Fit loops hold every tree's packed table until the end-of-training
    fetch. Shallow tables are tiny; deep ones (cap-wide levels) are fetched
    to HOST immediately so a 50-tree depth-20 forest cannot OOM the chip —
    one small transfer per deep tree instead of ~17 GB resident."""
    if max_depth > 10:
        return np.asarray(packed)
    return packed


def build_feat_masks(max_depth: int, feat_mask_fn, F: Optional[int] = None,
                     maxB: Optional[int] = None):
    """Per-level (S_d, F) column-sampling masks for grow_tree_device."""
    if feat_mask_fn is None:
        return None
    widths = level_widths(max_depth, frontier_cap(F, maxB))
    return [np.asarray(feat_mask_fn(wd), bool) for wd in widths[:max_depth]]


def level_widths(max_depth: int, cap: Optional[int] = None) -> Tuple[int, ...]:
    """Per-level slot counts S_d = min(2^d, cap)."""
    cap = cap or frontier_cap()
    return tuple(min(2 ** d, cap) for d in range(max_depth + 1))


def level_offsets(widths: Tuple[int, ...]) -> Tuple[int, ...]:
    out, acc = [], 0
    for s in widths:
        out.append(acc)
        acc += s
    return tuple(out)


def total_slots(max_depth: int, cap: Optional[int] = None) -> int:
    return sum(level_widths(max_depth, cap))


def pack_width(maxB: int) -> int:
    """Per-slot f32 lanes: split_feat, thresh, na_left, gain, left_table
    (maxB), tot (3), left_slot, right_slot."""
    return 4 + maxB + 3 + 2


# ---------------------------------------------------------------------------
# device split search (replicated per shard; inputs are psum'd histograms)
# ---------------------------------------------------------------------------

def _search_level(hist, *, nbins, is_cat, maxB, min_rows, min_split_improvement,
                  feat_mask):
    """hist (S, F, maxB, 3) -> split tables for this level.

    Returns split_feat (S,) int32 (-1 terminal), thresh (S,) int32 (position
    in sorted-bin space), na_left (S,) bool, gain (S,) f32,
    left_table (S, maxB) bool, tot (S, 3) f32 node totals.
    """
    import jax.numpy as jnp

    S, F = hist.shape[0], hist.shape[1]
    nb = jnp.asarray(nbins, jnp.int32)                    # (F,) incl NA bin
    cat = jnp.asarray(is_cat)
    binsr = jnp.arange(maxB, dtype=jnp.int32)

    na_pos = nb - 1                                        # (F,)
    val_mask = binsr[None, :] < na_pos[:, None]            # (F, maxB) value bins
    na = jnp.take_along_axis(
        hist, na_pos[None, :, None, None].astype(jnp.int32).repeat(S, 0),
        axis=2)[:, :, 0, :]                                # (S, F, 3)
    V = hist * val_mask[None, :, :, None]
    tot = V.sum(axis=2) + na                               # (S, F, 3)

    w_, wy_, wyy_ = tot[..., 0], tot[..., 1], tot[..., 2]
    se_parent = wyy_ - jnp.where(w_ > EPS_W, wy_ * wy_ / jnp.maximum(w_, EPS_W), 0.0)

    # bin ordering: categorical by per-node mean response, numeric by index
    mean = jnp.where(V[..., 0] > EPS_W,
                     V[..., 1] / jnp.maximum(V[..., 0], EPS_W), jnp.inf)
    sort_key = jnp.where(cat[None, :, None], mean,
                         binsr[None, None, :].astype(jnp.float32))
    order = jnp.argsort(sort_key, axis=2)                  # (S, F, maxB)
    Vs = jnp.take_along_axis(V, order[..., None], axis=2)
    prefix = jnp.cumsum(Vs, axis=2)                        # (S, F, maxB, 3)
    cand = prefix[:, :, :-1, :]                            # split after pos t

    # valid candidate positions: t <= nbins[f]-3 (value bins minus one)
    cand_ok = binsr[None, :-1] <= (nb[:, None] - 3)        # (F, maxB-1)

    def gains_for(na_dir):
        L = cand + (na[:, :, None, :] if na_dir else 0.0)
        R = tot[:, :, None, :] - L
        ok = (L[..., 0] >= min_rows) & (R[..., 0] >= min_rows) & cand_ok[None]
        seL = L[..., 2] - jnp.where(L[..., 0] > EPS_W,
                                    L[..., 1] ** 2 / jnp.maximum(L[..., 0], EPS_W), 0.0)
        seR = R[..., 2] - jnp.where(R[..., 0] > EPS_W,
                                    R[..., 1] ** 2 / jnp.maximum(R[..., 0], EPS_W), 0.0)
        g = se_parent[:, :, None] - seL - seR
        return jnp.where(ok, g, -jnp.inf)

    gains = jnp.stack([gains_for(0), gains_for(1)], axis=-1)  # (S,F,maxB-1,2)
    if feat_mask is not None:
        gains = jnp.where(feat_mask[:, :, None, None], gains, -jnp.inf)

    flat = gains.reshape(S, -1)
    bi = jnp.argmax(flat, axis=1)
    bg = jnp.take_along_axis(flat, bi[:, None], axis=1)[:, 0]
    per_f = (maxB - 1) * 2
    f_star = (bi // per_f).astype(jnp.int32)
    rem = bi % per_f
    t_star = (rem // 2).astype(jnp.int32)
    na_left = (rem % 2).astype(jnp.bool_)

    valid = bg > min_split_improvement
    split_feat = jnp.where(valid, f_star, -1)

    # routing LUT: bin b goes left iff its position in the sorted order <= t*
    order_sel = jnp.take_along_axis(
        order, f_star[:, None, None].repeat(maxB, 2), axis=1)[:, 0, :]  # (S,maxB)
    rank = jnp.argsort(order_sel, axis=1)          # inverse permutation
    go_left = rank <= t_star[:, None]
    napos_sel = na_pos[f_star]                     # (S,)
    left_table = jnp.where(binsr[None, :] == napos_sel[:, None],
                           na_left[:, None], go_left)

    tot0 = tot[:, 0, :]                            # per-f totals identical
    return (split_feat, t_star, na_left,
            jnp.where(valid, bg, 0.0).astype(jnp.float32),
            left_table, tot0)


# ---------------------------------------------------------------------------
# routing: one level of one tree, shared by tree_program and apply_packed
# ---------------------------------------------------------------------------

def _route(binned, row_node, row_leaf, gid0: int, split):
    """Move every live row (row_leaf < 0) one level down: a row whose slot
    is terminal gets its global leaf id gid0 + slot, the others the child
    slot `left_table[slot, bin at the slot's split feature]` sends them to.
    `split` = this level's (split_feat, left_slot, right_slot) (S,) int32
    and left_table (S, maxB) bool; None at the last level, where every slot
    is terminal and nothing is read. -> (row_node, row_leaf).

    No operand of a gather here carries the row axis (module docstring):
    the bin by compressed._bin_at, the slot's scalars by
    compressed._at_node, and left_table[slot, b] as bit b & 31 of the
    uint32 word at slot * W + (b >> 5) of the table packed W words a slot
    (on S x maxB elements, once a level), read through _at_node too. One
    form for thresholds and subsets of levels: left_table keeps its
    meaning."""
    import jax.numpy as jnp

    from h2o3_tpu.models.tree.compressed import _at_node, _bin_at

    live = row_leaf < 0
    if split is None:
        return row_node, jnp.where(live, gid0 + row_node, row_leaf)
    split_feat, left_slot, right_slot, left_table = split
    S, maxB = left_table.shape
    W = route_words(maxB)
    bits = jnp.pad(left_table, ((0, 0), (0, W * 32 - maxB))).astype(jnp.uint32)
    words = jnp.sum(bits.reshape(S * W, 32) << jnp.arange(32, dtype=jnp.uint32),
                    axis=1, dtype=jnp.uint32)
    f, lft, rgt = _at_node((split_feat, left_slot, right_slot), row_node)
    terminal = f < 0
    row_leaf = jnp.where(live & terminal, gid0 + row_node, row_leaf)
    b = jnp.minimum(_bin_at(binned, jnp.maximum(f, 0))[0], maxB - 1)
    word, = _at_node((words,), row_node * W + (b >> 5))
    go_left = (word >> (b & 31).astype(jnp.uint32)) & 1 == 1
    return (jnp.where(live & ~terminal, jnp.where(go_left, lft, rgt), 0),
            row_leaf)


def route_words(maxB: int) -> int:
    """uint32 words a slot's row of left_table packs into."""
    return -(-maxB // 32)


def route_forms(max_depth: int, F: int, maxB: int) -> Tuple[str, ...]:
    """How each routing level of a tree reads its widest table, the packed
    words (`select` | `gather`: _at_node's rule, from S x W); the last
    level reads none and is not listed."""
    from h2o3_tpu.models.tree.compressed import table_form

    widths = level_widths(max_depth, frontier_cap(F, maxB))
    return tuple(table_form(S * route_words(maxB))
                 for S in widths[:max_depth])


# ---------------------------------------------------------------------------
# the level histogram: two lowerings of one signature, one rule from shape
# ---------------------------------------------------------------------------
# Both run inside a shard_map over the mesh's "rows" axis on one shard's
# rows, padded to a multiple of blk with dead rows (tree_program does
# that), and return the psum'd (S, F, maxB, 3) sums of (w, w·y, w·y²) over
# the live rows of each (slot, feature, bin). nbins (F,) are the bins each
# feature has, maxB their maximum.

_SUBLANES = 8               # sublanes of a 32-bit TPU tile


def hist_lane_widths(nbins: tuple) -> list:
    """Lanes a feature takes in hist_matmul's one-hot: its bins rounded up
    to whole sublane tiles."""
    return [-(-int(nb) // _SUBLANES) * _SUBLANES for nb in nbins]


def hist_matmul(binned, row_node, live, w, y, S: int, *, nbins: tuple,
                maxB: int, blk: int):
    """(S, F, maxB, 3) via blocked bf16 one-hot matmul + psum — the
    MXU lowering; O(N·lanes·S·3) FLOPs, almost all on zeros. The
    one-hot carries the bins that exist, nbins[f] lanes a feature
    (BinSpec.offsets' layout), not maxB: with a 300-level enum beside a
    7-level one two thirds of F·maxB lanes would be bins no row can
    fall in.

    Both operands are built with the rows on the lane axis (the layout
    the bin matrix has on a TPU) and nothing placed at an offset inside a
    tile (module docstring): the bin one-hot (L, blk) is a feature's row of
    bins broadcast over that feature's lanes, each feature's lanes rounded
    up to whole sublane tiles, against the static bin of each lane (-1 in
    the padding: no row has it); the values (3S, blk) are one compare of
    the row's slot against the static slot of each of 3S lanes, column
    order (3, S). The zeros and ones are the same as any other build's, so
    the sums are bit for bit what the concatenation of jax.nn.one_hot a
    feature gave (tests/test_tree_hist.py holds that build). The sums are
    laid out to (S, F, maxB, 3) afterwards, zeros in the lanes a feature
    does not have."""
    import jax
    import jax.numpy as jnp

    F = len(nbins)
    widths = hist_lane_widths(nbins)
    offs = np.cumsum([0] + widths[:-1])
    lane_bin = [np.where(np.arange(wd) < nb, np.arange(wd), -1)[:, None]
                .astype(np.int32) for nb, wd in zip(nbins, widths)]
    lane_slot = np.tile(np.arange(S, dtype=np.int32), 3)[:, None]
    lane_val = np.repeat(np.arange(3), S)[:, None]
    binsT = binned.T                                         # (F, n)

    def body(i, acc):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * blk, blk,
                                                    a.ndim - 1)
        bb = sl(binsT).astype(jnp.int32)
        nodeb = sl(row_node)
        wb = jnp.where(sl(live), sl(w), 0.0)
        yb = sl(y)
        wyb = wb * yb
        Ob = jnp.concatenate([bb[f][None, :] == lane_bin[f]
                              for f in range(F)])            # (L, blk)
        val = jnp.where(lane_val == 0, wb[None, :],
                        jnp.where(lane_val == 1, wyb[None, :],
                                  (wyb * yb)[None, :]))
        V = jnp.where(nodeb[None, :] == lane_slot, val, 0.0)  # (3S, blk)
        return acc + jax.lax.dot_general(
            Ob.astype(jnp.bfloat16), V.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    acc0 = _compat_pcast(jnp.zeros((sum(widths), 3 * S), jnp.float32),
                         ("rows",), to="varying")
    acc = jax.lax.fori_loop(0, binned.shape[0] // blk, body, acc0)
    with jax.named_scope("psum"):
        acc = jax.lax.psum(acc, "rows")
    acc = jnp.concatenate(
        [jnp.pad(acc[o:o + nb], ((0, maxB - nb), (0, 0)))
         for o, nb in zip(offs, nbins)])
    return acc.reshape(F, maxB, 3, S).transpose(3, 0, 1, 2)


def hist_scatter(binned, row_node, live, w, y, S: int, *, nbins: tuple,
                 maxB: int, blk: int):
    """(S, F, maxB, 3) via scatter-add — O(N·F) per level, the right
    asymptotics once the frontier is thousands wide (deep DRF levels);
    the matmul path's O(N·F·B·S) FLOPs stop being free there. One
    scatter over the shard's rows: blk is not used."""
    import jax
    import jax.numpy as jnp

    F = len(nbins)
    node = jnp.where(live, row_node, S)               # dead rows → pad slot
    base = (node[:, None] * F + jnp.arange(F)[None, :]) * maxB + binned
    w_live = jnp.where(live, w, 0.0)
    vals = jnp.stack([w_live, w_live * y, w_live * y * y], -1)  # (n, 3)
    acc0 = _compat_pcast(jnp.zeros(((S + 1) * F * maxB, 3), jnp.float32),
                         ("rows",), to="varying")
    acc = acc0.at[base.reshape(-1)].add(
        jnp.broadcast_to(vals[:, None, :],
                         (vals.shape[0], F, 3)).reshape(-1, 3))
    with jax.named_scope("psum"):
        acc = jax.lax.psum(acc, "rows")
    return acc[: S * F * maxB].reshape(S, F, maxB, 3)


def hist_lowering(S: int):
    """The histogram lowering of a level, from its static width alone:
    the matmul while the node one-hot is at most MATMUL_S_LIMIT wide, the
    scatter-add beyond (module docstring)."""
    return hist_matmul if S <= MATMUL_S_LIMIT else hist_scatter


# ---------------------------------------------------------------------------
# the leaf pass: per-leaf sums of the GammaPass inputs, on the MXU
# ---------------------------------------------------------------------------

_LEAF_COLS = 4              # leaf_sums' columns: w, w·y, num, den


def leaf_split(L: int) -> Tuple[int, int]:
    """(H, lo): how leaf_sums lays L slots out, slot = hi·lo + low with
    hi < H — ops.segsum.onehot_split's rule for its four columns, from the
    pass's static width: one one-hot up to 128 slots, 12·H + lo lanes a row
    past that (the sweep on a v5e, 16M rows, ms a tree at lo 128 / 256 /
    512 / 1,024: 2,048 slots 11.4 / 8.9 / 16.7 / 21.7, flat 33.1; 8,192
    slots 29.5 / 25.5 / 24.9 / 23.9; 16,384 slots 55.4 / 45.7 / 42.0 /
    42.7; 40,960 slots 133 / 113 / 105 / 102)."""
    return segsum.onehot_split(L, _LEAF_COLS)


def leaf_lanes(L: int) -> int:
    """Lanes a row takes in leaf_sums' two operands (_pick_blk's input)."""
    return segsum.onehot_lanes(L, _LEAF_COLS)


def leaf_sums(row_leaf, w, y, num, den, tot_slots: int, blk: int):
    """(tot_slots, 4) f32 sums of (w, w·y, num, den) over the rows of each
    global leaf slot, psum'd over `rows`; inside the same shard_map as the
    histograms. row_leaf (n,) int32 holds a row's leaf slot; a negative or
    off-range one (dead rows, pad rows at tot_slots) is summed into the
    off-range slot tot_slots and dropped. The four (n,) vectors are never
    stacked into an (n, 4) array, whose minor axis a TPU pads to 128 lanes.

    ops.segsum.segment_sum_mxu over L = tot_slots + 1 slots in blocks of
    blk rows: each block one dot of the leaf one-hot against the four
    columns' exact bf16 pieces, so every product is exact and only the f32
    accumulation rounds: f32 sums, in blocks, not a lower precision. Past
    128 slots the layout is leaf_split's, (12·H, blk) · (lo, blk)ᵀ."""
    import jax.numpy as jnp

    def slot_of(sl):
        slot = sl(row_leaf)
        return jnp.where(slot >= 0, jnp.minimum(slot, tot_slots), tot_slots)

    def cols_of(sl):
        wb = sl(w)
        return wb, wb * sl(y), sl(num), sl(den)

    return segsum.segment_sum_mxu(
        slot_of, cols_of, n=row_leaf.shape[0], k=_LEAF_COLS,
        nslots=tot_slots + 1, axis="rows", blk=blk)[:tot_slots]


# ---------------------------------------------------------------------------
# the per-tree program
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _grow_fn(max_depth: int, F: int, maxB: int, nbins: tuple, is_cat: tuple,
             min_rows: float, min_split_improvement: float,
             has_masks: bool, mesh, n_shard: int, blk: int, cap: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.obs import compiles

    nblk = -(-n_shard // blk)
    pad_to = nblk * blk
    widths = level_widths(max_depth, cap)
    offs = level_offsets(widths)
    tot_slots = sum(widths)
    Smax = max(widths)
    K = pack_width(maxB)
    leaf_blk = _pick_blk(pad_to, leaf_lanes(tot_slots + 1))

    def tree_program(binned, w, y, num, den, masks):
        n = binned.shape[0]
        if pad_to != n:
            padn = pad_to - n
            binned = jnp.pad(binned, ((0, padn), (0, 0)))
            w = jnp.pad(w, (0, padn))
            y = jnp.pad(y, (0, padn))
            num = jnp.pad(num, (0, padn))
            den = jnp.pad(den, (0, padn))
        # center y for the histogram: SE-reduction gains are invariant under
        # a constant shift, and a centered target keeps the bf16 histogram
        # operands at signal scale (w·y² of a mean-1000/σ-20 target would
        # otherwise bury the gains in quantization noise). Leaf statistics
        # (leaf4) use the UNcentered values through the f32 path below; only
        # the packed per-node (w, wy, wyy) totals are in centered space.
        swy, sw = jnp.sum(w * y), jnp.sum(w)
        with jax.named_scope("stats/psum"):     # the all-reduces alone
            swy, sw = jax.lax.psum(swy, "rows"), jax.lax.psum(sw, "rows")
        ymean = swy / jnp.maximum(sw, EPS_W)
        yc = y - ymean
        row_node = jnp.zeros(pad_to, jnp.int32)
        row_leaf = jnp.full(pad_to, -1, jnp.int32)
        if pad_to != n:        # pad rows are immediately dead
            row_leaf = row_leaf.at[n:].set(tot_slots)   # off-range sentinel

        packed = jnp.zeros((max_depth + 1, Smax, K), jnp.float32)
        for d in range(max_depth + 1):
            S = widths[d]
            live = row_leaf < 0
            if d < max_depth:
                # named scopes are metadata on the ops: a profiler trace
                # can sum device time by level and stage, whatever numbers
                # XLA gives its fusions
                with jax.named_scope(f"level{d}/hist"):
                    hist = hist_lowering(S)(binned, row_node, live, w, yc,
                                            S, nbins=nbins, maxB=maxB,
                                            blk=blk)
                fm = masks[d] if has_masks else None
                with jax.named_scope(f"level{d}/search"):
                    (split_feat, t_star, na_left, gain,
                     left_table, tot) = _search_level(
                        hist, nbins=nbins, is_cat=is_cat, maxB=maxB,
                        min_rows=min_rows,
                        min_split_improvement=min_split_improvement,
                        feat_mask=fm)
            else:
                split_feat = jnp.full(S, -1, jnp.int32)
                t_star = jnp.zeros(S, jnp.int32)
                na_left = jnp.zeros(S, bool)
                gain = jnp.zeros(S, jnp.float32)
                left_table = jnp.zeros((S, maxB), bool)
                tot = jnp.zeros((S, 3), jnp.float32)

            # frontier budget: keep at most S_{d+1}//2 splits, best-gain
            # first; the rest terminalize (greedy-best under the cap)
            if d < max_depth:
                S_next = widths[d + 1]
                want = split_feat >= 0
                if 2 * S > S_next:          # cap can bind at this level
                    max_splits = S_next // 2
                    order = jnp.argsort(-jnp.where(want, gain, -jnp.inf))
                    rank = jnp.argsort(order)
                    keep = want & (rank < max_splits)
                else:
                    keep = want
                split_feat = jnp.where(keep, split_feat, -1)
                gain = jnp.where(keep, gain, 0.0)
                ki = keep.astype(jnp.int32)
                excl = jnp.cumsum(ki) - ki
                left_slot = jnp.where(keep, 2 * excl, -1)
                right_slot = jnp.where(keep, 2 * excl + 1, -1)
            else:
                left_slot = jnp.full(S, -1, jnp.int32)
                right_slot = jnp.full(S, -1, jnp.int32)

            # de-center the recorded node totals back to true y space
            # (wy = wy_c + w·ȳ; wyy = wyy_c + 2ȳ·wy_c + ȳ²·w)
            tot_true = jnp.stack(
                [tot[:, 0],
                 tot[:, 1] + tot[:, 0] * ymean,
                 tot[:, 2] + 2 * ymean * tot[:, 1] + ymean * ymean * tot[:, 0]],
                axis=1)
            row = jnp.concatenate(
                [split_feat.astype(jnp.float32)[:, None],
                 t_star.astype(jnp.float32)[:, None],
                 na_left.astype(jnp.float32)[:, None],
                 gain[:, None],
                 left_table.astype(jnp.float32),
                 tot_true,
                 left_slot.astype(jnp.float32)[:, None],
                 right_slot.astype(jnp.float32)[:, None]], axis=1)  # (S, K)
            packed = packed.at[d, :S, :].set(row)

            with jax.named_scope(f"level{d}/route"):
                row_node, row_leaf = _route(
                    binned, row_node, row_leaf, offs[d],
                    None if d == max_depth else
                    (split_feat, left_slot, right_slot, left_table))

        with jax.named_scope("leaf_sums"):
            leaf4 = leaf_sums(row_leaf, w, y, num, den, tot_slots, leaf_blk)
        row_leaf = jnp.where(row_leaf >= tot_slots, -1, row_leaf)  # clear pad
        return packed, leaf4, row_leaf[:n]

    in_specs = (P("rows", None), P("rows"), P("rows"), P("rows"), P("rows"),
                tuple(P() for _ in range(max_depth)) if has_masks else P())
    fn = _compat_shard_map(tree_program, mesh=mesh,
                       in_specs=in_specs,
                       out_specs=(P(), P(), P("rows")))
    return compiles.ledgered_jit(
        "tree", fn, program=f"tree_grow_d{max_depth}")


def _count_route(forms: Tuple[str, ...]) -> None:
    """h2o3_tree_route_levels_total{form} and the `trees` span's
    `route_levels` / `route_gather_levels`, from the static level widths of
    the tree being dispatched: host arithmetic, no device op."""
    from h2o3_tpu.obs import metrics, tracing

    gathered = forms.count("gather")
    metrics.inc("h2o3_tree_route_levels_total", len(forms) - gathered,
                form="select")
    metrics.inc("h2o3_tree_route_levels_total", gathered, form="gather")
    tracing.add_attrs(route_levels=len(forms), route_gather_levels=gathered)


def hist_forms(max_depth: int, F: int, maxB: int) -> Tuple[str, ...]:
    """The lowering of each histogram level of a tree (`matmul` |
    `scatter`: hist_lowering's rule, from the level's width); the last
    level builds none and is not listed."""
    widths = level_widths(max_depth, frontier_cap(F, maxB))
    return tuple("matmul" if hist_lowering(S) is hist_matmul else "scatter"
                 for S in widths[:max_depth])


def _count_hist(forms: Tuple[str, ...]) -> None:
    """h2o3_tree_hist_levels_total{lowering} and the `trees` span's
    `hist_matmul_levels` / `hist_scatter_levels`, counted like
    _count_route: host arithmetic on static widths, no device op."""
    from h2o3_tpu.obs import metrics, tracing

    scattered = forms.count("scatter")
    metrics.inc("h2o3_tree_hist_levels_total", len(forms) - scattered,
                lowering="matmul")
    metrics.inc("h2o3_tree_hist_levels_total", scattered, lowering="scatter")
    tracing.add_attrs(hist_matmul_levels=len(forms) - scattered,
                      hist_scatter_levels=scattered)


def leaf_forms(max_depth: int, F: int, maxB: int) -> str:
    """How a tree's leaf pass lays its slots out (`matmul`: one one-hot |
    `matmul_split`: the slot's low part a one-hot, its high part a mask
    of the values: leaf_split's rule, from the tree's total slots)."""
    H, _lo = leaf_split(total_slots(max_depth, frontier_cap(F, maxB)) + 1)
    return "matmul" if H == 1 else "matmul_split"


def _count_leaf(form: str) -> None:
    """h2o3_tree_leaf_sums_total{lowering} and the `trees` span's
    `leaf_lowering`, counted like _count_route: host arithmetic on static
    widths, no device op."""
    from h2o3_tpu.obs import metrics, tracing

    metrics.inc("h2o3_tree_leaf_sums_total", 1, lowering=form)
    tracing.set_attrs(leaf_lowering=form)


def psum_bytes(max_depth: int, nbins: tuple, shards: int) -> dict:
    """Bytes a shard hands each all-reduce over `rows` of one tree, summed
    by site, from static shapes: `hist` (a level's f32 sums as its lowering
    lays them out: lanes x 3S for the matmul, (S + 1)·F·maxB x 3 for the
    scatter), `leaf_sums` ((total_slots + 1) x 4 f32) and `stats` (the two
    scalars of the centering mean). All 0 on a mesh of one device, where a
    psum moves nothing."""
    if shards <= 1:
        return {"hist": 0, "leaf_sums": 0, "stats": 0}
    F, maxB = len(nbins), max(nbins)
    widths = level_widths(max_depth, frontier_cap(F, maxB))
    lanes = sum(hist_lane_widths(nbins))
    hist = sum(lanes * 3 * S if form == "matmul" else (S + 1) * F * maxB * 3
               for form, S in zip(hist_forms(max_depth, F, maxB), widths))
    return {"hist": 4 * hist, "leaf_sums": 4 * (sum(widths) + 1) * _LEAF_COLS,
            "stats": 4 * 2}


def _count_psum(sites: dict, shards: int) -> None:
    """h2o3_tree_psum_bytes_total{site} and the `trees` span's `shards` /
    `psum_bytes`, counted like _count_route: host arithmetic on static
    shapes, no device op."""
    from h2o3_tpu.obs import metrics, tracing

    for site, n in sites.items():
        metrics.inc("h2o3_tree_psum_bytes_total", n, site=site)
    tracing.set_attrs(shards=shards)
    tracing.add_attrs(psum_bytes=sum(sites.values()))


def _pick_blk(n_shard: int, lanes: int) -> int:
    """Row-block size: keep the per-block (blk, lanes) bf16 one-hot under
    ~64 MB (ops.segsum.row_block)."""
    return segsum.row_block(n_shard, lanes)


def _mesh_size(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names])) or 1


def grow_tree_device(binned, w, y, spec, *, max_depth: int, min_rows: float,
                     min_split_improvement: float, num=None, den=None,
                     feat_masks: Optional[List[np.ndarray]] = None):
    """Grow one tree fully on device — NOTHING is fetched to host.

    binned (N, F) integer bin matrix (uint8/int16/int32 per BinSpec.bin_columns)
    row-sharded — since the sharded data plane (PR 7) this block is packed
    shard-locally by core/sharded_frame, so the training input pipeline
    never stages full columns on the coordinator; w, y, num, den (N,)
    device (num/den are
    the GammaPass numerator/denominator rows; default num=w·y, den=w).
    feat_masks: optional per-level (S_d, F) bool arrays, levels
    0..max_depth-1 (mtries / column sampling) — widths per level_widths().

    Returns device arrays (packed, leaf4, row_leaf):
      packed   — (max_depth+1, S_max, pack_width(maxB)) f32 per-level split
                 tables with explicit child-slot links
      leaf4    — (total_slots, 4) per-leaf sums of (w, w·y, num, den),
                 indexed by GLOBAL slot id (level offset + slot)
      row_leaf — (N,) int32 global leaf slot id per row
    """
    import jax.numpy as jnp

    mesh = _mesh()
    N, F = binned.shape
    shards = _mesh_size(mesh)
    n_shard = N // shards
    maxB = int(spec.nbins.max())
    nbins = tuple(int(b) for b in spec.nbins)
    blk = _pick_blk(n_shard, int(spec.nbins.sum()))
    has_masks = feat_masks is not None
    fn = _grow_fn(int(max_depth), F, maxB, nbins,
                  tuple(bool(c) for c in spec.is_cat), float(min_rows),
                  float(min_split_improvement), has_masks, mesh, n_shard, blk,
                  frontier_cap(F, maxB))
    _count_route(route_forms(int(max_depth), F, maxB))
    _count_hist(hist_forms(int(max_depth), F, maxB))
    _count_leaf(leaf_forms(int(max_depth), F, maxB))
    _count_psum(psum_bytes(int(max_depth), nbins, shards), shards)
    w = w.astype(jnp.float32)
    y = y.astype(jnp.float32)
    if num is None:
        num = w * y
    if den is None:
        den = w
    # host numpy inputs replicate cleanly under multi-process meshes (a
    # process-local device array would carry a conflicting placement)
    masks_in = (tuple(np.asarray(m) for m in feat_masks) if has_masks
                else np.zeros(0, np.float32))
    return fn(binned, w, y, num.astype(jnp.float32), den.astype(jnp.float32),
              masks_in)


# ---------------------------------------------------------------------------
# device traversal with packed tables (in-training validation scoring)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _apply_fn(max_depth: int, maxB: int, mesh, cap: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    widths = level_widths(max_depth, cap)
    offs = level_offsets(widths)
    K = pack_width(maxB)

    def apply(binned, packed, values):
        """Route rows through the packed tree; -> (n,) leaf values."""
        n = binned.shape[0]
        row_node = jnp.zeros(n, jnp.int32)
        row_leaf = jnp.full(n, -1, jnp.int32)
        for d in range(max_depth + 1):
            lv = packed[d, :widths[d]]
            row_node, row_leaf = _route(
                binned, row_node, row_leaf, offs[d],
                None if d == max_depth else
                (lv[:, 0].astype(jnp.int32), lv[:, K - 2].astype(jnp.int32),
                 lv[:, K - 1].astype(jnp.int32), lv[:, 4:4 + maxB] > 0.5))
        return values[jnp.maximum(row_leaf, 0)]

    fn = _compat_shard_map(apply, mesh=mesh,
                       in_specs=(P("rows", None), P(), P()),
                       out_specs=P("rows"))
    from h2o3_tpu.obs import compiles

    return compiles.ledgered_jit("tree", fn,
                                 program=f"tree_apply_d{max_depth}")


def apply_packed(binned, packed, values, max_depth: int, maxB: int):
    """Device traversal: (N, F) binned rows -> (N,) leaf values, using a
    packed tree table and a (total_slots,) leaf-value array."""
    import jax.numpy as jnp

    F = binned.shape[1]
    fn = _apply_fn(int(max_depth), int(maxB), _mesh(), frontier_cap(F, maxB))
    return fn(binned, packed, values.astype(jnp.float32))


def assemble_trees(packs, leaf_vals, leaf_wys, spec, max_depth: int,
                   scale: float = 1.0):
    """End-of-training epilogue shared by every fit loop: stack the
    device-resident per-tree tables, fetch them in ONE transfer, and build
    the HostTrees (leaf values scaled by `scale` — DRF divides by the tree
    count so the summed traversal averages)."""
    import jax.numpy as jnp

    if packs and isinstance(packs[0], np.ndarray):
        # deep trees were host-stashed per tree (stash_packed) — stack on
        # HOST; re-uploading would recreate the full-forest HBM footprint
        packs_np = np.stack(packs)
    else:
        packs_np = np.asarray(jnp.stack(packs))
    vals_np = np.asarray(jnp.stack(leaf_vals), np.float64) * scale
    wys_np = np.asarray(jnp.stack(leaf_wys), np.float64)
    _count_splits(packs_np, spec, max_depth)
    return [host_tree_from_packed(packs_np[i], wys_np[i], spec, max_depth,
                                  leaf_values=vals_np[i])
            for i in range(len(packs))]


def _count_splits(packs_np: np.ndarray, spec, max_depth: int) -> None:
    """h2o3_tree_splits_total{kind} and the `assemble` span's `nodes` /
    `enum_splits`, from the fetched tables: a slot inside its level's width
    whose split feature is >= 0 is a split (slots beyond the width are the
    table's zero fill), and its kind is its feature's."""
    from h2o3_tpu.obs import metrics, tracing

    widths = level_widths(max_depth,
                          frontier_cap(spec.F, int(spec.nbins.max())))
    feats = np.concatenate(
        [packs_np[:, d, :S, 0].reshape(-1) for d, S in enumerate(widths)])
    feats = feats[feats >= 0].astype(np.int64)
    enum = int(np.count_nonzero(np.asarray(spec.is_cat)[feats]))
    metrics.inc("h2o3_tree_splits_total", enum, kind="enum")
    metrics.inc("h2o3_tree_splits_total", len(feats) - enum, kind="numeric")
    tracing.set_attrs(nodes=2 * len(feats) + int(packs_np.shape[0]),
                      enum_splits=enum)


# ---------------------------------------------------------------------------
# host tree assembly (end-of-training, from the batch-fetched tables)
# ---------------------------------------------------------------------------

def host_tree_from_packed(packed_np: np.ndarray, leaf_wy: np.ndarray,
                          spec, max_depth: int,
                          leaf_values: Optional[np.ndarray] = None):
    """Assemble a HostTree from one tree's packed table (numpy).

    packed_np (max_depth+1, S_max, K); leaf_wy (total_slots, 2) = per-leaf
    (w, w·y); leaf_values optional (total_slots,) final leaf predictions.
    Leaf ids are GLOBAL slot ids — n_leaves is total_slots, so leaf-value
    arrays index directly by global slot id."""
    from h2o3_tpu.models.tree.dtree import HostTree, Split

    maxB = int(spec.nbins.max())
    K = pack_width(maxB)
    cap = frontier_cap(spec.F, maxB)
    widths = level_widths(max_depth, cap)
    offs = level_offsets(widths)
    tree = HostTree()
    tree.n_leaves = sum(widths)
    slot_nid = {(0, 0): 0}
    root_tot = packed_np[0, 0, 4 + maxB:4 + maxB + 3]
    tree.nodes[0].weight = float(root_tot[0])
    tree.nodes[0].pred = float(root_tot[1]) / max(float(root_tot[0]), EPS_W)

    for d in range(max_depth + 1):
        lv = packed_np[d]
        next_lv = packed_np[d + 1] if d + 1 <= max_depth else None
        for (dd, s), nid in [x for x in slot_nid.items() if x[0][0] == d]:
            node = tree.nodes[nid]
            f = int(lv[s, 0])
            if f < 0:
                gid = offs[d] + s
                node.leaf_id = gid
                lw, lwy = leaf_wy[gid]
                node.weight = float(lw)
                node.pred = float(lwy) / max(float(lw), EPS_W)
                if leaf_values is not None:
                    node.leaf_value = float(leaf_values[gid])
                continue
            Bf = int(spec.nbins[f])
            lt_row = lv[s, 4:4 + maxB] > 0.5
            if bool(spec.is_cat[f]):
                sp = Split(f, True, -1, lt_row[: Bf - 1].copy(),
                           bool(lv[s, 2] > 0.5), float(lv[s, 3]),
                           (0.0, 0.0), (0.0, 0.0))
            else:
                sp = Split(f, False, int(lv[s, 1]), None,
                           bool(lv[s, 2] > 0.5), float(lv[s, 3]),
                           (0.0, 0.0), (0.0, 0.0))
            node.split = sp
            node.left = tree.new_node(d + 1)
            node.right = tree.new_node(d + 1)
            ls, rs = int(lv[s, K - 2]), int(lv[s, K - 1])
            slot_nid[(d + 1, ls)] = node.left
            slot_nid[(d + 1, rs)] = node.right
            if next_lv is not None:
                for child_nid, cs in ((node.left, ls), (node.right, rs)):
                    cw = float(next_lv[cs, 4 + maxB])
                    cwy = float(next_lv[cs, 4 + maxB + 1])
                    tree.nodes[child_nid].weight = cw
                    tree.nodes[child_nid].pred = cwy / max(cw, EPS_W)
                sp.left_stats = (float(next_lv[ls, 4 + maxB]),
                                 float(next_lv[ls, 4 + maxB + 1]))
                sp.right_stats = (float(next_lv[rs, 4 + maxB]),
                                  float(next_lv[rs, 4 + maxB + 1]))
    return tree
