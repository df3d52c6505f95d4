"""Segment sums on the MXU: per-slot sums of a few f32 columns over rows.

Reference: the per-bin / per-leaf accumulations of H2O's MRTasks
(hex/AUC2.java's threshold histogram; the GammaPass of hex/tree) add each
row into its slot of a small array. On a TPU the literal form, a
scatter-add, serializes on the rows. Here a block of rows is one dot on the
MXU instead: the slot one-hot, rows on lanes, against the columns' exact
bf16 pieces, with the row axis contracted. Products are exact and only the
f32 accumulation rounds, so the sums are f32 sums, in blocks.

Callers: the tree program's leaf pass (`models/tree/device_tree.leaf_sums`)
and the AUC histogram of the binomial metrics pass
(`models/metrics._binomial_hist`). The layout rule and the row block are
host arithmetic on static widths.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

PIECES = 3                  # bf16 pieces an f32 value splits into, exactly
LANES = 128                 # lanes of a TPU tile
ONEHOT_BUDGET = 64 * 1024 * 1024    # bytes of a block's bf16 operands


def onehot_split(L: int, k: int) -> Tuple[int, int]:
    """(H, lo): how L slots of k columns are laid out, slot = hi·lo + low
    with hi < H. Up to a tile's 128 lanes the slots are one one-hot
    (H = 1, lo = L). Past that the one-hot carries the low lo of a slot
    and the values are masked by its high part: 3k·H + lo lanes a row
    where a flat one-hot would be L, least near lo = sqrt(3k·L); lo is the
    power of two at or above it, at least 128."""
    if L <= LANES:
        return 1, L
    lo = LANES
    while lo * lo < PIECES * k * L:
        lo *= 2
    return -(-L // lo), lo


def onehot_lanes(L: int, k: int) -> int:
    """Lanes a row takes in the two operands of onehot_split's layout."""
    H, lo = onehot_split(L, k)
    return H * PIECES * k + lo


def row_block(n: int, lanes: int) -> int:
    """Row-block size: keep a block's (blk, lanes) bf16 operands under
    ONEHOT_BUDGET, a power of two of at least 1,024 rows, at most n."""
    budget = ONEHOT_BUDGET // (2 * lanes)
    blk = 1 << max(int(np.floor(np.log2(max(budget, 1)))), 10)
    return int(min(blk, max(n, 1)))


def segment_sum_mxu(slot_of, cols_of, *, n: int, k: int, nslots: int,
                    axis=None, blk: Optional[int] = None):
    """(nslots, k) f32 sums of k f32 columns over the rows of each slot,
    for n ≥ 1 rows. slot_of(sl) gives a block's (blk,) int32 slots and
    cols_of(sl) its k (blk,) f32 columns, where sl slices an (n,) array to
    the block: the caller's per-row arithmetic runs on the block, inside
    the loop, not on all n rows before it. A row whose slot lies outside
    [0, nslots) adds to none. Traceable; inside a shard_map give the row
    `axis` (a mesh axis name or a tuple of them), and the sums are psum'd
    over it once the pieces are added, so the all-reduce moves nslots·k
    f32.

    A block of blk rows (default row_block's) is one dot contracting the
    row axis, rows on lanes in both operands: the values (3k·H, blk) are
    the columns each as its three bf16 pieces (ops.elementwise.bf16_pieces:
    their sum is the f32 value), the one-hot (lo, blk) is a compare against
    a static slot a lane and exact in bf16; past 128 slots the one-hot
    carries the slot's low part and the values are masked by its high part
    (onehot_split). n need not be a multiple of blk: the last block starts
    early and the rows it shares with the one before count once. A
    non-finite value spreads to every slot (0 × NaN): zero such rows'
    columns in cols_of."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.compat import pcast
    from h2o3_tpu.ops.elementwise import bf16_pieces

    H, lo = onehot_split(nslots, k)
    blk = min(blk or row_block(n, onehot_lanes(nslots, k)), n)
    C = PIECES * k
    lane_lo = np.arange(lo, dtype=np.int32)[:, None]
    lane_hi = np.tile(np.arange(H, dtype=np.int32), C)[:, None]
    at = jnp.arange(blk, dtype=jnp.int32)

    def body(i, acc):
        start = jnp.minimum(i * blk, n - blk)
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, blk)
        s = slot_of(sl)
        s = jnp.where(start + at >= i * blk, s, -1)          # counted before
        V = jnp.stack([p for ps in zip(*(bf16_pieces(c) for c in cols_of(sl)))
                       for p in ps])                         # (3k, blk)
        if H == 1:
            O = s[None, :] == lane_lo
        else:
            # lo is a power of two here: low part by mask, high by shift
            O = (s & (lo - 1))[None, :] == lane_lo
            V = jnp.where((s >> (lo.bit_length() - 1))[None, :] == lane_hi,
                          jnp.repeat(V, H, axis=0), 0.0)     # (3k·H, blk)
        return acc + jax.lax.dot_general(
            V.astype(jnp.bfloat16), O.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    acc0 = jnp.zeros((C * H, lo), jnp.float32)
    if axis is not None:
        acc0 = pcast(acc0, axis if isinstance(axis, tuple) else (axis,),
                     to="varying")
    acc = jax.lax.fori_loop(0, -(-n // blk), body, acc0)
    acc = acc.reshape(PIECES, k, H * lo)
    acc = (acc[2] + acc[1] + acc[0])[:, :nslots].T           # (nslots, k)
    if axis is not None:
        with jax.named_scope("psum"):
            acc = jax.lax.psum(acc, axis)
    return acc
