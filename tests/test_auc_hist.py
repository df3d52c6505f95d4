"""The AUC histogram of the binomial metrics pass (models/metrics.
_binomial_hist, ops.segsum's blocked one-hot dot) against a float64
`np.add.at` over the same bins, rows sharded or not; and segment_sum_mxu
itself with row counts on both sides of a block's edge."""

import numpy as np
import pytest

BLK = 64
NS = [1, 7, BLK - 1, BLK, BLK + 1, 3 * BLK + 5]


def _case(n, seed=0, exact=False):
    """y, p, w (f32 numpy): p at exact bin edges k/400, at 0, 1 and 1-1e-7
    among uniform draws; fractional weights, a zero weight on every fifth
    row, and y = NaN on the zero-weight rows. exact: weights in eighths, so
    that every sum of them is exact in f32 in any order."""
    rng = np.random.default_rng(seed + n)
    p = rng.random(n).astype(np.float32)
    edges = np.concatenate([np.arange(0, 401, 23) / 400.0,
                            [0.0, 1.0, 1.0 - 1e-7]]).astype(np.float32)
    take = rng.random(n) < 0.4
    p[take] = rng.choice(edges, int(take.sum()))
    y = (rng.random(n) < 0.35).astype(np.float32)
    w = rng.uniform(0.05, 3.0, n).astype(np.float32)
    if exact:
        w = (np.ceil(w * 8) / 8).astype(np.float32)
    w[::5] = 0.0
    y[w == 0] = np.nan
    return y, p, w


def _truth(y, p, w, nbins=400):
    """float64 sums over the bins the program's f32 expression gives."""
    b = np.clip((p * np.float32(nbins)).astype(np.int32), 0, nbins - 1)
    live = w != 0
    y64, w64 = y.astype(np.float64), w.astype(np.float64)
    pos, neg = np.zeros(nbins), np.zeros(nbins)
    np.add.at(pos, b[live], (w64 * y64)[live])
    np.add.at(neg, b[live], (w64 * (1 - y64))[live])
    return pos, neg


def _row_sharded(arrays, shards):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:shards]), ("rows",))
    return [jax.device_put(a, NamedSharding(mesh, P("rows"))) for a in arrays]


def _close(got, want, rel=1e-6):
    got = np.asarray(got, np.float64)
    assert np.all(np.abs(got - want) <= rel * np.abs(want) + 1e-30), \
        np.max(np.abs(got - want))


@pytest.mark.parametrize("nslots", [64, 400])
@pytest.mark.parametrize("n", NS)
def test_segment_sum_block_edges(n, nslots):
    """segment_sum_mxu in blocks of 64 rows, one one-hot (64 slots) and
    split as lo 128 x H 4 (400): every slot within 1e-6 of the float64
    sums; rows with a slot of -1, nslots or past H·lo add to none."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.ops import segsum

    rng = np.random.default_rng(n + nslots)
    slot = rng.integers(0, nslots, n).astype(np.int32)
    slot[::6] = rng.choice([-1, nslots, 4 * 128 + 3], len(slot[::6]))
    a = rng.uniform(-2.0, 3.0, n).astype(np.float32)
    b = rng.uniform(0.0, 1.0, n).astype(np.float32)
    got = jax.jit(lambda s, x, y: segsum.segment_sum_mxu(
        lambda sl: sl(s), lambda sl: (sl(x), sl(y)), n=n, k=2,
        nslots=nslots, blk=BLK))(*(jnp.asarray(v) for v in (slot, a, b)))
    want = np.zeros((nslots, 2))
    live = (slot >= 0) & (slot < nslots)
    np.add.at(want[:, 0], slot[live], a[live].astype(np.float64))
    np.add.at(want[:, 1], slot[live], b[live].astype(np.float64))
    mag = np.zeros(nslots)
    np.add.at(mag, slot[live], np.abs(a[live]).astype(np.float64))
    got = np.asarray(got, np.float64)
    assert got.shape == (nslots, 2)
    assert np.all(np.abs(got[:, 0] - want[:, 0]) <= 1e-6 * mag + 1e-30)
    _close(got[:, 1], want[:, 1])


@pytest.mark.parametrize("n", NS)
def test_matmul_bins_against_f64_truth(n):
    """Every bin within 1e-6 of the float64 sums, the zero-weight NaN
    rows in none."""
    import jax.numpy as jnp

    from h2o3_tpu.models import metrics as M

    y, p, w = _case(n)
    pos, neg = M._binomial_hist(jnp.asarray(y), jnp.asarray(p),
                                jnp.asarray(w))
    tp, tn = _truth(y, p, w)
    _close(pos, tp)
    _close(neg, tn)


@pytest.mark.parametrize("n", NS)
def test_auc_and_gains_lift_agree_with_f64_truth(n):
    """compute_auc and gains_lift read the same from the program's bins as
    from the float64 sums over the same rows. The weights are eighths, so
    the f32 sums are exact in any order (random weights differ in f32
    rounding alone, which moves an AUC by about 1e-8: the test above
    bounds the bins)."""
    import jax.numpy as jnp

    from h2o3_tpu.models import metrics as M

    y, p, w = _case(n, seed=1, exact=True)
    mm = [np.asarray(h, np.float64) for h in M._binomial_hist(
        *(jnp.asarray(a) for a in (y, p, w)))]
    sc = list(_truth(y, p, w))
    a, b = M.compute_auc(*mm), M.compute_auc(*sc)
    for field in ("auc", "pr_auc", "gini", "max_f1", "max_f1_threshold",
                  "p", "n"):
        assert getattr(a, field) == pytest.approx(getattr(b, field),
                                                  abs=1e-9), field
    (ga, ka), (gb, kb) = M.gains_lift(*mm), M.gains_lift(*sc)
    assert ka == pytest.approx(kb, abs=1e-9, nan_ok=True)
    assert len(ga.rows) == len(gb.rows)
    assert np.allclose(np.asarray(ga.rows, np.float64),
                       np.asarray(gb.rows, np.float64), rtol=0, atol=1e-9)


@pytest.mark.parametrize("n", [4, 28, 4 * (3 * BLK + 5)])
def test_row_sharded_over_four_devices(n, monkeypatch):
    """Rows sharded over 4 of the 8 devices: the shard_map program reads
    its mesh from the input, gives the one-device bins, and all-reduces
    the (400, 2) f32 sums once; the pass counts 800 values for it."""
    import jax.numpy as jnp

    from h2o3_tpu.models import metrics as M

    y, p, w = _case(n, seed=2, exact=True)
    one = M._binomial_hist(jnp.asarray(y), jnp.asarray(p), jnp.asarray(w))
    ys, ps, ws = _row_sharded((y, p, w), 4)
    mesh, axis = M._row_mesh(ys)
    assert mesh is not None and mesh.size == 4 and axis == "rows"
    four = M._binomial_hist(ys, ps, ws)
    tp, tn = _truth(y, p, w)
    for got, ref, want in zip(four, one, (tp, tn)):
        _close(got, want)
        _close(got, np.asarray(ref, np.float64))
    text = M._hist_matmul(M.NBINS, mesh, axis).lower(ys, ps, ws).as_text()
    assert text.count("all_reduce") == 1 and "tensor<400x2xf32>" in text

    counted = []
    monkeypatch.setattr(M, "_count_psum",
                        lambda y_, values: counted.append(values))
    mm = M.make_binomial_metrics(ys, ps, ws)
    assert counted == [3 + 2 * M.NBINS]
    assert mm.auc == pytest.approx(M.compute_auc(
        *(np.asarray(h) for h in one)).auc, abs=1e-9)


def test_two_calls_of_one_shape_compile_once():
    """The program is keyed by the rows (per shard), mesh and dtype: a
    second call of a shape is an executable-cache hit in obs/compiles."""
    import jax.numpy as jnp

    from h2o3_tpu.models import metrics as M
    from h2o3_tpu.obs import compiles

    def compiled():
        return compiles.family_table().get("metrics", {}).get("compiles", 0)

    y, p, w = (jnp.asarray(a) for a in _case(1013, seed=4))
    start = compiled()
    M._binomial_hist(y, p, w)
    assert compiled() == start + 1
    M._binomial_hist(y, p * 0.5, w)
    assert compiled() == start + 1


def test_empty_input_is_zeros():
    import jax.numpy as jnp

    from h2o3_tpu.models import metrics as M

    pos, neg = M._binomial_hist(*(jnp.zeros(0, jnp.float32),) * 3)
    assert pos.shape == neg.shape == (M.NBINS,)
    assert not np.any(np.asarray(pos)) and not np.any(np.asarray(neg))
