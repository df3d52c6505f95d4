"""What a cell of enum columns needs of the program, beside
``bench/harness/system.py``: a training frame of enum and real columns with
their level names as domains, and a reader of the trained forest that keeps
a categorical split as what it is, a set of levels.

Like ``system.py`` this module touches the program; nothing else the enum
cell brings does.
"""

from __future__ import annotations

import numpy as np


def install_training_frame(system, key: str, columns, cols, y,
                           response: str, response_domain) -> None:
    """Device columns (already row-sharded) -> a resident frame.
    ``columns``: (name, "enum" | "real", domain) per column, as the data
    recipe states them; enum columns arrive as level codes (-1 = missing)."""
    from h2o3_tpu.core.frame import Column, code_dtype

    n = int(y.shape[0])
    system._check_rows(n)
    fr = system.h2o.H2OFrame(destination_frame=key)
    for (name, ctype, dom), c in zip(columns, cols):
        if ctype == "enum":
            fr.add(name, Column.from_device(c.astype(code_dtype(len(dom))),
                                            "enum", n, domain=list(dom)))
        else:
            fr.add(name, Column.from_device(c, "real", n))
    codes = y.astype(code_dtype(len(response_domain)))
    fr.add(response, Column.from_device(codes, "enum", n,
                                        domain=list(response_domain)))
    fr.install()


def forest_arrays(fo, spec) -> dict:
    """A ``CompressedForest`` and its ``BinSpec`` as plain arrays. Beside
    what ``System.read_forest`` returns: ``na_left`` (T, M), ``cat_split``
    (T, M; -1 = numeric split, else a row of ``cat_rows``), ``cat_rows``
    (one bool array a categorical split, cut to its feature's level count:
    True = the level goes left), ``is_cat`` and ``levels`` per feature."""
    feat = np.asarray(fo.feat, np.int32)
    tb = np.asarray(fo.thresh_bin, np.int64)
    cs = np.asarray(fo.cat_split, np.int32)
    table = np.asarray(fo.cat_table, bool)
    levels = np.asarray(spec.cards, np.int64)
    thr = np.zeros(feat.shape, np.float32)
    cat_rows = [None] * int(cs.max() + 1 if cs.size else 0)
    for t, nid in zip(*np.nonzero(feat >= 0)):
        f = int(feat[t, nid])
        if cs[t, nid] >= 0:
            cat_rows[int(cs[t, nid])] = table[cs[t, nid], : int(levels[f])]
        else:
            thr[t, nid] = spec.threshold_value(f, int(tb[t, nid]))
    return {"feat": feat, "thr": thr,
            "na_left": np.asarray(fo.na_left, bool),
            "left": np.asarray(fo.left, np.int32),
            "right": np.asarray(fo.right, np.int32),
            "leaf": np.asarray(fo.leaf_val, np.float32),
            "cover": np.asarray(fo.cover, np.float64),
            "cat_split": cs, "cat_rows": cat_rows,
            "is_cat": np.asarray(spec.is_cat, bool), "levels": levels,
            "init_f": float(fo.init_f),
            "edges": [np.asarray(e, np.float32) for e in spec.edges],
            "max_depth": int(fo.max_depth)}


def read_forest(system, model_id: str) -> dict:
    m = system.model(model_id)
    return forest_arrays(m.forest, m.spec)
