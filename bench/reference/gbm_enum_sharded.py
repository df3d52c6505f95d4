"""``bench/reference/gbm_enum`` over columns that are row-sharded over
several devices: the same judge, the same block programs, each device's rows
walked on that device.

``gbm_enum`` reads a block of rows by ``dynamic_slice`` from the whole
column. On a column sharded over four chips the partitioner gathers the
column to every device for that, 320 MB a column a dispatch at 80M rows, so
the file as it is cannot judge a four-chip cell inside a run's time. Nothing
of its arithmetic changes here. ``_Sharded`` stands in for its ``_Data``:
one ``_Data`` a device over that device's shard of every column (a view, no
copy), the unedited jitted block programs running where their rows live,
the devices driven side by side from one thread each, and every block's
sums added in float64 on the host as before, whichever device they came
from. Columns on one device (the control script's) give one part, which is
``gbm_enum`` itself.

The interface is the one every reference module gives the harness:
``check_model``, ``controls``; ``grow`` and ``check_forest`` for the tests.
"""

from __future__ import annotations

import contextlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench.reference import gbm_enum as base

_Plain = base._Data         # the name is re-bound for the length of a call


def device_parts(cols, y) -> list:
    """[(cols, y)] a device, in row order: each device's shard of every
    column as a single-device array (``addressable_shards``; no copy)."""
    def shards(a):
        found = sorted(a.addressable_shards,
                       key=lambda s: s.index[0].start or 0)
        return [s.data for s in found]

    per_col = [shards(c) for c in cols]
    ys = shards(y)
    if any(len(p) != len(ys) for p in per_col):
        raise ValueError("columns and response are sharded differently")
    return [(tuple(p[i] for p in per_col), ys[i]) for i in range(len(ys))]


class _Sharded:
    """What ``gbm_enum`` asks of its ``_Data``, over one ``_Data`` a device.
    A walk's state is the flat list of the parts' per-block states, the
    first device's blocks first, as ``grow`` and ``check_forest`` index
    it."""

    walk = _Plain.walk

    def __init__(self, cols, y, params, layout, precision, edges):
        self.parts = [_Plain(c, yy, params, layout, precision, edges)
                      for c, yy in device_parts(cols, y)]
        first = self.parts[0]
        self.n = sum(p.n for p in self.parts)
        self.layout, self.depth, self.M = first.layout, first.depth, first.M
        self.n_edges = first.n_edges
        self._pool = ThreadPoolExecutor(len(self.parts))

    def _split(self, flat: list) -> list:
        out, at = [], 0
        for p in self.parts:
            out.append(flat[at:at + p.nblocks])
            at += p.nblocks
        return out

    def _each(self, fn, *per_part) -> list:
        """``fn(part, ...)`` on every device at once (a part's dispatches
        and fetches block its own thread only)."""
        return list(self._pool.map(fn, self.parts, *per_part))

    def start_margins(self, init_f: float):
        for p in self.parts:
            p.start_margins(init_f)

    def start_walk(self):
        return [s for p in self.parts for s in p.start_walk()]

    def level_hist(self, state, n: int):
        outs = self._each(lambda p, s: p.level_hist(s, n),
                          self._split(state))
        return tuple(sum(o[i] for o in outs) for i in range(3))

    def level_route(self, state, tab, lt):
        outs = self._each(lambda p, s: p.level_route(s, tab, lt),
                          self._split(state))
        return (sum(o[0] for o in outs), sum(o[1] for o in outs),
                [s for o in outs for s in o[2]])

    def add_leaves(self, fins, leaf: np.ndarray):
        self._each(lambda p, f: p.add_leaves(f, leaf), self._split(fins))

    def logloss(self) -> float:
        return sum(self._each(lambda p: p.logloss() * p.n)) / self.n


@contextlib.contextmanager
def _sharded_data():
    """``gbm_enum``'s functions build their ``_Data`` by that name; for the
    length of one call the name is ``_Sharded``."""
    base._Data = _Sharded
    try:
        yield
    finally:
        base._Data = _Plain


def grow(cols, y, cfg: dict, **kw) -> dict:
    with _sharded_data():
        return base.grow(cols, y, cfg, **kw)


def check_forest(cols, y, cfg: dict, forest: dict, **kw) -> dict:
    with _sharded_data():
        return base.check_forest(cols, y, cfg, forest, **kw)


def check_model(cols, y, cfg: dict, produced: dict) -> dict:
    with _sharded_data():
        return base.check_model(cols, y, cfg, produced)


def controls(cols, y, cfg: dict, **kw):
    steps = base.controls(cols, y, cfg, **kw)
    while True:
        with _sharded_data():       # held while a step runs, not between
            step = next(steps, None)
        if step is None:
            return
        yield step
