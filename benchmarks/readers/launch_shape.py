"""A ratio of the launch's shape, its mean over the window's flush
records (``mark_rate.py``'s ``window_records``).  Every flush record
carries ``a`` (the pow2 width the launch packed at; 0 where the full
grid ran and nothing was gathered), ``cols`` (its real columns),
``cols_max`` (the busiest shard's) and ``shards`` (a launch is
``shards`` blocks of ``a`` columns).

``pad``: ``1 - cols / (shards * a)`` over the records with ``a`` > 0:
the share of the blocks that was padding.  ``busiest``: ``cols_max /
cols`` over the records with columns: the busiest shard's share of
them (``1 / shards`` when even, 1 on one chip).  A program whose
records carry no shape gives nothing to read."""

import importlib.util
import os

RATIOS = {
    "pad": lambda r: (1.0 - r["cols"] / (r["shards"] * r["a"])
                      if r["a"] > 0 else None),
    "busiest": lambda r: (r["cols_max"] / r["cols"]
                          if r["cols"] > 0 else None),
}


def _window_records(facts):
    spec = importlib.util.spec_from_file_location(
        "reader_mark_rate",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "mark_rate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.window_records(facts)


def read(facts, ratio):
    fn = RATIOS[ratio]
    vals = [fn(r) for r in _window_records(facts) if "a" in r]
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    return sum(vals) / len(vals), len(vals)
