"""A deployment's data, made from the seed: the stream every lane runs on.

The surrogate rows of the configuration's dataset (``repro.data``, at the
published sizes), the paper's 10 % pre-training split, the 22-expert pool
fitted on it and its predictions on the remaining stream, all on the
device.  The reference gets the same predictions and labels as data, and
works out the transmission costs from the configuration's sizes itself.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from bench.reference import costs_from_sizes


class Stream(NamedTuple):
    preds: object            # (K, n_stream) float32 on the device
    y: object                # (n_stream,)   float32 on the device
    costs: object            # (K,)          float32 on the device, the pool's
    preds_host: np.ndarray   # the same data on the host, for the reference
    y_host: np.ndarray
    ref_costs: np.ndarray    # (K,) float64, from the configuration's sizes


def build(config: dict, data_seed: int) -> Stream:
    """Fit the pool and predict the stream, from ``data_seed``.

    ``config["rows"]`` below the dataset's published count keeps only the
    first rows (a rehearsal on the CPU)."""
    import jax
    from repro.data import make_dataset, pretrain_split
    from repro.data.tabular import TabularDataset
    from repro.experts import build_paper_pool, pool_predict_all

    ds = make_dataset(config["dataset"], seed=data_seed)
    rows = config["rows"]
    if rows < ds.x.shape[0]:
        ds = TabularDataset(ds.name, ds.x[:rows], ds.y[:rows])
    (xp, yp), (xs, ys) = pretrain_split(ds, frac=config["pretrain_frac"],
                                        seed=data_seed)
    pool = build_paper_pool(xp, yp, seed=data_seed)
    preds = pool_predict_all(pool, xs)
    y = jax.device_put(ys)
    jax.block_until_ready((preds, y, pool.costs))
    ref_costs = costs_from_sizes(xp.shape[0], config["features"],
                                 config["mlp_hidden"])
    return Stream(preds, y, pool.costs, np.asarray(preds), np.asarray(ys),
                  ref_costs)

